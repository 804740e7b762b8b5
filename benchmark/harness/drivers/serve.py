"""The serving driver: the window drives ``DecodeEngine.submit``.

Set-up makes bf16 weights on the device from ``--seed`` in one jitted
call, builds the engine the configuration describes (its thread not yet
started), and warms every program the mix can reach through the engine's
own admission: for each prompt bucket and each admission batch size,
that many one-token requests and one ``run_once``. Then the engine's
thread starts and one client thread offers the mix (an open loop on a
schedule, or a closed loop of waiting clients), stamping each request's
due time and every token as it leaves the handle's queue.
"""

from __future__ import annotations

import gc
import queue
import re
import time
from typing import List

import numpy as np

from benchmark.harness import adapter, check, client, common, trace
from benchmark.harness import weights as W

SWEEP_S = 0.001          # the client looks at every live handle this often
DRAIN_S = 60.0           # an answer may come this long after the close
TRACE_AT, TRACE_FOR_S = 0.4, 3.0   # from that share of the window on


def _drain(req: client.Request, now: float) -> None:
    """Move what the engine has put on the handle's queue to the record."""
    out = req.handle.out
    while True:
        try:
            tok = out.get_nowait()
        except queue.Empty:
            return
        if isinstance(tok, (int, np.integer)):
            if req.t_first is None:
                req.t_first = now
            req.t_last = now
            req.tokens.append(int(tok))
            req.stamps.append(now)
        else:                    # the engine's end-of-stream mark
            req.done = True
            err = getattr(req.handle, "error", None)
            req.error = repr(err) if err is not None else None
            return


def _submit(engine, req: client.Request, now: float, due: float) -> None:
    req.t_due, req.t_submit = due, now
    req.handle = engine.submit(
        req.prompt, max_new=req.max_new, temperature=req.temperature,
        top_k=req.top_k, top_p=req.top_p, seed=req.sampler_seed,
        prefix_len=req.prefix_len)


def _warm(engine, mix: dict, vocab: int, log) -> int:
    """Touch every prefill, insert and step program the mix can reach."""
    from kubeflow_tpu.serving.engine import pow2_bucket

    cap = engine.config.max_seq_len
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    buckets = sorted({pow2_bucket(n, cap) for n in range(lo, hi + 1)})
    sizes, k = [1], 2
    while k <= min(engine.slots, max(1, engine.admit_batch_max)):
        sizes.append(k)
        k *= 2
    rng = np.random.default_rng(0)
    n = 0
    for b in buckets:
        length = min(b, hi, cap - 1)
        for k in sizes:
            handles = [engine.submit(rng.integers(0, vocab, length),
                                     max_new=1, temperature=0.7,
                                     top_p=0.95, seed=i)
                       for i in range(k)]
            engine.run_once(timeout=0.01)
            for h in handles:
                if h.result() is None or h.error is not None:
                    raise RuntimeError(f"warm-up request failed: {h.error}")
            n += 1
    log(f"warm-up: {len(buckets)} prompt buckets {buckets} x admission "
        f"sizes {sizes} = {n} shapes")
    return n


def run(cell) -> dict:
    import jax

    from kubeflow_tpu.obs.requests import RequestLedger
    from kubeflow_tpu.serving.engine import DecodeEngine

    cfg, mix, log = cell.cfg, cell.mix, common.log
    eng_cfg = cfg["assumed"]["engine"]
    dtype = adapter.dtype_of(cfg["torch_dtype"])
    pc = adapter.program_config(cfg, dtype=dtype, param_dtype=dtype)
    # no ``jax.default_device`` scope here: it is part of jit's cache key
    # and thread-local, so programs warmed under it would be built again
    # by the engine's thread
    params = jax.jit(lambda k: adapter.to_program_params(
        W.init_weights(cfg, k, dtype), cfg))(W.seed_key(cell.seed))
    ledger = RequestLedger(capacity=1 << 16)
    engine = DecodeEngine(
        pc, params, slots=eng_cfg["slots"],
        steps_per_sync=eng_cfg["steps_per_sync"],
        paged=eng_cfg["paged"], precompile=eng_cfg["precompile"],
        autostart=False, name=cfg["name"], request_ledger=ledger)
    _warm(engine, mix, cfg["vocab_size"], log)
    reqs = client.build_requests(mix, cell.seconds, cfg["vocab_size"],
                                 cell.seed)
    jax.block_until_ready(params)
    engine.start()
    built_before = cell.compiles.total
    steps0, tokens0 = engine.steps_total, engine.tokens_total

    tracing = trace.Window(cell) if cell.trace else None
    t0 = time.monotonic()
    cell.mark_window_start()
    t_end = t0 + cell.seconds
    live: List[client.Request] = []
    lateness = []
    if mix["loop"] == "open":
        todo = sorted(reqs, key=lambda r: r.due)[::-1]       # pop() = next
        backlog = None
    else:
        c = int(mix["clients"])
        backlog = [reqs[i::c][::-1] for i in range(c)]
        todo = []
        for lane in backlog:
            r = lane.pop()
            r.lane = lane
            todo.append(r)
    submitted = 0
    while True:
        now = time.monotonic()
        if tracing is not None:
            tracing.tick(now - t0, TRACE_AT * cell.seconds, TRACE_FOR_S)
        if now < t_end:
            while todo and t0 + todo[-1].due <= now:
                r = todo.pop()
                due = t0 + r.due if mix["loop"] == "open" else now
                _submit(engine, r, now, due)
                lateness.append(now - due)
                live.append(r)
                submitted += 1
        elif not live:
            break
        elif now > t_end + DRAIN_S:
            break
        still = []
        for r in live:
            _drain(r, now)
            if not r.done:
                still.append(r)
            elif backlog is not None and now < t_end and r.lane:
                nxt = r.lane.pop()
                nxt.lane = r.lane
                _submit(engine, nxt, now, now)
                still.append(nxt)
                submitted += 1
        live = still
        time.sleep(SWEEP_S)
    if tracing is not None:
        tracing.close()
    built = cell.compiles.total - built_before
    steps = engine.steps_total - steps0
    tokens_engine = engine.tokens_total - tokens0
    sent = [r for r in reqs if r.t_submit is not None]
    log(f"window: {submitted} requests sent, {sum(r.done for r in sent)} "
        f"finished, {len(live)} never finished; generator lateness "
        f"p50 {1e3 * np.median(lateness):.3f} ms max "
        f"{1e3 * np.max(lateness):.3f} ms; programs built in window: "
        f"{built}; engine batch prefills {engine.batch_prefills}")

    # -- the window's numbers ------------------------------------------------
    good = [r for r in sent
            if r.done and not r.error and len(r.tokens) == r.max_new]
    in_window = sum(sum(1 for t in r.stamps if t <= t_end) for r in sent)
    e2e = {"serve_tokens_per_s": in_window / cell.seconds}
    if good:
        series = {
            "ttft": [1e3 * (r.t_first - r.t_due) for r in good],
            "tpot": [1e3 * (r.t_last - r.t_first) / (len(r.tokens) - 1)
                     for r in good if len(r.tokens) > 1]}
        for m in cell.metrics_reported("end_to_end"):
            hit = re.fullmatch(r"(ttft|tpot)_p(\d+)_ms", m["name"])
            if hit and series[hit.group(1)]:
                e2e[m["name"]] = client.percentile(series[hit.group(1)],
                                                   float(hit.group(2)))
        log("latencies of the window's requests, ms at p50/p80/p95: "
            + "; ".join(f"{k} " + "/".join(
                f"{client.percentile(v, q):.1f}" for q in (50, 80, 95))
                for k, v in series.items() if v))
    records = {rec.rid: rec for rec in ledger.records()}
    device = common.device_block(cell.devices)

    # -- free the program's state, then run the reference ---------------------
    slots, k_steps = engine.slots, engine.steps_per_sync
    engine.close()
    del engine, params
    gc.collect()
    limits = cell.limits
    sample = check.pick_sample(sent, limits["sample_requests"], cell.seed)
    numbers = {}
    t_ref = time.monotonic()
    if sample:
        width = cfg["max_position_embeddings"]
        max_out = int(mix["output_tokens"]["max"])
        gaps = check.serve_gaps(cfg, cell.seed, sample, width, max_out,
                                control=cell.control)
        numbers = check.gap_numbers(gaps["served"])
        if "control" in gaps:
            numbers.update({f"control_{k}": v for k, v in
                            check.gap_numbers(gaps["control"]).items()})
        paths = [_path_of(records, r) for r in sample]
        log(f"reference: {len(sample)} greedy requests, "
            f"{int(gaps['served'].size)} served tokens, admitted through "
            f"{paths.count('batch')} batch and {paths.count('row')} row "
            f"prefills, {time.monotonic() - t_ref:.1f} s")
    numbers["undelivered_tokens"] = float(
        sum(abs(len(r.tokens) - r.max_new) for r in sent if r.done
            and not r.error))

    return {
        "end_to_end": e2e, "attempted": len(sent),
        "failed": len(sent) - len(good),
        "numbers": numbers, "device": device, "trace": tracing,
        "serve": {
            "requests": sent, "good": good, "records": records,
            "t0": t0, "t_end": t_end, "steps": steps,
            "tokens_engine": tokens_engine, "slots": slots,
            "steps_per_sync": k_steps, "tokens_in_window": in_window,
            "programs_built_in_window": built,
        },
    }


def _path_of(records: dict, req: client.Request) -> str:
    """Row or batch prefill: members of one batch admission share the
    very timestamp at which their prefill phase opened."""
    rec = records.get(getattr(req.handle, "rid", None))
    if rec is None:
        return "unknown"
    start = next((a for a, _b, p in rec.intervals if p == "prefill"), None)
    twins = sum(1 for other in records.values()
                if any(p == "prefill" and a == start
                       for a, _b, p in other.intervals))
    return "batch" if twins > 1 else "row"
