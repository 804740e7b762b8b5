"""The serving driver of the hybrid (KDA + MLA + routed-MLP) decoder: the
window drives ``DecodeEngine.submit``, as ``drivers/serve.py`` does for
the dense decoder, whose client loop, warm-up and stamps it shares. What
differs is what the configuration brings: its weights
(``weights_hybrid``), its adapter, its reference, token ids drawn from the
held slice of the vocabulary, and a comparison that also reads how often
the served precision flips a routing decision.

``correct`` is decided as for the dense serve cells: a sample of the
greedy requests that the window finished is run once through the plain
float32 reference (prompt and served tokens, teacher forced, request by
request so that it fits); the number is the gap by which a served token's
reference logit lies below the reference's best at that position.
"""

from __future__ import annotations

import gc
import re
import time
from typing import List

import numpy as np

from benchmark.harness import adapter_hybrid as adapter
from benchmark.harness import (
    check,
    client,
    common,
    engine_rounds,
    moe_rounds,
    trace,
)
from benchmark.harness import weights_hybrid as W
from benchmark.harness.drivers.serve import (
    DRAIN_S,
    SWEEP_S,
    TRACE_AT,
    TRACE_FOR_S,
    _drain,
    _path_of,
    _submit,
    _warm,
)


def reference_gaps(ref, cfg: dict, seed: int, router_bias, sample: list,
                   width: int, max_out: int, control=None) -> dict:
    """Reference logits over each sampled request's prompt and served
    tokens. Returns the per-token gaps of the served tokens
    (``served``), with ``control`` those of the tokens that the reference
    computed through ``control`` puts first, and ``flips``: per (token,
    routed layer), whether the reference with every matmul operand
    rounded to bfloat16 chooses another set of experts than in float32.
    ``width`` and ``max_out`` fix the program's shape (the mix's longest
    request and answer), so that every seed runs the one program.
    ``router_bias`` is the fitted one the program was given."""
    import jax
    import jax.numpy as jnp

    dtype = adapter.dtype_of(cfg["torch_dtype"])
    n = len(sample)
    toks = np.zeros((n, width), np.int32)
    pos = np.zeros((n, max_out), np.int32)
    served = np.zeros((n, max_out), np.int32)
    live = np.zeros((n, max_out), bool)
    real = np.zeros((n, width), bool)
    for i, r in enumerate(sample):
        p, m = r.prompt.size, len(r.tokens)
        seq = np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
        toks[i, :seq.size] = seq
        real[i, :seq.size] = True
        pos[i, :m] = p - 1 + np.arange(m)
        served[i, :m] = r.tokens
        live[i, :m] = True

    def one(key, router_bias, toks, pos, served):
        """One request: (T,), (M,), (M,) -> gaps (M,), flips (Le, T). The
        weights are made inside the program, so a layer's exist only
        while that layer runs."""
        w = W.init_weights(cfg, key, dtype, router_bias)
        at = lambda h: jnp.take(h[0], pos, axis=0)  # noqa: E731
        h, chosen = ref.forward(w, toks[None], cfg)
        logits = ref.logits(w, at(h))
        best = jnp.max(logits, axis=-1)
        got = jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
        out = {"served": best - got}
        _h, chosen16 = ref.forward(w, toks[None], cfg, ref.bf16_operands)
        out["flips"] = jnp.any(jnp.sort(chosen, -1) != jnp.sort(chosen16, -1),
                               axis=-1)[:, 0]
        if control is not None:
            hc = ref.hidden(w, toks[None], cfg, control)
            first = jnp.argmax(ref.logits(w, at(hc), control), axis=-1)
            cgot = jnp.take_along_axis(logits, first[:, None], -1)[:, 0]
            out["control"] = best - cgot
        return out

    program, key = jax.jit(one), W.seed_key(seed)
    outs = [jax.device_get(program(key, router_bias, toks[i], pos[i],
                                   served[i])) for i in range(n)]
    gaps = {k: np.stack([o[k] for o in outs])[live]
            for k in outs[0] if k != "flips"}
    flips = np.stack([o["flips"] for o in outs])             # (n, Le, T)
    gaps["flips"] = np.moveaxis(flips, 1, 2)[real]           # (tokens, Le)
    return gaps


def run(cell) -> dict:
    import jax

    from kubeflow_tpu.obs.requests import RequestLedger
    from kubeflow_tpu.serving.engine import DecodeEngine

    cfg, mix, log = cell.cfg, cell.mix, common.log
    eng_cfg = cfg["assumed"]["engine"]
    dtype = adapter.dtype_of(cfg["torch_dtype"])
    pc = adapter.program_config(cfg, dtype=dtype, param_dtype=dtype)
    ref = check.load_reference(cfg["reference"])
    key = W.seed_key(cell.seed)
    # first the fit, alone on the device: it runs the reference's forward
    router_bias = jax.block_until_ready(jax.jit(
        lambda k: W.balanced_router_bias(cfg, k, dtype, ref))(key))
    params = jax.jit(lambda k, b: adapter.to_program_params(
        W.init_weights(cfg, k, dtype, b), cfg))(key, router_bias)
    ledger = RequestLedger(capacity=1 << 16)
    engine = DecodeEngine(
        pc, params, slots=eng_cfg["slots"],
        steps_per_sync=eng_cfg["steps_per_sync"],
        paged=eng_cfg["paged"], precompile=eng_cfg["precompile"],
        admit_batch_max=eng_cfg.get("admit_batch_max"),
        autostart=False, name=cfg["name"], request_ledger=ledger)
    # token ids come from the rows of the vocabulary held here
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
    sample = check.pick_sample(sent, cell.limits["sample_requests"],
                               cell.seed)
    numbers = {}
    t_ref = time.monotonic()
    if sample:
        max_out = int(mix["output_tokens"]["max"])
        width = min(cfg["max_position_embeddings"],
                    int(mix["prompt_tokens"]["max"]) + max_out)
        gaps = reference_gaps(ref, cfg, cell.seed, router_bias, sample,
                              width, max_out, control=cell.control)
        numbers = check.gap_numbers(gaps["served"])
        numbers["routing_flip_share"] = float(np.mean(gaps["flips"]))
        if "control" in gaps:
            numbers.update({f"control_{k}": v for k, v in
                            check.gap_numbers(gaps["control"]).items()})
        paths = [_path_of(records, r) for r in sample]
        log(f"reference: {len(sample)} greedy requests, "
            f"{int(gaps['served'].size)} served tokens, admitted through "
            f"{paths.count('batch')} batch and {paths.count('row')} row "
            f"prefills, {time.monotonic() - t_ref:.1f} s; routing decisions "
            f"(token, layer) that bfloat16 operands flip in the reference: "
            f"{numbers['routing_flip_share']:.5f} of "
            f"{int(gaps['flips'].size)}")
    numbers["undelivered_tokens"] = float(
        sum(abs(len(r.tokens) - r.max_new) for r in sent if r.done
            and not r.error))

    out = {
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
    counted = moe_rounds.per_layer_step(dict(out, cell=cell))
    if counted is not None:
        log(f"held experts a routed layer a decode step: {counted[0]:.3f} "
            f"pairs on {counted[1]:.3f} distinct experts of "
            f"{cfg['num_experts']}, over {steps} steps")
    # the rate is counted in whole rounds (slots x steps a sync tokens at
    # once); the engine's own phases and the last round's stamp say how
    # the host's clock moved below that grain
    rounds = [r for r in engine_rounds.window_rounds(dict(out, cell=cell))
              or [] if r.attrs["k"] > 0]
    stamps = [t for r in sent for t in r.stamps if t <= t_end]
    if rounds and stamps:
        mean = {p: 1e3 * float(np.mean([r.attrs[f"{p}_s"] for r in rounds]))
                for p in ("admit", "step", "sync", "emit")}
        log(f"{len(rounds)} rounds of the window stepped; ms a round at the "
            f"mean: " + ", ".join(f"{p} {v:.3f}" for p, v in mean.items())
            + f"; last tokens stamped {max(stamps) - t0:.4f} s into the "
            f"window: {in_window / (max(stamps) - t0):.3f} tokens/s to there")
    return out
