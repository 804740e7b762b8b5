"""The serving driver of MiMo-V2-Flash's decoder (128-position window
layers with sinks beside global layers, routed MLP with no shared
expert): the closed loop with ``warm_start`` of ``drivers/serve_dsa.py``,
whose warm-up and helpers it imports, over this configuration's weights,
adapter and reference. Every prompt of the mix is longer than the
engine's chunk, so every admission runs the chunk program.

``correct`` is decided as for the other serve cells: a sample of the
greedy requests that finished is run once through the plain float32
reference (prompt and served tokens, teacher forced, request by request
so that it fits); the number is the gap by which a served token's
reference logit lies below the reference's best at that position. On the
first (the longest) request the run also reads, held to no limit, the
reference with bfloat16 operands (``bf16_*`` and ``routing_flip_share``:
what the served precision costs this model) and with the sinks left out
(``no_sinks_*``: what the sinks weigh in this comparison).
"""

from __future__ import annotations

import functools
import gc
import time
from typing import List

import numpy as np

from benchmark.harness import adapter_swa as adapter
from benchmark.harness import (
    check,
    client,
    common,
    engine_rounds,
    swa_rounds,
    trace,
)
from benchmark.harness import weights_swa as W
from benchmark.harness.drivers.serve import (
    DRAIN_S,
    SWEEP_S,
    TRACE_AT,
    TRACE_FOR_S,
    _drain,
    _submit,
)
from benchmark.harness.drivers.serve_dsa import STALLED_S, _warm, variants_of

REHEARSED = {"no_sinks": {"fault": "no_sinks"}}


def reference_gaps(ref, cfg: dict, seed: int, router_bias, sample: list,
                   width: int, max_out: int, control=None) -> dict:
    """Reference logits over each sampled request's prompt and served
    tokens. Returns the per-token gaps of the served tokens (``served``)
    and, read on the first (the longest) request alone, for each variant
    of ``control``, for the reference with bfloat16 operands (``bf16``)
    and with the sinks left out (``no_sinks``) the gaps of the tokens that
    the variant puts first, and ``flips``: per (token, routed layer),
    whether bfloat16 operands choose another set of experts than float32.
    ``width`` and ``max_out`` fix the program's shape, so that every seed
    runs the one program."""
    import jax
    import jax.numpy as jnp

    dtype = adapter.dtype_of(cfg["torch_dtype"])
    variants = dict(variants_of(control), bf16={"cast": ref.bf16_operands},
                    **REHEARSED)
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

    def forward(key, router_bias, toks, pos, **kw):
        """One request through the reference (``kw``: a variant's cast or
        fault): (logits at ``pos`` (M, V), chosen experts (Le, T, K)).
        The weights are made inside the program; every variant is a
        program of its own, so that no two forwards' temporaries are
        alive together."""
        w = W.init_weights(cfg, key, dtype, router_bias)
        h, chosen = ref.forward(w, toks[None], cfg, **kw)
        logits = ref.logits(w, jnp.take(h[0], pos, axis=0), kw.get("cast"))
        return logits, chosen[:, 0]

    key = W.seed_key(seed)
    plain = jax.jit(forward)
    others = {name: jax.jit(functools.partial(forward, **kw))
              for name, kw in variants.items()}
    gaps = {name: [] for name in ["served", *variants]}
    for i in range(n):
        args = (key, router_bias, toks[i], pos[i])
        logits, chosen = plain(*args)
        best = jnp.max(logits, axis=-1)
        at = lambda ids: np.asarray(best - jnp.take_along_axis(  # noqa: E731
            logits, ids[:, None], -1)[:, 0])[live[i]]
        gaps["served"].append(at(jnp.asarray(served[i])))
        if i > 0:
            continue
        for name, program in others.items():
            logits_v, chosen_v = program(*args)
            gaps[name].append(at(jnp.argmax(logits_v, axis=-1)))
            if name == "bf16":
                flips = np.asarray(jnp.any(
                    jnp.sort(chosen, -1) != jnp.sort(chosen_v, -1), -1))
            del logits_v, chosen_v
    out = {name: np.concatenate(v) for name, v in gaps.items()}
    out["flips"] = np.moveaxis(flips, 0, 1)[real[0]]       # (tokens, Le)
    return out


def run(cell) -> dict:
    import jax

    cfg, mix, log = cell.cfg, cell.mix, common.log
    try:
        from kubeflow_tpu.models.hybrid import RotaryAttention  # noqa: F401
    except ImportError as e:
        # any commit before PR 40: no window layer, nothing to measure
        log(f"the program has no window attention layer: {e}")
        raise SystemExit(2)
    from kubeflow_tpu.obs.requests import RequestLedger
    from kubeflow_tpu.serving.engine import DecodeEngine

    if not mix.get("warm_start") or mix["loop"] != "closed":
        raise ValueError("serve_swa drives a closed loop with warm_start")
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
    _warm(engine, pc.prefill_chunk, cfg["vocab_size"], log)
    # token ids come from the rows of the vocabulary held here
    reqs = client.build_requests(mix, cell.seconds, cfg["vocab_size"],
                                 cell.seed)
    jax.block_until_ready(params)
    slots = engine.slots
    engine.start()

    # -- the closed loop, started during set-up ------------------------------
    c = int(mix["clients"])
    backlog = [reqs[i::c][::-1] for i in range(c)]
    live: List[client.Request] = []
    t_warm = time.monotonic()
    for lane in backlog:
        r = lane.pop()
        r.lane = lane
        _submit(engine, r, t_warm, t_warm)
        live.append(r)
    submitted = len(live)
    tracing = trace.Window(cell) if cell.trace else None
    t0 = t_end = None
    built_before = steps0 = tokens0 = 0
    need = int(cell.limits["sample_requests"])
    while True:
        now = time.monotonic()
        if t0 is None:
            if sum(r.t_first is not None for r in live) >= slots:
                # every slot decodes: the window opens
                t0, t_end = now, now + cell.seconds
                cell.mark_window_start()
                built_before = cell.compiles.total
                steps0, tokens0 = engine.steps_total, engine.tokens_total
                log(f"warm start: {slots} slots decoding "
                    f"{now - t_warm:.1f} s after the loop began, "
                    f"{sum(r.done for r in reqs)} requests already over")
            elif now > t_warm + 900.0:
                raise RuntimeError("the slots never filled")
        elif tracing is not None:
            tracing.tick(now - t0, TRACE_AT * cell.seconds, TRACE_FOR_S)
        if t0 is not None and now >= t_end:
            done_greedy = sum(r.greedy and r.done and not r.error
                              for r in reqs)
            if not live or done_greedy >= need or now > t_end + DRAIN_S:
                break
        still = []
        for r in live:
            _drain(r, now)
            if not r.done:
                still.append(r)
            elif (t0 is None or now < t_end) and r.lane:
                nxt = r.lane.pop()
                nxt.lane = r.lane
                _submit(engine, nxt, now, now)
                still.append(nxt)
                submitted += 1
        live = still
        time.sleep(SWEEP_S)
    t_close = time.monotonic()
    if tracing is not None:
        tracing.close()
    built = cell.compiles.total - built_before
    steps = engine.steps_total - steps0
    tokens_engine = engine.tokens_total - tokens0
    sent = [r for r in reqs if r.t_submit is not None]
    ended = [r for r in sent if r.done]
    # a row that was decoding and fell silent did not merely run out of
    # window: it failed
    stalled = [r for r in live if r.t_last is not None
               and t_close - r.t_last > STALLED_S]
    log(f"window: {submitted} requests sent since the loop began, "
        f"{len(ended)} ended, {len(live)} still running at the close "
        f"({len(stalled)} of them silent for {STALLED_S:.0f} s); programs "
        f"built in window: {built}; chunk programs run "
        f"{engine.prefill_chunks}")

    # -- the window's numbers ------------------------------------------------
    good = [r for r in ended
            if not r.error and len(r.tokens) == r.max_new]
    in_window = sum(sum(1 for t in r.stamps if t0 < t <= t_end)
                    for r in sent)
    e2e = {"serve_tokens_per_s": in_window / cell.seconds}
    records = {rec.rid: rec for rec in ledger.records()}
    device = common.device_block(cell.devices)

    # -- free the program's state, then run the reference ---------------------
    k_steps = engine.steps_per_sync
    engine.close()
    del engine, params
    gc.collect()
    sample = check.pick_sample(sent, need, cell.seed)
    numbers = {}
    t_ref = time.monotonic()
    if sample:
        max_out = int(mix["output_tokens"]["max"])
        width = min(cfg["max_position_embeddings"],
                    int(mix["max_total_tokens"]))
        gaps = reference_gaps(ref, cfg, cell.seed, router_bias, sample,
                              width, max_out, control=cell.control)
        numbers = check.gap_numbers(gaps["served"])
        numbers["routing_flip_share"] = float(np.mean(gaps["flips"]))
        for name in ["bf16", *REHEARSED, *variants_of(cell.control)]:
            numbers.update({f"{name}_{k}": v for k, v in
                            check.gap_numbers(gaps[name]).items()})
        log(f"reference: {len(sample)} greedy requests of "
            f"{[int(r.prompt.size) + len(r.tokens) for r in sample]} "
            f"positions, {int(gaps['served'].size)} served tokens, "
            f"{time.monotonic() - t_ref:.1f} s; on the longest, bfloat16 "
            f"operands flip {numbers['routing_flip_share']:.5f} of "
            f"{int(gaps['flips'].size)} routing decisions (token, layer) "
            f"in the reference; with the sinks left out its gaps read "
            f"max {numbers['no_sinks_logit_gap_max']:.3f} mean "
            f"{numbers['no_sinks_logit_gap_mean']:.4f}")
    numbers["undelivered_tokens"] = float(
        sum(abs(len(r.tokens) - r.max_new) for r in ended if not r.error))

    out = {
        "end_to_end": e2e, "attempted": len(ended) + len(stalled),
        "failed": len(ended) - len(good) + len(stalled),
        "numbers": numbers, "device": device, "trace": tracing,
        "serve": {
            "requests": sent, "good": good, "records": records,
            "t0": t0, "t_end": t_end, "steps": steps,
            "tokens_engine": tokens_engine, "slots": slots,
            "steps_per_sync": k_steps, "tokens_in_window": in_window,
            "programs_built_in_window": built,
        },
    }
    counted = swa_rounds.per_layer_step(dict(out, cell=cell))
    if counted is not None:
        log(f"held experts a routed layer a decode step: {counted[0]:.3f} "
            f"pairs on {counted[1]:.3f} distinct experts of "
            f"{cfg['n_routed_experts']}, over {steps} steps")
    read = swa_rounds.attention_positions(dict(out, cell=cell))
    if read is not None:
        log(f"a decode step's attention read {read[0]:.0f} cached positions "
            f"a global layer and {read[1]:.0f} ring slots a window layer")
    # the rate is counted in whole rounds (slots x steps a sync tokens at
    # once); the engine's own phases say how the host's clock moved
    rounds = [r for r in engine_rounds.window_rounds(dict(out, cell=cell))
              or [] if r.attrs["k"] > 0]
    if rounds:
        mean = {p: 1e3 * float(np.mean([r.attrs[f"{p}_s"] for r in rounds]))
                for p in ("admit", "step", "sync", "emit")}
        log(f"{len(rounds)} rounds of the window stepped; ms a round at the "
            f"mean: " + ", ".join(f"{p} {v:.3f}" for p, v in mean.items())
            + f"; admit in all {sum(r.attrs['admit_s'] for r in rounds):.2f}"
            " s")
    return out
