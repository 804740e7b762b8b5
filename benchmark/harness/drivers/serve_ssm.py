"""The serving driver of NVIDIA-Nemotron-3-Nano's decoder (Mamba-2,
rope-free GQA and relu² MoE blocks, one sublayer a block): the window
drives ``DecodeEngine.submit`` as ``drivers/serve.py`` does, whose stamps
and warm-up it shares (every prompt bucket at every admission size).
What differs is what the configuration brings (its weights, adapter and
reference, token ids drawn from the held slice of the vocabulary) and the
mix's ``warm_start``, taken as ``drivers/serve_dsa.py`` takes it:

the closed loop starts during set-up, through the engine's own admission,
and the measured window opens once every slot holds a request that has
delivered its first token. Tokens stamped before that do not count, those
inside the window do; a request that finishes, before or in the window,
is replaced from its lane, and in the window that admission stalls the
decoding rows as it does in this engine. After the window nothing new is
sent, and the run waits only until the comparison has its sample.

``correct`` is decided as for the other serve cells: a sample of the
greedy requests that finished is run once through the plain float32
reference (prompt and served tokens, teacher forced, request by request
so that it fits); the number is the gap by which a served token's
reference logit lies below the reference's best at that position. A
reference variant (the fp8 control, a rehearsed fault) is read the same
way, on the tokens it puts first; the hand-over that the conv-tail fault
breaks is each request's own prompt length. That fault touches the few
tokens decoded right after the hand-over and fades with the state, so
beside the gaps over all served tokens the comparison reads
``handover_gap_mean``: the mean gap over the first ``conv_kernel`` tokens
a request decodes (served tokens 1 .. ``conv_kernel``; token 0 is the
prefill's own).
"""

from __future__ import annotations

import functools
import gc
import time
from typing import List

import numpy as np

from benchmark.harness import adapter_ssm as adapter
from benchmark.harness import (
    check,
    client,
    common,
    engine_rounds,
    ssm_rounds,
    trace,
)
from benchmark.harness import weights_ssm as W
from benchmark.harness.drivers.serve import (
    DRAIN_S,
    TRACE_AT,
    TRACE_FOR_S,
    _drain,
    _submit,
    _warm,
)
from benchmark.harness.drivers.serve_dsa import STALLED_S, variants_of

# The client looks at every live handle this often. The other serve drivers
# sweep every millisecond over 24 to 96 handles; this cell has 384, a sweep
# of them takes a third of a millisecond of Python, and at one a millisecond
# the client held the interpreter's lock a quarter of the time against the
# engine's thread (launch and emit, 7 ms a round of 153). A round's tokens
# arrive together every 153 ms, so nothing is read from a finer stamp.
SWEEP_S = 0.01


def reference_gaps(ref, cfg: dict, seed: int, router_bias, sample: list,
                   width: int, max_out: int, control=None) -> dict:
    """Reference logits over each sampled request's prompt and served
    tokens. Returns the per-token gaps of the served tokens (``served``);
    for each variant of ``control`` (the keywords of ``ref.forward``:
    ``cast``, ``fault``) the gaps of the tokens that the reference
    computed through it puts first; and ``flips``: per (token, routed
    block), whether the reference with every matmul operand rounded to
    bfloat16 chooses another set of experts than in float32. Each set of
    gaps comes with ``<name>_handover``: its first ``conv_kernel``
    decoded tokens of every request. ``width`` and ``max_out`` fix the
    program's shape, so that every seed runs the one program."""
    import jax
    import jax.numpy as jnp

    dtype = adapter.dtype_of(cfg["torch_dtype"])
    variants = variants_of(control)
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

    def forward(key, router_bias, toks, pos, handover, **kw):
        """One request through the reference (``kw``: a variant's cast or
        fault): (logits at ``pos`` (M, V), chosen experts (Le, T, K)).
        The weights are made inside the program, so a block's exist only
        while that block runs; every variant is a program of its own."""
        w = W.init_weights(cfg, key, dtype, router_bias)
        h, chosen = ref.forward(w, toks[None], cfg, handover=handover, **kw)
        logits = ref.logits(w, jnp.take(h[0], pos, axis=0), kw.get("cast"))
        return logits, chosen[:, 0]

    key = W.seed_key(seed)
    plain = jax.jit(forward)
    others = {name: jax.jit(functools.partial(forward, **kw))
              for name, kw in dict(variants,
                                   bf16={"cast": ref.bf16_operands}).items()}
    gaps = {name: [] for name in ["served", *variants]}
    flips = []
    for i, r in enumerate(sample):
        args = (key, router_bias, toks[i], pos[i], jnp.int32(r.prompt.size))
        logits, chosen = plain(*args)
        best = jnp.max(logits, axis=-1)
        at = lambda ids: np.asarray(best - jnp.take_along_axis(  # noqa: E731
            logits, ids[:, None], -1)[:, 0])[live[i]]
        gaps["served"].append(at(jnp.asarray(served[i])))
        for name, program in others.items():
            logits_v, chosen_v = program(*args)
            if name == "bf16":
                flip = np.asarray(jnp.any(
                    jnp.sort(chosen, -1) != jnp.sort(chosen_v, -1), -1))
                flips.append(np.moveaxis(flip, 0, 1)[real[i]])
            else:
                gaps[name].append(at(jnp.argmax(logits_v, axis=-1)))
            del logits_v, chosen_v
    taps = cfg["conv_kernel"]
    out = {name: np.concatenate(v) for name, v in gaps.items()}
    out.update({f"{name}_handover": np.concatenate(
        [g[1:1 + taps] for g in v]) for name, v in gaps.items()})
    out["flips"] = np.concatenate(flips)                    # (tokens, Le)
    return out


def run(cell) -> dict:
    import jax

    cfg, mix, log = cell.cfg, cell.mix, common.log
    try:
        from kubeflow_tpu.ops import ssm  # noqa: F401
    except ImportError as e:
        # any commit before PR 35: no state-space block, nothing to measure
        log(f"the program has no state-space block: {e}")
        raise SystemExit(2)
    from kubeflow_tpu.obs.requests import RequestLedger
    from kubeflow_tpu.serving.engine import DecodeEngine

    if not mix.get("warm_start") or mix["loop"] != "closed":
        raise ValueError("serve_ssm drives a closed loop with warm_start")
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
    t_warm = time.monotonic()
    _warm(engine, mix, cfg["vocab_size"], log)
    log(f"warm-up took {time.monotonic() - t_warm:.1f} s")
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
        f"{len(ended)} ended, {len(live)} still running or waiting at the "
        f"close ({len(stalled)} of them silent for {STALLED_S:.0f} s); "
        f"programs built in window: {built}; engine batch prefills "
        f"{engine.batch_prefills}")

    # -- the window's numbers ------------------------------------------------
    good = [r for r in ended
            if not r.error and len(r.tokens) == r.max_new]
    in_window = sum(sum(1 for t in r.stamps if t0 < t <= t_end)
                    for r in sent)
    e2e = {"serve_tokens_per_s": in_window / cell.seconds}
    admitted = [r for r in sent if r.t_first is not None
                and t0 < r.t_first <= t_end]
    log(f"{len(admitted)} requests were admitted inside the window "
        f"({len(admitted) / cell.seconds:.2f} a second)")
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
        numbers["handover_gap_mean"] = float(
            np.mean(gaps["served_handover"]))
        numbers["routing_flip_share"] = float(np.mean(gaps["flips"]))
        for name in variants_of(cell.control):
            numbers.update({f"{name}_{k}": v for k, v in
                            check.gap_numbers(gaps[name]).items()})
            numbers[f"{name}_handover_gap_mean"] = float(
                np.mean(gaps[f"{name}_handover"]))
        log(f"reference: {len(sample)} greedy requests of "
            f"{[int(r.prompt.size) + len(r.tokens) for r in sample]} "
            f"positions, {int(gaps['served'].size)} served tokens, "
            f"{time.monotonic() - t_ref:.1f} s; routing decisions (token, "
            f"block) that bfloat16 operands flip in the reference: "
            f"{numbers['routing_flip_share']:.5f} of "
            f"{int(gaps['flips'].size)}")
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
    counted = ssm_rounds.per_block_step(dict(out, cell=cell))
    if counted is not None:
        log(f"held experts a routed block a decode step: {counted[0]:.3f} "
            f"pairs on {counted[1]:.3f} distinct experts of "
            f"{cfg['n_routed_experts']}, over {steps} steps")
    scanned = ssm_rounds.scan_tokens(dict(out, cell=cell))
    if scanned is not None:
        log(f"the window's admissions scanned {scanned[0]} tokens, "
            f"{scanned[1]} of them bucket padding")
    # the rate is counted in whole rounds (slots x steps a sync tokens at
    # once); the engine's own phases say how the host's clock moved
    rounds = [r for r in engine_rounds.window_rounds(dict(out, cell=cell))
              or [] if r.attrs["k"] > 0]
    stamps = [t for r in sent for t in r.stamps if t0 < t <= t_end]
    if rounds and stamps:
        mean = {p: 1e3 * float(np.mean([r.attrs[f"{p}_s"] for r in rounds]))
                for p in ("admit", "step", "sync", "emit")}
        log(f"{len(rounds)} rounds of the window stepped; ms a round at the "
            f"mean: " + ", ".join(f"{p} {v:.3f}" for p, v in mean.items())
            + f"; admit in all {sum(r.attrs['admit_s'] for r in rounds):.2f}"
            f" s; last tokens stamped {max(stamps) - t0:.4f} s into the "
            f"window: {in_window / (max(stamps) - t0):.3f} tokens/s to there")
        # a run that reads low: were all its rounds slower (the device),
        # or a few of them much (a stall)? sync of rounds with no admission
        quiet = np.asarray([1e3 * r.attrs["sync_s"] for r in rounds
                            if r.attrs["admit_s"] < 1e-3])
        if quiet.size:
            tenths = np.percentile(quiet, [0, 10, 50, 90, 100])
            log(f"sync ms of the {quiet.size} rounds without an admission, "
                "min / p10 / p50 / p90 / max: "
                + " / ".join(f"{v:.2f}" for v in tenths)
                + "; p50 of the window's quarters: " + " / ".join(
                    f"{np.median(q):.2f}" for q in np.array_split(quiet, 4)))
    return out
