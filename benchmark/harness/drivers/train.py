"""The training driver: the window drives ``make_lm_train_step``.

Set-up builds ONE object, the compiled step with its sharded state, from
``--seed`` (weights made on the device under ``jit`` in the published
layout, reshaped into the program's tree), drives it through its first
three steps by the window's own call and feed, and hands that same
object to the window. What those steps leave (each loss and raw gradient
norm, Adam's first moment after step 1, the parameters after step 3) is
reduced to norms at once and compared with the plain reference after the
window has closed and the state is freed.

In the window a new batch of token ids is put on the device every step
and steps are enqueued one ahead of the device; the window closes on a
loss readback.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark.harness import adapter, check, client, common, trace
from benchmark.harness import weights as W

CHECK_STEPS = 3
TRACE_STEPS = 4


def _adam_moments(opt_state):
    """The (mu, nu) holder inside an optax chain's state."""
    if hasattr(opt_state, "mu") and hasattr(opt_state, "nu"):
        return opt_state
    if isinstance(opt_state, (tuple, list)):
        for part in opt_state:
            found = _adam_moments(part)
            if found is not None:
                return found
    return None


def build(cell):
    """(state, step, feed, mesh): the one object the window drives."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from kubeflow_tpu.models.transformer import Transformer
    from kubeflow_tpu.parallel.mesh import MeshConfig, create_mesh
    from kubeflow_tpu.train.trainer import (
        TrainState, create_sharded_state, make_lm_train_step,
        make_optimizer,
    )

    cfg, mix = cell.cfg, cell.mix
    tr = cfg["assumed"]["train"]
    opt = tr["optimizer"]
    mesh = create_mesh(MeshConfig(**tr["mesh"]), devices=cell.devices)
    pdt = adapter.dtype_of(tr["param_dtype"])
    pc = adapter.program_config(
        cfg, dtype=adapter.dtype_of(tr["activation_dtype"]),
        param_dtype=pdt, attention_impl=tr["attention_impl"],
        remat=tr["remat"], max_seq_len=int(mix["seq_len"]))
    model = Transformer(pc, return_hidden=bool(tr["loss_chunk"]))
    tx = make_optimizer(
        opt["learning_rate"], warmup_steps=opt["warmup_steps"],
        decay_steps=opt["decay_steps"], weight_decay=opt["weight_decay"],
        b1=opt["b1"], b2=opt["b2"], grad_clip=opt["grad_clip"])

    def init_fn(key):
        params = adapter.to_program_params(
            W.init_weights(cfg, key, pdt), cfg)
        return TrainState.create(apply_fn=model.apply, params=params, tx=tx)

    state, _ = create_sharded_state(init_fn, W.seed_key(cell.seed), mesh)
    step = make_lm_train_step(mesh, loss_chunk=tr["loss_chunk"],
                              logits_softcap=0.0)
    rows = NamedSharding(mesh, PartitionSpec(("dcn", "dp"), None))

    def feed(i: int):
        ids = client.train_batch(mix, cfg["vocab_size"], cell.seed, i)
        return jax.device_put(ids, rows)

    return state, step, feed, mesh


def first_steps(cell, state, step, feed):
    """Drive the object through its first steps; returns (state, the
    program's readings as device values)."""
    import jax

    cfg = cell.cfg
    b1 = cfg["assumed"]["train"]["optimizer"]["b1"]

    @jax.jit
    def grad_norms(mu):
        g = jax.tree_util.tree_map(lambda m: m / (1.0 - b1), mu)
        return check.leaf_norms(adapter.from_program_params(g, cfg))

    pdt = adapter.dtype_of(cfg["assumed"]["train"]["param_dtype"])

    @jax.jit
    def change_norms(params, key):
        w0 = W.init_weights(cfg, key, pdt)
        w = adapter.from_program_params(params, cfg)
        return check.leaf_norms({n: w[n] - w0[n] for n in w0})

    losses, raws, g1 = [], [], None
    for i in range(CHECK_STEPS):
        state, m = step(state, feed(i))
        losses.append(m["loss"])
        raws.append(m["grad_norm"])
        if i == 0:
            g1 = grad_norms(_adam_moments(state.opt_state).mu)
    change = change_norms(state.params, W.seed_key(cell.seed))
    return state, {"loss": losses, "grad_norm": raws, "first_grad": g1,
                   "change": change}


def run(cell) -> dict:
    import jax

    cfg, mix, log = cell.cfg, cell.mix, common.log
    state, step, feed, _mesh = build(cell)
    state, readings = first_steps(cell, state, step, feed)
    prog = {"loss": [float(x) for x in readings["loss"]],
            "grad_norm": [float(x) for x in readings["grad_norm"]],
            "first_grad": jax.device_get(readings["first_grad"]),
            "change": jax.device_get(readings["change"])}
    log(f"first steps: losses {prog['loss']} grad norms {prog['grad_norm']}")
    # one more step so that the window opens on a warm, empty queue
    state, m = step(state, feed(CHECK_STEPS))
    float(m["loss"])

    tokens_per_step = int(mix["batch"]) * int(mix["seq_len"])
    tracing = trace.Window(cell) if cell.trace else None
    built_before = cell.compiles.total
    dispatch = []
    t0 = time.monotonic()
    cell.mark_window_start()
    t_end = t0 + cell.seconds
    i, prev, steps_done = CHECK_STEPS + 1, None, 0
    traced_from = None
    while True:
        if (tracing is not None and tracing.t_start is None
                and time.monotonic() - t0 >= 2.0):
            if prev is not None:
                float(prev["loss"])
            tracing.start()
            traced_from = steps_done
        a = time.monotonic()
        state, m = step(state, feed(i))
        dispatch.append(time.monotonic() - a)
        i += 1
        if prev is not None:
            float(prev["loss"])          # stay one step ahead of the device
        steps_done += 1 if prev is not None else 0
        prev = m
        if (tracing is not None and tracing.t_stop is None
                and traced_from is not None
                and steps_done - traced_from >= TRACE_STEPS):
            float(m["loss"])
            tracing.stop()
        if time.monotonic() >= t_end:
            break
    last_loss = float(prev["loss"])       # the window closes on a readback
    steps_done += 1
    elapsed = time.monotonic() - t0
    if tracing is not None:
        tracing.close()
    built = cell.compiles.total - built_before
    log(f"window: {steps_done} steps in {elapsed:.3f} s, last loss "
        f"{last_loss:.4f}; programs built in window: {built}")
    device = common.device_block(cell.devices)

    del state, m, prev, readings
    gc.collect()
    t_ref = time.monotonic()
    batches = [client.train_batch(mix, cfg["vocab_size"], cell.seed, s)
               for s in range(CHECK_STEPS)]
    opt = cfg["assumed"]["train"]["optimizer"]
    ref = check.train_reference(cfg, opt, cell.seed, batches)
    numbers = check.train_numbers(prog, ref)
    if cell.control is not None:
        ctl = check.train_reference(cfg, opt, cell.seed, batches,
                                    control=cell.control)
        numbers.update({f"control_{k}": v for k, v in
                        check.train_numbers(ctl, ref).items()})
        half = check.train_reference(cfg, opt, cell.seed, batches,
                                     rows=slice(0, int(mix["batch"]) // 2))
        numbers.update({f"halfbatch_{k}": v for k, v in
                        check.train_numbers(half, ref).items()})
    if not np.isfinite(last_loss):
        numbers["loss_gap_max"] = float("inf")
    log(f"reference: {CHECK_STEPS} steps in "
        f"{time.monotonic() - t_ref:.1f} s; reference losses {ref['loss']}")

    return {
        "end_to_end": {
            "train_tokens_per_s": steps_done * tokens_per_step / elapsed},
        "attempted": steps_done, "failed": 0, "numbers": numbers,
        "device": device, "trace": tracing,
        "train": {"steps": steps_done, "elapsed_s": elapsed,
                  "tokens_per_step": tokens_per_step,
                  "dispatch_s": dispatch, "seq_len": int(mix["seq_len"]),
                  "batch": int(mix["batch"]),
                  "programs_built_in_window": built},
    }
