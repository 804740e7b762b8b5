"""Transformer LM training job with checkpoint/resume — the flagship workload.

The DDP-BERT-equivalent of BASELINE.md config 3, as SPMD pjit with optional
tensor parallelism: ``python -m kubeflow_tpu.examples.lm --steps 100 --tp 2``.
Resumes from ``KFTPU_CHECKPOINT_DIR`` automatically after a gang restart.
"""

from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp

from kubeflow_tpu.examples.common import (
    checkpoint_dir,
    launcher_init,
    log_metrics,
    make_step_telemetry,
)
from kubeflow_tpu.parallel.mesh import data_parallel_size
from kubeflow_tpu.models import Transformer, TransformerConfig
from kubeflow_tpu.train import (
    TrainState,
    create_sharded_state,
    make_lm_train_step,
    make_optimizer,
)
from kubeflow_tpu.train.checkpoint import CheckpointManager
from kubeflow_tpu.utils.profiler import StepProfiler


def main(argv=None) -> float:
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--per-device-batch", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=512)
    p.add_argument("--vocab-size", type=int, default=32000)
    p.add_argument("--d-model", type=int, default=768)
    p.add_argument("--n-layers", type=int, default=12)
    p.add_argument("--n-heads", type=int, default=12)
    p.add_argument("--d-ff", type=int, default=3072)
    p.add_argument("--n-experts", type=int, default=0)
    p.add_argument("--tp", type=int, default=None)
    p.add_argument("--learning-rate", type=float, default=3e-4)
    p.add_argument("--checkpoint-every", type=int, default=50)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--export", default=None, metavar="DIR",
                   help="export the trained model for serving "
                        "(versioned model-store layout)")
    p.add_argument("--generate", type=int, default=0, metavar="N",
                   help="after training, greedy-decode N tokens as a "
                        "smoke sample")
    p.add_argument("--draft-layers", type=int, default=0, metavar="L",
                   help="with --export: also distill an L-layer draft "
                        "from the trained model and export it as the "
                        "paired speculative draft (<export>-draft, "
                        "draft_of pairing)")
    p.add_argument("--draft-distill-steps", type=int, default=200)
    args = p.parse_args(argv)

    penv, mesh = launcher_init(tp=args.tp)
    config = TransformerConfig(
        vocab_size=args.vocab_size,
        d_model=args.d_model,
        n_layers=args.n_layers,
        n_heads=args.n_heads,
        n_kv_heads=args.n_heads,
        d_ff=args.d_ff,
        max_seq_len=args.seq_len,
        n_experts=args.n_experts,
    )
    model = Transformer(config)
    batch = args.per_device_batch * data_parallel_size(mesh)
    tx = make_optimizer(args.learning_rate, warmup_steps=20,
                        decay_steps=args.steps + 1)
    sample = jnp.zeros((batch, args.seq_len), jnp.int32)

    def init_fn(rng):
        params = model.init(rng, sample)["params"]
        return TrainState.create(apply_fn=model.apply, params=params, tx=tx)

    state, _ = create_sharded_state(init_fn, jax.random.key(0), mesh)

    ckpt = None
    start_step = 0
    if checkpoint_dir():
        ckpt = CheckpointManager(checkpoint_dir())
        state, start_step = ckpt.restore_or_init(state)
    if start_step >= args.steps:
        # restarted after the final checkpoint: nothing left to train —
        # but the export/sample side effects must still happen, or a
        # job preempted between its last checkpoint and exit never
        # delivers the model it was asked to export
        log_metrics(start_step, done=True)
        _finish(args, config, state)
        if ckpt:
            ckpt.close()
        return 0.0

    # step telemetry (docs/OBSERVABILITY.md training plane): wall time,
    # tokens/s, MFU + recompiles into the metrics registry, per-host
    # beacons to the operator when inside a gang, flight-recorder dump
    # on step failure / slow step
    telem = make_step_telemetry(tokens_per_step=batch * args.seq_len)
    step_fn = telem.wrap(make_lm_train_step(mesh))
    prof = StepProfiler.from_env()
    data_rng = jax.random.key(1234)
    t0 = time.perf_counter()
    tokens_done = 0
    for step in range(start_step + 1, args.steps + 1):
        prof.step(step)
        rng = jax.random.fold_in(data_rng, step)
        tokens = jax.random.randint(rng, (batch, args.seq_len), 0,
                                    config.vocab_size)
        state, metrics = step_fn(state, tokens)
        tokens_done += batch * args.seq_len
        if step % args.log_every == 0 or step == args.steps:
            tps = tokens_done / (time.perf_counter() - t0)
            log_metrics(step, loss=metrics["loss"],
                        grad_norm=metrics["grad_norm"],
                        tokens_per_sec=tps,
                        tokens_per_sec_per_chip=tps / jax.device_count(),
                        **{f"step_{k}": v
                           for k, v in telem.summary().items()})
        if ckpt and (step % args.checkpoint_every == 0 or step == args.steps):
            ckpt.save(step, state)
    prof.close()
    telem.close()  # the last step's record closes once it has completed
    if ckpt:
        ckpt.wait()
        ckpt.close()
    _finish(args, config, state)
    return float(metrics["loss"])


def _finish(args, config, state) -> None:
    """Post-training side effects: sample + export (also on the
    restarted-after-final-checkpoint path)."""
    if args.generate:
        # train -> decode, end to end: greedy sample from the trained
        # weights through the KV-cache path (models/decode.py)
        from kubeflow_tpu.models.decode import generate

        prompt_len = max(1, min(8, config.max_seq_len // 2))
        max_new = min(args.generate, config.max_seq_len - prompt_len)
        if max_new < 1:
            log_metrics(args.steps, sample_skipped=(
                f"max_seq_len {config.max_seq_len} leaves no room to "
                "generate"))
        else:
            prompt = jax.random.randint(jax.random.key(7),
                                        (1, prompt_len), 0,
                                        config.vocab_size)
            out = generate(config, state.params, prompt,
                           max_new_tokens=max_new)
            log_metrics(args.steps, sample_tokens=out[0].tolist())
    if args.export:
        from kubeflow_tpu.serving import export_model, transformer_export_config

        vdir = export_model(
            args.export, "transformer", state.params, version=1,
            config=transformer_export_config(config))
        log_metrics(args.steps, exported=vdir)
        if args.draft_layers:
            # train → serve WITH speculative decoding, end to end: a
            # layer-truncated, self-distilled draft exported as this
            # model's paired draft (serving routes "speculative": true
            # requests through it; see train/distill.py)
            from kubeflow_tpu.train.distill import make_draft

            dcfg, dparams, stats = make_draft(
                config, state.params, n_layers=args.draft_layers,
                distill_steps=args.draft_distill_steps)
            name = os.path.basename(os.path.normpath(args.export))
            droot = os.path.join(os.path.dirname(
                os.path.normpath(args.export)), f"{name}-draft")
            ddir = export_model(
                droot, "transformer", dparams, version=1,
                config=transformer_export_config(dcfg),
                draft_of=f"{name}@1")
            log_metrics(args.steps, draft_exported=ddir,
                        draft_distill_loss=stats["last_loss"])


if __name__ == "__main__":
    main()
