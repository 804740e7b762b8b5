"""The five BASELINE.md benchmark configs, runnable on whatever chips exist.

Reference counterpart: the kubebench pipeline drives ``tf_cnn_benchmarks``
workloads and a csv reporter (``/root/reference/kubeflow/kubebench/
kubebench-job.libsonnet:250-396``); the reference publishes no numbers
(BASELINE.md), so each config here *measures* and reports:

1. ``mnist``      — tf-cnn MNIST 1-worker parity: correctness smoke
                    (loss must fall) + images/sec.
2. ``resnet50``   — the headline: SPMD training throughput, images/sec/chip
                    + achieved TFLOP/s + MFU.
3. ``bert``       — DDP BERT-base parity: masked-LM step time + MFU.
4. ``allreduce``  — MPI/NCCL ring-allreduce parity: XLA AllReduce bus GB/s.
5. ``serving``    — tf-serving parity: REST predict p50/p99 latency + QPS.

MFU accounting: FLOPs per step are analytic model FLOPs (the MFU
convention — rematerialization or backend-specific lowering must not
inflate the score), adjusted for the exact model variant under test; peak
comes from the device kind, and a TPU this table does not know is an
error, never a silent peak of zero.
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Any, Callable, Dict, Optional

import numpy as np

# bf16 peak TFLOP/s per chip by device kind (substring match, lowercase).
# Source: Google Cloud TPU documentation, the per-generation system
# architecture pages (v5e: 197 TFLOP/s bf16, 819 GB/s HBM).
_PEAK_TFLOPS = {
    "v5 lite": 197.0,   # v5e ("TPU v5 lite" is what jax reports)
    "v5litepod": 197.0,
    "v5e": 197.0,
    "v5p": 459.0,
    "v4": 275.0,
    "v6 lite": 918.0,   # v6e / Trillium
    "v6e": 918.0,
    "v3": 123.0,
    "v2": 46.0,
}


def _by_device_kind(table: Dict[str, float]) -> float:
    """First substring match of the attached chip's kind in ``table``.
    On a TPU backend a kind the table does not know raises — an MFU or
    roofline row computed against a guessed peak is worse than no row.
    Off-TPU (the CPU test tier) there is no peak: 0.0, and the callers
    emit no utilization."""
    import jax

    kind = jax.devices()[0].device_kind.lower()
    for k, v in table.items():
        if k in kind:
            return v
    if jax.default_backend() == "tpu":
        raise LookupError(
            f"device_kind {jax.devices()[0].device_kind!r} is not in the "
            "peaks table (kubeflow_tpu/bench/suite.py); add it with its "
            "source before benchmarking on it")
    return 0.0


def peak_flops_per_chip() -> float:
    """bf16 peak FLOP/s of one attached chip (0.0 off-TPU)."""
    return _by_device_kind(_PEAK_TFLOPS) * 1e12


def resnet50_train_flops_per_image(stem: str) -> float:
    """Analytic fwd+bwd FLOPs per 224² image (3 × forward).

    The standard 7×7-stem ResNet-50 forward is ~4.11 GFLOP; the
    space_to_depth stem replaces the 0.236 GFLOP stem conv with a
    0.077 GFLOP 2×2 conv over folded pixels — the MFU constant must match
    the model actually compiled or the score is inflated."""
    fwd = 4.11e9 if stem == "conv" else 4.11e9 - 0.236e9 + 0.077e9
    return 3.0 * fwd


def _timed_steps(step: Callable, n_steps: int, warmup: int,
                 sync: Callable[[], None]) -> float:
    """Seconds per step, after warmup; ``sync`` forces device completion
    (every caller reads the last step's loss back to the host, which
    cannot return before the step it depends on has run)."""
    for _ in range(warmup):
        step()
    sync()
    t0 = time.perf_counter()
    for _ in range(n_steps):
        step()
    sync()
    return (time.perf_counter() - t0) / n_steps


# HBM bandwidth per chip by device kind (GB/s, bf16 era datasheets)
_HBM_GBPS = {
    "v5 lite": 819.0, "v5litepod": 819.0, "v5e": 819.0,
    "v5p": 2765.0, "v4": 1228.0, "v6 lite": 1640.0, "v6e": 1640.0,
    "v3": 900.0, "v2": 700.0,
}


def _roofline(jitted, mesh, sec_per_step: float, *args) -> Dict[str, Any]:
    """Memory-roofline context for a jitted step: XLA's bytes-accessed
    estimate vs the chip's HBM bandwidth.

    MFU alone misleads on bandwidth-bound workloads (ResNet-50 training
    with exact BatchNorm reads/writes ~25× more activation bytes per FLOP
    than a transformer): when ``hbm_bound_fraction`` ≈ 1, the step is at
    the memory roofline and more MFU is not available at this batch size
    and dtype — cf. the profile traces committed per round."""
    try:
        bw = _by_device_kind(_HBM_GBPS)
        if not bw:
            return {}
        # one extra AOT trace+compile to read cost_analysis; the backend
        # compile cache (the step just ran with these shapes) keeps it cheap
        from kubeflow_tpu.parallel.mesh import mesh_context

        with mesh_context(mesh):
            ca = jitted.lower(*args).compile().cost_analysis()
        if isinstance(ca, list):
            ca = ca[0] if ca else {}
        nbytes = float(ca.get("bytes accessed", 0.0))
        if not nbytes:
            return {}
        roofline_s = nbytes / (bw * 1e9)
        return {
            "hbm_gb_per_step": round(nbytes / 1e9, 2),
            "hbm_roofline_ms": round(roofline_s * 1e3, 2),
            "hbm_bound_fraction": round(roofline_s / sec_per_step, 3),
        }
    except Exception:  # noqa: BLE001 — context, never a bench failure
        return {}


def _capture_trace(step: Callable, sync: Callable[[], None],
                   logdir: str, n_steps: int = 3) -> None:
    """Profile n compiled steps AFTER timing (capture overhead must not
    contaminate the reported numbers); trace lands in ``logdir``. Capture
    is auxiliary: a profiler failure must never void the measured result."""
    import logging

    from kubeflow_tpu.utils.profiler import trace

    try:
        with trace(logdir):
            for _ in range(n_steps):
                step()
            sync()
    except Exception as e:  # noqa: BLE001
        logging.getLogger(__name__).warning(
            "trace capture failed (result kept): %s: %s",
            type(e).__name__, e)


def _mfu(flops_per_step: Optional[float], sec_per_step: float,
         n_chips: int) -> Dict[str, float]:
    peak = peak_flops_per_chip()
    if not flops_per_step or not peak:
        return {}
    achieved = flops_per_step / sec_per_step
    return {
        "tflops_per_chip": round(achieved / n_chips / 1e12, 2),
        "mfu": round(achieved / (peak * n_chips), 4),
    }


def _step_telemetry_pass(step: Callable, sync: Callable[[], None],
                         jitted: Any, *, n_steps: int,
                         flops_per_step: Optional[float],
                         n_chips: int) -> Dict[str, Any]:
    """A short per-step-synced pass through :class:`StepTelemetry` AFTER
    the mean-timing pass, so the BENCH artifact carries step-REGULARITY
    evidence (p50/p99 step time, recompile count, MFU) next to the
    means. Separate pass by design: per-step sync serializes dispatch
    and must not contaminate the headline throughput numbers. Auxiliary
    by contract — any failure returns {} and the measured result stands."""
    try:
        from kubeflow_tpu.obs.steps import StepTelemetry
        from kubeflow_tpu.utils.metrics import Registry

        telem = StepTelemetry(
            registry=Registry(),  # private: no global-registry pollution
            flops_per_step=flops_per_step,
            peak_flops_per_chip=peak_flops_per_chip() or None,
            n_chips=n_chips, use_cost_analysis=False)

        def one_synced():
            step()
            sync()

        one_synced.jitted = jitted  # real recompile accounting (cache delta)
        wrapped = telem.wrap(one_synced)
        # compile & memory evidence beside the goodput block
        # (docs/OBSERVABILITY.md "Compile & memory"): a private ledger
        # subscribed for the pass's duration records any backend
        # compiles the pass triggers, and the AOT fingerprint/budget
        # read prices the program's predicted footprint
        from kubeflow_tpu.obs.xprof import CompileLedger, HbmSampler

        ledger = CompileLedger()
        ledger.install()
        try:
            for _ in range(n_steps):
                wrapped()
        finally:
            ledger.uninstall()
        out: Dict[str, Any] = {"step_telemetry": telem.summary()}
        # the goodput block (docs/OBSERVABILITY.md "Goodput"): the
        # productive fraction of the pass's wall clock next to img/s,
        # so a round that recompiles or stalls between steps reads as
        # the badput it is, not as a flat throughput number
        from kubeflow_tpu.obs.goodput import from_step_records

        block = from_step_records(telem.recorder.records())
        if block:
            out["goodput"] = block
        compile_block = ledger.summary()
        if compile_block.get("count"):
            out["compile"] = compile_block
        memory: Dict[str, Any] = {}
        try:
            from kubeflow_tpu.obs.xprof import (
                hlo_fingerprint,
                memory_budget,
            )

            lower = getattr(jitted, "lower", None)
            if lower is not None:
                lowered = lower()
                compiled = lowered.compile()
                budget = memory_budget(compiled)
                if budget:
                    memory["budget_bytes"] = budget
                    memory["fingerprint"] = hlo_fingerprint(lowered)
        except Exception:  # noqa: BLE001 — evidence, never a failure
            pass
        watermark = HbmSampler().sample()
        if watermark:
            memory["hbm_bytes"] = {k: int(v)
                                   for k, v in watermark.items()}
        if memory:
            out["memory"] = memory
        return out
    except Exception:  # noqa: BLE001 — evidence, never a bench failure
        return {}


# -- config 1: MNIST smoke ---------------------------------------------------


def bench_mnist(steps: int = 30, batch: int = 256) -> Dict[str, Any]:
    """tf-cnn MNIST 1-worker parity: loss must fall while we time it."""
    import jax
    import jax.numpy as jnp
    import optax

    from kubeflow_tpu.models import MnistCnn
    from kubeflow_tpu.parallel import MeshConfig, create_mesh
    from kubeflow_tpu.train import (
        TrainState, create_sharded_state, make_image_train_step,
    )

    mesh = create_mesh(MeshConfig(dp=jax.device_count()))
    model = MnistCnn()
    rng = jax.random.key(0)
    # synthetic-but-learnable task: label = quadrant of the brightest pixel
    images = jax.random.uniform(rng, (batch, 28, 28, 1), jnp.float32)
    flat = images.reshape(batch, -1).argmax(axis=1)
    labels = ((flat // 28 // 14) * 2 + (flat % 28) // 14).astype(jnp.int32)

    def init_fn(rng):
        params = model.init(rng, images[:2])["params"]
        return TrainState.create(
            apply_fn=lambda v, x, train=True: model.apply(v, x),
            params=params, tx=optax.adam(1e-3))

    state, _ = create_sharded_state(init_fn, rng, mesh)
    step = make_image_train_step(mesh)
    state, first = step(state, images, labels)
    first_loss = float(first["loss"])
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, images, labels)
    last_loss = float(metrics["loss"])
    dt = time.perf_counter() - t0
    return {
        "images_per_sec": round(steps * batch / dt, 1),
        "first_loss": round(first_loss, 4),
        "last_loss": round(last_loss, 4),
        "learned": last_loss < first_loss,
    }


# -- config 2: ResNet-50 training (the headline) -----------------------------


def bench_resnet50(batch_per_chip: int = 256, steps: int = 20,
                   warmup: int = 5,
                   profile_dir: Optional[str] = None) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import optax

    from kubeflow_tpu.models.resnet import resnet50
    from kubeflow_tpu.parallel import MeshConfig, create_mesh
    from kubeflow_tpu.train import (
        TrainState, create_sharded_state, make_image_train_step,
    )

    n_chips = jax.device_count()
    mesh = create_mesh(MeshConfig(dp=n_chips))
    # KFTPU_RESNET_ACT_COMPRESS=1: int8 forward-saved conv inputs
    # (ops/act_compress.py) — the PERF.md bandwidth-lever A/B switch
    model = resnet50(
        num_classes=1000,
        act_compress=os.environ.get("KFTPU_RESNET_ACT_COMPRESS",
                                    "0") == "1",
        # KFTPU_RESNET_FUSED_BN=1: bn2+ReLU fused into conv3's GEMM
        # (ops/bnconv.py) — the PERF.md normalize-pass lever A/B switch
        fused_bn_conv=os.environ.get("KFTPU_RESNET_FUSED_BN",
                                     "0") == "1")
    stem = model.config.stem
    batch = batch_per_chip * n_chips
    rng = jax.random.key(0)
    images = jax.random.normal(rng, (batch, 224, 224, 3), jnp.bfloat16)
    labels = jax.random.randint(rng, (batch,), 0, 1000)
    # the reference workload trains with momentum SGD
    # (tf_cnn_benchmarks defaults); matching it also keeps the optimizer
    # update bandwidth-light next to adamw's two moment buffers
    tx = optax.sgd(0.1, momentum=0.9, nesterov=False)

    def init_fn(rng):
        variables = model.init(rng, images[:2], train=True)
        return TrainState.create(
            apply_fn=model.apply, params=variables["params"],
            batch_stats=variables["batch_stats"], tx=tx)

    state, _ = create_sharded_state(init_fn, rng, mesh)
    step = make_image_train_step(mesh)

    holder = {"state": state}

    def one():
        holder["state"], holder["m"] = step(holder["state"], images, labels)

    sec = _timed_steps(one, steps, warmup,
                       sync=lambda: float(holder["m"]["loss"]))
    if profile_dir:
        _capture_trace(one, lambda: float(holder["m"]["loss"]), profile_dir)
    ips = batch / sec
    out = {
        "images_per_sec_per_chip": round(ips / n_chips, 2),
        "n_chips": n_chips,
        "batch_per_chip": batch_per_chip,
        "stem": stem,
        **_mfu(resnet50_train_flops_per_image(stem) * batch, sec, n_chips),
    }
    out.update(_roofline(step.jitted, mesh, sec,
                         holder["state"], images, labels))
    out.update(_step_telemetry_pass(
        one, lambda: float(holder["m"]["loss"]), step.jitted,
        n_steps=min(8, steps),
        flops_per_step=resnet50_train_flops_per_image(stem) * batch,
        n_chips=n_chips))
    return out


# -- config 3: BERT-base step time -------------------------------------------


def bench_bert(batch_per_chip: int = 16, seq_len: int = 512,
               steps: int = 10, warmup: int = 3,
               profile_dir: Optional[str] = None) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.bert import Bert, bert_base
    from kubeflow_tpu.parallel import MeshConfig, create_mesh
    from kubeflow_tpu.parallel.mesh import record_kernel_placements
    from kubeflow_tpu.train import (
        TrainState, create_sharded_state, make_mlm_train_step, make_optimizer,
    )

    n_chips = jax.device_count()
    mesh = create_mesh(MeshConfig(dp=n_chips))
    cfg = bert_base()
    model = Bert(cfg)
    batch = batch_per_chip * n_chips
    rng = jax.random.key(0)
    tokens = jax.random.randint(rng, (batch, seq_len), 0, cfg.vocab_size)
    labels = jax.random.randint(jax.random.key(1), (batch, seq_len), 0,
                                cfg.vocab_size)
    weights = (jax.random.uniform(jax.random.key(2), (batch, seq_len))
               < 0.15).astype(jnp.float32)
    tx = make_optimizer(1e-4, warmup_steps=10, decay_steps=1000)

    def init_fn(rng):
        params = model.init(rng, tokens[:2])["params"]
        return TrainState.create(apply_fn=model.apply, params=params, tx=tx)

    state, _ = create_sharded_state(init_fn, rng, mesh)
    step = make_mlm_train_step(mesh)

    holder = {"state": state}

    def one():
        holder["state"], holder["m"] = step(holder["state"], tokens, labels,
                                            weights)

    # record every tile resolution the compile makes (attention_impl
    # "auto": flash + table on TPU, dense oracle elsewhere) so the
    # artifact row attributes a BERT MFU move to a table change
    from kubeflow_tpu.ops import autotune

    with autotune.record_resolutions() as tile_rec, \
            record_kernel_placements() as placed:
        sec = _timed_steps(one, steps, warmup,
                           sync=lambda: float(holder["m"]["loss"]))
    if profile_dir:
        _capture_trace(one, lambda: float(holder["m"]["loss"]), profile_dir)
    # analytic transformer train FLOPs: 6·N·D (N params, D tokens) plus the
    # attention score/value matmuls, 12·L·S²·d per token fwd+bwd
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(state.params))
    flops_per_step = (6 * n_params * batch * seq_len
                      + 12 * cfg.n_layers * batch * seq_len * seq_len
                      * cfg.d_model)
    return {
        "step_time_ms": round(sec * 1e3, 2),
        "tokens_per_sec_per_chip": round(batch * seq_len / sec / n_chips, 1),
        "n_chips": n_chips,
        "batch_per_chip": batch_per_chip,
        "seq_len": seq_len,
        # resolved tile configs + resolution source (table|fallback|
        # override); empty when the run took the dense XLA path (the
        # off-TPU "auto" oracle)
        "attention_impl": cfg.attention_impl,
        "tile_config": autotune.summarize_resolutions(tile_rec),
        # how parallel/mesh.py:shard_kernel split each kernel over the
        # mesh ([] on one chip); a dropped axis is replicated work
        "kernel_placement": placed,
        **_mfu(flops_per_step, sec, n_chips),
        **_step_telemetry_pass(
            one, lambda: float(holder["m"]["loss"]), step.jitted,
            n_steps=min(8, steps), flops_per_step=flops_per_step,
            n_chips=n_chips),
    }


# -- long-context training (the capability the reference lacks) -------------


def bench_longcontext(seq_len: int = 8192, batch_per_chip: int = 2,
                      steps: int = 8, warmup: int = 2,
                      d_model: int = 1024, n_layers: int = 8,
                      n_heads: int = 16, d_ff: int = 4096,
                      loss_chunk: Optional[int] = None,
                      profile_dir: Optional[str] = None) -> Dict[str, Any]:
    """Long-sequence LM training throughput with the Pallas flash-attention
    path — the long-context capability SURVEY §5 names as first-class (the
    reference's training stack has no sequence-parallel/long-context story
    at all). On one chip this exercises the flash kernel + remat; the
    sequence-parallel ring path over tp is covered by the virtual-mesh
    tier (tests/test_ops.py) and the multichip dryrun."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models import Transformer, TransformerConfig
    from kubeflow_tpu.parallel import MeshConfig, create_mesh
    from kubeflow_tpu.parallel.mesh import record_kernel_placements
    from kubeflow_tpu.train import (
        TrainState, create_sharded_state, make_lm_train_step, make_optimizer,
    )

    n_chips = jax.device_count()
    mesh = create_mesh(MeshConfig(dp=n_chips))
    config = TransformerConfig(
        vocab_size=32000, d_model=d_model, n_layers=n_layers,
        n_heads=n_heads, n_kv_heads=n_heads, d_ff=d_ff,
        max_seq_len=seq_len, attention_impl="flash", remat=True,
    )
    # past 16k the full (B, S, V) f32 logit tensor alone approaches HBM
    # capacity — the chunked-loss path (hidden states out, vocab
    # projection per chunk) is what makes those contexts trainable
    if loss_chunk is None and seq_len > 16384:
        loss_chunk = 4096
    model = Transformer(config, return_hidden=bool(loss_chunk))
    batch = batch_per_chip * n_chips
    tokens = jax.random.randint(jax.random.key(0), (batch, seq_len), 0,
                                config.vocab_size)
    tx = make_optimizer(3e-4, warmup_steps=5, decay_steps=100)

    def init_fn(rng):
        # init over a 2-example slice: param shapes don't depend on batch,
        # and a full-batch init would execute a whole extra forward
        params = model.init(rng, tokens[:2])["params"]
        return TrainState.create(apply_fn=model.apply, params=params, tx=tx)

    state, _ = create_sharded_state(init_fn, jax.random.key(0), mesh)
    step = make_lm_train_step(mesh, loss_chunk=loss_chunk,
                              logits_softcap=config.logits_softcap)
    holder = {"state": state}

    def one():
        holder["state"], holder["m"] = step(holder["state"], tokens)

    # the flash tiles this run compiled with, and where they resolved
    # from (tile_config in the row): an A/B round can attribute a
    # tok/s move to a tile_table.json change instead of guessing
    from kubeflow_tpu.ops import autotune

    with autotune.record_resolutions() as tile_rec, \
            record_kernel_placements() as placed:
        sec = _timed_steps(one, steps, warmup,
                           sync=lambda: float(holder["m"]["loss"]))
    if profile_dir:
        _capture_trace(one, lambda: float(holder["m"]["loss"]), profile_dir)
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(holder["state"].params))
    # 6·N·D plus causal attention matmuls (12·L·S²·d per token, halved for
    # causality) — remat recompute is excluded per the MFU convention
    flops_per_step = (6 * n_params * batch * seq_len
                      + 6 * config.n_layers * batch * seq_len * seq_len
                      * config.d_model)
    return {
        "tokens_per_sec_per_chip": round(batch * seq_len / sec / n_chips, 1),
        "step_time_ms": round(sec * 1e3, 2),
        "seq_len": seq_len,
        "batch_per_chip": batch_per_chip,
        "attention": "flash(pallas)+remat",
        "tile_config": autotune.summarize_resolutions(tile_rec),
        # how parallel/mesh.py:shard_kernel split each kernel over the
        # mesh ([] on one chip); a dropped axis is replicated work
        "kernel_placement": placed,
        "loss": f"chunked({loss_chunk})" if loss_chunk else "full_logits",
        "n_chips": n_chips,
        **_mfu(flops_per_step, sec, n_chips),
    }


# -- config 4: allreduce microbench ------------------------------------------


def bench_allreduce(size_mb: float = 64.0, iters: int = 10) -> Dict[str, Any]:
    import jax

    from kubeflow_tpu.ops.collectives import bench_collective
    from kubeflow_tpu.parallel import MeshConfig, create_mesh

    n = jax.device_count()
    if n < 2:
        # a 1-chip allreduce is the identity. Still record the 8-device
        # virtual CPU mesh number (subprocess — this process holds the
        # chip; the child runs with JAX_PLATFORMS=cpu and never opens
        # it) so regressions in the collective path stay visible
        # round-over-round even on 1-chip hardware.
        out: Dict[str, Any] = {"n_chips": n, "skipped": "needs >=2 chips"}
        virt = _virtual_mesh_allreduce(size_mb=8.0, iters=iters)
        if virt is not None:
            out["virtual_cpu_mesh"] = virt
        return out
    mesh = create_mesh(MeshConfig(dp=n))
    res = bench_collective("all_reduce", mesh, "dp", size_mb=size_mb,
                           iters=iters)
    return {
        "bus_gb_per_sec": round(res.bus_gb_s, 2),
        "payload_mb": round(res.size_mb, 1),
        "mean_ms": round(res.mean_s * 1e3, 3),
        "n_chips": n,
    }


def _virtual_mesh_allreduce(*, size_mb: float, iters: int,
                            n_devices: int = 8) -> Optional[Dict[str, Any]]:
    """AllReduce bus bandwidth over an 8-device virtual CPU mesh, measured
    in a subprocess (this interpreter's backend is already chosen, and on
    the chip it owns the TPU: the child gets ``JAX_PLATFORMS=cpu`` and
    never opens it). Tracks the collective *code path*, not hardware speed.
    Returns None (with a logged warning) when the subprocess fails, so the
    published key always has the success shape."""
    import logging
    import subprocess

    prog = (
        "import json\n"
        "from kubeflow_tpu.ops.collectives import bench_collective\n"
        "from kubeflow_tpu.parallel import MeshConfig, create_mesh\n"
        f"mesh = create_mesh(MeshConfig(dp={n_devices}))\n"
        f"r = bench_collective('all_reduce', mesh, 'dp', "
        f"size_mb={size_mb}, iters={iters})\n"
        "print(json.dumps({'bus_gb_per_sec': round(r.bus_gb_s, 2), "
        "'payload_mb': round(r.size_mb, 1), "
        "'mean_ms': round(r.mean_s * 1e3, 3), "
        f"'n_devices': {n_devices}}}))\n"
    )
    try:
        proc = subprocess.run(
            [sys.executable, "-c", prog], capture_output=True, text=True,
            timeout=300, cwd=_REPO_ROOT,
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "XLA_FLAGS": "--xla_force_host_platform_device_count="
                              f"{n_devices}"})
        if proc.returncode:
            logging.getLogger(__name__).warning(
                "virtual-mesh allreduce failed: %s",
                proc.stderr.strip()[-300:])
            return None
        return json.loads(proc.stdout.strip().splitlines()[-1])
    except (subprocess.TimeoutExpired, OSError, ValueError) as e:
        logging.getLogger(__name__).warning(
            "virtual-mesh allreduce failed: %s: %s", type(e).__name__, e)
        return None


def bench_decode(batch: int = 8, prompt_len: int = 128,
                 new_tokens: int = 128, d_model: int = 1024,
                 n_layers: int = 8, n_heads: int = 16,
                 d_ff: int = 4096,
                 profile_dir: Optional[str] = None) -> Dict[str, Any]:
    """Autoregressive generation throughput (KV-cache decode loop).

    The LLM-serving hot path the reference has no story for: prefill +
    ``lax.scan`` over single-token steps, all one compiled program
    (``kubeflow_tpu/models/decode.py``). Decode is memory-bound (every
    step reads all params + the KV cache), so the roofline here is
    HBM bytes/token, not FLOPs."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models import Transformer, TransformerConfig
    from kubeflow_tpu.models.decode import make_generate

    n_chips = jax.device_count()
    config = TransformerConfig(
        vocab_size=32000, d_model=d_model, n_layers=n_layers,
        n_heads=n_heads, n_kv_heads=n_heads, d_ff=d_ff,
        max_seq_len=prompt_len + new_tokens, remat=False)
    model = Transformer(config)
    prompt = jax.random.randint(jax.random.key(0), (batch, prompt_len), 0,
                                config.vocab_size)
    params = jax.jit(model.init)(jax.random.key(1), prompt[:2])["params"]

    fn = make_generate(config, max_new_tokens=new_tokens)
    true_len = jnp.int32(prompt_len)
    rng = jax.random.key(2)

    out = fn(params, prompt, true_len, rng)  # compile
    _ = np.asarray(out)
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        out = fn(params, prompt, true_len, rng)
    _ = np.asarray(out)
    dt = (time.perf_counter() - t0) / reps
    if profile_dir:
        holder: Dict[str, Any] = {}

        def one():
            holder["out"] = fn(params, prompt, true_len, rng)

        _capture_trace(one, lambda: np.asarray(holder["out"]),
                       profile_dir, n_steps=1)

    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    # per decoded token the chip reads every param (bf16) once — the
    # memory-bound roofline for batch-small decode
    total_new = batch * new_tokens
    return {
        "tokens_per_sec_per_chip": round(total_new / dt / n_chips, 1),
        "ms_per_token": round(dt / new_tokens * 1e3, 3),
        "batch": batch,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "n_params_m": round(n_params / 1e6, 1),
        "n_chips": n_chips,
    }


def engine_bench_setup(concurrency: int = 48, prompt_len: int = 128,
                       new_tokens: int = 128, d_model: int = 1024,
                       n_layers: int = 8, n_heads: int = 16,
                       d_ff: int = 4096):
    """The decode-engine bench workload: (config, params, prompts).
    Shared with ``scripts/sync_sweep.py`` so sweeps measure exactly the
    bench's shapes."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models import Transformer, TransformerConfig

    config = TransformerConfig(
        vocab_size=32000, d_model=d_model, n_layers=n_layers,
        n_heads=n_heads, n_kv_heads=n_heads, d_ff=d_ff,
        max_seq_len=prompt_len + new_tokens, remat=False)
    model = Transformer(config)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, config.vocab_size,
                           (concurrency, prompt_len), dtype=np.int32)
    params = jax.jit(model.init)(
        jax.random.key(1),
        jnp.asarray(prompts[:2]))["params"]
    return config, params, prompts


def engine_drain(eng) -> None:
    while eng.active_count or eng.pending_count:
        eng.run_once(timeout=0.01)


def ledger_burst_ttft_ms(ledger, wave) -> Optional[float]:
    """Burst TTFT off the request ledger — production's definition
    (docs/OBSERVABILITY.md "Request lifecycle"), replacing the bench's
    old hand-rolled first-wave stamp: wall from the burst's first
    submit until EVERY wave member held its first token (each record's
    submit + ttft). None (JSON null) when a wave member never produced
    one — total run time masquerading as TTFT would poison any A/B
    read of this number."""
    ttfts = [ledger.ttft_ms(r.rid) for r in wave]
    if not wave or any(f is None for f in ttfts):
        return None
    first_all = (max(r.t_submit + f / 1e3 for r, f in zip(wave, ttfts))
                 - min(r.t_submit for r in wave))
    return round(first_all * 1e3, 1)


def engine_throughput(config, params, prompts, *, slots: int,
                      steps_per_sync: int, new_tokens: int,
                      sampler_bound: Optional[int], sampled: bool,
                      sample_kw: Optional[Dict[str, Any]] = None,
                      sampler_impl: Optional[str] = None,
                      paged: bool = False,
                      paged_attention_impl: Optional[str] = None,
                      request_ledger=None,
                      name: str = "bench"):
    """tokens/sec through a fresh engine (params shared in HBM).
    Returns (tok/s/chip, engine steps, burst TTFT ms, batch prefills).
    ``request_ledger`` (a fresh one per run by default, so bench bursts
    never mix into the process ledger) also hands the caller the
    per-request phase breakdown via its ``bench_block()``."""
    import jax

    from kubeflow_tpu.obs import requests as reqobs
    from kubeflow_tpu.serving.engine import DecodeEngine

    n_chips = jax.device_count()
    if request_ledger is None:
        request_ledger = reqobs.RequestLedger()
    eng = DecodeEngine(config, params, slots=slots,
                       steps_per_sync=steps_per_sync,
                       sampler_bound=sampler_bound,
                       sampler_impl=sampler_impl, paged=paged,
                       paged_attention_impl=paged_attention_impl,
                       autostart=False, name=name,
                       request_ledger=request_ledger)

    # warm the compiled programs: the row prefill, insert, step —
    # and every batch-prefill bucket burst admission can hit (a
    # first-shape compile inside the timed window would be measured
    # as serving time)
    kw = dict(sample_kw) if sampled and sample_kw else {}
    n = 1
    while True:
        warms = [eng.submit(prompts[i % len(prompts)],
                            max_new=steps_per_sync + 1, **kw)
                 for i in range(n)]
        engine_drain(eng)
        for w in warms:
            list(w.stream())
        if n >= min(eng.admit_batch_max, slots):
            break
        n *= 2

    steps0, bp0 = eng.steps_total, eng.batch_prefills
    t0 = time.perf_counter()
    reqs = [eng.submit(p, max_new=new_tokens, seed=i, **kw)
            for i, p in enumerate(prompts)]
    wave = reqs[:slots]
    if paged:
        # chunked prefill interleaves admissions with decode: burst
        # TTFT is the wall time until EVERY wave member has its first
        # token (decode of earlier admits proceeds meanwhile)
        for _ in range(10000):
            eng.run_once(timeout=0.01)
            if all(r._seen or r.out.qsize() for r in wave):
                break
    else:
        # burst TTFT: admit the first wave explicitly (one _admit pass
        # fills every free slot, and each request's first token is
        # emitted during its prefill sample) — the number batched
        # admission improves
        eng._admit(None)
    engine_drain(eng)
    total = sum(len(r.result()) for r in reqs)
    dt = time.perf_counter() - t0
    ttft = ledger_burst_ttft_ms(eng.rledger, wave)
    return (round(total / dt / n_chips, 1),
            eng.steps_total - steps0, ttft,
            eng.batch_prefills - bp0)


def engine_prefix_counters(config, params, prompts, *, slots: int,
                           steps_per_sync: int, new_tokens: int,
                           name: str = "bench-prefix") -> Dict[str, Any]:
    """Prefix-trie / copy-on-write effectiveness under a shared-system-
    prompt workload: every request carries the same prefix, chosen one
    token PAST a page boundary so full pages trie-share and the partial
    boundary page exercises a COW split per hit. Returns the counters
    ``engine.snapshot()`` surfaces (docs/OBSERVABILITY.md) plus the
    derived hit rate — the numbers that adjudicate page-granular
    matching against the old exact-prefix store."""
    from kubeflow_tpu.serving.engine import DecodeEngine

    eng = DecodeEngine(config, params, slots=slots,
                       steps_per_sync=steps_per_sync, paged=True,
                       autostart=False, name=name)
    prompt_len = prompts.shape[1]
    # one full page + one boundary token when prompt_len = 2 pages
    prefix_len = min(eng.kv_page_size + 1, prompt_len - 1)
    shared = np.concatenate(
        [np.broadcast_to(prompts[0, :prefix_len],
                         (len(prompts), prefix_len)),
         prompts[:, prefix_len:]], axis=1)
    # warm the trie with the first request alone (a burst placed before
    # the first prefill completes would miss by timing, not by policy —
    # the store pins pages at prefill completion), then burst the rest:
    # every follower should page-share and COW-split
    first = eng.submit(shared[0], max_new=new_tokens,
                       prefix_len=prefix_len)
    engine_drain(eng)
    first.result()
    reqs = [eng.submit(p, max_new=new_tokens, prefix_len=prefix_len)
            for p in shared[1:]]
    engine_drain(eng)
    for r in reqs:
        r.result()
    total = max(1, eng.prefix_hits + eng.prefix_misses)
    counters = {
        "paged_prefix_hits": eng.prefix_hits,
        "paged_prefix_misses": eng.prefix_misses,
        "paged_prefix_hit_rate": round(eng.prefix_hits / total, 3),
        "paged_prefix_pages_shared": eng.prefix_pages_shared,
        "paged_cow_splits": eng.cow_splits,
        "paged_prefix_len": prefix_len,
    }
    eng.close()
    return counters


def bench_decode_engine(concurrency: int = 48, slots: int = 32,
                        prompt_len: int = 128, new_tokens: int = 128,
                        steps_per_sync: int = 64, d_model: int = 1024,
                        n_layers: int = 8, n_heads: int = 16,
                        d_ff: int = 4096,
                        profile_dir: Optional[str] = None
                        ) -> Dict[str, Any]:
    """Continuous-batching serving throughput: ``concurrency`` generate
    requests share the DecodeEngine's ``slots``-row decode batch
    (``kubeflow_tpu/serving/engine.py``) — the production :generate
    path. Decode is HBM-bound per step, so throughput scales with
    effective batch until cache traffic dominates; this measures the
    engine at effective batch = ``slots`` (vs ``bench_decode``'s fixed
    whole-request batch), including prefill, admission, and sampling
    overheads — the number a capacity planner uses. ``steps_per_sync``
    defaults to the r5 sweep's measured optimum (PERF.md, 64 — the
    throughput configuration; serving's latency-bound default lives in
    the manifest)."""
    import jax

    from kubeflow_tpu.parallel.mesh import record_kernel_placements
    from kubeflow_tpu.serving.engine import DecodeEngine

    n_chips = jax.device_count()
    config, params, prompts = engine_bench_setup(
        concurrency, prompt_len, new_tokens, d_model, n_layers,
        n_heads, d_ff)

    sample_kw = {"temperature": 0.8, "top_k": 40, "top_p": 0.95}

    def run_engine(sampler_bound: Optional[int], sampled: bool,
                   sampler_impl: Optional[str] = None,
                   paged: bool = False,
                   paged_attention_impl: Optional[str] = None,
                   request_ledger=None):
        return engine_throughput(
            config, params, prompts, slots=slots,
            steps_per_sync=steps_per_sync, new_tokens=new_tokens,
            sampler_bound=sampler_bound, sampled=sampled,
            sample_kw=sample_kw, sampler_impl=sampler_impl, paged=paged,
            paged_attention_impl=paged_attention_impl,
            request_ledger=request_ledger)

    # sampler modes at the same effective batch: greedy rides the
    # argmax fast-path step; "sampled" pays the per-row sampler. The
    # first lever was bounded-vs-exact-sort (~2.4× tax for correct
    # sampling at slots=32); the fused Pallas kernel
    # (ops/sampling.py) is the exact path that must close that gap.
    bound = 64  # DecodeEngine's default sampler_bound
    # the headline greedy run keeps its request ledger: the artifact's
    # "requests" block is its per-phase breakdown (docs/OBSERVABILITY.md
    # "Request lifecycle")
    from kubeflow_tpu.obs import requests as reqobs

    req_ledger = reqobs.RequestLedger()
    greedy_tps, engine_steps, ttft_ms, batch_prefills = run_engine(
        bound, sampled=False, request_ledger=req_ledger)
    sampled_bounded_tps, _, _, _ = run_engine(bound, sampled=True)
    sampled_exact_tps, _, _, _ = run_engine(
        0, sampled=True, sampler_impl="exact_sort")
    sampled_fused_tps, _, _, _ = run_engine(
        0, sampled=True, sampler_impl="fused")
    # paged-vs-dense: same greedy workload through the paged KV cache
    # + chunked-prefill admission (burst TTFT is the headline there —
    # whole-prompt prefills no longer block the decode loop). The
    # gather-vs-kernel A/B adjudicates the Pallas paged-attention
    # kernel (ops/paged_attention.py): same workload, decode-step
    # attention reads the dense logical view vs streaming live pages
    # through the page table. On the CPU tier the kernel runs in the
    # Pallas interpreter — its wall-clock there proves the path
    # executes, never a perf claim; the TPU-attached round reads it.
    paged_gather_tps, _, paged_gather_ttft, _ = run_engine(
        bound, sampled=False, paged=True, paged_attention_impl="gather")
    # the kernel run is the tuned one: record its tile resolution
    # (paged_attn head_block + source) so the artifact attributes a
    # kernel-row move to a tile-table change
    from kubeflow_tpu.ops import autotune

    with autotune.record_resolutions() as paged_tile_rec, \
            record_kernel_placements() as placed:
        paged_kernel_tps, _, paged_kernel_ttft, _ = run_engine(
            bound, sampled=False, paged=True,
            paged_attention_impl="kernel")
    # "auto" resolves to the kernel on the TPU backend and the gather
    # elsewhere — the headline paged rows reuse the matching A/B run
    # instead of paying a third paged engine pass
    auto_kernel = jax.default_backend() == "tpu"
    paged_tps = paged_kernel_tps if auto_kernel else paged_gather_tps
    paged_ttft_ms = (paged_kernel_ttft if auto_kernel
                     else paged_gather_ttft)
    prefix_counters = engine_prefix_counters(
        config, params, prompts, slots=slots,
        steps_per_sync=steps_per_sync, new_tokens=new_tokens)
    if profile_dir:
        # trace a short greedy engine run. jit caches are per engine
        # instance, so this engine precompiles its step programs and
        # serves one warm request first — the captured trace is decode
        # steps, not XLA compiles. Nothing is consumed after the
        # capture: _capture_trace swallows profiler failures by design,
        # and a blocking read on a maybe-undrained request could hang
        # the bench after all measurements already succeeded.
        eng = DecodeEngine(config, params, slots=slots,
                           steps_per_sync=steps_per_sync,
                           sampler_bound=bound, precompile=True,
                           autostart=False, name="bench-trace")
        warm = eng.submit(prompts[0], max_new=steps_per_sync + 1)
        engine_drain(eng)
        list(warm.stream())
        eng.submit(prompts[0], max_new=min(new_tokens,
                                           4 * steps_per_sync))
        _capture_trace(lambda: engine_drain(eng), lambda: None, profile_dir,
                       n_steps=1)
    return {
        "tokens_per_sec_per_chip": greedy_tps,
        "sampled_bounded_tokens_per_sec_per_chip": sampled_bounded_tps,
        "sampled_exact_sort_tokens_per_sec_per_chip": sampled_exact_tps,
        "sampled_exact_fused_tokens_per_sec_per_chip": sampled_fused_tps,
        "paged_tokens_per_sec_per_chip": paged_tps,
        "paged_burst_first_tokens_ms": paged_ttft_ms,
        "paged_attn_gather_tokens_per_sec_per_chip": paged_gather_tps,
        "paged_attn_kernel_tokens_per_sec_per_chip": paged_kernel_tps,
        "paged_attn_kernel_vs_gather": (
            round(paged_kernel_tps / paged_gather_tps, 3)
            if paged_gather_tps else None),
        "tile_config": autotune.summarize_resolutions(paged_tile_rec),
        # how parallel/mesh.py:shard_kernel split each kernel over the
        # mesh ([] on one chip); a dropped axis is replicated work
        "kernel_placement": placed,
        **prefix_counters,
        "requests": req_ledger.bench_block(),
        "burst_first_tokens_ms": ttft_ms,
        "batch_prefills": batch_prefills,
        "sampler_bound": bound,
        "sampled_params": sample_kw,
        "effective_batch": slots,
        "concurrency": concurrency,
        "steps_per_sync": steps_per_sync,
        "new_tokens": new_tokens,
        "prompt_len": prompt_len,
        "engine_steps": engine_steps,
        "n_chips": n_chips,
    }


# -- config 5: serving latency/QPS -------------------------------------------


def bench_serving(requests: int = 200, batch: int = 8,
                  image_size: int = 224,
                  rest_requests: int = 30) -> Dict[str, Any]:
    """Predict p50/p99 + QPS through BOTH serving surfaces.

    Primary numbers are the gRPC :9000 binary-tensor path — the reference
    model server's primary surface (``/root/reference/kubeflow/tf-serving/
    tf-serving-template.libsonnet:33-48``) and the one a production client
    uses. The REST JSON path (``rest_*`` keys, fewer iterations — the
    batch-8 224² request is ~24 MB of ASCII floats) is measured separately
    so the JSON encode/decode overhead is itself visible rather than
    masquerading as model latency."""
    import tempfile
    import urllib.request

    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.resnet import ResNet, ResNetConfig
    from kubeflow_tpu.serving import ModelServer, export_model
    from kubeflow_tpu.serving.grpc_server import PredictClient, serve_grpc

    # serving-size ResNet-50; fp32 params exported, bf16 compute.
    # init under jit: one compiled program instead of an eager dispatch
    # (and a compile) per op
    cfg = ResNetConfig(stage_sizes=(3, 4, 6, 3), num_classes=1000)
    model = ResNet(cfg)
    rng = jax.random.key(0)
    x0 = jnp.zeros((1, image_size, image_size, 3), jnp.float32)
    variables = jax.jit(
        lambda r: model.init(r, x0, train=False))(rng)

    def timed(fn, n):
        lat = []
        t0 = time.perf_counter()
        for _ in range(n):
            t = time.perf_counter()
            fn()
            lat.append(time.perf_counter() - t)
        wall = time.perf_counter() - t0
        ms = np.array(lat) * 1e3
        return (round(float(np.percentile(ms, 50)), 2),
                round(float(np.percentile(ms, 99)), 2), wall)

    with tempfile.TemporaryDirectory() as d:
        export_model(
            os.path.join(d, "resnet"), "resnet",
            {"params": variables["params"],
             "batch_stats": variables["batch_stats"]},
            version=1,
            config={"stage_sizes": list(cfg.stage_sizes),
                    "num_classes": cfg.num_classes,
                    "stem": cfg.stem},
            input_shape=(image_size, image_size, 3))
        server = grpc_server = client = None
        try:
            server = ModelServer(d, port=0, max_batch_size=batch,
                                 poll_interval_s=3600)
            port = server.start()
            grpc_server, grpc_port = serve_grpc(server.repo, port=0,
                                                max_batch_size=batch)
            client = PredictClient(f"127.0.0.1:{grpc_port}")
            # seeded: bench inputs must be identical run to run, or
            # latency deltas between rounds also carry a data delta
            images = np.random.default_rng(0).random(
                (batch, image_size, image_size, 3), dtype=np.float32)

            client.predict("resnet", images)  # compile
            grpc_p50, grpc_p99, grpc_wall = timed(
                lambda: client.predict("resnet", images), requests)

            # uint8 pixels (the image-client convention): 4× less wire
            # bytes; the server casts to f32 before predict
            images_u8 = (images * 255).astype(np.uint8)
            client.predict("resnet", images_u8)
            u8_p50, u8_p99, u8_wall = timed(
                lambda: client.predict("resnet", images_u8), requests)

            url = f"http://127.0.0.1:{port}/v1/models/resnet:predict"
            payload = json.dumps({"instances": images.tolist()}).encode()

            def rest_predict():
                req = urllib.request.Request(
                    url, data=payload,
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=120) as resp:
                    json.loads(resp.read())

            rest_predict()  # warm
            rest_p50, rest_p99, rest_wall = timed(rest_predict, rest_requests)
        finally:
            if client is not None:
                client.close()
            if grpc_server is not None:
                grpc_server.stop(grace=0)
            if server is not None:
                server.stop()

    n_chips = jax.device_count()
    return {
        "p50_ms": grpc_p50,
        "p99_ms": grpc_p99,
        "qps_per_chip": round(requests * batch / grpc_wall / n_chips, 1),
        "transport": "grpc",
        "uint8_p50_ms": u8_p50,
        "uint8_p99_ms": u8_p99,
        "uint8_qps_per_chip": round(
            requests * batch / u8_wall / n_chips, 1),
        "rest_p50_ms": rest_p50,
        "rest_p99_ms": rest_p99,
        "rest_qps_per_chip": round(
            rest_requests * batch / rest_wall / n_chips, 1),
        "batch": batch,
        "n_chips": n_chips,
    }


# -- runner ------------------------------------------------------------------

def bench_edge_fleet(replicas: int = 3, prefixes: int = 4,
                     repeats: int = 16, page_size: int = 16,
                     burst: int = 48) -> Dict[str, Any]:
    """Fleet-edge routing quality + multiplex cold start (docs/EDGE.md).

    Host-side control-plane numbers (routing, shedding, weight paging
    are CPU work wherever the chips are), adjudicable every round:

    - ``edge_affinity_hit_rate`` vs ``edge_round_robin_hit_rate``:
      fleet prefix-trie hit rate for the SAME repeated-prefix stream
      under both policies — the routing win as one number;
    - ``edge_shed_fraction``: fraction of a 2x-capacity burst shed at
      overload pressure (the shed-before-collapse knee);
    - ``multiplex_cold_start_ms``: wall time to fault a real exported
      model's weights from a versioned store (the "cold-start ms, not
      s" ROADMAP bar).
    """
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.edge.fleet import (
        FleetEdge,
        FleetRequest,
        FleetRouter,
        ReplicaSim,
        SloAdmissionGate,
        fleet_prefix_hits,
        sim_dispatch,
    )
    from kubeflow_tpu.models import MnistCnn
    from kubeflow_tpu.serving.model_store import export_model
    from kubeflow_tpu.serving.multiplex import ModelMultiplexer

    rng = np.random.default_rng(11)
    stream = []
    for p in range(prefixes):
        prefix = np.arange(1000 * p, 1000 * p + 3 * page_size,
                           dtype=np.int32)
        for _ in range(repeats):
            suffix = rng.integers(50000, 60000, size=page_size // 2)
            stream.append((np.concatenate([prefix, suffix])
                           .astype(np.int32), int(prefix.size)))

    def hit_rate(policy: str) -> float:
        sims = {f"r{i}": ReplicaSim(f"r{i}", page_size=page_size)
                for i in range(replicas)}
        router = FleetRouter(page_size=page_size, policy=policy)
        router.sync({n: f"http://{n}" for n in sims})
        edge = FleetEdge(router, SloAdmissionGate(),
                         dispatch=sim_dispatch(sims))
        for prompt, prefix_len in stream:
            code, _ = edge.handle(FleetRequest(prompt=prompt,
                                               prefix_len=prefix_len))
            assert code == 200
        return fleet_prefix_hits(sims) / len(stream)

    affinity_rate = hit_rate("affinity")
    rr_rate = hit_rate("round_robin")

    # overload burst: every replica at near-exhausted pages
    sims = {f"r{i}": ReplicaSim(f"r{i}", page_size=page_size)
            for i in range(replicas)}
    router = FleetRouter(page_size=page_size)
    router.sync({n: f"http://{n}" for n in sims})
    gate = SloAdmissionGate()
    edge = FleetEdge(router, gate, dispatch=sim_dispatch(sims))
    for n in sims:
        gate.observe_snapshot(n, {"pages_total": 100, "pages_free": 5,
                                  "slots": 4, "pending": 0})
    classes = ("interactive", "standard", "batch")
    shed = 0
    for i in range(burst):
        code, _ = edge.handle(FleetRequest(
            prompt=np.arange(2 * page_size),
            headers={"X-Kftpu-Slo-Class": classes[i % len(classes)]}))
        shed += code == 503

    # multiplex cold start against a real store artifact
    model = MnistCnn()
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 28, 28, 1)))["params"]
    with tempfile.TemporaryDirectory() as store_root:
        export_model(os.path.join(store_root, "m0"), "mnist", params,
                     version=1)
        export_model(os.path.join(store_root, "m1"), "mnist", params,
                     version=1)
        mux = ModelMultiplexer(store_root, max_resident=1)
        mux.get("m0")
        mux.get("m1")            # pages m0 out
        cold = mux.get("m0")     # a real re-fault from disk
        assert cold.kind == "mnist"
        snap = mux.snapshot()
        cold_ms = snap["models"]["m0"]["cold_start_ms"]

    return {
        "edge_affinity_hit_rate": round(affinity_rate, 4),
        "edge_round_robin_hit_rate": round(rr_rate, 4),
        "edge_shed_fraction": round(shed / burst, 4),
        "multiplex_cold_start_ms": round(cold_ms, 3),
        "multiplex_loads": snap["multiplex_loads"],
        "replicas": replicas,
        "requests": len(stream),
        "burst": burst,
    }


CONFIGS: Dict[str, Callable[[], Dict[str, Any]]] = {
    "mnist": bench_mnist,
    "resnet50": bench_resnet50,
    "bert": bench_bert,
    "longcontext": bench_longcontext,
    "allreduce": bench_allreduce,
    "serving": bench_serving,
    "decode": bench_decode,
    "decode_engine": bench_decode_engine,
    "edge_fleet": bench_edge_fleet,
}


_PROFILABLE = ("resnet50", "bert", "longcontext", "decode",
               "decode_engine")


def run_all(only: Optional[list] = None,
            profile_dir: Optional[str] = None) -> Dict[str, Dict[str, Any]]:
    """Run every config; one failing config must not kill the rest (its
    row carries ``error``, and :func:`main` turns any such row into a
    non-zero exit).

    ``profile_dir`` captures an XLA trace of the training hot loops into
    ``<profile_dir>/<config>/`` (after timing, so capture overhead never
    contaminates the numbers)."""
    import jax

    out: Dict[str, Dict[str, Any]] = {}
    for name, fn in CONFIGS.items():
        if only and name not in only:
            continue
        try:
            if profile_dir and name in _PROFILABLE:
                out[name] = fn(profile_dir=os.path.join(profile_dir, name))
                out[name]["trace_dir"] = os.path.join(profile_dir, name)
            else:
                out[name] = fn()
            # the artifact must say what actually ran the numbers
            out[name].setdefault("platform", jax.default_backend())
            out[name].setdefault("device_kind",
                                 jax.devices()[0].device_kind)
        except Exception as e:  # noqa: BLE001
            out[name] = {"error": f"{type(e).__name__}: {e}"}
    return out


_REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


def run_all_isolated(only: Optional[list] = None,
                     profile_dir: Optional[str] = None,
                     timeout_s: Optional[float] = None,
                     ) -> Dict[str, Dict[str, Any]]:
    """run_all with each config in its OWN subprocess, strictly one
    after another, under a plain timeout (``KFTPU_BENCH_TIMEOUT_S``,
    default 900).

    One process per chip: this parent imports jax but never initializes
    a backend, so each child opens the chip the previous one released.
    A child that overruns is killed by ``subprocess.run`` and its row
    carries ``error``; the next child still gets the chip (the kernel
    releases a dead process's device handle — checked on the v5e,
    CHANGES.md PR 21). A child that exits non-zero (a config raised)
    still printed its rows: they are kept, ``error`` and all."""
    import subprocess

    if timeout_s is None:
        timeout_s = float(os.environ.get("KFTPU_BENCH_TIMEOUT_S", "900"))
    out: Dict[str, Dict[str, Any]] = {}
    for name in (n for n in CONFIGS if not only or n in only):
        args = [name]
        if profile_dir and name in _PROFILABLE:
            args += ["--profile", profile_dir]
        try:
            proc = subprocess.run(
                [sys.executable, "-m", "kubeflow_tpu.bench.suite", *args],
                capture_output=True, text=True, timeout=timeout_s,
                cwd=_REPO_ROOT)
        except subprocess.TimeoutExpired:
            out[name] = {"error": f"timeout after {timeout_s:.0f}s"}
            continue
        except OSError as e:
            out[name] = {"error": f"{type(e).__name__}: {e}"}
            continue
        try:
            payload = json.loads(proc.stdout.strip().splitlines()[-1])
            out[name] = payload.get(name, payload)
        except (ValueError, IndexError):
            out[name] = {"error": (proc.stderr.strip() or "no output")
                         [-300:]}
    return out


def main() -> None:
    import argparse

    p = argparse.ArgumentParser(description="BASELINE.md bench suite")
    p.add_argument("configs", nargs="*", choices=[*CONFIGS, []],
                   help="subset to run (default: all)")
    p.add_argument("--profile", metavar="DIR", default=None,
                   help="capture XLA profiler traces of the hot loops")
    args = p.parse_args()
    from kubeflow_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    results = run_all(args.configs or None, profile_dir=args.profile)
    print(json.dumps(results))
    failed = sorted(n for n, r in results.items() if "error" in r)
    if failed:
        # the rows above record what raised; the exit code records
        # that something did
        sys.exit(f"bench configs raised: {', '.join(failed)}")


if __name__ == "__main__":
    main()
