"""Multi-process multislice workload: the DCN mesh under REAL
``jax.distributed``.

Each process is one slice's host (the TpuJob operator's ``slices: N``
deployment: per-pod ``MEGASCALE_SLICE_ID`` + the coordinator env
contract, ``kubeflow_tpu/operators/tpujob.py``). The single-process
``dryrun_multislice`` (``__graft_entry__.py``) proves the mesh math;
this proves the *cross-process* half the operator actually ships:
coordinator bootstrap, slice-major global device order
(``kubeflow_tpu/parallel/mesh.py`` dcn axis contract), and a compiled
train step whose collectives span processes.

Prints one JSON line with the per-step losses; the harness asserts all
ranks agree and that the loss matches the single-process oracle.
"""

from __future__ import annotations

import json
import sys


def main() -> int:
    import jax

    # several ranks share this host, and a chip belongs to one process
    # at a time: each rank exposes only its virtual CPU devices
    jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.models import Transformer, TransformerConfig
    from kubeflow_tpu.parallel import distributed as dist
    from kubeflow_tpu.train import (
        TrainState,
        create_sharded_state,
        make_lm_train_step,
        make_optimizer,
    )

    penv = dist.from_env()
    dist.initialize()  # the operator's env contract

    n_procs = jax.process_count()
    devs = jax.devices()
    # the operator assigns ranks slice-major, so jax's process-major
    # global device order IS slice-major — multislice_mesh's contract
    mesh = dist.multislice_mesh(penv, tp=2)
    dcn, dp, pp, tp = mesh.devices.shape

    config = TransformerConfig(
        vocab_size=128, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=32, dtype=jnp.float32, remat=False)
    model = Transformer(config)
    # identical on every rank: jit treats host-local numpy as replicated
    tokens = np.asarray(jax.random.randint(
        jax.random.key(7), (2 * dcn * dp, 16), 0, config.vocab_size))
    tx = make_optimizer(1e-3, warmup_steps=1, decay_steps=10)

    def init_fn(rng):
        params = model.init(rng, tokens)["params"]
        return TrainState.create(apply_fn=model.apply, params=params,
                                 tx=tx)

    state, _ = create_sharded_state(init_fn, jax.random.key(0), mesh)
    step = make_lm_train_step(mesh)
    losses = []
    for _ in range(2):
        state, metrics = step(state, tokens)
        # the loss is replicated; every process can read it
        losses.append(float(metrics["loss"]))
    ok = all(l == l for l in losses)  # NaN guard
    print(json.dumps({
        "process_id": penv.process_id,
        "slice_id": penv.slice_id,
        "processes": n_procs,
        "devices": len(devs),
        "mesh": {"dcn": dcn, "dp": dp, "pp": pp, "tp": tp},
        "losses": [round(l, 6) for l in losses],
        "ok": ok,
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
