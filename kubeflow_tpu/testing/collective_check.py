"""Workload for the multi-process tier: initialize from the operator env
contract, run a psum over all processes, verify, print one JSON line.

This is the ``simple_tfjob_tests`` analogue (the smoke workload the
reference's E2E DAG runs, ``testing/workflows/components/workflows.
libsonnet:187-330``) for the SPMD path: success means the coordinator
bootstrap (hard part (c)) and cross-process collectives both work.
"""

from __future__ import annotations

import json
import sys


def main() -> int:
    import jax

    # several ranks share this host, and a chip belongs to one process
    # at a time: every rank runs on the CPU backend and contributes
    # exactly its one virtual CPU device
    jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp

    from kubeflow_tpu.parallel import distributed as dist

    penv = dist.from_env()
    dist.initialize()  # reads the same env the operator injects

    n = jax.process_count()
    assert n == penv.num_processes, (n, penv.num_processes)
    devices = jax.devices()

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(devices, ("dp",))
    # each process contributes (process_id + 1); psum must see them all
    local = jnp.asarray([float(penv.process_id + 1)])
    arr = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("dp")), local, (n,))

    @jax.jit
    def total(x):
        return x.sum()

    got = float(total(arr))
    want = n * (n + 1) / 2.0
    ok = abs(got - want) < 1e-6
    print(json.dumps({
        "process_id": penv.process_id,
        "processes": n,
        "devices": len(devices),
        "psum": got,
        "expected": want,
        "ok": ok,
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
