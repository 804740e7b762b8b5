"""Test harness config: force an 8-device virtual CPU mesh.

The reference's answer to "how do you test multi-node without a cluster" is
real CI clusters (see SURVEY.md §4); we add the tier it lacks: a virtual
multi-device CPU mesh so every sharding/collective path runs in unit tests.

Tests never use the chip: the platform is pinned to CPU here (as the
tier-1 command's ``JAX_PLATFORMS=cpu`` also does), and the persistent
compile cache stays off — tests that run a launcher's ``main()`` in
process would otherwise point the rest of the session at it.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)


def shard_params(params, mesh):
    """Place a param tree with its tensor-parallel partition specs —
    the multi-chip serving layout. Shared by every sharded-mesh test
    (engine, decode, transformer, speculative) so a change to the
    sharding rules propagates to all of them."""
    from jax.sharding import NamedSharding

    from kubeflow_tpu.models import param_partition_specs
    from kubeflow_tpu.parallel.mesh import shape_aware_spec

    specs = param_partition_specs(params)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(
            x, NamedSharding(mesh, shape_aware_spec(s, x.shape, mesh))),
        params, specs, is_leaf=lambda x: not isinstance(x, dict))


# (n_heads, n_kv_heads, head size) of the dense decode cache's merged K/V
# axis (KH·Dh lanes): one 128-lane tile's worth to 320 lanes, which is no
# multiple of 128. Shared by the decode and engine geometry tests.
KV_GEOMETRIES = {"h4kv4d64": (4, 4, 64), "h6kv3d64": (6, 3, 64),
                 "h15kv5d64": (15, 5, 64), "h2kv2d128": (2, 2, 128)}


def full_forward_greedy(model, params, prompt, n):
    """Oracle with no cache at all: re-run the full forward per token.
    ``prompt``: (B, S) tokens; returns the (B, n) greedy continuation."""
    import jax.numpy as jnp

    tokens = jnp.asarray(prompt, jnp.int32)
    out = []
    for _ in range(n):
        logits = model.apply({"params": params}, tokens)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        out.append(nxt)
        tokens = jnp.concatenate([tokens, nxt[:, None]], axis=1)
    return jnp.stack(out, axis=1)
