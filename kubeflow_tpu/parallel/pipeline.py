"""Pipeline parallelism: SPMD microbatch pipeline over the ``pp`` mesh axis.

The reference has no model parallelism of any kind (SURVEY.md §2c: PP =
"ABSENT"). This module adds GPipe-style pipelining the TPU-native way: not
per-stage processes with send/recv (the GPU framework shape), but a single
SPMD program under partial-manual ``shard_map`` — manual over ``pp`` only,
so every device runs the same tick loop and activations move one
``ppermute`` hop per tick (XLA lowers the hop onto the ICI link between
neighbouring stages), while dp/tp stay auto-sharded inside each stage (tp
constraints in the block code keep working).

Schedule (one stage per pp-rank): tick t: stage 0 ingests microbatch t
(while t < M); every stage applies its layers to its current activation;
activations shift right; stage S-1's output for microbatch t emerges at
tick t + S - 1. Forward+backward flow through ``lax.scan`` autodiff — the
classic GPipe bubble (S-1)/M, amortized by more microbatches.

Stage weights are the scanned transformer block stack
(``kubeflow_tpu/models/transformer.py`` stacks blocks with a leading layer
axis) reshaped so each pp-rank holds ``n_layers / pp`` contiguous layers —
the reshape happens inside jit, so the same checkpoint loads pipelined or
not.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from kubeflow_tpu import compat

# stage_fn(stage_params, x) -> y; applies one stage's layers to a microbatch
StageFn = Callable[[Any, jnp.ndarray], jnp.ndarray]


def _axis_size(mesh: Mesh, axis: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape))[axis]


def split_stages(stacked_params: Any, n_stages: int) -> Any:
    """Reshape leading layer axis L -> (n_stages, L/n_stages) on every leaf."""

    def reshape(leaf):
        L = leaf.shape[0]
        if L % n_stages:
            raise ValueError(f"layers {L} not divisible by stages {n_stages}")
        return leaf.reshape(n_stages, L // n_stages, *leaf.shape[1:])

    return jax.tree_util.tree_map(reshape, stacked_params)


def merge_stages(staged_params: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda leaf: leaf.reshape(-1, *leaf.shape[2:]), staged_params
    )


def pipeline_apply(
    stage_fn: StageFn,
    staged_params: Any,
    microbatches: jnp.ndarray,
    *,
    mesh: Mesh,
    axis: str = "pp",
) -> jnp.ndarray:
    """Run microbatches through the stage pipeline; returns stacked outputs.

    ``staged_params`` leaves have leading dim = pp size (sharded over
    ``axis``); ``microbatches`` is (M, mb, ...), replicated along ``axis``
    (dp/tp sharding of the inner dims is orthogonal — those axes stay auto).
    Output is (M, mb, ...) replicated along ``axis``: the last stage's
    results are broadcast back with one ``psum``-sized hop so the loss code
    after the pipeline is ordinary SPMD.
    """
    n_stages = _axis_size(mesh, axis)
    M = microbatches.shape[0]
    total = M + n_stages - 1
    perm = [(j, (j + 1) % n_stages) for j in range(n_stages)]

    def per_device(staged_local, mb_local):
        # staged_local: (1, L/S, ...) this rank's stage; mb_local (M, mb, ...)
        params_me = jax.tree_util.tree_map(lambda l: l[0], staged_local)
        rank = jax.lax.axis_index(axis)
        # pvary: carries become rank-dependent after the first tick, so their
        # init must already be typed varying-over-pp for the scan carry
        def _vary(x):
            return compat.pvary(x, (axis,))

        state = _vary(jnp.zeros(mb_local.shape[1:], mb_local.dtype))
        out = _vary(jnp.zeros_like(mb_local))

        def tick(carry, t):
            state, out = carry
            # stage 0 ingests microbatch t (clamped; ticks t >= M recompute
            # the last microbatch on stage 0 — wasted flops, not wrong,
            # since only the last stage's writes reach the output)
            feed = mb_local[jnp.minimum(t, M - 1)]
            x = jnp.where(rank == 0, feed, state)
            y = stage_fn(params_me, x)
            done_idx = t - (n_stages - 1)
            write = jnp.logical_and(rank == n_stages - 1, done_idx >= 0)
            out = jnp.where(
                write,
                jax.lax.dynamic_update_index_in_dim(
                    out, y, jnp.maximum(done_idx, 0), 0
                ),
                out,
            )
            state = jax.lax.ppermute(y, axis, perm)
            return (state, out), None

        (_, out), _ = jax.lax.scan(tick, (state, out), jnp.arange(total))
        # broadcast the last stage's outputs to every rank
        mask = (rank == n_stages - 1).astype(out.dtype)
        return jax.lax.psum(out * mask, axis)

    fn = compat.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        axis_names={axis},  # manual over pp only; dp/tp stay auto
    )
    return fn(staged_params, microbatches)


# ---------------------------------------------------------------------------
# Pipelined transformer LM forward
# ---------------------------------------------------------------------------


def make_pipelined_lm_forward(
    model,
    mesh: Mesh,
    *,
    n_microbatches: int,
    axis: str = "pp",
):
    """Forward fn (params, tokens) -> logits with the block stack pipelined.

    Embedding and the final norm/unembed run replicated on every pp rank
    (cheap relative to the block stack); the scanned block stack is staged
    over ``axis``. Requires ``scan_layers=True`` params (the stacked
    "blocks" subtree).
    """
    from kubeflow_tpu.models.transformer import (  # local import: no cycle
        Block,
        RMSNorm,
        remat_block,
        rope_tables,
    )

    n_stages = _axis_size(mesh, axis)
    c = model.config
    # honor config.remat here too — pipelining targets exactly the
    # large-model regime where un-rematted activations would blow HBM
    block_cls = remat_block() if c.remat else Block
    block = block_cls(c)
    final_norm = RMSNorm(param_dtype=c.param_dtype)

    def forward(params, tokens):
        B, S = tokens.shape
        if B % n_microbatches:
            raise ValueError(
                f"batch {B} not divisible by microbatches {n_microbatches}"
            )
        embed = params["token_embed"].astype(c.dtype)
        x = jnp.take(embed, tokens, axis=0)
        sin, cos = rope_tables(S, c.head_dim, c.rope_theta)

        staged = split_stages(params["blocks"], n_stages)

        def stage_fn(stage_params, x):
            def layer(x, layer_params):
                y, _ = block.apply({"params": layer_params}, x, (sin, cos))
                return y, None

            x, _ = jax.lax.scan(layer, x, stage_params)
            return x

        mbs = x.reshape(n_microbatches, B // n_microbatches, S, c.d_model)
        y = pipeline_apply(stage_fn, staged, mbs, mesh=mesh, axis=axis)
        x = y.reshape(B, S, c.d_model)

        x = final_norm.apply({"params": params["final_norm"]}, x)
        logits = jnp.einsum("bsd,vd->bsv", x, embed).astype(jnp.float32)
        if c.logits_softcap:
            logits = c.logits_softcap * jnp.tanh(logits / c.logits_softcap)
        return logits

    return forward
