"""Sharded training loop primitives: state init, train steps, optimizers.

In the reference, the training loop lives in opaque workload containers
(``tf_cnn_benchmarks`` — see SURVEY.md §3.3 "HOT LOOP"): workers pull params
from parameter servers over gRPC per step. Here the hot loop is a single
pjit-compiled SPMD step over a device mesh; gradient exchange is an XLA
AllReduce over ICI, and TP/SP/EP shardings come from the models' logical
axes (``kubeflow_tpu/parallel/mesh.py``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax.training import train_state
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from kubeflow_tpu.models.transformer import leaf_logical_axes
from kubeflow_tpu.parallel.mesh import (
    AxisRules,
    DEFAULT_RULES,
    logical_to_mesh_axes,
    mesh_context,
    shape_aware_spec,
    spec_for_mesh,
)


class TrainState(train_state.TrainState):
    """TrainState with optional BN statistics (for the ResNet family)."""

    batch_stats: Any = None


def _leaf_axes(path, leaf, pipelined: bool):
    axes = leaf_logical_axes(path, leaf)
    if pipelined and axes:
        # scanned "blocks" leaves: leading layer axis becomes the pipeline
        # stage axis (contiguous L/pp layers per pp rank)
        from kubeflow_tpu.models.transformer import _path_names

        if "blocks" in _path_names(path):
            axes = ("stage",) + tuple(axes[1:])
    return axes


def state_partition_specs(state: Any, rules: AxisRules = DEFAULT_RULES,
                          *, pipelined: bool = False) -> Any:
    """PartitionSpec for every leaf of a (possibly abstract) train state."""

    def spec(path, leaf):
        return logical_to_mesh_axes(_leaf_axes(path, leaf, pipelined), rules)

    return jax.tree_util.tree_map_with_path(spec, state)


def state_shardings(state: Any, mesh: Mesh, rules: AxisRules = DEFAULT_RULES,
                    *, pipelined: bool = False) -> Any:
    def shard(path, leaf):
        spec = spec_for_mesh(
            logical_to_mesh_axes(_leaf_axes(path, leaf, pipelined), rules),
            mesh)
        shape = getattr(leaf, "shape", ())
        return NamedSharding(mesh, shape_aware_spec(spec, shape, mesh))

    return jax.tree_util.tree_map_with_path(shard, state)


def make_optimizer(
    learning_rate: float = 3e-4,
    *,
    warmup_steps: int = 100,
    decay_steps: int = 10_000,
    weight_decay: float = 0.1,
    b1: float = 0.9,
    b2: float = 0.95,
    grad_clip: float = 1.0,
) -> optax.GradientTransformation:
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=learning_rate,
        warmup_steps=warmup_steps,
        decay_steps=max(decay_steps, warmup_steps + 1),
    )
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(schedule, b1=b1, b2=b2, weight_decay=weight_decay),
    )


def create_sharded_state(
    init_fn: Callable[[jax.Array], TrainState],
    rng: jax.Array,
    mesh: Mesh,
    rules: AxisRules = DEFAULT_RULES,
    *,
    pipelined: bool = False,
) -> Tuple[TrainState, Any]:
    """Initialize a TrainState directly into its sharded layout.

    ``init_fn`` is traced abstractly to derive per-leaf shardings, then
    jit-compiled with those as out_shardings so every param lands sharded —
    no host-side full materialization (matters when params exceed one HBM).
    ``pipelined`` shards the scanned layer axis over pp (pipeline stages).
    """
    abstract = jax.eval_shape(init_fn, rng)
    shardings = state_shardings(abstract, mesh, rules, pipelined=pipelined)
    # one-time init compile, consumed immediately — billed by the
    # CompileLedger listener; an AOT fingerprint buys nothing here
    state = jax.jit(init_fn, out_shardings=shardings)(rng)  # tpulint: disable=TPU018
    return state, shardings


def next_token_loss(logits: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    """Causal LM loss: predict tokens[:, 1:] from logits[:, :-1].

    Written as logsumexp minus the target's logit, read from the logits
    themselves: a gather from ``log_softmax``'s result keeps those
    B·(S-1)·V float32 (3.2 GB at 2 x 8192 tokens of a 49k vocabulary)
    alive until the loss value is read, which the compiler schedules
    after the whole backward."""
    logits = logits[:, :-1]
    tgt = tokens[:, 1:]
    picked = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    return jnp.mean(lse - picked.astype(jnp.float32))


def softmax_cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def chunked_next_token_loss(hidden: jnp.ndarray, embed: jnp.ndarray,
                            tokens: jnp.ndarray, *, chunk: int = 4096,
                            softcap: float = 0.0) -> jnp.ndarray:
    """``next_token_loss`` computed from HIDDEN states with the vocab
    projection done per sequence chunk — (B, S, V) f32 logits are never
    materialized, and ``jax.checkpoint`` recomputes each chunk's logits
    in the backward so only (B, chunk, V) lives at once. At seq 65536 /
    vocab 32k the full-logit path alone is ~8.4 GB; chunked, the loss's
    working set is chunk/S of that. The math matches the model's head
    exactly (tied-embedding einsum in activation dtype, f32 softmax,
    optional softcap) so loss values and gradients are parity-testable
    against the unchunked path."""
    B, S, D = hidden.shape
    n = S - 1
    h = hidden[:, :-1]
    tgt = tokens[:, 1:]
    pad = (-n) % chunk
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        tgt = jnp.pad(tgt, ((0, 0), (0, pad)))
    valid = (jnp.arange(n + pad) < n)
    nc = (n + pad) // chunk
    h = h.reshape(B, nc, chunk, D).transpose(1, 0, 2, 3)
    tgt = tgt.reshape(B, nc, chunk).transpose(1, 0, 2)
    valid = valid.reshape(nc, chunk)

    @jax.checkpoint
    def chunk_ll(h_c, t_c, m_c):
        logits = jnp.einsum("bcd,vd->bcv", h_c,
                            embed.astype(h_c.dtype)).astype(jnp.float32)
        if softcap:
            logits = softcap * jnp.tanh(logits / softcap)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, t_c[..., None], axis=-1)[..., 0]
        return jnp.sum(ll * m_c[None, :])

    def body(acc, xs):
        return acc + chunk_ll(*xs), None

    total, _ = jax.lax.scan(body, jnp.float32(0.0), (h, tgt, valid))
    return -total / (B * n)


def make_lm_train_step(
    mesh: Mesh,
    rules: AxisRules = DEFAULT_RULES,
    *,
    moe_aux_weight: float = 0.01,
    donate: bool = True,
    loss_chunk: Optional[int] = None,
    logits_softcap: float = 0.0,
):
    """Build the jitted SPMD LM train step: (state, tokens) -> (state, metrics).

    ``loss_chunk``: long-context mode — ``state.apply_fn`` must return
    post-final-norm HIDDEN states (``Transformer(config,
    return_hidden=True)``) and the loss projects to vocab per
    ``loss_chunk``-token chunk (``chunked_next_token_loss``), so the
    full (B, S, V) logit tensor never exists. ALWAYS forward the
    model's ``config.logits_softcap`` here — the chunked loss re-applies
    the head's softcap itself (the hidden-states model never applies
    it), and a mismatch silently trains a different objective than the
    full-logits path."""
    batch_spec = spec_for_mesh(logical_to_mesh_axes(("batch", "seq"), rules), mesh)

    def step(state: TrainState, tokens: jnp.ndarray):
        tokens = jax.lax.with_sharding_constraint(tokens, batch_spec)

        def loss_fn(params):
            out, mut = state.apply_fn(
                {"params": params}, tokens, mutable=["losses"]
            )
            if loss_chunk:
                loss = chunked_next_token_loss(
                    out, params["token_embed"], tokens,
                    chunk=loss_chunk, softcap=logits_softcap)
            else:
                loss = next_token_loss(out, tokens)
            aux = sum(
                jnp.sum(v) for v in jax.tree_util.tree_leaves(mut)
            ) if mut else 0.0
            return loss + moe_aux_weight * aux, loss

        grads, lm_loss = jax.grad(loss_fn, has_aux=True)(state.params)
        new_state = state.apply_gradients(grads=grads)
        metrics = {
            "loss": lm_loss,
            "grad_norm": optax.global_norm(grads),
            "step": new_state.step,
        }
        return new_state, metrics

    def run(state, tokens):
        with mesh_context(mesh):
            return jitted(state, tokens)

    jitted = jax.jit(step, donate_argnums=(0,) if donate else ())
    return _ledgered(run, jitted, mesh)


def _ledgered(run, jitted, mesh):
    """Expose a step runner's AOT surfaces: ``run.jitted`` (bench
    roofline / HLO inspection) and ``run.aot_compile(ledger, *args)``,
    which lands the step's compile on a ``CompileLedger`` — HLO
    fingerprint, memory budget, and the ``kftpu_compile_seconds``
    series — before the step loop starts, so startup compile cost is
    attributed instead of billed as badput."""
    def aot_compile(ledger, *example_args, module: str = "train.step"):
        with mesh_context(mesh):
            return ledger.timed_compile(jitted, *example_args,
                                        module=module)
    run.jitted = jitted
    run.aot_compile = aot_compile
    return run


def masked_lm_loss(logits: jnp.ndarray, labels: jnp.ndarray,
                   weights: jnp.ndarray) -> jnp.ndarray:
    """MLM objective: cross-entropy at masked positions only."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(jnp.sum(weights), 1.0)
    return -jnp.sum(ll * weights) / denom


def make_mlm_train_step(
    mesh: Mesh,
    rules: AxisRules = DEFAULT_RULES,
    *,
    donate: bool = True,
):
    """Jitted SPMD masked-LM step: (state, tokens, labels, weights) ->
    (state, metrics). ``tokens`` are the corrupted inputs; ``labels`` the
    originals; ``weights`` mark masked positions."""
    batch_spec = spec_for_mesh(logical_to_mesh_axes(("batch", "seq"), rules), mesh)

    def step(state: TrainState, tokens, labels, weights):
        tokens = jax.lax.with_sharding_constraint(tokens, batch_spec)
        labels = jax.lax.with_sharding_constraint(labels, batch_spec)
        weights = jax.lax.with_sharding_constraint(weights, batch_spec)

        def loss_fn(params):
            logits = state.apply_fn({"params": params}, tokens)
            return masked_lm_loss(logits, labels, weights)

        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        new_state = state.apply_gradients(grads=grads)
        metrics = {
            "loss": loss,
            "grad_norm": optax.global_norm(grads),
            "step": new_state.step,
        }
        return new_state, metrics

    def run(state, tokens, labels, weights):
        with mesh_context(mesh):
            return jitted(state, tokens, labels, weights)

    jitted = jax.jit(step, donate_argnums=(0,) if donate else ())
    return _ledgered(run, jitted, mesh)


def make_pipelined_lm_train_step(
    model,
    mesh: Mesh,
    *,
    n_microbatches: int,
    rules: AxisRules = DEFAULT_RULES,
    donate: bool = True,
):
    """LM train step with the block stack pipelined over the ``pp`` axis.

    Composes pp with dp/tp: stages are manual over pp
    (``kubeflow_tpu/parallel/pipeline.py``); dp/tp sharding inside each
    stage stays auto. State must be created with ``pipelined=True`` so the
    scanned layer axis lands stage-sharded. MoE auxiliary losses are not
    collected on this path (the pipeline applies blocks functionally).
    """
    from kubeflow_tpu.parallel.pipeline import make_pipelined_lm_forward

    fwd = make_pipelined_lm_forward(model, mesh, n_microbatches=n_microbatches)
    batch_spec = spec_for_mesh(logical_to_mesh_axes(("batch", "seq"), rules), mesh)

    def step(state: TrainState, tokens: jnp.ndarray):
        tokens = jax.lax.with_sharding_constraint(tokens, batch_spec)

        def loss_fn(params):
            return next_token_loss(fwd(params, tokens), tokens)

        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        new_state = state.apply_gradients(grads=grads)
        return new_state, {
            "loss": loss,
            "grad_norm": optax.global_norm(grads),
            "step": new_state.step,
        }

    jitted = jax.jit(step, donate_argnums=(0,) if donate else ())

    def run(state, tokens):
        with mesh_context(mesh):
            return jitted(state, tokens)

    return _ledgered(run, jitted, mesh)


def make_image_train_step(
    mesh: Mesh,
    rules: AxisRules = DEFAULT_RULES,
    *,
    donate: bool = True,
):
    """Jitted SPMD classifier train step with BN-stat updates (ResNet path)."""
    batch_spec = spec_for_mesh(
        logical_to_mesh_axes(("batch", None, None, None), rules), mesh)
    label_spec = spec_for_mesh(logical_to_mesh_axes(("batch",), rules), mesh)

    def step(state: TrainState, images: jnp.ndarray, labels: jnp.ndarray):
        images = jax.lax.with_sharding_constraint(images, batch_spec)
        labels = jax.lax.with_sharding_constraint(labels, label_spec)

        def loss_fn(params):
            variables = {"params": params}
            if state.batch_stats is not None:
                variables["batch_stats"] = state.batch_stats
                logits, mut = state.apply_fn(
                    variables, images, train=True, mutable=["batch_stats"]
                )
                new_stats = mut["batch_stats"]
            else:
                logits = state.apply_fn(variables, images, train=True)
                new_stats = None
            loss = softmax_cross_entropy(logits, labels)
            acc = jnp.mean(jnp.argmax(logits, -1) == labels)
            return loss, (new_stats, acc)

        (loss, (new_stats, acc)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(state.params)
        new_state = state.apply_gradients(grads=grads)
        if new_stats is not None:
            new_state = new_state.replace(batch_stats=new_stats)
        return new_state, {"loss": loss, "accuracy": acc, "step": new_state.step}

    jitted = jax.jit(step, donate_argnums=(0,) if donate else ())

    def run(state, images, labels):
        with mesh_context(mesh):
            return jitted(state, images, labels)

    return _ledgered(run, jitted, mesh)
