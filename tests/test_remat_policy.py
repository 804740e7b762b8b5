"""What a rematerialised block keeps for its backward
(``models/transformer.py:remat_block``): the flash forward's output and
log-sum-exp, named in ``ops/attention.py:_flash_vjp_fwd``, so that a
layer's backward runs the one fused dQ/dK/dV kernel and not the forward
kernel again; and
nothing where no flash kernel ran, nor in a served program."""

import collections
import contextlib
import dataclasses
import hashlib

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import Transformer, tiny_config
from kubeflow_tpu.models import bert, transformer
from kubeflow_tpu.parallel import MeshConfig, create_mesh
from kubeflow_tpu.parallel.mesh import (
    mesh_context,
    record_kernel_placements,
)

LAYERS = 2


def plain_remat():
    """The parent's spelling: the whole block recomputed."""
    return nn.remat(transformer.Block, prevent_cse=False)


def kernels(jaxpr, found=None):
    """Kernel function name → number of ``pallas_call``s, through every
    nested jaxpr (scan bodies, remat, shard_map)."""
    found = collections.Counter() if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["jaxpr"].debug_info.func_name] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            kernels(sub, found)
    return found


def _lm(remat, scan_layers):
    config = tiny_config(attention_impl="flash", n_kv_heads=4,
                         n_layers=LAYERS, remat=remat,
                         scan_layers=scan_layers)
    model = Transformer(config)
    tokens = jax.random.randint(jax.random.key(3), (4, 32), 0,
                                config.vocab_size)
    params = model.init(jax.random.key(0), tokens)["params"]
    return params, lambda p: jnp.mean(
        model.apply({"params": p}, tokens) ** 2)


def _bert(remat, scan_layers):
    config = dataclasses.replace(
        bert.bert_tiny(), attention_impl="flash", n_layers=LAYERS,
        remat=remat, scan_layers=scan_layers)
    model = bert.Bert(config)
    tokens = jax.random.randint(jax.random.key(3), (2, 32), 0,
                                config.vocab_size)
    lengths = jnp.array([32, 19], jnp.int32)
    params = model.init(jax.random.key(0), tokens,
                        seq_lengths=lengths)["params"]
    keep = (jnp.arange(32)[None, :] < lengths[:, None])[..., None]

    def loss(p):
        logits = model.apply({"params": p}, tokens, seq_lengths=lengths)
        return jnp.sum(jnp.where(keep, logits, 0.0) ** 2) / keep.sum()

    return params, loss


# name → (builder, scan_layers, mesh or None)
CASES = {
    "lm_scanned": (_lm, True, None),
    "lm_unrolled": (_lm, False, None),
    "bert_kv_len": (_bert, True, None),
    "lm_tp2_shard_kernel": (_lm, True, MeshConfig(tp=2)),
}


@pytest.mark.parametrize("case", list(CASES))
def test_remat_backward_runs_the_forward_kernel_once_a_layer(case,
                                                             monkeypatch):
    build, scan_layers, mesh_config = CASES[case]
    on_mesh = contextlib.nullcontext if mesh_config is None else (
        lambda: mesh_context(create_mesh(mesh_config,
                                         devices=jax.devices()[:2])))
    # a scanned stack holds one layer's kernels in each scan body
    a_layer = 1 if scan_layers else LAYERS

    def run(remat):
        params, loss = build(remat, scan_layers)
        fn = jax.value_and_grad(loss)
        with on_mesh():
            count = kernels(jax.make_jaxpr(fn)(params).jaxpr)
            return count, jax.jit(fn)(params)

    with record_kernel_placements() as placed:
        count, (got_l, got_g) = run(True)
    assert count == {"_flash_fwd_kernel": a_layer,
                     "_flash_bwd_fused_kernel": a_layer}
    if mesh_config is not None:
        # the kernels sat inside shard_kernel's shard_map, heads split
        assert placed == [{"kernel": "flash_attention", "devices": 2,
                           "split": {"heads": 2}, "dropped": []}]

    def same(a, b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # the parent's program ran the forward kernel twice a layer, and
    # handed the backward the very values this one keeps
    for module in (transformer, bert):
        monkeypatch.setattr(module, "remat_block", plain_remat)
    count, (want_l, want_g) = run(True)
    assert count["_flash_fwd_kernel"] == 2 * a_layer
    assert count["_flash_bwd_fused_kernel"] == a_layer
    same(got_l, want_l)
    jax.tree_util.tree_map(same, got_g, want_g)

    # and nothing is lost against keeping every activation
    count, (want_l, want_g) = run(False)
    assert count["_flash_fwd_kernel"] == a_layer
    same(got_l, want_l)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0, atol=1e-7),
        got_g, want_g)


def _digest(lowered):
    """A lowered program's text, as a hash: a failure shows two short
    strings, not two programs."""
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]


@pytest.mark.parametrize("impl", ["dense", "blockwise"])
def test_policy_keeps_nothing_where_no_flash_kernel_ran(impl, monkeypatch):
    """No value carries a name, so the train step's program is the one
    plain ``nn.remat`` gives."""
    config = tiny_config(attention_impl=impl, n_layers=LAYERS, remat=True)
    model = Transformer(config)
    tokens = jnp.zeros((2, 32), jnp.int32)
    params = model.init(jax.random.key(0), tokens)["params"]

    def step(p):
        return jax.value_and_grad(lambda p: jnp.mean(
            model.apply({"params": p}, tokens) ** 2))(p)

    got = _digest(jax.jit(step).lower(params))
    monkeypatch.setattr(transformer, "remat_block", plain_remat)
    assert got == _digest(jax.jit(step).lower(params))


# sha256 (first 16 hex) of each served program's lowered text at the toy
# shapes below, read with this test on the parent commit (0db8dfe). A PR
# that changes a served program on purpose replaces them.
SERVED = {
    "_step": "c9103e30b703fc01",
    "_step_greedy": "d84391848943836b",
    "_prefill": "8c53257ef002a227",
    "_prefill_batch": "0476105c9bfaf894",
}


def test_served_programs_lower_to_the_parents_text():
    """A served model takes no gradient and is built with
    ``decode=True``: it is never wrapped by ``remat_block``, and a name
    is given in the kernel's gradient rule alone, so the engine's step
    and prefill programs are the parent's, letter for letter."""
    from kubeflow_tpu.serving.engine import DecodeEngine

    config = tiny_config(attention_impl="flash", n_layers=LAYERS,
                         remat=True, max_seq_len=64)
    params = Transformer(config).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    eng = DecodeEngine(config, params, slots=2, steps_per_sync=2,
                       autostart=False)
    try:
        kv = eng._kv
        vec_i = jnp.zeros((2,), jnp.int32)
        ones_f = jnp.ones((2,), jnp.float32)
        one_i, one_f = jnp.int32(0), jnp.float32(1.0)
        got = {
            "_step": _digest(eng._step.lower(
                params, kv.cache, vec_i, vec_i, vec_i, ones_f, vec_i,
                ones_f)),
            "_step_greedy": _digest(eng._step_greedy.lower(
                params, kv.cache, vec_i)),
            "_prefill": _digest(kv._prefill.lower(
                params, jnp.zeros((1, 32), jnp.int32), jnp.int32(20),
                one_f, one_i, one_f, one_i, one_i)),
            "_prefill_batch": _digest(kv._prefill_batch.lower(
                params, jnp.zeros((2, 32), jnp.int32),
                jnp.array([20, 32], jnp.int32), ones_f, vec_i, ones_f,
                vec_i)),
        }
    finally:
        eng.close()
    assert got == SERVED
