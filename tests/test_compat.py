"""The jax SPMD surface behind ``kubeflow_tpu.compat``, exercised on
the installed jax over the virtual CPU mesh: full- and partial-manual
``shard_map`` (eager, jit, grad), the varying-axes cast, named-axis
helpers, the ambient mesh. No stand-ins — every test runs the real
API the shims forward to.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from kubeflow_tpu import compat


@pytest.fixture(scope="module")
def mesh_dp_tp():
    devs = np.array(jax.devices()[:4]).reshape(2, 2)
    return Mesh(devs, ("dp", "tp"))


@pytest.fixture(scope="module")
def mesh_dp_pp_tp():
    devs = np.array(jax.devices()[:8]).reshape(2, 2, 2)
    return Mesh(devs, ("dp", "pp", "tp"))


def test_installed_jax_is_the_pinned_surface():
    """pyproject pins jax>=0.9; the shims have no older branch, so a
    downgrade must fail here, loudly, not inside a traced region."""
    major, minor = (int(x) for x in jax.__version__.split(".")[:2])
    assert (major, minor) >= (0, 9)
    for owner, name in ((jax, "shard_map"), (jax.lax, "pcast"),
                        (jax.lax, "axis_size"),
                        (jax.sharding, "get_abstract_mesh"),
                        (jax.sharding, "set_mesh")):
        assert hasattr(owner, name), name


# -- shard_map ---------------------------------------------------------------


class TestShardMap:
    def test_full_manual_psum(self, mesh_dp_tp):
        def summed(x):
            return jax.lax.psum(x, "tp")

        fn = compat.shard_map(summed, mesh=mesh_dp_tp,
                              in_specs=(P(None, "tp"),), out_specs=P())
        x = jnp.arange(16.0).reshape(2, 8)
        out = fn(x)
        # every tp shard returns the sum of its row halves
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(x[:, :4] + x[:, 4:]))

    def test_full_manual_axis_index_and_ppermute(self, mesh_dp_tp):
        def rotate(x):
            n = compat.axis_size("tp")
            perm = [(j, (j + 1) % n) for j in range(n)]
            return jax.lax.ppermute(x, "tp", perm)

        fn = compat.shard_map(rotate, mesh=mesh_dp_tp,
                              in_specs=(P(None, "tp"),),
                              out_specs=P(None, "tp"))
        x = jnp.arange(8.0).reshape(2, 4)
        out = np.asarray(fn(x))
        # ring rotation by one hop swaps the two tp shards
        np.testing.assert_allclose(out[:, 2:], np.asarray(x)[:, :2])
        np.testing.assert_allclose(out[:, :2], np.asarray(x)[:, 2:])

    def test_partial_manual_eager_jit_grad(self, mesh_dp_pp_tp):
        """axis_names={pp} on a 3-axis mesh — the exact pipeline shape."""
        def stagewise(x):
            rank = jax.lax.axis_index("pp")
            return jax.lax.psum(x * (rank + 1), "pp")

        fn = compat.shard_map(stagewise, mesh=mesh_dp_pp_tp,
                              in_specs=(P("pp"),), out_specs=P(),
                              axis_names={"pp"})
        x = jnp.arange(4.0).reshape(2, 2)
        # per-rank (1, 2) shards, psum over pp, P() out: global (1, 2)
        expect = np.asarray(x[0] * 1 + x[1] * 2)[None]
        np.testing.assert_allclose(np.asarray(fn(x)), expect)
        np.testing.assert_allclose(np.asarray(jax.jit(fn)(x)), expect)
        g = jax.grad(lambda v: fn(v).sum())(x)
        np.testing.assert_allclose(np.asarray(g),
                                   [[1.0, 1.0], [2.0, 2.0]])

    def test_partial_manual_binds_only_its_axes(self, mesh_dp_pp_tp):
        """Inside an ``axis_names={pp}`` region only pp is a bound named
        axis; dp and tp stay with the partitioner."""
        seen = {}

        def body(x):
            seen["bound"] = compat.bound_axes(("dp", "pp", "tp"))
            return x * 2.0

        fn = compat.shard_map(body, mesh=mesh_dp_pp_tp,
                              in_specs=(P("pp"),), out_specs=P("pp"),
                              axis_names={"pp"})
        np.testing.assert_allclose(
            np.asarray(jax.jit(fn)(jnp.ones((4, 4)))), 2.0)
        assert seen["bound"] == {"pp"}

    def test_partial_manual_specs_may_not_name_auto_axes(
            self, mesh_dp_pp_tp):
        """jax itself refuses a spec that shards over an axis outside
        ``axis_names`` — the four platform call sites rely on it."""
        fn = compat.shard_map(lambda x: x, mesh=mesh_dp_pp_tp,
                              in_specs=(P("dp"),), out_specs=P("dp"),
                              axis_names={"pp"})
        with pytest.raises(ValueError, match="manual"):
            fn(jnp.ones((4, 4)))

    def test_check_vma_rejects_an_unreduced_replicated_output(
            self, mesh_dp_tp):
        """The vma checker is on by default: returning a tp-varying
        value under a replicated out_spec is a type error."""
        fn = compat.shard_map(lambda x: x, mesh=mesh_dp_tp,
                              in_specs=(P(None, "tp"),), out_specs=P())
        with pytest.raises(ValueError, match="vary|varying"):
            fn(jnp.ones((2, 4)))

    def test_check_vma_false_is_forwarded(self, mesh_dp_tp):
        """...and ``check_vma=False`` reaches jax: the same program
        then traces (each shard's value passes through unchecked)."""
        fn = compat.shard_map(lambda x: x, mesh=mesh_dp_tp,
                              in_specs=(P(None, "tp"),), out_specs=P(),
                              check_vma=False)
        assert fn(jnp.ones((2, 4))).shape == (2, 2)

    def test_ring_causal_skip_differentiates(self, mesh_dp_tp):
        """grad through ``lax.cond`` inside a manual region — the ring
        attention causal-skip shape the vma checker must accept."""
        def body(x):
            idx = jax.lax.axis_index("tp")
            y = jax.lax.cond(idx > 0, lambda v: v * 3.0, lambda v: v, x)
            return jax.lax.psum(y, "tp")

        fn = compat.shard_map(body, mesh=mesh_dp_tp,
                              in_specs=(P(None, "tp"),), out_specs=P())
        g = jax.grad(lambda v: fn(v).sum())(jnp.ones((2, 4)))
        np.testing.assert_allclose(np.asarray(g),
                                   [[1.0, 1.0, 3.0, 3.0]] * 2)


# -- the varying-axes cast ---------------------------------------------------


class TestPvary:
    def test_already_varying_operand_is_tolerated(self, mesh_dp_tp):
        """The seed failure: a tp-sharded input already varies over tp,
        and jax 0.9's pcast refuses to re-vary it — the shim skips."""
        def body(x):
            assert jax.typeof(x).vma == frozenset({"tp"})
            return compat.pvary(x, ("tp",)) * 2.0

        fn = compat.shard_map(body, mesh=mesh_dp_tp,
                              in_specs=(P(None, "tp"),),
                              out_specs=P(None, "tp"))
        np.testing.assert_allclose(np.asarray(fn(jnp.ones((2, 4)))), 2.0)

    def test_replicated_value_becomes_varying(self, mesh_dp_tp):
        seen = {}

        def body(x):
            z = compat.pvary(jnp.zeros(()), ("tp",))
            seen["vma"] = jax.typeof(z).vma
            return x + z

        fn = compat.shard_map(body, mesh=mesh_dp_tp,
                              in_specs=(P(None, "tp"),),
                              out_specs=P(None, "tp"))
        fn(jnp.ones((2, 4)))
        assert seen["vma"] == frozenset({"tp"})

    def test_only_missing_axes_are_cast(self, mesh_dp_tp):
        seen = {}

        def body(x):
            z = compat.pvary(x, ("dp", "tp"))
            seen["vma"] = jax.typeof(z).vma
            return z

        fn = compat.shard_map(body, mesh=mesh_dp_tp,
                              in_specs=(P(None, "tp"),),
                              out_specs=P("dp", "tp"))
        fn(jnp.ones((2, 4)))
        assert seen["vma"] == frozenset({"dp", "tp"})

    def test_scan_carry_needs_it(self, mesh_dp_tp):
        """Why the shim exists: a replicated init carried through a scan
        whose body makes it varying is a vma type error without the
        cast, and traces with it (``parallel/pipeline.py``)."""
        def make(cast):
            def body(x):
                init = jnp.zeros(x.shape, x.dtype)
                if cast:
                    init = compat.pvary(init, ("tp",))
                out, _ = jax.lax.scan(lambda c, _: (c + x, None), init,
                                      None, length=3)
                return out

            return compat.shard_map(body, mesh=mesh_dp_tp,
                                    in_specs=(P(None, "tp"),),
                                    out_specs=P(None, "tp"))

        with pytest.raises(TypeError):
            make(False)(jnp.ones((2, 4)))
        np.testing.assert_allclose(
            np.asarray(make(True)(jnp.ones((2, 4)))), 3.0)

    def test_outside_region_is_identity(self):
        x = jnp.ones((3,))
        np.testing.assert_allclose(np.asarray(compat.pvary(x, ())), 1.0)


# -- named-axis helpers ------------------------------------------------------


class TestAxisHelpers:
    def test_axis_size_inside_region_is_a_python_int(self, mesh_dp_tp):
        sizes = {}

        def body(x):
            sizes["n"] = compat.axis_size("tp")
            return x

        fn = compat.shard_map(body, mesh=mesh_dp_tp,
                              in_specs=(P(None, "tp"),),
                              out_specs=P(None, "tp"))
        fn(jnp.ones((2, 4)))
        assert sizes["n"] == 2 and isinstance(sizes["n"], int)

    def test_bound_axes_inside_and_outside(self, mesh_dp_tp):
        assert compat.bound_axes(("dp", "tp")) == set()
        seen = {}

        def body(x):
            seen["bound"] = compat.bound_axes(("dp", "tp", "nope"))
            return x

        fn = compat.shard_map(body, mesh=mesh_dp_tp,
                              in_specs=(P(None, "tp"),),
                              out_specs=P(None, "tp"))
        fn(jnp.ones((2, 4)))
        # full-manual region: both mesh axes bound, unknown names not
        assert seen["bound"] == {"dp", "tp"}


# -- current mesh / mesh context --------------------------------------------


class TestCurrentMesh:
    def test_empty_outside_context(self):
        mesh = compat.current_mesh()
        assert mesh.empty
        assert "tp" not in tuple(mesh.axis_names)

    def test_ambient_inside_context(self, mesh_dp_tp):
        with compat.mesh_context(mesh_dp_tp):
            mesh = compat.current_mesh()
            assert not mesh.empty
            assert tuple(mesh.axis_names) == ("dp", "tp")
        assert compat.current_mesh().empty

    def test_bare_partition_spec_constraint_resolves(self, mesh_dp_tp):
        """What the context is for: ``with_sharding_constraint`` with a
        bare PartitionSpec under jit picks up the ambient mesh."""
        @jax.jit
        def f(x):
            return jax.lax.with_sharding_constraint(x * 2.0, P("dp", "tp"))

        with compat.mesh_context(mesh_dp_tp):
            out = f(jnp.ones((4, 4)))
        assert out.sharding.spec == P("dp", "tp")
        assert len({s.device.id for s in out.addressable_shards}) == 4
