"""tpulint unit tests: per-checker fixtures (positive / negative /
pragma / baseline) plus the whole-repo gate that makes the analyzers a
tier-1 CI check."""

import json
import os
import textwrap

import pytest

from kubeflow_tpu.analysis import baseline as baseline_mod
from kubeflow_tpu.analysis import runner
from kubeflow_tpu.analysis.checkers.host_call_in_jit import (
    HostCallInJitChecker,
)
from kubeflow_tpu.analysis.checkers.mesh_axes import MeshAxesChecker
from kubeflow_tpu.analysis.checkers.raw_clock import RawClockChecker
from kubeflow_tpu.analysis.checkers.spec_legality import SpecLegalityChecker
from kubeflow_tpu.analysis.checkers.tile_legality import TileLegalityChecker
from kubeflow_tpu.analysis.checkers.unbound_collective import (
    UnboundCollectiveChecker,
)
from kubeflow_tpu.analysis.checkers.unbounded_retry import (
    UnboundedRetryChecker,
)
from kubeflow_tpu.analysis.checkers.version_gate import VersionGateChecker
from kubeflow_tpu.analysis.checkers.wiring import WiringChecker
from kubeflow_tpu.analysis.registry import all_checkers, create_checkers
from kubeflow_tpu.analysis.runner import lint_modules, run_lint
from kubeflow_tpu.analysis.walker import ModuleInfo

REPO = runner.repo_root()


def mod(src, rel="kubeflow_tpu/fixture.py"):
    return ModuleInfo.from_source(rel, textwrap.dedent(src))


def check(checker, *modules):
    out = []
    for m in modules:
        out.extend(checker.check(m))
    out.extend(checker.finalize())
    return out


# -- registry / framework ---------------------------------------------------

def test_registry_has_all_eighteen_rules():
    assert set(all_checkers()) == {f"TPU{i:03d}" for i in range(1, 19)}


def test_create_checkers_rejects_unknown_rule():
    with pytest.raises(KeyError):
        create_checkers(["TPU999"])


# -- TPU001 tile legality ---------------------------------------------------

def test_tpu001_literal_lane_violation():
    m = mod("""
        import jax.experimental.pallas as pl
        def f():
            return pl.pallas_call(
                k, in_specs=[pl.BlockSpec((256, 64), lambda i: (i, 0))])
    """)
    f = check(TileLegalityChecker(), m)
    assert len(f) == 1 and f[0].rule == "TPU001"
    assert "lane block dim 64" in f[0].message


def test_tpu001_literal_ok_and_broadcast_dim():
    m = mod("""
        import jax.experimental.pallas as pl
        def f():
            specs = [pl.BlockSpec((8, 128), lambda i: (i, 0)),
                     pl.BlockSpec((1, 256), lambda i: (0, i)),
                     pl.BlockSpec((1, 512, 1), lambda i: (0, i, 0))]
    """)
    assert check(TileLegalityChecker(), m) == []


def test_tpu001_size_one_dim_walked_by_its_index_map():
    """The ops/sampling.py bug the v5e refused: a (1, Vp) block over a
    (B, Vp) array — the index map moving along the size-1 dim says the
    array's dim is larger than 1."""
    m = mod("""
        import jax.experimental.pallas as pl
        def f():
            specs = [pl.BlockSpec((1, 32000), lambda b: (b, 0)),
                     pl.BlockSpec((1, 1), lambda b: (b, 0))]
    """)
    f = check(TileLegalityChecker(), m)
    assert [x.rule for x in f] == ["TPU001"] * 2
    assert all("sublane block dim 1 is walked" in x.message for x in f)


def test_tpu001_size_one_dim_that_is_the_whole_array_dim():
    """...while a size-1 dim indexed by the constant 0 is the array's
    whole dim (lse/out blocks), and a named index map says nothing."""
    m = mod("""
        import jax.experimental.pallas as pl
        def f():
            specs = [pl.BlockSpec((1, 1, 128), lambda b: (b, 0, 0)),
                     pl.BlockSpec((1, 512, 1), lambda b, i, j: (b, i, 0)),
                     pl.BlockSpec((1, 1, 128), q_map)]
    """)
    assert check(TileLegalityChecker(), m) == []


def test_tpu001_sublane_violation():
    m = mod("""
        import jax.experimental.pallas as pl
        def f():
            s = pl.BlockSpec((4, 128), lambda i: (i, 0))
    """)
    f = check(TileLegalityChecker(), m)
    assert len(f) == 1 and "sublane block dim 4" in f[0].message


def test_tpu001_fallback_guard_suppresses_literals():
    m = mod("""
        import jax.experimental.pallas as pl
        def f(x):
            if not _tileable(x.shape):
                return reference(x)
            return pl.pallas_call(
                k, in_specs=[pl.BlockSpec((256, 64), lambda i: (i, 0))])
    """)
    assert check(TileLegalityChecker(), m) == []


def test_tpu001_pick_block_bad_floor_even_with_guard():
    # the PR 1 failure mode: guard + picker share the wrong floor, so
    # the fallback guard must NOT excuse a pick-block lane floor < 128
    m = mod("""
        import jax.experimental.pallas as pl
        def f(x, K):
            if not _tileable(x.shape):
                return reference(x)
            bk = _pick_block(K, 256)
            return pl.pallas_call(
                k, in_specs=[pl.BlockSpec((8, bk), lambda i: (i, 0))])
    """)
    f = check(TileLegalityChecker(), m)
    assert len(f) == 1 and "floor 8" in f[0].message


def test_tpu001_nonconstant_floor_stays_silent():
    # an unprovable floor must not be assumed to be the bad default —
    # `floor=LANE` where LANE is a named constant is valid code
    m = mod("""
        import jax.experimental.pallas as pl
        LANE = 128
        def f(x, K):
            bk = _pick_block(K, 256, floor=LANE)
            return pl.pallas_call(
                k, in_specs=[pl.BlockSpec((8, bk), lambda i: (i, 0))])
    """)
    assert check(TileLegalityChecker(), m) == []


def test_tpu001_pick_block_good_floor():
    m = mod("""
        import jax.experimental.pallas as pl
        def f(x, K):
            bk = _pick_block(K, 256, floor=128)
            return pl.pallas_call(
                k, in_specs=[pl.BlockSpec((8, bk), lambda i: (i, 0))])
    """)
    assert check(TileLegalityChecker(), m) == []


def test_tpu001_flags_reintroduced_bnconv_bug():
    """Re-introduce the PR 1 bnconv lane-dim bug (drop the floor=128 on
    the lane-axis _pick_block calls) and TPU001 must light up; the
    committed file must stay clean."""
    path = os.path.join(REPO, "kubeflow_tpu", "ops", "bnconv.py")
    with open(path) as fh:
        src = fh.read()
    buggy = src.replace(", floor=128)", ")")
    assert buggy != src, "bnconv no longer uses floor=128 lane picks"
    rel = "kubeflow_tpu/ops/bnconv.py"
    bad = check(TileLegalityChecker(), ModuleInfo.from_source(rel, buggy))
    assert bad and all(f.rule == "TPU001" for f in bad)
    assert any("floor 8" in f.message for f in bad)
    good = check(TileLegalityChecker(), ModuleInfo.from_source(rel, src))
    assert good == []


# -- TPU002 host call in jit ------------------------------------------------

def test_tpu002_decorated_jit():
    m = mod("""
        import jax, time
        @jax.jit
        def step(x):
            t = time.time()
            return x + t
    """)
    f = check(HostCallInJitChecker(), m)
    assert len(f) == 1 and "time.time" in f[0].message


def test_tpu002_pallas_kernel_via_partial():
    m = mod("""
        import functools
        import numpy as np
        import jax.experimental.pallas as pl
        def _kern(x_ref, o_ref):
            o_ref[...] = x_ref[...] * np.random.rand()
        def run(x):
            return pl.pallas_call(functools.partial(_kern))(x)
    """)
    f = check(HostCallInJitChecker(), m)
    assert len(f) == 1 and "np.random.rand" in f[0].message


def test_tpu002_jit_call_form_and_print():
    m = mod("""
        import jax
        def step(x):
            print("tracing", x)
            return x
        fast = jax.jit(step)
    """)
    f = check(HostCallInJitChecker(), m)
    assert len(f) == 1 and "print" in f[0].message


def test_tpu002_host_call_outside_jit_ok():
    m = mod("""
        import time
        def loop(x):
            return time.time() + x
    """)
    assert check(HostCallInJitChecker(), m) == []


def test_tpu002_debug_escape_hatch_ok():
    m = mod("""
        import jax
        @jax.jit
        def step(x):
            jax.debug.print("x={}", x)
            return x
    """)
    assert check(HostCallInJitChecker(), m) == []


# -- TPU003 raw clock -------------------------------------------------------

def test_tpu003_raw_calls_flagged():
    m = mod("""
        import time
        def reconcile():
            t0 = time.time()
            time.sleep(1)
    """)
    f = check(RawClockChecker(), m)
    assert [x.rule for x in f] == ["TPU003", "TPU003"]


def test_tpu003_injectable_default_idiom_ok():
    m = mod("""
        import time
        def window(self, now=None):
            now = now if now is not None else time.time()
            return now
    """)
    assert check(RawClockChecker(), m) == []


def test_tpu003_clock_reference_ok():
    m = mod("""
        import time
        class C:
            def __init__(self, clock=None):
                self.clock = clock if clock is not None else time.monotonic
    """)
    assert check(RawClockChecker(), m) == []


def test_tpu003_examples_skipped():
    m = mod("import time\nts = time.time()\n",
            rel="kubeflow_tpu/examples/mnist.py")
    assert check(RawClockChecker(), m) == []


def test_tpu003_pragma_suppresses():
    m = mod("""
        import time
        def main():
            while True:  # serve forever
                time.sleep(3600)  # tpulint: disable=TPU003
    """)
    findings, suppressed = lint_modules([m], rules=["TPU003"])
    assert findings == [] and suppressed == 1


# -- TPU004 wiring ----------------------------------------------------------

COMPONENT_SRC = """
    DEFAULTS = {"name": "serving-autoscaler", "port": 8090}
    @register("autoscaler", DEFAULTS, "desc")
    def render(config, params):
        return [o.service_account("a", "ns"),
                o.cluster_role("a", []),
                o.cluster_role_binding("a", "a", "a", "ns")]
"""


def test_tpu004_url_port_drift():
    comp = mod(COMPONENT_SRC,
               rel="kubeflow_tpu/manifests/components/autoscaler.py")
    presets = mod("""
        URL = "http://serving-autoscaler:9999"
    """, rel="kubeflow_tpu/config/presets.py")
    f = check(WiringChecker(), comp, presets)
    assert len(f) == 1 and "9999" in f[0].message
    assert f[0].path == "kubeflow_tpu/config/presets.py"


def test_tpu004_url_port_match_and_foreign_hosts_ok():
    comp = mod(COMPONENT_SRC,
               rel="kubeflow_tpu/manifests/components/autoscaler.py")
    presets = mod("""
        URL = "http://serving-autoscaler:8090"
        OTHER = "http://127.0.0.1:9999"
        EXT = "https://example.com:443/x"
    """, rel="kubeflow_tpu/config/presets.py")
    assert check(WiringChecker(), comp, presets) == []


def test_tpu004_unknown_component_spec():
    comp = mod(COMPONENT_SRC,
               rel="kubeflow_tpu/manifests/components/autoscaler.py")
    presets = mod("""
        parts = [ComponentSpec("autoscaler"), ComponentSpec("no-such")]
    """, rel="kubeflow_tpu/config/presets.py")
    f = check(WiringChecker(), comp, presets)
    assert len(f) == 1 and "no-such" in f[0].message


def test_tpu004_role_without_binding():
    comp = mod("""
        DEFAULTS = {"name": "thing", "port": 80}
        @register("thing", DEFAULTS, "d")
        def render(config, params):
            return [o.cluster_role("t", [])]
    """, rel="kubeflow_tpu/manifests/components/thing.py")
    f = check(WiringChecker(), comp)
    assert len(f) == 1 and "cluster_role_binding" in f[0].message


def test_tpu004_role_without_binding_no_defaults_dict():
    # rbac pairing must not depend on the module declaring DEFAULTS
    comp = mod("""
        @register("thing", None, "d")
        def render(config, params):
            return [o.cluster_role("t", [])]
    """, rel="kubeflow_tpu/manifests/components/thing.py")
    f = check(WiringChecker(), comp)
    assert len(f) == 1 and "cluster_role_binding" in f[0].message


TRACE_COMPONENT_SRC = """
    DEFAULTS = {"name": "trace-collector", "port": 8095}
    @register("trace-collector", DEFAULTS, "desc")
    def render(config, params):
        return [o.service_account("t", "ns"),
                o.cluster_role("t", []),
                o.cluster_role_binding("t", "t", "t", "ns")]
"""

TRACE_SERVICE_SRC = """
    class Svc:
        def handle(self, method, path, body, user=""):
            if path == "/api/traces":
                return 200, []
            if path == "/api/traces:ingest":
                return 200, {}
            if path.startswith("/api/traces/"):
                return 200, {}
            return 404, {}
"""


def test_tpu004_api_route_drift():
    comp = mod(TRACE_COMPONENT_SRC,
               rel="kubeflow_tpu/manifests/components/trace_collector.py")
    svc = mod(TRACE_SERVICE_SRC, rel="kubeflow_tpu/obs/service.py")
    caller = mod("""
        URL = "http://trace-collector:8095/api/spans:push"
    """, rel="kubeflow_tpu/obs/export.py")
    f = check(WiringChecker(), comp, svc, caller)
    assert len(f) == 1 and "/api/spans:push" in f[0].message
    assert f[0].path == "kubeflow_tpu/obs/export.py"
    assert "obs/service.py" in f[0].message


def test_tpu004_api_route_exact_and_prefix_match_ok():
    comp = mod(TRACE_COMPONENT_SRC,
               rel="kubeflow_tpu/manifests/components/trace_collector.py")
    svc = mod(TRACE_SERVICE_SRC, rel="kubeflow_tpu/obs/service.py")
    caller = mod("""
        INGEST = "http://trace-collector:8095/api/traces:ingest"
        ONE = "http://trace-collector:8095/api/traces/abc123"
        # unknown host / no path: not this sub-rule's business
        OTHER = "http://somewhere-else:1234/api/nope"
        BARE = "http://trace-collector:8095"
    """, rel="kubeflow_tpu/obs/export.py")
    assert check(WiringChecker(), comp, svc, caller) == []


def test_tpu004_jobs_telemetry_route_registered():
    """The training-telemetry surface is in the dashboard's TPU004 route
    table: the REAL dashboard/server.py (whose "/api/..." constants ARE
    the table) accepts a caller URL under /api/jobs/, and a typo'd
    variant is the drift the sub-rule exists to catch."""
    rel = "kubeflow_tpu/dashboard/server.py"
    with open(os.path.join(REPO, rel)) as f:
        dash = ModuleInfo.from_source(rel, f.read())
    comp = mod("""
        DEFAULTS = {"name": "centraldashboard", "port": 80}
        @register("centraldashboard", DEFAULTS, "d")
        def render(config, params):
            return [o.service_account("d", "ns"),
                    o.cluster_role("d", []),
                    o.cluster_role_binding("d", "d", "d", "ns")]
    """, rel="kubeflow_tpu/manifests/components/dashboard.py")
    good = mod("""
        URL = "http://centraldashboard:80/api/jobs/ns/train/telemetry"
    """, rel="kubeflow_tpu/operators/tpujob.py")
    assert check(WiringChecker(), comp, dash, good) == []
    bad = mod("""
        URL = "http://centraldashboard:80/api/job-telemetry/ns/train"
    """, rel="kubeflow_tpu/operators/tpujob.py")
    f = check(WiringChecker(), comp, dash, bad)
    assert len(f) == 1 and "/api/job-telemetry/ns/train" in f[0].message


# -- TPU005 unbounded retry -------------------------------------------------

def test_tpu005_while_true_sleep_no_exit():
    m = mod("""
        import time
        def pump():
            while True:
                try:
                    connect()
                except Exception:
                    time.sleep(2)
    """)
    f = check(UnboundedRetryChecker(), m)
    assert len(f) == 1 and f[0].rule == "TPU005"


def test_tpu005_break_return_deadline_ok():
    m = mod("""
        import time
        def a():
            while True:
                if done():
                    break
                time.sleep(1)
        def b(clock, timeout):
            t0 = clock()
            while clock() - t0 < timeout:
                time.sleep(1)
        def c():
            for attempt in range(3):
                time.sleep(2 ** attempt)
    """)
    assert check(UnboundedRetryChecker(), m) == []


def test_tpu005_nested_loop_break_does_not_count():
    m = mod("""
        import time
        def pump():
            while True:
                for x in items():
                    if x:
                        break
                time.sleep(2)
    """)
    assert len(check(UnboundedRetryChecker(), m)) == 1


def test_tpu005_pragma_inside_span_suppresses():
    m = mod("""
        import time
        def main():
            while True:
                time.sleep(3600)  # tpulint: disable=TPU005
    """)
    findings, suppressed = lint_modules([m], rules=["TPU005"])
    assert findings == [] and suppressed == 1


# -- TPU006 version-gated api -----------------------------------------------

def test_tpu006_direct_jax_shard_map():
    m = mod("""
        import jax
        def wrap(core, mesh, spec):
            return jax.shard_map(core, mesh=mesh, in_specs=(spec,),
                                 out_specs=spec)
    """)
    f = check(VersionGateChecker(), m)
    assert len(f) == 1 and f[0].rule == "TPU006"
    assert "jax.shard_map" in f[0].message
    assert "compat" in f[0].hint


def test_tpu006_from_imports_and_experimental_module():
    m = mod("""
        from jax import shard_map
        from jax.sharding import get_abstract_mesh
        from jax.experimental.shard_map import shard_map as legacy
        from jax.experimental import shard_map as sm2
        import jax.experimental.shard_map as sm
    """)
    f = check(VersionGateChecker(), m)
    assert len(f) == 5 and all(x.rule == "TPU006" for x in f)


def test_tpu006_other_gated_apis():
    m = mod("""
        import jax
        def f(x, mesh):
            n = jax.lax.axis_size("tp")
            x = jax.lax.pvary(x, ("tp",))
            with jax.sharding.use_mesh(mesh):
                m = jax.sharding.get_abstract_mesh()
            return x, n, m
    """)
    f = check(VersionGateChecker(), m)
    assert {x.message.split(" ")[0] for x in f} == {
        "jax.lax.axis_size", "jax.lax.pvary",
        "jax.sharding.use_mesh", "jax.sharding.get_abstract_mesh"}


def test_tpu006_compat_is_sanctioned():
    m = mod("""
        import jax
        from jax.experimental.shard_map import shard_map
        def shim(f, **kw):
            return jax.shard_map(f, **kw)
    """, rel="kubeflow_tpu/compat/jaxshim.py")
    assert check(VersionGateChecker(), m) == []


def test_tpu006_string_probes_not_flagged():
    # getattr/hasattr feature probes are how compat itself resolves
    # the surface — a string cannot crash at import/attribute time
    m = mod("""
        import jax
        HAS = hasattr(jax, "shard_map")
        fn = getattr(jax.lax, "axis_size", None)
    """)
    assert check(VersionGateChecker(), m) == []


def test_tpu006_exemption_is_exact_path_not_substring():
    # a sibling "netcompat/" (or a nested */compat/) must not inherit
    # the sanctioned-directory exemption
    src = """
        import jax
        def wrap(core, mesh, spec):
            return jax.shard_map(core, mesh=mesh, in_specs=(spec,),
                                 out_specs=spec)
    """
    for rel in ("kubeflow_tpu/netcompat/x.py",
                "kubeflow_tpu/serving/compat/x.py"):
        f = check(VersionGateChecker(), mod(src, rel=rel))
        assert len(f) == 1, rel
    assert check(VersionGateChecker(),
                 mod(src, rel="kubeflow_tpu/compat/x.py")) == []


def test_tpu006_committed_callsites_stay_on_compat():
    """Re-introduce the bug that killed the 22 tier-1 tests — swap a
    consumer's compat.shard_map back to jax.shard_map — and TPU006
    must light up; the committed files must stay clean."""
    for rel in ("kubeflow_tpu/parallel/pipeline.py",
                "kubeflow_tpu/models/transformer.py",
                "kubeflow_tpu/ops/collectives.py",
                "kubeflow_tpu/ops/attention.py"):
        with open(os.path.join(REPO, rel)) as fh:
            src = fh.read()
        assert check(VersionGateChecker(),
                     ModuleInfo.from_source(rel, src)) == []
        buggy = src.replace("compat.shard_map(", "jax.shard_map(")
        assert buggy != src, f"{rel} no longer routes through compat"
        bad = check(VersionGateChecker(),
                    ModuleInfo.from_source(rel, buggy))
        assert bad and all(f.rule == "TPU006" for f in bad), rel


# -- TPU007 mesh-axis consistency --------------------------------------------

MESH_DECL_SRC = """
    MESH_AXES = ("dcn", "dp", "pp", "tp")
"""


def test_tpu007_collective_axis_typo():
    decl = mod(MESH_DECL_SRC, rel="kubeflow_tpu/parallel/mesh.py")
    use = mod("""
        import jax
        def f(x):
            return jax.lax.psum(x, "tpp")
    """, rel="kubeflow_tpu/ops/thing.py")
    f = [x for x in check(MeshAxesChecker(), decl, use)]
    assert len(f) == 1 and f[0].rule == "TPU007"
    assert "'tpp'" in f[0].message and "dcn, dp, pp, tp" in f[0].message


def test_tpu007_spec_and_axis_names_and_defaults():
    decl = mod(MESH_DECL_SRC, rel="kubeflow_tpu/parallel/mesh.py")
    use = mod("""
        from jax.sharding import PartitionSpec as P
        def wrap(core, mesh, seq_axis="tq"):
            spec = P(("dcn", "dq"), "tp")
            return shard_map(core, mesh=mesh, in_specs=(spec,),
                             out_specs=spec, axis_names={"qq"})
    """, rel="kubeflow_tpu/ops/thing.py")
    f = check(MeshAxesChecker(), decl, use)
    assert sorted(x.message.split("'")[1] for x in f) == [
        "dq", "qq", "tq"]


def test_tpu007_known_axes_and_mesh_ctor_declarations_ok():
    decl = mod(MESH_DECL_SRC, rel="kubeflow_tpu/parallel/mesh.py")
    extra = mod("""
        from jax.sharding import Mesh
        mesh = Mesh(devices, ("rows",))
    """, rel="kubeflow_tpu/testing/grid.py")
    use = mod("""
        import jax
        from jax.sharding import PartitionSpec as P
        def f(x, axis="dp"):
            spec = P(("dcn", "dp"), "rows", None)
            return jax.lax.psum(x, axis_name="tp")
    """, rel="kubeflow_tpu/ops/thing.py")
    assert check(MeshAxesChecker(), decl, extra, use) == []


def test_tpu007_axis_first_positional_calls():
    # axis_index/axis_size take the axis as their FIRST positional arg
    decl = mod(MESH_DECL_SRC, rel="kubeflow_tpu/parallel/mesh.py")
    use = mod("""
        import jax
        from kubeflow_tpu import compat
        def f():
            i = jax.lax.axis_index("tppp")
            n = compat.axis_size("tp")
            return i, n
    """, rel="kubeflow_tpu/ops/thing.py")
    f = check(MeshAxesChecker(), decl, use)
    assert len(f) == 1 and "'tppp'" in f[0].message


def test_tpu007_silent_without_declarations():
    # scoped run: no declaration in the walked subset -> no guessing
    use = mod("""
        import jax
        def f(x):
            return jax.lax.psum(x, "anything")
    """)
    assert check(MeshAxesChecker(), use) == []


def test_tpu007_variable_axes_not_chased():
    decl = mod(MESH_DECL_SRC, rel="kubeflow_tpu/parallel/mesh.py")
    use = mod("""
        import jax
        def f(x, axis):
            return jax.lax.psum(x, axis)
    """)
    assert check(MeshAxesChecker(), decl, use) == []


# -- TPU008 partitionspec legality -------------------------------------------

def test_tpu008_duplicate_axis_across_entries():
    m = mod("""
        from jax.sharding import PartitionSpec as P
        spec = P("tp", "tp")
    """)
    f = check(SpecLegalityChecker(), m)
    assert len(f) == 1 and f[0].rule == "TPU008"
    assert "'tp' appears twice" in f[0].message


def test_tpu008_duplicate_axis_inside_tuple_entry():
    m = mod("""
        from jax.sharding import PartitionSpec as P
        spec = P(("dp", "dp"), None)
    """)
    assert len(check(SpecLegalityChecker(), m)) == 1


def test_tpu008_legal_specs_ok():
    m = mod("""
        from jax.sharding import PartitionSpec as P
        a = P(("dcn", "dp"), "tp")
        b = P(None, "tp", None, None)
        c = P()
    """)
    assert check(SpecLegalityChecker(), m) == []


def test_tpu008_rank_overflow_inferable():
    m = mod("""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        def f():
            x = jnp.zeros((4, 8))
            return jax.lax.with_sharding_constraint(
                x, P("dp", "tp", "pp"))
    """)
    f = check(SpecLegalityChecker(), m)
    assert len(f) == 1 and "rank 2" in f[0].message


def test_tpu008_rank_unprovable_stays_silent():
    m = mod("""
        import jax
        from jax.sharding import PartitionSpec as P
        def f(x):
            return jax.lax.with_sharding_constraint(
                x, P("dp", "tp", "pp"))
    """)
    assert check(SpecLegalityChecker(), m) == []


# -- TPU009 unbound collective -----------------------------------------------

def test_tpu009_bare_literal_collective():
    m = mod("""
        import jax
        def helper(x):
            return jax.lax.ppermute(x, "dp", [(0, 1)])
    """)
    f = check(UnboundCollectiveChecker(), m)
    assert len(f) == 1 and f[0].rule == "TPU009"
    assert "'dp'" in f[0].message


def test_tpu009_shard_wrapped_by_name_ok():
    m = mod("""
        import jax
        def core(x):
            return jax.lax.psum(x, "tp")
        def run(mesh, spec, x):
            fn = shard_map(core, mesh=mesh, in_specs=(spec,),
                           out_specs=spec, axis_names={"tp"})
            return fn(x)
    """)
    assert check(UnboundCollectiveChecker(), m) == []


def test_tpu009_full_manual_binds_everything():
    m = mod("""
        import functools
        import jax
        def core(x):
            return jax.lax.all_to_all(x, "tp", split_axis=2,
                                      concat_axis=1, tiled=True)
        def run(mesh, spec, x):
            fn = shard_map(functools.partial(core), mesh=mesh,
                           in_specs=(spec,), out_specs=spec)
            return fn(x)
    """)
    assert check(UnboundCollectiveChecker(), m) == []


def test_tpu009_wrong_axis_still_flagged():
    m = mod("""
        import jax
        def core(x):
            return jax.lax.psum(x, "dp")
        def run(mesh, spec, x):
            return shard_map(core, mesh=mesh, in_specs=(spec,),
                             out_specs=spec, axis_names={"tp"})(x)
    """)
    f = check(UnboundCollectiveChecker(), m)
    assert len(f) == 1 and "'dp'" in f[0].message


def test_tpu009_nested_def_inherits_binding():
    m = mod("""
        import jax
        def run(mesh, spec, x):
            def core(v):
                def inner(u):
                    return jax.lax.psum(u, "pp")
                return inner(v)
            return shard_map(core, mesh=mesh, in_specs=(spec,),
                             out_specs=spec, axis_names={"pp"})(x)
    """)
    assert check(UnboundCollectiveChecker(), m) == []


def test_tpu009_inline_lambda_body_is_bound():
    # an inline lambda handed straight to shard_map IS the region body;
    # flagging it would violate false-negatives-over-false-positives
    m = mod("""
        import jax
        def run(mesh, spec, x):
            fn = shard_map(lambda v: jax.lax.psum(v, "tp"), mesh=mesh,
                           in_specs=(spec,), out_specs=spec,
                           axis_names={"tp"})
            return fn(x)
    """)
    assert check(UnboundCollectiveChecker(), m) == []
    wrong_axis = mod("""
        import jax
        def run(mesh, spec, x):
            return shard_map(lambda v: jax.lax.psum(v, "dp"), mesh=mesh,
                             in_specs=(spec,), out_specs=spec,
                             axis_names={"tp"})(x)
    """)
    f = check(UnboundCollectiveChecker(), wrong_axis)
    assert len(f) == 1 and "'dp'" in f[0].message


def test_tpu009_pmap_axis_name_binds():
    m = mod("""
        import jax
        def step(x):
            return jax.lax.pmean(x, "batch")
        run = jax.pmap(step, axis_name="batch")
    """)
    assert check(UnboundCollectiveChecker(), m) == []


def test_tpu009_parameter_axis_not_flagged():
    # the ops/attention.py convention: axis flows in as a parameter
    m = mod("""
        import jax
        def core(x, axis_name):
            return jax.lax.psum(x, axis_name)
    """)
    assert check(UnboundCollectiveChecker(), m) == []


def test_tpu009_axis_index_first_positional():
    # axis_index's axis is its first positional arg — an unbound one
    # raises at trace time exactly like psum's second positional
    m = mod("""
        import jax
        def helper():
            return jax.lax.axis_index("dp")
    """)
    f = check(UnboundCollectiveChecker(), m)
    assert len(f) == 1 and "'dp'" in f[0].message
    bound = mod("""
        import jax
        def core(x):
            return x + jax.lax.axis_index("pp")
        def run(mesh, spec, x):
            return shard_map(core, mesh=mesh, in_specs=(spec,),
                             out_specs=spec, axis_names={"pp"})(x)
    """)
    assert check(UnboundCollectiveChecker(), bound) == []


def test_tpu009_pragma_suppresses():
    m = mod("""
        import jax
        def helper(x):
            return jax.lax.psum(x, "dp")  # tpulint: disable=TPU009 doc example
    """)
    findings, suppressed = lint_modules([m], rules=["TPU009"])
    assert findings == [] and suppressed == 1


# -- acceptance fixture: the three SPMD bug classes, one finding each --------

def test_spmd_fixture_yields_exactly_tpu006_007_008():
    """ISSUE acceptance: a synthetic module with a direct
    ``jax.shard_map`` call, a mesh-axis typo, and a duplicated
    PartitionSpec axis yields exactly one TPU006, one TPU007, and one
    TPU008 finding."""
    decl = mod(MESH_DECL_SRC, rel="kubeflow_tpu/parallel/mesh.py")
    fixture = mod("""
        import jax
        from jax.sharding import PartitionSpec as P

        def run(core, mesh, x):
            fn = jax.shard_map(core, mesh=mesh,
                               in_specs=(P("dp", "dp"),),
                               out_specs=P(None, "ttp"))
            return fn(x)
    """, rel="kubeflow_tpu/ops/fixture.py")
    findings, _ = lint_modules([decl, fixture])
    by_rule = sorted(f.rule for f, _ in findings
                     if f.path.endswith("fixture.py"))
    assert by_rule == ["TPU006", "TPU007", "TPU008"], [
        f.format() for f, _ in findings]


# -- pragmas / baseline workflow --------------------------------------------

def test_line_pragma_with_trailing_justification_prose():
    # the documented style encourages a human-readable reason after the
    # rule list; prose must not be absorbed into the rule token
    m = mod("""
        import time
        def main():
            while True:
                time.sleep(3600)  # tpulint: disable=TPU003,TPU005 serving forever is the point
    """)
    findings, suppressed = lint_modules([m], rules=["TPU003", "TPU005"])
    assert findings == [] and suppressed == 2


def test_file_pragma_disables_rule_for_whole_file():
    m = mod("""
        # tpulint: disable-file=TPU003
        import time
        a = time.time()
        b = time.sleep(1)
    """)
    findings, suppressed = lint_modules([m], rules=["TPU003"])
    assert findings == [] and suppressed == 2


def test_baseline_roundtrip(tmp_path):
    m = mod("""
        import time
        def f():
            time.sleep(1)
    """)
    findings, _ = lint_modules([m], rules=["TPU003"])
    assert len(findings) == 1
    path = str(tmp_path / "base.json")
    baseline_mod.save(path, findings)
    # same findings → fully grandfathered
    assert baseline_mod.new_findings(findings, baseline_mod.load(path)) == []
    # a second occurrence beyond the baselined count is new
    m2 = mod("""
        import time
        def f():
            time.sleep(1)
        def g():
            time.sleep(1)
    """)
    findings2, _ = lint_modules([m2], rules=["TPU003"])
    new = baseline_mod.new_findings(findings2, baseline_mod.load(path))
    assert len(new) == 1


def test_baseline_survives_line_drift(tmp_path):
    m = mod("import time\nts = time.sleep(5)\n")
    findings, _ = lint_modules([m], rules=["TPU003"])
    path = str(tmp_path / "base.json")
    baseline_mod.save(path, findings)
    # same offending line, shifted down and re-indented: still baselined
    m2 = mod("import time\n\n\nif True:\n    ts = time.sleep(5)\n")
    findings2, _ = lint_modules([m2], rules=["TPU003"])
    assert len(findings2) == 1
    assert baseline_mod.new_findings(
        findings2, baseline_mod.load(path)) == []


def test_baseline_version_mismatch(tmp_path):
    path = tmp_path / "base.json"
    path.write_text(json.dumps({"version": 99, "findings": {}}))
    with pytest.raises(ValueError):
        baseline_mod.load(str(path))


# -- whole-repo gate --------------------------------------------------------

def test_repo_is_clean_under_committed_baseline():
    """The tier-1 enforcement point: the analyzers run in-process over
    the real package and must report zero non-baselined findings."""
    report = run_lint()
    msgs = "\n".join(f.format() for f in report.new)
    assert report.new == [], f"new tpulint findings:\n{msgs}"
    assert report.files > 100  # sanity: the walk actually saw the repo


def test_cli_exits_zero_on_clean_repo(tmp_path):
    import subprocess
    import sys
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "run_tpulint.py"),
         "--format", "json"],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["new"] == []


def test_cli_sarif_output_shape(tmp_path):
    """--format sarif must emit valid SARIF 2.1.0: driver + full rule
    catalog always, results only for NEW findings (a clean repo run
    annotates nothing — baselined debt must not spam PR lines)."""
    import subprocess
    import sys
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "run_tpulint.py"),
         "--format", "sarif"],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["version"] == "2.1.0"
    run = payload["runs"][0]
    assert run["tool"]["driver"]["name"] == "tpulint"
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert {"TPU001", "TPU006", "TPU007", "TPU008", "TPU009"} <= rule_ids
    assert run["results"] == []


def test_cli_sarif_reports_new_findings(tmp_path):
    """SARIF results carry ruleId/level/message/region for each new
    finding, against a bad file and an empty baseline."""
    import subprocess
    import sys
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import jax\n"
        "def wrap(core, mesh, spec):\n"
        "    return jax.shard_map(core, mesh=mesh, in_specs=(spec,),\n"
        "                         out_specs=spec)\n")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "run_tpulint.py"),
         "--format", "sarif", "--baseline", "", str(bad)],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    results = json.loads(proc.stdout)["runs"][0]["results"]
    assert len(results) == 1
    r = results[0]
    assert r["ruleId"] == "TPU006" and r["level"] == "error"
    loc = r["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith("bad.py")
    assert loc["region"]["startLine"] == 3


def test_cli_refuses_scoped_baseline_update(tmp_path):
    """A path- or rule-scoped --baseline-update would rewrite the
    baseline from a subset of findings, wiping grandfathered entries
    outside the scope — the CLI must refuse, loudly."""
    import subprocess
    import sys
    script = os.path.join(REPO, "scripts", "run_tpulint.py")
    before = open(os.path.join(REPO, "tpulint_baseline.json")).read()
    for extra in (["kubeflow_tpu/ops"], ["--rules", "TPU001"]):
        proc = subprocess.run(
            [sys.executable, script, "--baseline-update", *extra],
            capture_output=True, text=True, cwd=REPO)
        assert proc.returncode == 2, (extra, proc.stdout, proc.stderr)
        assert "full, unfiltered run" in proc.stderr
    assert open(os.path.join(REPO, "tpulint_baseline.json")).read() == before
