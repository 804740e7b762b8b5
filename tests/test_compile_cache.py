"""The one compile-cache helper (kubeflow_tpu/utils/compile_cache.py):
placeable from outside through the standard variable, otherwise one
fixed git-ignored directory in the checkout — never a path that moves.
"""

import os
import re
import subprocess
import sys

import jax
import pytest

from kubeflow_tpu.utils import compile_cache as cc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them (the
    test session's own config must not move)."""
    seen = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: seen.__setitem__(k, v))
    return seen


def test_variable_set_means_no_directory_set_in_code(monkeypatch,
                                                     config_updates):
    monkeypatch.setenv(cc.ENV_CACHE_DIR, "/some/dir")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert cc.enable_compile_cache() == "/some/dir"
    assert cc.compile_cache_dir() == "/some/dir"
    assert "jax_compilation_cache_dir" not in config_updates
    # placement is left to jax; caching everything is still ours
    assert config_updates == {
        "jax_persistent_cache_min_compile_time_secs": 0.0}


def test_variable_unset_uses_the_fixed_checkout_directory(monkeypatch,
                                                          config_updates):
    monkeypatch.delenv(cc.ENV_CACHE_DIR, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert cc.enable_compile_cache() == cc.DEFAULT_CACHE_DIR
    assert config_updates["jax_compilation_cache_dir"] == \
        os.path.join(REPO, ".jax_cache")


def test_cpu_backend_keeps_no_cache_unless_placed(monkeypatch,
                                                  config_updates):
    """XLA:CPU reloads its own AOT results with machine-feature errors;
    a CPU process caches only where someone explicitly points it."""
    monkeypatch.delenv(cc.ENV_CACHE_DIR, raising=False)
    assert jax.default_backend() == "cpu"
    assert cc.enable_compile_cache() is None
    assert config_updates == {}


def test_default_directory_is_identical_across_processes(tmp_path):
    env = {k: v for k, v in os.environ.items() if k != cc.ENV_CACHE_DIR}
    env["PYTHONPATH"] = REPO
    prog = ("from kubeflow_tpu.utils.compile_cache import "
            "compile_cache_dir; print(compile_cache_dir())")
    seen = {
        subprocess.run([sys.executable, "-c", prog], cwd=cwd, env=env,
                       capture_output=True, text=True, check=True,
                       timeout=60).stdout.strip()
        for cwd in (REPO, str(tmp_path))}
    assert seen == {os.path.join(REPO, ".jax_cache")}
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_no_other_code_path_places_the_cache():
    """One helper: no other module updates the cache directory, none of
    the retired knobs survive, and the helper builds its path from
    nothing that moves."""
    hits = []
    roots = [os.path.join(REPO, "kubeflow_tpu"),
             os.path.join(REPO, "scripts")]
    files = [os.path.join(REPO, n) for n in ("bench.py", "chip_smoke.py",
                                             "__graft_entry__.py")]
    for root in roots:
        for dirpath, _, names in os.walk(root):
            files += [os.path.join(dirpath, n) for n in names
                      if n.endswith(".py")]
    for path in files:
        with open(path) as f:
            text = f.read()
        if re.search(r"jax_compilation_cache_dir|KFTPU_COMPILE_CACHE_DIR",
                     text):
            hits.append(os.path.relpath(path, REPO))
    assert hits == ["kubeflow_tpu/utils/compile_cache.py"]
    with open(cc.__file__) as f:
        helper = f.read()
    for moving in ("tempfile", "getpid", "time.", "mkdtemp"):
        assert moving not in helper.split('"""', 2)[2], moving


def test_serving_manifest_places_the_cache_with_the_standard_variable():
    from kubeflow_tpu.config.deployment import DeploymentConfig
    from kubeflow_tpu.manifests.components import serving

    objs = serving.render(DeploymentConfig(name="t"),
                          dict(serving.DEFAULTS))
    deploy = next(o for o in objs if o["kind"] == "Deployment")
    env = {e["name"]: e.get("value")
           for e in deploy["spec"]["template"]["spec"]["containers"][0]["env"]}
    assert env[cc.ENV_CACHE_DIR] == "/models/default/.xla-compile-cache"
    assert "KFTPU_COMPILE_CACHE_DIR" not in env
