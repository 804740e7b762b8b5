"""The one place the program decides where XLA's persistent compile
cache lives.

Every process that compiles for the chip — the trainer launchers
(``examples/common.py:launcher_init``), the serving main
(``serving/server.py:main``), the per-config bench child
(``bench/suite.py:main``) and ``chip_smoke.py`` — calls
:func:`enable_compile_cache` before its first compilation (jax decides
once, at the first compile, whether the cache is in use).

Placement rule: when ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it
itself and this module sets no directory in code, so whoever runs the
program (a pod spec, the chip tool) places the cache. Otherwise the
cache is ONE fixed directory beside the package — never a temp dir, a
pid, a timestamp or the model base path: a cache directory that moves
between runs never hits.
"""

from __future__ import annotations

import os
from typing import Optional

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache (git-ignored); the checkout root is the
# directory holding the kubeflow_tpu package
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def compile_cache_dir() -> str:
    """The directory the cache will use — no jax import, no side
    effect (``chip_smoke.py``'s jax-free parent prints it)."""
    return os.environ.get(ENV_CACHE_DIR) or DEFAULT_CACHE_DIR


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compile cache on; returns its directory, or
    None when this process keeps none. Initializes the jax backend, so
    call it where the process is about to use the device anyway (and
    after ``jax.distributed.initialize``)."""
    import jax

    if not os.environ.get(ENV_CACHE_DIR):
        if jax.default_backend() == "cpu":
            # XLA:CPU reloads its own AOT results with machine-feature
            # mismatch errors (and a stated SIGILL risk) even on the
            # host that wrote them, and no CPU compile here is slow
            # enough to be worth keeping
            return None
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    # the engine's per-bucket programs and the launchers' small jits
    # each compile in under the default 1 s floor; together they are
    # most of a warm start, so cache them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return compile_cache_dir()
