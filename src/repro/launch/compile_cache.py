"""JAX's persistent compilation cache for the entry points.

``enable_compile_cache()`` is the first call of ``chip_smoke.py``,
``python -m repro.launch.serve`` and ``benchmarks/run.py``.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and uses that
directory; this module sets none.  Otherwise, on an accelerator, the
cache lives at one fixed directory of the checkout, ``.jax_cache/`` at
the repository root (git-ignored), resolved from this file and never
from a temporary name, a pid or the time, so the next run finds what
this one compiled.  On the CPU backend the default cache stays off:
XLA:CPU entries are tied to the host's instruction set, and loading one
built on another host can crash the process.

Every compile is cached, however short (``min_compile_time 0``): the
served path is many small programs (one per expert and bucket size),
each under JAX's default one-second threshold.
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         "..", "..", ".."))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str | None:
    """Turn the persistent compilation cache on and return its
    directory (None: off, on the CPU backend without ``ENV_VAR``).
    Call it before the first compile."""
    path = os.environ.get(ENV_VAR)
    if not path:
        if jax.default_backend() == "cpu":
            return None
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
