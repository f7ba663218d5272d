"""Where JAX keeps its persistent compile cache.

A cold run compiles every cohort's round phases again (ten CNN slots of
the image zoo times about six phases each). The persistent cache lets
later processes load them instead. Its directory is part of each entry's
key, so it must not move between runs: a temp name, a pid or a time in
the path would never hit.

Entry points call :func:`enable_compile_cache` from ``main``, never at
import, so importing the package changes no global JAX state.
"""
from __future__ import annotations

import os
import pathlib
from typing import Optional

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# the checkout's root (src/repro/common/ -> three levels up); git-ignored
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compile cache on; return its directory or None.

    When ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here. Otherwise an accelerator's programs go to
    ``.jax_cache`` at the root of the checkout. CPU programs are not
    cached there: they compile quickly, and XLA:CPU entries are built for
    the features of the host that wrote them (loading them elsewhere warns
    of illegal instructions).
    """
    placed = os.environ.get(ENV_VAR, "")
    if placed:
        return placed
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
