"""JAX's persistent compilation cache, turned on by entry points only.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no directory.  Otherwise the cache lives at ``.jax_cache/`` in
the checkout root: a fixed path, because the path is part of what makes a
later run find an entry, so a temporary or per-process directory would
never be hit.

``enable()`` is called first thing by ``chip_smoke.py``,
``launch/serve.py``, ``benchmarks/run.py`` and ``benchmarks/track.py``.
Importing the library, or running the tests, never turns the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir(environ: Mapping[str, str] = os.environ) -> str:
    """The directory the cache uses: the environment's, else the fixed
    ``.jax_cache/`` of the checkout."""
    return environ.get(ENV_VAR) or str(CHECKOUT_CACHE)


def enable() -> str:
    """Turn the persistent compilation cache on for this process and
    return its directory."""
    import jax
    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    # keep every program: the kernels compile in about a second, under the
    # default one-second threshold, yet a cold chip run pays each of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
