"""JAX's persistent compilation cache for the repo's entry points.

``chip_smoke.py``, ``benchmarks/run.py`` and
``examples/plan_and_serve_rpq.py`` call :func:`enable` before their first
compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps
its cache there and nothing else is configured; otherwise the cache goes
to :data:`CHECKOUT_CACHE_DIR`, a fixed directory inside the checkout (the
path is part of what a later run must find, so it is never built from a
temporary name, a process id or the time).  The thresholds are lowered so
that the small Pallas level programs are cached too.
"""

from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
