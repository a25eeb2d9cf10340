"""Persistent XLA compile cache placement — one rule for every entry point.

The codec lanes mint one compiled program per (op, k, m, pow2 width,
pow2 rows) (dataplane/ring.py, ops/fused.py); a fresh process otherwise
re-compiles each of them on first use. s3/server.py main(),
frontdoor/worker.py main() and chip_smoke.py call enable() before
first device use (so does the server `python3 benchmarks/run.py` starts).

Placement comes from OUTSIDE when it is given: with
JAX_COMPILATION_CACHE_DIR set, JAX reads it itself and no directory is
set in code. Otherwise the cache lives at a FIXED path inside the
checkout (`.jax_cache`, git-ignored) — the directory is part of JAX's
cache key, so a path derived from a temp name, a pid or a time would
never hit.
"""

from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # Most lane programs compile in well under JAX's default one-second
    # floor for persisting an entry, and there are dozens of them: keep
    # every program, whatever it cost to compile.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
