"""HBM-resident hot-object tier (docs/HOTTIER.md).

The dataplane ring (PR 8) made device memory a *staging* detail: every
byte still round-trips drives on each GET. This tier makes it a
*serving* tier — the hottest objects' encoded data shards (+ their
mxsum bitrot digests) stay resident in pre-allocated device arrays, so
a hot GET is one device-side gather+digest launch and one D2H DMA:
zero drive opens, no quorum fan-out, no per-request host reassembly.

Gate: `MTPU_HOTTIER=1` (opt-in). The drive path is never removed — it
is the fallback on every miss AND the bit-exactness oracle
(tests/test_hottier.py). Correctness never rests on invalidation
timeliness: a tier hit requires the *freshly elected*
FileInfo (signature-validated by the metaplane set cache when armed)
to match the resident entry's identity exactly, so a stale entry can
only ever miss, never serve.

The process-global tier is created lazily on first use. In the
multi-process front door the real tier lives in worker 0 beside the
LaneServer; sibling workers install a router (set_router) whose client
rides the shm ring's OP_HOTGET so every worker's hot GETs coalesce
into worker 0's launches (minio_tpu/frontdoor/laneserver.py).
"""

from __future__ import annotations

import os
import threading

ENABLE_ENV = "MTPU_HOTTIER"

_global_mu = threading.Lock()
_global_tier = None
# Optional tier router (the multi-process front door installs one so
# non-owner workers route hot GETs over the shm ring — OP_HOTGET).
_router = None
# Optional process-global admit reader: fn(bucket, obj) -> (info,
# byte-iterator). Registered by servers that own a full object layer
# (frontdoor worker 0); per-miss readers from the erasure sets are
# used when a note carries one.
_reader = None


def enabled() -> bool:
    """Read the env gate live — opt-IN (the tier pins device memory)."""
    return os.environ.get(ENABLE_ENV, "0") in ("1", "true", "on")


def get_tier():
    """The process-global tier, created on first use."""
    global _global_tier
    with _global_mu:
        if _global_tier is None or _global_tier.closed:
            from minio_tpu.hottier.tier import HotObjectTier

            _global_tier = HotObjectTier()
        return _global_tier


def set_router(fn) -> None:
    """Install (or clear, with None) a tier router consulted by
    maybe_tier before the process-local tier."""
    global _router
    _router = fn


def set_reader(fn) -> None:
    """Register the process-global admit reader (or clear with None)."""
    global _reader
    _reader = fn


def default_reader():
    return _reader


def maybe_tier():
    """The serving tier when the gate is on, else None (drive path).
    The GET integration point calls this per request."""
    if not enabled():
        return None
    if _router is not None:
        tier = _router()
        if tier is not None:
            return tier
    return get_tier()


def reset_global() -> None:
    """Close and drop the global tier (tests; safe when never built)."""
    global _global_tier
    with _global_mu:
        tier, _global_tier = _global_tier, None
    if tier is not None:
        tier.close()
