"""Batched device data plane (docs/DATAPLANE.md).

Aggregates concurrent codec work — PUT shard-encodes, GET
reconstructions, bitrot verifies — from request threads into coalesced
fused-kernel launches (batcher.py) staged through a ring of
pre-allocated device-bound buffers (ring.py), instead of one dispatch
per object. Which work rides a lane and which launches directly is
decided in one place, route.py.

ON BY DEFAULT since the pipeline convergence (PR 12): the env gate is
opt-OUT — `MTPU_BATCHED_DATAPLANE=0` restores per-object dispatch,
which survives as the fallback and the bit-exactness oracle (the
chaos-storm oracle runs are its remaining deployment). The
process-global plane is created lazily on first use and lives for the
process (its threads are daemons named `mtpu-dataplane-*`, exempted as
session-lived in utils/sanitize.py); tests that build private planes
close() them.
"""

from __future__ import annotations

import os
import threading

from minio_tpu.dataplane.batcher import BatchPlane  # noqa: F401

ENABLE_ENV = "MTPU_BATCHED_DATAPLANE"

_global_mu = threading.Lock()
_global_plane: BatchPlane | None = None
# Optional plane router (the multi-process front door installs one so
# non-owner workers route submissions over the shared-memory lane ring
# — minio_tpu/frontdoor/laneserver.py). Called under the env gate;
# returning None falls through to the process-local plane.
_router = None


def enabled() -> bool:
    """Read the env gate live — cheap, and tests flip it per-case.
    Default ON; "0"/"false"/"off" opts out (per-object oracle)."""
    return os.environ.get(ENABLE_ENV, "1") not in ("0", "false", "off")


def get_plane() -> BatchPlane:
    """The process-global plane, created on first use."""
    global _global_plane
    with _global_mu:
        if _global_plane is None or _global_plane.closed:
            _global_plane = BatchPlane()
        return _global_plane


def set_router(fn) -> None:
    """Install (or clear, with None) a plane router consulted by
    maybe_plane before the process-local plane."""
    global _router
    _router = fn


def maybe_plane() -> BatchPlane | None:
    """The global plane when the gate is on, else None (per-object
    dispatch). The serving integration points call this per batch."""
    if not enabled():
        return None
    if _router is not None:
        plane = _router()
        if plane is not None:
            return plane
    return get_plane()


def reset_global() -> None:
    """Close and drop the global plane (tests; safe when never built)."""
    global _global_plane
    with _global_mu:
        plane, _global_plane = _global_plane, None
    if plane is not None:
        plane.close()
