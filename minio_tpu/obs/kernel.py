"""Device-plane kernel observability: per-kernel latency + batch shape.

The TPU kernel plane was a black box beyond the rolling encode gauge —
profiling-driven kernel optimization (arxiv.org/pdf/2108.02692's program
of measure → specialize → re-measure for XOR/erasure codes) needs the
live latency distribution of each launch class, on each backend, from
the production serving path.

Families (rendered by admin/metrics.py through the shared registry):

- `minio_tpu_kernel_seconds{kernel,backend}` — wall time of one launch
  as observed by the dispatching host thread.
- `minio_tpu_kernel_batch_blocks{kernel,backend}` — batch rows staged
  into the most recent launch.
- `minio_tpu_kernel_batch_bytes{kernel,backend}` — bytes staged into
  the most recent launch.
- `minio_tpu_kernel_launches_total{kernel,backend}` — launch count.

Batched-dataplane families (minio_tpu/dataplane, docs/DATAPLANE.md):
`minio_tpu_dataplane_launches_total{op}` / `_requests_total{op}`
(amortization ratio), `_batch_fill{op}` (occupancy histogram),
`_queue_wait_seconds{op}` (submit→launch wait),
`_backpressure_total{op}` (bounded-queue rejections → 503 SlowDown).
Lane launches also ride `minio_tpu_kernel_seconds{kernel="dp_*"}`.

Timing semantics: JAX dispatch is asynchronous, so by default the
histogram records the host-side dispatch+launch wall time — cheap
(two clock reads + one observe, no device sync forced on the serving
pipeline) and already enough to catch recompiles, host staging stalls
and batch-shape regressions. Setting MTPU_KERNEL_SYNC=1 (or
set_sync(True)) blocks on the launch's outputs before stamping, turning
the family into true device-complete latency for profiling sessions —
never the default, because a forced sync would serialize the
dispatch-ahead encode pipeline it is measuring.

Compiles: `minio_tpu_jit_compiles_total{program}` and
`minio_tpu_jit_compile_seconds_total{program}` count JAX's own
backend-compile events (`count_compiles`).

Typed `kernel` trace records ride the bus under the same zero-overhead
subscriber gate as every other plane.
"""

from __future__ import annotations

import os
import time

from minio_tpu.obs.histogram import counter as _counter
from minio_tpu.obs.histogram import gauge as _gauge
from minio_tpu.obs.histogram import histogram as _histogram
from minio_tpu.obs.span import has_subscribers as _has_subscribers
from minio_tpu.obs.span import publish as _publish

_KERNEL_SECONDS = _histogram(
    "minio_tpu_kernel_seconds",
    "Kernel launch wall time by kernel and backend (host-observed; "
    "MTPU_KERNEL_SYNC=1 for device-complete timing)",
    ("kernel", "backend"))
_KERNEL_LAUNCHES = _counter(
    "minio_tpu_kernel_launches_total",
    "Kernel launches by kernel and backend", ("kernel", "backend"))
_KERNEL_BLOCKS = _gauge(
    "minio_tpu_kernel_batch_blocks",
    "Batch rows staged into the most recent kernel launch",
    ("kernel", "backend"))
_KERNEL_BYTES = _gauge(
    "minio_tpu_kernel_batch_bytes",
    "Bytes staged into the most recent kernel launch",
    ("kernel", "backend"))

# Batched-dataplane families (minio_tpu/dataplane, docs/DATAPLANE.md):
# how well coalescing amortizes the launch tax, observable live.
_DP_QUEUE_WAIT = _histogram(
    "minio_tpu_dataplane_queue_wait_seconds",
    "Submit-to-launch wait of one coalesced codec request", ("op",))
_DP_FILL = _histogram(
    "minio_tpu_dataplane_batch_fill",
    "Filled fraction of each coalesced lane launch (occupancy)", ("op",))
_DP_LAUNCHES = _counter(
    "minio_tpu_dataplane_launches_total",
    "Coalesced lane launches by op", ("op",))
_DP_REQUESTS = _counter(
    "minio_tpu_dataplane_requests_total",
    "Codec requests carried by coalesced launches", ("op",))
_DP_REJECTED = _counter(
    "minio_tpu_dataplane_backpressure_total",
    "Requests rejected at the bounded submission queue (503 SlowDown)",
    ("op",))

# Compiles, counted by the program itself: JAX reports every backend
# compile (a persistent-cache hit included, at the time the retrieval
# took) to its monitoring listeners. A request that meets a new shape
# pays this on its own thread; in a warm window both stay flat.
_JIT_COMPILES = _counter(
    "minio_tpu_jit_compiles_total",
    "XLA backend compiles (persistent-cache retrievals included) by "
    "program", ("program",))
_JIT_COMPILE_SECONDS = _counter(
    "minio_tpu_jit_compile_seconds_total",
    "Seconds spent in XLA backend compiles by program", ("program",))
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_listener_on = False

_SYNC = os.environ.get("MTPU_KERNEL_SYNC", "") in ("1", "true", "on")


def _on_event_duration(event: str, duration: float, **kw) -> None:
    if event != _COMPILE_EVENT:
        return
    # `fun_name` reads `jit(encode_with_digests)`.
    program = str(kw.get("fun_name") or "unknown")
    _JIT_COMPILES.labels(program=program).inc()
    _JIT_COMPILE_SECONDS.labels(program=program).inc(duration)


def count_compiles() -> None:
    """Register the compile listener, once a process (listeners are
    process-global in JAX). Called where the server initialises JAX."""
    global _compile_listener_on
    if _compile_listener_on:
        return
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_event_duration)
    _compile_listener_on = True


def set_sync(on: bool) -> None:
    """Force block_until_ready before stamping (profiling sessions)."""
    global _SYNC
    _SYNC = bool(on)


def sync_enabled() -> bool:
    return _SYNC


def dataplane_launch(op: str, filled: int, capacity: int,
                     waits: list[float]) -> None:
    """Record one coalesced launch: occupancy + per-request queue wait
    (submit to launch). Called by the dispatcher thread only."""
    _DP_LAUNCHES.labels(op=op).inc()
    _DP_REQUESTS.labels(op=op).inc(len(waits))
    if capacity:
        _DP_FILL.labels(op=op).observe(filled / capacity)
    wait_hist = _DP_QUEUE_WAIT.labels(op=op)
    for w in waits:
        wait_hist.observe(w)


def dataplane_rejected(op: str) -> None:
    """One submission bounced off the bounded queue (backpressure)."""
    _DP_REJECTED.labels(op=op).inc()


def observe(kernel: str, backend: str, t0: float, *,
            blocks: int = 0, nbytes: int = 0, out=None) -> None:
    """Record one launch: t0 from time.perf_counter() before dispatch;
    `out` is the launch's output pytree (synced only under MTPU_KERNEL_SYNC).
    Exceptions from a failed sync propagate — a launch that dies must not
    be recorded as fast."""
    if out is not None and _SYNC:
        import jax

        jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    _KERNEL_SECONDS.labels(kernel=kernel, backend=backend).observe(dt)
    _KERNEL_LAUNCHES.labels(kernel=kernel, backend=backend).inc()
    if blocks:
        _KERNEL_BLOCKS.set(blocks, kernel=kernel, backend=backend)
    if nbytes:
        _KERNEL_BYTES.set(nbytes, kernel=kernel, backend=backend)
    if _has_subscribers():
        rec = {"type": "kernel", "time": time.time(),
               "kernel": kernel, "backend": backend,
               "durationNs": int(dt * 1e9)}
        if blocks:
            rec["blocks"] = blocks
        if nbytes:
            rec["bytes"] = nbytes
        _publish(rec)
