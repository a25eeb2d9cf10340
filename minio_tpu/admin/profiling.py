"""Profiling plane: host cProfile + optional JAX device trace capture.

Role-equivalent of cmd/utils.go:276 startProfiler and the peer fan-out
(cmd/notification.go:286-301 StartProfiling/DownloadProfilingData): an
admin starts profiling on every node, lets the workload run, then downloads
one archive holding each node's profiles. The TPU-native addition is the
device trace — jax.profiler captures XLA/Pallas execution timelines
alongside the host CPU profile (SURVEY.md §5.1 TPU mapping)."""

from __future__ import annotations

import cProfile
import io
import os
import pstats
import shutil
import tempfile
import threading
import zipfile

from minio_tpu.obs import flight


class Profiler:
    """One node's profiling session (at most one active at a time).

    Kinds: `cpu` (cProfile), `device` (best-effort jax.profiler capture,
    silently absent when it can't run) and `tpu` — the explicit device
    plane: jax.profiler.start_trace/stop_trace whose capture dir rides
    the same zip_profiles / peer profile_download fan-out, degrading to
    a marker file explaining WHY when the host has no usable device
    profiler (CPU-only containers must not fail the cluster-wide
    profiling round, and an empty archive must not read as "captured
    nothing interesting").

    The device trace is taken WITHOUT the Python tracer
    (`python_tracer_level=0`): hooking every Python call stalls the
    serving threads for seconds at start and slows the host all through
    the slice. What the host did is in the trace all the same, as the
    `mtpu/<stage>` annotations of `obs.flight.span`, which the session
    arms for its length (docs/TRACING.md)."""

    def __init__(self):
        self._mu = threading.Lock()
        self._cpu: cProfile.Profile | None = None
        self._jax_dir: str | None = None
        self._jax_name: str | None = None
        self._tpu_marker: str | None = None

    @property
    def running(self) -> bool:
        return (self._cpu is not None or self._jax_dir is not None
                or self._tpu_marker is not None)

    def start(self, kinds: tuple[str, ...] = ("cpu",)) -> None:
        with self._mu:
            if self.running:
                raise RuntimeError("profiler already running")
            if "cpu" in kinds:
                self._cpu = cProfile.Profile()
                self._cpu.enable()
            device_kind = ("tpu" if "tpu" in kinds
                           else "device" if "device" in kinds else None)
            if device_kind is not None:
                d = tempfile.mkdtemp(prefix="mtpu-jaxprof-")
                try:
                    import jax

                    backend = jax.default_backend()
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    opts.host_tracer_level = 2
                    jax.profiler.start_trace(d, profiler_options=opts)
                    flight.set_profiling(True)
                    self._jax_dir = d
                    self._jax_name = ("tpu_trace.zip"
                                      if device_kind == "tpu"
                                      else "device_trace.zip")
                    if device_kind == "tpu" and backend == "cpu":
                        # Capture runs (host trace), but flag the backend
                        # so the archive reader knows no TPU was profiled.
                        self._tpu_marker = (
                            "jax.default_backend() == 'cpu': trace holds "
                            "host/XLA-CPU events only, no TPU timeline")
                except Exception as e:  # noqa: BLE001 - no device/profiler
                    shutil.rmtree(d, ignore_errors=True)
                    if device_kind == "tpu":
                        self._tpu_marker = (
                            f"device trace unavailable on this host: "
                            f"{type(e).__name__}: {e}")

    def stop_collect(self) -> dict[str, bytes]:
        """Stop everything and return {filename: payload}."""
        out: dict[str, bytes] = {}
        with self._mu:
            if self._cpu is not None:
                self._cpu.disable()
                stats = pstats.Stats(self._cpu)
                txt = io.StringIO()
                stats.stream = txt
                stats.sort_stats("cumulative").print_stats(100)
                out["cpu.txt"] = txt.getvalue().encode()
                with tempfile.NamedTemporaryFile(suffix=".pstats",
                                                 delete=False) as f:
                    tmp = f.name
                stats.dump_stats(tmp)
                # mtpu: allow(MTPU002) - admin cold path: stop() runs once
                # per profiling session and _mu only guards profiler state
                with open(tmp, "rb") as f:
                    out["cpu.pstats"] = f.read()
                os.unlink(tmp)
                self._cpu = None
            if self._jax_dir is not None:
                flight.set_profiling(False)
                try:
                    import jax

                    jax.profiler.stop_trace()
                except Exception:  # noqa: BLE001
                    pass
                buf = io.BytesIO()
                with zipfile.ZipFile(buf, "w", zipfile.ZIP_DEFLATED) as z:
                    for root, _dirs, files in os.walk(self._jax_dir):
                        for fn in files:
                            p = os.path.join(root, fn)
                            z.write(p, os.path.relpath(p, self._jax_dir))
                out[self._jax_name or "device_trace.zip"] = buf.getvalue()
                shutil.rmtree(self._jax_dir, ignore_errors=True)
                self._jax_dir = None
                self._jax_name = None
            if self._tpu_marker is not None:
                out["tpu_trace.MARKER.txt"] = self._tpu_marker.encode()
                self._tpu_marker = None
        return out


def zip_profiles(per_node: dict[str, dict[str, bytes]]) -> bytes:
    """Bundle every node's profile files into one archive
    (DownloadProfilingData's zip, cmd/notification.go:301)."""
    buf = io.BytesIO()
    with zipfile.ZipFile(buf, "w", zipfile.ZIP_DEFLATED) as z:
        for node, files in per_node.items():
            for name, payload in files.items():
                z.writestr(f"{node}/{name}", payload)
    return buf.getvalue()
