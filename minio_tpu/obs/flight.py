"""Always-on bounded flight recorder: per-request stage timelines.

Aggregate histograms say *that* a PUT took 4 ms; they cannot say where
the 4 ms went once the request crossed into the batch planes (dataplane
lanes, group-commit WAL, shm ring, hot tier). The flight recorder keeps
the critical-path decomposition per request:

- a `Timeline` rides the request's contextvars (the same channel the
  trace id uses, crossing executor hops via `obs.ctx_wrap`) and records
  two kinds of entries:

  * sequential **marks** — `mark("encode")` closes the segment from the
    previous mark (or request entry) to now. Sequential segments tile
    the request wall clock end to end, so their sum reconstructs the
    e2e latency (the stage-sum fidelity contract tested in tier-1);
  * detail **stamps** — `stamp("dp_queue_wait", dt, plane="dataplane")`
    attaches a plane-measured duration that overlaps a sequential
    segment (queue wait inside `encode`, fsync wait inside `commit`).
    Stamps attribute, marks account;
  * detail **spans** — `with span("enc_wait", "dataplane"): ...` times a
    section where the work happens. Repeated spans of one stage in one
    request accumulate into ONE entry (`dur` summed, `n` counted,
    `start` the first), so `minio_tpu_stage_seconds_count` stays one per
    request per stage and Δsum ÷ Δcount reads "ms per request".

  Every entry carries its start offset from the request's `t0` and a
  parent (the span open when it began, else the sequential segment that
  holds its start), so the stages of concurrent requests can be laid on
  one clock.

- `span()` is the ONE span primitive with three sinks: the Timeline
  entry above; the `internal` trace-bus record of `obs.span()` (by
  calling it, only while the bus has a subscriber); and — only while a
  device-profiling session runs (admin/profiling.py arms
  `set_profiling`) — a `jax.profiler.TraceAnnotation("mtpu/<stage>",
  trace_id=…, api=…)`, which lands on its thread's line of the
  `/host:CPU` plane of the `.xplane.pb`, on the profiler's own clock
  (docs/TRACING.md). With no session that sink costs one flag read.

- completed timelines land in a per-process bounded ring (last N
  requests) plus a slowest-N-per-API board, both queryable through
  `GET /minio/admin/v3/perf/timeline?traceid=|api=|worst=` — federated
  across front-door workers (shm spool, frontdoor/shm.py FlightSpool)
  and across peers the way `/metrics/cluster` fans out;
- every stage feeds the `minio_tpu_stage_seconds{api,stage,plane}`
  histogram family — the input for knob auto-tuning and SLO checks.

Zero-overhead contract (mirrors the trace bus): disarmed
(`MTPU_FLIGHT=0`), `begin()` never binds a Timeline, so every
`mark()`/`stamp()`/`span()`/`current()` on the hot path is one
contextvar read returning None (`span()` returns the shared no-op).
`Timeline.allocated` counts constructions so tests can assert the
disarmed path allocates nothing; `annotations` counts TraceAnnotation
constructions the same way for the no-session path.
"""

from __future__ import annotations

import contextvars
import os
import threading
import time
from collections import deque

from minio_tpu.obs.histogram import histogram
from minio_tpu.obs.span import _NOOP
from minio_tpu.obs.span import current_node as _current_node
from minio_tpu.obs.span import has_subscribers as _has_subscribers
from minio_tpu.obs.span import span as _bus_span
from minio_tpu.obs.span import trace_id as _trace_id

ARM_ENV = "MTPU_FLIGHT"
RING_ENV = "MTPU_FLIGHT_RING"
WORST_ENV = "MTPU_FLIGHT_WORST"

_ARMED = os.environ.get(ARM_ENV, "1") not in ("0", "false", "off")
_RING_N = max(1, int(os.environ.get(RING_ENV, "256") or 256))
_WORST_N = max(1, int(os.environ.get(WORST_ENV, "8") or 8))

_STAGE = histogram(
    "minio_tpu_stage_seconds",
    "Per-request stage latency decomposition across the planes",
    ("api", "stage", "plane"))

_tl: contextvars.ContextVar = contextvars.ContextVar(
    "mtpu_flight", default=None)

# Stage of the innermost open span on this thread of control: the parent
# of whatever span or stamp begins under it.
_open: contextvars.ContextVar = contextvars.ContextVar(
    "mtpu_flight_open", default=None)

# Device-profile sink: armed by admin/profiling.py for the length of a
# `tpu`/`device` session. `annotations` counts constructions (guard).
_PROFILING = False
_TraceAnnotation = None
annotations = 0

_mu = threading.Lock()
_ring: deque = deque(maxlen=_RING_N)        # completed snapshots, FIFO
_worst: dict[str, list] = {}                # api -> [(e2e_ns, snap)] desc
_sink = None                                # worker shm spool writer
_sibling_reader = None                      # reads other workers' spools
_worker = -1                                # front-door worker id, -1 solo


class Timeline:
    """One request's stage record. Thread-safe: plane threads stamp
    concurrently with the request thread marking (the batcher's finish
    thread materializes while the handler drains the response)."""

    allocated = 0  # class-level construction count (zero-overhead guard)

    __slots__ = ("trace_id", "api", "tenant", "_t0", "_cursor", "_stages",
                 "_spans", "_done", "_lock")

    def __init__(self, trace_id: str, api: str = ""):
        Timeline.allocated += 1
        self.trace_id = trace_id
        self.api = api
        self.tenant = ""
        now = time.perf_counter()
        self._t0 = now
        self._cursor = now
        # [stage, plane, dur_s, sequential, start_s from t0, parent, n]
        self._stages: list[list] = []
        self._spans: dict | None = None     # (stage, plane) -> its entry
        self._done = False
        self._lock = threading.Lock()

    def mark(self, stage: str, plane: str = "s3") -> None:
        """Close the sequential segment [previous mark, now)."""
        now = time.perf_counter()
        with self._lock:
            if self._done:
                return
            self._stages.append([stage, plane, now - self._cursor, True,
                                 self._cursor - self._t0, None, 1])
            self._cursor = now

    def stamp(self, stage: str, dur: float, plane: str,
              end: float | None = None) -> None:
        """Attach a plane-measured overlapping duration (seconds) that
        ended at `end` (perf_counter; now when not given)."""
        if end is None:
            end = time.perf_counter()
        with self._lock:
            if self._done:
                return
            self._stages.append([stage, plane, dur, False,
                                 max(0.0, end - dur - self._t0),
                                 _open.get(), 1])

    def add(self, stage: str, plane: str, t_start: float, dur: float,
            parent: str | None) -> None:
        """One closed span: accumulates into the stage's single entry."""
        with self._lock:
            if self._done:
                return
            spans = self._spans
            if spans is None:
                spans = self._spans = {}
            e = spans.get((stage, plane))
            if e is None:
                e = spans[(stage, plane)] = [
                    stage, plane, dur, False,
                    max(0.0, t_start - self._t0), parent, 1]
                self._stages.append(e)
            else:
                e[2] += dur
                e[6] += 1

    def finalize(self, status: int, final_stage: str | None) -> dict:
        now = time.perf_counter()
        with self._lock:
            self._done = True
            if final_stage is not None:
                self._stages.append(
                    [final_stage, "s3", now - self._cursor, True,
                     self._cursor - self._t0, None, 1])
            stages = list(self._stages)
        api = self.api or "unknown"
        segs = [e for e in stages if e[3]]
        for e in stages:
            _STAGE.labels(api=api, stage=e[0], plane=e[1]).observe(e[2])
            if not e[3] and e[5] is None:
                # Top-level detail: its parent is the sequential segment
                # that holds its start.
                e[5] = next((g[0] for g in segs
                             if g[4] <= e[4] < g[4] + g[2]), None)
        return {
            "trace_id": self.trace_id,
            "api": api,
            "tenant": self.tenant,
            "node": _current_node(),
            "worker": _worker,
            "time": time.time(),
            "t0": self._t0,
            "status": status,
            "e2e_ns": int((now - self._t0) * 1e9),
            "stages": [{"stage": s, "plane": p,
                        "dur_ns": int(d * 1e9), "seq": q,
                        "start_ns": int(st * 1e9), "parent": par, "n": n}
                       for s, p, d, q, st, par, n in stages],
        }


# --- request lifecycle -------------------------------------------------------


def begin(trace_id: str, api: str = "") -> Timeline | None:
    """Bind a fresh Timeline to the current context. Returns None (and
    binds nothing — zero allocation) when disarmed."""
    if not _ARMED:
        return None
    tl = Timeline(trace_id, api)
    _tl.set(tl)
    return tl


def current() -> Timeline | None:
    return _tl.get()


def set_api(api: str) -> None:
    tl = _tl.get()
    if tl is not None:
        tl.api = api


def set_tenant(tenant: str) -> None:
    tl = _tl.get()
    if tl is not None:
        tl.tenant = tenant


def mark(stage: str, plane: str = "s3") -> None:
    tl = _tl.get()
    if tl is not None:
        tl.mark(stage, plane)


def stamp(stage: str, dur: float, plane: str) -> None:
    tl = _tl.get()
    if tl is not None:
        tl.stamp(stage, dur, plane)


# --- the span primitive -------------------------------------------------------


class StageSpan:
    """One open span (see `span`)."""

    __slots__ = ("stage", "plane", "_tl", "_bus", "_ann", "_tok", "_t0")

    def __init__(self, stage: str, plane: str, tl, bus, ann):
        self.stage = stage
        self.plane = plane
        self._tl = tl
        self._bus = bus
        self._ann = ann
        self._tok = None
        self._t0 = 0.0

    def set(self, **kv) -> None:
        """Attrs discovered mid-span (byte counts): on the bus record."""
        if self._bus is not None:
            self._bus.set(**kv)

    def __enter__(self) -> "StageSpan":
        if self._ann is not None:
            self._ann.__enter__()
        if self._bus is not None:
            self._bus.__enter__()
        self._tok = _open.set(self.stage)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        now = time.perf_counter()
        parent = self._tok.old_value
        if parent is contextvars.Token.MISSING:
            parent = None
        _open.reset(self._tok)
        if self._tl is not None:
            self._tl.add(self.stage, self.plane, self._t0, now - self._t0,
                         parent)
        if self._bus is not None:
            self._bus.__exit__(exc_type, exc, tb)
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        return False


def span(stage: str, plane: str = "s3", timeline: bool = True, **attrs):
    """Timed section `stage`: `with flight.span("enc_wait", "dataplane")`.

    Sinks, each only when someone reads it: the request Timeline (a
    detail entry, accumulated per stage; `timeline=False` where a
    sequential `mark` of the same name already accounts the time, or on
    a plane thread that serves many requests at once), the trace bus
    (`obs.span(stage, **attrs)`, with a subscriber), the device profile
    (`TraceAnnotation("mtpu/<stage>")`, during a session). With none of
    the three this returns the shared no-op and constructs nothing."""
    tl = _tl.get()
    bus = _has_subscribers()
    if not _PROFILING and not bus and (tl is None or not timeline):
        return _NOOP
    ann = None
    if _PROFILING:
        global annotations
        annotations += 1
        ann = _TraceAnnotation(
            "mtpu/" + stage,
            trace_id=(tl.trace_id if tl is not None else _trace_id()) or "",
            api=tl.api if tl is not None else "", **attrs)
    return StageSpan(stage, plane, tl if timeline else None,
                     _bus_span(stage, **attrs) if bus else None, ann)


def set_profiling(on: bool) -> None:
    """Armed by the profiling session (admin/profiling.py) at start,
    cleared at stop: while set, every span is also a TraceAnnotation."""
    global _PROFILING, _TraceAnnotation
    if on and _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation

        _TraceAnnotation = TraceAnnotation
    _PROFILING = bool(on)


def end(status: int = 200, final_stage: str | None = "resp_drain") -> None:
    """Finalize the context timeline: close the trailing sequential
    segment, feed the stage histograms, record into the ring + worst
    board, and hand the snapshot to the worker spool sink if wired."""
    tl = _tl.get()
    if tl is None:
        return
    _tl.set(None)
    finish(tl, status=status, final_stage=final_stage)


def detached(trace_id: str, api: str) -> Timeline | None:
    """A Timeline NOT bound to the context — for server-side work whose
    originating request lives in another process (ring lane serves)."""
    if not _ARMED:
        return None
    return Timeline(trace_id, api)


def finish(tl: Timeline, status: int = 200,
           final_stage: str | None = None) -> dict:
    snap = tl.finalize(status, final_stage)
    with _mu:
        _ring.append(snap)
        board = _worst.setdefault(snap["api"], [])
        board.append((snap["e2e_ns"], snap))
        board.sort(key=lambda t: -t[0])
        del board[_WORST_N:]
    sink = _sink
    if sink is not None:
        try:
            sink(snap)
        # mtpu: allow(MTPU003) - the spool is a best-effort cross-worker
        # mirror; the local ring above already holds the snapshot, and a
        # recorder failure must never fail the request being recorded.
        except Exception:  # noqa: BLE001
            pass
    return snap


# --- wiring (worker fan-in) --------------------------------------------------


def armed() -> bool:
    return _ARMED


def set_armed(on: bool) -> None:
    """Test/bench hook — the production gate is MTPU_FLIGHT at boot."""
    global _ARMED
    _ARMED = bool(on)


def set_worker(worker: int) -> None:
    global _worker
    _worker = worker


def attach_sink(fn) -> None:
    """Every finished snapshot is also handed to `fn(snap)` — the
    front-door worker wires its shm FlightSpool writer here so the
    admin endpoint can read all workers' recorders from any worker."""
    global _sink
    _sink = fn


def set_sibling_reader(fn) -> None:
    """`fn() -> list[snap]` reading the OTHER workers' spools."""
    global _sibling_reader
    _sibling_reader = fn


def reset() -> None:
    """Drop recorded state (tests)."""
    global _sink, _sibling_reader
    with _mu:
        _ring.clear()
        _worst.clear()
    _sink = None
    _sibling_reader = None


# --- query -------------------------------------------------------------------


def _matches(snap: dict, traceid: str, api: str, tenant: str = "") -> bool:
    if traceid and snap.get("trace_id") != traceid:
        return False
    if api and snap.get("api") != api:
        return False
    if tenant and snap.get("tenant") != tenant:
        return False
    return True


def query(snaps, traceid: str = "", api: str = "",
          worst: int = 0, tenant: str = "") -> list[dict]:
    """Filter + order an iterable of snapshots: trace-id/api/tenant
    exact match; `worst` keeps the N slowest, else newest first."""
    out = [s for s in snaps if _matches(s, traceid, api, tenant)]
    if worst > 0:
        out.sort(key=lambda s: -s.get("e2e_ns", 0))
        return out[:worst]
    out.reverse()
    return out


def snapshot(traceid: str = "", api: str = "",
             worst: int = 0, tenant: str = "") -> list[dict]:
    """This process's recorder contents, filtered."""
    with _mu:
        if worst > 0:
            boards = ([_worst.get(api, [])] if api
                      else list(_worst.values()))
            snaps = [s for board in boards for _, s in board]
        else:
            snaps = list(_ring)
    return query(snaps, traceid, api, worst, tenant)


def collect(traceid: str = "", api: str = "",
            worst: int = 0, tenant: str = "") -> list[dict]:
    """Local recorder + sibling front-door workers' spools, filtered.
    Peer federation happens a layer up (admin/handlers.py), the same
    split /metrics/cluster uses."""
    snaps = snapshot(traceid, api, worst, tenant)
    reader = _sibling_reader
    if reader is not None:
        try:
            snaps = query(snaps + reader(), traceid, api, worst, tenant)
        # mtpu: allow(MTPU003) - a sibling worker mid-respawn (its spool
        # gone or half-built) degrades the answer to local-only; the
        # query must still serve what this worker has.
        except Exception:  # noqa: BLE001
            pass
    return snaps
