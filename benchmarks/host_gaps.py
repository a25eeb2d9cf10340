"""Device idle gaps, named by what the host was doing.

    JAX_PLATFORMS=cpu python benchmarks/host_gaps.py <xplane.pb> <platform>

`trace_reduce.py` names an idle gap of the busiest device by the program
that ended it. What the host did meanwhile is in the same trace when the
program annotates its stages: `minio_tpu/obs/flight.py` turns every span
into a `TraceAnnotation("mtpu/<stage>")` while a profiling session runs,
and the profiler puts each on its thread's line of the `/host:CPU` plane, on
the clock of the device planes.

`attribute` is a pure function over the plane list `trace_reduce.read_planes`
returns. For each idle interval of the busiest device it names the
innermost `mtpu/` span that was open for the largest part of it: on the
thread that issued the program which ended the gap, where that can be told
(the line that holds the program's `PjitFunction(<name>)` call) and that
thread had a span open for at least half of the gap; else the span that
holds the most thread-time of the gap over all threads (twenty requests are
in flight: what most host threads were in); `no_request` where none was
open. A trace with no `mtpu/` event
(the parent commit's, or the recorded one under `tests/data`) gives None:
there is nothing to read, and nothing is made up.
"""

from __future__ import annotations

import bisect
import heapq
import json
import sys

from trace_reduce import MODULES_LINE, OPS_LINE, short, total, union

HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "mtpu/"
NO_REQUEST = "no_request"
_ISSUE_PREFIX = "PjitFunction("

Segment = tuple[float, float, str]      # [start, end) under one span name


def innermost_segments(spans: list[tuple[float, float, str]]) -> list[Segment]:
    """(start, end, name) spans, nested or overlapping -> disjoint segments,
    each named by the span that began last among those open (on one thread
    that is the innermost one). Time under no span gives no segment."""
    edges = sorted({t for s, e, _n in spans if e > s for t in (s, e)})
    order = sorted((s, e, n) for s, e, n in spans if e > s)
    out: list[Segment] = []
    heap: list[tuple[float, float, str]] = []     # (-start, end, name)
    i = 0
    for a, b in zip(edges, edges[1:]):
        while i < len(order) and order[i][0] <= a:
            s, e, n = order[i]
            heapq.heappush(heap, (-s, e, n))
            i += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        if not heap:
            continue
        name = heap[0][2]
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def shares(segments: list[Segment], starts: list[float], a: float,
           b: float) -> dict[str, float]:
    """name -> time of [a, b) under it; NO_REQUEST for the rest."""
    out: dict[str, float] = {}
    covered = 0.0
    i = max(0, bisect.bisect_right(starts, a) - 1)
    while i < len(segments) and segments[i][0] < b:
        s, e, n = segments[i]
        d = min(e, b) - max(s, a)
        if d > 0:
            out[n] = out.get(n, 0.0) + d
            covered += d
        i += 1
    if b - a - covered > 0:
        out[NO_REQUEST] = b - a - covered
    return out


def attribute(planes: list[dict], platform: str) -> dict | None:
    """-> {"idle_attributed_pct", "no_request_pct", "idle_s", "by_span":
    [[span, idle seconds of the gaps it names]...], "host_thread_s": [[span,
    thread-seconds under it while the device idled]...], "idle_gaps":
    [["<span> before <module>", seconds]...], "names": {the gap's name in
    trace_reduce: [[its names here, seconds]...]}}, or None when the trace
    holds no `mtpu/` event or no device plane."""
    host_lines = [ln for p in planes if p["name"] == HOST_PLANE
                  for ln in p["lines"]]
    per_line = [[(s, s + d, n[len(SPAN_PREFIX):]) for n, s, d in ln["events"]
                 if n.startswith(SPAN_PREFIX)] for ln in host_lines]
    if not any(per_line):
        return None
    prefix = f"/device:{platform.upper()}:"
    lo, hi = float("inf"), float("-inf")
    for p in planes:
        for ln in p["lines"]:
            for _n, s, d in ln["events"]:
                lo, hi = min(lo, s), max(hi, s + d)
    top = None
    for p in planes:
        if not p["name"].startswith(prefix):
            continue
        ops = [ev for ln in p["lines"] if ln["name"] == OPS_LINE
               for ev in ln["events"]]
        busy = union([(s, s + d) for _n, s, d in ops])
        if top is None or total(busy) > total(top["busy"]):
            top = {"busy": busy, "modules": sorted(
                (s, s + d, short(n)) for ln in p["lines"]
                if ln["name"] == MODULES_LINE for n, s, d in ln["events"])}
    if top is None or not top["busy"]:
        return None
    mod_starts = [m[0] for m in top["modules"]]

    def module_at(t: float) -> str | None:
        i = bisect.bisect_right(mod_starts, t) - 1
        m = top["modules"][i] if i >= 0 else None
        return m[2] if m is not None and t < m[1] else None

    # Where each program was issued: (start, line index) of its
    # PjitFunction(<name>) calls, by program name as the device has it.
    issued: dict[str, list[tuple[float, int]]] = {}
    for li, ln in enumerate(host_lines):
        for n, s, _d in ln["events"]:
            if n.startswith(_ISSUE_PREFIX) and n.endswith(")"):
                issued.setdefault("jit_" + n[len(_ISSUE_PREFIX):-1],
                                  []).append((s, li))
    for calls in issued.values():
        calls.sort()

    def issuing_line(module: str | None, t: float) -> int | None:
        calls = issued.get(module or "")
        if not calls:
            return None
        i = bisect.bisect_right(calls, (t, len(host_lines))) - 1
        return calls[i][1] if i >= 0 else None

    segs_all = innermost_segments([sp for line in per_line for sp in line])
    starts_all = [s for s, _e, _n in segs_all]
    segs_line = [innermost_segments(line) for line in per_line]
    starts_line = [[s for s, _e, _n in segs] for segs in segs_line]

    def thread_time(a: float, b: float) -> dict[str, float]:
        """span -> thread-time of [a, b) under it, over all host threads."""
        out: dict[str, float] = {}
        for segs, starts in zip(segs_line, starts_line):
            for name, d in shares(segs, starts, a, b).items():
                if name != NO_REQUEST:
                    out[name] = out.get(name, 0.0) + d
        return out

    idle = attributed = 0.0
    host_time: dict[str, float] = {}
    by_span: dict[str, float] = {}
    gaps: dict[str, float] = {}
    names: dict[str, dict[str, float]] = {}
    edges = [lo] + [e for _s, e in top["busy"]]
    nexts = [s for s, _e in top["busy"]] + [hi]
    for a, b in zip(edges, nexts):
        if b <= a:
            continue
        idle += b - a
        nobody = shares(segs_all, starts_all, a, b).get(NO_REQUEST, 0.0)
        attributed += (b - a) - nobody
        named = thread_time(a, b)
        for name, d in named.items():
            host_time[name] = host_time.get(name, 0.0) + d
        if nobody > 0:
            named[NO_REQUEST] = nobody
        module = module_at(b) if b < hi else None
        li = issuing_line(module, b)
        if li is not None:
            mine = shares(segs_line[li], starts_line[li], a, b)
            # A thread that sat idle for most of the gap (a dispatcher
            # between batches, an executor thread between requests) says
            # nothing about it: all threads' spans name it then.
            if mine.get(NO_REQUEST, 0.0) <= 0.5 * (b - a):
                named = mine
        span = max(named.items(), key=lambda kv: kv[1])[0]
        by_span[span] = by_span.get(span, 0.0) + (b - a)
        old = (f"before {module or '?'}" if b < hi
               else "before the slice's end")
        new = f"{span} {old}"
        gaps[new] = gaps.get(new, 0.0) + (b - a)
        names.setdefault(old, {})
        names[old][new] = names[old].get(new, 0.0) + (b - a)

    def ranked(d: dict[str, float], n: int = 10) -> list[list]:
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:n]]

    return {
        "idle_s": idle / 1e9,
        "idle_attributed_pct": 100.0 * attributed / idle if idle else 0.0,
        "no_request_pct": (100.0 * by_span.get(NO_REQUEST, 0.0) / idle
                           if idle else 0.0),
        "by_span": ranked(by_span, 20),
        "host_thread_s": ranked(host_time, 20),
        "idle_gaps": ranked(gaps),
        "names": {old: ranked(new, 3) for old, new in names.items()},
    }


def main(argv: list[str]) -> int:
    from trace_reduce import read_planes

    red = attribute(read_planes(argv[1]), argv[2])
    json.dump(red, sys.stdout)
    sys.stdout.write("\n")
    return 0 if red is not None else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
