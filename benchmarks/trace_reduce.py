"""Device trace -> numbers. Runs in a child of its own, on the CPU backend.

    JAX_PLATFORMS=cpu python benchmarks/trace_reduce.py <xplane.pb> <out.json> <platform>

Reads the profiler's `.xplane.pb` with `jax.profiler.ProfileData` and
nothing else. A device plane is one named `/device:<PLATFORM>:<n>`; on it,
the line `XLA Ops` holds one event per operation that ran on the device
(`XLA Modules` holds the jitted programs that contain them). Busy time is the
union of the op events' intervals, so nested or overlapping events count
once. An archive with no device plane, or with no op on any, is a failed
traced run: there is nothing to read, and nothing is made up.
"""

from __future__ import annotations

import bisect
import json
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE_MARKS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute", "psum",
                    "all_reduce", "all_gather", "ppermute")


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted, merged [start, end) intervals."""
    out: list[tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: list[tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def is_collective(name: str) -> bool:
    low = name.lower()
    return any(m in low for m in COLLECTIVE_MARKS)


def short(name: str) -> str:
    """`%fusion.1 = s32[...] fusion(...)` -> `%fusion.1`;
    `jit_encode(123456)` -> `jit_encode`."""
    name = name.split(" = ", 1)[0]
    if name.endswith(")") and "(" in name:
        head, _, tail = name.rpartition("(")
        if tail[:-1].isdigit():
            name = head
    return name


def reduce_planes(planes: list[dict], platform: str) -> dict:
    """planes: [{"name":, "lines": [{"name":, "events": [(name, start_ns,
    dur_ns), ...]}]}] -> the reduction. Pure, so a test can feed it."""
    prefix = f"/device:{platform.upper()}:"
    lo, hi = float("inf"), float("-inf")
    for p in planes:
        for ln in p["lines"]:
            for _n, s, d in ln["events"]:
                lo, hi = min(lo, s), max(hi, s + d)
    devices = []
    for p in planes:
        if not p["name"].startswith(prefix):
            continue
        ops = [ev for ln in p["lines"] if ln["name"] == OPS_LINE
               for ev in ln["events"]]
        mods = [ev for ln in p["lines"] if ln["name"] == MODULES_LINE
                for ev in ln["events"]]
        busy = union([(s, s + d) for _n, s, d in ops])
        devices.append({"name": p["name"], "ops": ops, "modules": mods,
                        "busy": busy, "busy_ns": total(busy)})
    if not devices:
        raise SystemExit(f"no {prefix}* plane in the trace: "
                         f"{[p['name'] for p in planes]}")
    if not any(d["busy_ns"] > 0 for d in devices):
        raise SystemExit("no operation ran on a device in the traced slice")
    window_ns = hi - lo
    top = max(devices, key=lambda d: d["busy_ns"])

    # Every op under the jitted program (module) that contains it.
    mods = sorted((s, s + d, short(n)) for n, s, d in top["modules"])
    mod_starts = [m[0] for m in mods]

    def module_of(t: float) -> str:
        i = bisect.bisect_right(mod_starts, t) - 1
        return mods[i][2] if i >= 0 and t < mods[i][1] else "?"

    by_op: dict[str, float] = {}
    coll = []
    for name, s, d in top["ops"]:
        label = f"{module_of(s)}/{short(name)}"
        by_op[label] = by_op.get(label, 0.0) + d
        if is_collective(name):
            coll.append((s, s + d))
    by_mod: dict[str, float] = {}
    for start, end, name in mods:
        by_mod[name] = by_mod.get(name, 0.0) + (end - start)

    # Idle gaps of the busiest device, named by the operation that ended
    # them: what the host did meanwhile is not in the trace.
    gaps: dict[str, float] = {}
    starts = sorted((s, n) for n, s, _d in top["ops"])
    edges = [lo] + [e for _s, e in top["busy"]]
    nexts = [s for s, _e in top["busy"]] + [hi]
    keys = [s for s, _n in starts]
    for end_prev, start_next in zip(edges, nexts):
        if start_next <= end_prev:
            continue
        i = bisect.bisect_left(keys, start_next)
        what = (f"before {module_of(start_next)}"
                if i < len(starts) and starts[i][0] == start_next
                else "before the slice's end")
        gaps[what] = gaps.get(what, 0.0) + (start_next - end_prev)

    def top10(d: dict[str, float]) -> list[list]:
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    metrics = {"device_idle_pct": 100.0 * (1.0 - top["busy_ns"] / window_ns)}
    if len(devices) > 1:
        metrics["collective_pct"] = (100.0 * total(union(coll))
                                     / top["busy_ns"])
    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(d["busy_ns"] for d in devices) / len(devices) / 1e9,
        "busy_s_busiest": top["busy_ns"] / 1e9,
        "devices": {d["name"]: d["busy_ns"] / 1e9 for d in devices},
        "metrics": metrics,
        "modules": top10(by_mod),
        "breakdown": {"device_ops": top10(by_op), "idle_gaps": top10(gaps)},
    }


def read_planes(path: str) -> list[dict]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [{"name": p.name,
             "lines": [{"name": ln.name,
                        "events": [(e.name, e.start_ns, e.duration_ns)
                                   for e in ln.events]}
                       for ln in p.lines]}
            for p in data.planes]


def main(argv: list[str]) -> int:
    xplane, out, platform = argv[1], argv[2], argv[3]
    red = reduce_planes(read_planes(xplane), platform)
    with open(out, "w") as f:
        json.dump(red, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
