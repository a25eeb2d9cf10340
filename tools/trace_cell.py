#!/usr/bin/env python3
"""One traced run of a benchmark cell that keeps what the harness throws away.

    chiprun -- python3 tools/trace_cell.py --workload <cell> --seed <n> \\
        [--seconds 20] [--out chiprun_out/trace_cell]

`benchmarks/run.py` reduces the device trace to a few numbers and removes the
run's directory. This runs the same `run_cell(..., trace=True)`, unchanged,
and at two of its call sites keeps more, under `<out>/<cell>.<seed>.*`:

- `.xplane.pb.gz`: the profiler's trace itself (docs/TRACING.md says how
  to open it);
- `.gaps.json`: `benchmarks/host_gaps.py` over it: the device's idle gaps
  named by the `mtpu/` host span open in them, `idle_attributed_pct`;
- `.timelines.json`: `/minio/admin/v3/perf/timeline` of PutObject and
  GetObject right after the slice (the recorder's last 256 requests, each
  entry with `t0`, `start_ns`, `parent`, `n`), and the slice's own length;
- `.result.json`: the harness's result line.

Nothing here is part of the yardstick: it reads, and the numbers it prints
go to PERF.md by hand.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
sys.path.insert(0, BENCH)

import run  # noqa: E402  (benchmarks/run.py)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "trace_cell"))
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    stem = os.path.join(args.out, f"{args.workload}.{args.seed}")

    reduce_trace, take = run.reduce_trace, run.TraceSlice.take

    def reduce_keep(xplane: str, platform: str) -> dict:
        with open(xplane, "rb") as src, \
                gzip.open(stem + ".xplane.pb.gz", "wb") as dst:
            shutil.copyfileobj(src, dst)
        r = subprocess.run(
            [sys.executable, os.path.join(BENCH, "host_gaps.py"), xplane,
             platform], env=dict(os.environ, JAX_PLATFORMS="cpu"),
            stdout=subprocess.PIPE, stdin=subprocess.DEVNULL)
        with open(stem + ".gaps.json", "wb") as f:
            f.write(r.stdout)
        run.say("host gaps: " + r.stdout.decode(errors="replace").strip())
        return reduce_trace(xplane, platform)

    def take_keep(self) -> None:
        take(self)
        c = self.server.client()
        doc = {"slice_s": self.t_stop - self.t_begin}
        for api in ("PutObject", "GetObject"):
            r = c.request("GET", "/minio/admin/v3/perf/timeline",
                          query={"api": api, "all": "false"})
            doc[api] = json.loads(r.body)["timelines"] if r.ok else []
        c.close()
        with open(stem + ".timelines.json", "w") as f:
            json.dump(doc, f)
        run.say(f"traced slice: {doc['slice_s']:.3f} s from the start "
                "call to the download call")

    run.reduce_trace, run.TraceSlice.take = reduce_keep, take_keep
    try:
        result = run.run_cell(args.workload, args.seed, args.seconds, True)
    except run.RunFailed as e:
        run.note(f"trace_cell: {e}")
        return 3
    with open(stem + ".result.json", "w") as f:
        json.dump(result, f)
    run.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
