#!/usr/bin/env python3
"""Run a cell's control, at the cell's own size: the cell's clients and the
comparison that decides `correct`, against the plain reference put in the
program's place with one guarantee broken (`reference_server.py`).

    python benchmarks/control.py --workload <name> --seeds 1,2,3 --seconds 8 --break quorum|bitrot|bit-exact|rebuild|state|heal-zeros|heal-skip|none

Prints one line per seed: the numbers compared, and `correct`. It prints no
rate: nothing here is a measurement of the system.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402


def control(workload: str, seed: int, seconds: float, broken: str,
            root: str = run.ROOT,
            readback_wait_s: float = run.READBACK_WAIT_S) -> dict:
    os.environ["BENCH_CONTROL_BREAK"] = "" if broken == "none" else broken
    try:
        r = run.run_cell(workload, seed, seconds, False, platform="reference",
                         launcher=os.path.join(HERE, "reference_server.py"),
                         root=root, readback_wait_s=readback_wait_s)
    finally:
        del os.environ["BENCH_CONTROL_BREAK"]
    return {"workload": workload, "seed": seed, "break": broken,
            "correct": r["correct"], "attempted": r["attempted"],
            "compared": r["compared"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--break", dest="broken", required=True,
                    choices=("quorum", "bitrot", "bit-exact", "rebuild", "state",
                             "heal-zeros", "heal-skip", "none"))
    ap.add_argument("--readback-wait", type=float,
                    default=run.READBACK_WAIT_S,
                    help="seconds past the close that a read back waits "
                         "for a 503 to end (`quorum` never ends one)")
    args = ap.parse_args(argv)
    for seed in args.seeds.split(","):
        print(json.dumps(control(args.workload, int(seed), args.seconds,
                                 args.broken,
                                 readback_wait_s=args.readback_wait)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
