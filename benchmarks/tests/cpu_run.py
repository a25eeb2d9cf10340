"""`run.py`'s main for the tests: the same flow and the same last line, but
on the CPU backend through `cpu_serve.py` (the CLI itself takes a TPU or
nothing).

    python cpu_run.py <checkout root> <workload> <seed> <seconds>
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402


def main(argv: list[str]) -> int:
    root, workload, seed, seconds = argv[1], argv[2], int(argv[3]), float(argv[4])
    try:
        result = run.run_cell(
            workload, seed, seconds, False, platform="cpu",
            launcher=os.path.join(root, "benchmarks", "tests", "cpu_serve.py"),
            root=root)
    except run.RunFailed as e:
        run.note(f"cpu_run: {e}")
        return 3
    run.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
