"""The server child: say which device this process holds, then become the
program's own server through its user entry point.

    python benchmarks/serve.py <run_dir> <platform> <chips> <server args...>

Writes `<run_dir>/device.json` (platform, device_kind, count as JAX reports
them) and exits non-zero unless the platform and the count are the cell's.
After the server has shut down (SIGTERM), writes `<run_dir>/memory.json`: the
peak bytes in use on the fullest device. Sets nothing of the program.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv: list[str]) -> int:
    run_dir, platform, chips, server_args = argv[1], argv[2], int(argv[3]), argv[4:]
    sys.path.insert(0, os.path.dirname(HERE))
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    with open(os.path.join(run_dir, "device.json.tmp"), "w") as f:
        json.dump(device, f)
    os.replace(os.path.join(run_dir, "device.json.tmp"),
               os.path.join(run_dir, "device.json"))
    if device["platform"] != platform or device["count"] != chips:
        print(f"serve: wanted {chips} x {platform}, jax.devices() gives "
              f"{device}", file=sys.stderr)
        return 3
    from minio_tpu.s3.server import main as server_main

    try:
        server_main(server_args)
    finally:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in devs]
        with open(os.path.join(run_dir, "memory.json"), "w") as f:
            json.dump({"memory_peak_bytes": max(peaks)}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
