"""chip_smoke.py's contract with the driver, checked without a chip.

The driver reads the LAST line of the script's stdout and wants exactly
{"ok": true, "device": {"platform", "kind", "count"}} — no further key,
no byte after it. PR 21 was refused for that line alone.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

import chip_smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_final_line_has_exactly_the_contract_keys():
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
           "jax": "0.9.0", "compile_cache": "/x"}  # extras must not leak
    last = chip_smoke.final_line(dev)
    assert set(last) == {"ok", "device"}
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["ok"] is True
    text = json.dumps(last)
    assert "\n" not in text
    assert json.loads(text) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_no_chip_exits_nonzero_fast_and_starts_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120, env=env,
                       cwd=ROOT)
    took = time.monotonic() - t0
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout and '"ok"' not in r.stdout
    # It stopped at the device phase: no build, no server, no later phase.
    assert "phase device" in r.stderr and "no TPU" in r.stderr
    for later in ("native", "kernels", "serve"):
        assert f"phase {later}" not in r.stderr
    assert r.stdout == ""
    assert took < 60, took


_STUB = textwrap.dedent("""
    import os, subprocess, sys, threading, time
    import chip_smoke

    def device(state):
        state["device"] = {"platform": "tpu", "kind": "stub", "count": 1}
        return dict(state["device"], extra="on an earlier line only")

    def noisy(state):
        print("noise: print()")
        sys.stdout.write("noise: sys.stdout\\n")
        os.write(1, b"noise: raw fd 1\\n")
        subprocess.run([sys.executable, "-c",
                        "print('noise: child stdout', flush=True)"])
        # A leaked non-daemon worker must not hold the exit either.
        threading.Thread(target=time.sleep, args=(60,)).start()
        if MODE == "fail":
            raise RuntimeError("phase failed")
        return {"note": "ran"}

    MODE = sys.argv[1]
    out_fd = chip_smoke.claim_stdout()
    chip_smoke.finish([("device", device), ("noisy", noisy)], out_fd)
""")


def _run_stub(mode: str):
    return subprocess.run([sys.executable, "-c", _STUB, mode],
                          capture_output=True, text=True, timeout=50,
                          cwd=ROOT, env=dict(os.environ, PYTHONPATH=ROOT))


def test_last_stdout_line_is_the_final_line_despite_noise():
    r = _run_stub("ok")
    assert r.returncode == 0, r.stderr
    assert "noise" not in r.stdout
    assert r.stdout.endswith("}\n") and not r.stdout.endswith("\n\n")
    lines = r.stdout.splitlines()
    assert [json.loads(ln).get("phase") for ln in lines[:-1]] == [
        "device", "noisy"]
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "tpu", "kind": "stub",
                               "count": 1}}
    # Nothing was lost: the noise went to stderr.
    for src in ("print()", "sys.stdout", "raw fd 1", "child stdout"):
        assert f"noise: {src}" in r.stderr


def test_failed_phase_exits_nonzero_without_a_final_line():
    r = _run_stub("fail")
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "phase failed" in r.stderr
