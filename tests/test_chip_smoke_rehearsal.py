"""CPU rehearsal of chip_smoke.py's served phases at a tiny size.

The same phase functions the script runs on the chip, on 4 drives (EC 2+2)
with `bitrot_algorithm="mxsum256"` given explicitly — on the CPU backend
the default is the host-native sip256 lane, which never touches the codec
this smoke is about. Proves the control flow before chip time is spent;
says nothing about the device.
"""

from __future__ import annotations

import shutil

import pytest

import chip_smoke

PLAN = chip_smoke.Plan(drives=4, parity=2, small=(6, 10 << 10),
                       lane=(8, 24 << 10), medium=(3, (2 << 20) + 17),
                       parts=(2, 5 << 20), degrade=1, clients=4)
SEED = 22


@pytest.fixture(scope="module")
def smoke(tmp_path_factory):
    """The served phases run once, in the script's order; the tests read
    the lines they returned (so no test depends on another having run)."""
    from minio_tpu import dataplane

    root = str(tmp_path_factory.mktemp("chip-smoke"))
    server = chip_smoke.Server(root, PLAN, bitrot_algorithm="mxsum256")
    # Other tests of this worker have fed the process-wide registry.
    state: dict = {
        "kernel_launches_before": chip_smoke.kernel_launches(server)}
    lines = {
        "serve": chip_smoke.phase_serve(state, server, PLAN, SEED),
        "degraded_heal": chip_smoke.phase_degraded_heal(state, server, PLAN),
        "metrics": chip_smoke.phase_metrics(state, server, PLAN,
                                            platform="cpu"),
    }
    yield server, state, lines
    server.stop()
    dataplane.reset_global()
    shutil.rmtree(root, ignore_errors=True)


def test_serve_phase(smoke):
    _server, state, lines = smoke
    line = lines["serve"]
    assert line["objects"] == 6 + 8 + 3 + 1
    assert line["listed"] == {"s/": 6, "l/": 8, "m/": 3, "mp/": 1}
    assert set(state["want"]) == set(state["sizes"])


def test_degraded_and_heal_phase(smoke):
    line = smoke[2]["degraded_heal"]
    # One lane object, one medium object, the multipart object: m = 2
    # shards lost of each (one data, one parity position), every part.
    assert line["victims"] == ["l/0001", "m/0001", chip_smoke.MP_KEY]
    assert line["shard_files_removed"] == 2 * (1 + 1 + PLAN.parts[0])
    # Healed twice (MRF after the degraded GETs, then the admin heal),
    # every healed file verified frame by frame both times.
    assert line["frames_verified"] >= 2 * line["shard_files_removed"]
    assert line["heal_items"] >= 3


def test_metrics_phase_names_the_backend(smoke):
    server, state, lines = smoke
    line = lines["metrics"]
    assert line["stored_checksums"] == {
        "mxsum256": 8 + 3 + PLAN.parts[0]}
    assert line["inline_objects"] == 6
    assert line["lane_launches"] > 0
    on_cpu = line["kernel_launches"]["cpu:xla"]
    assert on_cpu["encode_digests"][0] > 0 and on_cpu["dp_encode"][0] > 0
    # Heal verifies its survivors on the device too: no host lane.
    assert "host" not in line["kernel_launches"]
    # The same phase on a host whose codec ran elsewhere must fail: that
    # is the check that keeps a host-lane pass from counting on the chip.
    with pytest.raises(SystemExit, match="kernel observations under"):
        chip_smoke.phase_metrics(state, server, PLAN, platform="tpu")


def test_frame_verifier_catches_a_flipped_byte(tmp_path):
    from minio_tpu.ops import mxsum

    chunks = [bytes(range(256)) * 4, b"tail-chunk"]
    path = tmp_path / "part.1"
    path.write_bytes(b"".join(mxsum.digest_np(c) + c for c in chunks))
    size = sum(len(c) for c in chunks)
    assert chip_smoke._verify_frames(str(path), 1024, size) == 2
    raw = bytearray(path.read_bytes())
    raw[40] ^= 1
    path.write_bytes(bytes(raw))
    with pytest.raises(SystemExit, match="frame 0 digest"):
        chip_smoke._verify_frames(str(path), 1024, size)
