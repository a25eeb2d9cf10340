"""The benchmark's arithmetic, each against numbers worked by hand."""

import json
import os

import numpy as np
import pytest

import reference
import rehearsal
import scrape
import trace_reduce
import traffic
import verify
import work

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


# --- the plain reference ---------------------------------------------------


def test_gf_and_the_2_plus_2_code_by_hand():
    assert reference.gf_mul(0x80, 2) == 0x1D          # x^8 = x^4+x^3+x^2+1
    assert reference.gf_mul(3, 3) == 5 and reference.gf_mul(3, 4) == 12
    # V = [[1,0],[1,1],[1,2],[1,3]]; its top block is its own inverse, so
    # the parity rows are [1^2, 2] and [1^3, 3].
    assert reference.parity_rows(2, 2) == ((3, 2), (2, 3))
    data = np.array([[1, 2], [3, 4]], dtype=np.uint8)
    # p0 = 3*d0 ^ 2*d1 = [3^6, 6^8]; p1 = 2*d0 ^ 3*d1 = [2^5, 4^12]
    assert reference.encode_block(data, 2).tolist() == [[5, 14], [7, 8]]


def test_shard_files_layout_by_hand():
    body = bytes(range(1, 8))                          # 7 bytes, block 4, k=2
    files = reference.shard_files(body, 2, 2, 4, digest=lambda row: b"D")
    # block 1 = 1,2,3,4 -> rows [1,2] [3,4]; block 2 = 5,6,7 padded to
    # [5,6] [7,0].
    assert files[0] == b"D" + bytes([1, 2]) + b"D" + bytes([5, 6])
    assert files[1] == b"D" + bytes([3, 4]) + b"D" + bytes([7, 0])
    assert files[2][:3] == b"D" + bytes([5, 14])


def test_reference_agrees_with_the_program_it_never_imports():
    from minio_tpu.erasure.metadata import hash_order
    from minio_tpu.ops import gf, mxsum

    rng = np.random.default_rng(7)
    for k, m in ((12, 4), (8, 4), (2, 2)):
        d = rng.integers(0, 256, (k, 999), dtype=np.uint8)
        assert np.array_equal(reference.encode_block(d, m),
                              gf.encode_ref(d, m))
    for n in (0, 1, 16384, 87382):
        c = rng.integers(0, 256, n, dtype=np.uint8)
        assert reference.mxsum256(c) == mxsum.digest_np(c)
    for key in ("bench/a", "bench/s1/w0t0/0000001"):
        want = [s - 1 for s in hash_order(key, 16)]
        assert reference.shard_of_drive(*key.split("/", 1), 16) == want
    assert reference.write_quorum(12, 4) == 12
    assert reference.write_quorum(2, 2) == 3


# --- work and bytes --------------------------------------------------------


def test_codec_bytes_by_hand():
    mib = 1 << 20
    # 12+4, 10 MiB: 10 blocks; a shard row is ceil(2^20/12) = 87382 bytes.
    assert work.codec_bytes("PUT", 10 * mib, 12, 4, mib) == (
        10 * mib + 10 * 87382 * 4 + 10 * 16 * 32)
    assert work.codec_bytes("GET", 10 * mib, 12, 4, mib) == (
        10 * 87382 * 12 + 10 * 12 * 32)
    # 8+4, 128 KiB: one block, rows of exactly 16384 bytes.
    assert work.codec_bytes("PUT", 131072, 8, 4, mib) == (
        131072 + 16384 * 4 + 12 * 32)
    # 512 int-ops per input byte at m = 4, plus 16 per byte hashed.
    assert work.codec_int_ops("PUT", 131072, 8, 4, mib) == (
        16384 * 2 * 64 * 32 + 16384 * 12 * 16)
    least = work.least_seconds([("PUT", 131072)], 8, 4, mib, "TPU v5 lite")
    assert least["hbm_s"] == pytest.approx(196992 / 819e9)
    with pytest.raises(KeyError):
        work.peaks("TPU v9")


# --- scrape deltas ---------------------------------------------------------


def test_scrape_parser_and_deltas_on_two_recorded_expositions():
    before, after = rehearsal.recorded_scrapes()
    key = ("minio_tpu_stage_seconds_sum",
           (("api", "PutObject"), ("plane", "s3"), ("stage", "rx_drain")))
    assert before[key] == 0.022415 and after[key] == 0.122415
    here = os.path.dirname(DATA)
    with open(os.path.join(os.path.dirname(here), "layer_metrics",
                           "entry_ms_per_op.json")) as f:
        spec = json.load(f)
    # PutObject: auth +0.001, rx_drain +0.1, resp_drain +0.004 over 4 more
    # requests; GetObject unchanged -> 105 ms / 4.
    assert scrape.delta_ratio(before, after, spec, {}) == pytest.approx(26.25)
    fsyncs = {"numerator": [{"family": "minio_tpu_metaplane_fsyncs_total"}],
              "denominator": "client_ops"}
    assert scrape.delta_ratio(before, after, fsyncs,
                              {"client_ops": 4}) == pytest.approx(4.0)
    assert scrape.delta_ratio(before, after, fsyncs,
                              {"client_ops": 0}) is None
    assert scrape.backends(after) == {"native": 7.0}
    table = scrape.stage_table(before, after)
    assert table["PutObject"]["rx_drain"] == [25.0, 4]
    assert "GetObject" not in table


# --- the trace reduction ---------------------------------------------------


def test_union_counts_overlap_once():
    assert trace_reduce.union([(0, 10), (5, 12), (20, 30), (30, 31)]) == [
        (0, 12), (20, 31)]


def _planes(devices=1):
    ops = [("%fusion.1 = s32[8] fusion(...)", 100, 50),
           ("%all-reduce.2 = u8[4] all-reduce(...)", 140, 30),   # overlaps
           ("%copy.3 = u8[4] copy(...)", 400, 100)]
    mods = [("jit_encode(123)", 100, 80), ("jit_other(9)", 400, 100)]
    host = {"name": "/host:CPU", "lines": [{"name": "", "events": [
        ("$run", 0, 1000)]}]}
    devs = [{"name": f"/device:TPU:{i}", "lines": [
        {"name": "XLA Modules", "events": mods},
        {"name": "XLA Ops", "events": ops if i == 0 else ops[:1]}]}
        for i in range(devices)]
    return [host, *devs]


def test_reduction_by_hand_one_device():
    red = trace_reduce.reduce_planes(_planes(1), "tpu")
    # busy = [100,170) + [400,500) = 170 ns of a 1000 ns slice
    assert red["busy_s"] == pytest.approx(170e-9)
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["metrics"]["device_idle_pct"] == pytest.approx(83.0)
    assert "collective_pct" not in red["metrics"]
    assert red["breakdown"]["device_ops"][0] == [
        "jit_other/%copy.3", pytest.approx(100e-9)]
    gaps = dict(map(tuple, red["breakdown"]["idle_gaps"]))
    assert gaps["before jit_encode"] == pytest.approx(100e-9)
    assert gaps["before jit_other"] == pytest.approx(230e-9)
    assert gaps["before the slice's end"] == pytest.approx(500e-9)


def test_reduction_by_hand_four_devices():
    red = trace_reduce.reduce_planes(_planes(4), "tpu")
    # device 0 is the busiest (170 ns); the others ran one op (50 ns)
    assert red["busy_s_busiest"] == pytest.approx(170e-9)
    assert red["busy_s"] == pytest.approx((170 + 3 * 50) / 4 * 1e-9)
    assert red["metrics"]["collective_pct"] == pytest.approx(100 * 30 / 170)


def test_no_device_plane_is_a_failed_reduction():
    with pytest.raises(SystemExit):
        trace_reduce.reduce_planes(_planes(1)[:1], "tpu")
    with pytest.raises(SystemExit):
        trace_reduce.reduce_planes(_planes(1), "gpu")


# --- the generator ---------------------------------------------------------


def test_traffic_follows_from_the_seed():
    with open(os.path.join(os.path.dirname(os.path.dirname(DATA)), "traffic",
                           "get-10MiB.json")) as f:
        mix = json.load(f)
    a = traffic.OpStream(mix, 3000000000, 1, 2)
    b = traffic.OpStream(mix, 3000000000, 1, 2)
    assert [a.next() for _ in range(5)] == [b.next() for _ in range(5)]
    pre = traffic.preload_objects(mix, 3000000000)
    assert len(pre) == 64 and {o.size for o in pre} == {10485760}
    assert sum(traffic.split_clients(mix)) == 20
    assert traffic.make_body(5, 64, 1) == traffic.make_body(5, 64, 1)
    assert traffic.make_body(5, 64, 1) != traffic.make_body(6, 64, 1)


def test_reduction_of_a_recorded_v5e_trace(tmp_path):
    """A cut of a real trace (one v5e, `ec12p4-16d.put-10MiB`, PR 24): six
    `jit_encode_with_digests` launches of 654.9 us each, read through
    jax.profiler.ProfileData as the reduction child reads it."""
    out = tmp_path / "red.json"
    assert trace_reduce.main(
        ["trace_reduce.py", os.path.join(DATA, "put-10MiB.v5e.xplane.pb"),
         str(out), "tpu"]) == 0
    red = json.loads(out.read_text())
    assert list(red["devices"]) == ["/device:TPU:0"]
    assert red["busy_s"] == pytest.approx(0.003930865, rel=1e-6)
    assert red["busy_s"] == red["busy_s_busiest"]
    assert dict(map(tuple, red["modules"]))[
        "jit_encode_with_digests"] == pytest.approx(6 * 654.9e-6, rel=1e-3)
    assert red["breakdown"]["device_ops"][0][0] == (
        "jit_encode_with_digests/%gf2_matmul_with_weights.1")
    assert red["metrics"]["device_idle_pct"] == pytest.approx(
        100 * (1 - red["busy_s"] / red["window_s"]))
    assert 99 < red["metrics"]["device_idle_pct"] < 100
    assert len(red["breakdown"]["idle_gaps"]) <= 10


class _Answer:
    def __init__(self, status):
        self.status, self.body = status, b""


def _patient(answers, health, give_up=60.0):
    """A read back against a program that gives `answers` in turn and says
    of its health what `health` holds in turn; a quarter second a sleep."""
    now = [0.0]
    answers, health = iter(answers), iter(health)
    fetch = verify.PatientFetch(
        lambda key: _Answer(next(answers)), lambda: next(health), give_up,
        clock=lambda: now[0],
        sleep=lambda s: now.__setitem__(0, now[0] + s))
    return fetch, now


@pytest.mark.parametrize("answers,health,status,asked_again", [
    # the answer, at once
    ([200], [True], 200, 0),
    # a 503 asks for a retry, healthy or not
    ([503, 503, 200], [True, True, True, True, True], 200, 2),
    # no drive to ask: 404 from a program that says it is not healthy is
    # late, not wrong; asked again once it is healthy
    ([404, 200], [True, False, False, True], 200, 1),
    # not healthy at the close: the first question waits
    ([200], [False, False, True], 200, 0),
    # an outage that began and ended under one request
    ([0, 200], [True, True, True], 200, 1),
    # twice running with the program healthy before and after: judged by
    # what it says
    ([404, 404], [True, True, True, True], 404, 1),
    ([404, 503, 500, 500], [True] * 8, 500, 3),
])
def test_a_read_back_waits_for_a_late_answer_only(answers, health, status,
                                                  asked_again):
    fetch, _ = _patient(answers, health)
    assert fetch("k").status == status
    assert fetch.asked_again == asked_again
    assert len(fetch.log) == sum(a != 200 for a in answers)


def test_a_read_back_gives_up_a_minute_past_the_close():
    forever = iter(lambda: 503, None)
    fetch, now = _patient(forever, iter(lambda: True, None), give_up=2.0)
    assert fetch("k").status == 503
    assert 2.0 < now[0] <= 2.5
    # and every later one is asked once
    assert fetch("k2").status == 503 and now[0] <= 2.5
