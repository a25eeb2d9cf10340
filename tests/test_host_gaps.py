"""benchmarks/host_gaps.py: device idle gaps named by the host span open in
them, on hand-made plane lists; and benchmarks/trace_reduce.py held to its
recorded output on the real v5e trace. The two benchmark modules are
imported by path (the driver's tier-1 does not collect benchmarks/tests/);
everything runs on the CPU, with no device."""

import importlib.util
import json
import os
import sys

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
DATA = os.path.join(BENCH, "tests", "data")


def _load(name: str):
    """Import benchmarks/<name>.py the way run.py's children see it: with
    the benchmark directory on sys.path, under its own bare name."""
    if name in sys.modules:
        return sys.modules[name]
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def host_gaps():
    _load("trace_reduce")
    return _load("host_gaps")


MS = 1_000_000  # ns


def _planes(host_lines, ops, modules):
    """host_lines: [[(name, start_ms, dur_ms)...] per thread]; ops and
    modules: the device's events, same units."""
    def ns(evs):
        return [(n, s * MS, d * MS) for n, s, d in evs]

    return [
        {"name": "/host:CPU",
         "lines": [{"name": f"thread-{i}", "events": ns(evs)}
                   for i, evs in enumerate(host_lines)]},
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Modules", "events": ns(modules)},
                   {"name": "XLA Ops", "events": ns(ops)}]},
    ]


# One device program at [100, 101) ms and one at [200, 201): the slice
# runs from the first host event to the last one's end, so the gaps are
# [0, 100), [101, 200) and [201, 300).
OPS = [("%fusion.1 = u8[] fusion()", 100, 1),
       ("%fusion.1 = u8[] fusion()", 200, 1)]
MODULES = [("jit_encode_with_digests(1)", 100, 1),
           ("jit_lane_encode_k8m4_w16384_r4_d(2)", 200, 1)]
EDGE = [("slice", 0, 300)]   # a host event that is no span: the slice

CASES = {
    # A gap wholly inside one thread's enc_wait.
    "inside_enc_wait": (
        [EDGE, [("mtpu/encode", 90, 120), ("mtpu/enc_wait", 101, 99)]],
        "enc_wait before jit_lane_encode_k8m4_w16384_r4_d"),
    # The body is still arriving: the loop thread sits in rx_wait.
    "inside_rx_wait": (
        [EDGE, [("mtpu/rx_wait", 95, 110)]],
        "rx_wait before jit_lane_encode_k8m4_w16384_r4_d"),
    # Two threads' spans share the gap; the larger part names it.
    "two_threads_larger_part": (
        [EDGE, [("mtpu/rx_wait", 101, 30)], [("mtpu/enc_feed", 131, 69)]],
        "enc_feed before jit_lane_encode_k8m4_w16384_r4_d"),
    # Nobody had a request open.
    "no_span": (
        [EDGE, [("mtpu/tx_send", 250, 10)]],
        "no_request before jit_lane_encode_k8m4_w16384_r4_d"),
    # The thread that issued the next program wins over a longer span on
    # another thread: the launch came out of ITS enc_stage.
    "issuing_thread_preferred": (
        [EDGE,
         [("mtpu/rx_wait", 101, 99)],
         [("mtpu/enc_stage", 150, 50),
          ("PjitFunction(lane_encode_k8m4_w16384_r4_d)", 199, 1)]],
        "enc_stage before jit_lane_encode_k8m4_w16384_r4_d"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_gap_is_named_by_the_host_span(host_gaps, case):
    host_lines, want = CASES[case]
    red = host_gaps.attribute(_planes(host_lines, OPS, MODULES), "tpu")
    named = dict((k, v) for k, v in red["idle_gaps"])
    assert want in named, named
    assert named[want] == pytest.approx(0.099)
    # The reducer's own name of the same gap leads to the new one.
    old = "before jit_lane_encode_k8m4_w16384_r4_d"
    assert red["names"][old][0][0] == want


def test_idle_attributed_share(host_gaps):
    """[0,100) has no span, [101,200) is under enc_wait for 99 ms, [201,300)
    under a 10 ms tx_send: 109 of 298 idle ms had a request's span open."""
    host_lines = [EDGE, [("mtpu/enc_wait", 101, 99)],
                  [("mtpu/tx_send", 250, 10)]]
    red = host_gaps.attribute(_planes(host_lines, OPS, MODULES), "tpu")
    assert red["idle_s"] == pytest.approx(0.298)
    assert red["idle_attributed_pct"] == pytest.approx(100 * 109 / 298)
    by = dict((k, v) for k, v in red["by_span"])
    assert by["enc_wait"] == pytest.approx(0.099)
    assert by["no_request"] == pytest.approx(0.199)
    assert red["no_request_pct"] == pytest.approx(100 * 199 / 298)
    # What the host's threads were in while the device idled.
    assert dict(red["host_thread_s"]) == {
        "enc_wait": pytest.approx(0.099), "tx_send": pytest.approx(0.010)}


def test_innermost_span_names_nested_time(host_gaps):
    segs = host_gaps.innermost_segments([
        (0, 100, "encode"), (10, 40, "enc_stage"), (40, 90, "enc_wait")])
    assert segs == [(0, 10, "encode"), (10, 40, "enc_stage"),
                    (40, 90, "enc_wait"), (90, 100, "encode")]


def test_no_annotations_nothing_to_read(host_gaps):
    """The parent commit's traces hold no mtpu/ event: None, not zeros."""
    assert host_gaps.attribute(_planes([EDGE], OPS, MODULES), "tpu") is None


def test_recorded_trace_reduces_as_before(host_gaps):
    """trace_reduce.py on the recorded v5e trace (Python-tracer events
    only on its host plane) writes what the parent commit wrote, byte for
    byte; and host_gaps finds nothing to read there."""
    tr = _load("trace_reduce")
    planes = tr.read_planes(os.path.join(DATA, "put-10MiB.v5e.xplane.pb"))
    with open(os.path.join(DATA, "put-10MiB.v5e.reduced.json")) as f:
        want = f.read()
    assert json.dumps(tr.reduce_planes(planes, "tpu")) == want
    assert host_gaps.attribute(planes, "tpu") is None
