"""The codec route (minio_tpu/dataplane/route.py): one seam decides
between a coalescing lane and a direct launch.

`test_route_table` is the module's decision table as cases: every op,
a width at its gate and one byte over it, the plane enabled, disabled
(`MTPU_BATCHED_DATAPLANE=0`) and enabled with its lane parked and full.
Each case says which side served and holds the routed result to the
direct launch's, byte for byte. The layering tests keep the decision
from growing a second copy.
"""

import ast
import io
import pathlib
import time

import numpy as np
import pytest

from minio_tpu import dataplane
from minio_tpu.dataplane import route
from minio_tpu.dataplane.batcher import BatchPlane
from minio_tpu.erasure.codec import ErasureCodec
from minio_tpu.ops import fused
from minio_tpu.utils import errors as se

RNG = np.random.default_rng(20261002)
PKG = pathlib.Path(__file__).resolve().parent.parent / "minio_tpu"
K, M = 2, 1


def _blob(size: int) -> bytes:
    return RNG.integers(0, 256, size=size, dtype=np.uint8).tobytes()


def _flat(rows):
    return [[bytes(c) for c in row] for row in rows]


def _lost_one(codec: ErasureCodec, block_len: int):
    """One encoded block with shard 0 gone -> (rows, block_lens)."""
    chunks = codec.encode_blocks([_blob(block_len)])[0]
    return [[None] + chunks[1:]], [block_len]


# op -> (gate, run(width) -> (routed result, direct result)); `width`
# is the chunk width the gate sees.
def _encode(width: int):
    codec = ErasureCodec(K, M, 4 * route.ENCODE_GATE)
    blocks = [_blob(K * width - 1), _blob(17)]  # ceil((K*w - 1) / K) == w
    got_c, got_d = route.begin_encode(codec, blocks, with_digests=True).wait()
    want_c, want_d = codec.begin_encode(blocks, with_digests=True).wait()
    return (_flat(got_c), _flat(got_d)), (_flat(want_c), _flat(want_d))


def _decode(width: int):
    codec = ErasureCodec(K, M, 4 * route.RECON_GATE)
    rows, lens = _lost_one(codec, K * width - 1)
    return (_flat(route.decode_blocks(codec, rows, lens)),
            _flat(codec.decode_blocks(rows, lens)))


def _reconstruct(width: int):
    codec = ErasureCodec(K, M, 4 * route.RECON_GATE)
    rows, lens = _lost_one(codec, K * width - 1)
    got_c, got_d = route.begin_reconstruct(
        codec, rows, lens, (0,), with_digests=True).wait()
    want_c, want_d = codec.begin_reconstruct(
        rows, lens, (0,), with_digests=True).wait()
    return (_flat(got_c), _flat(got_d)), (_flat(want_c), _flat(want_d))


def _digest(width: int):
    chunks = [_blob(width), _blob(17)]
    return ([bytes(d) for d in route.digest_chunks(chunks, width)],
            fused.digest_chunks_host(chunks, width))


OPS = {
    "encode": (route.ENCODE_GATE, _encode),
    "decode": (route.RECON_GATE, _decode),
    "reconstruct": (route.RECON_GATE, _reconstruct),
    "digest": (route.ENCODE_GATE, _digest),
}


@pytest.fixture
def private_plane():
    """A small plane of the test's own behind `maybe_plane()`, as a
    front-door worker installs its ring client."""
    p = BatchPlane(queue_cap=2, max_wait_s=0.01)
    dataplane.set_router(lambda: p)
    yield p
    dataplane.set_router(None)
    p._gate.set()
    p.close()


def _park_and_fill(p: BatchPlane) -> list:
    """Park the dispatcher behind its gate and fill the bounded queue
    (test_dataplane's backpressure recipe) -> the handles to drain."""
    k, m, bs = 4, 2, 1 << 12
    p.begin_encode(k, m, bs, [_blob(64)]).wait()  # the dispatcher idles
    p._gate.clear()
    held = [p.begin_encode(k, m, bs, [_blob(64)])]  # walks it to the gate
    deadline = time.monotonic() + 10
    while not p._q.empty():
        assert time.monotonic() < deadline, "dispatcher never parked"
        time.sleep(0.005)
    held += [p.begin_encode(k, m, bs, [_blob(64)]) for _ in range(2)]
    return held


@pytest.mark.parametrize("lane", ["free", "off", "full"])
@pytest.mark.parametrize("over", [0, 1], ids=["at-gate", "over-gate"])
@pytest.mark.parametrize("op", list(OPS))
def test_route_table(op, over, lane, private_plane, monkeypatch):
    gate, run = OPS[op]
    monkeypatch.setenv(dataplane.ENABLE_ENV, "0" if lane == "off" else "1")
    held = _park_and_fill(private_plane) if lane == "full" else []
    before = private_plane.stats()
    tries_lane = lane != "off" and not over
    try:
        if op == "encode" and lane == "full" and not over:
            # PUT sheds: the S3 layer answers 503 SlowDown.
            with pytest.raises(se.OperationTimedOut, match="saturated"):
                run(gate + over)
        else:
            got, want = run(gate + over)
            assert got == want
    finally:
        private_plane._gate.set()
        for h in held:
            h.wait()
    after = private_plane.stats()
    moved = after["requests"] - before["requests"] - len(held)
    if tries_lane and lane == "free":
        assert moved > 0, "the lane did not serve"
        assert after["rejected"] == before["rejected"]
    else:
        assert moved == 0, "the lane served what the table sends direct"
        assert (after["rejected"] - before["rejected"]
                == (1 if tries_lane else 0))


def test_parityless_geometry_goes_direct(private_plane):
    """`codec.m == 0` has nothing to coalesce but digests: encode, decode
    and reconstruct stay direct; the digest route ignores `m`."""
    codec = ErasureCodec(2, 0, 1 << 12)
    blocks = [_blob(1000)]
    got_c, got_d = route.begin_encode(codec, blocks, with_digests=True).wait()
    want_c, want_d = codec.begin_encode(blocks, with_digests=True).wait()
    assert (_flat(got_c), _flat(got_d)) == (_flat(want_c), _flat(want_d))
    rows = [[bytes(c) for c in got_c[0]]]
    assert route.decode_blocks(codec, rows, [1000]) == rows
    assert private_plane.stats()["requests"] == 0


def test_full_lane_fails_the_put_with_slowdown(private_plane, tmp_path):
    """The shed reaches the PUT's caller as the exception S3 maps to
    503 SlowDown, and the object is not there afterwards."""
    from minio_tpu.erasure import ErasureObjects
    from minio_tpu.s3 import errors as s3err
    from minio_tpu.storage import LocalDrive

    es = ErasureObjects([LocalDrive(str(tmp_path / f"d{i}"))
                         for i in range(4)],
                        parity=2, bitrot_algorithm="mxsum256")
    try:
        es.make_bucket("bkt")
        body = _blob(100 << 10)  # past the inline limit, chunks under the gate
        held = _park_and_fill(private_plane)
        try:
            with pytest.raises(se.OperationTimedOut) as ei:
                es.put_object("bkt", "shed", io.BytesIO(body), len(body))
        finally:
            private_plane._gate.set()
            for h in held:
                h.wait()
        api = s3err.from_exception(ei.value).api
        assert (api.code, api.http_status) == ("SlowDown", 503)
        with pytest.raises(se.ObjectNotFound):
            es.get_object_info("bkt", "shed")
        es.put_object("bkt", "kept", io.BytesIO(body), len(body))
        _info, it = es.get_object("bkt", "kept")
        assert b"".join(it) == body
    finally:
        es.close()


# ---------------------------------------------------------------------------
# layering
# ---------------------------------------------------------------------------

def _imports(path: pathlib.Path) -> set[str]:
    names: set[str] = set()
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            names.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            names.add(node.module)
            names.update(f"{node.module}.{a.name}" for a in node.names)
    return names


@pytest.mark.parametrize(
    "path", sorted((PKG / "ops").glob("*.py")), ids=lambda p: p.name)
def test_ops_imports_no_higher_layer(path):
    """The kernel package knows nothing of the layers that call it."""
    for name in _imports(path):
        for upper in ("dataplane", "erasure", "storage"):
            assert not (name + ".").startswith(f"minio_tpu.{upper}."), (
                f"{path.name} imports {name}")


def test_dataplane_imports_no_erasure():
    for path in sorted((PKG / "dataplane").glob("*.py")):
        for name in _imports(path):
            assert not (name + ".").startswith("minio_tpu.erasure."), (
                f"dataplane/{path.name} imports {name}")


def test_lane_or_direct_is_decided_in_one_place():
    """`maybe_plane`, the width gates and the plane's own gate methods
    appear in the route and the planes' implementations only."""
    allowed = ("dataplane/", "frontdoor/")
    for path in sorted(PKG.rglob("*.py")):
        rel = path.relative_to(PKG).as_posix()
        text = path.read_text()
        assert "accepts_chunk" not in text, rel
        assert "accepts_recon_chunk" not in text, rel
        if not rel.startswith(allowed):
            assert "maybe_plane" not in text, rel
        if rel != "dataplane/route.py" and rel != "obs/calibration.py":
            assert "ENCODE_GATE" not in text and "RECON_GATE" not in text, rel
