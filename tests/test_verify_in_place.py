"""GET verifies a batch in place: each shard's records land in their rows
of the verify launch's staging array (`BitrotReader.read_records_into`,
one `os.preadv` of a local file), and the launch takes that array as it
lies (`fused.digest_staged_host`). Held here against `read_records` and
`fused.digest_chunks_host`, over every kind of source, and through the
object layer with `mxsum256` at EC 12+4: the digests, a flipped byte,
hedged spares with and without a slot, a straggler that writes into its
slot after the verify, the compiled shapes, and
`minio_tpu_get_verify_rows_total`."""

import io
import json
import os
import threading
import time

import numpy as np
import pytest

from minio_tpu.chaos.naughty import HANG, _SlowStream
from minio_tpu.dataplane import route
from minio_tpu.erasure import ErasureObjects
from minio_tpu.erasure import objects as objects_mod
from minio_tpu.erasure.codec import ErasureCodec
from minio_tpu.erasure.metadata import shuffle_by_distribution
from minio_tpu.ops import bitrot, fused
from minio_tpu.storage import LocalDrive
from minio_tpu.utils import errors as se

ALGO = "mxsum256"
DL = 32
MIB = 1 << 20
BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")


def _shard_file(path, data_size: int, shard_size: int) -> None:
    data = os.urandom(data_size)
    with open(path, "wb") as f:
        w = bitrot.BitrotWriter(f, shard_size, ALGO)
        for off in range(0, data_size, shard_size):
            w.write(data[off:off + shard_size])


def _source(kind: str, path, monkeypatch):
    """-> (src, reads()): a source over the file at `path` and how many
    reads the layer under the reader saw."""
    if kind in ("file", "fileio"):
        calls = []
        real = os.preadv
        monkeypatch.setattr(os, "preadv", lambda fd, bufs, off: (
            calls.append(off), real(fd, bufs, off))[1])
        monkeypatch.setattr(os, "pread", lambda *a: pytest.fail("pread"))
        src = open(path, "rb", buffering=-1 if kind == "file" else 0)
        return src, lambda: len(calls)
    monkeypatch.setattr(os, "preadv", lambda *a: pytest.fail("preadv"))
    if kind == "bytesio":
        with open(path, "rb") as f:
            src = io.BytesIO(f.read())
        real_read = src.read
        calls = []
        src.read = lambda *a: (calls.append(1), real_read(*a))[1]
        return src, lambda: len(calls)
    assert kind == "naughty"
    # The fault injector's pacing wrapper: it forwards fileno() to the
    # file inside, and the reader must still go through its read().
    paced = []
    monkeypatch.setattr(_SlowStream, "_pace", lambda self: paced.append(1))
    src = _SlowStream(open(path, "rb"), 0.001, threading.Event())
    assert src.fileno() >= 0
    return src, lambda: len(paced)


SOURCES = ["file", "fileio", "bytesio", "naughty"]
# (what, data_size, shard_size, first, count)
RANGES = [
    ("a whole batch", 10 * 1000, 1000, 0, 10),
    ("a short last chunk", 3 * 1000 + 17, 1000, 2, 2),
    ("a batch in mid-shard ending short", 25 * 1000 + 333, 1000, 18, 8),
    ("a shard of one short chunk", 5, 1000, 0, 1),
]


@pytest.mark.parametrize("kind", SOURCES)
@pytest.mark.parametrize("what,data_size,shard_size,first,count", RANGES,
                         ids=[r[0].replace(" ", "-") for r in RANGES])
def test_records_land_in_their_rows_and_digest_as_copied(
        tmp_path, monkeypatch, kind, what, data_size, shard_size, first,
        count):
    path = tmp_path / "shard"
    _shard_file(path, data_size, shard_size)
    with open(path, "rb") as f:
        expect = bitrot.BitrotReader(f, data_size, shard_size,
                                     ALGO).read_records(first, count)
    rows = fused.bucket_rows(count)
    # Rows that held other bytes before the read: a short chunk's tail
    # has to be zero after it, or its digest is wrong.
    stage = np.full((rows, shard_size), 0xA5, dtype=np.uint8)
    want = np.full((rows, DL), 0x5A, dtype=np.uint8)
    src, reads = _source(kind, path, monkeypatch)
    with src:
        r = bitrot.BitrotReader(src, data_size, shard_size, ALGO)
        chunks = r.read_records_into(first, count, want[:count],
                                     stage[:count])
    assert reads() == 1  # one read a shard a batch, whatever the source
    assert [bytes(c) for c in chunks] == [bytes(c) for _w, c in expect]
    assert [bytes(w) for w in want[:count]] == [w for w, _c in expect]
    for j, c in enumerate(chunks):
        assert isinstance(c, memoryview) and c.obj.base is stage
        assert not stage[j, len(c):].any()
    lens = np.zeros(rows, dtype=np.int32)
    lens[:count] = [len(c) for c in chunks]
    got = fused.digest_staged_host(stage, lens)
    host = fused.digest_chunks_host([bytes(c) for c in chunks], shard_size)
    assert [got[j].tobytes() for j in range(count)] == host
    assert host == [w for w, _c in expect]  # the stored digests


def test_a_scatter_read_that_answers_short_is_read_on(tmp_path, monkeypatch):
    _shard_file(tmp_path / "shard", 5000, 1000)
    real = os.preadv

    def short(fd, bufs, off):
        assert len(bufs) <= 3
        room = 700
        cut = []
        for b in bufs:
            cut.append(b[:room])
            room -= len(cut[-1])
            if not room:
                break
        return real(fd, cut, off)

    monkeypatch.setattr(os, "preadv", short)
    monkeypatch.setattr(bitrot, "_IOV_MAX", 3)
    stage = np.empty((8, 1000), dtype=np.uint8)
    want = np.empty((8, DL), dtype=np.uint8)
    with open(tmp_path / "shard", "rb") as src:
        r = bitrot.BitrotReader(src, 5000, 1000, ALGO)
        chunks = r.read_records_into(0, 5, want[:5], stage[:5])
        expect = r.read_records(0, 5)
    assert [(bytes(w), bytes(c)) for w, c in zip(want, chunks)] == [
        (w, bytes(c)) for w, c in expect]


def test_read_into_past_the_shard_or_a_closed_file_raises(tmp_path):
    _shard_file(tmp_path / "shard", 5000, 1000)
    stage = np.empty((8, 1000), dtype=np.uint8)
    want = np.empty((8, DL), dtype=np.uint8)
    with open(tmp_path / "shard", "r+b") as f:
        f.truncate(3 * (DL + 1000) + 10)
    src = open(tmp_path / "shard", "rb")
    r = bitrot.BitrotReader(src, 5000, 1000, ALGO)
    assert r.read_records_into(0, 0, want, stage) == []
    with pytest.raises(se.FileCorrupt):
        r.read_records_into(4, 2, want[:2], stage[:2])  # past the shard
    with pytest.raises(se.FileCorrupt):
        r.read_records_into(2, 3, want[:3], stage[:3])  # truncated file
    src.close()
    with pytest.raises(se.FaultyDisk):
        r.read_records_into(0, 2, want[:2], stage[:2])


# ---------------- through the object layer, mxsum256 at EC 12+4 ----------

K, M = 12, 4


class _Mrf:
    def __init__(self):
        self.calls = []

    def add_partial(self, bucket, obj, version_id, deep=False):
        self.calls.append((bucket, obj, deep))

    def close(self):
        pass


@pytest.fixture(scope="module")
def layer(tmp_path_factory):
    root = tmp_path_factory.mktemp("inplace")
    drives = [LocalDrive(str(root / f"d{i}")) for i in range(K + M)]
    # The served batch: 16 blocks, so a 10 MiB object is one batch of ten,
    # the [128, 87382] launch of the benchmark's GET cell.
    es = ErasureObjects(drives, parity=M, bitrot_algorithm=ALGO,
                        batch_blocks=16)
    es.hedge_delay = 60.0  # no spare reader unless a case asks for one
    es.make_bucket("bkt")
    body = os.urandom(10 * MIB)
    es.put_object("bkt", "whole", io.BytesIO(body), len(body))
    yield es, body
    es.close()
    for d in drives:
        d.close_wal()


def _get(es, key) -> bytes:
    _info, stream = es.get_object("bkt", key)
    return b"".join(stream)


def _rows_counted() -> tuple[int, int]:
    return (objects_mod._VERIFY_READ.value, objects_mod._VERIFY_COPIED.value)


def _delta(before) -> tuple[int, int]:
    return tuple(a - b for a, b in zip(_rows_counted(), before))


def _shard_paths(es, key) -> list[str]:
    fi = es._read_quorum_fileinfo("bkt", key, "")
    by_shard = shuffle_by_distribution(es.drives, fi.erasure.distribution)
    return [os.path.join(d.root, "bkt", key, fi.data_dir, "part.1")
            for d in by_shard]


def _read_rows(es, key, body_len, blocks, open_src, **kw):
    """_read_chunk_rows of blocks 0 … blocks − 1 over sources that
    open_src(shard index, path) makes -> (rows, readers, codec)."""
    fi = es._read_quorum_fileinfo("bkt", key, "")
    codec = ErasureCodec(K, M, fi.erasure.block_size)
    paths = _shard_paths(es, key)
    readers = [None] * (K + M)

    def open_reader(i):
        return bitrot.BitrotReader(
            open_src(i, paths[i]), codec.shard_file_size(body_len),
            codec.shard_size(), ALGO)

    rows = es._read_chunk_rows(
        readers, list(range(K)), list(range(blocks)), [MIB] * blocks,
        codec, K + M, kw.pop("dead", set()), ALGO,
        pool=es._shard_read_pool(), open_reader=open_reader, **kw)
    return rows, readers, codec


def _close(readers) -> None:
    for r in readers:
        if r is not None:
            r.src.close()


def _data(codec, rows) -> bytes:
    """The blocks the rows hold, each cut to its MIB (shard padding off)."""
    return b"".join(b"".join(bytes(c) for c in blk)[:MIB]
                    for blk in route.decode_blocks(codec, rows,
                                                   [MIB] * len(rows)))


def test_a_10mib_get_verifies_its_batch_in_place(layer, monkeypatch):
    es, body = layer
    preadvs, real = [], os.preadv
    monkeypatch.setattr(os, "preadv", lambda fd, bufs, off: (
        preadvs.append(len(bufs)), real(fd, bufs, off))[1])
    monkeypatch.setattr(fused, "digest_chunks_host",
                        lambda *a: pytest.fail("copied a chunk a row"))
    before = _rows_counted()
    assert _get(es, "whole") == body
    # 12 shards x 10 blocks, all read into their rows; one scatter read a
    # shard: a [digest, chunk] pair of iovecs a record.
    assert _delta(before) == (K * 10, 0)
    assert preadvs == [20] * K


def test_the_staged_launch_compiles_no_new_shape(layer):
    """The compile-count probe (tests/test_dataplane.py): the launch the
    copying verify makes for 120 rows of 87382 bytes is the one the
    staged verify makes."""
    es, body = layer
    fused.digest_chunks_host([b"\x01"] * (K * 10), -(-MIB // K))
    size = fused.verify_digests.__wrapped__._cache_size()
    assert _get(es, "whole") == body
    assert fused.verify_digests.__wrapped__._cache_size() == size


@pytest.mark.parametrize("kind", ["file", "bytesio", "naughty"])
def test_any_source_lands_in_place(layer, kind):
    """A plain file takes the scatter read; a BytesIO and the injector's
    pacing wrapper one read-exact and one copy a shard; all verify in
    place and give the object's bytes."""
    es, body = layer

    def open_src(_i, path):
        if kind == "file":
            return open(path, "rb")
        if kind == "bytesio":
            with open(path, "rb") as f:
                return io.BytesIO(f.read())
        return _SlowStream(open(path, "rb"), 0.0, threading.Event())

    before = _rows_counted()
    rows, readers, codec = _read_rows(es, "whole", len(body), 10, open_src)
    _close(readers)
    assert _delta(before) == (K * 10, 0)
    assert _data(codec, rows) == body


def test_a_flipped_byte_marks_only_its_shard_and_the_get_reselects(layer):
    es, body = layer
    es.put_object("bkt", "rotten", io.BytesIO(body), len(body))
    bad = 2
    with open(_shard_paths(es, "rotten")[bad], "r+b") as f:
        # a chunk byte of block 1
        f.seek((DL + -(-MIB // K)) + DL + 1234)
        b = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([b[0] ^ 0x10]))
    dead, corrupt = set(), set()
    with pytest.raises(se.FileCorrupt, match=f"shard {bad}: bitrot"):
        _read_rows(es, "rotten", len(body), 10,
                   lambda _i, path: open(path, "rb"), dead=dead,
                   corrupt=corrupt)
    assert dead == corrupt == {bad}
    es.mrf = mrf = _Mrf()
    try:
        before = _rows_counted()
        assert _get(es, "rotten") == body
    finally:
        es.mrf = None
    # The batch read twice, in place both times: the second with a parity
    # shard in the rotten one's place.
    assert _delta(before) == (2 * K * 10, 0)
    assert mrf.calls == [("bkt", "rotten", True)]


@pytest.mark.parametrize("blocks,staged", [
    (10, "copied"),  # rows 128 = 12 slots of 10: the spare has none
    (4, "read"),     # rows 64 = 16 slots of 4: the spare has one
])
def test_a_hedged_spare_and_a_late_straggler(layer, blocks, staged):
    """Shard 0 hangs in its read; the hedge launches a spare on shard 12
    and the batch completes without shard 0. Then shard 0's read returns
    and writes into its slot: no byte handed on changes."""
    es, body = layer
    gate = threading.Event()

    def open_src(i, path):
        if i == 0:
            return _SlowStream(open(path, "rb"), HANG, gate)
        return open(path, "rb")

    es.hedge_delay = 0.05
    benched = set()
    before = _rows_counted()
    try:
        rows, readers, codec = _read_rows(es, "whole", len(body), blocks,
                                          open_src, benched=benched)
    finally:
        es.hedge_delay = 60.0
        gate.set()
    try:
        assert benched == {0}
        assert all(row[0] is None and row[K] is not None for row in rows)
        n_rows = K * blocks
        assert _delta(before) == ((n_rows, 0) if staged == "read"
                                  else (0, n_rows))
        handed = [bytes(c) for row in rows for c in row if c is not None]
        want = body[:blocks * MIB]
        assert _data(codec, rows) == want
        # The straggler lands its own records in slot 0, rows 0 … blocks − 1
        # of the array the slices handed on are views of.
        stage = rows[0][1].obj.base
        with open(_shard_paths(es, "whole")[0], "rb") as f:
            first = f.read(DL + -(-MIB // K))[DL:]
        deadline = time.monotonic() + 10
        while bytes(stage[0, :len(first)]) != first:
            assert time.monotonic() < deadline, "the straggler never wrote"
            time.sleep(0.01)
        assert [bytes(c) for row in rows for c in row
                if c is not None] == handed
        assert _data(codec, rows) == want
    finally:
        _close(readers)


def test_get_verify_inplace_pct_reads_the_scrape(layer):
    """The benchmark's metric file over the program's own exposition: 100
    x read rows over all rows; nothing (left out of the line) for a
    program without the family."""
    import importlib.util

    from minio_tpu import obs
    from minio_tpu.admin.metrics import PromText

    spec_ = importlib.util.spec_from_file_location(
        "bench_scrape", os.path.join(BENCH, "scrape.py"))
    scrape = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(scrape)
    with open(os.path.join(BENCH, "layer_metrics",
                           "get_verify_inplace_pct.json")) as f:
        spec = json.load(f)

    def exposition():
        p = PromText()
        obs.render_into(p)
        return scrape.parse(p.render().decode())

    es, body = layer
    before = exposition()
    assert _get(es, "whole") == body
    after = exposition()
    assert scrape.delta_ratio(before, after, spec, {}) == 100.0
    gone = {key: v for key, v in after.items()
            if key[0] != "minio_tpu_get_verify_rows_total"}
    assert len(gone) == len(after) - 2
    assert scrape.delta_ratio(gone, gone, spec, {}) is None
