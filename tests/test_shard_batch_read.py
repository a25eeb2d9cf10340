"""GET reads a shard's records of a batch with ONE read (PR 35):
`BitrotReader.read_records` against `read_record`, record for record,
over every kind of source a drive hands out, and through the object
layer with `mxsum256` (the route the chip serves), where
`minio_tpu_get_shard_reads_total` rises by one a shard a batch."""

import io
import os

import pytest

from minio_tpu.chaos.naughty import NaughtyDisk, _SlowStream
from minio_tpu.erasure import ErasureObjects
from minio_tpu.erasure import objects as objects_mod
from minio_tpu.erasure.codec import ErasureCodec
from minio_tpu.erasure.metadata import shuffle_by_distribution
from minio_tpu.ops import bitrot
from minio_tpu.storage import LocalDrive
from minio_tpu.utils import errors as se

ALGO = "blake2b256"  # any 32-byte digest: the records are read unverified
DL = 32
MIB = 1 << 20


def _shard_file(path, data_size: int, shard_size: int) -> bytes:
    """A [digest][chunk] shard file of data_size shard bytes -> its bytes."""
    data = os.urandom(data_size)
    with open(path, "wb") as f:
        w = bitrot.BitrotWriter(f, shard_size, ALGO)
        for off in range(0, data_size, shard_size):
            w.write(data[off:off + shard_size])
    with open(path, "rb") as f:
        return f.read()


class _CountingBytesIO(io.BytesIO):
    def __init__(self, data):
        super().__init__(data)
        self.seeks = self.reads = 0

    def seek(self, *a):
        self.seeks += 1
        return super().seek(*a)

    def read(self, *a):
        self.reads += 1
        return super().read(*a)


class _Trickle(io.RawIOBase):
    """A raw stream that answers at most 1000 bytes a read, as a socket
    may: the fallback has to read on until the range is full."""

    def __init__(self, data):
        super().__init__()
        self._data, self._pos, self.reads = data, 0, 0

    def seek(self, pos, whence=0):
        self._pos = pos
        return pos

    def read(self, n=-1):
        self.reads += 1
        out = self._data[self._pos:self._pos + min(n, 1000)]
        self._pos += len(out)
        return out


def _open_source(kind: str, path, tmp_path, monkeypatch):
    """-> (src, reads()): the source of `kind` over the file at `path`, and
    how many reads the layer under the reader has seen since."""
    if kind in ("file", "fileio"):
        calls = []
        real = os.pread

        def spy(fd, n, off):
            calls.append(off)
            return real(fd, n, off)

        monkeypatch.setattr(os, "pread", spy)
        src = open(path, "rb") if kind == "file" else open(
            path, "rb", buffering=0)
        return src, lambda: len(calls)
    with open(path, "rb") as f:
        raw = f.read()
    if kind == "bytesio":
        src = _CountingBytesIO(raw)
        return src, lambda: src.reads
    if kind == "trickle":
        src = _Trickle(raw)
        return src, lambda: src.reads
    assert kind == "naughty"
    # The chaos plane's pacing wrapper over a local drive's stream: its
    # __getattr__ forwards fileno() to the file inside, and the reader
    # must still go through its read().
    drive = LocalDrive(str(tmp_path / "nd"))
    drive.make_vol("v")
    drive.write_all("v", "shard", raw)
    paced = []
    monkeypatch.setattr(_SlowStream, "_pace",
                        lambda self: paced.append(1))
    nd = NaughtyDisk(drive, stream_chunk_delay=0.001)
    src = nd.read_file_stream("v", "shard")
    assert isinstance(src, _SlowStream) and src.fileno() >= 0
    return src, lambda: len(paced)


SOURCES = ["file", "fileio", "bytesio", "trickle", "naughty"]
# (what, data_size, shard_size, first, count)
RANGES = [
    ("a whole batch of the GET cell", 10 * 87382, 87382, 0, 10),
    ("one record", 10 * 87382, 87382, 4, 1),
    ("a short last chunk", 3 * 1000 + 17, 1000, 2, 2),
    ("the last batch of a shard that is no multiple", 25 * 1000 + 333,
     1000, 20, 6),
    ("a batch in mid-shard", 25 * 1000 + 333, 1000, 7, 10),
    ("a shard of one short chunk", 5, 1000, 0, 1),
]


@pytest.mark.parametrize("kind", SOURCES)
@pytest.mark.parametrize("what,data_size,shard_size,first,count", RANGES,
                         ids=[r[0].replace(" ", "-") for r in RANGES])
def test_read_records_equals_read_record(tmp_path, monkeypatch, kind, what,
                                         data_size, shard_size, first,
                                         count):
    path = tmp_path / "shard"
    raw = _shard_file(path, data_size, shard_size)
    src, reads = _open_source(kind, path, tmp_path, monkeypatch)
    with src:
        r = bitrot.BitrotReader(src, data_size, shard_size, ALGO)
        got = r.read_records(first, count)
        seen = reads()
        assert len(got) == count
        for ci, (want, chunk) in zip(range(first, first + count), got):
            assert isinstance(want, bytes) and isinstance(chunk, memoryview)
            rec = raw[ci * (DL + shard_size):(ci + 1) * (DL + shard_size)]
            assert (want, bytes(chunk)) == (rec[:DL], rec[DL:])
            if kind != "trickle":  # read_record takes a short answer for EOF
                assert (want, bytes(chunk)) == r.read_record(ci)
        # One buffer holds them all: a chunk is a view, never a copy.
        assert len({id(chunk.obj) for _w, chunk in got}) == 1
        if kind == "trickle":
            assert seen == -(-(count * DL + sum(
                len(c) for _w, c in got)) // 1000)
        else:
            assert seen == 1  # read_record: two reads a record
        if kind == "bytesio":
            assert src.seeks == 1 + count  # ours, then read_record's


@pytest.mark.parametrize("kind", ["file", "bytesio", "naughty"])
@pytest.mark.parametrize("what,first,count,cut", [
    ("past the shard", 8, 3, 0),
    ("starts past the shard", 10, 1, 0),
    ("negative", -1, 2, 0),
    ("truncated in the last chunk", 0, 10, 1),
    ("truncated in a digest", 7, 3, 1000 + 20),
    ("truncated before the range", 8, 2, 3 * 1032),
])
def test_read_records_raises_file_corrupt(tmp_path, monkeypatch, kind, what,
                                          first, count, cut):
    path = tmp_path / "shard"
    raw = _shard_file(path, 10 * 1000, 1000)
    if cut:
        with open(path, "wb") as f:
            f.write(raw[:-cut])
    src, _reads = _open_source(kind, path, tmp_path, monkeypatch)
    with src:
        r = bitrot.BitrotReader(src, 10 * 1000, 1000, ALGO)
        with pytest.raises(se.FileCorrupt):
            r.read_records(first, count)
        with pytest.raises(se.FileCorrupt):  # as read_record does
            for ci in range(first, first + count):
                r.read_record(ci)


def test_read_records_of_nothing_and_of_a_closed_file(tmp_path):
    path = tmp_path / "shard"
    _shard_file(path, 5000, 1000)
    src = open(path, "rb")
    r = bitrot.BitrotReader(src, 5000, 1000, ALGO)
    assert r.read_records(2, 0) == []
    src.close()
    # Raises, and never reads a descriptor number that was handed on.
    with pytest.raises(se.FaultyDisk):
        r.read_records(0, 5)


def test_a_positional_read_that_answers_short_is_read_on(tmp_path,
                                                         monkeypatch):
    raw = _shard_file(tmp_path / "shard", 5000, 1000)
    real = os.pread
    monkeypatch.setattr(os, "pread",
                        lambda fd, n, off: real(fd, min(n, 700), off))
    with open(tmp_path / "shard", "rb") as src:
        r = bitrot.BitrotReader(src, 5000, 1000, ALGO)
        got = r.read_records(0, 5)
    assert b"".join(w + bytes(c) for w, c in got) == raw


def test_a_buffered_reader_over_no_file_takes_the_fallback(tmp_path):
    """Only a file with a descriptor of its own is read positionally."""
    raw = _shard_file(tmp_path / "shard", 5000, 1000)
    src = io.BufferedReader(io.BytesIO(raw))
    r = bitrot.BitrotReader(src, 5000, 1000, ALGO)
    assert [(w, bytes(c)) for w, c in r.read_records(1, 4)] == [
        r.read_record(ci) for ci in range(1, 5)]


# ---------------- through the object layer, mxsum256 ----------------

BATCH = 4  # blocks a read batch: a 10 MiB object is batches of 4, 4, 2


class _Mrf:
    def __init__(self):
        self.calls = []

    def add_partial(self, bucket, obj, version_id, deep=False):
        self.calls.append((bucket, obj, deep))

    def close(self):
        pass


def _body(n: int) -> bytes:
    words = -(-n // 8)
    return b"".join(((i * 0x9E3779B97F4A7C15) & (2**64 - 1)).to_bytes(
        8, "little") for i in range(words))[:n]


@pytest.fixture(scope="module", params=[(12, 4), (2, 2)],
                ids=["ec12p4", "ec2p2"])
def layer(request, tmp_path_factory):
    k, m = request.param
    root = tmp_path_factory.mktemp(f"batchread-{k}p{m}")
    drives = [LocalDrive(str(root / f"d{i}")) for i in range(k + m)]
    es = ErasureObjects(drives, parity=m, bitrot_algorithm="mxsum256",
                        batch_blocks=BATCH)
    es.hedge_delay = 60.0  # no spare reader: the counts below are exact
    es.make_bucket("bkt")
    body = _body(10 * MIB)
    es.put_object("bkt", "whole", io.BytesIO(body), len(body))
    yield es, k, m, body
    es.close()
    for d in drives:
        d.close_wal()  # stop the drives' group-commit threads


def _counts() -> tuple[int, int]:
    return (objects_mod._SHARD_READS.value, objects_mod._SHARD_RECORDS.value)


def _get(es, key, **kw) -> bytes:
    _info, stream = es.get_object("bkt", key, **kw)
    return b"".join(stream)


def _record_bytes(k: int) -> int:
    """Bytes of one full [digest][chunk] record at k data shards."""
    return DL + -(-MIB // k)


def _shard_paths(es, key) -> list[str]:
    """part.1 of every shard of the object, in shard order (data first)."""
    fi = es._read_quorum_fileinfo("bkt", key, "")
    by_shard = shuffle_by_distribution(es.drives, fi.erasure.distribution)
    return [os.path.join(d.root, "bkt", key, fi.data_dir, "part.1")
            for d in by_shard]


@pytest.mark.parametrize("what,offset,length,batches,blocks", [
    ("the whole object", 0, 10 * MIB, 3, 10),
    ("a range across a batch edge", 2 * MIB + 17, 5 * MIB, 2, 6),
    ("a range inside one block", 9 * MIB + 5, 1000, 1, 1),
    ("the last two blocks", 8 * MIB + 1, 2 * MIB - 1, 1, 2),
])
def test_get_reads_one_read_a_shard_a_batch(layer, monkeypatch, what, offset,
                                            length, batches, blocks):
    es, k, _m, body = layer
    preads, real = [], os.pread
    monkeypatch.setattr(os, "pread", lambda fd, n, off: (
        preads.append(off), real(fd, n, off))[1])
    preadvs, real_v = [], os.preadv
    monkeypatch.setattr(os, "preadv", lambda fd, bufs, off: (
        preadvs.append(off), real_v(fd, bufs, off))[1])
    before = _counts()
    assert _get(es, "whole", offset=offset,
                length=length) == body[offset:offset + length]
    reads, records = (a - b for a, b in zip(_counts(), before))
    assert (reads, records) == (k * batches, k * blocks)
    # A local drive's file is read positionally: one system call a read,
    # a scatter read into the verify launch's rows.
    assert (len(preadvs), len(preads)) == (reads, 0)


def test_get_falls_back_to_a_read_a_block(layer, monkeypatch):
    """Ids that are not consecutive (no caller passes such today) take
    read_record a block, and a reader without read_records is never asked
    for one."""
    es, k, _m, body = layer
    monkeypatch.setattr(
        bitrot.BitrotReader, "read_records",
        lambda self, first, count: pytest.fail("batched read of odd ids"))
    fi = es._read_quorum_fileinfo("bkt", "whole", "")
    readers = [None] * len(es.drives)
    shuffled = shuffle_by_distribution(es.drives, fi.erasure.distribution)
    rel = f"whole/{fi.data_dir}/part.1"
    codec = ErasureCodec(k, fi.erasure.parity_blocks, fi.erasure.block_size)

    def open_reader(i):
        return bitrot.BitrotReader(
            shuffled[i].read_file_stream("bkt", rel),
            codec.shard_file_size(len(body)), codec.shard_size(), "mxsum256")

    before = _counts()
    try:
        rows = es._read_chunk_rows(
            readers, list(range(k)), [1, 3, 8], [MIB] * 3, codec,
            len(es.drives), set(), "mxsum256", pool=es._shard_read_pool(),
            corrupt=set(), open_reader=open_reader, benched=set())
    finally:
        for r in readers:
            if r is not None:
                r.src.close()
    assert tuple(a - b for a, b in zip(_counts(), before)) == (3 * k, 3 * k)
    for b, row in zip([1, 3, 8], rows):
        assert b"".join(row[:k])[:MIB] == body[b * MIB:(b + 1) * MIB]


def test_get_with_a_flipped_byte_reselects_and_queues_a_deep_heal(layer):
    es, k, _m, body = layer
    es.put_object("bkt", "rotten", io.BytesIO(body), len(body))
    path = _shard_paths(es, "rotten")[0]
    with open(path, "r+b") as f:  # a chunk byte of data shard 0, block 1
        f.seek(_record_bytes(k) + DL + 99)
        b = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([b[0] ^ 0x40]))
    es.mrf = mrf = _Mrf()
    try:
        before = _counts()
        assert _get(es, "rotten") == body
        reads, _records = (a - b for a, b in zip(_counts(), before))
    finally:
        es.mrf = None
    # The first batch is read twice: once with the rotten shard, whose
    # digest the device finds wrong, then with a parity shard in its place.
    assert reads == k * 4
    assert mrf.calls == [("bkt", "rotten", True)]


def test_get_with_data_shards_missing_rebuilds(layer):
    es, k, m, body = layer
    es.put_object("bkt", "holes", io.BytesIO(body), len(body))
    for path in _shard_paths(es, "holes")[:m]:
        os.remove(path)
    es.mrf = mrf = _Mrf()
    try:
        before = _counts()
        assert _get(es, "holes") == body
        assert _get(es, "holes", offset=3 * MIB - 5,
                    length=2 * MIB) == body[3 * MIB - 5:5 * MIB - 5]
        reads, records = (a - b for a, b in zip(_counts(), before))
    finally:
        es.mrf = None
    # A shard that cannot be opened is no read. The whole GET reads its
    # first batch twice (the k - m data shards that are there, then k
    # survivors after re-selection) and two batches more; the ranged one
    # is one batch of blocks 2..4, read twice likewise.
    assert reads == ((k - m) + 3 * k) + ((k - m) + k)
    assert records == (4 * (k - m) + 10 * k) + (3 * (k - m) + 3 * k)
    assert mrf.calls == [("bkt", "holes", False)] * 2


def _exposition() -> str:
    from minio_tpu import obs
    from minio_tpu.admin.metrics import PromText

    p = PromText()
    obs.render_into(p)
    return p.render().decode()


def test_get_shard_reads_per_op_reads_the_scrape(layer):
    """The benchmark's metric file over the program's own exposition:
    reads ÷ the clients' operations; 0.0, never nothing, for a program
    without the family (the parent's)."""
    import importlib.util
    import json

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    spec_ = importlib.util.spec_from_file_location(
        "bench_scrape", os.path.join(bench, "scrape.py"))
    scrape = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(scrape)
    with open(os.path.join(bench, "layer_metrics",
                           "get_shard_reads_per_op.json")) as f:
        spec = json.load(f)
    es, k, _m, body = layer
    before = scrape.parse(_exposition())
    assert _get(es, "whole") == body and _get(es, "whole") == body
    after = scrape.parse(_exposition())
    window = {"client_ops": 2}
    assert scrape.delta_ratio(before, after, spec, window) == 3.0 * k
    gone = {key: v for key, v in after.items()
            if not key[0].startswith("minio_tpu_get_shard_")}
    assert len(gone) == len(after) - 2
    assert scrape.delta_ratio(gone, gone, spec, window) == 0.0
