"""Bitrot protection: checksum algorithms + the streaming shard-file format.

Format (role-equivalent of the reference's streaming bitrot files,
cmd/bitrot-streaming.go:46-74): a shard file is a sequence of
[digest][chunk] records, one per shard_size chunk — each chunk's digest sits
immediately before the chunk, so reads verify incrementally without a
second pass and writes hash each chunk while it is still hot.

Algorithms (registry analogous to cmd/bitrot.go:31-41):
  blake2b256  - keyed BLAKE2b-256 (hashlib, C speed)       [default, host]
  sha256      - SHA-256 (hashlib)
  xxh64       - xxHash64 (xxhash, non-cryptographic, fastest host option)
  mxhash256   - keyed GF(2) matmul tree hash on the TPU MXU, fused with the
                erasure kernel (ops/mxhash.py). Registered lazily.

The framework's fixed bitrot key plays the role of the reference's
magicHighwayHash256Key (cmd/bitrot.go:31): bitrot is integrity against
random corruption, not an authenticated-crypto boundary, so a fixed public
key is fine — it only has to be stable across the cluster.
"""

from __future__ import annotations

import hashlib
import io
import os
import time
from typing import BinaryIO, Callable

from minio_tpu.obs import kernel as obs_kernel
from minio_tpu.utils import errors as se

try:
    import xxhash

    _HAVE_XXHASH = True
except ImportError:  # pragma: no cover - baked into this image
    _HAVE_XXHASH = False

_HAVE_PREAD = hasattr(os, "pread")  # every Unix
_HAVE_PREADV = hasattr(os, "preadv")
# iovecs one preadv takes; Linux's 1024 where the system states none
_IOV_MAX = (os.sysconf("SC_IOV_MAX")
            if "SC_IOV_MAX" in getattr(os, "sysconf_names", {}) else -1)
_IOV_MAX = _IOV_MAX if _IOV_MAX > 0 else 1024


def _preadv_exact(fd: int, bufs: list[memoryview], off: int,
                  length: int) -> None:
    """Fill `bufs` (`length` bytes in all) from `fd` at `off`: one
    os.preadv, and more only where it answers short (a signal, or more
    iovecs than the system takes at once). FileCorrupt at end of file."""
    done = 0
    while True:
        got = os.preadv(fd, bufs[:_IOV_MAX], off + done)
        if got <= 0:
            raise se.FileCorrupt(
                f"short read: {done} of {length} bytes at {off}")
        done += got
        if done >= length:
            return
        while got >= len(bufs[0]):  # drop the buffers it filled
            got -= len(bufs[0])
            bufs = bufs[1:]
        bufs = [bufs[0][got:]] + bufs[1:]

# Fixed 256-bit bitrot key (same role as the reference's magic HH key).
BITROT_KEY = bytes.fromhex(
    "6d696e696f5f7470755f626974726f74"  # "minio_tpu_bitrot"
    "5f6b65795f76315f3230323630373239"  # "_key_v1_20260729"
)

# The reference's magicHighwayHash256Key (cmd/bitrot.go:31) — the
# highwayhash256 registry entry keys with it for algorithm-level parity
# with the reference's default bitrot configuration.
HH_BITROT_KEY = bytes(
    [0x4B, 0xE7, 0x34, 0xFA, 0x8E, 0x23, 0x8A, 0xCD, 0x26, 0x3E, 0x83,
     0xE6, 0xBB, 0x96, 0x85, 0x52, 0x04, 0x0F, 0x93, 0x5D, 0xA3, 0x9F,
     0x44, 0x14, 0x97, 0xE0, 0x9D, 0x13, 0x22, 0xDE, 0x36, 0xA0])

def _pick_default() -> str:
    """sip256 (native C++ 4-lane SipHash kernel, native/mtpu_native.cc)
    plays the reference's HighwayHash-256S default role
    (cmd/xl-storage-format-v1.go:117-119); blake2b when no toolchain."""
    try:
        from minio_tpu.native import available

        if available():
            return "sip256"
    except Exception:  # noqa: BLE001
        pass
    return "blake2b256"


class _Blake2b256:
    digest_len = 32

    @staticmethod
    def digest(data: bytes) -> bytes:
        return hashlib.blake2b(data, digest_size=32, key=BITROT_KEY).digest()


class _Sha256:
    digest_len = 32

    @staticmethod
    def digest(data: bytes) -> bytes:
        return hashlib.sha256(data).digest()


class _Xxh64:
    digest_len = 8

    @staticmethod
    def digest(data: bytes) -> bytes:
        return xxhash.xxh64(data, seed=0x6D74_7075).digest()


class _Sip256:
    """Keyed 4-lane SipHash-256 — native C++ kernel with bit-exact Python
    fallback (minio_tpu/native). The framework's HighwayHash analogue."""

    digest_len = 32

    @staticmethod
    def digest(data: bytes) -> bytes:
        from minio_tpu.native import sip256

        return sip256(BITROT_KEY, data)


class _HighwayHash256:
    """HighwayHash-256 keyed with the reference's magic bitrot key — the
    reference DEFAULT algorithm (cmd/bitrot.go:31-38,140), offered for
    algorithm-level parity. Native C++ kernel (validated against vectors
    generated by Google's reference implementation) with a bit-exact
    pure-Python fallback. See docs/BITROT.md for the algorithm roles."""

    digest_len = 32

    @staticmethod
    def digest(data: bytes) -> bytes:
        from minio_tpu.native.lib import highwayhash256

        return highwayhash256(HH_BITROT_KEY, data)


_REGISTRY: dict[str, object] = {
    "blake2b256": _Blake2b256,
    "sha256": _Sha256,
    "sip256": _Sip256,
    "highwayhash256": _HighwayHash256,
}
if _HAVE_XXHASH:
    _REGISTRY["xxh64"] = _Xxh64

DEFAULT_ALGORITHM = _pick_default()

_DEVICE_DEFAULT: str | None = None


def device_default_algorithm() -> str:
    """Default bitrot algorithm for the active JAX backend: mxsum256 on
    accelerators (hashed inside the fused codec launch, ops/fused.py),
    the host-native default on CPU. Lazy — touching jax.default_backend()
    initializes the backend, so only call when a codec path is in play."""
    global _DEVICE_DEFAULT
    if _DEVICE_DEFAULT is None:
        import jax

        # A backend that fails to initialise raises here: serving on the
        # host lane instead would hide the missing device.
        on_device = jax.default_backend() != "cpu"
        _DEVICE_DEFAULT = "mxsum256" if on_device else DEFAULT_ALGORITHM
    return _DEVICE_DEFAULT


def register_algorithm(name: str, algo: object) -> None:
    """Register an algorithm object exposing digest_len and digest(bytes)."""
    _REGISTRY[name] = algo


def get_algorithm(name: str):
    try:
        return _REGISTRY[name]
    except KeyError:
        if name == "mxhash256":  # device hash: registered on first use
            from minio_tpu.ops import mxhash

            mxhash.register()
            return _REGISTRY[name]
        if name == "mxsum256":  # device linear checksum: registered on first use
            from minio_tpu.ops import mxsum

            mxsum.register()
            return _REGISTRY[name]
        raise se.CorruptedFormat(f"unknown bitrot algorithm {name!r}") from None


def digest_len(algorithm: str) -> int:
    return get_algorithm(algorithm).digest_len


def bitrot_shard_file_size(data_size: int, shard_size: int, algorithm: str) -> int:
    """On-disk size of a shard file holding data_size shard bytes
    (cmd/bitrot.go:140-145)."""
    if data_size == 0:
        return 0
    n_chunks = -(-data_size // shard_size)
    return data_size + n_chunks * digest_len(algorithm)


class BitrotWriter:
    """Writes [digest][chunk] records. Chunks must arrive in shard_size units
    (the last may be short) — exactly how the erasure encoder emits them."""

    def __init__(self, out: BinaryIO, shard_size: int, algorithm: str = DEFAULT_ALGORITHM):
        self.out = out
        self.shard_size = shard_size
        self.algo = get_algorithm(algorithm)
        self.algorithm = algorithm
        self._written = 0
        self._obs_kernel = f"bitrot_{algorithm}"

    def write(self, chunk: bytes) -> None:
        if len(chunk) > self.shard_size:
            raise ValueError(f"chunk {len(chunk)} > shard_size {self.shard_size}")
        # Host bitrot hashing is the write-path "kernel" of the non-fused
        # lanes — its latency distribution belongs in the same family the
        # device launches feed (minio_tpu_kernel_seconds).
        t0 = time.perf_counter()
        digest = self.algo.digest(chunk)
        obs_kernel.observe(self._obs_kernel, "host", t0, nbytes=len(chunk))
        self.out.write(digest)
        self.out.write(chunk)
        self._written += len(chunk)

    @property
    def bytes_written(self) -> int:
        return self._written


class BitrotReader:
    """Verifying reader over a [digest][chunk] shard file.

    read_at(offset, length) addresses *logical* shard bytes; the reader maps
    to physical records, verifies every touched chunk, and raises FileCorrupt
    on digest mismatch (reference returns errFileCorrupt,
    cmd/bitrot-streaming.go:139-158)."""

    def __init__(self, src: BinaryIO, data_size: int, shard_size: int,
                 algorithm: str = DEFAULT_ALGORITHM):
        self.src = src
        self.data_size = data_size
        self.shard_size = shard_size
        self.algo = get_algorithm(algorithm)
        self._obs_kernel = f"bitrot_verify_{algorithm}"

    def read_record(self, chunk_index: int) -> tuple[bytes, bytes]:
        """One raw [digest][chunk] record WITHOUT verifying — the erasure
        read path collects records across drives and blocks and verifies
        them in one batched device launch (ops/fused.verify_digests)
        instead of hashing per chunk host-side."""
        dl = self.algo.digest_len
        first_byte = chunk_index * self.shard_size
        if not 0 <= first_byte < max(self.data_size, 1):
            raise se.FileCorrupt(f"chunk {chunk_index} outside shard")
        rec_off = chunk_index * (dl + self.shard_size)
        self.src.seek(rec_off)
        want = self.src.read(dl)
        chunk_len = min(self.shard_size, self.data_size - first_byte)
        chunk = self.src.read(chunk_len)
        if len(want) != dl or len(chunk) != chunk_len:
            raise se.FileCorrupt(f"short read at chunk {chunk_index}")
        return want, chunk

    def read_records(self, first: int,
                     count: int) -> list[tuple[bytes, memoryview]]:
        """The raw records first … first + count − 1, UNVERIFIED like
        read_record's, from ONE read of their contiguous byte range into
        one fresh buffer: [(stored digest, chunk)], the chunks as
        memoryview slices of that buffer (no copy of a chunk; they keep it
        alive). The erasure GET reads this way where its verify copies the
        chunks (a lane's width, a hedged spare with no slot left in the
        staging array); its usual path reads in place (read_records_into).

        How it reads follows from what `src` is, not from a knob. A plain
        file as open(path, "rb") gives it (the local drive's) is read
        positionally through its descriptor: no seek state, the file
        object's buffer bypassed. Every other source (a remote drive's
        ranged stream, a fault injector's pacing wrapper, a BytesIO) takes
        one seek and one read-exact through its own read(), so an
        injector still sees the read.

        Ownership: the descriptor is looked up on every call and a closed
        file raises (FaultyDisk), but a close that lands between that
        look-up and the read could hand the read a descriptor number the
        process has reused. Callers therefore close a reader only after
        the task reading it has returned, which is what
        erasure/objects.py _abandon_shard (close in the future's done
        callback) and _close_readers (after the producer's join) do."""
        if count <= 0:
            return []
        dl = self.algo.digest_len
        lens = self._chunk_lens(first, count)
        buf = self._read_range(first * (dl + self.shard_size),
                               count * dl + sum(lens))
        out = []
        off = 0
        for chunk_len in lens:
            out.append((bytes(buf[off:off + dl]),
                        buf[off + dl:off + dl + chunk_len]))
            off += dl + chunk_len
        return out

    def read_records_into(self, first: int, count: int, want_rows,
                          chunk_rows) -> list[memoryview]:
        """read_records, landing in the caller's arrays: record j's
        stored digest in want_rows[j] ([count, digest_len] uint8) and its
        chunk in chunk_rows[j, :len] ([count, width] uint8), the rest of
        a short chunk's row zeroed (an mxsum256 digest over the row needs
        zeros past its length). -> the chunks, as memoryviews of their
        rows. The erasure GET hands it its shard's rows of the verify
        launch's staging array, so no chunk is copied on the way to the
        device (erasure/objects.py _read_shards).

        A plain file is read with ONE os.preadv, a [digest, chunk] iovec
        pair a record, read on where it answers short. Any other source
        takes read_records' one seek + read-exact into a buffer, placed
        with one strided copy for the chunks and one for the digests, so
        a fault injector still sees the read. Ownership as read_records."""
        if count <= 0:
            return []
        dl = self.algo.digest_len
        lens = self._chunk_lens(first, count)
        off = first * (dl + self.shard_size)
        length = count * dl + sum(lens)
        fd = self._plain_fd() if _HAVE_PREADV else None
        if fd is not None:
            bufs = []
            for j, n in enumerate(lens):
                bufs += (memoryview(want_rows[j]),
                         memoryview(chunk_rows[j, :n]))
            _preadv_exact(fd, bufs, off, length)
        else:
            import numpy as np

            buf = np.frombuffer(self._read_range(off, length), np.uint8)
            # Every record but a shard's last is full: one [digest][chunk]
            # stride of dl + shard_size bytes.
            full = count if lens[-1] == self.shard_size else count - 1
            if full:
                recs = buf[:full * (dl + self.shard_size)].reshape(full, -1)
                want_rows[:full] = recs[:, :dl]
                chunk_rows[:full, :self.shard_size] = recs[:, dl:]
            if full < count:
                tail = buf[full * (dl + self.shard_size):]
                want_rows[full] = tail[:dl]
                chunk_rows[full, :lens[-1]] = tail[dl:]
        if lens[-1] < chunk_rows.shape[1]:
            chunk_rows[count - 1, lens[-1]:] = 0
        return [memoryview(chunk_rows[j, :n]) for j, n in enumerate(lens)]

    def _chunk_lens(self, first: int, count: int) -> list[int]:
        """Chunk byte-lengths of records first … first + count − 1 (all
        shard_size but a shard's last), or FileCorrupt outside the shard."""
        first_byte = first * self.shard_size
        last_start = (first + count - 1) * self.shard_size
        if first < 0 or not last_start < max(self.data_size, 1):
            raise se.FileCorrupt(
                f"chunks {first}..{first + count - 1} outside shard")
        data_len = min(self.data_size,
                       last_start + self.shard_size) - first_byte
        return [min(self.shard_size, data_len - ci * self.shard_size)
                for ci in range(count)]

    def _plain_fd(self) -> int | None:
        """The descriptor of a plain file source, which is read
        positionally; None for any other source. FaultyDisk if closed."""
        src = self.src
        raw = src.raw if isinstance(src, io.BufferedReader) else src
        if not (_HAVE_PREAD and isinstance(raw, io.FileIO)):
            return None
        try:
            return raw.fileno()
        except ValueError:
            raise se.FaultyDisk("shard reader closed under a read") from None

    def _read_range(self, off: int, length: int) -> memoryview:
        """Exactly `length` bytes at `off` of the source, or FileCorrupt."""
        src = self.src
        fd = self._plain_fd()
        if fd is not None:

            def read(got: int) -> bytes:
                return os.pread(fd, length - got, off + got)
        else:
            src.seek(off)

            def read(got: int) -> bytes:
                return src.read(length - got)
        data = read(0) or b""
        if len(data) < length:  # short of the end: a signal, a raw stream
            data = bytearray(data)
            while len(data) < length:
                more = read(len(data))
                if not more:
                    raise se.FileCorrupt(
                        f"short read: {len(data)} of {length} bytes at {off}")
                data += more
        return memoryview(data)

    def read_at(self, offset: int, length: int) -> bytes:
        if offset < 0 or length < 0 or offset + length > self.data_size:
            raise se.FileCorrupt(
                f"read [{offset}, {offset + length}) outside shard of {self.data_size}"
            )
        if length == 0:
            return b""
        dl = self.algo.digest_len
        first = offset // self.shard_size
        last = (offset + length - 1) // self.shard_size
        out = bytearray()
        hashed = 0
        t_hash = 0.0
        for ci in range(first, last + 1):
            rec_off = ci * (dl + self.shard_size)
            self.src.seek(rec_off)
            want = self.src.read(dl)
            chunk_len = min(self.shard_size, self.data_size - ci * self.shard_size)
            chunk = self.src.read(chunk_len)
            if len(want) != dl or len(chunk) != chunk_len:
                raise se.FileCorrupt(f"short read at chunk {ci}")
            th = time.perf_counter()
            got = self.algo.digest(chunk)
            t_hash += time.perf_counter() - th
            hashed += chunk_len
            if got != want:
                raise se.FileCorrupt(f"bitrot digest mismatch at chunk {ci}")
            out += chunk
        # One observation per read_at, hash time only (aggregated over the
        # touched chunks) — the verify "kernel" of the host read lane.
        # observe() stamps dt = now - t0, so back-date t0 by the hash time.
        obs_kernel.observe(self._obs_kernel, "host",
                           time.perf_counter() - t_hash, nbytes=hashed)
        rel = offset - first * self.shard_size
        return bytes(out[rel:rel + length])


def verify_shard_file(src: BinaryIO, data_size: int, shard_size: int,
                      algorithm: str = DEFAULT_ALGORITHM,
                      digest_chunks: Callable[[list, int], list[bytes]]
                      | None = None) -> None:
    """Whole-file deep verify (reference VerifyFile, cmd/xl-storage.go:2179).

    mxsum256 files verify in batched device launches (32 chunks per
    launch) — the host fallback math is a slow per-chunk matvec, and deep
    scans touch every byte of every shard. `digest_chunks(chunks, cap)`
    computes them: by default one launch a group
    (fused.digest_chunks_host); storage/local.py passes the routed one,
    so concurrent scanner and heal workers share lane launches."""
    reader = BitrotReader(src, data_size, shard_size, algorithm)
    if algorithm == "mxsum256" and data_size:
        if digest_chunks is None:
            from minio_tpu.ops import fused

            digest_chunks = fused.digest_chunks_host
        n_chunks = -(-data_size // shard_size)
        group = 32
        for start in range(0, n_chunks, group):
            records = [reader.read_record(ci)
                       for ci in range(start, min(start + group, n_chunks))]
            got = digest_chunks([c for _w, c in records], shard_size)
            for ci, ((want, _c), g) in enumerate(zip(records, got),
                                                 start=start):
                if g != want:
                    raise se.FileCorrupt(
                        f"bitrot digest mismatch at chunk {ci}")
        return
    off = 0
    while off < data_size:
        n = min(shard_size, data_size - off)
        reader.read_at(off, n)
        off += n


class WholeBitrotWriter:
    """Legacy whole-file bitrot (cmd/bitrot-whole.go): ONE digest over the
    entire shard file, stored in metadata (ChecksumInfo.hash) rather than
    interleaved — the chunk stream on disk is the raw shard bytes. Kept for
    format parity; the streaming format is the default."""

    def __init__(self, out: BinaryIO, algorithm: str = DEFAULT_ALGORITHM):
        self.out = out
        self.algorithm = algorithm
        self._algo = get_algorithm(algorithm)
        self._buf = bytearray()

    def write(self, chunk: bytes) -> None:
        self.out.write(chunk)
        self._buf += chunk

    def digest(self) -> bytes:
        """Final whole-file digest for the metadata record."""
        return self._algo.digest(bytes(self._buf))


class WholeBitrotReader:
    """Verify-on-first-read whole-file reader: the entire shard is hashed
    once against the metadata digest; subsequent read_at calls serve from
    the verified buffer (cmd/bitrot-whole.go wholeBitrotReader)."""

    def __init__(self, src: BinaryIO, expected_digest: bytes,
                 algorithm: str = DEFAULT_ALGORITHM):
        self.src = src
        self.expected = expected_digest
        self._algo = get_algorithm(algorithm)
        self._data: bytes | None = None

    def _load(self) -> bytes:
        if self._data is None:
            self.src.seek(0)
            data = self.src.read()
            if self._algo.digest(data) != self.expected:
                raise se.FileCorrupt("whole-file bitrot digest mismatch")
            self._data = data
        return self._data

    def read_at(self, offset: int, length: int) -> bytes:
        data = self._load()
        if offset < 0 or offset + length > len(data):
            raise se.FileCorrupt(
                f"read [{offset}, {offset + length}) outside {len(data)}")
        return data[offset:offset + length]
