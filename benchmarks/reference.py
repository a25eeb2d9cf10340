"""The plain reference: what one erasure-coded object is on the drives.

Straightforward numpy and Python integers, written from the formats'
definitions and importing nothing of the program: no table, matrix or key
that the program made.

* Reed-Solomon over GF(2^8), reducing polynomial x^8+x^4+x^3+x^2+1 (0x11D):
  the systematic code of klauspost/reedsolomon that MinIO uses. Take the
  n x k Vandermonde matrix V[r, c] = r**c (0**0 = 1), multiply it on the
  right by the inverse of its top k x k block: the first k rows become the
  identity, the last m = n - k rows make the parity.
* An object is cut into blocks of `block_size` bytes. A block of L bytes is
  padded with zeros to k * w, w = ceil(L / k), and split into k data rows of
  w bytes; the m parity rows follow. Shard i of the object is the sequence of
  its row i of every block.
* Any k rows of a block give its data rows back: take the k x k part of the
  generator (identity over the parity rows) that made them, invert it,
  multiply (`decode_rows`).
* A shard file is one `[digest][chunk]` record per block (the streaming
  bitrot format), digest = mxsum256 of that row.
* mxsum256: digest_c = sum_i int8(data_i) * K[i, c]
  + sum_j int8(len_le8[j]) * L[j, c] (mod 2**32), c = 0..7, written as eight
  little-endian words. K is a keyed stream of int8 rows, made 65536 rows at
  a time by numpy's PCG64 seeded with key_word(8..16) ^ "mxsum" + chunk
  number; L is 8 rows from PCG64 seeded with key_word(16..24) ^ "len".
* Drive i of an n-drive set holds shard (start + i) mod n, with start =
  blake2b-64(bucket "/" key), big-endian, mod n.
"""

from __future__ import annotations

import functools
import hashlib

import numpy as np

GF_POLY = 0x11D
DIGEST_LEN = 32
# The format's fixed bitrot key: "minio_tpu_bitrot_key_v1_20260729".
BITROT_KEY = b"minio_tpu_bitrot_key_v1_20260729"
_K_ROWS = 1 << 16


# --- GF(2^8) ---------------------------------------------------------------


def gf_mul(a: int, b: int) -> int:
    """Carry-less multiply, reduced by 0x11D."""
    r = 0
    while b:
        if b & 1:
            r ^= a
        a <<= 1
        if a & 0x100:
            a ^= GF_POLY
        b >>= 1
    return r


def gf_pow(a: int, n: int) -> int:
    r = 1
    for _ in range(n):
        r = gf_mul(r, a)
    return r


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("inverse of 0 in GF(2^8)")
    return gf_pow(a, 254)


def _mat_mul(a: list[list[int]], b: list[list[int]]) -> list[list[int]]:
    out = []
    for row in a:
        o = []
        for c in range(len(b[0])):
            v = 0
            for x, brow in zip(row, b):
                v ^= gf_mul(x, brow[c])
            o.append(v)
        out.append(o)
    return out


def _mat_inv(m: list[list[int]]) -> list[list[int]]:
    n = len(m)
    aug = [list(r) + [int(i == j) for j in range(n)] for i, r in enumerate(m)]
    for col in range(n):
        piv = next((r for r in range(col, n) if aug[r][col]), None)
        if piv is None:
            raise ValueError("singular matrix over GF(2^8)")
        aug[col], aug[piv] = aug[piv], aug[col]
        inv = gf_inv(aug[col][col])
        aug[col] = [gf_mul(x, inv) for x in aug[col]]
        for r in range(n):
            if r != col and aug[r][col]:
                f = aug[r][col]
                aug[r] = [x ^ gf_mul(f, y) for x, y in zip(aug[r], aug[col])]
    return [r[n:] for r in aug]


@functools.lru_cache(maxsize=None)
def parity_rows(k: int, m: int) -> tuple[tuple[int, ...], ...]:
    """The m x k parity part of the systematic generator."""
    vm = [[gf_pow(r, c) for c in range(k)] for r in range(k + m)]
    return tuple(tuple(r) for r in _mat_mul(vm, _mat_inv(vm[:k]))[k:])


@functools.lru_cache(maxsize=None)
def _times_table(c: int) -> np.ndarray:
    return np.array([gf_mul(c, x) for x in range(256)], dtype=np.uint8)


def encode_block(data_rows: np.ndarray, m: int) -> np.ndarray:
    """[k, w] u8 data rows -> [m, w] u8 parity rows."""
    k, w = data_rows.shape
    out = np.zeros((m, w), dtype=np.uint8)
    for j, row in enumerate(parity_rows(k, m)):
        for i, c in enumerate(row):
            out[j] ^= _times_table(c)[data_rows[i]]
    return out


@functools.lru_cache(maxsize=None)
def decode_matrix(k: int, m: int, have: tuple[int, ...]) -> tuple:
    """The k x k matrix that gives the data rows back from rows `have` (k
    row indices of the k + m, in the order the rows are handed over)."""
    gen = [[int(i == j) for j in range(k)] for i in range(k)] + [
        list(r) for r in parity_rows(k, m)]
    return tuple(tuple(r) for r in _mat_inv([gen[i] for i in have]))


def decode_rows(present: dict[int, np.ndarray], k: int, m: int) -> np.ndarray:
    """{row index: [w] u8 row}, any k or more of a block's k + m rows ->
    its [k, w] u8 data rows."""
    have = tuple(sorted(present))[:k]
    if len(have) < k or have[0] < 0 or have[-1] >= k + m:
        raise ValueError(f"{len(present)} rows of a {k}+{m} block: "
                         f"{sorted(present)}")
    inv = decode_matrix(k, m, have)
    out = np.zeros((k, present[have[0]].size), dtype=np.uint8)
    for i in range(k):
        for c, j in zip(inv[i], have):
            if c:
                out[i] ^= _times_table(c)[present[j]]
    return out


# --- mxsum256 --------------------------------------------------------------

_key_rows = np.zeros((0, 8), dtype=np.int64)


def _key(n: int) -> np.ndarray:
    global _key_rows
    if _key_rows.shape[0] < n:
        seed = int.from_bytes(BITROT_KEY[8:16], "little") ^ 0x6D7873756D
        have = _key_rows.shape[0] // _K_ROWS
        parts = [_key_rows]
        for ci in range(have, -(-n // _K_ROWS)):
            rng = np.random.Generator(np.random.PCG64(seed + ci))
            parts.append(rng.integers(-128, 128, (_K_ROWS, 8),
                                      dtype=np.int8).astype(np.int64))
        _key_rows = np.concatenate(parts)
    return _key_rows[:n]


def _len_key() -> np.ndarray:
    seed = int.from_bytes(BITROT_KEY[16:24], "little") ^ 0x6C656E
    rng = np.random.Generator(np.random.PCG64(seed))
    return rng.integers(-128, 128, (8, 8), dtype=np.int8).astype(np.int64)


def mxsum256(chunk: bytes | np.ndarray) -> bytes:
    a = np.frombuffer(chunk, dtype=np.uint8) if isinstance(
        chunk, (bytes, bytearray, memoryview)) else chunk
    acc = a.view(np.int8).astype(np.int64) @ _key(a.size) if a.size else \
        np.zeros(8, np.int64)
    lrow = np.frombuffer(int(a.size).to_bytes(8, "little"), dtype=np.int8)
    acc = acc + lrow.astype(np.int64) @ _len_key()
    return (acc & 0xFFFFFFFF).astype("<u4").tobytes()


# --- layout ----------------------------------------------------------------


def shard_rows(block: bytes, k: int, m: int) -> np.ndarray:
    """One block -> its [k + m, w] rows."""
    w = -(-len(block) // k)
    buf = np.zeros(k * w, dtype=np.uint8)
    buf[:len(block)] = np.frombuffer(block, dtype=np.uint8)
    data = buf.reshape(k, w)
    return np.concatenate([data, encode_block(data, m)])


def shard_files(body: bytes, k: int, m: int, block_size: int,
                digest=mxsum256) -> list[bytes]:
    """The k + m shard files of one object, framed `[digest][chunk]`."""
    files = [bytearray() for _ in range(k + m)]
    for off in range(0, len(body), block_size):
        rows = shard_rows(body[off:off + block_size], k, m)
        for f, row in zip(files, rows):
            f += digest(row)
            f += row.tobytes()
    return [bytes(f) for f in files]


def shard_of_drive(bucket: str, key: str, n: int) -> list[int]:
    """shard index (0-based) held by each drive of the set, in set order."""
    start = int.from_bytes(hashlib.blake2b(
        f"{bucket}/{key}".encode(), digest_size=8).digest(), "big") % n
    return [(start + i) % n for i in range(n)]


def write_quorum(k: int, m: int) -> int:
    """MinIO's data write quorum: k drives, one more where k == m."""
    return k + (1 if k == m else 0)
