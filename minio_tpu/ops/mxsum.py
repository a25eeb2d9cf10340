"""mxsum256 — keyed linear bitrot checksum as one int8 MXU matmul.

The production device-side bitrot hash, fused into the same launch as the
erasure codec (the role HighwayHash-256 plays host-side in the reference:
every shard chunk hashed while hot, cmd/bitrot-streaming.go:46). Where
ops/mxhash.py chains GF(2) compressions (a Merkle-Damgard walk, ~4k int
ops/byte), mxsum is a single linear map — the cheapest construction the MXU
can evaluate (~16 ops/byte) and the only one whose cost is independent of
chunk length *per compiled program*:

    digest_c = sum_i data_i * K[i, c]  +  sum_j len_le[j] * L[j, c]   (mod 2^32)

with c = 0..7 int32 columns (32-byte digest), K an unbounded keyed stream of
int8 rows derived from BITROT_KEY (PCG64), and L a fixed int8 length key.

Zero padding is free: padded tail bytes contribute 0, so a chunk of any
length s <= cap hashes identically under any cap — one compiled program
serves every chunk length (the length rides in as *data*, not shape), and
ragged final chunks join the same batched launch as full chunks. This is
what makes the hash fusable into the serving PutObject/GetObject paths
without compile-cache blowups.

Detection model (bitrot = random corruption, not an auth boundary — same
threat model as the reference's fixed magicHighwayHash256Key,
cmd/bitrot.go:31): a corruption e != 0 escapes iff e . K[:, c] == 0 mod 2^32
for all 8 columns simultaneously. A single flipped byte always perturbs
column c unless K[i, c] == 0 (each |e * K[i,c]| < 2^16, no wrap), so
single-byte rot escapes only at the ~2^-64 chance that all 8 key bytes for
that position are zero; a random multi-byte corruption escapes with
probability ~2^-256 (the kernel fraction of a full-rank map into Z_2^32^8).
Truncation/extension is caught by the L term.

Host fallback is pure numpy (exact int64 accumulation then mod 2^32 —
bit-identical to the device's wrapping int32 accumulation); tests and CPU
backends use it, device backends verify in batches on-device.
"""

from __future__ import annotations

import functools
import threading

import numpy as np

DIGEST_LEN = 32
COLS = 8  # int32 words per digest

_KEY_CHUNK = 1 << 16  # K-stream generation granularity (rows)
_key_lock = threading.Lock()
_key_i8 = np.zeros((0, COLS), dtype=np.int8)
_key_i64 = np.zeros((0, COLS), dtype=np.int64)


def _grow_key(n_rows: int) -> None:
    global _key_i8, _key_i64
    from minio_tpu.ops.bitrot import BITROT_KEY

    seed = int.from_bytes(BITROT_KEY[8:16], "little") ^ 0x6D78_73756D  # "mxsum"
    with _key_lock:
        have = _key_i8.shape[0]
        if have >= n_rows:
            return
        n_chunks = -(-n_rows // _KEY_CHUNK)
        parts = [_key_i8]
        for ci in range(have // _KEY_CHUNK, n_chunks):
            rng = np.random.Generator(np.random.PCG64(seed + ci))
            parts.append(rng.integers(-128, 128, (_KEY_CHUNK, COLS), dtype=np.int8))
        _key_i8 = np.concatenate(parts, axis=0)
        _key_i64 = _key_i8.astype(np.int64)


def _key_rows(n_rows: int) -> np.ndarray:
    """First n_rows of the keyed int8 stream K, shape [n_rows, 8]. K[:a] is
    always a prefix of K[:b] — a chunk's digest must not depend on the cap
    it was hashed under."""
    if _key_i8.shape[0] < n_rows:
        _grow_key(n_rows)
    return _key_i8[:n_rows]


def _key_rows_i64(n_rows: int) -> np.ndarray:
    if _key_i64.shape[0] < n_rows:
        _grow_key(n_rows)
    return _key_i64[:n_rows]


@functools.lru_cache(maxsize=1)
def _len_key() -> np.ndarray:
    from minio_tpu.ops.bitrot import BITROT_KEY

    seed = int.from_bytes(BITROT_KEY[16:24], "little") ^ 0x6C656E
    rng = np.random.Generator(np.random.PCG64(seed))
    return rng.integers(-128, 128, (8, COLS), dtype=np.int8)


def digest_np(data: bytes | np.ndarray) -> bytes:
    """Host digest of one chunk (numpy, exact)."""
    arr = (np.frombuffer(data, dtype=np.uint8)
           if isinstance(data, (bytes, bytearray, memoryview)) else data)
    s = arr.size
    if s:
        acc = arr.astype(np.int8).astype(np.int64) @ _key_rows_i64(s)
    else:
        acc = np.zeros(COLS, np.int64)
    lrow = np.frombuffer(np.uint64(s).tobytes(), dtype=np.uint8)
    acc = acc + lrow.astype(np.int8).astype(np.int64) @ _len_key().astype(np.int64)
    return (acc & 0xFFFFFFFF).astype("<u4").tobytes()


def digest_batch_np(chunks: np.ndarray, lengths) -> np.ndarray:
    """Host batched digest: chunks [B, S] u8 (each row zero-padded beyond
    its length), lengths [B]. Returns [B, 32] u8."""
    b, s = chunks.shape
    if s:
        acc = chunks.astype(np.int8).astype(np.int64) @ _key_rows_i64(s)
    else:
        acc = np.zeros((b, COLS), np.int64)
    lrows = np.ascontiguousarray(
        np.asarray(lengths, dtype=np.uint64)).view(np.uint8).reshape(b, 8)
    acc = acc + lrows.astype(np.int8).astype(np.int64) @ _len_key().astype(np.int64)
    return (acc & 0xFFFFFFFF).astype("<u4").view(np.uint8).reshape(b, DIGEST_LEN)


# --- device path -------------------------------------------------------------


def len_term_device(lengths):
    """Device length-key contribution: lengths [B] (< 2^32) -> [B, 8] i32.
    Only the low 4 LE bytes are nonzero (no uint64 on device; the host's
    key rows 4-7 multiply zeros), so L[:4] suffices."""
    import jax
    import jax.numpy as jnp

    lengths = lengths.astype(jnp.uint32)
    shifts = jnp.arange(4, dtype=jnp.uint32) * 8
    lrows = ((lengths[:, None] >> shifts) & jnp.uint32(0xFF)).astype(jnp.int8)
    return jax.lax.dot_general(
        lrows, jnp.asarray(_len_key()[:4]),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)


def pack_words_device(acc):
    """Device digest framing: acc [B, 8] i32 -> [B, 32] u8 (LE words)."""
    import jax.numpy as jnp

    w = acc.astype(jnp.uint32)
    bshift = jnp.arange(4, dtype=jnp.uint32) * 8
    by = (w[:, :, None] >> bshift) & jnp.uint32(0xFF)          # [B, 8, 4]
    return by.reshape(w.shape[0], DIGEST_LEN).astype(jnp.uint8)


def digest_device(chunks, lengths):
    """Device batched digest: chunks [B, S] u8 (zero-padded beyond each
    row's length), lengths [B] int32/uint32 (< 2^32). Returns [B, 32] u8.

    jnp-traceable — call inside jit (the fused codec launches). One int8
    MXU contraction + a tiny length term; int32 accumulation wraps mod 2^32
    exactly like the host's int64-then-mask path.
    """
    import jax
    import jax.numpy as jnp

    b, s = chunks.shape
    # The scope goes into the ops' HLO metadata, which xprof and an HLO
    # dump show; a `ProfileData` event keeps its HLO name (`%fusion.5`).
    with jax.named_scope("mxsum256"):
        acc = jnp.zeros((b, COLS), dtype=jnp.int32)
        if s:
            k = jnp.asarray(_key_rows(s))                      # [S, 8] i8
            acc = jax.lax.dot_general(
                chunks.astype(jnp.int8), k,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)              # [B, 8]
        return pack_words_device(acc + len_term_device(lengths))


class MXSum256:
    """Bitrot registry adapter (ops/bitrot.py register_algorithm)."""

    digest_len = DIGEST_LEN

    @staticmethod
    def digest(data: bytes) -> bytes:
        return digest_np(data)


def register() -> None:
    from minio_tpu.ops import bitrot

    bitrot.register_algorithm("mxsum256", MXSum256)
