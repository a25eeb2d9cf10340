"""Fused erasure-codec + bitrot launches — the production device path.

One jit launch per batch of erasure blocks computes parity AND the mxsum256
bitrot digest of every shard chunk while the shards are resident on device
(SURVEY.md §2.3: the reference hashes each chunk host-side while hot,
cmd/bitrot-streaming.go:46-74; here the hash shares the launch with the
GF(2) contraction). The serving paths call these:

  PutObject  -> encode_with_digests      (erasure/codec.py begin_encode)
  GetObject  -> verify_digests           (batched chunk verify on read)
  Heal       -> reconstruct_with_digests (rebuilt shards + their digests)

Kernel dispatch: the Pallas tiled kernel (ops/rs_pallas.py) on TPU-like
backends — ragged shard widths are zero-padded to its TILE in-graph (parity
columns never mix, so padding is free and sliced back off) — and the pure
XLA path (ops/rs_xla.py) on CPU. Ragged *chunk lengths* need no padding
tricks at all: mxsum256 digests are computed under per-row dynamic lengths
(zero tail bytes contribute nothing), so a batch mixing full and short
chunks is one launch, one compiled program.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp

from minio_tpu.obs import kernel as obs_kernel
from minio_tpu.ops import mxsum, rs_pallas, rs_xla

_BACKEND: str | None = None


def bucket_rows(b: int) -> int:
    """Next power-of-two batch-row count (>= 1).

    jit traces once per SHAPE: under mixed object sizes the tail batch
    of every object carries a different row count, so unbucketed batch
    dims mint a fresh trace per distinct count — compile churn on the
    serving path. The dispatch layers (erasure/codec.py staging,
    digest_chunks_host, dataplane lanes) pad the batch dim to this
    bucket and slice results back, bounding the trace count per entry
    point to log2(max batch)+1 (compile-count probe:
    tests/test_dataplane.py)."""
    from minio_tpu.utils.shardmath import pow2_bucket

    return pow2_bucket(b)


def bucket_width(s: int, floor: int = 512) -> int:
    """Next power-of-two staging width (>= floor) for a shard chunk of s
    bytes. The dispatch layers stage batches at the bucket of their
    ACTUAL max chunk length instead of the geometry's full shard width:
    a small object's launch then touches KiBs, not a 1 MiB-block-wide
    row of padding. Free by construction — parity columns never mix and
    mxsum digests are cap-invariant (ops/mxsum.py), so results are
    bit-identical under any staging width >= the chunk length."""
    from minio_tpu.utils.shardmath import pow2_bucket

    return pow2_bucket(s, floor=floor)


def _backend() -> str:
    """`minio_tpu_kernel_seconds` backend label: JAX platform + which
    erasure kernel the dispatch selects (tpu:pallas / cpu:xla / ...).
    Cached — resolving it touches the backend."""
    global _BACKEND
    if _BACKEND is None:
        _BACKEND = (f"{jax.default_backend()}:"
                    f"{'pallas' if rs_pallas.use_pallas() else 'xla'}")
    return _BACKEND


def _observed(kernel: str, out_of=None):
    """Wrap a jitted entry point with minio_tpu_kernel_seconds
    instrumentation. The first positional arg is the batch array (its
    shape[0]/size label the launch); `out_of` picks the array to sync on
    under MTPU_KERNEL_SYNC from the return value (identity by default).
    Under an OUTER trace (a caller composed us into its own jax.jit) the
    observation is skipped entirely — a trace-time stamp would record
    compile cost once and then nothing, poisoning the distribution."""
    def deco(jit_fn):
        @functools.wraps(jit_fn)
        def wrapper(data, *a, **kw):
            if isinstance(data, jax.core.Tracer):
                return jit_fn(data, *a, **kw)
            t0 = time.perf_counter()
            out = jit_fn(data, *a, **kw)
            obs_kernel.observe(
                kernel, _backend(), t0, blocks=data.shape[0],
                nbytes=data.size,
                out=out if out_of is None else out_of(out))
            return out
        return wrapper
    return deco


def _encode_dispatch(data: jax.Array, k: int, m: int) -> jax.Array:
    b, _, s = data.shape
    # named_scope: `rs_encode` goes into the HLO metadata of the pad, the
    # kernel and the slice (xprof, HLO dumps); trace events keep HLO names.
    with jax.named_scope("rs_encode"):
        if rs_pallas.use_pallas():
            pad = (-s) % rs_pallas.TILE
            if pad:
                dp = jnp.pad(data, ((0, 0), (0, 0), (0, pad)))
                return rs_pallas.encode(dp, k, m)[:, :, :s]
            return rs_pallas.encode(data, k, m)
        return rs_xla.encode(data, k, m)


def _reconstruct_dispatch(shards: jax.Array, k: int, n: int,
                          survivors: tuple[int, ...],
                          targets: tuple[int, ...]) -> jax.Array:
    b, _, s = shards.shape
    with jax.named_scope("rs_reconstruct"):
        if rs_pallas.use_pallas():
            pad = (-s) % rs_pallas.TILE
            if pad:
                sp = jnp.pad(shards, ((0, 0), (0, 0), (0, pad)))
                return rs_pallas.reconstruct(
                    sp, k, n, survivors, targets)[:, :, :s]
            return rs_pallas.reconstruct(shards, k, n, survivors, targets)
        return rs_xla.reconstruct(shards, k, n, survivors, targets)


@_observed("encode")
@functools.partial(jax.jit, static_argnames=("k", "m"))
def encode_only(data: jax.Array, k: int, m: int) -> jax.Array:
    """Plain parity launch with the same kernel dispatch (used when the
    bitrot algorithm is a host hash): data [B, k, S] u8 -> [B, m, S] u8."""
    return _encode_dispatch(data, k, m)


@_observed("encode_digests")
@functools.partial(jax.jit, static_argnames=("k", "m"))
def encode_with_digests(data: jax.Array, k: int, m: int,
                        chunk_lens: jax.Array | None = None
                        ) -> tuple[jax.Array, jax.Array]:
    """data [B, k, S] u8 (rows zero-padded past each block's chunk length)
    -> (parity [B, m, S] u8, digests [B, k+m, 32] u8).

    chunk_lens [B] int32: each block's actual chunk byte-length (defaults to
    S). Digests are mxsum256 over each shard's chunk_lens[b] bytes — exactly
    the [digest][chunk] records the bitrot writer frames (ops/bitrot.py)."""
    b, _, s = data.shape
    n = k + m
    if chunk_lens is None:
        chunk_lens = jnp.full((b,), s, dtype=jnp.int32)
    parity = _encode_dispatch(data, k, m)
    shards = jnp.concatenate([data, parity], axis=1)        # [B, n, S]
    lens = jnp.repeat(chunk_lens, n)                        # row-major [B*n]
    digs = mxsum.digest_device(shards.reshape(b * n, s), lens)
    return parity, digs.reshape(b, n, mxsum.DIGEST_LEN)


@_observed("reconstruct_digests")
@functools.partial(jax.jit, static_argnames=("k", "n", "survivors", "targets"))
def reconstruct_with_digests(shards: jax.Array, k: int, n: int,
                             survivors: tuple[int, ...],
                             targets: tuple[int, ...],
                             chunk_lens: jax.Array | None = None
                             ) -> tuple[jax.Array, jax.Array]:
    """Rebuild `targets` from any-k `survivors` and digest the rebuilt
    chunks in the same launch (heal writes them straight into fresh
    [digest][chunk] shard files — cmd/erasure-healing.go:401-461).

    shards [B, n, S] u8 -> (rebuilt [B, t, S] u8, digests [B, t, 32] u8)."""
    b, _, s = shards.shape
    t = len(targets)
    if chunk_lens is None:
        chunk_lens = jnp.full((b,), s, dtype=jnp.int32)
    rebuilt = _reconstruct_dispatch(shards, k, n, survivors, targets)
    lens = jnp.repeat(chunk_lens, t)
    digs = mxsum.digest_device(rebuilt.reshape(b * t, s), lens)
    return rebuilt, digs.reshape(b, t, mxsum.DIGEST_LEN)


@_observed("reconstruct")
@functools.partial(jax.jit, static_argnames=("k", "n", "survivors", "targets"))
def reconstruct_only(shards: jax.Array, k: int, n: int,
                     survivors: tuple[int, ...],
                     targets: tuple[int, ...]) -> jax.Array:
    """Plain rebuild launch with kernel dispatch (host-hash algorithms):
    shards [B, n, S] u8 -> [B, t, S] u8."""
    return _reconstruct_dispatch(shards, k, n, survivors, targets)


def _weights_matmul_dispatch(surv: jax.Array, w_t: jax.Array,
                             out_shards: int) -> jax.Array:
    """Runtime-weights contraction with kernel dispatch: surv [B, k, S],
    w_t [t*8, k*8] (pre-transposed) -> [B, t, S]."""
    b, _, s = surv.shape
    with jax.named_scope("rs_reconstruct"):
        if rs_pallas.use_pallas():
            pad = (-s) % rs_pallas.TILE
            if pad:
                sp = jnp.pad(surv, ((0, 0), (0, 0), (0, pad)))
                return rs_pallas.gf2_matmul_with_weights(
                    sp, w_t, out_shards)[:, :, :s]
            return rs_pallas.gf2_matmul_with_weights(surv, w_t, out_shards)
        return rs_xla.gf2_matmul_with_weights(surv, jnp.transpose(w_t),
                                              out_shards)


@_observed("reconstruct_weights", out_of=lambda out: out[0])
@functools.partial(jax.jit, static_argnames=("out_shards", "with_digests"))
def reconstruct_weights_digests(surv: jax.Array, w_t: jax.Array,
                                chunk_lens: jax.Array, out_shards: int,
                                with_digests: bool = True):
    """Heal rebuild with the decode matrix as RUNTIME DATA: the failure
    pattern never enters the jit compile key, so a heal sweep over objects
    with arbitrary drive states reuses one compiled program per shape
    (there are C(n, <=m) patterns — making them static would recompile per
    pattern and stall the sweep). surv is survivor-compacted [B, k, S];
    w_t the pattern's [t*8, k*8] transposed decode matrix.

    -> (rebuilt [B, t, S], digests [B, t, 32] | None)."""
    b, _, s = surv.shape
    rebuilt = _weights_matmul_dispatch(surv, w_t, out_shards)
    if not with_digests:
        return rebuilt, None
    lens = jnp.repeat(chunk_lens, out_shards)
    digs = mxsum.digest_device(rebuilt.reshape(b * out_shards, s), lens)
    return rebuilt, digs.reshape(b, out_shards, mxsum.DIGEST_LEN)


@_observed("verify_digests")
@jax.jit
def verify_digests(chunks: jax.Array, lens: jax.Array) -> jax.Array:
    """Batched read-path verify: chunks [N, S] u8 (zero-padded rows),
    lens [N] int32 -> digests [N, 32] u8. The GET path compares these to the
    stored record digests — one launch per read batch instead of one host
    hash per chunk (cmd/bitrot-streaming.go:115-158 verifies per ReadAt)."""
    return mxsum.digest_device(chunks, lens)


def digest_chunks_host(chunks: list[bytes], cap: int) -> list[bytes]:
    """Host convenience: mxsum256 digests of a ragged list of byte chunks
    (each <= cap) in one device launch. Row count pads to a power of two so
    the jitted program sees a bounded shape set; the staging array recycles
    through the byte pool (pkg/bpool role) — np.asarray on the launch
    output blocks until the input was consumed, so returning it is safe."""
    import numpy as np

    from minio_tpu.utils.bufpool import GLOBAL_POOL

    n = bucket_rows(len(chunks))
    batch = GLOBAL_POOL.get((n, cap), zero=True)
    lens = np.zeros(n, dtype=np.int32)
    for i, c in enumerate(chunks):
        batch[i, : len(c)] = np.frombuffer(c, dtype=np.uint8)
        lens[i] = len(c)
    got = np.asarray(verify_digests(batch, lens))
    GLOBAL_POOL.put(batch)
    return [got[i].tobytes() for i in range(len(chunks))]


def digest_staged_host(stage, lens):
    """mxsum256 digests of an array the caller has already staged: stage
    [bucket_rows(n), cap] u8, each row zero past lens[row] ([rows] int32;
    a row whose length is 0 may hold anything, its digest is not for
    reading) -> digests [rows, 32] u8 as numpy. The same launch and
    shapes as digest_chunks_host, without its copy a chunk: the erasure
    GET reads its records straight into `stage`'s rows."""
    import numpy as np

    return np.asarray(verify_digests(stage, lens))
