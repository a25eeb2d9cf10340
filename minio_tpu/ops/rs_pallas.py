"""Pallas TPU kernels for the batched GF(2) Reed-Solomon codec.

Same math as ops/rs_xla.py (bit-lift → int8 MXU contraction → mod-2 →
byte-pack) hand-tiled as one Pallas kernel so the whole epilogue stays in
VMEM with the matmul: the unpack/pack never round-trips to HBM, which is
what bounds the XLA version at large batch. Grid = (batch, S/TILE); the
[t*8, k*8] weight block is resident in VMEM for every step.

One kernel serves encode AND reconstruct — both are GF(2) bit-matrix
contractions, only the weight differs (encode_bitmatrix vs the cached
per-failure-pattern decode_bitmatrix), mirroring the symmetry rs_xla
exploits (cmd/erasure-coding.go:70,89).

The kernels are numerically identical to rs_xla — tests assert
bit-exactness in interpreter mode; on hardware `use_pallas()` flips the
serving/bench path (MTPU_USE_PALLAS=1, default on TPU backends). Callers
with ragged S pad to TILE (ops/fused.py does; parity columns never mix so
padding is free) or fall back to rs_xla.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from minio_tpu.ops import gf

TILE = 512  # lanes per grid step (last-dim multiple of 128)


def use_pallas() -> bool:
    env = os.environ.get("MTPU_USE_PALLAS", "")
    if env in ("0", "off"):
        return False
    if env in ("1", "on"):
        return True
    # A backend error propagates: picking the XLA route on it would hide
    # a device that failed to initialise.
    return jax.default_backend() != "cpu"


def _gf2_kernel(kin: int, tout: int, ts: int, wt_ref, x_ref, o_ref):
    """One (batch, tile) step: x [kin, ts] u8 → o [tout, ts] u8.

    Everything stays in [rows, lanes] orientation — no transposes (Mosaic
    rejects narrow-type transposes); the weight arrives pre-transposed as
    [tout*8, kin*8] so the contraction directly yields [tout*8, ts]."""
    x = x_ref[:].astype(jnp.int32)                          # [kin, ts]
    shifts = jax.lax.broadcasted_iota(jnp.int32, (kin, 8, ts), 1)
    bits = ((x[:, None, :] >> shifts) & 1)                  # [kin, 8, ts]
    bits = bits.reshape(kin * 8, ts).astype(jnp.int8)
    y = jax.lax.dot_general(
        wt_ref[:], bits, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)                   # [tout*8, ts]
    y = y.reshape(tout, 8, ts)
    pshift = jax.lax.broadcasted_iota(jnp.int32, (tout, 8, ts), 1)
    # Parity bit of y placed at position i in one step: (y << i) & (1 << i).
    # (Masking with 1 first makes Mosaic narrow the vector to i1, which its
    # casts reject — mask after the shift instead.)
    masked = (y << pshift) & (jnp.int32(1) << pshift)
    # Sum == OR here (disjoint bit positions); Mosaic keeps additions wide
    # where it narrows OR-trees to i1.
    packed = jnp.sum(masked, axis=1, dtype=jnp.int32)       # [tout, ts]
    o_ref[:] = packed.astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("out_shards", "interpret"))
def gf2_matmul_with_weights(x: jax.Array, w_t: jax.Array, out_shards: int,
                            interpret: bool = False) -> jax.Array:
    """Raw tiled contraction: x [B, kin, S] u8, w_t [out*8, kin*8] i8
    (pre-transposed) -> [B, out, S] u8. S must divide by TILE."""
    b, kin, s = x.shape
    assert s % TILE == 0, s
    kernel = functools.partial(_gf2_kernel, kin, out_shards, TILE)
    return pl.pallas_call(
        kernel,
        grid=(b, s // TILE),
        in_specs=[
            pl.BlockSpec((out_shards * 8, kin * 8), lambda i, j: (0, 0)),
            pl.BlockSpec((None, kin, TILE), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((None, out_shards, TILE), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((b, out_shards, s), jnp.uint8),
        interpret=interpret,
    )(w_t, x)


@functools.lru_cache(maxsize=256)
def _encode_weights_t(k: int, m: int) -> np.ndarray:
    return np.ascontiguousarray(gf.encode_bitmatrix(k, m).T, dtype=np.int8)


@functools.lru_cache(maxsize=4096)
def _decode_weights_t(k: int, n: int, survivors: tuple[int, ...],
                      targets: tuple[int, ...]) -> np.ndarray:
    return np.ascontiguousarray(
        gf.decode_bitmatrix(k, n, survivors, targets).T, dtype=np.int8)


def encode(data: jax.Array, k: int, m: int,
           interpret: bool = False) -> jax.Array:
    """data [B, k, S] u8 -> parity [B, m, S] u8. S must divide by TILE."""
    w_t = jnp.asarray(_encode_weights_t(k, m))
    return gf2_matmul_with_weights(data, w_t, m, interpret=interpret)


def reconstruct(shards: jax.Array, k: int, n: int,
                survivors: tuple[int, ...], targets: tuple[int, ...],
                interpret: bool = False) -> jax.Array:
    """Rebuild `targets` from any-k `survivors` (the heal/decode kernel —
    the other half of the north star, cmd/erasure-healing.go:401-461).

    shards [B, n, S] u8 with survivor rows meaningful; S % TILE == 0."""
    surv = shards[:, list(survivors[:k]), :]
    w_t = jnp.asarray(_decode_weights_t(k, n, tuple(survivors[:k]), tuple(targets)))
    return gf2_matmul_with_weights(surv, w_t, len(targets), interpret=interpret)
