"""Lane or direct launch: the one place that decides (docs/DATAPLANE.md).

Codec work reaches the device one of two ways: coalesced with other
requests' rows in a `BatchPlane` lane, or as its own launch through
`ErasureCodec` / `fused.digest_chunks_host`. The whole decision:

| op | lane when | lane full (`OperationTimedOut`) | else |
|---|---|---|---|
| `begin_encode` (PUT) | plane enabled, `codec.m > 0`, `ceil(max block len / k)` <= `ENCODE_GATE` | propagates: S3 answers 503 SlowDown (`s3/errors._EXC_MAP`) | `codec.begin_encode` |
| `decode_blocks` (GET) | plane enabled, `codec.m > 0`, `block_lens` non-empty, `ceil(max len / k)` <= `RECON_GATE` | falls back to direct | `codec.decode_blocks` |
| `begin_reconstruct` (heal) | the same gate on `block_lens` | falls back | `codec.begin_reconstruct` |
| `digest_chunks` (GET verify, deep verify) | plane enabled, `cap` <= `ENCODE_GATE` (no `m` test) | falls back | `fused.digest_chunks_host` |
| `digest_staged` (GET verify of a batch its reads laid into the launch's array) | never: the caller stages only where `verifies_in_place(cap)`, `digest_chunks`' lane test negated, holds | – | `fused.digest_staged_host` |

"Plane enabled" is `dataplane.maybe_plane()`: `MTPU_BATCHED_DATAPLANE=0`
means always direct, and a front-door worker's router answers there
too. The asymmetries are deliberate. A full lane is admission control
for PUT, as a full WAL queue is (utils/admission.py); reads and
verifies fall back, because a failed verify reads as a drive fault
(healing.py would mark healthy drives offline for load on the shared
plane). The verify lane has no decode matrix, so it ignores `m`.
The mesh launch is a property of the direct encode
(`ErasureCodec._dispatch_encode`), not a third route.

The codec is taken by duck type (`k`, `m`, `block_size` and the direct
methods), so `dataplane/` imports nothing from `erasure/`.
"""

from __future__ import annotations

from minio_tpu.dataplane import maybe_plane
from minio_tpu.utils import errors as se
from minio_tpu.utils.shardmath import ceil_div

# Widest chunk (bytes) a lane coalesces; wider work launches directly.
# Both are crossovers measured on the 8-device CPU mesh, in records
# since deleted (coalesced encode 1.05-1.07x at 10 KiB objects,
# 0.76-0.97x at 1 MiB; reconstruct, whose rows each carry a decode
# matrix, +15 % at 16 KiB chunks and -19 % at 64 KiB). No chip cell has
# moved them yet: the cells run chunks of 16384 (lanes) and 87382
# (direct), nothing in between.
ENCODE_GATE = 65536
RECON_GATE = 16384


def begin_encode(codec, blocks: list[bytes], with_digests: bool = False):
    """-> a handle whose wait() gives (chunk rows, digest rows | None).
    A saturated lane raises OperationTimedOut to the caller."""
    plane = maybe_plane() if codec.m else None
    if plane is not None and ceil_div(
            max(map(len, blocks)), codec.k) <= ENCODE_GATE:
        return plane.begin_encode(codec.k, codec.m, codec.block_size,
                                  blocks, with_digests=with_digests)
    return codec.begin_encode(blocks, with_digests=with_digests)


def _recon_plane(codec, block_lens):
    plane = maybe_plane() if codec.m else None
    if (plane is not None and block_lens
            and ceil_div(max(block_lens), codec.k) <= RECON_GATE):
        return plane
    return None


def decode_blocks(codec, rows, block_lens):
    """GET-path reconstruction of the data chunks; mixed failure
    patterns share a lane launch (per-row decode matrices ride as
    data)."""
    plane = _recon_plane(codec, block_lens)
    if plane is not None:
        try:
            return plane.decode_blocks(codec.k, codec.m, codec.block_size,
                                       rows, block_lens)
        except se.OperationTimedOut:
            pass  # lane saturated: the direct launch still serves
    return codec.decode_blocks(rows, block_lens)


def begin_reconstruct(codec, rows, block_lens, targets,
                      with_digests: bool = False):
    """Heal-shaped rebuild (one failure pattern a batch) -> a handle
    whose wait() gives (rebuilt chunks per target, digests | None)."""
    plane = _recon_plane(codec, block_lens)
    if plane is not None:
        try:
            return plane.begin_reconstruct(
                codec.k, codec.m, codec.block_size, rows, block_lens,
                targets, with_digests=with_digests)
        except se.OperationTimedOut:
            pass  # lane saturated: the direct launch still serves
    return codec.begin_reconstruct(rows, block_lens, targets,
                                   with_digests=with_digests)


def _verify_plane(cap: int):
    plane = maybe_plane()
    return plane if plane is not None and cap <= ENCODE_GATE else None


def digest_chunks(chunks: list, cap: int) -> list[bytes]:
    """mxsum256 digests of a ragged list of chunks, each <= cap."""
    plane = _verify_plane(cap)
    if plane is not None:
        try:
            return plane.digest_chunks(chunks, cap)
        except se.OperationTimedOut:
            pass  # lane saturated: the direct launch still serves
    from minio_tpu.ops import fused

    return fused.digest_chunks_host(chunks, cap)


def verifies_in_place(cap: int) -> bool:
    """Whether digest_chunks(…, cap) would launch directly: the caller
    may then stage the rows itself and call digest_staged."""
    return _verify_plane(cap) is None


def digest_staged(stage, lens):
    """Digests [rows, 32] of rows the caller staged (verifies_in_place
    said so): the direct launch, no copy a chunk."""
    from minio_tpu.ops import fused

    return fused.digest_staged_host(stage, lens)
