"""Host-side planning and unpacking around a codec launch.

The direct launch (erasure/codec.py) and the coalescing lanes
(dataplane/batcher.py) stage differently (a fresh array, a recycled
ring slot) but plan a block and unpack a launch's output the same way;
that half lives here, once.
"""

from __future__ import annotations

import numpy as np

from minio_tpu.utils import errors as se
from minio_tpu.utils.shardmath import ceil_div


def split_block(block: bytes, k: int):
    """One erasure block -> (chunk length s = ceil(len / k), the block
    zero-padded to k*s bytes or None when it is that long already, its
    [k, s] u8 view). Padding is free: parity columns never mix."""
    s = ceil_div(len(block), k)
    if len(block) == k * s:
        return s, None, np.frombuffer(block, dtype=np.uint8).reshape(k, s)
    flat = np.zeros(k * s, dtype=np.uint8)
    flat[:len(block)] = np.frombuffer(block, dtype=np.uint8)
    return s, flat, flat.reshape(k, s)


def encode_rows(k: int, m: int, blocks, lens, padded, parity, digs,
                out_chunks: list, out_digs: list | None) -> None:
    """Append, per block, its n = k+m shard chunks (data chunks alias
    the block or its padded copy, parity chunks alias `parity`) and,
    when `out_digs` is a list, its n chunk digests."""
    for bi, block in enumerate(blocks):
        s = lens[bi]
        mv = memoryview(padded[bi] if padded[bi] is not None else block)
        row = [mv[i * s:(i + 1) * s] for i in range(k)]
        if m:
            row += [memoryview(parity[bi, j])[:s] for j in range(m)]
        out_chunks.append(row)
        if out_digs is not None:
            out_digs.append([digs[bi, i].tobytes() for i in range(k + m)])


def rebuilt_rows(rebuilt, digs, lens, t: int,
                 out_chunks: list, out_digs: list | None) -> None:
    """Append, per block, its `t` rebuilt chunks cut to the block's
    chunk length and, when `out_digs` is a list, their digests."""
    for r, s in enumerate(lens):
        out_chunks.append([rebuilt[r, ti, :s].tobytes() for ti in range(t)])
        if out_digs is not None:
            out_digs.append([digs[r, ti].tobytes() for ti in range(t)])


def one_pattern_survivors(shard_chunks, k: int, n: int) -> tuple[int, ...]:
    """The first k present shards of a batch whose blocks all share one
    failure pattern (the heal shape); raises on a mixed batch."""
    pattern = [c is not None for c in shard_chunks[0]]
    for row in shard_chunks[1:]:
        if [c is not None for c in row] != pattern:
            raise ValueError(
                "begin_reconstruct needs one failure pattern per batch "
                "(use decode_blocks for mixed patterns)")
    present = [i for i in range(n) if pattern[i]]
    if len(present) < k:
        raise se.InsufficientReadQuorum(
            "", "", f"only {len(present)} of {k} shards available")
    return tuple(present[:k])


def plan_rebuild(shard_chunks, k: int, n: int, need_all: bool):
    """Per block, which k shards rebuild which missing ones ->
    (want: the shard indices the caller asked for, per_block:
    [(survivors, targets)], t_max: the most targets any block has)."""
    want = list(range(n) if need_all else range(k))
    per_block: list[tuple[tuple[int, ...], tuple[int, ...]]] = []
    t_max = 0
    for bi, row in enumerate(shard_chunks):
        present = [i for i in range(n) if row[i] is not None]
        if len(present) < k:
            raise se.InsufficientReadQuorum(
                "", "", f"block {bi}: only {len(present)} of {k} shards")
        targets = tuple(i for i in want if row[i] is None)
        per_block.append((tuple(present[:k]), targets))
        t_max = max(t_max, len(targets))
    return want, per_block, t_max


def patch_rows(shard_chunks, per_block, lens, rebuilt, want) -> list:
    """Per block, the wanted chunks with every target taken from
    `rebuilt[block, target ordinal]`, cut to the block's chunk length."""
    out = []
    for bi, row in enumerate(shard_chunks):
        fixed = list(row)
        for ti, shard_idx in enumerate(per_block[bi][1]):
            fixed[shard_idx] = rebuilt[bi, ti, :lens[bi]].tobytes()
        out.append([fixed[i] for i in want])
    return out
