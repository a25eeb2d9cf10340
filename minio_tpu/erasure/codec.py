"""ErasureCodec — geometry + batched device codec for object streams.

Mirrors the Erasure surface (cmd/erasure-coding.go:28-143): shard_size /
shard_file_size / shard_file_offset math plus Encode/Decode entry points —
but batched: the streaming loops hand the codec a *batch* of 1 MiB blocks
per call so the GF(2) matmul launches stay MXU-sized (the reference encodes
block-at-a-time per goroutine; on TPU batching across blocks is where
throughput comes from — SURVEY.md §2.4 P2).

Partial-block handling exploits column independence of the GF math: a short
block is split into ceil(len/k) shards, zero-padded to the full shard width,
batch-encoded with the full blocks, and the parity is simply truncated back
— parity columns never mix, so padding is free.
"""

from __future__ import annotations

import time

import numpy as np

from minio_tpu.obs import flight
from minio_tpu.obs import kernel as obs_kernel
from minio_tpu.ops import rs_xla, staging
from minio_tpu.utils.shardmath import ceil_div as _ceil_div
from minio_tpu.utils import shardmath

DEFAULT_BLOCK_SIZE = 1 << 20  # reference blockSizeV2, cmd/object-api-common.go:41

_SERVING_MESH: object = "unset"


def serving_mesh():
    """The device mesh the SERVING codec shards over, or None.

    Multi-chip hosts (a v5e-8 slice is 8 local devices) run the fused
    encode+digest launch sharded (dp, tp, sp) with psum completing the
    GF(2) contraction over ICI — the P6/ICI path of SURVEY §2.4/§5.8 in
    the production PutObject, not just the dryrun. Single-device hosts
    return None (plain fused launch). CPU "devices" are virtual (one
    physical core), so the mesh path is opt-in there via
    MTPU_MESH_CODEC=1 — which is how the test suite exercises it on the
    8-device CPU mesh.
    """
    global _SERVING_MESH
    import os

    if _SERVING_MESH == "unset":
        import jax

        devs = jax.devices()
        use = len(devs) > 1 and (
            devs[0].platform != "cpu"
            or os.environ.get("MTPU_MESH_CODEC") == "1")
        if use:
            from minio_tpu.parallel import make_mesh

            _SERVING_MESH = make_mesh(devices=devs)
        else:
            _SERVING_MESH = None
    return _SERVING_MESH


class PendingEncode:
    """Handle to an in-flight device encode launch (JAX async dispatch).

    begin_encode returns immediately after queuing the launch; the host
    thread overlaps the next batch's read/copy and the previous batch's
    drive fan-out with this batch's device compute — the reference's
    read/encode/write block pipeline (cmd/erasure-encode.go:80-107, P2 in
    SURVEY §2.4) expressed as dispatch-ahead instead of goroutines.

    wait() materializes results with ONE contiguous device->host transfer
    per tensor and hands out zero-copy memoryview slices (no per-shard
    .tobytes()). Data chunks alias the caller's original block buffers;
    parity chunks alias the transferred array, which the views keep alive.
    """

    def __init__(self, codec: "ErasureCodec", blocks: list[bytes],
                 chunk_lens: list[int], padded: list[np.ndarray | None],
                 parity_dev, digs_dev):
        self._codec = codec
        self._blocks = blocks
        self._lens = chunk_lens
        self._padded = padded
        self._parity_dev = parity_dev
        self._digs_dev = digs_dev

    def wait(self) -> tuple[list[list[memoryview]], list[list[bytes]] | None]:
        """-> (per-block list of n shard chunks, per-block list of n chunk
        digests or None when digests were not requested)."""
        k, m = self._codec.k, self._codec.m
        # Device done + the one D2H per tensor.
        with flight.span("enc_wait", "dataplane"):
            parity = (np.asarray(self._parity_dev)
                      if self._parity_dev is not None else None)
            digs = (np.asarray(self._digs_dev)
                    if self._digs_dev is not None else None)
        out_chunks: list[list[memoryview]] = []
        out_digs: list[list[bytes]] | None = [] if digs is not None else None
        staging.encode_rows(k, m, self._blocks, self._lens, self._padded,
                            parity, digs, out_chunks, out_digs)
        return out_chunks, out_digs


class PendingDecode:
    """Handle to an in-flight rebuild launch (see begin_reconstruct)."""

    def __init__(self, targets: tuple[int, ...], chunk_lens: list[int],
                 rebuilt_dev, digs_dev):
        self.targets = targets
        self._lens = chunk_lens
        self._rebuilt_dev = rebuilt_dev
        self._digs_dev = digs_dev

    def wait(self) -> tuple[list[list[bytes]], list[list[bytes]] | None]:
        """-> (per block: rebuilt chunk per target, per block: digest per
        target or None)."""
        rebuilt = np.asarray(self._rebuilt_dev)
        digs = (np.asarray(self._digs_dev)
                if self._digs_dev is not None else None)
        out_chunks, out_digs = [], [] if digs is not None else None
        staging.rebuilt_rows(rebuilt, digs, self._lens, len(self.targets),
                             out_chunks, out_digs)
        return out_chunks, out_digs


class ErasureCodec:
    def __init__(self, data_blocks: int, parity_blocks: int,
                 block_size: int = DEFAULT_BLOCK_SIZE):
        if data_blocks <= 0 or parity_blocks < 0:
            raise ValueError(f"bad geometry k={data_blocks} m={parity_blocks}")
        if data_blocks + parity_blocks > 256:
            raise ValueError("k+m exceeds GF(2^8) limit of 256")
        self.k = data_blocks
        self.m = parity_blocks
        self.block_size = block_size

    # --- geometry (cmd/erasure-coding.go:115-143) ---

    def shard_size(self) -> int:
        return shardmath.shard_size(self.block_size, self.k)

    def shard_file_size(self, total_length: int) -> int:
        return shardmath.shard_file_size(total_length, self.block_size, self.k)

    def shard_file_offset(self, start: int, length: int, total_length: int) -> int:
        return shardmath.shard_file_offset(start, length, total_length,
                                           self.block_size, self.k)

    # --- batched encode ---

    def begin_encode(self, blocks: list[bytes],
                     with_digests: bool = False) -> PendingEncode:
        """Queue one device launch encoding a batch of erasure blocks
        (parity, and with_digests=True the mxsum256 bitrot digest of every
        shard chunk in the same launch — ops/fused.py). Returns immediately;
        results come from PendingEncode.wait()."""
        with flight.span("enc_stage", "dataplane"):
            batch, chunk_lens, padded = self._stage_blocks(blocks)
        parity_dev = digs_dev = None
        if self.m or with_digests:
            # H2D and the jitted call, until it returns (async dispatch).
            with flight.span("enc_dispatch", "dataplane"):
                parity_dev, digs_dev = self._dispatch_encode(
                    batch, chunk_lens, len(blocks), with_digests)
        return PendingEncode(self, blocks, chunk_lens, padded,
                             parity_dev, digs_dev)

    def _stage_blocks(self, blocks: list[bytes]):
        """Host staging of one batch -> (batch [rows, k, s_stage] u8,
        chunk_lens per block, padded copies of the short blocks)."""
        from minio_tpu.ops import fused

        s_full = self.shard_size()
        # Shape bucketing (fused.bucket_rows / bucket_width): pad the
        # row count to the next power of two so mixed object sizes
        # (whose tail batches carry arbitrary block counts) cannot
        # churn the jit trace cache, and stage at the batch's ACTUAL
        # pow-2 chunk width instead of the geometry's full shard width
        # — a 10 KiB object must not pay a 1 MiB-block-wide launch.
        # Both paddings are invisible in results: parity columns never
        # mix and mxsum digests are cap-invariant; pad rows are zeros
        # with chunk_len 0 and every consumer iterates real blocks only.
        chunk_lens: list[int] = []
        padded: list[np.ndarray | None] = []
        views: list[np.ndarray] = []
        for bi, block in enumerate(blocks):
            if not 0 < len(block) <= self.block_size:
                raise ValueError(f"block {bi} size {len(block)}")
            s, flat, view = staging.split_block(block, self.k)
            chunk_lens.append(s)
            padded.append(flat)
            views.append(view)
        rows = fused.bucket_rows(len(blocks))
        s_stage = min(s_full, fused.bucket_width(max(chunk_lens)))
        batch = np.empty((rows, self.k, s_stage), dtype=np.uint8)
        for bi, view in enumerate(views):
            s = chunk_lens[bi]
            batch[bi, :, :s] = view
            batch[bi, :, s:] = 0
        if rows != len(blocks):
            batch[len(blocks):] = 0
        return batch, chunk_lens, padded

    def _dispatch_encode(self, batch, chunk_lens: list[int], b: int,
                         with_digests: bool):
        """The device call for one staged batch of `b` real blocks ->
        (parity_dev | None, digs_dev | None), not yet materialized."""
        import jax.numpy as jnp

        from minio_tpu.ops import fused

        rows, _k, s_stage = batch.shape
        s_full = self.shard_size()
        staged_lens = chunk_lens + [0] * (rows - b)
        parity_dev = digs_dev = None
        mesh = serving_mesh()
        # rows (not b) is the staged batch dim: pow-2 row padding
        # keeps non-pow-2 tail batches mesh-eligible — pad rows are
        # zeros, their parity/digests are computed and ignored
        # (wait() iterates real blocks only).
        dims_ok = (mesh is not None
                   and rows % mesh.shape["dp"] == 0
                   and self.k % mesh.shape["tp"] == 0
                   and s_full % mesh.shape["sp"] == 0)
        if (dims_ok and self.m and with_digests
                and all(s == s_full for s in chunk_lens)):
            # Multi-device host, full blocks: the mesh-sharded fused
            # launch (psum GF contraction over ICI, sp-sharded mxsum)
            # — the host numpy batch stays uncommitted so jit shards
            # it straight onto the mesh. Ragged tails fall through to
            # the single-device launch, which handles per-block
            # lengths.
            from minio_tpu.parallel import sharded_encode_with_mxsum

            t0 = time.perf_counter()
            parity_dev, digs_dev = sharded_encode_with_mxsum(
                mesh, batch, self.k, self.m)
            obs_kernel.observe("encode_digests", "mesh", t0,
                               blocks=b, nbytes=batch.size,
                               out=parity_dev)
        elif dims_ok and self.m and not with_digests:
            from minio_tpu.parallel import sharded_encode

            t0 = time.perf_counter()
            parity_dev = sharded_encode(mesh, batch, self.k, self.m)
            obs_kernel.observe("encode", "mesh", t0,
                               blocks=b, nbytes=batch.size,
                               out=parity_dev)
        else:
            data_dev = jnp.asarray(batch)
            lens_dev = jnp.asarray(staged_lens, dtype=jnp.int32)
            if self.m and with_digests:
                parity_dev, digs_dev = fused.encode_with_digests(
                    data_dev, self.k, self.m, lens_dev)
            elif self.m:
                parity_dev = fused.encode_only(data_dev, self.k, self.m)
            else:  # digests for a parity-less geometry (k shards only)
                digs_dev = fused.verify_digests(
                    data_dev.reshape(rows * self.k, s_stage),
                    jnp.repeat(lens_dev, self.k),
                ).reshape(rows, self.k, -1)
        return parity_dev, digs_dev

    def encode_blocks(self, blocks: list[bytes]) -> list[list[bytes]]:
        """Synchronous encode: per block, the n = k+m shard chunks (data
        first, then parity), each ceil(len(block)/k) bytes."""
        if not blocks:
            return []
        chunks, _ = self.begin_encode(blocks).wait()
        return [[bytes(c) for c in row] for row in chunks]

    def begin_reconstruct(self, shard_chunks: list[list[bytes | None]],
                          block_lens: list[int],
                          targets: tuple[int, ...],
                          with_digests: bool = False) -> "PendingDecode":
        """Queue one rebuild launch for a batch of blocks sharing a single
        failure pattern (the heal loop's shape: one object, one drive
        state). with_digests=True computes the rebuilt chunks' mxsum256
        digests in the SAME launch (fused.reconstruct_with_digests) —
        heal writes them straight into fresh [digest][chunk] shard files.
        Returns immediately (JAX async dispatch): the heal loop reads the
        next batch while the device rebuilds this one."""
        import jax.numpy as jnp

        from minio_tpu.ops import fused

        if not shard_chunks:
            return PendingDecode(tuple(targets), [], None, None)
        n = self.k + self.m
        s_full = self.shard_size()
        survivors = staging.one_pattern_survivors(shard_chunks, self.k, n)
        chunk_lens = [_ceil_div(bl, self.k) for bl in block_lens]
        # Survivor-compacted staging ([B, k, S], no dead parity rows) and
        # the decode matrix as runtime data — the failure pattern stays
        # out of the jit compile key (C(n, <=m) patterns exist; static
        # args would recompile the kernel per pattern mid-sweep). Rows
        # pad to the power-of-two bucket (fused.bucket_rows) so a heal
        # sweep's ragged tail batches reuse the same compiled program.
        rows = fused.bucket_rows(len(shard_chunks))
        s_stage = min(s_full, fused.bucket_width(max(chunk_lens)))
        batch = np.zeros((rows, self.k, s_stage), dtype=np.uint8)
        for bi, row in enumerate(shard_chunks):
            for ci, si in enumerate(survivors):
                c = row[si]
                batch[bi, ci, : len(c)] = np.frombuffer(c, dtype=np.uint8)
        from minio_tpu.ops import rs_pallas

        w_t = jnp.asarray(rs_pallas._decode_weights_t(
            self.k, n, survivors, tuple(targets)))
        staged_lens = chunk_lens + [0] * (rows - len(shard_chunks))
        rebuilt_dev, digs_dev = fused.reconstruct_weights_digests(
            jnp.asarray(batch), w_t,
            jnp.asarray(staged_lens, dtype=jnp.int32),
            len(targets), with_digests=with_digests)
        return PendingDecode(tuple(targets), chunk_lens, rebuilt_dev, digs_dev)

    # --- batched decode / reconstruct ---

    def decode_blocks(
        self,
        shard_chunks: list[list[bytes | None]],
        block_lens: list[int],
        need_all: bool = False,
    ) -> list[list[bytes]]:
        """Rebuild data (and optionally parity) chunks for a batch of blocks.

        shard_chunks[b][i] is shard i's chunk for block b, or None if that
        drive is unavailable — the any-k semantics of the reference's
        DecodeDataBlocks/Reconstruct (cmd/erasure-coding.go:89-113). All
        blocks in one call must share a single failure pattern (the caller
        groups by pattern; patterns are per-GET stable since drive health
        doesn't flip per block).

        Returns per block the k data chunks (need_all=False) or all n chunks.
        """
        n = self.k + self.m
        if not shard_chunks:
            return []
        present = [shard_chunks[0][i] is not None for i in range(n)]
        for row in shard_chunks:
            if [c is not None for c in row] != present:
                # Mixed failure patterns: the per-block-weight launch.
                return self.decode_blocks_multi(shard_chunks, block_lens, need_all)
        if sum(present) < self.k:
            from minio_tpu.utils import errors as se
            raise se.InsufficientReadQuorum(
                "", "", f"only {sum(present)} of required {self.k} shards available"
            )
        want = range(n) if need_all else range(self.k)
        targets = [i for i in want if not present[i]]

        chunk_lens = [_ceil_div(bl, self.k) for bl in block_lens]
        if not targets:
            return [
                [row[i] for i in want]  # type: ignore[misc]
                for row in shard_chunks
            ]

        survivors = tuple([i for i in range(n) if present[i]][: self.k])
        from minio_tpu.ops import fused

        s_stage = min(self.shard_size(),
                      fused.bucket_width(max(chunk_lens)))
        # Rows are already compacted to the k survivors, so feed the raw
        # GF(2) contraction with the per-pattern decode weights directly.
        batch = np.zeros((len(shard_chunks), self.k, s_stage),
                         dtype=np.uint8)
        for bi, row in enumerate(shard_chunks):
            for si, shard_idx in enumerate(survivors):
                c = row[shard_idx]
                batch[bi, si, : len(c)] = np.frombuffer(c, dtype=np.uint8)
        w = rs_xla._device_decode_weights(self.k, n, survivors, tuple(targets))
        rebuilt = np.asarray(
            rs_xla.gf2_matmul_with_weights(batch, w, len(targets))
        )
        return staging.patch_rows(
            shard_chunks, [(survivors, targets)] * len(shard_chunks),
            chunk_lens, rebuilt, want)

    def decode_blocks_multi(
        self,
        shard_chunks: list[list[bytes | None]],
        block_lens: list[int],
        need_all: bool = False,
    ) -> list[list[bytes]]:
        """decode_blocks for a batch whose blocks have DIFFERENT failure
        patterns: every block carries its own stacked decode matrix and the
        whole batch rebuilds in ONE launch (rs_xla.gf2_matmul_multi) — the
        TPU-native form of healing many objects with differing drive states
        in a single batched solve (cmd/erasure-healing.go heals pattern by
        pattern)."""
        from minio_tpu.ops import fused

        n = self.k + self.m
        if not shard_chunks:
            return []
        want, per_block, t_max = staging.plan_rebuild(
            shard_chunks, self.k, n, need_all)
        if t_max == 0:
            return [[row[i] for i in want] for row in shard_chunks]  # type: ignore[misc]
        chunk_lens = [_ceil_div(bl, self.k) for bl in block_lens]
        s_stage = min(self.shard_size(),
                      fused.bucket_width(max(chunk_lens)))
        batch = np.zeros((len(shard_chunks), self.k, s_stage),
                         dtype=np.uint8)
        weights = np.zeros((len(shard_chunks), self.k * 8, t_max * 8),
                           dtype=np.int8)
        for bi, row in enumerate(shard_chunks):
            survivors, targets = per_block[bi]
            for si, shard_idx in enumerate(survivors):
                c = row[shard_idx]
                batch[bi, si, : len(c)] = np.frombuffer(c, dtype=np.uint8)
            if targets:
                w = rs_xla._decode_weights_np(self.k, n, survivors, targets)
                weights[bi, :, : len(targets) * 8] = w
        rebuilt = np.asarray(rs_xla.gf2_matmul_multi(batch, weights, t_max))
        return staging.patch_rows(shard_chunks, per_block, chunk_lens,
                                  rebuilt, want)
