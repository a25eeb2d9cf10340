"""ErasureObjects — one erasure set: the core object engine.

Role-equivalent of erasureObjects (cmd/erasure.go:49, cmd/erasure-object.go):
PutObject streams blocks through the batched TPU codec and fans bitrot-framed
shards out to drives with write-quorum accounting; GetObject elects metadata
by quorum, reads any-k shards (data-first), and reconstructs through the
codec only when shards are missing; deletes and tagging follow the same
quorum discipline.

Differences from the reference are deliberate TPU-first design:
- blocks are encoded in batches (default 16 x 1 MiB per device launch,
  dispatch-ahead depth 3)
  rather than block-at-a-time (cmd/erasure-encode.go:80);
- reconstruction groups blocks by failure pattern into single batched
  launches (cmd/erasure-decode.go reconstructs per block);
- drive fan-out is a thread pool feeding streaming create_file generators
  (the io.Pipe + goroutine pattern, cmd/erasure-encode.go:36, collapsed
  into queues).
"""

from __future__ import annotations

import hashlib
import os
import queue
import threading
import time
import uuid
from concurrent.futures import TimeoutError as _FutTimeout
from typing import BinaryIO, Iterator

import numpy as np

from minio_tpu import hottier, metaplane, obs
from minio_tpu.dataplane import route
from minio_tpu.obs import flight
from minio_tpu.erasure.codec import DEFAULT_BLOCK_SIZE, ErasureCodec
from minio_tpu.erasure import listing
from minio_tpu.erasure.sysstore import SysConfigStore
from minio_tpu.erasure.healing import HealingMixin, MRFHealer
from minio_tpu.erasure.multipart import MultipartMixin
from minio_tpu.erasure.metadata import (
    election_sig,
    find_fileinfo_in_quorum,
    hash_order,
    note_leaked_worker,
    parallel_map,
    run_bounded,
    shuffle_by_distribution,
)
from minio_tpu.erasure.writer_pool import shard_writers
from minio_tpu.storage import healthcheck as _health
from minio_tpu.erasure.types import (
    BucketInfo,
    DeletedObject,
    ListObjectsInfo,
    ListObjectVersionsInfo,
    ObjectInfo,
    ObjectOptions,
    ObjectToDelete,
)
from minio_tpu.ops import bitrot
from minio_tpu.storage.api import StorageAPI
from minio_tpu.storage.fileinfo import ChecksumInfo, ErasureInfo, FileInfo, PartInfo
from minio_tpu.storage.xlmeta import XLMeta
from minio_tpu.utils import errors as se
from minio_tpu.utils.quorum import reduce_write_quorum

_WRITE_SENTINEL = None

# Objects at or below this size are inlined into the journal instead of
# getting shard files (reference inlines small objects in xl.meta v2).
INLINE_DATA_LIMIT = 16 << 10

# Tail-latency hedging on shard reads (first-k-wins): launched spares and
# how many of them beat the straggler they covered for.
_HEDGED_READS = obs.counter(
    "minio_tpu_hedged_reads_total",
    "Spare shard reads launched after the hedge delay").labels()
_HEDGED_WINS = obs.counter(
    "minio_tpu_hedged_reads_won_total",
    "Hedged shard reads that made quorum before the straggler").labels()

# What _read_shards asked of the shards' readers: one read a shard a
# batch (BitrotReader.read_records_into or read_records), so reads / GET
# is the shards read times the batches, and records / reads the batch's
# blocks.
_SHARD_READS = obs.counter(
    "minio_tpu_get_shard_reads_total",
    "Reads GET's shard tasks issued to a shard's reader").labels()
_SHARD_RECORDS = obs.counter(
    "minio_tpu_get_shard_records_total",
    "Records ([digest][chunk]) those reads returned").labels()
# Rows GET's batched verify digested: read straight into the launch's
# staging array (_VerifyStage), or copied there (a lane, a hedged spare
# with no slot left, ids that are not consecutive).
_VERIFY_ROWS = obs.counter(
    "minio_tpu_get_verify_rows_total",
    "Rows GET's batched verify digested, by how they reached the "
    "launch's staging array", ("staged",))
_VERIFY_READ = _VERIFY_ROWS.labels(staged="read")
_VERIFY_COPIED = _VERIFY_ROWS.labels(staged="copied")

# Shared with cache/disk.py (the registry dedupes by family name):
# latest-only caches — the disk cache and the HBM hot tier — bypass
# explicitly-versioned reads and account them here instead of
# miscounting them as misses (docs/METRICS.md).
_CACHE_BYPASS = obs.counter(
    "minio_tpu_cache_bypass_total",
    "Reads that bypassed a latest-only cache tier by contract",
    ("reason",))


def _read_full(data: BinaryIO, n: int) -> bytes:
    """Read exactly n bytes unless EOF — short read()s are legal for
    sockets/pipes and must not skew the fixed-block erasure layout.

    Fast path: most sources (BytesIO, spool files) satisfy the whole read
    in one call — return that buffer directly instead of paying two extra
    whole-segment copies (bytearray append + bytes()), which showed up as
    ~25% of large-PUT wall time. The slow path hands back its accumulator
    bytearray as-is: every consumer (md5, np.frombuffer, the native
    encoder's from_buffer borrow) takes any bytes-like buffer."""
    if n <= 0:
        return b""
    first = data.read(n)
    if not first:
        return b""
    if len(first) == n:
        return first
    buf = bytearray(first)
    while len(buf) < n:
        chunk = data.read(n - len(buf))
        if not chunk:
            break
        buf += chunk
    return buf


def default_parity(n_drives: int) -> int:
    """Default parity per set width (reference storage-class defaults,
    cmd/config/storageclass/storage-class.go:234)."""
    if n_drives == 1:
        return 0
    if n_drives <= 3:
        return 1
    if n_drives <= 5:
        return 2
    if n_drives <= 7:
        return 3
    return 4


class ErasureObjects(HealingMixin, MultipartMixin, SysConfigStore):
    def __init__(
        self,
        drives: list[StorageAPI],
        parity: int | None = None,
        block_size: int = DEFAULT_BLOCK_SIZE,
        batch_blocks: int = 16,
        bitrot_algorithm: str | None = None,
        enable_mrf: bool = False,
        nslock=None,
    ):
        if not drives:
            raise ValueError("empty drive set")
        self.drives = drives
        # Per-(bucket,object) namespace lock around mutating commits —
        # in-process by default, dsync-quorum in distributed topologies
        # (reference NewNSLock, cmd/namespace-lock.go:48).
        if nslock is None:
            from minio_tpu.dist.nslock import NamespaceLockMap
            nslock = NamespaceLockMap()
        self.nslock = nslock
        self.n = len(drives)
        self.parity = default_parity(self.n) if parity is None else parity
        # Reference validateParity bound (parity <= drives/2): beyond it
        # data quorum k(+1) drops below a majority and two conflicting
        # partial writes could both claim success.
        if not 0 <= self.parity <= self.n // 2:
            raise ValueError(
                f"parity {self.parity} invalid for {self.n} drives "
                f"(bound: drives/2 = {self.n // 2})")
        self.block_size = block_size
        self.batch_blocks = batch_blocks
        # Default bitrot algorithm follows the backend: mxsum256 on
        # accelerators (fused into the codec launches), host-native hash on
        # CPU (reference default HH256S, cmd/xl-storage-format-v1.go:117).
        self.bitrot_algorithm = (bitrot_algorithm if bitrot_algorithm
                                 else bitrot.device_default_algorithm())
        self.mrf: MRFHealer | None = MRFHealer(self) if enable_mrf else None
        self._read_pool = None
        self._read_pool_mu = threading.Lock()
        # Bucket-existence TTL cache: put_object stats every drive for the
        # bucket otherwise, a pool dispatch per op. Reference keeps bucket
        # metadata fully in memory (BucketMetadataSys); a short TTL keeps
        # cross-node deletes visible within a bound instead of a broadcast.
        self._bucket_cache: dict[str, tuple[float, BucketInfo]] = {}
        self._bucket_cache_ttl = 2.0
        # Quorum metadata reads run serially when the set is small and
        # all-local: with the journal parse cache a per-drive read is ~10us,
        # below the shared-pool dispatch cost. Wide sets and any remote
        # drive keep the parallel fan-out (RPC/disk latency dominates there).
        self._serial_meta_reads = self.n <= 8 and self._drives_all_local()
        # Hedged shard reads: rolling EWMA of one shard's batch-read
        # latency feeds the hedge delay; hedge_delay pins it explicitly
        # (tests / operator override). None delay + no history = no hedge
        # before the hard data deadline.
        self._shard_lat: float | None = None
        self.hedge_delay: float | None = None
        # Set-level post-election FileInfo cache (docs/METAPLANE.md):
        # GET/HEAD revalidate one cached election with per-local-drive
        # journal signatures instead of paying the N-drive fan-out.
        # Gated with the group-commit plane; None = every read elects.
        self._setcache = None
        if metaplane.enabled():
            from minio_tpu.metaplane.setcache import SetFileInfoCache

            self._setcache = SetFileInfoCache(metaplane.cache_objects())

    def _meta_invalidate(self, bucket: str, obj: str) -> None:
        """Drop the set-level FileInfo cache entry after a mutating
        fan-out (delete, metadata write, multipart complete, heal).
        Signature validation would catch these anyway; eager
        invalidation keeps the common case from paying a miss probe.
        The HBM hot tier rides the same hook: the mutation drops (and,
        for a still-hot key, re-admits) its device residence — the
        serve-time identity check makes this advisory, never
        load-bearing (docs/HOTTIER.md)."""
        if self._setcache is not None:
            self._setcache.invalidate(bucket, obj)
        tier = hottier.maybe_tier()
        if tier is not None:
            tier.invalidate(bucket, obj)

    @property
    def fast_local_reads(self) -> bool:
        """True when a metadata read on this set is reliably cheap (~100us):
        small all-local set with measured-fast journal stores. The HTTP
        layer uses this to run small-object opens directly on the event
        loop instead of paying an executor round trip."""
        return self._serial_meta_reads and all(
            getattr(d, "fast_sync", False) for d in self.drives)

    def _drives_all_local(self) -> bool:
        from minio_tpu.storage.local import LocalDrive

        for d in self.drives:
            if type(_health.unwrap(d)) is not LocalDrive:
                return False
        return True

    def _meta_deadline(self) -> float:
        """Fan-out deadline for metadata-class quorum ops: the max of the
        drives' adaptive per-op deadlines (drive-resilience plane)."""
        return _health.fleet_deadlines(self.drives)[0]

    def _data_deadline(self) -> float:
        return _health.fleet_deadlines(self.drives)[1]

    def _walk_deadline(self) -> float:
        return _health.fleet_deadlines(self.drives)[2]

    def _drives_all_online(self) -> bool:
        for d in self.drives:
            if isinstance(d, _health.HealthChecker) and d.state != _health.ONLINE:
                return False
        return True

    def _shard_read_pool(self):
        """Long-lived per-instance pool for parallel shard reads — a fresh
        pool per GET stream would pay thread spawn on the hot read path."""
        from concurrent.futures import ThreadPoolExecutor

        with self._read_pool_mu:
            if self._read_pool is None:
                self._read_pool = ThreadPoolExecutor(
                    max_workers=max(self.n, 8),
                    thread_name_prefix="shard-read")
            return self._read_pool

    def close(self) -> None:
        if self.mrf is not None:
            self.mrf.close()
        with self._read_pool_mu:
            if self._read_pool is not None:
                # Keep the (shut-down) executor referenced: a racing GET
                # stream then gets RuntimeError from submit — converted to
                # a quorum error in _read_chunk_rows — rather than an
                # AttributeError from a nulled pool, and a late caller
                # can't silently spawn a leaked replacement pool.
                self._read_pool.shutdown(wait=False, cancel_futures=True)

    def all_drives(self) -> list[StorageAPI]:
        return list(self.drives)

    def health(self) -> dict:
        # Deadline'd fan-out: the readiness probe must answer even while
        # a drive is hanging (a hung disk_info counts as offline).
        results = parallel_map(
            [lambda d=d: d.disk_info() for d in self.drives],
            deadline=self._meta_deadline())
        online = sum(1 for r in results if not isinstance(r, Exception))
        quorum = self._write_quorum_data(self.parity)
        return {
            "healthy": online >= quorum,
            "sets": [{"online": online, "total": self.n, "write_quorum": quorum}],
        }

    # ------------------------------------------------------------------
    # buckets (cmd/erasure-bucket.go)
    # ------------------------------------------------------------------

    def make_bucket(self, bucket: str, opts: ObjectOptions | None = None) -> None:
        _validate_bucket_name(bucket)
        results = parallel_map([lambda d=d: d.make_vol(bucket) for d in self.drives],
                               deadline=self._meta_deadline())
        exists = sum(1 for r in results if isinstance(r, se.VolumeExists))
        if exists >= self._write_quorum_meta():
            raise se.BucketExists(bucket)
        # A minority of stale VolumeExists drives (e.g. a drive that missed a
        # prior delete_bucket) counts as success — the dir is simply reused.
        results = [None if isinstance(r, se.VolumeExists) else r for r in results]
        try:
            reduce_write_quorum(results, self._write_quorum_meta(), bucket)
        except se.InsufficientWriteQuorum:
            parallel_map([lambda d=d: d.delete_vol(bucket) for d in self.drives],
                         deadline=self._meta_deadline())
            raise

    def get_bucket_info(self, bucket: str) -> BucketInfo:
        hit = self._bucket_cache.get(bucket)
        if hit is not None and hit[0] > time.monotonic():
            return hit[1]
        results = parallel_map([lambda d=d: d.stat_vol(bucket) for d in self.drives],
                               deadline=self._meta_deadline())
        for r in results:
            if not isinstance(r, Exception):
                info = BucketInfo(r.name, r.created)
                self._bucket_cache[bucket] = (
                    time.monotonic() + self._bucket_cache_ttl, info)
                return info
        self._bucket_cache.pop(bucket, None)
        if any(isinstance(r, se.VolumeNotFound) for r in results):
            raise se.BucketNotFound(bucket)
        raise se.BucketNotFound(bucket, "", "no drive answered")

    def list_buckets(self) -> list[BucketInfo]:
        results = parallel_map([lambda d=d: d.list_vols() for d in self.drives],
                               deadline=self._meta_deadline())
        seen: dict[str, BucketInfo] = {}
        for r in results:
            if isinstance(r, Exception):
                continue
            for v in r:
                if v.name not in seen:
                    seen[v.name] = BucketInfo(v.name, v.created)
        return sorted(seen.values(), key=lambda b: b.name)

    def delete_bucket(self, bucket: str, force: bool = False) -> None:
        self._bucket_cache.pop(bucket, None)
        tier = hottier.maybe_tier()
        if tier is not None:
            tier.invalidate_bucket(bucket)
        # Data-class deadline: a forced delete rmtrees arbitrary trees.
        results = parallel_map(
            [lambda d=d: d.delete_vol(bucket, force=force) for d in self.drives],
            deadline=self._data_deadline(),
        )
        if any(isinstance(r, se.VolumeNotEmpty) for r in results):
            raise se.BucketNotEmpty(bucket)
        if all(isinstance(r, se.VolumeNotFound) for r in results):
            raise se.BucketNotFound(bucket)
        reduce_write_quorum(results, self._write_quorum_meta(), bucket)

    def parity_for_class(self, sc: str) -> int:
        """Parity for a storage class (reference GetParityForSC,
        cmd/config/storageclass/storage-class.go:234): the `storageclass`
        config subsystem ("EC:N") overrides per class when set on the set
        (sc_parity, applied live by the server); otherwise STANDARD uses
        the constructor parity and RRS drops two below it."""
        sc_map = getattr(self, "sc_parity", None) or {}
        if sc == "REDUCED_REDUNDANCY":
            m = sc_map.get("RRS")
            if m is not None:
                # CONFIGURED values clamp to the reference validateParity
                # bound (parity <= drives/2 — beyond it a sub-majority
                # write could claim quorum). Constructor-chosen defaults
                # pass through untouched: explicit geometries are the
                # operator's call, already validated at construction.
                return max(0, min(int(m), self.n // 2))
            return max(1, self.parity - 2) if self.n >= 4 else self.parity
        m = sc_map.get("STANDARD")
        if m is not None:
            return max(0, min(int(m), self.n // 2))
        return self.parity

    def _write_quorum_meta(self) -> int:
        return self.n // 2 + 1

    def _write_quorum_data(self, parity: int) -> int:
        """Data write quorum: k drives, +1 when k == m so two conflicting
        half-writes can't both claim quorum (cmd/erasure-object.go:639-642)."""
        k = self.n - parity
        return k + (1 if k == parity else 0)

    # ------------------------------------------------------------------
    # put object (cmd/erasure-object.go:606-810)
    # ------------------------------------------------------------------

    def put_object(
        self,
        bucket: str,
        obj: str,
        data: BinaryIO,
        size: int = -1,
        opts: ObjectOptions | None = None,
    ) -> ObjectInfo:
        opts = opts or ObjectOptions()
        _validate_object_name(obj)
        self.get_bucket_info(bucket)

        sc = opts.user_defined.get("x-amz-storage-class", "")
        m = self.parity_for_class(sc)
        k = self.n - m
        write_quorum = self._write_quorum_data(m)

        fi = FileInfo.new(bucket, obj)
        if opts.versioned:
            fi.version_id = opts.version_id or str(uuid.uuid4())
        fi.mod_time = opts.mod_time or time.time()
        fi.metadata = dict(opts.user_defined)
        dist = hash_order(f"{bucket}/{obj}", self.n)
        fi.erasure = ErasureInfo(
            data_blocks=k,
            parity_blocks=m,
            block_size=self.block_size,
            distribution=dist,
            checksums=[ChecksumInfo(1, self.bitrot_algorithm)],
        )

        codec = ErasureCodec(k, m, self.block_size)
        shuffled = shuffle_by_distribution(self.drives, dist)

        md5 = hashlib.md5()
        total = 0
        first_block = _read_full(
            data, min(self.block_size, size) if size >= 0 else self.block_size
        )
        # Timeline: request-body receive up to the first block boundary
        # (small objects: the whole body) is the rx_drain stage.
        flight.mark("rx_drain")

        # Small-object fast path: inline into the journal, no shard files —
        # one metadata write per drive instead of shard + rename.
        if len(first_block) <= INLINE_DATA_LIMIT and (
            size < 0 and len(first_block) < self.block_size or 0 <= size <= INLINE_DATA_LIMIT
        ):
            if 0 <= size != len(first_block):
                raise se.IncompleteBody(
                    bucket, obj, f"got {len(first_block)} of {size} bytes")
            md5.update(first_block)
            fi.size = len(first_block)
            # No defensive copy: the buffer is never mutated after this
            # point, and the journal serializer takes any bytes-like.
            fi.inline_data = first_block
            fi.data_dir = ""
            fi.metadata.setdefault("etag", md5.hexdigest())
            fi.parts = [PartInfo(1, fi.size, fi.size, fi.mod_time)]
            # Inline versions carry no shard files, so the per-drive shard
            # index is meaningless — writing index 0 on every drive makes
            # all journals byte-identical, letting the set share ONE
            # serialized journal (write_metadata_single) instead of four
            # load+merge+serialize rounds.
            fi.erasure.index = 0
            journal = XLMeta()
            journal.add_version(fi)
            raw = journal.serialize()
            # Serial fan-out when every drive's measured journal-store cost
            # is below the pool-dispatch cost (all-local fast-sync media);
            # slow-fsync drives keep the parallel write so the op pays
            # max(fsync) rather than sum(fsync). A non-ONLINE drive forces
            # the deadline-bounded parallel path (a hang must not wedge
            # the serial loop).
            serial_writes = self.fast_local_reads and self._drives_all_online()
            with self.nslock.lock(bucket, obj) as lease:
                self._check_put_precondition(bucket, obj, opts)
                with flight.span("commit", "metaplane", timeline=False,
                                 bucket=bucket, object=obj, inline=True):
                    outcomes = None
                    if self._setcache is not None:
                        # Metaplane armed: two-phase group commit —
                        # submit to every drive's WAL from this thread,
                        # then await the shared fsyncs; no pool worker
                        # blocked per drive (docs/METAPLANE.md).
                        outcomes = self._inline_commit_fast(
                            shuffled, bucket, obj, fi, raw, journal)
                    if outcomes is None:
                        outcomes = parallel_map(
                            [
                                lambda d=d: d.write_metadata_single(
                                    bucket, obj, fi, raw, journal,
                                    defer_reclaim=True)
                                for d in shuffled
                            ],
                            serial=serial_writes,
                            deadline=self._meta_deadline(),
                        )

                def undo_inline():
                    # Same undo discipline as the streaming commit: an
                    # inline overwrite below quorum must restore the
                    # displaced generation on drives that committed.
                    self._meta_invalidate(bucket, obj)
                    undo_fi = FileInfo(volume=bucket, name=obj,
                                       version_id=fi.version_id)

                    def undo(i, d):
                        if not isinstance(outcomes[i], Exception):
                            d.undo_rename(bucket, obj, undo_fi,
                                          outcomes[i])

                    parallel_map([lambda i=i, d=d: undo(i, d)
                                  for i, d in enumerate(shuffled)],
                                 deadline=self._meta_deadline())

                try:
                    reduce_write_quorum(outcomes, write_quorum, bucket, obj)
                except Exception:
                    undo_inline()
                    raise
                if not lease.held:
                    # Lock quorum lost mid-commit (see the streaming
                    # path): roll back rather than complete unprotected.
                    undo_inline()
                    raise se.OperationTimedOut(
                        bucket, obj, "dsync lock quorum lost during "
                        "commit; write rolled back")
                toks = [o for o in outcomes
                        if o and not isinstance(o, Exception)]
                if toks:
                    parallel_map(
                        [lambda d=d, t=t: d.commit_rename(t)
                         for d, t in zip(shuffled, outcomes)
                         if t and not isinstance(t, Exception)],
                        deadline=self._meta_deadline())
                if self._setcache is not None:
                    # Write-through: the committed journal IS what an
                    # election would return (index 0 on every drive),
                    # so the first GET skips the fan-out outright.
                    self._setcache.populate(bucket, obj, "", fi, shuffled)
                tier = hottier.maybe_tier()
                if tier is not None:
                    # An inline overwrite displaces any shard-backed
                    # resident generation (the streaming path rides
                    # _meta_invalidate; inline commits skip it).
                    tier.invalidate(bucket, obj)
            flight.mark("commit", "metaplane")
            return self._fi_to_object_info(bucket, obj, fi)

        # Streaming erasure path.
        tmp_rel = f"tmp/{uuid.uuid4().hex}"
        sys_vol = ".mtpu.sys"

        def cleanup_tmp():
            parallel_map(
                [lambda d=d: d.delete(sys_vol, tmp_rel, recursive=True)
                 for d in shuffled],
                deadline=self._meta_deadline())

        try:
            # The Timeline entry of encode/commit/quorum-read is the
            # sequential mark's; the span adds the bus record and the
            # device-profile annotation, no second entry.
            with flight.span("encode", "dataplane", timeline=False,
                             bucket=bucket, object=obj) as sp:
                total, md5_hex, errs = self._fan_out_encode(
                    shuffled, sys_vol, f"{tmp_rel}/part.1", data, size, codec,
                    write_quorum, bucket, obj, initial=first_block,
                )
                sp.set(bytes=total)
            flight.mark("encode", "dataplane")
        except (se.StorageError, se.ObjectError):
            # Quorum lost mid-encode (InsufficientWriteQuorum is an
            # ObjectError): the healthy drives' tmp staging must not
            # linger — every other failure path fans out this cleanup.
            cleanup_tmp()
            raise

        if size >= 0 and total != size:
            cleanup_tmp()
            raise se.IncompleteBody(bucket, obj, f"got {total} of {size} bytes")

        fi.size = total
        fi.metadata.setdefault("etag", md5_hex)
        fi.parts = [PartInfo(1, total, total, fi.mod_time)]

        tokens: list = [None] * len(shuffled)

        def commit(i: int, drive: StorageAPI):
            if errs[i] is not None:
                raise errs[i]
            tokens[i] = drive.rename_data(
                sys_vol, tmp_rel, _clone_for_drive(fi, i + 1), bucket, obj,
                defer_reclaim=True)

        # Commit under the namespace lock (the reference takes the dist
        # lock just before metadata write + rename, cmd/erasure-object.go:736).
        with self.nslock.lock(bucket, obj) as lease:
            try:
                self._check_put_precondition(bucket, obj, opts)
            except se.ObjectError:
                cleanup_tmp()
                raise
            with flight.span("commit", "metaplane", timeline=False,
                             bucket=bucket, object=obj):
                outcomes = parallel_map(
                    [lambda i=i, d=d: commit(i, d)
                     for i, d in enumerate(shuffled)],
                    deadline=self._meta_deadline(),
                )

            def undo_commit():
                # UNDO everywhere — drives that failed still hold tmp
                # staging; drives that committed must drop the
                # just-written version AND restore whatever the commit
                # displaced (a replaced version's journal entry + data
                # dir), or listings (which union journals) would show an
                # object GET quorum-fails on, and an overwrite would
                # have destroyed the previous generation (reference
                # undo-rename discipline).
                self._meta_invalidate(bucket, obj)
                undo_fi = FileInfo(volume=bucket, name=obj,
                                   version_id=fi.version_id,
                                   data_dir=fi.data_dir)

                def undo(i, d):
                    if isinstance(outcomes[i], Exception):
                        d.delete(sys_vol, tmp_rel, recursive=True)
                    else:
                        d.undo_rename(bucket, obj, undo_fi, tokens[i])

                parallel_map([lambda i=i, d=d: undo(i, d)
                              for i, d in enumerate(shuffled)],
                             deadline=self._meta_deadline())

            try:
                reduce_write_quorum(outcomes, write_quorum, bucket, obj)
            except Exception:
                undo_commit()
                raise
            if not lease.held:
                # The dsync lock lost its refresh quorum mid-commit (a
                # partition isolated us from the locker majority): the
                # critical section is no longer protected, so a racing
                # writer on the other side may have committed too.
                # Completing would risk a silent split-brain overwrite —
                # roll back and fail typed instead.
                undo_commit()
                raise se.OperationTimedOut(
                    bucket, obj,
                    "dsync lock quorum lost during commit; write rolled back")
            # Quorum reached under a live lock: discard the displaced
            # state for good.
            if any(tokens):
                parallel_map([lambda d=d, t=t: d.commit_rename(t)
                              for d, t in zip(shuffled, tokens) if t],
                             deadline=self._meta_deadline())
            self._meta_invalidate(bucket, obj)
        flight.mark("commit", "metaplane")
        # Partial success: quorum met but some drive missed the write — queue
        # it for background heal (reference addPartial, cmd/erasure-object.go:1150).
        if self.mrf is not None and any(isinstance(o, Exception) for o in outcomes):
            self.mrf.add_partial(bucket, obj, fi.version_id)
        return self._fi_to_object_info(bucket, obj, fi)

    # ------------------------------------------------------------------
    # get object (cmd/erasure-object.go:137-358)
    # ------------------------------------------------------------------

    def get_object_info(self, bucket: str, obj: str,
                        opts: ObjectOptions | None = None) -> ObjectInfo:
        opts = opts or ObjectOptions()
        # Same election-window read lock as get_object_reader.
        with self.nslock.rlock(bucket, obj):
            fi = self._read_quorum_fileinfo(bucket, obj, opts.version_id)
        if fi.deleted:
            if opts.version_id:
                return self._fi_to_object_info(bucket, obj, fi)
            raise se.ObjectNotFound(bucket, obj)
        return self._fi_to_object_info(bucket, obj, fi)

    def get_object_reader(
        self,
        bucket: str,
        obj: str,
        opts: ObjectOptions | None = None,
    ):
        """ONE quorum metadata read for info + data: returns
        (info, open_range) where open_range(offset, length) streams object
        bytes using the already-elected FileInfo. The HTTP GET path needs
        the info before it can choose the byte range (SSE/compression
        transforms); the two-call shape (get_object_info + get_object) paid
        the quorum read twice (reference folds this into a single
        GetObjectNInfo reader, cmd/erasure-object.go:137)."""
        opts = opts or ObjectOptions()
        # Read lock around the metadata election (reference GetObject
        # takes the namespace RLock, cmd/erasure-object.go:176): a
        # concurrent overwrite fans journals out drive by drive, and an
        # unlocked reader can catch the set split 50/50 with NEITHER
        # version reaching read quorum. Held for the election only —
        # inline objects are then fully consistent (payload rides the
        # elected journal); shard streams open after release, where the
        # per-record bitrot framing turns any later mutation into a
        # typed read error, never silent corruption.
        with self.nslock.rlock(bucket, obj):
            fi = self._read_quorum_fileinfo(bucket, obj, opts.version_id)
        # Timeline: quorum metadata election (the GET's metadata stage —
        # decode + transfer land in the trailing resp_drain segment).
        flight.mark("meta_elect", "metaplane")
        if fi.deleted:
            raise se.ObjectNotFound(bucket, obj)
        info = self._fi_to_object_info(bucket, obj, fi)
        pinned = bool(opts.version_id)

        def open_range(offset: int = 0, length: int = -1) -> Iterator[bytes]:
            return self._open_fi_range(bucket, obj, fi, offset, length,
                                       pinned=pinned)

        return info, open_range

    def get_object(
        self,
        bucket: str,
        obj: str,
        offset: int = 0,
        length: int = -1,
        opts: ObjectOptions | None = None,
    ) -> tuple[ObjectInfo, Iterator[bytes]]:
        info, open_range = self.get_object_reader(bucket, obj, opts)
        return info, open_range(offset, length)

    def _open_fi_range(self, bucket: str, obj: str, fi: FileInfo,
                       offset: int, length: int,
                       pinned: bool = False) -> Iterator[bytes]:
        if length < 0:
            length = fi.size - offset
        if offset < 0 or length < 0 or offset + length > fi.size:
            raise se.InvalidRange(bucket, obj, f"[{offset}, {offset + length}) of {fi.size}")
        if fi.inline_data:
            payload = fi.inline_data[offset: offset + length]
            return iter([payload])
        tier_name = fi.metadata.get(
            "x-mtpu-internal-transition-tier") if fi.metadata else ""
        if not tier_name and fi.data_dir:
            hot = hottier.maybe_tier()
            if hot is not None:
                if pinned:
                    # Latest-only tier: an explicitly versioned read
                    # bypasses by contract — same accounting as the
                    # disk cache's versioned bypass (docs/METRICS.md).
                    _CACHE_BYPASS.labels(reason="hottier_versioned").inc()
                else:
                    served = hot.serve(bucket, obj, fi, offset, length)
                    if served is not None:
                        # Device-resident hit: one gather+digest launch
                        # + one DMA; zero drive opens.
                        return served
                    hot.note_miss(
                        bucket, obj, fi.size,
                        reader=lambda b=bucket, o=obj: self.get_object(
                            b, o),
                        grid=(fi.erasure.data_blocks,
                              fi.erasure.block_size))
        if tier_name and not fi.data_dir:
            # Transitioned version: data lives on the remote tier; stream
            # through transparently (reference transitioned-object reads,
            # cmd/bucket-lifecycle.go getTransitionedObjectReader). Parts
            # metadata survives transition, so multipart-SSE decryption
            # still sees its per-part layout.
            from minio_tpu.scanner import tiers as tiermod

            reg = tiermod.global_registry()
            key = fi.metadata.get("x-mtpu-internal-transition-key", "")
            try:
                if reg is None:
                    raise tiermod.TierError("no tier registry configured")
                tier = reg.get(tier_name)
                return tier.get(key, offset, length)
            except tiermod.TierError as e:
                # Typed, not a 500: the data's only copy is on a tier we
                # can't reach (e.g. tier deleted with force).
                raise se.ObjectNotFound(bucket, obj,
                                        f"tier {tier_name!r}: {e}") from e
        return self._stream_erasure(bucket, obj, fi, offset, length)

    def _stream_erasure(self, bucket: str, obj: str, fi: FileInfo,
                        offset: int, length: int) -> Iterator[bytes]:
        """Stream [offset, offset+length) across the object's parts — each
        part is an independent erasure stream with its own shard files
        (reference per-part decode loop, cmd/erasure-object.go:297-316)."""
        if length == 0:
            return
        part_off = 0
        for part in fi.parts:
            part_end = part_off + part.size
            if part_end <= offset:
                part_off = part_end
                continue
            if part_off >= offset + length:
                break
            lo = max(offset, part_off) - part_off
            hi = min(offset + length, part_end) - part_off
            yield from self._stream_one_part(bucket, obj, fi, part, lo, hi - lo)
            part_off = part_end

    def _stream_one_part(self, bucket: str, obj: str, fi: FileInfo, part,
                         offset: int, length: int) -> Iterator[bytes]:
        k = fi.erasure.data_blocks
        n = k + fi.erasure.parity_blocks
        codec = ErasureCodec(k, fi.erasure.parity_blocks, fi.erasure.block_size)
        shard_size = codec.shard_size()
        algo = next((c.algorithm for c in fi.erasure.checksums), self.bitrot_algorithm)
        shuffled = shuffle_by_distribution(self.drives, fi.erasure.distribution)
        rel = f"{obj}/{fi.data_dir}/part.{part.number}"
        shard_data_size = codec.shard_file_size(part.size)

        native = self._native_stream(bucket, obj, fi, part, algo, shuffled,
                                     rel, offset, length)
        if native is not None:
            yield from native
            return

        readers: list[bitrot.BitrotReader | None] = [None] * n

        def open_reader(i: int):
            f = shuffled[i].read_file_stream(bucket, rel)
            return bitrot.BitrotReader(f, shard_data_size, shard_size, algo)

        if length == 0:
            return
        first_block = offset // fi.erasure.block_size
        last_block = (offset + length - 1) // fi.erasure.block_size

        # Select shards data-first (parity only on demand) — the staggered
        # any-k read strategy (cmd/erasure-decode.go:120-188). Opening is
        # deferred into the pooled read tasks (_read_chunk_rows), so a
        # drive hanging at open() is hedged/deadlined exactly like one
        # hanging mid-read. Drives already known dead — health-OFFLINE
        # locals and OPEN-breaker peers — start excluded, so selection
        # jumps straight to reconstruction instead of paying a doomed
        # open per batch (the native lane has always done this).
        dead: set[int] = {i for i, d in enumerate(shuffled)
                          if not d.is_online()}
        corrupt: set[int] = set()  # the subset of dead that OBSERVED bitrot
        # Hedge losers: healthy-but-slow shards sidelined for this stream.
        # Never heal-triggering, and reclaimable when selection runs short
        # — a benched shard must not cost quorum on a real failure later.
        benched: set[int] = set()

        def ensure_readers() -> list[int]:
            chosen = [i for i in list(range(k)) + list(range(k, n))
                      if i not in dead and i not in benched][:k]
            if len(chosen) < k and benched:
                benched.clear()  # second chance: slow beats no quorum
                chosen = [i for i in list(range(k)) + list(range(k, n))
                          if i not in dead][:k]
            if len(chosen) < k:
                raise se.InsufficientReadQuorum(bucket, obj, "not enough live shards")
            return sorted(chosen)

        pool = self._shard_read_pool()
        batches: list[tuple[list[int], list[int]]] = []
        bi = first_block
        while bi <= last_block:
            ids = list(range(bi, min(bi + self.batch_blocks, last_block + 1)))
            batches.append((ids, [
                min(fi.erasure.block_size, part.size - b * fi.erasure.block_size)
                for b in ids
            ]))
            bi = ids[-1] + 1

        if len(batches) <= 1:
            # Single batch (small/ranged GET, the high-QPS case): nothing
            # to overlap — read inline, skip the producer thread entirely.
            try:
                for ids, lens in batches:
                    while True:
                        chosen = ensure_readers()
                        try:
                            rows = self._read_chunk_rows(
                                readers, chosen, ids, lens, codec, n,
                                dead, algo, pool=pool, corrupt=corrupt,
                                open_reader=open_reader, benched=benched)
                            break
                        except se.StorageError:
                            continue
                    yield from self._decoded_ranges(
                        codec, rows, ids, lens, fi.erasure.block_size,
                        offset, length)
            finally:
                for r in readers:
                    if r is not None:
                        try:
                            r.src.close()
                        except Exception:  # noqa: BLE001
                            pass
                if dead and self.mrf is not None:
                    self.mrf.add_partial(bucket, obj, fi.version_id,
                                         deep=bool(corrupt))
            return

        # Read-ahead producer (the GET half of P2, SURVEY §2.4): one
        # dedicated thread reads batch N+1 while the consumer verifies,
        # decodes and sends batch N. Readers/dead/re-selection are touched
        # ONLY by the producer, so the existing retry semantics are
        # unchanged. A bounded queue + stop-checked puts guarantee the
        # producer exits promptly on early close.
        out_q: queue.Queue = queue.Queue(maxsize=2)
        stop = threading.Event()

        def _offer(item) -> bool:
            while not stop.is_set():
                try:
                    out_q.put(item, timeout=0.2)
                    return True
                except queue.Full:
                    continue
            return False

        cleanup_mu = threading.Lock()
        cleaned = [False]

        def _close_readers() -> None:
            # Exactly-once, from whichever side owns the readers last:
            # the consumer's finally (normal case) or the producer's exit
            # (the consumer's join timed out on a hung read).
            with cleanup_mu:
                if cleaned[0]:
                    return
                cleaned[0] = True
            for r in readers:
                if r is not None:
                    try:
                        r.src.close()
                    except Exception:  # noqa: BLE001
                        pass

        def producer_run() -> None:
            try:
                for ids, lens in batches:
                    if stop.is_set():
                        return
                    while not stop.is_set():
                        chosen = ensure_readers()
                        try:
                            rows = self._read_chunk_rows(
                                readers, chosen, ids, lens, codec, n,
                                dead, algo, pool=pool, corrupt=corrupt,
                                open_reader=open_reader, benched=benched,
                            )
                            break
                        except se.StorageError:
                            continue  # reader died; re-choose, retry batch
                    else:
                        return  # early close during a failing batch
                    if not _offer(("rows", ids, lens, rows)):
                        return
                _offer(("done", None, None, None))
            except BaseException as e:  # noqa: BLE001 - relay to consumer
                _offer(("err", e, None, None))
            finally:
                if stop.is_set():
                    # The consumer may already have run its finally (join
                    # timeout): the readers are ours to close.
                    _close_readers()

        prod = threading.Thread(target=obs.ctx_wrap(producer_run),
                                daemon=True, name="shard-readahead")
        prod.start()
        try:
            while True:
                with flight.span("readahead_wait", "erasure"):
                    tag, a, b_, c = out_q.get()
                if tag == "done":
                    break
                if tag == "err":
                    raise a
                yield from self._decoded_ranges(
                    codec, c, a, b_, fi.erasure.block_size, offset, length)
        finally:
            # Runs on normal completion AND early close (GeneratorExit) —
            # callers that read exactly length bytes leave the generator
            # paused, so cleanup cannot live after the loop. (The shard
            # pool is instance-owned and outlives the stream.) Stop and
            # join the read-ahead producer BEFORE closing readers — it is
            # the only thread touching them.
            stop.set()
            while True:
                try:
                    out_q.get_nowait()
                except queue.Empty:
                    break
            prod.join(timeout=5.0)
            if not prod.is_alive():
                _close_readers()
            # else: producer is wedged in a slow read — closing the files
            # under it would corrupt its reads/retries; its own finally
            # closes the readers when it exits.
            # Served the read but some shard was dead/corrupt: one-shot heal
            # trigger (reference cmd/erasure-object.go:321-344).
            if dead and self.mrf is not None:
                self.mrf.add_partial(bucket, obj, fi.version_id,
                                     deep=bool(corrupt))

    def _decoded_ranges(self, codec: ErasureCodec, rows, batch_ids,
                        block_lens, block_size: int, offset: int,
                        length: int):
        """One read batch -> the memoryview slices of [offset,
        offset+length) it holds, in order, sliced as the caller pulls.
        The `decode` span closes before the first slice is handed out: it
        must not stay open while the caller sends."""
        with flight.span("decode", "erasure"):
            decoded = route.decode_blocks(codec, rows, block_lens)
        for j, b in enumerate(batch_ids):
            blk_start = b * block_size
            lo = max(offset, blk_start) - blk_start
            hi = min(offset + length, blk_start + block_lens[j]) - blk_start
            if hi > lo:
                yield from _yield_block_range(decoded[j], lo, hi)

    def _native_stream(self, bucket: str, obj: str, fi: FileInfo, part,
                       algo: str, shuffled: list[StorageAPI], rel: str,
                       offset: int, length: int):
        """Native serving lane for GET: pread + sip256 verify + any-k
        reconstruct + block assembly in one GIL-released C++ call per
        window (native/mtpu_native.cc mtpu_decode_part — the reference's
        parallelReader + bitrot verify + ReconstructData,
        cmd/erasure-decode.go:120-205). Remote drives join the same
        window: their framed byte ranges prefetch over RPC (in parallel)
        and feed the decoder as in-memory shards — readers stay
        interface-uniform like the reference's (cmd/erasure-decode.go:
        120-188), so one remote drive no longer demotes the whole GET to
        the Python path. None -> Python/device path."""
        from minio_tpu.native import plane

        if (algo not in ("sip256", "highwayhash256") or length <= 0
                or not plane.available()):
            return None
        paths, remotes = _shard_paths_mixed(shuffled, bucket, rel)
        if paths is None:
            return None
        k, m = fi.erasure.data_blocks, fi.erasure.parity_blocks
        bs = fi.erasure.block_size
        n = k + m

        def gen():
            from concurrent.futures import ThreadPoolExecutor

            corrupt_seen = False
            # Health-OFFLINE drives start dead (zero I/O on them); later
            # windows also never re-read a shard already known bad.
            dead: set[int] = {i for i, d in enumerate(shuffled)
                              if not d.is_online()}
            end = offset + length
            # One open stream per remote shard for the whole GET (stat +
            # open once, sequential ranged reads ride its readahead).
            streams: dict[int, object] = {}
            # Long-lived range streams (reference ReadFileStream shape):
            # the whole GET's framed extent rides ONE streamed request
            # per remote shard; windows read sequentially off it.
            rstreams: dict[int, tuple] = {}     # i -> (stream, next_off)
            lo_all, ln_all = plane.framed_range(k, bs, part.size, offset,
                                                length)

            def fetch_remote(i, lo, ln):
                ent = rstreams.pop(i, None)
                if ent is not None and ent[1] != lo:
                    try:
                        ent[0].close()
                    except Exception:  # noqa: BLE001
                        pass
                    ent = None
                if ent is None:
                    opener = getattr(remotes[i], "read_file_range_stream",
                                     None)
                    if opener is None:
                        # Fault injectors / exotic wrappers interpose on
                        # read_file_stream — keep their per-call hooks.
                        return _fetch_framed(remotes[i], bucket, rel, lo,
                                             ln, streams, i)
                    try:
                        ent = (opener(bucket, rel, lo,
                                      lo_all + ln_all - lo), lo)
                    except (se.StorageError, OSError):
                        return None
                st = ent[0]
                try:
                    buf = _read_exact(st, ln)
                except (se.StorageError, OSError, ValueError):
                    try:
                        st.close()
                    except Exception:  # noqa: BLE001
                        pass
                    return None
                rstreams[i] = (st, lo + ln)
                return buf

            # All-local GETs take one giant decode window (fewest C
            # calls); with remote shards the window shrinks so the
            # one-ahead pipeline genuinely overlaps window N+1's RPC
            # prefetch with window N's decode — a single 64 MiB window
            # would serialize the whole transfer before the first
            # decode byte.
            wb = plane.window_blocks(bs)
            if any(r is not None for r in remotes):
                wb = plane.pipeline_window_blocks(bs)

            def windows():
                pos = offset
                while pos < end:
                    wend = min(end, (pos // bs + wb) * bs)
                    yield pos, wend
                    pos = wend

            def decode_window(pos, wend):
                """One window with remote-shard escalation: start from the
                data-first k selection; remote shards the selection needs
                prefetch their framed range over RPC (in parallel); on
                failures the selection widens until served or < k left."""
                nonlocal corrupt_seen
                mem: dict[int, bytes] = {}
                lo, ln = plane.framed_range(k, bs, part.size, pos,
                                            wend - pos)
                while True:
                    alive = [i for i in range(n) if i not in dead]
                    if len(alive) < k:
                        raise se.InsufficientReadQuorum(
                            bucket, obj, "not enough live shards")
                    need = [i for i in alive[:k]
                            if remotes[i] is not None and i not in mem]
                    if need:
                        # Deadline'd: a hung remote/injected shard becomes
                        # a timeout value -> dead -> re-selection, instead
                        # of wedging the whole GET window.
                        fetches = parallel_map([
                            lambda i=i: fetch_remote(i, lo, ln)
                            for i in need],
                            deadline=self._data_deadline())
                        lost = False
                        for i, blob in zip(need, fetches):
                            if isinstance(blob, (bytes, bytearray)):
                                mem[i] = blob
                            else:
                                dead.add(i)
                                lost = True
                        if lost:
                            continue  # re-select around the dead fetch
                    skip = dead | {i for i in range(n)
                                   if remotes[i] is not None
                                   and i not in mem}
                    data, states = plane.decode_range(
                        paths, k, m, bs, part.size, pos, wend - pos,
                        skip=skip, algorithm=algo, mem=mem)
                    saw_fail = False
                    for i, s in enumerate(states):
                        if s < 0:
                            dead.add(i)
                            saw_fail = True
                        if s == -2:
                            corrupt_seen = True
                    if data is not None:
                        return data
                    if not saw_fail:
                        raise se.InsufficientReadQuorum(
                            bucket, obj, "not enough live shards")

            # One-window read-ahead: window N+1 decodes (GIL-released C
            # call) in a worker while window N streams to the client —
            # the GET half of P2 (the Python lane's read-ahead producer).
            with ThreadPoolExecutor(max_workers=1,
                                    thread_name_prefix="native-decode") as ex:
                try:
                    fut = None
                    decode_ctx = obs.ctx_wrap(decode_window)
                    pending = windows()
                    nxt = next(pending, None)
                    while nxt is not None:
                        pos, wend = nxt
                        if fut is None:
                            fut = ex.submit(decode_ctx, pos, wend)
                        try:
                            # Bounded: a local pread hung inside the C
                            # call (NFS stall) must fail the GET typed
                            # and on time, never wedge it.
                            data = fut.result(
                                timeout=2.0 * self._data_deadline())
                        except _FutTimeout:
                            note_leaked_worker()
                            raise se.OperationTimedOut(
                                bucket, obj, "native decode window "
                                "exceeded the data deadline") from None
                        except OSError as e:
                            raise se.FaultyDisk(
                                f"native decode: {e}") from e
                        nxt = next(pending, None)
                        fut = (ex.submit(decode_ctx, nxt[0], nxt[1])
                               if nxt is not None else None)
                        yield data
                finally:
                    # An abandoned GET (client disconnect mid-stream) can
                    # leave window N+1 decoding in the worker; closing
                    # its streams under it would fail healthy shards and
                    # mark live nodes offline. Settle the future first
                    # (same discipline as the Python lane's
                    # producer-join before closing readers).
                    if fut is not None and not fut.cancel():
                        try:
                            fut.result(timeout=30)
                        except Exception:  # noqa: BLE001 — teardown only
                            pass
                    for f in streams.values():
                        try:
                            f.close()
                        except Exception:  # noqa: BLE001
                            pass
                    streams.clear()
                    for st, _off in rstreams.values():
                        try:
                            st.close()
                        except Exception:  # noqa: BLE001
                            pass
                    rstreams.clear()
                    # One-shot heal trigger on any dead/corrupt shard seen
                    # (reference cmd/erasure-object.go:321-344).
                    if dead and self.mrf is not None:
                        self.mrf.add_partial(bucket, obj, fi.version_id,
                                             deep=corrupt_seen)

        return gen()

    def _hedge_delay(self) -> float | None:
        """Seconds to wait on a shard-read straggler before launching a
        spare reader on an unused parity drive. Derived from the rolling
        shard-read latency EWMA unless pinned via self.hedge_delay; None
        (no history yet) defers to the hard data deadline."""
        if self.hedge_delay is not None:
            return self.hedge_delay
        e = self._shard_lat
        if e is None:
            return None
        return max(4.0 * e, 0.02)

    def _note_shard_latency(self, dur: float) -> None:
        e = self._shard_lat
        self._shard_lat = dur if e is None else 0.8 * e + 0.2 * dur

    def _abandon_shard(self, i: int, fut, readers, dead,
                       benched=None, failed=True) -> None:
        """A straggler lost the hedge (failed=False: sidelined in
        `benched`, reclaimable, never heal-triggering) or hit the data
        deadline (failed=True: marked dead like any failed drive):
        reclaim its reader when the read eventually returns; the pool
        worker it occupies is accounted and replaced until then.
        read_shard re-checks the exclusion sets after opening, so a late
        open can never resurrect the slot."""
        if failed or benched is None:
            dead.add(i)
        else:
            benched.add(i)
        rdr = readers[i]
        readers[i] = None

        def _cleanup(_f, rdr=rdr):
            if rdr is not None:
                try:
                    rdr.src.close()
                except Exception:  # noqa: BLE001 - teardown only
                    pass

        if fut.cancel():
            _cleanup(None)
            return
        note_leaked_worker(self._read_pool, fut)
        fut.add_done_callback(_cleanup)

    def _read_chunk_rows(self, readers, chosen, batch_ids, block_lens, codec,
                         n, dead, algo=None, pool=None, corrupt=None,
                         open_reader=None, benched=None):
        """Read one batch of chunk rows from the chosen shards and verify
        them; marks dead drives and raises StorageError to trigger
        re-selection."""
        batched_verify = algo == "mxsum256"
        cap = codec.shard_size()
        # A fresh staging array each attempt at a batch (see _read_shards).
        stage = (_VerifyStage(len(chosen), len(batch_ids), cap)
                 if batched_verify and _consecutive(batch_ids)
                 and route.verifies_in_place(cap) else None)
        with flight.span("shard_read", "erasure"):
            results = self._read_shards(
                readers, chosen, batch_ids, block_lens, codec, n, dead,
                batched_verify, pool, corrupt, open_reader, benched, stage)
        rows: list[list[bytes | None]] = []
        records: list[tuple[int, bytes, bytes]] = []  # (drive, want, chunk)
        for j, _b in enumerate(batch_ids):
            row: list[bytes | None] = [None] * n
            for i in sorted(results):
                want, chunk = results[i][j]
                row[i] = chunk
                if batched_verify:
                    records.append((i, want, chunk))
            rows.append(row)
        if records:
            # Staging, lane submit or launch, and the D2H of the digests.
            with flight.span("verify_wait", "dataplane"):
                if stage is not None and all(i in stage.slot_of
                                             for i in results):
                    self._verify_staged(stage, results, readers, dead,
                                        corrupt)
                else:
                    self._verify_records(records, codec, readers, dead,
                                         corrupt)
        return rows

    def _read_shards(self, readers, chosen, batch_ids, block_lens, codec,
                     n, dead, batched_verify, pool, corrupt, open_reader,
                     benched, stage=None) -> dict[int, list]:
        """shard index -> its [(stored digest | None, chunk)] per block of
        the batch, for the first k shards that answer.

        Shards read in PARALLEL (one worker per shard, each reading its
        batch's contiguous records with one read — per-drive sequential
        I/O, cross-drive concurrency, the reference's parallelReader
        goroutine layout, cmd/erasure-decode.go:120-188); host hashing
        and preads release the GIL in native code. mxsum256 shard files
        verify in ONE device launch per batch (fused.verify_digests)
        instead of per-chunk host hashing — the TPU-native form of the
        reference's verify-every-ReadAt (cmd/bitrot-streaming.go:115-158).

        With a `stage` (_VerifyStage: mxsum256, consecutive ids, a direct
        verify launch) each submitted shard takes the next SLOT, rows
        slot·B … slot·B + B − 1 of the launch's [bucket_rows(k·B), cap]
        array (B blocks a batch), in submission order, and its reader
        lands its records there (BitrotReader.read_records_into: one
        preadv of a local file); the chunks it hands on are views of those
        rows. A spare whose slot would fall past the array's rows reads
        into a buffer of its own (read_records) and the batch verifies by
        copy. The array is never pooled: an abandoned straggler may still
        write into its slot after the verify, and the chunks handed on
        keep it alive until the last slice is sent, so a fresh np.empty a
        batch is what keeps a late write out of another request's bytes
        (and no memset is needed: rows whose length is 0 are not read).

        First-k-wins with hedging: after the hedge delay (rolling-latency
        derived) spare readers launch on unused parity shards, and the
        batch completes with the FIRST k shard results — a slow or hung
        drive degrades GET latency by one hedge delay, not one deadline.
        Stragglers still pending when k arrive (or at the hard data
        deadline) are abandoned, never awaited."""
        shard_size = codec.shard_size()
        chunk_lens = [-(-bl // codec.k) for bl in block_lens]
        # _stream_one_part cuts batches as range(bi, ...); a caller that
        # ever passes other ids gets a read a block.
        consecutive = _consecutive(batch_ids)
        # Slots go in submission order, on this thread.
        slot_for = stage.take if stage is not None else (lambda _i: None)

        def read_shard(i: int, slot: int | None = None
                       ) -> list[tuple[bytes | None, bytes | memoryview]]:
            r = readers[i]
            if r is None:
                if open_reader is None:
                    raise se.FaultyDisk(f"shard {i}: no reader")
                r = open_reader(i)
                if i in dead or (benched is not None and i in benched):
                    # Abandoned while the open was in flight: don't
                    # publish a zombie reader.
                    try:
                        r.src.close()
                    except Exception:  # noqa: BLE001
                        pass
                    raise se.FaultyDisk(f"shard {i}: abandoned")
                readers[i] = r
            reads = len(batch_ids)
            if not batched_verify:
                out = [(None, r.read_at(b * shard_size, chunk_lens[j]))
                       for j, b in enumerate(batch_ids)]
            elif slot is not None:
                # One read a shard a batch, straight into its slot's rows.
                want, chunk_rows = stage.rows(slot)
                out = [(memoryview(w), c) for w, c in zip(
                    want, r.read_records_into(batch_ids[0], len(batch_ids),
                                              want, chunk_rows))]
                reads = 1
            elif consecutive:
                # One read a shard a batch: the records are contiguous,
                # and the chunks come back as views of that one buffer.
                out = r.read_records(batch_ids[0], len(batch_ids))
                reads = 1
            else:
                out = [r.read_record(b) for b in batch_ids]
            _SHARD_READS.inc(reads)
            _SHARD_RECORDS.inc(len(out))
            for j, (_want, chunk) in enumerate(out):
                if len(chunk) != chunk_lens[j]:
                    raise se.FileCorrupt(
                        f"chunk {batch_ids[j]} length {len(chunk)} != "
                        f"{chunk_lens[j]}")
            return out

        from concurrent.futures import FIRST_COMPLETED, CancelledError
        from concurrent.futures import wait as _fwait

        _SHARD_ERRS = (se.StorageError, OSError, CancelledError, RuntimeError)
        results: dict[int, list] = {}
        first_err: tuple[int, Exception] | None = None
        need = len(chosen)

        def record_failure(i: int, e: Exception) -> None:
            nonlocal first_err
            dead.add(i)
            # FileCorrupt = observed bitrot/truncation -> the queued
            # heal must deep-verify; a plain open/read failure only
            # needs the presence scan.
            if isinstance(e, se.FileCorrupt) and corrupt is not None:
                corrupt.add(i)
            readers[i] = None
            if first_err is None:
                first_err = (i, e)

        if pool is not None:
            futures: dict = {}
            rev: dict = {}
            started: dict[int, float] = {}
            pool_down = False

            def submit(i: int) -> bool:
                try:
                    # ctx_wrap: shard reads run in pool workers but their
                    # storage/RPC trace records belong to this request.
                    f = pool.submit(obs.ctx_wrap(read_shard), i, slot_for(i))
                except RuntimeError:
                    return False
                futures[i] = f
                rev[f] = i
                started[i] = time.monotonic()
                return True

            for i in chosen:
                if not submit(i):
                    pool_down = True
                    break
            if pool_down:
                # Pool shut down mid-submit (layer closing). Do NOT fall
                # back to inline reads: already-running futures share the
                # BitrotReaders' seek state, so a concurrent inline pass
                # could serve wrong chunks. Wait the started ones out,
                # mark every chosen shard dead, and degrade to a clean
                # quorum error.
                for f in futures.values():
                    f.cancel()
                for f in futures.values():
                    try:
                        f.result()
                    # CancelledError is a BaseException on stock
                    # CPython >= 3.8 — name it or the drain loop leaks it.
                    except (Exception, CancelledError):  # noqa: BLE001
                        pass
                for i in chosen:
                    dead.add(i)
                    readers[i] = None
                raise se.FileCorrupt("layer closing") from None

            t0 = time.monotonic()
            end = t0 + self._data_deadline()
            hd = self._hedge_delay()
            hedge_at = (t0 + hd) if hd is not None else None
            hedged: set[int] = set()
            pending = set(futures)
            while pending and len(results) < need:
                now = time.monotonic()
                if now >= end:
                    break
                timeout = end - now
                if hedge_at is not None:
                    timeout = min(timeout, max(0.0, hedge_at - now))
                done, _ = _fwait({futures[i] for i in pending},
                                 timeout=timeout,
                                 return_when=FIRST_COMPLETED)
                for f in done:
                    i = rev[f]
                    pending.discard(i)
                    try:
                        results[i] = f.result()
                        self._note_shard_latency(
                            time.monotonic() - started[i])
                        if (i in hedged and len(results) <= need
                                and any(j not in hedged for j in pending)):
                            _HEDGED_WINS.inc()
                    except _SHARD_ERRS as e:
                        record_failure(i, e)
                if (len(results) < need and pending and hedge_at is not None
                        and time.monotonic() >= hedge_at):
                    # One spare per straggler, parity-order, never
                    # reusing a shard already dead or in play.
                    hedge_at = None
                    spares = [s for s in range(n)
                              if s not in dead and s not in futures
                              and (benched is None or s not in benched)]
                    for s in spares[:len(pending)]:
                        if submit(s):
                            pending.add(s)
                            hedged.add(s)
                            _HEDGED_READS.inc()
            # Settle leftovers: harvest already-done stragglers for free,
            # abandon the rest (hedge losers / deadline breakers).
            deadline_hit = len(results) < need
            for i in list(pending):
                f = futures[i]
                if f.done():
                    try:
                        results[i] = f.result()
                        continue
                    except _SHARD_ERRS as e:
                        record_failure(i, e)
                        continue
                self._abandon_shard(i, f, readers, dead, benched,
                                    failed=deadline_hit)
                if deadline_hit and first_err is None:
                    first_err = (i, se.OperationTimedOut(
                        msg="shard read exceeded the data deadline"))
            if len(results) < need:
                i, e = first_err if first_err is not None else (
                    -1, se.FaultyDisk("no shard results"))
                raise se.FileCorrupt(f"shard {i}: {e}") from e
        else:
            for i in chosen:
                try:
                    results[i] = read_shard(i, slot_for(i))
                except _SHARD_ERRS as e:
                    record_failure(i, e)
            if first_err is not None:
                i, e = first_err
                raise se.FileCorrupt(f"shard {i}: {e}") from e
        return results

    def _verify_records(self, records, codec, readers, dead,
                        corrupt=None) -> None:
        """One batched mxsum256 launch over every chunk just read, each
        copied into the launch's rows; a digest mismatch marks the drive
        dead and retriggers shard selection."""
        got = route.digest_chunks([c for _i, _w, c in records],
                                  codec.shard_size())
        _VERIFY_COPIED.inc(len(records))
        for ri, (i, want, _chunk) in enumerate(records):
            if got[ri] != want:
                self._verify_failed(i, readers, dead, corrupt)

    def _verify_staged(self, stage, results, readers, dead,
                       corrupt=None) -> None:
        """_verify_records for a batch whose harvested shards all read
        into their slots of `stage`: the launch takes the array as it
        lies, and the stored digests compare in one array operation. Only
        the harvested rows get a length; the rest are not read."""
        b = stage.blocks
        lens = [len(c) for _w, c in next(iter(results.values()))]
        for i in results:
            s = stage.slot_of[i] * b
            stage.lens[s:s + b] = lens
        got = route.digest_staged(stage.stage, stage.lens)
        _VERIFY_READ.inc(b * len(results))
        bad = np.flatnonzero((got != stage.want).any(axis=1)
                             & (stage.lens > 0))
        if bad.size:
            # The first mismatch in block-then-shard order, as
            # _verify_records marks it.
            shard = {s: i for i, s in stage.slot_of.items()}
            _j, i = min((r % b, shard[r // b]) for r in bad.tolist())
            self._verify_failed(i, readers, dead, corrupt)

    @staticmethod
    def _verify_failed(i: int, readers, dead, corrupt) -> None:
        dead.add(i)
        if corrupt is not None:
            corrupt.add(i)
        readers[i] = None
        raise se.FileCorrupt(f"shard {i}: bitrot digest mismatch")

    # ------------------------------------------------------------------
    # delete (cmd/erasure-object.go:894-1031)
    # ------------------------------------------------------------------

    def delete_object(self, bucket: str, obj: str,
                      opts: ObjectOptions | None = None) -> ObjectInfo:
        opts = opts or ObjectOptions()
        self.get_bucket_info(bucket)
        write_quorum = self._write_quorum_meta()

        if opts.versioned and not opts.version_id:
            # Versioned delete without a version: write a delete marker.
            marker = FileInfo(
                volume=bucket, name=obj, version_id=str(uuid.uuid4()),
                deleted=True, mod_time=time.time(),
            )
            with self.nslock.lock(bucket, obj):
                results = parallel_map(
                    [lambda d=d: d.delete_version(bucket, obj, marker) for d in self.drives],
                    deadline=self._meta_deadline(),
                )
                self._meta_invalidate(bucket, obj)
                reduce_write_quorum(results, write_quorum, bucket, obj)
            return ObjectInfo(bucket=bucket, name=obj, version_id=marker.version_id,
                              delete_marker=True, mod_time=marker.mod_time)

        with self.nslock.lock(bucket, obj):
            fi = self._read_quorum_fileinfo(bucket, obj, opts.version_id)
            target = FileInfo(volume=bucket, name=obj, version_id=opts.version_id,
                              data_dir=fi.data_dir)
            results = parallel_map(
                [lambda d=d: d.delete_version(bucket, obj, target) for d in self.drives],
                deadline=self._meta_deadline(),
            )
            self._meta_invalidate(bucket, obj)
            # A drive that never had the version is as good as deleted on it.
            results = [
                None if isinstance(r, (se.FileNotFound, se.FileVersionNotFound)) else r
                for r in results
            ]
            reduce_write_quorum(results, write_quorum, bucket, obj)
        return ObjectInfo(bucket=bucket, name=obj, version_id=opts.version_id,
                          delete_marker=fi.deleted)

    def delete_objects(self, bucket: str, objects: list[ObjectToDelete],
                       opts: ObjectOptions | None = None
                       ) -> list[DeletedObject | Exception]:
        return listing.bulk_delete(self.delete_object, bucket, objects, opts)

    # ------------------------------------------------------------------
    # listing (flat merge; the metacache system layers on top later)
    # ------------------------------------------------------------------

    def list_objects(self, bucket: str, prefix: str = "", marker: str = "",
                     delimiter: str = "", max_keys: int = 1000) -> ListObjectsInfo:
        self.get_bucket_info(bucket)
        # Marker pushdown (subtree pruning, group-aware delimiter walks):
        # listing.pushdown_stream is the single policy shared by every
        # layer; paginate re-filters either way.
        return listing.paginate_objects(
            listing.pushdown_stream(
                lambda sa: self.stream_journals(bucket, prefix, sa),
                prefix, marker, delimiter),
            lambda name, fi: self._fi_to_object_info(bucket, name, fi),
            prefix, marker, delimiter, max_keys,
        )

    def list_object_versions(self, bucket: str, prefix: str = "", marker: str = "",
                             version_marker: str = "", delimiter: str = "",
                             max_keys: int = 1000) -> ListObjectVersionsInfo:
        self.get_bucket_info(bucket)
        return listing.paginate_versions(
            listing.pushdown_stream(
                lambda sa: self.stream_journals(bucket, prefix, sa),
                prefix, marker, delimiter, version_marker),
            lambda name, fi: self._fi_to_object_info(bucket, name, fi),
            prefix, marker, version_marker, delimiter, max_keys,
        )

    def stream_journals(self, bucket: str, prefix: str = "",
                        start_after: str = "") -> Iterator[tuple[str, XLMeta]]:
        """SORTED (name, elected-journal) stream: per-drive sorted walk_dir
        streams k-way merged with newest-journal election — O(drives)
        memory regardless of namespace size (the reference's metacache
        listPath walk, cmd/metacache-set.go:534 + metacache-entries.go:198;
        replaces the materialized merged_journals map on every hot path).
        Names at or before start_after are skipped WITHOUT parsing their
        journals (cheap resume for heal walks and list markers); each
        drive's walk runs behind a prefetch thread so per-drive I/O
        overlaps (the reference's per-drive WalkDir goroutines)."""
        def drive_stream(d: StorageAPI):
            try:
                # start_after pushes down into the walk (subtree pruning:
                # O(page) resume); the belt-and-braces re-check covers
                # implementations that only best-effort the marker.
                for e in d.walk_dir(bucket, prefix, start_after):
                    if start_after and e.name <= start_after:
                        continue
                    try:
                        meta = XLMeta.parse(e.meta)
                    except se.StorageError:
                        continue  # corrupt copy: other drives elect
                    yield e.name, meta
            except se.StorageError:
                return  # offline/unformatted drive: quorum covers it

        # Per-drive walk deadline: a drive that stalls mid-walk is dropped
        # from the merge (exactly like an offline drive) instead of
        # wedging the whole listing/heal sweep.
        walk_deadline = _health.fleet_deadlines(self.drives)[2]
        return listing.merge_journal_streams(
            [listing.prefetch_stream(drive_stream(d), deadline=walk_deadline)
             for d in self.drives])

    def merged_journals(self, bucket: str, prefix: str) -> dict[str, XLMeta]:
        """Materialized journal map — O(namespace) memory; only for small
        bounded uses (tests, sys buckets). Hot paths use stream_journals."""
        return dict(self.stream_journals(bucket, prefix))

    # ------------------------------------------------------------------
    # tagging (cmd/erasure-object.go:1158)
    # ------------------------------------------------------------------

    def put_object_tags(self, bucket: str, obj: str, tags: str,
                        opts: ObjectOptions | None = None) -> ObjectInfo:
        return self.put_object_metadata(
            bucket, obj, {"x-amz-tagging": tags or None}, opts)

    def put_object_metadata(self, bucket: str, obj: str,
                            updates: dict[str, str | None],
                            opts: ObjectOptions | None = None) -> ObjectInfo:
        """Quorum metadata-only update of one version (reference
        PutObjectMetadata/PutObjectTags, cmd/erasure-object.go:1031,1158).
        A None value deletes the key."""
        opts = opts or ObjectOptions()
        fi = self._read_quorum_fileinfo(bucket, obj, opts.version_id)
        if fi.deleted:
            raise se.ObjectNotFound(bucket, obj)
        for k, v in updates.items():
            if v is None:
                fi.metadata.pop(k, None)
            else:
                fi.metadata[k] = v
        results = parallel_map(
            [
                lambda d=d, f=_clone_for_drive(fi, i + 1): d.write_metadata(bucket, obj, f)
                for i, d in enumerate(
                    shuffle_by_distribution(self.drives, fi.erasure.distribution)
                    if fi.erasure.distribution else self.drives
                )
            ],
            deadline=self._meta_deadline(),
        )
        self._meta_invalidate(bucket, obj)
        reduce_write_quorum(results, self._write_quorum_meta(), bucket, obj)
        return self._fi_to_object_info(bucket, obj, fi)

    def transition_version(self, bucket: str, obj: str, version_id: str,
                           tier_name: str, tier_key: str,
                           storage_class: str = "",
                           expect_mod_time: float | None = None) -> None:
        """Mark a version transitioned: metadata keeps size/etag/parts (the
        part layout drives multipart-SSE decryption on read-through) but
        data_dir empties and the shard data is reclaimed (write_metadata
        deletes the orphaned data dir on each drive) — reference transition
        state in xl.meta v2 + free of the data parts.

        expect_mod_time: abort if the version changed since the caller
        copied its data to the tier (the scanner's TOCTOU guard)."""
        with self.nslock.lock(bucket, obj):
            fi = self._read_quorum_fileinfo(bucket, obj, version_id)
            if fi.deleted:
                raise se.ObjectNotFound(bucket, obj)
            if fi.inline_data:
                raise se.ObjectError(
                    bucket, obj, "inline objects are too small to tier")
            if (expect_mod_time is not None
                    and abs(fi.mod_time - expect_mod_time) > 1e-6):
                raise se.ObjectError(
                    bucket, obj,
                    "object changed while its data was being tiered")
            fi.metadata["x-mtpu-internal-transition-tier"] = tier_name
            fi.metadata["x-mtpu-internal-transition-key"] = tier_key
            if storage_class:
                fi.metadata["x-amz-storage-class"] = storage_class
            fi.data_dir = ""
            results = parallel_map(
                [lambda d=d, f=_clone_for_drive(fi, i + 1):
                 d.write_metadata(bucket, obj, f)
                 for i, d in enumerate(
                     shuffle_by_distribution(self.drives, fi.erasure.distribution)
                     if fi.erasure.distribution else self.drives)],
                deadline=self._meta_deadline(),
            )
            self._meta_invalidate(bucket, obj)
            reduce_write_quorum(results, self._write_quorum_meta(), bucket, obj)

    def restore_transitioned(self, bucket: str, obj: str,
                             version_id: str = "") -> None:
        """Re-materialize a transitioned version's data from its tier
        (RestoreObject role): shards are rebuilt locally and the transition
        markers are dropped; the tier copy is removed. The conditional PUT
        (expect_mod_time, checked under the commit lock) guarantees a
        concurrent client write is never clobbered by stale tier data."""
        from minio_tpu.scanner import tiers as tiermod
        from minio_tpu.utils.streams import IterReader

        fi = self._read_quorum_fileinfo(bucket, obj, version_id)
        tier_name = fi.metadata.get("x-mtpu-internal-transition-tier", "")
        if not tier_name or fi.data_dir:
            return  # nothing to restore
        if len(fi.parts) > 1 and any(
                k.endswith("-sse") for k in fi.metadata):
            # Multipart SSE relies on the original per-part boundaries,
            # which a restore-as-single-part would destroy; reads already
            # stream through the tier, so refuse rather than corrupt.
            raise se.ObjectError(
                bucket, obj, "restore of multipart SSE objects is not "
                "supported; reads stream through the tier")
        reg = tiermod.global_registry()
        if reg is None:
            raise se.ObjectError(bucket, obj, "no tier registry configured")
        tier = reg.get(tier_name)
        key = fi.metadata.get("x-mtpu-internal-transition-key", "")

        meta = {k: v for k, v in fi.metadata.items()
                if not k.startswith("x-mtpu-internal-transition-")}
        opts = ObjectOptions(version_id=fi.version_id,
                             versioned=bool(fi.version_id),
                             user_defined=meta,
                             expect_mod_time=fi.mod_time)
        self.put_object(bucket, obj, IterReader(tier.get(key)), fi.size, opts)
        tier.remove(key)

    def get_object_tags(self, bucket: str, obj: str,
                        opts: ObjectOptions | None = None) -> str:
        info = self.get_object_info(bucket, obj, opts)
        return info.user_defined.get("x-amz-tagging", "")

    def delete_object_tags(self, bucket: str, obj: str,
                           opts: ObjectOptions | None = None) -> ObjectInfo:
        return self.put_object_tags(bucket, obj, "", opts)

    # ------------------------------------------------------------------
    # internals
    # ------------------------------------------------------------------

    def _native_fan_out(
        self,
        shuffled: list[StorageAPI],
        vol: str,
        rel: str,
        data: BinaryIO,
        size: int,
        codec: ErasureCodec,
        write_quorum: int,
        bucket: str,
        obj: str,
        initial: bytes = b"",
    ) -> tuple[int, str, list[Exception | None]] | None:
        """Native serving lane for the PUT fan-out: the whole block→shard→
        bitrot-frame→per-drive-file pipeline runs in ONE GIL-released C++
        call per segment (native/mtpu_native.cc mtpu_encode_part — the
        reference's native Erasure.Encode + parallelWriter + hash.Reader
        path, cmd/erasure-encode.go:36-109, pkg/hash/reader.go:37).

        Engaged when the set hashes with host-native sip256 and every drive
        is local; returns None to fall through to the device-codec fan-out
        otherwise. The per-call disk-ID guard is deferred to the commit
        (rename_data IS guarded), matching the quorum outcome either way."""
        from minio_tpu.native import plane

        if (self.bitrot_algorithm not in ("sip256", "highwayhash256")
                or not plane.available()):
            return None
        if codec.block_size % 64:
            return None  # md5 segment chaining needs 64-byte alignment
        paths = _local_shard_paths(shuffled, vol, rel)
        if paths is None:
            return None
        import os as _os
        from concurrent.futures import ThreadPoolExecutor

        enc = plane.PartEncoder(paths, codec.k, codec.m, codec.block_size,
                                algorithm=self.bitrot_algorithm)
        for i, p in enumerate(paths):
            try:
                _os.makedirs(_os.path.dirname(p), exist_ok=True)
            except OSError:
                # One bad drive (read-only/full fs) degrades to quorum
                # accounting, exactly like a failed writer thread in the
                # Python lane — never aborts the whole PUT.
                enc.fail_drive(i)
        seg = plane.seg_blocks(codec.block_size) * codec.block_size
        total = 0
        buf = initial
        # One-segment pipeline: the GIL-released C call for segment N runs
        # in a worker thread while this thread reads segment N+1 from the
        # client — the native lane's form of the P2 read/encode overlap
        # (the Python lane's dispatch-ahead, cmd/erasure-encode.go:80-107).
        with ThreadPoolExecutor(max_workers=1,
                                thread_name_prefix="native-encode") as ex:
            fut = None
            while True:
                want = seg - len(buf)
                if size >= 0:
                    want = min(want, size - total - len(buf))
                got = _read_full(data, want) if want > 0 else b""
                # Only the first segment carries a caller-consumed prefix;
                # every later segment hands the read buffer to the C call
                # as-is (ctypes borrows bytes zero-copy) — the
                # unconditional append here was a whole-segment memcpy per
                # segment.
                chunk = buf + got if buf else got
                final = (len(got) < want
                         or (size >= 0 and total + len(chunk) >= size)
                         or (size < 0 and len(chunk) < seg))
                try:
                    if fut is not None:
                        fut.result()  # segment N-1 fully written
                    fut = ex.submit(obs.ctx_wrap(enc.feed), chunk, final)
                    if final:
                        fut.result()
                except OSError as e:
                    raise se.FaultyDisk(f"native encode: {e}") from e
                total += len(chunk)
                alive = sum(1 for lost in enc.errors if not lost)
                if alive < write_quorum:
                    raise se.InsufficientWriteQuorum(
                        bucket, obj, "write fan-out lost quorum")
                if final:
                    break
                buf = b""
        errs: list[Exception | None] = [
            se.FaultyDisk(f"native shard write failed: {paths[i]}")
            if lost else None
            for i, lost in enumerate(enc.errors)
        ]
        return total, enc.md5_hex, errs

    def _fan_out_encode(
        self,
        shuffled: list[StorageAPI],
        vol: str,
        rel: str,
        data: BinaryIO,
        size: int,
        codec: ErasureCodec,
        write_quorum: int,
        bucket: str,
        obj: str,
        initial: bytes = b"",
    ) -> tuple[int, str, list[Exception | None]]:
        """Stream `data` through the batched codec, fanning bitrot-framed
        shards to one create_file per drive (the io.Pipe + goroutine fan-out
        of cmd/erasure-encode.go:36-70, collapsed into queues). Returns
        (bytes consumed, md5 hex, per-drive errors). `initial` is a prefix
        the caller already consumed from `data`.

        The all-local sip256 configuration takes the native C++ lane
        instead (_native_fan_out); this Python/device path serves
        accelerator-fused digests and remote-drive topologies."""
        native = self._native_fan_out(shuffled, vol, rel, data, size, codec,
                                      write_quorum, bucket, obj, initial)
        if native is not None:
            return native
        qs: list[queue.Queue] = [queue.Queue(maxsize=8) for _ in range(self.n)]
        errs: list[Exception | None] = [None] * self.n
        # A writer thread wedged inside a hung create_file stops draining
        # its queue; the producer notices the queue staying full past the
        # data deadline, marks the drive timed out, and stops feeding it —
        # the PUT then completes at quorum (the hung thread is a daemon,
        # accounted as leaked).
        gave_up = [False] * self.n
        put_timeout = self._data_deadline()

        def feed(i: int, item) -> None:
            if gave_up[i]:
                return
            try:
                qs[i].put(item, timeout=put_timeout)
            except queue.Full:
                gave_up[i] = True
                if errs[i] is None:
                    errs[i] = se.OperationTimedOut(
                        msg=f"drive shard write stalled > {put_timeout:.1f}s")
                note_leaked_worker()

        def writer(i: int, drive: StorageAPI):
            def gen():
                while True:
                    item = qs[i].get()
                    if item is _WRITE_SENTINEL:
                        return
                    digest, chunk = item  # [digest][chunk] record, unconcatenated
                    if digest is None:
                        # Host-hash algorithms digest HERE, in the per-drive
                        # thread (native call releases the GIL), not in the
                        # single producer thread — n drives hash in
                        # parallel, the reference's per-goroutine
                        # bitrot-writer layout (cmd/bitrot-streaming.go:46).
                        # Memoryview chunks pass straight through: every
                        # digest impl takes bytes-like buffers (the native
                        # kernels borrow writable views via from_buffer).
                        digest = bitrot_algo.digest(chunk)
                    yield digest
                    yield chunk

            try:
                drive.create_file(vol, rel, gen())
            except Exception as e:  # noqa: BLE001
                errs[i] = e
                # Drain so the producer never blocks on a dead drive.
                while qs[i].get() is not _WRITE_SENTINEL:
                    pass

        # One writer per drive, each handed to a parked thread: a queue
        # append and a wake-up, where starting a thread gives the GIL away
        # and waits for it. ctx_wrap per job: a reused thread carries this
        # request's trace context, never its last one's.
        writers = shard_writers()
        with flight.span("enc_spawn", "erasure"):
            jobs = [writers.submit(obs.ctx_wrap(writer), i, d)
                    for i, d in enumerate(shuffled)]

        # Device-fused digests share the encode launch (ops/fused.py); any
        # other algorithm is hashed host-side per chunk.
        use_fused = self.bitrot_algorithm == "mxsum256"
        bitrot_algo = bitrot.get_algorithm(self.bitrot_algorithm)
        md5 = hashlib.md5()
        total = 0
        # Dispatch-ahead pipeline (P2, SURVEY §2.4): up to PIPELINE batches
        # are in flight on device while the host reads the next batch and
        # fans out completed ones — the reference's read/encode/write
        # overlap (cmd/erasure-encode.go:80-107) via JAX async dispatch.
        pipeline_depth = 3
        pending: list = []

        def drain_one() -> None:
            chunk_rows, dig_rows = pending.pop(0).wait()
            # Blocks when the drives are behind (bounded writer queues).
            with flight.span("enc_feed", "erasure"):
                for bi, chunks in enumerate(chunk_rows):
                    digs = dig_rows[bi] if dig_rows is not None else None
                    for i in range(self.n):
                        # digest None -> the writer thread hashes the
                        # chunk.
                        feed(i, (digs[i] if digs is not None else None,
                                 chunks[i]))
            alive = sum(1 for e in errs if e is None)
            if alive < write_quorum:
                raise se.InsufficientWriteQuorum(bucket, obj, "write fan-out lost quorum")

        try:
            bs = codec.block_size  # geometry travels with the codec, not self
            batch: list[bytes] = []
            block = initial or _read_full(
                data, min(bs, size) if size >= 0 else bs
            )
            while block:
                with flight.span("enc_read", "erasure"):
                    md5.update(block)
                total += len(block)
                batch.append(block)
                if len(batch) >= self.batch_blocks:
                    pending.append(route.begin_encode(
                        codec, batch, with_digests=use_fused))
                    batch = []
                    if len(pending) >= pipeline_depth:
                        drain_one()
                remaining = bs if size < 0 else min(bs, size - total)
                with flight.span("enc_read", "erasure"):
                    block = _read_full(data, remaining)
            if batch:
                pending.append(route.begin_encode(
                    codec, batch, with_digests=use_fused))
            while pending:
                drain_one()
        finally:
            with flight.span("enc_join", "erasure"):
                for i, q in enumerate(qs):
                    try:
                        q.put(_WRITE_SENTINEL,
                              timeout=0.1 if gave_up[i] else put_timeout)
                    except queue.Full:
                        gave_up[i] = True
                # Bounded wait: a healthy writer drains to its sentinel
                # well inside the deadline; a wedged one is declared
                # timed out and left behind rather than blocking the PUT
                # forever (its thread parks again only if its call ever
                # returns).
                join_end = time.monotonic() + put_timeout
                for i, job in enumerate(jobs):
                    try:
                        exc = job.exception(
                            timeout=0.1 if gave_up[i]
                            else max(0.1, join_end - time.monotonic()))
                    except _FutTimeout:
                        gave_up[i] = True
                        if errs[i] is None:
                            errs[i] = se.OperationTimedOut(
                                msg="drive shard writer did not finish")
                            note_leaked_worker()
                    else:
                        # writer() records an Exception itself; what
                        # reaches the future ended the job some other way.
                        if exc is not None and errs[i] is None:
                            errs[i] = se.FaultyDisk(
                                f"drive shard writer died: {exc!r}")
        return total, md5.hexdigest(), errs

    def _inline_commit_fast(self, shuffled, bucket: str, obj: str,
                            fi: FileInfo, raw: bytes, journal):
        """Two-phase inline-PUT commit through the group-commit plane:
        submit the single-journal record to every drive's WAL
        (journal_commit_async — the call rides the full wrapper chain,
        so disk-ID checks, fault injection, and health deadlines all
        interpose), then await every shared-fsync future under the meta
        deadline. Outcomes mirror the sync fan-out: reclaim token or
        per-drive exception values for the quorum reducer.

        The submit side is PURE MEMORY on an unwrapped armed drive (the
        commit prework runs in the committer thread), so submits run
        inline with no pool hop. With the chaos drive wrap armed, an
        injected fault may block the call itself — there the submit
        loop runs under run_bounded, and a wedged loop falls back to
        the deadline'd parallel_map (a re-store after partial
        submission is idempotent: same key, same bytes).

        Returns None to fall back when any drive lacks the two-phase
        entry (remote / unarmed)."""
        fns = []
        for d in shuffled:
            fn = getattr(d, "journal_commit_async", None)
            if fn is None:
                return None
            fns.append(fn)
        futs: list = []

        def submit_all():
            for fn in fns:
                try:
                    f = fn(bucket, obj, fi, raw, meta=journal,
                           defer_reclaim=True)
                except Exception as e:  # noqa: BLE001 - per-drive data
                    futs.append(e)
                    continue
                if f is None:
                    futs.append(None)  # drive not armed: abort fast path
                    return
                futs.append(f)

        from minio_tpu.erasure.sysstore import submits_may_block

        if submits_may_block():
            if not run_bounded(submit_all, self._meta_deadline()):
                return None  # injected hang mid-submit: bounded fallback
        else:
            submit_all()
        if any(f is None for f in futs):
            return None
        deadline = time.monotonic() + self._meta_deadline()
        outcomes: list = []
        for f in futs:
            if isinstance(f, Exception):
                outcomes.append(f)
                continue
            try:
                outcomes.append(
                    f.result(timeout=max(0.0, deadline - time.monotonic())))
            except se.StorageError as e:
                outcomes.append(e)
            except _FutTimeout:
                outcomes.append(se.OperationTimedOut(
                    bucket, obj, "wal group commit exceeded deadline"))
            except Exception as e:  # noqa: BLE001 - per-drive data
                outcomes.append(e)
        return outcomes

    def _check_put_precondition(self, bucket: str, obj: str,
                                opts: ObjectOptions) -> None:
        """Conditional-PUT guard, called INSIDE the commit lock: abort the
        write if the latest (or named) version's mod_time moved since the
        caller observed it (tier restore's lost-update protection)."""
        if opts.expect_mod_time is None:
            return
        try:
            cur = self._read_quorum_fileinfo(bucket, obj, opts.version_id)
        except (se.ObjectNotFound, se.VersionNotFound):
            raise se.ObjectError(
                bucket, obj, "precondition failed: object vanished") from None
        if abs(cur.mod_time - opts.expect_mod_time) > 1e-6:
            raise se.ObjectError(
                bucket, obj, "precondition failed: object changed")

    def _read_quorum_fileinfo(self, bucket: str, obj: str,
                              version_id: str) -> FileInfo:
        sc = self._setcache
        pre_sigs = None
        if sc is not None:
            fi = sc.lookup(bucket, obj, version_id)
            if fi is not None:
                # Signature-validated post-election hit: the N-drive
                # fan-out + election is skipped entirely.
                return fi
            # Signatures BEFORE the election: a mutation racing the
            # fan-out read leaves these stale, so the entry self-
            # invalidates at the next lookup instead of serving the
            # pre-mutation election under post-mutation signatures.
            pre_sigs = sc.snapshot_sigs(bucket, obj, self.drives)
        with flight.span("quorum-read", "metaplane", timeline=False,
                         bucket=bucket, object=obj):
            fi = self._read_quorum_fileinfo_inner(bucket, obj, version_id)
        if sc is not None:
            sc.populate(bucket, obj, version_id, fi, self.drives,
                        sigs=pre_sigs)
        return fi

    def _read_quorum_fileinfo_inner(self, bucket: str, obj: str,
                                    version_id: str) -> FileInfo:
        # Serial reads only while every drive is ONLINE; the loop itself
        # runs in ONE bounded pool worker (run_bounded) so the FIRST hang
        # on an all-local set frees the caller at the deadline and falls
        # back to the deadline'd parallel fan-out — a hung drive there
        # becomes a timeout value the quorum reducers count as failed.
        serial_done = False
        if self._serial_meta_reads and self._drives_all_online():
            # All-local cached journal reads run sequentially; once a
            # strict majority agrees on (mod_time, data_dir, version),
            # the remaining drives cannot change the election — skip
            # them (the shards they hold are addressed by the elected
            # distribution, not by these metadata reads).
            out: dict = {"fi": None, "results": None}

            def serial_election():
                need = self.n // 2 + 1
                results = []
                tally: dict = {}
                for d in self.drives:
                    try:
                        r = d.read_version(bucket, obj, version_id)
                    except Exception as e:  # noqa: BLE001 — per-drive data
                        r = e
                    results.append(r)
                    # Early exit only for live versions: a delete marker's
                    # read quorum depends on the geometry of the NON-deleted
                    # versions other drives may hold, which a partial read
                    # cannot know — markers always take the full election.
                    if isinstance(r, FileInfo) and not r.deleted:
                        s = election_sig(r)
                        tally[s] = tally.get(s, 0) + 1
                        # The read quorum is this geometry's data_blocks,
                        # which can exceed a bare majority (k > n/2+1 at low
                        # parity) — stop only when both are satisfied.
                        k = r.erasure.data_blocks or 0
                        if tally[s] >= max(need, k):
                            # This fi IS the quorum election — re-counting
                            # through find_fileinfo_in_quorum adds nothing.
                            out["fi"] = r
                            return
                out["results"] = results

            if run_bounded(serial_election, self._meta_deadline()):
                if out["fi"] is not None:
                    return out["fi"]
                results = out["results"]
                serial_done = True
        if not serial_done:
            results = parallel_map(
                [lambda d=d: d.read_version(bucket, obj, version_id)
                 for d in self.drives],
                deadline=self._meta_deadline(),
            )
        if all(isinstance(r, se.FileNotFound) for r in results):
            raise se.ObjectNotFound(bucket, obj)
        if any(isinstance(r, se.FileVersionNotFound) for r in results) and not any(
            isinstance(r, FileInfo) for r in results
        ):
            raise se.VersionNotFound(bucket, obj)
        # Geometry majority decides the read quorum.
        ks = [r.erasure.data_blocks for r in results
              if isinstance(r, FileInfo) and not r.deleted and r.erasure.data_blocks]
        read_quorum = max(set(ks), key=ks.count) if ks else self.n // 2
        return find_fileinfo_in_quorum(results, max(1, read_quorum), bucket, obj)

    def latest_fileinfo(self, bucket: str, obj: str,
                        version_id: str = "") -> FileInfo:
        """Quorum-elected FileInfo including delete markers — the existence
        probe pool routing needs (a key whose latest version is a delete
        marker still *lives* here; reference getPoolIdxExisting,
        cmd/erasure-server-pool.go:252)."""
        return self._read_quorum_fileinfo(bucket, obj, version_id)

    def _fi_to_object_info(self, bucket: str, obj: str, fi: FileInfo) -> ObjectInfo:
        return listing.fi_to_object_info(bucket, obj, fi)


def _local_shard_paths(drives: list[StorageAPI], vol: str,
                       rel: str) -> list[str] | None:
    """Absolute shard-file paths when EVERY drive is local (unwrapping
    ONLY the disk-ID decorator); None if any drive is remote or otherwise
    wrapped — the native WRITE lanes (PUT fan-out, heal rebuild) need
    direct file access on all n drives. The GET lane uses the mixed form
    below instead."""
    paths, remotes = _shard_paths_mixed(drives, vol, rel)
    if paths is None or any(r is not None for r in remotes):
        return None
    return paths


def _shard_paths_mixed(drives: list[StorageAPI], vol: str, rel: str
                       ) -> tuple[list[str] | None, list[StorageAPI | None]]:
    """(paths, remotes) for the mixed native GET lane: paths[i] is the
    absolute shard path for a local drive ("" otherwise); remotes[i] is
    the drive object for every NON-local position — those shards prefetch
    their framed ranges through the drive's own read_file_stream, so any
    wrapper (remote client, fault injector) keeps its per-call
    interposition. (None, _) only when a local drive can't map the path
    (invalid name)."""
    from minio_tpu.storage.local import LocalDrive

    paths: list[str] = []
    remotes: list[StorageAPI | None] = []
    for d in drives:
        base = _health.unwrap(d)
        if isinstance(base, LocalDrive):
            try:
                paths.append(base._file_path(vol, rel))
                remotes.append(None)
                continue
            except se.StorageError:
                return None, []
        paths.append("")
        remotes.append(d)
    return paths, remotes


def _consecutive(ids) -> bool:
    return list(ids) == list(range(ids[0], ids[0] + len(ids)))


class _VerifyStage:
    """One attempt at a batch's verify launch, staged by its reads
    (_read_shards): `stage` [bucket_rows(shards·blocks), cap] u8 and the
    stored digests `want` [rows, 32], both np.empty; `lens` [rows] int32,
    set for the harvested shards' rows only. Slots are handed out in
    submission order (take, on the submitting thread)."""

    __slots__ = ("stage", "want", "lens", "blocks", "slots", "slot_of")

    def __init__(self, shards: int, blocks: int, cap: int):
        from minio_tpu.ops import fused, mxsum

        rows = fused.bucket_rows(shards * blocks)
        self.stage = np.empty((rows, cap), dtype=np.uint8)
        self.want = np.empty((rows, mxsum.DIGEST_LEN), dtype=np.uint8)
        self.lens = np.zeros(rows, dtype=np.int32)
        self.blocks = blocks
        self.slots = rows // blocks
        self.slot_of: dict[int, int] = {}  # shard index -> slot

    def take(self, shard: int) -> int | None:
        """The next slot for `shard`, or None past the array's rows."""
        slot = len(self.slot_of)
        if slot >= self.slots:
            return None
        self.slot_of[shard] = slot
        return slot

    def rows(self, slot: int):
        """(digest rows, chunk rows) of a slot."""
        s = slice(slot * self.blocks, (slot + 1) * self.blocks)
        return self.want[s], self.stage[s]


def _yield_block_range(chunks, lo: int, hi: int):
    """Yield [lo, hi) of a decoded block as memoryview slices of its k
    data chunks — the zero-copy replacement for joining the chunks into
    one fresh block buffer and slicing that (two full passes over the
    payload per block on the GET hot path). Trailing shard padding
    falls away because hi is capped at the block's real length."""
    pos = 0
    for c in chunks:
        if pos >= hi:
            return
        end = pos + len(c)
        a = max(lo, pos)
        b = min(hi, end)
        if b > a:
            yield memoryview(c)[a - pos:b - pos]
        pos = end


def _read_exact(f, n: int) -> bytes:
    """Read exactly n bytes from a stream; OSError on early EOF — the
    ONE short-read rule every remote shard reader shares. Returns the
    accumulator bytearray as-is (single-read fast path returns the
    stream's own buffer): consumers take any bytes-like, including the
    native decoder's mem shards (ctypes borrows writable buffers)."""
    first = f.read(n)
    if first and len(first) == n:
        return first
    buf = bytearray(first or b"")
    while len(buf) < n:
        c = f.read(n - len(buf))
        if not c:
            raise OSError("short read")
        buf += c
    return buf


def _fetch_framed(drive: StorageAPI, vol: str, rel: str, lo: int,
                  ln: int, streams: dict | None = None,
                  key: int | None = None) -> bytes | None:
    """Fetch [lo, lo+ln) of a shard file through the drive's stream API
    (ranged RPC for remote drives). None on any failure or short read —
    the caller marks the shard dead and re-selects. When `streams` is
    given, the open stream is cached under `key` across windows (one
    stat/open per shard per GET instead of per window); a failed stream
    is closed and evicted."""
    f = streams.get(key) if streams is not None else None
    opened = f is None
    if f is None:
        try:
            f = drive.read_file_stream(vol, rel)
        except (se.StorageError, OSError):
            return None
        if streams is not None:
            streams[key] = f
    try:
        f.seek(lo)
        buf = _read_exact(f, ln)
        if streams is None:
            try:
                f.close()
            except Exception:  # noqa: BLE001
                pass
        return buf
    except (se.StorageError, OSError, ValueError):
        if streams is not None:
            streams.pop(key, None)
        try:
            f.close()
        except Exception:  # noqa: BLE001
            pass
        return None


def _clone_for_drive(fi: FileInfo, index: int) -> FileInfo:
    out = fi.clone()
    out.erasure.index = index
    return out


def _validate_bucket_name(bucket: str) -> None:
    if not (3 <= len(bucket) <= 63) or bucket != bucket.lower() or "/" in bucket:
        raise se.BucketNameInvalid(bucket)
    if bucket.startswith(".") or bucket.startswith("-") or bucket.endswith("-"):
        raise se.BucketNameInvalid(bucket)
    if not all(c.isalnum() or c in ".-" for c in bucket):
        raise se.BucketNameInvalid(bucket)


def _validate_object_name(obj: str) -> None:
    if not obj or len(obj) > 1024 or obj.startswith("/"):
        raise se.ObjectNameInvalid("", obj)
    parts = obj.split("/")
    if any(p in ("..", "") for p in parts[:-1]) or parts[-1] == "..":
        raise se.ObjectNameInvalid("", obj)
