"""Healing: whole-set reconstruct of damaged/missing shards.

Role-equivalent of the reference's healing plane (cmd/erasure-healing.go:233-498,
cmd/erasure-healing-common.go:103,161, cmd/erasure-lowlevel-heal.go): classify
every drive of the set as ok/offline/missing/outdated/corrupt for an object
version, elect the authoritative metadata by modtime, reconstruct the target
shards for every part, and commit them with the same tmp→rename discipline as
PutObject. Dangling objects (ones that can never reach read quorum again) are
purged.

TPU-first difference: the reference heals shard-by-shard through a Decode→
Encode pipe (erasure-lowlevel-heal.go:28). Here reconstruction is the same
batched GF(2) contraction as GET — all missing shard columns for a batch of
blocks are produced by ONE device launch with decode weights for the failure
pattern, so healing a 4-drives-down set costs one matmul per block batch, not
four passes.

The MRF ("most recently failed") queue mirrors cmd/erasure.go:41-75: partial
writes and corrupt reads enqueue (bucket, object, version) and a background
worker re-heals them.
"""

from __future__ import annotations

import os
import queue
import threading
import uuid
from dataclasses import dataclass, field

from minio_tpu import obs
from minio_tpu.dataplane import route
from minio_tpu.erasure.codec import ErasureCodec
from minio_tpu.erasure.metadata import parallel_map, shuffle_by_distribution
from minio_tpu.ops import bitrot
from minio_tpu.storage.fileinfo import FileInfo
from minio_tpu.utils import errors as se

# Drive states (reference madmin drive states).
DRIVE_STATE_OK = "ok"
DRIVE_STATE_OFFLINE = "offline"
DRIVE_STATE_MISSING = "missing"
DRIVE_STATE_CORRUPT = "corrupt"
DRIVE_STATE_OUTDATED = "outdated"


@dataclass
class HealDriveState:
    endpoint: str
    state: str


@dataclass
class HealResultItem:
    """Result of one heal operation (reference madmin.HealResultItem)."""

    heal_type: str = "object"
    bucket: str = ""
    object: str = ""
    version_id: str = ""
    object_size: int = 0
    data_blocks: int = 0
    parity_blocks: int = 0
    disk_count: int = 0
    before: list[HealDriveState] = field(default_factory=list)
    after: list[HealDriveState] = field(default_factory=list)
    dry_run: bool = False
    purged: bool = False

    @property
    def healed_count(self) -> int:
        return sum(
            1
            for b, a in zip(self.before, self.after)
            if b.state != DRIVE_STATE_OK and a.state == DRIVE_STATE_OK
        )


def latest_fileinfo(results: list) -> FileInfo | None:
    """Elect the authoritative version: the FileInfo cohort with the newest
    mod_time (reference listOnlineDisks modtime election,
    cmd/erasure-healing-common.go:103). Returns None if no drive has one."""
    valid = [r for r in results if isinstance(r, FileInfo)]
    if not valid:
        return None
    latest_mt = max(fi.mod_time for fi in valid)
    cohort = [fi for fi in valid if fi.mod_time == latest_mt]
    # Prefer an entry carrying erasure geometry (a data-holding drive).
    for fi in cohort:
        if fi.deleted or fi.erasure.data_blocks:
            return fi
    return cohort[0]


def _same_version(fi: FileInfo, latest: FileInfo) -> bool:
    return (
        fi.mod_time == latest.mod_time
        and fi.data_dir == latest.data_dir
        and fi.version_id == latest.version_id
        and fi.deleted == latest.deleted
    )


class _ShardWriterPool:
    """Fan-out writer: one streaming create_file per (target drive, part),
    fed from queues — the healing analogue of PutObject's fan-out."""

    def __init__(self, drives_by_pos: dict[int, object], sys_vol: str, tmp_dirs: dict[int, str]):
        self.sys_vol = sys_vol
        self.tmp_dirs = tmp_dirs
        self.drives = drives_by_pos
        self.queues: dict[int, queue.Queue] = {}
        self.threads: dict[int, threading.Thread] = {}
        self.errs: dict[int, Exception | None] = {pos: None for pos in drives_by_pos}

    def start_part(self, part_number: int) -> None:
        for pos, drive in self.drives.items():
            if self.errs[pos] is not None:
                continue
            q: queue.Queue = queue.Queue(maxsize=4)
            self.queues[pos] = q

            def writer(pos=pos, drive=drive, q=q):
                def gen():
                    while True:
                        chunk = q.get()
                        if chunk is None:
                            return
                        yield chunk

                try:
                    drive.create_file(
                        self.sys_vol, f"{self.tmp_dirs[pos]}/part.{part_number}", gen()
                    )
                except Exception as e:  # noqa: BLE001 - per-drive failure is data
                    self.errs[pos] = e
                    while q.get() is not None:
                        pass

            t = threading.Thread(target=writer, daemon=True)
            self.threads[pos] = t
            t.start()

    def put(self, pos: int, framed: bytes) -> None:
        q = self.queues.get(pos)
        if q is not None:
            q.put(framed)

    def finish_part(self) -> None:
        for q in self.queues.values():
            q.put(None)
        for t in self.threads.values():
            t.join()
        self.queues.clear()
        self.threads.clear()


class HealingMixin:
    """Healing entry points for ErasureObjects (self provides drives, parity,
    codec config, bitrot_algorithm)."""

    # -- bucket heal (reference healBucket, cmd/erasure-healing.go:56) --

    def heal_bucket(self, bucket: str, dry_run: bool = False) -> HealResultItem:
        results = parallel_map([lambda d=d: d.stat_vol(bucket) for d in self.drives],
                               deadline=self._meta_deadline())
        res = HealResultItem(heal_type="bucket", bucket=bucket,
                             disk_count=self.n, dry_run=dry_run)
        have = [not isinstance(r, Exception) for r in results]
        for i, ok in enumerate(have):
            st = DRIVE_STATE_OK if ok else (
                DRIVE_STATE_MISSING
                if isinstance(results[i], se.VolumeNotFound)
                else DRIVE_STATE_OFFLINE
            )
            res.before.append(HealDriveState(self.drives[i].endpoint(), st))
        if not any(have):
            raise se.BucketNotFound(bucket)
        res.after = [HealDriveState(s.endpoint, s.state) for s in res.before]
        if dry_run:
            return res
        for i, ok in enumerate(have):
            if ok or not isinstance(results[i], se.VolumeNotFound):
                continue
            try:
                self.drives[i].make_vol(bucket)
                res.after[i].state = DRIVE_STATE_OK
            except se.VolumeExists:
                res.after[i].state = DRIVE_STATE_OK
            except se.StorageError:
                pass
        # The bucket's metadata doc lives in the mirrored sys store;
        # reading it triggers that store's read-repair, converging copies
        # lost/corrupted while a drive was away. Sets that don't host the
        # deployment's store simply have no doc and resolve FileNotFound.
        try:
            self.read_sys_config(f"buckets/{bucket}/metadata.mp")
        except se.StorageError:
            pass    # no doc (default config) or below quorum
        return res

    # -- object heal (reference healObject, cmd/erasure-healing.go:233) --

    def heal_object(
        self,
        bucket: str,
        obj: str,
        version_id: str = "",
        dry_run: bool = False,
        remove_dangling: bool = True,
        scan_deep: bool = False,
    ) -> HealResultItem:
        # Heal mutates shard files + journal: exclusive per-object lock
        # (reference cmd/erasure-healing.go:252-258).
        with self.nslock.lock(bucket, obj):
            return self._heal_object_locked(
                bucket, obj, version_id, dry_run, remove_dangling, scan_deep)

    def _heal_object_locked(
        self,
        bucket: str,
        obj: str,
        version_id: str = "",
        dry_run: bool = False,
        remove_dangling: bool = True,
        scan_deep: bool = False,
    ) -> HealResultItem:
        results = parallel_map(
            [lambda d=d: d.read_version(bucket, obj, version_id) for d in self.drives],
            deadline=self._meta_deadline(),
        )
        latest = latest_fileinfo(results)
        if latest is None:
            if all(isinstance(r, (se.FileNotFound, se.FileVersionNotFound)) for r in results):
                raise se.ObjectNotFound(bucket, obj)
            raise se.InsufficientReadQuorum(bucket, obj, "no readable metadata")

        if latest.deleted or not latest.erasure.distribution:
            return self._heal_metadata_only(bucket, obj, latest, results, dry_run)
        if (latest.metadata.get("x-mtpu-internal-transition-tier")
                and not latest.data_dir):
            # Transitioned stub: the data's only copy lives on the tier;
            # heal just the metadata quorum, never "reconstruct" (and never
            # purge) what is deliberately absent locally.
            return self._heal_metadata_only(bucket, obj, latest, results, dry_run)

        dist = latest.erasure.distribution
        k = latest.erasure.data_blocks
        n = len(dist)
        shuffled_drives = shuffle_by_distribution(self.drives, dist)
        shuffled_results = shuffle_by_distribution(results, dist)

        states = self._classify(bucket, obj, latest, shuffled_drives,
                                shuffled_results, scan_deep)

        res = HealResultItem(
            bucket=bucket, object=obj, version_id=latest.version_id,
            object_size=latest.size, data_blocks=k,
            parity_blocks=latest.erasure.parity_blocks,
            disk_count=self.n, dry_run=dry_run,
            before=[HealDriveState(d.endpoint(), s) for d, s in zip(shuffled_drives, states)],
        )
        res.after = [HealDriveState(s.endpoint, s.state) for s in res.before]

        avail = [i for i, s in enumerate(states) if s == DRIVE_STATE_OK]
        targets = [i for i, s in enumerate(states)
                   if s in (DRIVE_STATE_MISSING, DRIVE_STATE_CORRUPT, DRIVE_STATE_OUTDATED)]

        if len(avail) < k:
            # Can this object ever be healed? If missing-metadata drives alone
            # exceed parity, no quorum is reachable: dangling
            # (reference isObjectDangling, cmd/erasure-healing.go:758).
            notfound = sum(
                1 for r in results
                if isinstance(r, (se.FileNotFound, se.FileVersionNotFound))
            )
            if notfound > latest.erasure.parity_blocks and remove_dangling:
                if not dry_run:
                    self._purge_dangling(bucket, obj, latest)
                    res.purged = True
                return res
            raise se.InsufficientReadQuorum(
                bucket, obj, f"{len(avail)} of {k} shards available"
            )

        if not targets or dry_run:
            return res

        if latest.inline_data:
            self._heal_write_metadata(bucket, obj, latest, shuffled_drives, targets, res)
            return res

        healed = self._reconstruct_to_targets(
            bucket, obj, latest, shuffled_drives, avail, targets
        )
        for pos in healed:
            res.after[pos].state = DRIVE_STATE_OK
        return res

    # -- classification (reference disksWithAllParts,
    #    cmd/erasure-healing-common.go:161) --

    def _classify(self, bucket, obj, latest, shuffled_drives, shuffled_results,
                  scan_deep) -> list[str]:
        states: list[str] = []
        checks = []
        for pos, (drive, r) in enumerate(zip(shuffled_drives, shuffled_results)):
            if isinstance(r, (se.FileNotFound, se.FileVersionNotFound)):
                states.append(DRIVE_STATE_MISSING)
                checks.append(None)
            elif isinstance(r, (se.FileCorrupt, se.CorruptedFormat)):
                # Unreadable journal (CRC/decode failure) is damage to
                # heal, not an offline drive (reference disksWithAllParts
                # treats errFileCorrupt as heal-needing, never skips it).
                states.append(DRIVE_STATE_CORRUPT)
                checks.append(None)
            elif isinstance(r, Exception):
                states.append(DRIVE_STATE_OFFLINE)
                checks.append(None)
            elif not _same_version(r, latest):
                states.append(DRIVE_STATE_OUTDATED)
                checks.append(None)
            else:
                states.append(DRIVE_STATE_OK)
                if latest.inline_data:
                    checks.append(None)
                elif scan_deep:
                    checks.append(lambda d=drive: d.verify_file(bucket, obj, latest))
                else:
                    checks.append(lambda d=drive: d.check_parts(bucket, obj, latest))
        to_run = [(i, c) for i, c in enumerate(checks) if c is not None]
        outcomes = parallel_map([c for _, c in to_run],
                                deadline=self._data_deadline())
        for (i, _), out in zip(to_run, outcomes):
            if isinstance(out, Exception):
                states[i] = (
                    DRIVE_STATE_CORRUPT
                    if isinstance(out, (se.FileCorrupt, se.FileNotFound))
                    else DRIVE_STATE_OFFLINE
                )
        return states

    # -- reconstruction core --

    def _reconstruct_to_targets(self, bucket, obj, latest, shuffled_drives,
                                avail, targets) -> list[int]:
        """Rebuild every part's shards for the target positions; returns the
        positions successfully healed (committed via rename_data)."""
        k = latest.erasure.data_blocks
        m = latest.erasure.parity_blocks
        n = k + m
        codec = ErasureCodec(k, m, latest.erasure.block_size)
        shard_size = codec.shard_size()
        algo = next((c.algorithm for c in latest.erasure.checksums),
                    self.bitrot_algorithm)
        bitrot_algo = bitrot.get_algorithm(algo)
        sys_vol = ".mtpu.sys"

        # Unique per invocation: concurrent heals of the same object (MRF
        # worker + admin heal) must never share tmp files.
        heal_id = uuid.uuid4().hex
        tmp_dirs = {pos: f"tmp/heal-{heal_id}-{pos}" for pos in targets}
        pool = _ShardWriterPool(
            {pos: shuffled_drives[pos] for pos in targets}, sys_vol, tmp_dirs
        )

        chosen = avail[:k]
        native = self._native_rebuild(bucket, obj, latest, shuffled_drives,
                                      targets, algo, codec, sys_vol,
                                      tmp_dirs)
        if native is not None:
            for pos, err in native.items():
                pool.errs[pos] = err
            return self._commit_healed(bucket, obj, latest, shuffled_drives,
                                       targets, sys_vol, tmp_dirs, pool)
        use_fused = algo == "mxsum256"
        t_tuple = tuple(targets)
        # Lane or direct launch: dataplane/route.py. On the lanes a
        # whole-set heal shares launches with concurrent heals and
        # degraded GETs (per-row decode matrices ride as data).

        def begin_rebuild(rows, block_lens):
            return route.begin_reconstruct(codec, rows, block_lens, t_tuple,
                                           with_digests=use_fused)

        try:
            for part in latest.parts:
                shard_data_size = latest.erasure.shard_file_size(part.size)
                rel = f"{obj}/{latest.data_dir}/part.{part.number}"
                readers = {}
                for pos in chosen:
                    f = shuffled_drives[pos].read_file_stream(bucket, rel)
                    readers[pos] = bitrot.BitrotReader(f, shard_data_size, shard_size, algo)
                pool.start_part(part.number)
                try:
                    # Dispatch-ahead rebuild pipeline (mirrors the put
                    # path's P2 shape): the host reads batch N+1's shards
                    # while the device rebuilds batch N; rebuilt chunks +
                    # their bitrot digests come out of ONE fused launch
                    # when the algorithm is the device checksum.
                    pending: list = []

                    def drain_one() -> None:
                        chunks_rows, dig_rows = pending.pop(0).wait()
                        for j, chunks in enumerate(chunks_rows):
                            for ti, pos in enumerate(t_tuple):
                                d = (dig_rows[j][ti] if dig_rows is not None
                                     else bitrot_algo.digest(chunks[ti]))
                                pool.put(pos, d + chunks[ti])

                    n_blocks = max(1, -(-part.size // latest.erasure.block_size))
                    bi = 0
                    while bi < n_blocks:
                        batch_ids = list(range(bi, min(bi + self.batch_blocks, n_blocks)))
                        block_lens = [
                            min(latest.erasure.block_size,
                                part.size - b * latest.erasure.block_size)
                            for b in batch_ids
                        ]
                        rows = []
                        # Survivor records of a device-checksummed object
                        # verify in ONE device launch per batch, as the
                        # GET path's do; read_at would hash every chunk
                        # on the host (numpy), a host lane inside heal.
                        records = []
                        for j, b in enumerate(batch_ids):
                            chunk_len = -(-block_lens[j] // k)
                            row: list[bytes | None] = [None] * n
                            for pos in chosen:
                                if use_fused and chunk_len:
                                    want, row[pos] = readers[pos].read_record(b)
                                    records.append((b, want, row[pos]))
                                else:
                                    row[pos] = readers[pos].read_at(b * shard_size, chunk_len)
                            rows.append(row)
                        if records:
                            from minio_tpu.ops import fused

                            got = fused.digest_chunks_host(
                                [c for _b, _w, c in records], shard_size)
                            for (b, want, _c), g in zip(records, got):
                                if g != want:
                                    raise se.FileCorrupt(
                                        f"bitrot digest mismatch at chunk {b}")
                        pending.append(begin_rebuild(rows, block_lens))
                        if len(pending) >= 2:
                            drain_one()
                        bi = batch_ids[-1] + 1
                    while pending:
                        drain_one()
                finally:
                    for r in readers.values():
                        try:
                            r.src.close()
                        except Exception:  # noqa: BLE001
                            pass
                    pool.finish_part()
        except Exception:
            for pos in targets:
                try:
                    shuffled_drives[pos].delete(sys_vol, tmp_dirs[pos], recursive=True)
                except se.StorageError:
                    pass
            raise

        return self._commit_healed(bucket, obj, latest, shuffled_drives,
                                   targets, sys_vol, tmp_dirs, pool)

    def _commit_healed(self, bucket, obj, latest, shuffled_drives, targets,
                       sys_vol, tmp_dirs, pool) -> list[int]:
        # Heal rewrites journals out from under any cached election.
        self._meta_invalidate(bucket, obj)
        healed = []
        for pos in targets:
            if pool.errs[pos] is not None:
                continue
            fi = _clone_fi(latest, pos + 1)
            try:
                shuffled_drives[pos].rename_data(sys_vol, tmp_dirs[pos], fi, bucket, obj)
                healed.append(pos)
            except se.StorageError:
                try:
                    shuffled_drives[pos].delete(sys_vol, tmp_dirs[pos], recursive=True)
                except se.StorageError:
                    pass
        return healed

    def _native_rebuild(self, bucket, obj, latest, shuffled_drives, targets,
                        algo, codec, sys_vol, tmp_dirs
                        ) -> dict[int, Exception | None] | None:
        """Native heal lane: the GET-path C decoder reads + bitrot-verifies
        + reconstructs each part windowed, and the PUT-path C encoder —
        with every HEALTHY drive pre-failed — re-frames and writes ONLY the
        target positions' shard files into the heal tmp dirs. Same commit
        (rename_data) as the Python lane. Returns per-target errors, or
        None to fall through when the topology/algorithm doesn't qualify
        (remote drives, device-fused digests, odd block size)."""
        from minio_tpu.erasure.objects import _local_shard_paths
        from minio_tpu.native import plane

        if (algo not in ("sip256", "highwayhash256")
                or not plane.available() or codec.block_size % 64):
            return None
        k, m = codec.k, codec.m
        n = k + m
        errs: dict[int, Exception | None] = {pos: None for pos in targets}
        # Small enough windows that the 1-deep pipeline genuinely
        # overlaps: with one giant window, decode and the encoder's
        # write-back serialize and heal runs at decode+write instead of
        # max(decode, write) (reference erasure-lowlevel-heal.go pipes
        # the decode straight into the encode).
        win = plane.pipeline_window_blocks(codec.block_size) \
            * codec.block_size
        from minio_tpu.storage.healthcheck import unwrap as _unwrap_drive

        for part in latest.parts:
            rel = f"{obj}/{latest.data_dir}/part.{part.number}"
            src_paths = _local_shard_paths(shuffled_drives, bucket, rel)
            if src_paths is None:
                return None
            dst_paths = []
            for pos in range(n):
                d = shuffled_drives[pos]
                base = _unwrap_drive(d)
                # Non-target positions are pre-failed below; the C writer
                # skips a failed drive before ever opening its path, so
                # the placeholder is never touched.
                dst_paths.append(base._file_path(
                    sys_vol, f"{tmp_dirs[pos]}/part.{part.number}")
                    if pos in errs else "/dev/null")
            try:
                enc = plane.PartEncoder(dst_paths, k, m, codec.block_size,
                                        algorithm=algo, compute_md5=False)
                for pos in range(n):
                    # Pre-fail non-targets AND targets already lost on an
                    # earlier part — no point re-framing onto a dead tmp.
                    if pos not in errs or errs[pos] is not None:
                        enc.fail_drive(pos)
                    else:
                        os.makedirs(os.path.dirname(dst_paths[pos]),
                                    exist_ok=True)
                if part.size == 0:
                    enc.feed(b"", final=True)
                # 1-deep pipeline: decode window N+1 while the encoder
                # writes window N (same overlap shape as the PUT lane).
                # Dead shards found by one window (<0 states) feed the
                # next window's skip set so they aren't re-read/re-hashed.
                from concurrent.futures import ThreadPoolExecutor

                dead: set[int] = set()
                with ThreadPoolExecutor(
                        1, thread_name_prefix="native-heal") as ex:
                    fut = None
                    off = 0
                    while off < part.size:
                        ln = min(win, part.size - off)
                        out, states = plane.decode_range(
                            src_paths, k, m, codec.block_size, part.size,
                            off, ln, algorithm=algo, skip=dead)
                        if out is None:
                            # Fewer than k shards served this window:
                            # the Python lane has finer-grained survivor
                            # fallback. Settle the in-flight write first.
                            if fut is not None:
                                fut.result()
                            return None
                        dead.update(
                            i for i, s in enumerate(states) if s < 0)
                        if fut is not None:
                            fut.result()
                        fut = ex.submit(obs.ctx_wrap(enc.feed), out,
                                        off + ln >= part.size)
                        off += ln
                    if fut is not None:
                        fut.result()
            except OSError:
                # Decode window failed (IO error mid-stream): let the
                # Python lane decide.
                return None
            for pos in errs:
                if enc.errors[pos]:
                    errs[pos] = se.FaultyDisk(
                        f"native heal write failed: {dst_paths[pos]}")
        return errs

    # -- metadata-only heals (delete markers, inline objects) --

    def _heal_metadata_only(self, bucket, obj, latest, results, dry_run) -> HealResultItem:
        res = HealResultItem(
            bucket=bucket, object=obj, version_id=latest.version_id,
            object_size=latest.size, disk_count=self.n, dry_run=dry_run,
        )
        targets = []
        for i, r in enumerate(results):
            if isinstance(r, FileInfo) and _same_version(r, latest):
                st = DRIVE_STATE_OK
            elif isinstance(r, (se.FileNotFound, se.FileVersionNotFound)) or isinstance(
                r, FileInfo
            ):
                st = DRIVE_STATE_MISSING
                targets.append(i)
            else:
                st = DRIVE_STATE_OFFLINE
            res.before.append(HealDriveState(self.drives[i].endpoint(), st))
        res.after = [HealDriveState(s.endpoint, s.state) for s in res.before]
        if dry_run:
            return res
        self._heal_write_metadata(bucket, obj, latest, self.drives, targets, res,
                                  positions_are_physical=True)
        return res

    def _heal_write_metadata(self, bucket, obj, latest, drives, targets, res,
                             positions_are_physical=False):
        self._meta_invalidate(bucket, obj)

        def write(pos):
            fi = _clone_fi(latest, 0 if positions_are_physical else pos + 1)
            if latest.deleted:
                drives[pos].delete_version(bucket, obj, fi)
            else:
                drives[pos].write_metadata(bucket, obj, fi)

        outcomes = parallel_map([lambda p=p: write(p) for p in targets],
                                deadline=self._meta_deadline())
        for pos, out in zip(targets, outcomes):
            if not isinstance(out, Exception):
                res.after[pos].state = DRIVE_STATE_OK

    def heal_objects(self, bucket: str, prefix: str = "", **kw):
        """Walk every object under prefix and heal it (reference HealObjects
        walk, cmd/erasure-server-pool.go:1500) — streamed, O(page) memory
        even over a multi-million-object bucket."""
        for name, _meta in self.stream_journals(bucket, prefix):
            try:
                yield self.heal_object(bucket, name, **kw)
            except se.ObjectError as e:
                yield e

    # -- dangling purge (reference purgeObjectDangling,
    #    cmd/erasure-healing.go:700) --

    def _purge_dangling(self, bucket: str, obj: str, latest: FileInfo) -> None:
        target = FileInfo(volume=bucket, name=obj, version_id=latest.version_id,
                          data_dir=latest.data_dir)
        parallel_map(
            [lambda d=d: d.delete_version(bucket, obj, target) for d in self.drives],
            deadline=self._meta_deadline(),
        )


MRF_RETRY_INTERVAL = float(os.environ.get("MTPU_MRF_RETRY_INTERVAL", "1.0"))
MRF_RETRY_MAX = int(os.environ.get("MTPU_MRF_RETRY_MAX", "600"))
MRF_RETRY_CAP = float(os.environ.get("MTPU_MRF_RETRY_CAP", "60.0"))

_MRF_REQUEUES = obs.counter(
    "minio_tpu_mrf_requeues_total",
    "MRF heals requeued because target drives were still offline")


class MRFHealer:
    """Most-recently-failed heal queue (reference mrfOpCh, cmd/erasure.go:41-75):
    partial writes and corrupt reads enqueue here; a background worker retries
    the heal out of band.

    Partition-aware: a heal attempted while the missing shards' drives are
    still unreachable (peer breaker OPEN / mid-partition) classifies them
    OFFLINE and rebuilds nothing — such entries are REQUEUED with an
    exponentially backed-off delay (base `MTPU_MRF_RETRY_INTERVAL`, cap
    `MTPU_MRF_RETRY_CAP`, at most `MTPU_MRF_RETRY_MAX` attempts) instead
    of retired, so a degraded write's missed shards reliably drain once
    the partition heals while a permanently dead drive cannot keep the
    drain thread busy-spinning. Unhealable states (object deleted) drop."""

    def __init__(self, er, maxsize: int = 10000):
        self.er = er
        self.q: queue.Queue = queue.Queue(maxsize=maxsize)
        self._seen_lock = threading.Lock()
        # (bucket, obj, version_id) -> deep flag; a deep request upgrades
        # a pending shallow one in place (one heal pass, not two).
        self._pending: dict[tuple[str, str, str], bool] = {}
        self._attempts: dict[tuple[str, str, str], int] = {}
        # Key currently being healed. Kept OUT of _pending so an
        # add_partial racing the in-flight heal re-queues (the running
        # heal read its metadata before the new damage) — but still
        # counted by wait_idle.
        self._inflight: set[tuple[str, str, str]] = set()
        # Deferred re-heals: [(due_monotonic, key, deep)] — fed back to
        # _pending/queue at their due time; wait_idle blocks on them.
        self._retry: list[tuple[float, tuple[str, str, str], bool]] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._drain, daemon=True)
        self._thread.start()

    def add_partial(self, bucket: str, obj: str, version_id: str = "",
                    deep: bool = False) -> None:
        """deep=True when the caller OBSERVED bitrot (a corrupt read): the
        background heal then bitrot-verifies every shard, so in-place
        corruption is rebuilt rather than passed over by the presence-only
        normal scan."""
        key = (bucket, obj, version_id)
        with self._seen_lock:
            if key in self._pending:
                if deep:
                    self._pending[key] = True  # upgrade the queued heal
                return
            self._pending[key] = deep
        try:
            self.q.put_nowait(key)
        except queue.Full:
            with self._seen_lock:
                self._pending.pop(key, None)

    def _pump_due_retries(self) -> None:
        import time as _time

        now = _time.monotonic()
        with self._seen_lock:
            due = [(k, d) for t, k, d in self._retry if t <= now]
            self._retry = [e for e in self._retry if e[0] > now]
            # Re-enter through _pending so a racing add_partial
            # coalesces exactly as for a first-time enqueue; a retry
            # carrying deep=True UPGRADES an already-pending shallow
            # entry (the observed corruption must not be forgotten).
            to_queue = []
            for k, d in due:
                if k in self._pending:
                    if d:
                        self._pending[k] = True
                else:
                    self._pending[k] = d
                    to_queue.append((k, d))
            due = to_queue
        for key, _deep in due:
            try:
                self.q.put_nowait(key)
            except queue.Full:
                with self._seen_lock:
                    self._pending.pop(key, None)
                    self._attempts.pop(key, None)

    def _drain(self) -> None:
        import time as _time

        while not self._stop.is_set():
            self._pump_due_retries()
            try:
                key = self.q.get(timeout=0.2)
            except queue.Empty:
                continue
            bucket, obj, version_id = key
            # Pop-before-heal (so damage arriving DURING the heal
            # re-queues — this attempt read its metadata first), but
            # track the in-flight key so wait_idle keeps blocking.
            with self._seen_lock:
                deep = self._pending.pop(key, False)
                self._inflight.add(key)
            requeue = False
            try:
                res = self.er.heal_object(bucket, obj, version_id,
                                          scan_deep=deep)
                # Drives unreachable during the attempt (mid-partition /
                # OPEN peer breaker) classify OFFLINE and got nothing
                # rebuilt: the entry is NOT drained yet.
                requeue = any(s.state == DRIVE_STATE_OFFLINE
                              for s in (res.after or res.before or []))
            except (se.ObjectNotFound, se.FileNotFound,
                    se.FileVersionNotFound):
                pass  # deleted since: nothing left to heal
            except Exception:  # noqa: BLE001 - transient (quorum/transport)
                requeue = True
            with self._seen_lock:
                self._inflight.discard(key)
                self._attempts[key] = attempts = self._attempts.get(key, 0) + 1
                if (requeue and attempts < MRF_RETRY_MAX
                        and key not in self._pending):
                    # (a concurrent add_partial already re-queued it —
                    # that entry covers this retry.) Jittered exponential
                    # backoff: a partition drains at near-base cadence
                    # (few attempts), while a permanently dead drive —
                    # which keeps every heal of its set partial — settles
                    # to one cheap attempt per MRF_RETRY_CAP instead of
                    # hammering a full heal pass per object per interval.
                    delay = min(MRF_RETRY_INTERVAL * (2 ** (attempts - 1)),
                                max(MRF_RETRY_INTERVAL, MRF_RETRY_CAP))
                    self._retry.append(
                        (_time.monotonic() + delay, key, deep))
                    _MRF_REQUEUES.labels().inc()
                elif requeue and key in self._pending:
                    # A concurrent add_partial re-queued the key — that
                    # entry covers this retry, but it must not downgrade
                    # an observed-bitrot deep heal to shallow.
                    self._pending[key] = self._pending[key] or deep
                elif key not in self._pending:
                    # Episode over — drained, unhealable, or budget
                    # exhausted. Reset the counter either way so a
                    # FUTURE degraded write to this object gets a fresh
                    # retry budget (and the dict cannot grow unbounded).
                    self._attempts.pop(key, None)
            self.q.task_done()

    def wait_idle(self, timeout: float = 10.0) -> bool:
        """Testing hook: block until the queue drains (in-flight and
        requeued entries count until their heal actually completes)."""
        import time as _time

        deadline = _time.monotonic() + timeout
        while _time.monotonic() < deadline:
            with self._seen_lock:
                if (not self._pending and not self._retry
                        and not self._inflight and self.q.empty()):
                    return True
            _time.sleep(0.01)
        return False

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=2)


def _clone_fi(fi: FileInfo, index: int) -> FileInfo:
    out = fi.clone()
    out.erasure.index = index
    return out
