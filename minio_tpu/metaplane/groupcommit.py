"""DriveWAL — per-drive group commit over the WAL journal.

One committer thread per armed drive. Concurrent journal stores
(`LocalDrive._store_meta` / the inline-PUT single-journal fast path)
enqueue records and block on futures; the committer drains the queue,
appends the whole batch to the WAL with one `writev`, and fsyncs ONCE —
the futures resolve only after that fsync lands, so the S3 ack rides
exactly one shared fsync instead of a write+fsync+rename per request.

`meta.mp` files materialize asynchronously: after the fsync the batch
is published to an in-memory pending overlay (reads — `read_version`,
`read_xl`, `_load_meta` — consult it first, so read-your-write holds
the instant the future resolves), and the committer writes the actual
per-object journals when the queue goes idle (or when the backlog
exceeds `MTPU_WAL_MAX_PENDING`), *without* per-file fsync — durability
is the WAL until checkpoint. Checkpoint (WAL past `MTPU_WAL_MAX_BYTES`)
materializes everything, `os.sync()`s once, and truncates the journal.

Crash anatomy (proven by tests/test_metaplane.py + the armed chaos
storm):

- SIGKILL before the batch fsync — the WAL tail is torn; `wal.scan`
  stops before it; the writes were never acked and are legally lost.
- SIGKILL after fsync, before materialize — replay on next mount folds
  the WAL and rewrites every key's journal bit-exact; acked writes
  survive.
- SIGKILL mid-checkpoint — the WAL still holds every record until the
  post-sync truncate, and replay is idempotent.

Error discipline: an append/fsync failure marks the WAL broken, fails
the batch's futures with FaultyDisk (the caller's quorum accounting
treats the drive as failed), and subsequent submits fail fast. A
materialize failure leaves the record pending (still served from
memory, still durable in the WAL) and blocks checkpoint truncation.
"""

from __future__ import annotations

import os
import queue
import shutil
import threading
import time
from collections import OrderedDict
from concurrent.futures import Future

from minio_tpu import metaplane, obs, qos
from minio_tpu.metaplane import wal as walfmt
from minio_tpu.obs import flight
from minio_tpu.utils import admission
from minio_tpu.utils import errors as se

_COMMITS = obs.counter(
    "minio_tpu_metaplane_commits_total",
    "Journal records group-committed through the per-drive WAL",
    ("drive",))
_FSYNCS = obs.counter(
    "minio_tpu_metaplane_fsyncs_total",
    "WAL fsyncs — commits/fsyncs is the live group-commit amortization",
    ("drive",))
_BATCH_FILL = obs.histogram(
    "minio_tpu_metaplane_batch_fill",
    "Records per WAL group commit",
    ("drive",),
    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256))
_WAL_BYTES = obs.gauge(
    "minio_tpu_metaplane_wal_bytes",
    "Current WAL journal size (truncates at checkpoint)",
    ("drive",))

_seq_lock = threading.Lock()
_seq = 0

# Same-process segment ownership: the single-writer contract is per
# PROCESS (the flock enforces it across processes), so a LocalDrive
# re-mounted over the same root in one process — the restart pattern
# every format/heal bootstrap uses — gracefully takes the segment over
# by closing its predecessor (drain + checkpoint + flock release)
# instead of refusing with a duplicate-owner error.
_live_mu = threading.Lock()
_live_by_path: dict = {}


def _wal_cost(item) -> int:
    """Byte cost of one WAL submit for QoS byte quotas: the serialized
    payload length (index 3 across every record shape; "single" nests
    the raw journal at payload[1])."""
    raw = item[3]
    if isinstance(raw, tuple):
        raw = raw[1] if len(raw) > 1 else None
    if raw is None:
        return 0
    try:
        return len(raw)
    except TypeError:
        return 0


def _next_seq() -> int:
    global _seq
    with _seq_lock:
        _seq += 1
        return _seq


class Entry:
    """One pending (committed-but-not-materialized) journal state.
    `raw is None` means the journal was deleted (tombstone). `blob`
    marks a raw sys-file record (REC_BLOB): `path` is then the file
    path itself and materialization writes the bytes verbatim — meta
    readers (`pending_entry`) never see blob entries and blob readers
    (`pending_blob`) never see journal entries."""

    __slots__ = ("lsn", "raw", "meta", "memo", "mt", "blob")

    def __init__(self, lsn: int, raw, meta, mt: float,
                 blob: bool = False):
        self.lsn = lsn
        self.raw = raw
        self.meta = meta
        self.memo: dict = {}
        self.mt = mt
        self.blob = blob

    @property
    def removed(self) -> bool:
        return self.raw is None


def replay(drive, wal_path: str) -> "tuple[int, int]":
    """Fold + apply a WAL left by a previous process; returns
    (applied, failed) record counts — the journal is truncated only
    when failed == 0. Runs on EVERY mount (armed or not): a crashed
    armed session's acked writes must converge regardless of the next
    boot's gate. The `mt` tiebreak guards the armed→unarmed→armed
    interleave: state written directly by an unarmed process is newer
    than the stale WAL record and wins."""
    final = walfmt.fold(wal_path)
    applied, failed = _apply_fold(drive, final)
    if failed == 0:
        walfmt.reset(wal_path)
    return applied, failed


def replay_all(drive, wal_dir: str) -> "tuple[int, int]":
    """Replay every ORPHANED journal segment under the drive's wal dir
    in one merged fold — the multi-worker mount path
    (docs/FRONTDOOR.md). Serialized across concurrently-booting workers
    by an exclusive flock on `.replay.lock`; segments whose owner
    process is STILL ALIVE (it holds an exclusive flock on its open
    segment fd for its whole life — released by the kernel even on
    SIGKILL) are skipped entirely: folding them would race the live
    committer, and resetting them would silently unlink the fd its
    durability rides on. Orphan segments are truncated only on a
    fully-applied fold, exactly like the single-segment contract."""
    import fcntl

    os.makedirs(wal_dir, exist_ok=True)
    lfd = _replay_lock(wal_dir)
    try:
        applied, failed, _orphans = _replay_orphans(drive, wal_dir)
        return applied, failed
    finally:
        try:
            fcntl.flock(lfd, fcntl.LOCK_UN)
        finally:
            os.close(lfd)


def _replay_lock(wal_dir: str) -> int:
    import fcntl

    lfd = os.open(os.path.join(wal_dir, ".replay.lock"),
                  os.O_CREAT | os.O_RDWR, 0o644)
    fcntl.flock(lfd, fcntl.LOCK_EX)
    return lfd


def _replay_orphans(drive, wal_dir: str) -> "tuple[int, int, list]":
    """Core of replay_all; caller holds the `.replay.lock` flock.
    Returns (applied, failed, orphan_paths) — orphans are kept on disk
    when failed > 0 so the caller can seed its overlay from them."""
    import fcntl

    orphan_fds: list[int] = []
    orphans: list[str] = []
    try:
        for p in walfmt.segment_paths(wal_dir):
            try:
                fd = os.open(p, os.O_RDWR)
            except OSError:
                continue
            try:
                fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
            except OSError:
                os.close(fd)  # live owner: leave the segment alone
                continue
            orphan_fds.append(fd)
            orphans.append(p)
        if not orphans:
            return 0, 0, []
        final = walfmt.fold_merged(orphans)
        applied, failed = _apply_fold(drive, final)
        if failed == 0:
            for p in orphans:
                walfmt.reset(p)
        return applied, failed, orphans
    finally:
        for fd in orphan_fds:
            try:
                os.close(fd)
            except OSError:
                continue


def _apply_fold(drive, final) -> "tuple[int, int]":
    """Write a replay fold back to the drive; (applied, failed)."""
    from minio_tpu.storage.xlmeta import XLMeta

    applied = 0
    failed = 0
    for (vol, path), rec in final.items():
        stat_err = False
        # REC_REMOVE_PREFIX never reaches a fold: fold()/fold_merged()
        # consume tombstones in-stream (they delete the keys they
        # cover and are dropped), so the dispatch below is total over
        # every record type a fold output can contain.
        # mtpu: allow(MTPU009)
        if rec.rtype in (walfmt.REC_REPL_INTENT, walfmt.REC_REPL_DONE):
            # Replication intents live in their own segment
            # (replication.wal, replayed by replication/journal.py —
            # never by the drive mount). One in a DRIVE journal is
            # misrouted; keep it (failed blocks truncation) rather
            # than guess at materialization.
            failed += 1
            continue
        blob = rec.rtype in (walfmt.REC_BLOB, walfmt.REC_BLOB_REMOVE)
        try:
            # Blob records tiebreak against the blob FILE's mtime; the
            # journal records against the meta.mp under the key.
            disk_mt = (drive._disk_blob_mt(vol, path) if blob
                       else drive._disk_meta_mt(vol, path))
        except se.StorageError:
            disk_mt = None  # unreadable/corrupt journal: the record wins
            stat_err = True
        if disk_mt is not None and disk_mt > rec.mt + 1e-9:
            continue  # disk is newer (unarmed-session write)
        if rec.rtype == walfmt.REC_BLOB:
            try:
                drive._store_blob_disk(vol, path, rec.raw)
                applied += 1
            except se.StorageError:
                failed += 1
            continue
        if rec.rtype == walfmt.REC_BLOB_REMOVE:
            try:
                drive._remove_blob_disk(vol, path)
                applied += 1
            except se.StorageError:
                failed += 1
            continue
        if rec.rtype == walfmt.REC_COMMIT:
            try:
                meta = XLMeta.parse(rec.raw)  # scan hands out real bytes
            # mtpu: allow(MTPU003) - a CRC-valid but unparseable record
            # is unrecoverable by construction; skipping it (rather than
            # wedging the mount) degrades to a missed write on ONE
            # drive, which quorum + heal absorb.
            except Exception:  # noqa: BLE001
                continue
            try:
                drive._store_meta_disk(vol, path, rec.raw,
                                       meta=meta, fsync=False)
                applied += 1
            except se.StorageError:
                failed += 1
                continue
        elif rec.rtype == walfmt.REC_REMOVE:
            if disk_mt is None and not stat_err:
                continue  # genuinely absent: nothing to remove
            # A corrupt/unreadable journal under an acked REMOVE still
            # gets removed (that IS the acked state); a transient stat
            # failure falls through too — a failing _remove_meta_disk
            # then counts as failed and keeps the WAL for the next
            # mount instead of truncating the record away.
            try:
                drive._remove_meta_disk(vol, path)
                applied += 1
            except se.StorageError:
                failed += 1
                continue
        else:
            # A record type this build does not understand (newer
            # writer, older reader). The old bare `else` treated it as
            # a REMOVE and would have DELETED metadata for it — count
            # it failed instead, which keeps the journal for a build
            # that can apply it (truncation requires failed == 0).
            failed += 1
            continue
    if applied:
        os.sync()  # one barrier instead of a per-file fsync storm
    # Only a fully-applied journal may truncate (callers enforce): a
    # record that could not be written back (full/failing disk at
    # mount) is an ACKED state the WAL must keep carrying.
    return applied, failed


class DriveWAL:
    """Group-commit engine for one LocalDrive (see module docstring)."""

    def __init__(self, drive):
        self.drive = drive
        self._dir = os.path.join(drive.root, drive.sys_volume(), "wal")
        # Single-writer ownership under the multi-process front door:
        # each worker journals into its own segment; replay folds all.
        seg = metaplane.wal_segment()
        self.path = os.path.join(
            self._dir, f"journal.{seg}.wal" if seg else "journal.wal")
        os.makedirs(self._dir, exist_ok=True)
        self._max_bytes = metaplane.wal_max_bytes()
        self._max_pending = metaplane.wal_max_pending()
        self._max_batch = metaplane.wal_max_batch()
        # Test-only crash window: hold the committer this long before
        # each batch fsync so a harness can land a real SIGKILL between
        # append and fsync (tests/test_metaplane.py crash matrix).
        self._test_hold_fsync = float(
            os.environ.get("MTPU_WAL_TEST_HOLD_FSYNC_S", "0") or 0)
        # Lazy mode: never materialize between checkpoints (reads serve
        # from the pending overlay). The crash matrix uses it to pin the
        # fsynced-but-not-materialized state; also a valid operating
        # point for pure write bursts.
        self._lazy = os.environ.get("MTPU_WAL_LAZY_MATERIALIZE", "") == "1"
        # Multi-worker coherence (docs/FRONTDOOR.md): sibling workers
        # read through the filesystem, so every batch materializes
        # before its futures resolve (no per-file fsync — the ack still
        # rides exactly one WAL fsync) and the per-key LSN signature is
        # meaningless across processes (key_sig returns None; the set
        # cache falls back to stat triples, which eager materialization
        # keeps current).
        self._multi = not metaplane.single_owner()
        self._eager = metaplane.eager_materialize()

        # Replay-then-claim under ONE replay lock: fold every orphaned
        # segment, then open + flock our own before anyone else's
        # replay could mistake it for an orphan and truncate it out
        # from under the fd (the flock is the liveness mark replay_all
        # keys on; the kernel drops it even on SIGKILL).
        import fcntl

        # In-process predecessor (re-mount over the same root): close
        # it BEFORE taking the replay lock — its committer may need a
        # flush that briefly touches the same drive, and its released
        # flock is what lets the claim below succeed.
        with _live_mu:
            prior = _live_by_path.pop(self.path, None)
        if prior is not None:
            prior_wal = prior()
            if prior_wal is not None and not prior_wal._closed:
                prior_wal.close()

        replay_failed = 0
        replay_kept: list = []
        lfd = _replay_lock(self._dir)
        try:
            _applied, replay_failed, replay_kept = _replay_orphans(
                drive, self._dir)
            self._fd = os.open(self.path,
                               os.O_WRONLY | os.O_APPEND | os.O_CREAT,
                               0o644)
            try:
                fcntl.flock(self._fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
            except OSError:
                os.close(self._fd)
                raise se.FaultyDisk(
                    f"wal segment {self.path} is owned by a live "
                    "process (duplicate worker id?)") from None
        finally:
            try:
                fcntl.flock(lfd, fcntl.LOCK_UN)
            finally:
                os.close(lfd)
        if os.fstat(self._fd).st_size == 0:
            os.write(self._fd, walfmt.MAGIC)
            os.fsync(self._fd)
        self._bytes = os.fstat(self._fd).st_size

        # Admission queue: plain bounded queue, or a tenant-fair DRR
        # queue when the QoS plane is armed (MTPU_QOS=1). The tenant
        # key rides the item's Future (attached in _submit, like
        # mtpu_fctx); byte quotas meter the serialized payload — the
        # blob lane's large sys-files count at full weight. flush/close
        # barriers are control items: admitted unconditionally, and the
        # fair queue releases them only after everything enqueued
        # before them, preserving the flush contract under reordering.
        # Tombstones are ordering FENCES: replay's fold() resolves
        # dominance by WAL file order, so a remove_prefix/remove/
        # blob_remove reordered across tenant lanes would resurrect an
        # rmtree'd journal (tombstone written before an earlier commit)
        # or replay-delete a fresh one (later commit written before the
        # tombstone) — the fence pins file order to submit order there.
        self._q = qos.plane_queue(
            "metaplane", metaplane.wal_queue_depth(),
            tenant_of=lambda it: getattr(it[-1], "mtpu_tenant", None),
            cost_of=_wal_cost,
            is_control=lambda it: it[0] in ("flush", "close"),
            is_barrier=lambda it: it[0] in ("remove_prefix", "remove",
                                            "blob_remove"))
        self._mu = threading.Lock()  # pending overlay + key lsn map
        self._pending: "OrderedDict[tuple[str, str], Entry]" = OrderedDict()
        self._key_lsn: "OrderedDict[tuple[str, str], int]" = OrderedDict()
        self._key_lsn_cap = 65536
        # Blob keys that may still have a record in the WAL (cleared at
        # checkpoint — a truncated WAL cannot resurrect anything). None
        # = cap exceeded: "may exist" degrades to "always forget".
        self._blob_keys: "set | None" = set()
        self._blob_keys_cap = 65536
        self._lsn = 0
        self._broken: str | None = None
        self._closed = False
        self._trash: list[str] = []
        if replay_failed:
            # Replay could not write some acked records back (full or
            # flaky disk at mount) and kept the journal: seed the whole
            # fold into the pending overlay — reads serve the acked
            # state, drains retry materialization, and checkpoint stays
            # blocked until every record lands. Seed from the KEPT
            # orphan segments only — live siblings' segments are their
            # owners' to serve.
            for (vol, path), rec in walfmt.fold_merged(
                    replay_kept).items():
                # Not a dispatch gap: REC_REMOVE seeds raw=None (a
                # pending removal Entry) through the else by design,
                # and REC_REMOVE_PREFIX cannot appear in a fold —
                # fold_merged consumes tombstones in-stream.
                # mtpu: allow(MTPU009)
                if rec.rtype in (walfmt.REC_REPL_INTENT,
                                 walfmt.REC_REPL_DONE):
                    # Misrouted replication intent (its home is the
                    # replication.wal segment): it must not seed the
                    # drive overlay as a phantom journal entry.
                    continue
                self._lsn += 1
                blob = rec.rtype in (walfmt.REC_BLOB,
                                     walfmt.REC_BLOB_REMOVE)
                self._pending[(vol, path)] = Entry(
                    self._lsn,
                    rec.raw if rec.rtype in (walfmt.REC_COMMIT,
                                             walfmt.REC_BLOB) else None,
                    None, rec.mt, blob=blob)
                if not blob:
                    self._key_lsn[(vol, path)] = self._lsn

        self._c_commits = _COMMITS.labels(drive=drive.root)
        self._c_fsyncs = _FSYNCS.labels(drive=drive.root)
        self._h_fill = _BATCH_FILL.labels(drive=drive.root)
        self._g_bytes = _WAL_BYTES.labels(drive=drive.root)
        self._g_bytes.set(self._bytes)

        import weakref

        with _live_mu:
            _live_by_path[self.path] = weakref.ref(self)

        self._thread = threading.Thread(
            target=self._run, daemon=True,
            name=f"mtpu-metaplane-{_next_seq()}")
        self._thread.start()

    # ---------- submission (request threads) ----------

    def _bump_lsn(self, key: tuple[str, str]) -> int:
        with self._mu:
            self._lsn += 1
            self._key_lsn[key] = self._lsn
            self._key_lsn.move_to_end(key)
            while len(self._key_lsn) > self._key_lsn_cap:
                self._key_lsn.popitem(last=False)
            return self._lsn

    def _submit(self, item) -> Future:
        if self._broken is not None:
            raise se.FaultyDisk(f"wal broken: {self._broken}")
        if self._closed:
            raise se.FaultyDisk("wal closed")
        # Critical-path attribution rides the Future itself (every
        # submit shape ends in one): the committer thread reads it back
        # after the covering fsync to stamp the submitting request's
        # timeline and link the group's member trace ids. Attached
        # BEFORE enqueue — the committer may drain the item immediately.
        tid = obs.trace_id()
        tl = flight.current()
        if tid is not None or tl is not None:
            item[-1].mtpu_fctx = (tid, tl, time.perf_counter())
        tenant = qos.current_key()
        if tenant != qos.UNATTRIBUTED:
            item[-1].mtpu_tenant = tenant
        try:
            self._q.put_nowait(item)
        except queue.Full as e:
            # Unified admission: a full WAL queue sheds exactly like a
            # full dataplane lane — OperationTimedOut -> 503 SlowDown,
            # one shared shed family (utils/admission.py). Quorum
            # reducers raise the dominant error, so a set whose drives
            # all shed surfaces SlowDown, never a 500. A QoS
            # token-bucket reject is the same wire contract, distinct
            # cause slug.
            if isinstance(e, qos.QuotaFull):
                raise admission.shed(
                    "metaplane", "tenant_quota",
                    "tenant over wal rate quota") from None
            raise admission.shed(
                "metaplane", "wal_full",
                "wal commit queue full (backpressure)") from None
        return item[-1]

    def submit_commit(self, volume: str, path: str, raw, meta) -> Future:
        """Enqueue a full-journal store; resolves after the covering
        WAL fsync. `raw` is the serialized journal (bytes/memoryview,
        not copied); `meta` the parsed XLMeta (seeds the read overlay)."""
        self.drive._note_journal_key(volume, path)
        lsn = self._bump_lsn((volume, path))
        mt = meta.latest_mt if meta is not None else time.time()
        return self._submit(
            ("commit", volume, path, raw, meta, mt, lsn, Future()))

    def submit_remove(self, volume: str, path: str) -> Future:
        """Enqueue a journal deletion (last version removed)."""
        lsn = self._bump_lsn((volume, path))
        return self._submit(
            ("remove", volume, path, None, None, time.time(), lsn, Future()))

    def _bump_lsn_only(self) -> int:
        """LSN for a blob record: orders overlay entries without
        entering the per-key signature map (blobs have no set-cache
        signatures to serve)."""
        with self._mu:
            self._lsn += 1
            return self._lsn

    def submit_blob(self, volume: str, path: str, raw) -> Future:
        """Enqueue a raw sys-file store (multipart part journal,
        scanner checkpoint, sys-config doc) — the blob lane of the
        group commit: the ack rides the same shared WAL fsync as
        journal commits, and the file materializes on idle ticks with
        NO per-file fsync. `raw` is bytes/memoryview, not copied."""
        if not isinstance(raw, bytes):
            # Blob docs are small control files (json/msgpack) and in
            # practice arrive as bytes already; real bytes keep the
            # overlay directly servable by read_all and its callers.
            raw = memoryview(raw).tobytes()
        lsn = self._bump_lsn_only()
        with self._mu:
            if self._blob_keys is not None:
                self._blob_keys.add((volume, path))
                if len(self._blob_keys) > self._blob_keys_cap:
                    self._blob_keys = None  # superset tracking lost
        return self._submit(
            ("blob", volume, path, raw, None, time.time(), lsn, Future()))

    def has_blob_state(self, volume: str, path: str) -> bool:
        """True when the WAL may still carry a record for this blob
        (pending overlay, or a record appended since the last
        checkpoint) — the gate for forget_blob, so plain-file deletes
        of never-journaled files cost nothing."""
        key = (volume, path)
        with self._mu:
            ent = self._pending.get(key)
            if ent is not None and ent.blob:
                return True
            return self._blob_keys is None or key in self._blob_keys

    def forget_blob(self, volume: str, path: str) -> bool:
        """A blob file was deleted out-of-band (delete_sys_config, part
        cleanup): drop its overlay entry and log a BLOB_REMOVE so
        replay cannot resurrect a file whose COMMIT record is still in
        the WAL. Fire-and-forget like forget_key. Returns True when a
        LIVE pending entry was dropped — the caller's filesystem
        remove may then legitimately find no file on disk."""
        key = (volume, path)
        dropped = False
        with self._mu:
            ent = self._pending.get(key)
            if ent is not None and ent.blob:
                dropped = not ent.removed
                del self._pending[key]
        try:
            self._submit(("blob_remove", volume, path, None, None,
                          time.time(), self._bump_lsn_only(), Future()))
        except (se.StorageError, se.OperationTimedOut):
            pass  # broken/full: the stale copy loses the election
        return dropped

    def submit_single(self, volume: str, path: str, fi, raw, meta,
                      defer_reclaim: bool) -> Future:
        """Enqueue a single-journal store (the inline-PUT commit) whose
        PREWORK — vol stat, displaced-version stash, merge fallback —
        runs in the committer, so this call is pure memory: request
        threads never touch the drive on the submit side (no pool hop
        needed for hang isolation; a hung drive surfaces as a future
        the caller's deadline'd await stamps). The future resolves to
        the reclaim token (or raises the per-drive error).

        Same-key commits are serialized by the erasure layer's
        namespace lock, so a batch never carries two singles for one
        key whose prework could read around each other."""
        # Evaluated BEFORE noting the key: proves to the committer that
        # no journal predates this record, skipping its existence stat.
        assume_new = self.drive.journal_known_absent(volume, path)
        self.drive._note_journal_key(volume, path)
        lsn = self._bump_lsn((volume, path))
        mt = meta.latest_mt if meta is not None else time.time()
        return self._submit(
            ("single", volume, path, (fi, raw, defer_reclaim, assume_new),
             meta, mt, lsn, Future()))

    def flush(self, timeout: float = 60.0) -> None:
        """Barrier: every record enqueued before this call is durable
        AND materialized on return — listings/walks that read `meta.mp`
        straight off the filesystem call this first. Cheap when idle."""
        with self._mu:
            idle = not self._pending
        if idle and self._q.empty():
            return
        if self._broken is not None or self._closed:
            self._drain_materialize(force=True)
            return
        fut: Future = Future()
        try:
            self._q.put(("flush", fut), timeout=timeout)
        except queue.Full:
            raise admission.shed(
                "metaplane", "wal_flush_full",
                "wal commit queue full (backpressure)") from None
        fut.result(timeout=timeout)

    def forget_subtree(self, volume: str, prefix: str) -> None:
        """A recursive filesystem delete (session/tmp rmtree, volume
        force-delete) removed journals out-of-band: drop pending overlay
        entries AND per-key signature LSNs under the prefix (a stale
        ("w", lsn) signature must not keep validating a set-cache entry
        for a destroyed journal), and append one REMOVE_PREFIX tombstone
        so replay drops every earlier WAL record there — including
        records already materialized but not yet checkpointed.
        Fire-and-forget — the rmtree itself carries the operation's
        (pre-existing) durability semantics."""
        def under(k):
            return k[0] == volume and (not prefix or k[1] == prefix
                                       or k[1].startswith(prefix + "/"))

        with self._mu:
            for k in [k for k in self._pending if under(k)]:
                del self._pending[k]
            for k in [k for k in self._key_lsn if under(k)]:
                del self._key_lsn[k]
        try:
            self._submit(("remove_prefix", volume, prefix, None, None,
                          time.time(), 0, Future()))
        except (se.StorageError, se.OperationTimedOut):
            return  # broken/full: a replay resurrection here is the
            # dangling-object case deep heal already purges

    def forget_key(self, volume: str, path: str) -> None:
        """Exact-key variant of forget_subtree for a single journal
        removed out-of-band (never touches nested keys like 'a/b/c'
        when 'a/b' is forgotten)."""
        with self._mu:
            self._pending.pop((volume, path), None)
        try:
            self.submit_remove(volume, path)
        except (se.StorageError, se.OperationTimedOut):
            return  # as above: heal purges the dangling remnant

    # ---------- read overlay (request threads) ----------

    def pending_entry(self, volume: str, path: str) -> Entry | None:
        """The committed-but-unmaterialized state for a key, or None
        when disk is authoritative. `entry.removed` marks deletion.
        Blob entries are invisible here (journal readers only)."""
        with self._mu:
            ent = self._pending.get((volume, path))
            return None if ent is not None and ent.blob else ent

    def pending_blob(self, volume: str, path: str) -> Entry | None:
        """The committed-but-unmaterialized state of a raw sys file
        (read_all's overlay), or None when disk is authoritative."""
        with self._mu:
            ent = self._pending.get((volume, path))
            return ent if ent is not None and ent.blob else None

    def key_sig(self, volume: str, path: str):
        """Logical journal signature while armed: every mutation bumps
        the key's LSN at submit, so ("w", lsn) names the journal state
        exactly (one owning process per drive by contract). None once
        the key ages out of the LRU — callers fall back to stat — and
        always None under a multi-worker front door, where a sibling's
        commits move state this process's LSNs never see."""
        if self._multi:
            return None
        with self._mu:
            lsn = self._key_lsn.get((volume, path))
        return None if lsn is None else ("w", lsn)

    # ---------- committer ----------

    def _run(self) -> None:
        while True:
            try:
                item = self._q.get(timeout=0.5)
            except queue.Empty:
                if self._closed:
                    return
                self._drain_materialize()
                continue
            batch = [item]
            while len(batch) < self._max_batch:
                try:
                    batch.append(self._q.get_nowait())
                except queue.Empty:
                    break
            close_fut = None
            flushes: list[Future] = []
            recs: list[tuple] = []
            for it in batch:
                if it[0] == "flush":
                    flushes.append(it[1])
                elif it[0] == "close":
                    close_fut = it[1]
                else:
                    recs.append(it)
            if recs:
                self._commit_batch(recs)
            with self._mu:
                backlog = len(self._pending)
            # Materialize on IDLE (the queue-empty timeout tick above),
            # on barriers, and on backlog pressure — never eagerly after
            # every batch: per-key journal files cost ~5 filesystem
            # round-trips each, and paying them inside a burst would put
            # the deferred work right back on the commit path's medium.
            # A burst therefore rides the WAL at writev+fsync cost and
            # the backlog drains in the gaps (bounded by max_pending).
            if flushes or close_fut is not None \
                    or backlog > self._max_pending:
                self._drain_materialize(force=True)
            for f in flushes:
                f.set_result(None)
            if self._bytes > self._max_bytes and self._broken is None:
                self._checkpoint()
            if close_fut is not None:
                self._checkpoint()
                close_fut.set_result(None)
                return

    def _commit_batch(self, recs: list[tuple]) -> None:
        # Resolve "single" records' prework (vol stat, displaced-state
        # stash, merge fallback) HERE in the committer — the submit side
        # stayed pure memory. A prework failure fails only that record's
        # future; the rest of the batch commits.
        staged: list[tuple] = []  # (rtype, vol, path, raw, meta, mt,
        #                            lsn, fut, token)
        for kind, vol, path, payload, meta, mt, lsn, fut in recs:
            if kind == "single":
                fi, raw, defer_reclaim, assume_new = payload
                try:
                    self.drive._stat_vol_cached(vol)
                    token, merged = self.drive._single_prework(
                        vol, path, fi, defer_reclaim,
                        assume_new=assume_new, defer_fs=True)
                except Exception as e:  # noqa: BLE001 - per-record: the
                    # error travels to exactly the caller whose commit
                    # it is (quorum counts the drive as failed)
                    fut.set_exception(e if isinstance(e, se.StorageError)
                                      else se.FaultyDisk(str(e)))
                    continue
                if merged is not None:
                    meta = merged
                    raw = merged.serialize()
                    mt = merged.latest_mt
                staged.append((walfmt.REC_COMMIT, vol, path, raw, meta,
                               mt, lsn, fut, token))
            elif kind == "commit":
                staged.append((walfmt.REC_COMMIT, vol, path, payload,
                               meta, mt, lsn, fut, None))
            elif kind == "remove_prefix":
                staged.append((walfmt.REC_REMOVE_PREFIX, vol, path, b"",
                               None, mt, lsn, fut, None))
            elif kind == "blob":
                staged.append((walfmt.REC_BLOB, vol, path, payload,
                               None, mt, lsn, fut, None))
            elif kind == "blob_remove":
                staged.append((walfmt.REC_BLOB_REMOVE, vol, path, b"",
                               None, mt, lsn, fut, None))
            else:
                staged.append((walfmt.REC_REMOVE, vol, path, b"", None,
                               mt, lsn, fut, None))
        if not staged:
            return
        frames = [walfmt.frame_record(rtype, mt, vol, path, raw)
                  for rtype, vol, path, raw, _m, mt, _l, _f, _t in staged]
        try:
            # The committer's own span around the shared append + fsync;
            # the members' timeline entries are the stamps below.
            with flight.span("wal_fsync", "metaplane", timeline=False,
                             members=len(staged)):
                n = walfmt.append_records(self._fd, frames)
                if self._test_hold_fsync:
                    time.sleep(self._test_hold_fsync)
                os.fsync(self._fd)
        except OSError as e:
            self._broken = str(e)
            err = se.FaultyDisk(f"wal append/fsync failed: {e}")
            for rec in staged:
                rec[7].set_exception(err)
            return
        self._bytes += n
        self._g_bytes.set(self._bytes)
        self._c_fsyncs.inc()
        self._c_commits.inc(len(staged))
        self._h_fill.observe(len(staged))
        # Attribution: the fsync above is the durability point — stamp
        # each member request's timeline with its submit→fsync wait and
        # link the group's members in one `batch` record.
        t_ack = time.perf_counter()
        members = []
        tenants = set()
        for rec in staged:
            ten = getattr(rec[7], "mtpu_tenant", None)
            if ten:
                tenants.add(ten)
            fctx = getattr(rec[7], "mtpu_fctx", None)
            if fctx is None:
                continue
            tid, tl, t_sub = fctx
            if tid:
                members.append(tid)
            if tl is not None:
                tl.stamp("wal_fsync_wait", t_ack - t_sub, "metaplane",
                         end=t_ack)
        if obs.has_subscribers():
            obs.publish({"type": "batch", "plane": "metaplane",
                         "records": len(staged), "members": members,
                         "tenants": sorted(tenants),
                         "time": time.time()})
        # Publish the overlay BEFORE resolving futures: the instant the
        # ack fires, a read must see the new state. Entries carry LSNs
        # so a newer published state is never downgraded.
        with self._mu:
            for rtype, vol, path, raw, meta, mt, lsn, _fut, _tok in staged:
                # REC_REPL_INTENT/REC_REPL_DONE never enter the commit
                # queue — replication/journal.py appends them to its
                # own segment, never through DriveWAL staging.
                # mtpu: allow(MTPU009)
                if rtype == walfmt.REC_REMOVE_PREFIX:
                    # Drop anything that slipped into the overlay for
                    # the destroyed subtree between forget and commit.
                    pre = path
                    for k in [k for k in self._pending
                              if k[0] == vol
                              and (not pre or k[1] == pre
                                   or k[1].startswith(pre + "/"))]:
                        del self._pending[k]
                    continue
                key = (vol, path)
                cur = self._pending.get(key)
                if cur is not None and cur.lsn > lsn:
                    continue
                blob = rtype in (walfmt.REC_BLOB, walfmt.REC_BLOB_REMOVE)
                self._pending[key] = Entry(
                    lsn,
                    raw if rtype in (walfmt.REC_COMMIT, walfmt.REC_BLOB)
                    else None,
                    meta, mt, blob=blob)
                self._pending.move_to_end(key)
        if self._eager:
            # Cross-process read-your-write: sibling workers have no
            # view of this overlay, so the journals must be on the
            # filesystem before the ack fires (page-cache writes only —
            # durability stays the WAL fsync above).
            self._drain_materialize(force=True)
        for rec in staged:
            rec[7].set_result(rec[8])

    def note_trash(self, path: str) -> None:
        """A displaced data dir parked by an O(1) rename during commit
        prework; the tree is destroyed at the next idle drain instead
        of head-of-line blocking the committer's batch (a multi-GiB
        rmtree inside the commit cycle would stall every concurrent
        group commit on this drive past the meta deadline)."""
        self._trash.append(path)

    def _drain_trash(self) -> None:
        while self._trash:
            shutil.rmtree(self._trash.pop(), ignore_errors=True)

    def _drain_materialize(self, force: bool = False) -> None:
        """Write every currently-pending journal to its meta.mp (no
        per-file fsync — the WAL is durability until checkpoint). One
        pass over a snapshot: entries that fail stay pending (still
        served from memory, still in the WAL) and pin the checkpoint;
        entries superseded mid-write keep their newer overlay."""
        self._drain_trash()
        if self._lazy and not (force or self._closed):
            return
        with self._mu:
            snapshot = list(self._pending.items())
        for key, entry in snapshot:
            vol, path = key
            try:
                if entry.blob:
                    if entry.removed:
                        self.drive._remove_blob_disk(vol, path)
                    else:
                        self.drive._store_blob_disk(vol, path, entry.raw)
                elif entry.removed:
                    self.drive._remove_meta_disk(vol, path)
                else:
                    self.drive._store_meta_disk(
                        vol, path, entry.raw, meta=entry.meta, fsync=False)
            except se.StorageError:
                continue  # stays pending; checkpoint refuses to truncate
            with self._mu:
                if self._pending.get(key) is entry:
                    del self._pending[key]

    def _checkpoint(self) -> None:
        """Materialize everything, one sync barrier, truncate the WAL."""
        self._drain_materialize(force=True)
        with self._mu:
            if self._pending:
                return  # a stuck materialization pins the WAL
        try:
            os.sync()
            os.ftruncate(self._fd, 0)
            os.write(self._fd, walfmt.MAGIC)
            os.fsync(self._fd)
        except OSError as e:
            self._broken = str(e)
            return
        self._bytes = len(walfmt.MAGIC)
        self._g_bytes.set(self._bytes)
        with self._mu:
            # Truncated WAL cannot resurrect any blob: forget tracking
            # restarts empty (and recovers from a prior cap overflow).
            self._blob_keys = set()

    # ---------- lifecycle ----------

    def abandon(self) -> None:
        """Test-only SIGKILL simulation: stop the committer dead and
        release the segment flock WITHOUT materializing, checkpointing
        or resolving anything — on-disk state is exactly what a real
        crash leaves, and the segment reads as orphaned to the next
        mount's replay (a live committer's flock otherwise correctly
        blocks replay from folding a file mid-write)."""
        self._closed = True
        self._broken = "abandoned (test crash)"
        self._thread.join(5.0)
        try:
            os.close(self._fd)
        except OSError:
            pass

    def close(self, timeout: float = 30.0) -> None:
        """Drain, checkpoint, stop the committer (tests; process-lived
        drives just die with their daemon)."""
        if self._closed:
            return
        try:
            fut: Future = Future()
            self._q.put(("close", fut), timeout=timeout)
            self._closed = True
            fut.result(timeout=timeout)
        # mtpu: allow(MTPU003) - teardown: a broken WAL already failed
        # its waiters with typed errors; close only needs the committer
        # thread stopped.
        except Exception:  # noqa: BLE001
            self._closed = True
        self._thread.join(timeout=timeout)
        try:
            os.close(self._fd)
        except OSError:
            return
