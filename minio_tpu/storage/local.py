"""LocalDrive — POSIX implementation of StorageAPI.

Layout under the drive root (role-equivalent of xl-storage,
cmd/xl-storage.go:90, with our own format):

    <root>/.mtpu.sys/format.json      drive identity (format v1)
    <root>/.mtpu.sys/tmp/<uuid>/      staging area for in-flight writes
    <root>/<volume>/<object-key>/meta.mp          version journal
    <root>/<volume>/<object-key>/<data-dir>/part.N  bitrot-framed shards

Commit protocol: shards stream into the tmp area, then rename_data moves the
data dir into the object dir and rewrites the journal — rename is the atomic
commit point per drive, exactly the reference's tmp->rename discipline
(cmd/xl-storage.go:1780). fsync on data files and parent dirs at commit.
"""

from __future__ import annotations

import errno
import json
import os
import shutil
import threading
import time
import uuid
from collections import OrderedDict
from typing import BinaryIO, Iterable, Iterator

from minio_tpu import obs
from minio_tpu.dataplane import route
from minio_tpu.ops import bitrot
from minio_tpu.storage.api import (
    MARKER_GROUP_PAD,
    DiskInfo,
    StorageAPI,
    VolInfo,
    WalkEntry,
)
from minio_tpu.storage.fileinfo import FileInfo
from minio_tpu.storage.xlmeta import XLMeta
from minio_tpu.utils import errors as se

SYS_VOL = ".mtpu.sys"
META_FILE = "meta.mp"
FORMAT_FILE = "format.json"
FORMAT_VERSION = 1

_DIR_FSYNC_ERRORS = obs.counter(
    "minio_tpu_dir_fsync_errors_total",
    "Directory fsyncs that failed at a commit point (open or fsync "
    "error) — a pulled drive otherwise looks durably committed",
    ("drive",))


def _fsync_dir(path: str, drive: str = "") -> None:
    """Best-effort directory fsync at commit points. Failure stays
    best-effort (rename durability degrades to the filesystem's
    ordering), but it is COUNTED and traced — a drive yanked mid-commit
    must not be invisible."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError as e:
        _note_dir_fsync_error(drive or path, path, e)
        return
    try:
        os.fsync(fd)
    except OSError as e:
        _note_dir_fsync_error(drive or path, path, e)
    finally:
        os.close(fd)


def _note_dir_fsync_error(drive: str, path: str, err: OSError) -> None:
    _DIR_FSYNC_ERRORS.labels(drive=drive).inc()
    if obs.has_subscribers():
        obs.publish({"type": "storage", "time": time.time(),
                     "drive": drive, "op": "dir_fsync", "vol": "",
                     "path": path,
                     "error": f"{type(err).__name__}: {err}"})


class LocalDrive(StorageAPI):
    def __init__(self, root: str, endpoint: str = ""):
        self.root = os.path.abspath(root)
        self._endpoint = endpoint or self.root
        self._expected_id = ""
        # Stat-validated journal parse cache for the read path: key
        # (volume, path) -> ((st_ino, st_mtime_ns, st_size), XLMeta).
        # A hit replaces open+read+parse (~100us) with one stat (~2us);
        # the inode+mtime+size signature changes on every _store_meta
        # (tmp+rename creates a new inode), including writes by OTHER
        # processes sharing the drive, so staleness is impossible. Cached
        # XLMeta objects are only ever read (to_fileinfo); mutating paths
        # (write_metadata et al) parse fresh bytes.
        self._meta_cache: "OrderedDict[tuple[str, str], tuple]" = OrderedDict()
        self._meta_cache_cap = 16384
        self._mpath_cache: dict[tuple[str, str], str] = {}
        self._meta_cache_lock = threading.Lock()
        # Positive volume-existence TTL cache (WAL committer prework).
        self._vol_ok: dict[str, float] = {}
        # Fresh-volume key tracking: a volume THIS process created via
        # make_vol started empty, and every journal under it is created
        # through this drive (one owning process per drive by contract),
        # so `key not in set` PROVES no journal exists — the group-commit
        # prework skips the existence stat for new keys. The set is a
        # safe superset ("may exist"); None = tracking lost (cap hit),
        # absent vol = pre-existing volume. Ops: set add/contains are
        # GIL-atomic.
        self._fresh_vols: dict[str, "set | None"] = {}
        self._fresh_vol_cap = 1 << 17
        # EWMA of journal-store duration (write+fsync+rename): lets the
        # object layer choose serial fan-out for metadata writes on media
        # where the store is cheaper than a thread-pool dispatch (tmpfs,
        # NVMe with write cache) while keeping parallel fan-out on slow
        # fsync media. Unknown (no sample yet) reads as NOT fast.
        self._sync_ewma: float | None = None
        # Per-drive op latency + `storage` trace records — the shared
        # observer (pre-resolved histogram children, trace gated on
        # subscribers) keeps the hot-path cost at two clock reads + one
        # observe.
        self._observe_op = obs.drive_op_observer(self.root)
        try:
            os.makedirs(os.path.join(self.root, SYS_VOL, "tmp"), exist_ok=True)
        except OSError as e:
            raise se.DiskAccessDenied(str(e)) from e
        # Group-commit metadata plane (docs/METAPLANE.md): armed, every
        # journal store rides the per-drive WAL and one shared fsync.
        # Replay-on-mount runs even UNARMED when a previous (armed,
        # crashed) process left a journal — acked writes must converge
        # regardless of the next boot's gate.
        from minio_tpu import metaplane

        self._wal = None
        if metaplane.enabled():
            from minio_tpu.metaplane.groupcommit import DriveWAL

            self._wal = DriveWAL(self)  # replays any leftover journal
        else:
            wal_dir = os.path.join(self.root, SYS_VOL, "wal")
            from minio_tpu.metaplane import wal as walfmt

            if walfmt.segment_paths(wal_dir):
                from minio_tpu.metaplane import groupcommit

                groupcommit.replay_all(self, wal_dir)

    # ---------- identity ----------

    def _format_path(self) -> str:
        return os.path.join(self.root, SYS_VOL, FORMAT_FILE)

    def read_format(self) -> dict:
        # A missing ROOT means the drive is gone (unmounted/failed mount)
        # — that is FaultyDisk, never UnformattedDisk: heal_format must
        # not mistake an absent mount for a blank replacement and rebuild
        # the set onto the parent filesystem.
        if not os.path.isdir(self.root):
            raise se.FaultyDisk(f"drive root missing (unmounted?): {self.root}")
        try:
            with open(self._format_path(), "rb") as f:
                return json.load(f)
        except FileNotFoundError:
            raise se.UnformattedDisk(self.root) from None
        except (OSError, ValueError) as e:
            raise se.CorruptedFormat(str(e)) from e

    def write_format(self, fmt: dict) -> None:
        # A replaced/blank drive MOUNTED at this path has a root dir but
        # no skeleton — formatting creates the skeleton (live heal_format
        # path, reference HealFormat). A missing root is an absent drive:
        # refuse, or the format (and every healed shard after it) would
        # land on the parent filesystem.
        if not os.path.isdir(self.root):
            raise se.FaultyDisk(f"drive root missing (unmounted?): {self.root}")
        os.makedirs(os.path.join(self.root, SYS_VOL, "tmp"), exist_ok=True)
        tmp = self._format_path() + f".tmp.{uuid.uuid4().hex}"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(fmt, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self._format_path())
        _fsync_dir(os.path.dirname(self._format_path()), self.root)

    def disk_info(self) -> DiskInfo:
        st = os.statvfs(self.root)
        return DiskInfo(
            total=st.f_blocks * st.f_frsize,
            free=st.f_bavail * st.f_frsize,
            used=(st.f_blocks - st.f_bfree) * st.f_frsize,
            used_inodes=st.f_files - st.f_ffree,
            endpoint=self._endpoint,
            mount_path=self.root,
            id=self._safe_disk_id(),
        )

    def _safe_disk_id(self) -> str:
        try:
            return self.get_disk_id()
        except se.StorageError:
            return ""

    def get_disk_id(self) -> str:
        fmt = self.read_format()
        this = fmt.get("erasure", {}).get("this", "") or fmt.get("this", "")
        if self._expected_id and this != self._expected_id:
            raise se.InconsistentDisk(
                f"drive {self.root}: id {this!r} != expected {self._expected_id!r}"
            )
        return this

    def set_disk_id(self, disk_id: str) -> None:
        self._expected_id = disk_id

    def endpoint(self) -> str:
        return self._endpoint

    # ---------- path mapping ----------

    def _vol_dir(self, volume: str) -> str:
        if not volume or volume.startswith("/") or ".." in volume.split("/"):
            raise se.VolumeNotFound(volume)
        return os.path.join(self.root, volume)

    def _file_path(self, volume: str, path: str) -> str:
        parts = [p for p in path.split("/") if p not in ("", ".")]
        if any(p == ".." for p in parts):
            raise se.FileAccessDenied(path)
        return os.path.join(self._vol_dir(volume), *parts)

    # ---------- volumes ----------

    def make_vol(self, volume: str) -> None:
        d = self._vol_dir(volume)
        self._vol_ok.pop(volume, None)
        try:
            # mkdir, NOT makedirs: a missing drive root means the drive
            # is unmounted — creating it would put the volume (and every
            # shard after it) on the parent filesystem.
            os.mkdir(d)
            self._fresh_vols[volume] = set()
        except FileExistsError:
            raise se.VolumeExists(volume) from None
        except FileNotFoundError:
            if not os.path.isdir(self.root):
                raise se.FaultyDisk(
                    f"drive root missing (unmounted?): {self.root}"
                ) from None
            raise se.FaultyDisk(
                f"missing parent directory for volume {volume}") from None
        except OSError as e:
            raise se.FaultyDisk(str(e)) from e

    def list_vols(self) -> list[VolInfo]:
        out = []
        try:
            with os.scandir(self.root) as it:
                for entry in it:
                    if entry.is_dir() and entry.name != SYS_VOL:
                        out.append(VolInfo(entry.name, entry.stat().st_ctime))
        except OSError as e:
            raise se.FaultyDisk(str(e)) from e
        return sorted(out, key=lambda v: v.name)

    def stat_vol(self, volume: str) -> VolInfo:
        d = self._vol_dir(volume)
        try:
            st = os.stat(d)
        except FileNotFoundError:
            raise se.VolumeNotFound(volume) from None
        return VolInfo(volume, st.st_ctime)

    def _note_journal_key(self, volume: str, path: str) -> None:
        """Record that a journal may now exist at (volume, path) —
        called by every journal-creating path (WAL submit, disk store).
        Past the cap, tracking for the volume is dropped (None), never
        wrong."""
        s = self._fresh_vols.get(volume)
        if s is None:
            return
        if len(s) >= self._fresh_vol_cap:
            self._fresh_vols[volume] = None
            return
        s.add(path)

    def journal_known_absent(self, volume: str, path: str) -> bool:
        """True only when this process PROVABLY never created a journal
        at (volume, path) on a volume it created empty — lets the
        group-commit prework skip the existence stat for new keys.
        Never proven under a multi-worker front door: a sibling worker
        may have journaled the key through its own drive handle."""
        from minio_tpu import metaplane

        if not metaplane.single_owner():
            return False
        s = self._fresh_vols.get(volume)
        return s is not None and path not in s

    def _stat_vol_cached(self, volume: str) -> None:
        """Volume-existence check with a short positive TTL — the WAL
        committer's per-record guard. The erasure layer already fronts
        PUTs with its own 2s bucket cache, so the cross-process
        bucket-delete window this opens is one the request path
        accepts today; in-process delete_vol/make_vol invalidate."""
        now = time.monotonic()
        exp = self._vol_ok.get(volume)
        if exp is not None and exp > now:
            return
        self.stat_vol(volume)
        self._vol_ok[volume] = now + 2.0

    def delete_vol(self, volume: str, force: bool = False) -> None:
        d = self._vol_dir(volume)
        self._vol_ok.pop(volume, None)
        self._fresh_vols.pop(volume, None)
        if self._wal is not None:
            if force:
                self._wal.forget_subtree(volume, "")
            else:
                # The emptiness check below is the FILESYSTEM's rmdir:
                # acked journals still in the group-commit overlay must
                # materialize first or a non-empty bucket would delete.
                self._wal.flush()
        try:
            if force:
                shutil.rmtree(d)
            else:
                os.rmdir(d)
        except FileNotFoundError:
            raise se.VolumeNotFound(volume) from None
        except OSError as e:
            if e.errno == errno.ENOTEMPTY:
                raise se.VolumeNotEmpty(volume) from None
            raise se.FaultyDisk(str(e)) from e

    # ---------- plain files ----------

    def write_all(self, volume: str, path: str, data: bytes) -> None:
        self.stat_vol(volume)
        fp = self._file_path(volume, path)
        os.makedirs(os.path.dirname(fp), exist_ok=True)
        tmp = fp + f".tmp.{uuid.uuid4().hex}"
        try:
            with open(tmp, "wb") as f:
                f.write(data)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, fp)
        except OSError as e:
            raise se.FaultyDisk(str(e)) from e

    def write_all_async(self, volume: str, path: str, data: bytes):
        """Two-phase write_all through the group-commit plane: the
        returned future resolves after the shared WAL fsync covering
        the record (durability is the WAL, not a per-file fsync); the
        file itself materializes on idle ticks / flush barriers. None
        when the WAL is not armed — callers fall back to write_all.
        This is the blob lane sys-file traffic rides (multipart part
        journals, scanner checkpoints, sys-config docs) so background
        churn stops paying a foreground fsync per file per drive."""
        if self._wal is None:
            return None
        self.stat_vol(volume)
        self._file_path(volume, path)  # validate before journaling
        t0 = time.perf_counter()
        fut = self._wal.submit_blob(volume, path, data)

        def _done(f, t0=t0):
            # The callback runs in the committer thread; ctx_wrap binds
            # the SUBMITTING request's trace context so the storage
            # record lands in the right trace.
            self._note_sync(time.perf_counter() - t0)
            self._observe_op("write_all_async", t0, volume, path,
                             f.exception())

        fut.add_done_callback(obs.ctx_wrap(_done))
        return fut

    def _store_blob_disk(self, volume: str, path: str, raw) -> None:
        """Materialize a WAL blob record: tmp+rename, NO fsync (the WAL
        carries durability until checkpoint)."""
        fp = self._file_path(volume, path)
        os.makedirs(os.path.dirname(fp), exist_ok=True)
        tmp = fp + f".tmp.{uuid.uuid4().hex}"
        try:
            with open(tmp, "wb") as f:
                f.write(raw)
            os.replace(tmp, fp)
        except OSError as e:
            raise se.FaultyDisk(str(e)) from e

    def _remove_blob_disk(self, volume: str, path: str) -> None:
        fp = self._file_path(volume, path)
        try:
            os.remove(fp)
        except FileNotFoundError:
            pass
        except OSError as e:
            raise se.FaultyDisk(str(e)) from e
        self._prune_empty_parents(os.path.dirname(fp), volume)

    def _disk_blob_mt(self, volume: str, path: str) -> "float | None":
        """mtime of the ON-DISK blob file, None when absent — the WAL
        replay tiebreak for blob records (mirrors _disk_meta_mt)."""
        try:
            return os.stat(self._file_path(volume, path)).st_mtime
        except (FileNotFoundError, NotADirectoryError):
            return None
        except OSError as e:
            raise se.FaultyDisk(str(e)) from e

    def read_all(self, volume: str, path: str) -> bytes:
        if self._wal is not None:
            pe = self._wal.pending_blob(volume, path)
            if pe is not None:
                # Committed-but-unmaterialized blob: the overlay IS the
                # file (read-your-write the instant the group fsync
                # acks — multipart part elections, scanner resume).
                if pe.removed:
                    raise se.FileNotFound(f"{volume}/{path}")
                return pe.raw
        fp = self._file_path(volume, path)
        try:
            with open(fp, "rb") as f:
                return f.read()
        except FileNotFoundError:
            raise se.FileNotFound(f"{volume}/{path}") from None
        except IsADirectoryError:
            raise se.IsNotRegular(f"{volume}/{path}") from None
        except OSError as e:
            raise se.FaultyDisk(str(e)) from e

    def delete(self, volume: str, path: str, recursive: bool = False) -> None:
        fp = self._file_path(volume, path)
        wal_blob_pending = False
        if self._wal is not None:
            # The tree (or journal) vanishes out-of-band: drop any WAL
            # overlay underneath it and log REMOVEs so replay cannot
            # resurrect journals this rmtree destroys.
            if recursive:
                self._wal.forget_subtree(volume, path)
            elif os.path.basename(fp) == META_FILE:
                # Exact key only: forgetting the subtree would tombstone
                # NESTED keys ('a/b/c' under 'a/b') this delete never
                # touches.
                self._wal.forget_key(volume, os.path.dirname(path))
            elif self._wal.has_blob_state(volume, path):
                # A blob whose COMMIT record may still sit in the WAL
                # (part journal, sys-config doc): tombstone it so
                # replay cannot resurrect the deleted file. Plain files
                # that never rode the blob lane skip this entirely.
                wal_blob_pending = self._wal.forget_blob(volume, path)
        try:
            if recursive:
                shutil.rmtree(fp)
            elif os.path.isdir(fp):
                os.rmdir(fp)
            else:
                os.remove(fp)
        except FileNotFoundError:
            if wal_blob_pending:
                return  # the file only ever existed in the WAL overlay
            raise se.FileNotFound(f"{volume}/{path}") from None
        except OSError as e:
            if e.errno == errno.ENOTEMPTY:
                raise se.VolumeNotEmpty(path) from None
            raise se.FaultyDisk(str(e)) from e
        self._prune_empty_parents(os.path.dirname(fp), volume)

    def _prune_empty_parents(self, d: str, volume: str) -> None:
        vol_dir = self._vol_dir(volume)
        while d.startswith(vol_dir) and d != vol_dir:
            try:
                os.rmdir(d)
            except OSError:
                return
            d = os.path.dirname(d)

    def list_dir(self, volume: str, dir_path: str, count: int = -1) -> list[str]:
        if self._wal is not None:
            self._wal.flush()  # directory must reflect every acked commit
        d = self._file_path(volume, dir_path) if dir_path else self._vol_dir(volume)
        try:
            names = []
            with os.scandir(d) as it:
                for entry in it:
                    names.append(entry.name + "/" if entry.is_dir() else entry.name)
                    if 0 < count <= len(names):
                        break
            return sorted(names)
        except FileNotFoundError:
            raise se.FileNotFound(f"{volume}/{dir_path}") from None
        except NotADirectoryError:
            raise se.IsNotRegular(f"{volume}/{dir_path}") from None
        except OSError as e:
            raise se.FaultyDisk(str(e)) from e

    # ---------- shard files ----------

    def create_file(self, volume: str, path: str, chunks: Iterable[bytes]) -> int:
        """Shard-file write: native O_DIRECT aligned engine + fdatasync
        when available (native/mtpu_native.cc; reference
        cmd/xl-storage.go:1430 + pkg/disk/directio_unix.go), buffered
        Python IO otherwise."""
        from minio_tpu.native import DirectWriter

        fp = self._file_path(volume, path)
        os.makedirs(os.path.dirname(fp), exist_ok=True)
        written = 0
        with obs.timed_op(self._observe_op, "create_file", volume, path):
            try:
                w = DirectWriter(fp)
                try:
                    for chunk in chunks:
                        w.write(chunk)
                        written += len(chunk)
                finally:
                    w.close(sync=True)
            except OSError as e:
                raise se.FaultyDisk(str(e)) from e
        return written

    def append_file(self, volume: str, path: str, data: bytes) -> None:
        fp = self._file_path(volume, path)
        os.makedirs(os.path.dirname(fp), exist_ok=True)
        try:
            with open(fp, "ab") as f:
                f.write(data)
                f.flush()
                os.fsync(f.fileno())
        except OSError as e:
            raise se.FaultyDisk(str(e)) from e

    def read_file_stream(self, volume: str, path: str) -> BinaryIO:
        fp = self._file_path(volume, path)
        try:
            return open(fp, "rb")
        except FileNotFoundError:
            raise se.FileNotFound(f"{volume}/{path}") from None
        except IsADirectoryError:
            raise se.IsNotRegular(f"{volume}/{path}") from None
        except OSError as e:
            raise se.FaultyDisk(str(e)) from e

    def rename_file(self, src_volume: str, src_path: str,
                    dst_volume: str, dst_path: str) -> None:
        src = self._file_path(src_volume, src_path)
        dst = self._file_path(dst_volume, dst_path)
        os.makedirs(os.path.dirname(dst), exist_ok=True)
        try:
            os.replace(src, dst)
        except FileNotFoundError:
            raise se.FileNotFound(f"{src_volume}/{src_path}") from None
        except OSError as e:
            raise se.FaultyDisk(str(e)) from e
        _fsync_dir(os.path.dirname(dst), self.root)

    # ---------- versioned metadata ----------

    def _meta_path(self, volume: str, path: str) -> str:
        # Resolution is deterministic, so memoize: the split/validate/join
        # chain is a quarter of a cached-journal read on the hot GET path.
        key = (volume, path)
        mp = self._mpath_cache.get(key)
        if mp is None:
            mp = os.path.join(self._file_path(volume, path), META_FILE)
            if len(self._mpath_cache) >= self._meta_cache_cap * 2:
                self._mpath_cache.clear()
            self._mpath_cache[key] = mp
        return mp

    def _load_meta(self, volume: str, path: str) -> XLMeta:
        if self._wal is not None:
            pe = self._wal.pending_entry(volume, path)
            if pe is not None:
                if pe.removed:
                    raise se.FileNotFound(f"{volume}/{path}")
                # Fresh parse: _load_meta callers MUTATE the journal
                # (add_version/delete_version); the overlay's parsed
                # copy must stay pristine for readers.
                return XLMeta.parse(pe.raw)
        try:
            with open(self._meta_path(volume, path), "rb") as f:
                return XLMeta.parse(f.read())
        except FileNotFoundError:
            raise se.FileNotFound(f"{volume}/{path}") from None
        except NotADirectoryError:
            raise se.FileNotFound(f"{volume}/{path}") from None
        except OSError as e:
            raise se.FaultyDisk(str(e)) from e

    def _disk_meta_mt(self, volume: str, path: str) -> "float | None":
        """mod_time of the newest version in the ON-DISK journal, None
        when absent — the WAL replay tiebreak (never overlay-aware)."""
        try:
            with open(self._meta_path(volume, path), "rb") as f:
                raw = f.read()
        except (FileNotFoundError, NotADirectoryError):
            return None
        except OSError as e:
            raise se.FaultyDisk(str(e)) from e
        try:
            return XLMeta.parse(raw).latest_mt
        except se.StorageError:
            raise
        except Exception as e:  # noqa: BLE001 - any parse failure means
            # the on-disk journal is unusable; typed for the caller
            raise se.FileCorrupt(f"{volume}/{path}: {e}") from e

    def _note_sync(self, dt: float) -> None:
        e = self._sync_ewma
        self._sync_ewma = dt if e is None else 0.8 * e + 0.2 * dt

    @property
    def fast_sync(self) -> bool:
        e = self._sync_ewma
        return e is not None and e < 0.0005

    def _cache_put(self, volume: str, path: str, sig: tuple,
                   meta: XLMeta) -> None:
        """Insert/replace a journal cache entry (LRU-bounded)."""
        key = (volume, path)
        with self._meta_cache_lock:
            self._meta_cache[key] = (sig, meta, {})
            self._meta_cache.move_to_end(key)
            while len(self._meta_cache) > self._meta_cache_cap:
                self._meta_cache.popitem(last=False)

    # Read-seeded entries for files modified within this window of `now`
    # are not cached: kernel file timestamps tick coarsely (1-4ms), so a
    # concurrent writer could land a different journal with the same
    # (recycled inode, mtime tick, size) signature — the classic racy-stat
    # problem (same guard git uses for its index). Write-seeded entries are
    # exempt: every write through THIS process refreshes the entry, and a
    # drive has exactly one owning server process by contract (reference:
    # drives are never shared between nodes; remote access goes over RPC).
    _RACY_STAT_NS = 20_000_000

    def _cached_meta_entry(self, volume: str, path: str) -> tuple:
        """Stat-validated cache entry (XLMeta, fi_memo) for a journal.
        fi_memo maps version_id -> decoded FileInfo (read_version hands out
        clones, never the memoized object)."""
        if self._wal is not None:
            pe = self._wal.pending_entry(volume, path)
            if pe is not None:
                # Committed-but-unmaterialized state: the WAL overlay IS
                # the journal (read-your-write the instant the group
                # fsync acks).
                if pe.removed:
                    raise se.FileNotFound(f"{volume}/{path}")
                meta = pe.meta
                if meta is None:
                    meta = XLMeta.parse(pe.raw)
                    pe.meta = meta
                return meta, pe.memo
        mp = self._meta_path(volume, path)
        try:
            st = os.stat(mp)
        except FileNotFoundError:
            raise se.FileNotFound(f"{volume}/{path}") from None
        except NotADirectoryError:
            raise se.FileNotFound(f"{volume}/{path}") from None
        except OSError as e:
            raise se.FaultyDisk(str(e)) from e
        sig = (st.st_ino, st.st_mtime_ns, st.st_size)
        key = (volume, path)
        with self._meta_cache_lock:
            hit = self._meta_cache.get(key)
            if hit is not None and hit[0] == sig:
                self._meta_cache.move_to_end(key)
                return hit[1], hit[2]
        meta = self._load_meta(volume, path)
        if time.time_ns() - st.st_mtime_ns > self._RACY_STAT_NS:
            self._cache_put(volume, path, sig, meta)
        return meta, {}

    def _store_meta(self, volume: str, path: str, meta: XLMeta) -> None:
        raw = meta.serialize()
        if self._wal is not None:
            # Group commit: durability is the shared WAL fsync; the
            # meta.mp materializes asynchronously (reads consult the
            # overlay meanwhile).
            t0 = time.perf_counter()
            self._wal_wait(self._wal.submit_commit(volume, path, raw, meta))
            self._note_sync(time.perf_counter() - t0)
            return
        t0 = time.perf_counter()
        self._store_meta_disk(volume, path, raw, meta=meta, fsync=True)
        self._note_sync(time.perf_counter() - t0)

    def _store_meta_disk(self, volume: str, path: str, raw,
                         meta: "XLMeta | None" = None,
                         fsync: bool = True) -> None:
        """Write serialized journal bytes to meta.mp (tmp + optional
        fsync + rename). The WAL materializer calls this with
        fsync=False — the WAL carries durability until checkpoint."""
        mp = self._meta_path(volume, path)
        self._note_journal_key(volume, path)
        os.makedirs(os.path.dirname(mp), exist_ok=True)
        tmp = mp + f".tmp.{uuid.uuid4().hex}"
        try:
            fd = os.open(tmp, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o644)
            try:
                os.write(fd, raw)
                if fsync:
                    os.fsync(fd)
            finally:
                os.close(fd)
            # Sign BEFORE the rename: rename preserves the inode, so this
            # signature names exactly the bytes we wrote — if a concurrent
            # writer replaces the journal right after us, their file has a
            # different inode and our cache entry misses (fresh read),
            # never serves our version under their signature.
            st = os.stat(tmp)
            os.replace(tmp, mp)
        except OSError as e:
            raise se.FaultyDisk(str(e)) from e
        if meta is not None:
            # The writer never mutates `meta` after the store, so seed the
            # read cache with it (saves the next reader's parse).
            self._cache_put(volume, path,
                            (st.st_ino, st.st_mtime_ns, st.st_size), meta)
        else:
            with self._meta_cache_lock:
                self._meta_cache.pop((volume, path), None)

    def _remove_meta_disk(self, volume: str, path: str) -> None:
        """Remove a journal + prune empty parents (the materialized form
        of a WAL REMOVE record; also the direct delete_version tail)."""
        mp = self._meta_path(volume, path)
        try:
            os.remove(mp)
        except FileNotFoundError:
            pass
        except OSError as e:
            raise se.FaultyDisk(str(e)) from e
        with self._meta_cache_lock:
            self._meta_cache.pop((volume, path), None)
        obj_dir = os.path.dirname(mp)
        try:
            os.rmdir(obj_dir)
        except OSError:
            return  # non-empty (data dirs remain) or already gone
        self._prune_empty_parents(os.path.dirname(obj_dir), volume)

    @staticmethod
    def _wal_wait(fut):
        """Block on a group-commit future (returns its value — the
        reclaim token for singles); unreached commits become FaultyDisk
        (quorum counts the drive as failed)."""
        from concurrent.futures import TimeoutError as _FutTimeout

        try:
            return fut.result(timeout=60.0)
        except se.StorageError:
            raise
        except _FutTimeout:
            raise se.FaultyDisk("wal group commit stalled") from None

    def write_metadata_single(self, volume: str, path: str, fi: FileInfo,
                              raw: bytes, meta=None,
                              defer_reclaim: bool = False) -> "str | None":
        """Store the caller-serialized one-version journal directly when
        this drive's current journal is absent or holds exactly the version
        being replaced (the non-versioned overwrite); otherwise fall back
        to the classic merge. Cuts the small-object PUT from four
        serializes to one across the set. defer_reclaim: park the
        displaced version (entry + data dir) in a reclaim capsule and
        return its token — same commit_rename/undo_rename contract as
        rename_data, so a below-quorum inline overwrite is undoable."""
        with obs.timed_op(self._observe_op, "write_metadata_single",
                          volume, path):
            return self._write_metadata_single(
                volume, path, fi, raw, meta=meta,
                defer_reclaim=defer_reclaim)

    def _reclaim_dir(self, d: str, defer_fs: bool) -> None:
        """Destroy a displaced data dir. With defer_fs (committer
        context) the tree is parked with one O(1) rename and rmtree'd
        at the next idle drain — a large displaced object must not
        head-of-line block every concurrent group commit on the
        drive."""
        if defer_fs and self._wal is not None:
            trash = os.path.join(self.root, SYS_VOL, "tmp",
                                 f"trash-{uuid.uuid4().hex}")
            try:
                os.replace(d, trash)
            except OSError:
                pass  # fall through to the inline rmtree below
            else:
                self._wal.note_trash(trash)
                return
        shutil.rmtree(d, ignore_errors=True)

    def _single_prework(self, volume: str, path: str, fi: FileInfo,
                        defer_reclaim: bool,
                        assume_new: bool = False,
                        defer_fs: bool = False) -> tuple:
        """The non-commit half of a single-journal store: reclaim/stash
        whatever this write displaces, and detect the classic-merge case
        (multi-version journal / vid mismatch). Returns (token, merged):
        merged is the fully merged XLMeta to store INSTEAD of the
        caller-serialized one-version journal, or None when the raw
        single-version journal may be stored directly. Runs in the WAL
        committer when the plane is armed (the submit side is pure
        memory); same-key callers are serialized by the erasure layer's
        namespace lock."""
        token: str | None = None
        if assume_new:
            # Submit-side proof (journal_known_absent on a fresh volume)
            # that no journal exists: skip the existence probe entirely.
            return token, None
        try:
            cur, memo = self._cached_meta_entry(volume, path)
        except se.FileNotFound:
            cur = None
        if cur is not None:
            try:
                old = memo.get("")
                if old is None:
                    old = cur.to_fileinfo(volume, path)
                    memo[""] = old
            except se.StorageError:
                old = None
            if old is not None and defer_reclaim and not old.deleted \
                    and old.version_id == fi.version_id:
                token = self._stash_displaced(
                    volume, path, old,
                    move_data=bool(old.data_dir
                                   and old.data_dir != fi.data_dir))
            if old is None or (cur.version_count != 1 or old.deleted
                               or old.version_id != fi.version_id):
                # Classic merge (write_metadata semantics, inlined so
                # the committer can run it without re-entering the WAL):
                # reclaim the exact version's displaced data dir, fold
                # the new version into the full journal.
                try:
                    merged = self._load_meta(volume, path)
                except se.FileNotFound:
                    merged = XLMeta()
                try:
                    prev = merged.exact_version(volume, path,
                                                fi.version_id)
                    if prev.data_dir and prev.data_dir != fi.data_dir \
                            and not prev.deleted:
                        self._reclaim_dir(
                            os.path.join(self._file_path(volume, path),
                                         prev.data_dir), defer_fs)
                except se.StorageError:
                    pass
                merged.add_version(fi)
                return token, merged
            if old.data_dir and old.data_dir != fi.data_dir \
                    and not token:
                self._reclaim_dir(
                    os.path.join(self._file_path(volume, path),
                                 old.data_dir), defer_fs)
        return token, None

    def journal_commit_async(self, volume: str, path: str, fi: FileInfo,
                             raw, meta=None, defer_reclaim: bool = False):
        """Two-phase single-journal commit for the group-commit plane:
        enqueue the record (pure memory — vol stat, displaced-state
        stash, and merge fallback all run in the committer) and return
        a future that resolves to the reclaim token after the shared
        WAL fsync. The erasure layer submits to every drive first and
        then awaits all futures, so one PUT pays max(group fsync) once
        instead of a pool dispatch + blocked worker per drive. None
        when the WAL is not armed (callers use the sync fan-out)."""
        if self._wal is None:
            return None
        t0 = time.perf_counter()
        fut = self._wal.submit_single(volume, path, fi, raw, meta,
                                      defer_reclaim)

        def _done(f, t0=t0):
            # Committer-thread callback with the submitting request's
            # trace context: the commit's per-drive latency + `storage`
            # trace record stay attributable exactly like the sync
            # store's (the armed default must not lose the op from the
            # request trace).
            self._note_sync(time.perf_counter() - t0)
            self._observe_op("journal_commit_async", t0, volume, path,
                             f.exception())

        fut.add_done_callback(obs.ctx_wrap(_done))
        return fut

    def _write_metadata_single(self, volume: str, path: str, fi: FileInfo,
                               raw: bytes, meta=None,
                               defer_reclaim: bool = False) -> "str | None":
        if self._wal is not None:
            # Inline-PUT group commit: the ack contract is the shared
            # WAL fsync (docs/METAPLANE.md), not this drive's meta.mp.
            t0 = time.perf_counter()
            fut = self._wal.submit_single(volume, path, fi, raw, meta,
                                          defer_reclaim)
            token = self._wal_wait(fut)
            self._note_sync(time.perf_counter() - t0)
            return token
        self.stat_vol(volume)
        token, merged = self._single_prework(volume, path, fi,
                                             defer_reclaim)
        t0 = time.perf_counter()
        if merged is not None:
            self._store_meta_disk(volume, path, merged.serialize(),
                                  meta=merged, fsync=True)
        else:
            self._store_meta_disk(volume, path, raw, meta=meta, fsync=True)
        self._note_sync(time.perf_counter() - t0)
        return token

    def _stash_displaced(self, volume: str, path: str, old: FileInfo,
                         move_data: bool) -> "str | None":
        """Park a displaced version into a reclaim capsule (entry doc in
        old.mp, data dir in olddata when move_data) and return its token.
        A stash failure rolls the data move back and degrades to FaultyDisk
        — the caller's quorum accounting treats it like any drive error,
        never a stranded half-capsule."""
        token = f"reclaim-{uuid.uuid4().hex}"
        cap = os.path.join(self.root, SYS_VOL, "tmp", token)
        obj_dir = self._file_path(volume, path)
        old_data = os.path.join(obj_dir, old.data_dir) if old.data_dir \
            else ""
        moved = False
        try:
            os.makedirs(cap, exist_ok=True)
            oldj = XLMeta()
            oldj.add_version(old)
            with open(os.path.join(cap, "old.mp"), "wb") as f:
                f.write(oldj.serialize())
            if move_data and os.path.isdir(old_data):
                os.replace(old_data, os.path.join(cap, "olddata"))
                moved = True
        except OSError as e:
            if moved:
                try:
                    os.replace(os.path.join(cap, "olddata"), old_data)
                except OSError:
                    pass
            shutil.rmtree(cap, ignore_errors=True)
            raise se.FaultyDisk(f"reclaim stash: {e}") from e
        return token

    def write_metadata(self, volume: str, path: str, fi: FileInfo) -> None:
        self.stat_vol(volume)
        try:
            meta = self._load_meta(volume, path)
        except se.FileNotFound:
            meta = XLMeta()
        # Replacing a version (e.g. erasure object overwritten by an inline
        # one): reclaim the old data dir or its shards leak unreferenced.
        # Exact-vid lookup: a null-version write must reclaim only the null
        # version's dir, never "latest" (which may be a live version).
        try:
            old = meta.exact_version(volume, path, fi.version_id)
            if old.data_dir and old.data_dir != fi.data_dir and not old.deleted:
                shutil.rmtree(
                    os.path.join(self._file_path(volume, path), old.data_dir),
                    ignore_errors=True,
                )
        except se.StorageError:
            pass
        meta.add_version(fi)
        self._store_meta(volume, path, meta)

    def read_version(self, volume: str, path: str, version_id: str = "",
                     read_data: bool = False) -> FileInfo:
        # Inline timing (not obs.timed_op): a cached-journal read is ~2us
        # and a generator contextmanager entry would be measurable here.
        t0 = time.perf_counter()
        err: BaseException | None = None
        try:
            meta, fi_memo = self._cached_meta_entry(volume, path)
            fi = fi_memo.get(version_id)
            if fi is None:
                fi = meta.to_fileinfo(volume, path, version_id)
                fi_memo[version_id] = fi
            # Clone: callers mutate their FileInfo (erasure.index, checksum
            # election); the memoized copy must stay pristine.
            return fi.clone()
        except BaseException as e:
            err = e
            raise
        finally:
            self._observe_op("read_version", t0, volume, path, err)

    def read_xl(self, volume: str, path: str) -> bytes:
        if self._wal is not None:
            pe = self._wal.pending_entry(volume, path)
            if pe is not None:
                if pe.removed:
                    raise se.FileNotFound(f"{volume}/{path}")
                return pe.raw
        try:
            with open(self._meta_path(volume, path), "rb") as f:
                return f.read()
        except (FileNotFoundError, NotADirectoryError):
            raise se.FileNotFound(f"{volume}/{path}") from None
        except OSError as e:
            raise se.FaultyDisk(str(e)) from e

    def delete_version(self, volume: str, path: str, fi: FileInfo) -> None:
        try:
            meta = self._load_meta(volume, path)
        except se.FileNotFound:
            if fi.deleted:  # delete marker on nonexistent object is legal
                meta = XLMeta()
                meta.add_version(fi)
                self._store_meta(volume, path, meta)
                return
            raise
        if fi.deleted:
            meta.add_version(fi)
            self._store_meta(volume, path, meta)
            return
        removed = meta.delete_version(fi.version_id, volume, path)
        obj_dir = self._file_path(volume, path)
        if removed.data_dir:
            shutil.rmtree(os.path.join(obj_dir, removed.data_dir), ignore_errors=True)
        if meta.versions:
            self._store_meta(volume, path, meta)
        elif self._wal is not None:
            # The removal must be WAL-ordered (replay would otherwise
            # resurrect an earlier commit record for this key) and the
            # delete ack durable — ride the same group fsync.
            self._wal_wait(self._wal.submit_remove(volume, path))
        else:
            try:
                self._remove_meta_disk(volume, path)
            except se.StorageError:
                pass  # best-effort, as before: heal converges the rest

    def rename_data(self, src_volume: str, src_path: str, fi: FileInfo,
                    dst_volume: str, dst_path: str,
                    defer_reclaim: bool = False) -> str | None:
        """Commit staged data + journal entry. defer_reclaim=True defers
        destruction of whatever this commit DISPLACES (a replaced
        version's data dir, a clobbered stale data dir, the replaced
        journal entry) into a reclaim capsule under the sys tmp area and
        returns its token: the caller purges it after write quorum
        (commit_rename) or restores it on quorum failure (undo_rename) —
        the reference's commitRenameDataDir/undo discipline. Default
        (False) reclaims inline, the pre-existing single-drive
        semantics."""
        with obs.timed_op(self._observe_op, "rename_data",
                          dst_volume, dst_path):
            return self._rename_data(src_volume, src_path, fi,
                                     dst_volume, dst_path,
                                     defer_reclaim=defer_reclaim)

    def _rename_data(self, src_volume: str, src_path: str, fi: FileInfo,
                     dst_volume: str, dst_path: str,
                     defer_reclaim: bool = False) -> str | None:
        src_dir = self._file_path(src_volume, src_path)
        obj_dir = self._file_path(dst_volume, dst_path)
        os.makedirs(obj_dir, exist_ok=True)
        token: str | None = None
        if fi.data_dir:
            dst_data = os.path.join(obj_dir, fi.data_dir)
            # Healing overwrites an existing (corrupt/stale) data dir.
            # os.replace cannot clobber a non-empty dir, so move the old one
            # aside first and only discard it after the new data is in place —
            # a failed rename must never leave the drive with less data than
            # it had.
            aside = None
            if os.path.isdir(dst_data):
                aside = dst_data + f".old.{uuid.uuid4().hex}"
                os.replace(dst_data, aside)
            try:
                os.replace(src_dir, dst_data)
            except FileNotFoundError:
                if aside:
                    os.replace(aside, dst_data)
                raise se.FileNotFound(f"{src_volume}/{src_path}") from None
            except OSError as e:
                if aside:
                    os.replace(aside, dst_data)
                raise se.FaultyDisk(str(e)) from e
            if aside:
                # Defer-mode callers (PUT/complete commits) never clobber
                # an existing data dir of the same name — that is the
                # heal flow — so the aside is reclaimed inline either way.
                shutil.rmtree(aside, ignore_errors=True)
        try:
            meta = self._load_meta(dst_volume, dst_path)
        except se.FileNotFound:
            meta = XLMeta()
        except (se.FileCorrupt, se.CorruptedFormat):
            # Unreadable journal (CRC/decode failure): its version history
            # is already lost — rebuild from the incoming version rather
            # than wedging the commit (the reference's RenameData rewrites
            # a corrupted destination xl.meta; heal re-adds the rest).
            meta = XLMeta()
        # Replacing a null version: reclaim its data dir (exact-vid — see
        # write_metadata), or park the whole displaced version in a
        # reclaim capsule when the caller wants the commit undoable.
        try:
            old = meta.exact_version(dst_volume, dst_path, fi.version_id)
            displaces_data = (old.data_dir and old.data_dir != fi.data_dir
                              and not old.deleted)
            if defer_reclaim:
                token = self._stash_displaced(
                    dst_volume, dst_path, old,
                    move_data=bool(displaces_data))
            elif displaces_data:
                shutil.rmtree(os.path.join(obj_dir, old.data_dir),
                              ignore_errors=True)
        except se.FileVersionNotFound:
            pass
        except se.StorageError:
            pass
        meta.add_version(fi)
        self._store_meta(dst_volume, dst_path, meta)
        _fsync_dir(obj_dir, self.root)
        return token

    def commit_rename(self, token: str) -> None:
        """Quorum reached: discard the displaced state for good."""
        if not token or "/" in token or ".." in token:
            return
        shutil.rmtree(os.path.join(self.root, SYS_VOL, "tmp", token),
                      ignore_errors=True)

    def undo_rename(self, volume: str, path: str, fi: FileInfo,
                    token: str | None) -> None:
        """Quorum failed on other drives: remove the committed version
        and restore what rename_data displaced, so the drive rejoins the
        pre-PUT state (listings must not show a below-quorum object, and
        a replaced version's data must survive)."""
        try:
            self.delete_version(volume, path, fi)
        except se.StorageError:
            pass
        if not token or "/" in token or ".." in token:
            return
        cap = os.path.join(self.root, SYS_VOL, "tmp", token)
        if not os.path.isdir(cap):
            return
        obj_dir = self._file_path(volume, path)
        oldmp = os.path.join(cap, "old.mp")
        if os.path.exists(oldmp):
            try:
                oldj = XLMeta.parse(open(oldmp, "rb").read())
                old = oldj.to_fileinfo(volume, path)
                olddata = os.path.join(cap, "olddata")
                if os.path.isdir(olddata) and old.data_dir:
                    os.makedirs(obj_dir, exist_ok=True)
                    os.replace(olddata,
                               os.path.join(obj_dir, old.data_dir))
                try:
                    meta = self._load_meta(volume, path)
                except se.StorageError:
                    meta = XLMeta()
                meta.add_version(old)
                self._store_meta(volume, path, meta)
            except (se.StorageError, OSError):
                pass    # best-effort: heal converges the remainder
        shutil.rmtree(cap, ignore_errors=True)

    # ---------- verification / walking ----------

    def verify_file(self, volume: str, path: str, fi: FileInfo) -> None:
        shard_size = fi.erasure.shard_size()
        algo = next((c.algorithm for c in fi.erasure.checksums), bitrot.DEFAULT_ALGORITHM)
        for part in fi.parts:
            shard_data_size = fi.erasure.shard_file_size(part.size)
            rel = f"{path}/{fi.data_dir}/part.{part.number}"
            with self.read_file_stream(volume, rel) as f:
                bitrot.verify_shard_file(f, shard_data_size, shard_size, algo,
                                         digest_chunks=route.digest_chunks)

    def walk_dir(self, volume: str, prefix: str = "",
                 start_after: str = "") -> Iterator[WalkEntry]:
        """Sorted journal walk. Entries come out in LEXICOGRAPHIC order of
        the full object name — the invariant the streamed k-way listing
        merge relies on. Per-directory sorting alone is NOT lexicographic
        over full names ('a.txt' < 'a/b' because '.' < '/', yet a naive
        walk emits everything under a/ first), so each directory entry
        sorts under TWO keys: `name` for the object journal it may hold
        and `name + "/"` for its subtree (the reference's dir-entries-
        carry-trailing-slash convention, cmd/metacache-walk.go). This also
        lists keys nested under an object key ('a' and 'a/b' coexisting).
        """
        if self._wal is not None:
            # The walk reads meta.mp straight off the filesystem; every
            # acked commit must be materialized first (cheap when idle).
            self._wal.flush()
        base = self._vol_dir(volume)
        if not os.path.isdir(base):
            raise se.VolumeNotFound(volume)

        def _walk(rel: str) -> Iterator[WalkEntry]:
            d = os.path.join(base, rel) if rel else base
            try:
                with os.scandir(d) as it:
                    dirs = [e.name for e in it if e.is_dir()]
            except OSError:
                return
            items = []  # (sort_key, name, is_subtree)
            for dn in dirs:
                name = f"{rel}/{dn}" if rel else dn
                items.append((name, name, False))
                items.append((name + "/", name, True))
            for _key, name, is_subtree in sorted(items):
                if is_subtree:
                    if prefix and not (name.startswith(prefix)
                                       or prefix.startswith(name + "/")):
                        continue
                    # Marker prune: the largest key this subtree can hold
                    # is name+"/"+<max suffix> (names are length-capped at
                    # 1024). If even that bound is <= start_after, no key
                    # here can follow the marker — skip the subtree without
                    # touching its journals. Group-resume callers (NextMarker
                    # = a CommonPrefix) exploit this by passing
                    # marker+MARKER_GROUP_PAD so the whole group prunes too.
                    if start_after and name + "/" + MARKER_GROUP_PAD \
                            <= start_after:
                        continue
                    yield from _walk(name)
                    continue
                if prefix and not name.startswith(prefix):
                    continue
                if start_after and name <= start_after:
                    continue
                meta_p = os.path.join(base, *name.split("/"), META_FILE)
                try:
                    with open(meta_p, "rb") as f:
                        yield WalkEntry(name=name, meta=f.read())
                except OSError:
                    continue  # plain directory level (no journal here)

        yield from _walk("")

    # ---------- metadata-plane hooks (docs/METAPLANE.md) ----------

    def meta_sig(self, volume: str, path: str):
        """Cheap logical signature of this drive's journal for the
        set-level FileInfo cache: the WAL per-key LSN while armed (a
        dict lookup; bumps on every mutation), else the stat triple the
        per-drive journal cache already trusts. None = journal absent
        or unknowable (callers must re-elect)."""
        if self._wal is not None:
            sig = self._wal.key_sig(volume, path)
            if sig is not None:
                return sig
        try:
            st = os.stat(self._meta_path(volume, path))
        except OSError:
            return None
        return (st.st_ino, st.st_mtime_ns, st.st_size)

    def close_wal(self) -> None:
        """Drain + checkpoint + stop the group-commit thread (tests;
        process-lived drives just exit with their daemon)."""
        if self._wal is not None:
            self._wal.close()

    # ---------- tmp helpers (used by the erasure layer) ----------

    def new_tmp_dir(self) -> str:
        """Unique staging path under the sys tmp volume."""
        return f"tmp/{uuid.uuid4().hex}"

    def sys_volume(self) -> str:
        return SYS_VOL
