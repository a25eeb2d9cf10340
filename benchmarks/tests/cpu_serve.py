"""A launcher for the tests only: the benchmark's own `serve.py`, after
making the program store `mxsum256` on the CPU backend too (what it picks by
itself on a TPU), and after planting the fault `BENCH_TEST_FAULT` names
underneath the program, where the answer is produced (or, for `outage`, the
condition in which the program answers late)."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import serve  # noqa: E402


def plant(fault: str) -> None:
    from minio_tpu.ops import bitrot

    bitrot._DEVICE_DEFAULT = "mxsum256"
    if fault == "parity":
        # One parity byte altered as the encode launch hands it back.
        from minio_tpu.erasure import codec

        wait = codec.PendingEncode.wait

        def bad_wait(self):
            chunks, digs = wait(self)
            last = bytearray(chunks[0][-1])
            last[0] ^= 1
            chunks[0][-1] = memoryview(bytes(last))
            return chunks, digs

        codec.PendingEncode.wait = bad_wait
    elif fault == "lost-drives":
        # Acknowledged, but the commit reached fewer drives than quorum.
        from minio_tpu.erasure import objects

        quorum = objects.reduce_write_quorum
        objects.reduce_write_quorum = (
            lambda outcomes, q, *names: quorum(outcomes, 1, *names))
        from minio_tpu.storage import local

        rename = local.LocalDrive.rename_data

        def bad_rename(self, *a, **kw):
            if self.root.rstrip("/").endswith(("d0", "d1")):
                raise OSError("planted: this drive takes no commit")
            return rename(self, *a, **kw)

        local.LocalDrive.rename_data = bad_rename
    elif fault == "get-body":
        # One byte of every GET body altered where the handler sends it.
        from aiohttp import web

        write = web.StreamResponse.write

        async def bad_write(self, data):
            if len(data) > 4096:
                data = bytes([data[0] ^ 1]) + bytes(data[1:])
            return await write(self, data)

        web.StreamResponse.write = bad_write
    elif fault == "outage":
        # No fault of an answer: every drive is offline, as the program's
        # health check has it after the host stood still. Twice, for two
        # seconds each: from the first health probe (the read back's
        # first question), and from the first read of a shard file (the
        # first read back itself, its headers already sent).
        import time

        from minio_tpu.storage import healthcheck

        offline = {}          # what began it -> until when
        lookup = healthcheck.HealthChecker.__getattr__
        begin = healthcheck.HealthChecker._begin
        disk_info = healthcheck.HealthChecker.disk_info

        def lookup_then_outage(self, name):
            if name.startswith("read_file"):
                offline.setdefault("read", time.monotonic() + 2.0)
            return lookup(self, name)

        def probe_then_outage(self):
            offline.setdefault("probe", time.monotonic() + 2.0)
            return disk_info(self)

        def begin_or_offline(self, cls):
            if any(time.monotonic() < t for t in offline.values()):
                raise healthcheck.se.DiskNotFound("planted: drive offline")
            return begin(self, cls)

        healthcheck.HealthChecker.__getattr__ = lookup_then_outage
        healthcheck.HealthChecker.disk_info = probe_then_outage
        healthcheck.HealthChecker._begin = begin_or_offline
    elif fault == "rebuilt-row":
        # One byte of every rebuild altered as the launch hands it back.
        import numpy as np

        from minio_tpu.ops import rs_xla

        launch = rs_xla.gf2_matmul_with_weights

        def bad_launch(batch, w, n_out):
            out = np.array(launch(batch, w, n_out))
            out[0, 0, 0] ^= 1
            return out

        rs_xla.gf2_matmul_with_weights = bad_launch
    elif fault == "drive-back":
        # No fault of an answer: a blank drive is mounted where a lost one
        # was, so the state the configuration names does not hold.
        import threading
        import time

        roots = sys.argv[4:sys.argv.index("--parity")]

        def remount():
            while True:
                time.sleep(0.2)
                for r in roots:
                    if os.path.islink(r):
                        os.unlink(r)
                        os.mkdir(r)

        threading.Thread(target=remount, daemon=True).start()
    elif fault == "healed-shard":
        # One byte of a rebuilt shard altered on its way to the drive.
        from minio_tpu.erasure import healing

        put = healing._ShardWriterPool.put

        def bad_put(self, pos, framed):
            framed = bytearray(framed)
            framed[-1] ^= 1
            return put(self, pos, bytes(framed))

        healing._ShardWriterPool.put = bad_put
    elif fault == "heal-withheld":
        # The heal commits and says so, and d0 does not keep the shard file.
        import glob

        from minio_tpu.storage import local

        rename = local.LocalDrive.rename_data

        def rename_and_lose(self, src_vol, src_path, fi, dst_vol, dst_path,
                            **kw):
            out = rename(self, src_vol, src_path, fi, dst_vol, dst_path, **kw)
            if (src_path.startswith("tmp/heal-")
                    and self.root.rstrip("/").endswith("d0")):
                for part in glob.glob(os.path.join(
                        self.root, dst_vol, dst_path, "*", "part.*")):
                    os.unlink(part)
            return out

        local.LocalDrive.rename_data = rename_and_lose
    elif fault == "heal-leaves-blank":
        # The heal leaves d0 as it found it, and says so.
        from minio_tpu.erasure import healing

        rebuild = healing.HealingMixin._reconstruct_to_targets

        def rebuild_but_d0(self, bucket, obj, latest, drives, avail,
                           targets):
            return rebuild(self, bucket, obj, latest, drives, avail, [
                t for t in targets
                if not str(drives[t].endpoint()).rstrip("/").endswith("d0")])

        healing.HealingMixin._reconstruct_to_targets = rebuild_but_d0
    elif fault:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    plant(os.environ.get("BENCH_TEST_FAULT", ""))
    sys.exit(serve.main(sys.argv))
