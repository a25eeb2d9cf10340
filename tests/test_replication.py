"""Bucket replication tests: rule parsing, the live two-server flow
(cmd/bucket-replication.go role), the durable intent journal, the
retry/breaker fabric, and the two-cluster chaos gate — two OS-process
clusters, a partitioned inter-cluster link, a real SIGKILL of the
source mid-queue, and ledger-proven convergence after heal."""

import json
import os
import random
import signal
import socket
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest
import requests
from aiohttp import web

from minio_tpu.metaplane import wal as walfmt
from minio_tpu.replication import parse_replication_xml
from minio_tpu.replication.rules import META_STATUS
from tests.s3client import SigV4Client

ACCESS, SECRET = "reproot", "reproot-secret"

REPL_XML = b"""<ReplicationConfiguration>
  <Rule><ID>r1</ID><Status>Enabled</Status><Priority>1</Priority>
    <Filter><Prefix>docs/</Prefix></Filter>
    <Destination><Bucket>arn:aws:s3:::mirror</Bucket></Destination>
    <DeleteMarkerReplication><Status>Enabled</Status>
    </DeleteMarkerReplication>
    <DeleteReplication><Status>Enabled</Status></DeleteReplication>
  </Rule>
</ReplicationConfiguration>"""


def test_parse_replication_xml():
    cfg = parse_replication_xml(REPL_XML)
    assert len(cfg.rules) == 1
    r = cfg.rules[0]
    assert r.target_bucket == "mirror" and r.prefix == "docs/"
    assert r.delete_marker_replication and r.delete_replication
    assert cfg.rule_for("docs/a.txt") is r
    assert cfg.rule_for("other/a.txt") is None
    with pytest.raises(ValueError):
        parse_replication_xml(b"<ReplicationConfiguration Rule='x'>"
                              b"</ReplicationConfiguration>")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _boot(tmp_path, name):
    import asyncio

    from minio_tpu.s3.server import build_server

    srv = build_server([str(tmp_path / f"{name}{i}") for i in range(4)],
                       ACCESS, SECRET)
    port = _free_port()
    loop = asyncio.new_event_loop()
    started = threading.Event()

    def run():
        asyncio.set_event_loop(loop)

        async def start():
            runner = web.AppRunner(srv.app)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", port)
            await site.start()
            started.set()

        loop.run_until_complete(start())
        loop.run_forever()

    threading.Thread(target=run, daemon=True).start()
    assert started.wait(30)
    return srv, f"http://127.0.0.1:{port}", loop


@pytest.fixture()
def pair(tmp_path):
    src_srv, src_url, l1 = _boot(tmp_path, "src")
    dst_srv, dst_url, l2 = _boot(tmp_path, "dst")
    yield (src_srv, src_url), (dst_srv, dst_url)
    src_srv.replication.close()
    l1.call_soon_threadsafe(l1.stop)
    l2.call_soon_threadsafe(l2.stop)


def test_end_to_end_replication(pair):
    (src_srv, src_url), (dst_srv, dst_url) = pair
    src = SigV4Client(src_url, ACCESS, SECRET)
    dst = SigV4Client(dst_url, ACCESS, SECRET)

    assert src.put("/origin").status_code == 200
    assert dst.put("/mirror").status_code == 200

    # Register the remote target + replication config.
    r = src.put("/minio/admin/v3/set-remote-target",
                query={"bucket": "origin"},
                data=json.dumps({"endpoint": dst_url, "accessKey": ACCESS,
                                 "secretKey": SECRET,
                                 "targetBucket": "mirror"}).encode())
    assert r.status_code == 200, r.text
    r = src.put("/origin", data=REPL_XML, query={"replication": ""})
    assert r.status_code == 200, r.text

    # Matching put replicates; status flips to COMPLETED at the source.
    payload = b"replicate me" * 100
    r = src.put("/origin/docs/a.txt", data=payload,
                headers={"x-amz-meta-team": "infra"})
    assert r.status_code == 200
    src_srv.replication.drain()

    r = dst.get("/mirror/docs/a.txt")
    assert r.status_code == 200, r.text
    assert r.content == payload
    assert r.headers.get("x-amz-replication-status") == "REPLICA"
    assert r.headers.get("x-amz-meta-team") == "infra"

    deadline = time.time() + 5
    status = ""
    while time.time() < deadline:
        info = src_srv.obj.get_object_info("origin", "docs/a.txt")
        status = info.user_defined.get(META_STATUS, "")
        if status == "COMPLETED":
            break
        time.sleep(0.05)
    assert status == "COMPLETED"

    # Non-matching prefix does not replicate.
    src.put("/origin/other/b.txt", data=b"stays local")
    src_srv.replication.drain()
    assert dst.get("/mirror/other/b.txt").status_code == 404

    # Delete replication.
    assert src.delete("/origin/docs/a.txt").status_code == 204
    src_srv.replication.drain()
    deadline = time.time() + 5
    while time.time() < deadline:
        if dst.get("/mirror/docs/a.txt").status_code == 404:
            break
        time.sleep(0.05)
    assert dst.get("/mirror/docs/a.txt").status_code == 404

    # Stats moved.
    st = src_srv.replication.stats
    assert st["completed"] >= 2 and st["queued"] >= 2


def test_replication_failure_marks_failed(pair):
    (src_srv, src_url), (dst_srv, dst_url) = pair
    src = SigV4Client(src_url, ACCESS, SECRET)
    assert src.put("/origin").status_code == 200
    # Target endpoint is unreachable.
    src.put("/minio/admin/v3/set-remote-target", query={"bucket": "origin"},
            data=json.dumps({"endpoint": "http://127.0.0.1:1",
                             "accessKey": "x", "secretKey": "y",
                             "targetBucket": "mirror"}).encode())
    src.put("/origin", data=REPL_XML, query={"replication": ""})
    src.put("/origin/docs/fail.txt", data=b"x")
    src_srv.replication.drain()
    deadline = time.time() + 5
    status = ""
    while time.time() < deadline:
        info = src_srv.obj.get_object_info("origin", "docs/fail.txt")
        status = info.user_defined.get(META_STATUS, "")
        if status == "FAILED":
            break
        time.sleep(0.05)
    assert status == "FAILED"
    assert src_srv.replication.stats["failed"] >= 1


# ---------------------------------------------------------------------
# Durable intent journal (minio_tpu/replication/journal.py)
# ---------------------------------------------------------------------


def test_journal_append_replay_compact(tmp_path, monkeypatch):
    from minio_tpu.replication import journal as jmod

    path = str(tmp_path / "replication.wal")
    j = jmod.ReplicationJournal(path)
    docs = [{"bucket": "b", "key": f"k{i}", "version_id": "", "op": "put"}
            for i in range(3)]
    ids = []
    for d in docs:
        iid = j.mint_id()
        ids.append(iid)
        j.append_intent("b", iid, d)
    j.append_done("b", ids[1])
    # Replay = INTENT minus DONE, in append order.
    assert [i for i, _ in j.replay()] == [ids[0], ids[2]]
    assert j.replay()[0][1] == docs[0]
    assert j.backlog() == 2
    j.close()

    # Durable across close (append_intent fsyncs before returning).
    j2 = jmod.ReplicationJournal(path)
    assert [i for i, _ in j2.replay()] == [ids[0], ids[2]]

    # Torn tail: a half-written frame (SIGKILL mid-append) truncates
    # cleanly at scan; earlier acked intents are intact.
    frame = b"".join(walfmt.frame_record(
        walfmt.REC_REPL_INTENT, time.time(), "b", "torn", b"x"))
    with open(path, "ab") as f:
        f.write(frame[:len(frame) // 2])
    assert [i for i, _ in j2.replay()] == [ids[0], ids[2]]

    # Compaction rewrites the segment down to its live fold (DONE pairs
    # and the torn tail disappear) and keeps accepting appends.
    monkeypatch.setattr(jmod, "_COMPACT_BYTES", 1)
    before = os.path.getsize(path)
    assert j2.maybe_compact()
    assert os.path.getsize(path) < before
    assert [i for i, _ in j2.replay()] == [ids[0], ids[2]]
    iid = j2.mint_id()
    j2.append_intent("b", iid, docs[0])
    assert len(j2.replay()) == 3
    j2.close()


class _XmlMeta:
    """bucket_meta stub: every bucket carries REPL_XML."""

    class _B:
        replication_xml = REPL_XML

    def get(self, bucket):
        return self._B


class _NoTargets:
    def get_target(self, bucket):
        return None


class _NoLayer:
    drives = []

    def list_buckets(self):
        return []


def test_queue_full_sheds_but_journal_survives(tmp_path, monkeypatch):
    """A full queue sheds the in-memory task (counted), but the durable
    intent survives; a fresh pool's replay retires the backlog."""
    from minio_tpu.replication.pool import (OP_PUT, ReplicationPool,
                                            ReplicationTask)

    monkeypatch.setenv("MTPU_REPL_TEST_HOLD_S", "30")   # pin the worker
    pool = ReplicationPool(_NoLayer(), _XmlMeta(), _NoTargets(),
                           workers=1, queue_size=1,
                           journal_dir=str(tmp_path))
    try:
        for i in range(4):
            pool.queue_task(ReplicationTask("origin", f"docs/s{i}",
                                            op=OP_PUT))
        # Worker holds one task, the 1-slot queue holds one more: at
        # least two of four submissions shed. Every intent journaled.
        assert pool.stats["shed"] >= 1
        assert pool._journal is not None
        assert pool._journal.backlog() == 4
        assert pool.describe()["backlog"] == 4
    finally:
        pool.close()

    # Replay on a fresh pool re-enqueues all four; with no target
    # configured the obligation is void → workers retire the intents.
    monkeypatch.setenv("MTPU_REPL_TEST_HOLD_S", "0")
    pool2 = ReplicationPool(_NoLayer(), _XmlMeta(), _NoTargets(),
                            workers=2, queue_size=100,
                            journal_dir=str(tmp_path))
    try:
        assert pool2.stats["replayed"] == 4
        deadline = time.time() + 10
        while time.time() < deadline:
            if pool2.describe()["backlog"] == 0:
                break
            time.sleep(0.05)
        assert pool2.describe()["backlog"] == 0
        assert pool2._journal.backlog() == 0
    finally:
        pool2.close()


# ---------------------------------------------------------------------
# Retry/breaker fabric (minio_tpu/replication/client.py)
# ---------------------------------------------------------------------


def test_breaker_opens_and_fails_fast():
    from minio_tpu.dist import rpc
    from minio_tpu.replication import client as rc

    try:
        # Nothing listens on port 2: connect refusal is the partition
        # signature — a hard failure opens the breaker immediately.
        c = rc.RemoteS3Client("http://127.0.0.1:2", "x", "y", timeout=2.0)
        with pytest.raises(rc.RemoteS3Unreachable):
            c.head_object("mirror", "k")
        assert c.breaker.state() == rpc.BREAKER_OPEN
        # OPEN = zero socket work: the refusal is instant, not a
        # connect timeout.
        t0 = time.perf_counter()
        with pytest.raises(rc.RemoteS3Unreachable):
            c.head_object("mirror", "k")
        assert time.perf_counter() - t0 < 0.05
        # One breaker per target endpoint, shared process-wide.
        c2 = rc.RemoteS3Client("http://127.0.0.1:2", "x", "y")
        assert c2.breaker is c.breaker
    finally:
        rc.reset_breakers()


# ---------------------------------------------------------------------
# Per-key ordering (satellite: DELETE-after-PUT regression)
# ---------------------------------------------------------------------


def test_delete_after_put_ordering(tmp_path, monkeypatch):
    """With multiple workers, one key's PUT→DELETE history must apply
    in order on the far side: tasks route by key hash, and a retried
    PUT re-reads the (deleted) source so it can never resurrect."""
    from minio_tpu.replication.pool import (OP_DELETE, OP_PUT,
                                            ReplicationTask)

    monkeypatch.setenv("MTPU_REPL_WORKERS", "4")
    src_srv, src_url, l1 = _boot(tmp_path, "osrc")
    dst_srv, dst_url, l2 = _boot(tmp_path, "odst")
    try:
        src = SigV4Client(src_url, ACCESS, SECRET)
        dst = SigV4Client(dst_url, ACCESS, SECRET)
        assert src.put("/origin").status_code == 200
        assert dst.put("/mirror").status_code == 200
        r = src.put("/minio/admin/v3/set-remote-target",
                    query={"bucket": "origin"},
                    data=json.dumps({"endpoint": dst_url,
                                     "accessKey": ACCESS,
                                     "secretKey": SECRET,
                                     "targetBucket": "mirror"}).encode())
        assert r.status_code == 200, r.text
        assert src.put("/origin", data=REPL_XML,
                       query={"replication": ""}).status_code == 200

        pool = src_srv.replication
        # Same key → same worker queue, PUT or DELETE alike.
        for i in range(10):
            tp = ReplicationTask("origin", f"docs/o{i}.bin", op=OP_PUT)
            td = ReplicationTask("origin", f"docs/o{i}.bin", op=OP_DELETE)
            assert pool._route(tp) == pool._route(td)
        # And the keys spread across more than one worker, so the
        # ordering below is exercised under real parallelism.
        assert len({pool._route(ReplicationTask("origin", f"docs/o{i}.bin"))
                    for i in range(10)}) > 1

        for i in range(10):
            key = f"docs/o{i}.bin"
            assert src.put(f"/origin/{key}",
                           data=(b"%d" % i) * 3000).status_code == 200
            assert src.delete(f"/origin/{key}").status_code == 204
        pool.drain(timeout=20)

        deadline = time.time() + 15
        leftover = {}
        while time.time() < deadline:
            leftover = {i: dst.get(f"/mirror/docs/o{i}.bin").status_code
                        for i in range(10)}
            if all(c == 404 for c in leftover.values()):
                break
            time.sleep(0.2)
        assert all(c == 404 for c in leftover.values()), leftover
    finally:
        src_srv.replication.close()
        dst_srv.replication.close()
        l1.call_soon_threadsafe(l1.stop)
        l2.call_soon_threadsafe(l2.stop)


# ---------------------------------------------------------------------
# Two-cluster OS-process harness (the chaos-gate tier: SIGKILL here is
# a real SIGKILL, and the inter-cluster link is a real socket)
# ---------------------------------------------------------------------


class _ReplNode:
    """One single-node cluster: an OS-process server owning 4 drives on
    its own port (mirrors tests/crash_cluster.py, scaled to the
    two-cluster replication topology)."""

    def __init__(self, work, name: str, env_extra: dict | None = None):
        self.work = Path(work) / name
        self.name = name
        self.env_extra = dict(env_extra or {})
        self.port = _free_port()
        self.proc: subprocess.Popen | None = None
        self.endpoints = []
        for d in range(4):
            p = self.work / f"d{d}"
            p.mkdir(parents=True, exist_ok=True)
            self.endpoints.append(f"http://127.0.0.1:{self.port}{p}")

    @property
    def node(self) -> str:
        """Advertised identity — the faultplane src/dst term."""
        return f"127.0.0.1:{self.port}"

    @property
    def url(self) -> str:
        return f"http://127.0.0.1:{self.port}"

    def env(self) -> dict:
        env = dict(os.environ)
        env.pop("MTPU_BATCHED_DATAPLANE", None)
        env.pop("MTPU_METAPLANE", None)
        env.update({
            "MTPU_ROOT_USER": ACCESS,
            "MTPU_ROOT_PASSWORD": SECRET,
            "JAX_PLATFORMS": "cpu",
            "MTPU_FAULT_INJECTION": "1",
        })
        env.update(self.env_extra)
        return env

    def start(self) -> None:
        log = open(self.work / "node.log", "ab")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "minio_tpu.s3.server",
             "--address", f"127.0.0.1:{self.port}",
             "--parity", "1", "--scan-interval", "0",
             *self.endpoints],
            stdout=log, stderr=log, env=self.env(), cwd="/root/repo")

    def kill9(self) -> None:
        assert self.proc is not None
        self.proc.send_signal(signal.SIGKILL)
        self.proc.wait(timeout=30)
        self.proc = None

    def stop(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.send_signal(signal.SIGKILL)
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                pass
        self.proc = None

    def wait_healthy(self, timeout: float = 90) -> None:
        deadline = time.monotonic() + timeout
        last = ""
        while time.monotonic() < deadline:
            assert self.proc is not None
            if self.proc.poll() is not None:
                time.sleep(1.0)
                self.start()
                continue
            try:
                r = requests.get(self.url + "/minio/health/live", timeout=2)
                if r.status_code == 200:
                    return
                last = f"HTTP {r.status_code}"
            except requests.RequestException as e:
                last = str(e)
            time.sleep(0.25)
        raise AssertionError(
            f"{self.name} not healthy in {timeout}s ({last}); log tail: " +
            (self.work / "node.log").read_text()[-2000:])

    def client(self) -> SigV4Client:
        return SigV4Client(self.url, ACCESS, SECRET)

    def fault(self, doc: dict) -> dict:
        r = self.client().post("/minio/admin/v3/faults",
                               data=json.dumps(doc).encode(), timeout=15)
        assert r.status_code == 200, f"fault {doc}: {r.text}"
        return r.json()

    def scrape(self) -> dict:
        from minio_tpu.chaos.invariants import parse_exposition

        r = self.client().get("/minio/v2/metrics/node", timeout=15)
        assert r.status_code == 200, r.text
        return parse_exposition(r.text)


def _metric(samples: dict, name: str, **labels):
    for (n, lbls), v in samples.items():
        if n == name and all(dict(lbls).get(k) == want
                             for k, want in labels.items()):
            return v
    return None


def _wire_replication(scli: SigV4Client, dcli: SigV4Client,
                      dst_url: str) -> None:
    assert scli.put("/origin").status_code == 200
    assert dcli.put("/mirror").status_code == 200
    r = scli.put("/minio/admin/v3/set-remote-target",
                 query={"bucket": "origin"},
                 data=json.dumps({"endpoint": dst_url, "accessKey": ACCESS,
                                  "secretKey": SECRET,
                                  "targetBucket": "mirror"}).encode())
    assert r.status_code == 200, r.text
    r = scli.put("/origin", data=REPL_XML, query={"replication": ""})
    assert r.status_code == 200, r.text


def _storm(led, rng: random.Random, lo: int, hi: int,
           deletes: tuple = ()) -> None:
    """Acked PUTs (and DELETEs) over docs/k{lo..hi}, every mutation
    ledgered intent-before-request, acked only on the 2xx."""
    for i in range(lo, hi):
        data = rng.randbytes(rng.randrange(200, 4000))
        assert led.put(f"docs/k{i}.bin", data).status_code == 200
    for i in deletes:
        assert led.delete(f"docs/k{i}.bin").status_code in (200, 204)


def _assert_converged(ledger, scli: SigV4Client, dcli: SigV4Client,
                      timeout: float = 60) -> None:
    """Every ledger-settled PUT reads back from the far cluster with
    the exact sha256 AND the source's ETag; every settled DELETE is
    absent. Zero lost acked intents."""
    from minio_tpu.chaos.ledger import digest

    pending = dict(ledger.expected())
    deadline = time.time() + timeout
    last: dict = {}
    while pending and time.time() < deadline:
        for key, st in list(pending.items()):
            r = dcli.get(f"/mirror/{key}")
            if st.must_exist:
                if (r.status_code == 200
                        and digest(r.content) == st.settled.sha256):
                    s = scli.get(f"/origin/{key}")
                    assert s.status_code == 200
                    assert s.headers.get("ETag") == r.headers.get("ETag")
                    del pending[key]
                    continue
            elif st.settled is not None and st.settled.op == "delete":
                if r.status_code == 404:
                    del pending[key]
                    continue
            else:
                del pending[key]   # in-flight tail: any outcome legal
                continue
            last[key] = r.status_code
        if pending:
            time.sleep(0.3)
    assert not pending, (
        f"unconverged after {timeout}s: "
        f"{ {k: last.get(k) for k in pending} }")


def _wait_backlog_zero(node: _ReplNode, timeout: float) -> None:
    deadline = time.time() + timeout
    backlog = None
    while time.time() < deadline:
        backlog = _metric(node.scrape(), "minio_tpu_replication_backlog")
        if backlog == 0:
            return
        time.sleep(0.5)
    raise AssertionError(f"replication backlog did not drain: {backlog}")


# ---------------------------------------------------------------------
# Crash matrix: SIGKILL between the S3 ack and the first replication
# attempt (real kill, mirroring test_metaplane's discipline)
# ---------------------------------------------------------------------


def test_sigkill_between_ack_and_attempt_replays(tmp_path):
    src = _ReplNode(tmp_path, "ksrc", {"MTPU_REPL_TEST_HOLD_S": "3",
                                       "MTPU_REPL_RESYNC_INTERVAL": "1"})
    dst_srv, dst_url, loop = _boot(tmp_path, "kdst")
    try:
        src.start()
        src.wait_healthy()
        scli = src.client()
        dcli = SigV4Client(dst_url, ACCESS, SECRET)
        _wire_replication(scli, dcli, dst_url)

        payload = b"ack-then-crash" * 64
        assert scli.put("/origin/docs/crash.bin",
                        data=payload).status_code == 200
        # The worker is pinned in the ack-to-attempt hold: the kill
        # lands after the S3 ack, before any replication I/O.
        src.kill9()
        assert dcli.get("/mirror/docs/crash.bin").status_code == 404

        # The intent was fsynced before the ack: it must be on disk.
        wal = src.work / "d0" / ".mtpu.sys" / "wal" / "replication.wal"
        assert wal.exists() and wal.stat().st_size > len(walfmt.MAGIC)

        # Restart: mount replay re-enqueues the intent and the acked
        # write converges — nothing lost.
        src.env_extra["MTPU_REPL_TEST_HOLD_S"] = "0"
        src.start()
        src.wait_healthy()
        deadline = time.time() + 30
        r = None
        while time.time() < deadline:
            r = dcli.get("/mirror/docs/crash.bin")
            if r.status_code == 200 and r.content == payload:
                break
            time.sleep(0.3)
        assert r is not None and r.status_code == 200
        assert r.content == payload
    finally:
        src.stop()
        dst_srv.replication.close()
        loop.call_soon_threadsafe(loop.stop)


# ---------------------------------------------------------------------
# The two-cluster chaos gate
# ---------------------------------------------------------------------

_GATE_ENV = {"MTPU_REPL_RESYNC_INTERVAL": "1",
             "MTPU_REPL_RETRY_INTERVAL": "0.2",
             "MTPU_REPL_RETRY_CAP": "0.5",
             "MTPU_REPL_RETRY_MAX": "2"}


def test_two_cluster_partition_sigkill_heal_convergence(tmp_path):
    """Partition the inter-cluster link (breaker trips OPEN, backlog
    accumulates bounded), SIGKILL the source mid-queue, restart (=
    heal: the partition lived in the dead process), and prove ledger
    convergence: every acked PUT ETag-equal on the far side, every
    acked DELETE absent, zero lost acked intents."""
    src = _ReplNode(tmp_path, "csrc", _GATE_ENV)
    dst = _ReplNode(tmp_path, "cdst")
    try:
        src.start()
        dst.start()
        src.wait_healthy()
        dst.wait_healthy()
        scli, dcli = src.client(), dst.client()
        _wire_replication(scli, dcli, dst.url)

        led = scli.ledgered("origin")
        rng = random.Random(0xa11ce)

        # Phase 1: healthy link.
        _storm(led, rng, 0, 6, deletes=(1,))

        # Phase 2: partition the inter-cluster link on the source.
        src.fault({"op": "partition", "name": "xlink",
                   "groups": [[src.node], [dst.node]]})
        # Acked writes keep landing — replication is async; the
        # journal absorbs the obligation.
        _storm(led, rng, 6, 12, deletes=(7,))

        # The breaker trips OPEN and the backlog is visible on the
        # node scrape, bounded by the journal (not by retries).
        deadline = time.time() + 30
        backlog = state = None
        while time.time() < deadline:
            s = src.scrape()
            backlog = _metric(s, "minio_tpu_replication_backlog")
            state = _metric(
                s, "minio_tpu_replication_target_breaker_state",
                target=dst.node)
            if backlog and backlog > 0 and state == 2:
                break
            time.sleep(0.5)
        assert backlog and backlog > 0, f"no backlog under partition: {backlog}"
        assert state == 2, f"breaker not OPEN under partition: {state}"

        # Phase 3: SIGKILL the source mid-queue. The restart heals the
        # link (the fault rules die with the process) and journal
        # replay + the 1s resync cadence drain the backlog.
        src.kill9()
        src.start()
        src.wait_healthy()
        _wait_backlog_zero(src, timeout=45)

        _assert_converged(led.ledger, scli, dcli)
        assert led.ledger.acked_count() >= 14
    finally:
        src.stop()
        dst.stop()


def test_two_cluster_disarmed_convergence(tmp_path):
    """The disarmed twin of the gate: same storm shape, no faultplane
    programming, no kills — convergence with a quiet breaker proves
    the fault machinery costs nothing when nothing fails."""
    src = _ReplNode(tmp_path, "dsrc", {"MTPU_REPL_RESYNC_INTERVAL": "1"})
    dst = _ReplNode(tmp_path, "ddst")
    try:
        src.start()
        dst.start()
        src.wait_healthy()
        dst.wait_healthy()
        scli, dcli = src.client(), dst.client()
        _wire_replication(scli, dcli, dst.url)

        led = scli.ledgered("origin")
        rng = random.Random(0xa11ce)
        _storm(led, rng, 0, 6, deletes=(1,))
        _storm(led, rng, 6, 12, deletes=(7,))

        _wait_backlog_zero(src, timeout=30)
        _assert_converged(led.ledger, scli, dcli)
        assert led.ledger.acked_count() >= 14

        s = src.scrape()
        # Breaker never left CLOSED; nothing shed, nothing retried.
        state = _metric(s, "minio_tpu_replication_target_breaker_state",
                        target=dst.node)
        assert state in (None, 0)
        assert (_metric(s, "minio_tpu_replication_shed_total")
                or 0) == 0
    finally:
        src.stop()
        dst.stop()
