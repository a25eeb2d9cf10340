"""Multi-process front door (docs/FRONTDOOR.md).

Four tiers:
  1. in-process units — SO_REUSEPORT listeners, the shared-memory lane
     ring protocol (bit-exact vs the local plane, abandon/recovery),
     and the cross-segment WAL replay fold;
  2. a module-scoped 2-worker pool over shared tmp drives (router
     shard, batch planes + shared lanes armed): accept distribution,
     per-worker WAL segment ownership, and bit-exact PUT/GET against
     the single-process oracle under 16 concurrent clients;
  3. the worker_kill chaos storm: SIGKILL individual workers under a
     ledgered mixed workload — zero lost acknowledged writes, respawn
     within the SLO window;
  4. supervisor lifecycle — respawn-on-death and SIGTERM drain.
"""

from __future__ import annotations

import hashlib
import os
import socket
import threading
import time

import pytest

from minio_tpu import chaos
from minio_tpu.chaos import invariants, ledger as ledger_mod, schedule
from minio_tpu.chaos.workload import MixedWorkload
from minio_tpu.frontdoor import listener as fdl
from minio_tpu.frontdoor import shm
from minio_tpu.metaplane import wal as walfmt
from tests.conftest import S3_ACCESS, S3_SECRET, free_port
from tests.s3client import SigV4Client

SEED = chaos.master_seed(default=20260804)


# ---------------------------------------------------------------------------
# 1. units
# ---------------------------------------------------------------------------

def test_reuseport_listener_pair():
    """Two processes-worth of listeners may bind one port; accepts land
    on SOME member of the group (kernel balance policy is not asserted
    — gVisor routes degenerately, which is why `router` is the default
    shard policy)."""
    assert fdl.supports_reuseport()
    port = free_port()
    s1 = fdl.make_listener("127.0.0.1", port)
    s2 = fdl.make_listener("127.0.0.1", port)
    try:
        c = socket.create_connection(("127.0.0.1", port), timeout=5)
        got = []
        for s in (s1, s2):
            try:
                s.settimeout(0.3)
                conn, _ = s.accept()
                got.append(conn)
            except (socket.timeout, BlockingIOError):
                continue
        assert got, "no listener in the reuseport group saw the connect"
        for conn in got:
            conn.close()
        c.close()
    finally:
        s1.close()
        s2.close()


@pytest.fixture()
def lane_ring(monkeypatch):
    """A live ring + server (local plane) + client, torn down in order."""
    from minio_tpu import dataplane
    from minio_tpu.frontdoor import laneserver

    monkeypatch.setenv("MTPU_BATCHED_DATAPLANE", "1")
    ring = shm.Ring.create(nslots=8)
    server = laneserver.LaneServer(ring, worker=0)
    client = laneserver.LaneClient(shm.Ring.attach(ring.name),
                                   worker=1, nworkers=2)
    yield ring, server, client
    server.stop()
    client.close()
    ring.close()
    ring.unlink()
    dataplane.reset_global()


def test_ring_digest_and_encode_bitexact(lane_ring):
    from minio_tpu import dataplane

    _ring, _server, client = lane_ring
    oracle = dataplane.get_plane()

    chunks = [os.urandom(n) for n in (1, 500, 4096, 10_000)]
    got = client.digest_chunks(chunks, 16_384)
    want = oracle.digest_chunks(chunks, 16_384)
    assert [bytes(d) for d in got] == [bytes(d) for d in want]

    for k, m, sizes in ((4, 2, (100, 9_999, 40_000)), (2, 1, (7,))):
        blocks = [os.urandom(n) for n in sizes]
        rows, digs = client.begin_encode(
            k, m, 65_536, blocks, with_digests=True).wait()
        orows, odigs = oracle.begin_encode(
            k, m, 65_536, blocks, with_digests=True).wait()
        for bi in range(len(blocks)):
            for i in range(k + m):
                assert bytes(rows[bi][i]) == bytes(orows[bi][i])
            assert [bytes(d) for d in digs[bi]] == \
                [bytes(d) for d in odigs[bi]]


def test_ring_reconstruct_bitexact(lane_ring):
    """OP_RECONSTRUCT (PR 12): the heal shape — one failure pattern
    per batch — rides the ring bit-exact vs the in-process plane, with
    the rebuilt chunks' digests."""
    from minio_tpu import dataplane

    _ring, _server, client = lane_ring
    oracle = dataplane.get_plane()
    k, m, bs = 4, 2, 1 << 16
    n = k + m
    from minio_tpu.erasure.codec import ErasureCodec

    codec = ErasureCodec(k, m, bs)
    blocks = [os.urandom(sz) for sz in (40_000, 65_536, 123)]
    lens = [len(b) for b in blocks]
    enc = codec.encode_blocks(blocks)
    targets = (1, 4)
    rows = [[None if i in targets else bytes(row[i]) for i in range(n)]
            for row in enc]
    got, gdig = client.begin_reconstruct(
        k, m, bs, rows, lens, targets, with_digests=True).wait()
    want, wdig = oracle.begin_reconstruct(
        k, m, bs, rows, lens, targets, with_digests=True).wait()
    for bi in range(len(blocks)):
        assert [bytes(c) for c in got[bi]] == \
            [bytes(c) for c in want[bi]]
        assert [bytes(d) for d in gdig[bi]] == \
            [bytes(d) for d in wdig[bi]]


def test_ring_trace_id_hop_and_timelines(lane_ring):
    """The slot header carries the submitter's trace id across the
    process hop: the lane server serves under that context and records
    a detached `ring:<op>` timeline sharing it, while the submitter's
    own timeline gains a `ring_wait` detail stamp."""
    from minio_tpu import obs
    from minio_tpu.obs import flight

    _ring, _server, client = lane_ring
    flight.reset()
    rid = "RINGHOP000000001"
    tok = obs.set_trace_context(rid)
    flight.begin(rid, "GetObject")
    try:
        client.digest_chunks([os.urandom(1024)], 16_384)
    finally:
        flight.end()
        obs.reset_trace_context(tok)
    snaps = flight.collect(traceid=rid)
    apis = {s["api"] for s in snaps}
    assert {"GetObject", "ring:digest"} <= apis, apis
    sub = next(s for s in snaps if s["api"] == "GetObject")
    assert any(s["stage"] == "ring_wait" and s["plane"] == "ring"
               and not s["seq"] for s in sub["stages"]), sub["stages"]
    srv = next(s for s in snaps if s["api"] == "ring:digest")
    assert srv["trace_id"] == rid
    assert [s["stage"] for s in srv["stages"] if s["seq"]] == ["serve"]
    flight.reset()


def test_ring_serve_trace_record(lane_ring):
    """Worker 0's ring serves publish a `ring` trace record carrying
    the originating worker's trace id."""
    from minio_tpu import obs

    _ring, _server, client = lane_ring
    rid = "RINGREC000000001"
    got: list = []
    with obs.trace_bus().subscribe() as sub:
        tok = obs.set_trace_context(rid)
        try:
            client.digest_chunks([os.urandom(512)], 16_384)
        finally:
            obs.reset_trace_context(tok)
        deadline = time.time() + 5
        while time.time() < deadline:
            item = sub.get(timeout=0.25)
            if item is not None:
                got.append(item)
            if any(r.get("type") == "ring" for r in got):
                break
    rings = [r for r in got if r.get("type") == "ring"]
    assert rings, [r.get("type") for r in got]
    rec = rings[0]
    assert rec["plane"] == "ring" and rec["op"] == "digest"
    assert rec["ok"] and rec["durationNs"] >= 0
    assert rec.get("trace_id") == rid, rec


def test_ring_oversize_falls_back_local(lane_ring):
    _ring, _server, client = lane_ring
    big = [os.urandom(1 << 20)] * 2  # > req_cap of the default slot
    digs = client.digest_chunks(big, 1 << 20)
    assert len(digs) == 2 and len(bytes(digs[0])) == 32


def test_ring_abandon_recovery(monkeypatch):
    """A producer that times out (dead server) falls back locally and
    abandons its slot; a (re)started server recycles it to FREE."""
    from minio_tpu import dataplane
    from minio_tpu.frontdoor import laneserver

    monkeypatch.setenv("MTPU_BATCHED_DATAPLANE", "1")
    monkeypatch.setenv("MTPU_FRONTDOOR_RING_TIMEOUT_S", "0.2")
    ring = shm.Ring.create(nslots=4)
    client = laneserver.LaneClient(shm.Ring.attach(ring.name),
                                   worker=0, nworkers=4)
    try:
        chunks = [b"x" * 100]
        digs = client.digest_chunks(chunks, 128)  # no server: timeout
        assert len(bytes(digs[0])) == 32          # local result anyway
        assert any(ring.state(i) == shm.ABANDONED
                   for i in range(ring.nslots))
        server = laneserver.LaneServer(ring, worker=0)
        try:
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline and any(
                    ring.state(i) == shm.ABANDONED
                    for i in range(ring.nslots)):
                time.sleep(0.05)
            assert all(ring.state(i) == shm.FREE
                       for i in range(ring.nslots))
        finally:
            server.stop()
    finally:
        client.close()
        ring.close()
        ring.unlink()
        dataplane.reset_global()


def test_wal_fold_merged_cross_segment(tmp_path):
    """Per-worker segments fold into one replay work list: newest mt
    wins per key across segments; within one segment file order wins;
    a prefix tombstone drops other segments' OLDER records only."""
    w0 = str(tmp_path / "journal.w0.wal")
    w1 = str(tmp_path / "journal.w1.wal")

    def write(path, recs):
        fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_APPEND)
        os.write(fd, walfmt.MAGIC)
        walfmt.append_records(fd, [
            walfmt.frame_record(rt, mt, vol, key, raw)
            for rt, mt, vol, key, raw in recs])
        os.close(fd)

    write(w0, [
        (walfmt.REC_COMMIT, 10.0, "b", "k1", b"w0-old"),
        (walfmt.REC_COMMIT, 30.0, "b", "k2", b"w0-new"),
        # File order beats mt within a segment: k3 ends removed.
        (walfmt.REC_COMMIT, 50.0, "b", "k3", b"w0-create"),
        (walfmt.REC_REMOVE, 49.0, "b", "k3", b""),
    ])
    write(w1, [
        (walfmt.REC_COMMIT, 20.0, "b", "k1", b"w1-newer"),
        (walfmt.REC_COMMIT, 25.0, "b", "k2", b"w1-older"),
    ])
    merged = walfmt.fold_merged([w0, w1])
    assert merged[("b", "k1")].raw == b"w1-newer"      # cross-seg: mt
    assert merged[("b", "k2")].raw == b"w0-new"
    assert merged[("b", "k3")].rtype == walfmt.REC_REMOVE

    # Tombstone in w0 at mt=40 drops w1's older subtree records but
    # not w1's newer ones.
    w2 = str(tmp_path / "journal.w2.wal")
    w3 = str(tmp_path / "journal.w3.wal")
    write(w2, [(walfmt.REC_REMOVE_PREFIX, 40.0, "b", "tmp/s", b"")])
    write(w3, [
        (walfmt.REC_COMMIT, 35.0, "b", "tmp/s/part1", b"doomed"),
        (walfmt.REC_COMMIT, 45.0, "b", "tmp/s/part2", b"survives"),
    ])
    merged = walfmt.fold_merged([w2, w3])
    assert ("b", "tmp/s/part1") not in merged
    assert merged[("b", "tmp/s/part2")].raw == b"survives"


# ---------------------------------------------------------------------------
# 2. the 2-worker pool
# ---------------------------------------------------------------------------


class _FD:
    def __init__(self, sup, port):
        self.sup = sup
        self.port = port
        self.base = f"http://127.0.0.1:{port}"

    def client(self) -> SigV4Client:
        return SigV4Client(self.base, S3_ACCESS, S3_SECRET)

    def wait_pool(self, n: int, timeout: float = 60.0) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if (self.sup.alive_count() == n
                    and self.sup.router is not None
                    and len(self.sup.router.workers_connected()) == n):
                return
            time.sleep(0.2)
        raise AssertionError(
            f"pool never healed to {n}: alive={self.sup.alive()} "
            f"registered={self.sup.router.workers_connected()}")


@pytest.fixture(scope="module")
def fd(tmp_path_factory):
    from minio_tpu.frontdoor.supervisor import Supervisor

    root = tmp_path_factory.mktemp("frontdoor")
    drives = [str(root / f"d{i}") for i in range(4)]
    port = free_port()
    sup = Supervisor(
        drives, f"127.0.0.1:{port}", workers=2, parity=1,
        shared_lanes=True, log_dir=str(root),
        env={"MTPU_ROOT_USER": S3_ACCESS, "MTPU_ROOT_PASSWORD": S3_SECRET,
             "JAX_PLATFORMS": "cpu",
             "MTPU_METAPLANE": "1", "MTPU_BATCHED_DATAPLANE": "1",
             # Keep PUT encodes on the device-codec plane (the native
             # C++ lane would serve them host-side) so non-zero workers
             # route codec work over the shared-lane shm ring.
             "MTPU_NATIVE_PLANE": "0"})
    sup.start()
    f = _FD(sup, port)
    f.wait_pool(2)
    r = f.client().put("/fdbkt")
    assert r.status_code in (200, 409), r.text
    yield f
    sup.drain()


def test_accept_distribution(fd):
    """Fresh connections round-robin across BOTH workers (the router
    passes fds deterministically; every response says who served it)."""
    seen = {}
    for _ in range(12):
        c = socket.create_connection(("127.0.0.1", fd.port), timeout=10)
        c.sendall(b"GET /minio/health/live HTTP/1.1\r\nHost: x\r\n"
                  b"Connection: close\r\n\r\n")
        data = b""
        while True:
            part = c.recv(4096)
            if not part:
                break
            data += part
        c.close()
        for line in data.split(b"\r\n"):
            if line.lower().startswith(b"x-mtpu-worker"):
                wid = line.split(b":")[1].strip().decode()
                seen[wid] = seen.get(wid, 0) + 1
    assert set(seen) == {"0", "1"}, seen


def test_wal_single_writer_segments(fd):
    """Every worker journals into its OWN per-drive WAL segment — the
    cross-process single-writer contract is ownership of the file, not
    a lock around a shared one."""
    cls = [fd.client() for _ in range(4)]
    for i, c in enumerate(cls * 2):
        r = c.put(f"/fdbkt/seg-{i}", data=os.urandom(8_192))
        assert r.status_code == 200, r.text
    drive0 = fd.sup.drives[0]
    wal_dir = os.path.join(drive0, ".mtpu.sys", "wal")
    segs = sorted(n for n in os.listdir(wal_dir)
                  if n.startswith("journal") and n.endswith(".wal"))
    assert segs == ["journal.w0.wal", "journal.w1.wal"], segs


def test_flight_timeline_cross_worker_queryable(fd):
    """Acceptance: a request served by a NON-ZERO worker (its codec
    work routed over the shm ring) yields a stage timeline whose
    sequential stages sum to within 10% of e2e, queryable through the
    admin perf endpoint from ANY worker — the flight-spool fan-in."""
    rid = wid = None
    for i in range(12):
        c = fd.client()
        # Inside the dataplane serving gate (chunk <= 64 KiB at k=3),
        # so a non-zero worker routes the encode over the shm ring.
        r = c.put(f"/fdbkt/flt-{i}", data=os.urandom(120_000))
        assert r.status_code == 200, r.text
        w = r.headers.get("X-Mtpu-Worker", "0")
        if w != "0":
            rid, wid = r.headers["x-amz-request-id"], int(w)
            break
    assert rid, "router never placed a PUT on a non-zero worker"
    found = None
    deadline = time.monotonic() + 20
    while found is None and time.monotonic() < deadline:
        # Fresh connections round-robin, so this interrogates BOTH
        # workers; each must answer for the whole pool via the spools.
        r = fd.client().get("/minio/admin/v3/perf/timeline",
                            query={"traceid": rid, "all": "false"})
        assert r.status_code == 200, r.text
        tls = [s for s in r.json()["timelines"]
               if s["trace_id"] == rid and s["api"] == "PutObject"]
        if tls:
            found = tls[0]
            break
        time.sleep(0.25)
    assert found, f"timeline for {rid} not queryable from the pool"
    assert found["worker"] == wid
    stages = {s["stage"] for s in found["stages"]}
    assert {"auth", "rx_drain", "encode", "commit",
            "resp_drain"} <= stages, stages
    seq = sum(s["dur_ns"] for s in found["stages"] if s["seq"])
    assert abs(seq - found["e2e_ns"]) <= 0.1 * found["e2e_ns"], (
        seq, found["e2e_ns"])


def test_put_get_bitexact_vs_single_process_oracle(fd, client, bucket):
    """16 concurrent clients: everything PUT through the pool reads
    back bit-exact, and ETags match the single-process oracle server
    for identical payloads (same pipeline, N processes)."""
    rng_payloads = {
        f"ox-{i}": os.urandom(sz)
        for i, sz in enumerate([700, 9_000, 70_000, 300_001] * 4)
    }
    results: dict[str, tuple] = {}
    errs: list = []

    def one(key: str, payload: bytes) -> None:
        try:
            c = fd.client()
            r = c.put(f"/fdbkt/{key}", data=payload)
            assert r.status_code == 200, r.text
            etag = r.headers.get("ETag", "")
            g = c.get(f"/fdbkt/{key}")
            assert g.status_code == 200
            results[key] = (etag, hashlib.sha256(g.content).digest())
        except Exception as e:  # noqa: BLE001 - re-raised in the test
            errs.append((key, e))

    threads = [threading.Thread(target=one, args=(k, v))
               for k, v in rng_payloads.items()]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not errs, errs[:3]
    for key, payload in rng_payloads.items():
        etag, digest = results[key]
        assert digest == hashlib.sha256(payload).digest(), key
        # Same payload through the single-process oracle: same ETag.
        ro = client.put(f"/{bucket}/{key}", data=payload)
        assert ro.status_code == 200
        assert ro.headers.get("ETag", "") == etag, key


# ---------------------------------------------------------------------------
# 3. worker_kill chaos storm
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_worker_kill_storm_zero_lost_acks(fd, tmp_path):
    """SIGKILL individual front-door workers mid-storm under a ledgered
    mixed workload: zero lost acknowledged writes, no torn reads, and
    the pool respawns to full width inside the SLO window."""
    bucket = "fdstorm"
    r = fd.client().put(f"/{bucket}")
    assert r.status_code in (200, 409), r.text

    prog = schedule.ChaosProgram(SEED)
    prog.add(1.5, schedule.WORKER_KILL, "1")
    prog.add(4.0, schedule.WORKER_KILL, "0")
    prog.add(6.5, schedule.WORKER_KILL, "1")
    assert prog.schedule() == prog.schedule()  # preview is stable

    sched = schedule.ChaosScheduler(prog, {
        schedule.WORKER_KILL:
            lambda ev: fd.sup.kill_worker(int(ev.target)),
    })

    lgr = ledger_mod.WriteLedger(path=str(tmp_path / "fd-ledger.jsonl"))
    clients = [fd.client() for _ in range(2)]
    fleet = MixedWorkload(
        lambda _n=iter(range(10 ** 9)): clients[next(_n) % 2],
        lgr, bucket, seed=SEED, workers=4, op_timeout=60.0)

    sched.start()
    try:
        fleet.run_for(9.0)
    finally:
        sched.stop()
        assert sched.join(30.0)
    assert sched.errors() == [], sched.errors()
    assert sched.applied() == prog.schedule()

    # Respawn SLO: the supervisor heals the pool to full width.
    t0 = time.monotonic()
    fd.wait_pool(2, timeout=30.0)
    respawn_s = time.monotonic() - t0

    assert lgr.acked_count() >= 10, (
        f"storm too quiet: {lgr.describe()} "
        f"(ops {fleet.stats.describe()})")
    assert not fleet.stats.violations, (
        f"in-storm read violations {fleet.stats.violations[:5]} — "
        f"reproduce with MTPU_CHAOS_SEED={SEED}")

    verify = fd.client()

    def get_fn(key):
        r = verify.get(f"/{bucket}/{key}", timeout=60)
        return r.status_code, (r.content if r.status_code == 200 else b"")

    invariants.check_acknowledged_writes(get_fn, lgr,
                                         seed=SEED).assert_ok()
    lgr.close()
    assert respawn_s < 30.0, f"respawn took {respawn_s:.1f}s"


# ---------------------------------------------------------------------------
# 4. supervisor lifecycle
# ---------------------------------------------------------------------------


def test_respawn_and_graceful_drain(fd):
    """An unexpectedly dead worker respawns with a fresh pid; SIGTERM
    drain stops accepts first and workers exit 0 (WAL segments
    checkpointed, not killed). Runs against a PRIVATE 1-worker pool so
    the shared fixture keeps serving the other tests."""
    from minio_tpu.frontdoor.supervisor import Supervisor

    import tempfile

    root = tempfile.mkdtemp(prefix="mtpu-fd-drain-")
    port = free_port()
    sup = Supervisor(
        [os.path.join(root, f"d{i}") for i in range(4)],
        f"127.0.0.1:{port}", workers=1, parity=1,
        env={"MTPU_ROOT_USER": S3_ACCESS, "MTPU_ROOT_PASSWORD": S3_SECRET,
             "JAX_PLATFORMS": "cpu",
             "MTPU_METAPLANE": "1"})
    sup.start()
    try:
        pid0 = sup.pid(0)
        assert pid0 is not None
        sup.kill_worker(0)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            p = sup.pid(0)
            if p is not None and p != pid0:
                break
            time.sleep(0.2)
        assert sup.pid(0) not in (None, pid0), "worker never respawned"
        c = SigV4Client(f"http://127.0.0.1:{port}", S3_ACCESS, S3_SECRET)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            try:
                if c.put("/drainbkt").status_code in (200, 409):
                    break
            except Exception:  # noqa: BLE001 - respawn window
                pass
            time.sleep(0.3)
        procs = dict(sup.procs)
    finally:
        sup.drain()
    p0 = procs[0]
    assert p0 is not None and p0.poll() == 0, (
        f"drained worker exit code {p0.poll()!r} (want 0: graceful)")
    import shutil

    shutil.rmtree(root, ignore_errors=True)
