"""Shared OS-process cluster harness (verify-healing.sh tier).

Three `python -m minio_tpu.s3.server` processes on real sockets — the
only tier where SIGKILL is a real SIGKILL. Extracted from
tests/test_crash_recovery.py so the composed chaos tier
(tests/test_chaos.py) can drive the same topology: the conftest
`crash_cluster` fixture boots it once per session and both modules
share the running fleet.

Every node boots with the chaos hooks armed but inert:
`MTPU_FAULT_INJECTION=1` (guarded admin faults endpoint) and
`MTPU_CHAOS_DRIVE_WRAP=1` (each local drive carries a programmable
NaughtyDisk between the disk-ID check and the health checker). The
chaos scheduler programs faults over the admin API and SIGKILLs through
this harness — one seed, three fault planes, real process death.

Topology: 3 nodes × 4 drives, one 12-wide set at parity 4 → write
quorum is exactly 8, so the cluster keeps accepting writes with one
node dead (the reference's 3-node/EC-split premise).
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
from pathlib import Path

import requests

from tests.s3client import SigV4Client

ACCESS, SECRET = "crashroot", "crashroot-secret1"
N_NODES = 3
DRIVES_PER_NODE = 4
BOOT_TIMEOUT = 90


def _free_port_block(n: int, span: int = 1000) -> list[int]:
    """n S3 ports whose +span RPC companions are also free."""
    out: list[int] = []
    base = 20000 + (os.getpid() * 7) % 20000
    p = base
    while len(out) < n and p < 64000:
        ok = True
        for cand in (p, p + span):
            s = socket.socket()
            try:
                s.bind(("127.0.0.1", cand))
            except OSError:
                ok = False
            finally:
                s.close()
        if ok:
            out.append(p)
        p += 1
    assert len(out) == n, "no free port block"
    return out


class Cluster:
    """Three server OS processes sharing one endpoint layout."""

    def __init__(self, work: Path):
        self.work = work
        self.ports = _free_port_block(N_NODES)
        self.procs: dict[int, subprocess.Popen | None] = {}
        self.endpoints = []
        for i in range(N_NODES):
            for d in range(DRIVES_PER_NODE):
                path = work / f"n{i}" / f"d{d}"
                path.parent.mkdir(parents=True, exist_ok=True)
                self.endpoints.append(
                    f"http://127.0.0.1:{self.ports[i]}{path}")

    def env(self) -> dict:
        env = dict(os.environ)
        # A leaked per-test gate override (monkeypatch active while the
        # session fixture boots) must not flip the cluster off its
        # defaults-on posture.
        env.pop("MTPU_BATCHED_DATAPLANE", None)
        env.pop("MTPU_METAPLANE", None)
        env.update({
            "MTPU_ROOT_USER": ACCESS,
            "MTPU_ROOT_PASSWORD": SECRET,
            "JAX_PLATFORMS": "cpu",
            # Composed chaos plane: fault surfaces armed (inert until
            # programmed over the guarded admin endpoint), MRF requeue
            # cadence tightened so degraded-write shards drain within
            # the test window once a partition lifts.
            "MTPU_FAULT_INJECTION": "1",
            "MTPU_CHAOS_DRIVE_WRAP": "1",
            "MTPU_MRF_RETRY_INTERVAL": "0.2",
            # HBM hot tier armed (opt-in gate): the storm's SIGKILLs,
            # partitions and heals all run with device-resident serving
            # live — the tier must never mask a lost or stale write
            # (the hottier cases in test_chaos.py + the storm
            # invariants). Admission threshold raised from the default
            # 1.5: the post-storm invariant checkers read EVERY acked
            # key 2-4x back-to-back, which at the default would queue a
            # full-namespace admission wave (background oracle reads)
            # in every node exactly while deep-heal convergence runs on
            # this 1-core host. 4 still admits the dedicated hottier
            # test's polled keys in a handful of reads.
            "MTPU_HOTTIER": "1",
            "MTPU_HOTTIER_MIN_HEAT": "4",
            # Both batch planes run at their DEFAULTS — on since the
            # pipeline convergence (PR 12) — so the tier-1 storm's
            # SIGKILL lands mid-coalesced-batch and between WAL-append/
            # shared-fsync/materialize exactly as production would see
            # it: zero-lost-acknowledged-write is proven with the
            # default pipeline serving, no special arming. (The
            # per-request oracle deployment is MTPU_*=0.)
            # Tight drive deadlines: an injected hang must walk the
            # drive FAULTY→OFFLINE within the bounded storm window
            # (deadlines stay adaptive — a genuinely slow sandbox
            # inflates them back out).
            "MTPU_DRIVE_DEADLINE_META": "2.5",
            "MTPU_DRIVE_DEADLINE_DATA": "5",
            "MTPU_DRIVE_DEADLINE_WALK": "5",
        })
        return env

    def node_name(self, i: int) -> str:
        """The node's advertised identity — faultplane src/dst terms."""
        return f"127.0.0.1:{self.ports[i]}"

    def start(self, i: int) -> None:
        log = open(self.work / f"node{i}.log", "ab")
        self.procs[i] = subprocess.Popen(
            [sys.executable, "-m", "minio_tpu.s3.server",
             "--address", f"127.0.0.1:{self.ports[i]}",
             "--parity", "4", "--scan-interval", "0",
             *self.endpoints],
            stdout=log, stderr=log, env=self.env(),
            cwd="/root/repo")

    def kill9(self, i: int) -> None:
        p = self.procs[i]
        assert p is not None
        p.send_signal(signal.SIGKILL)
        p.wait(timeout=30)
        self.procs[i] = None

    def stop_all(self) -> None:
        for i, p in self.procs.items():
            if p is not None and p.poll() is None:
                p.send_signal(signal.SIGKILL)
        for p in self.procs.values():
            if p is not None:
                try:
                    p.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    pass

    def base(self, i: int) -> str:
        return f"http://127.0.0.1:{self.ports[i]}"

    def wait_healthy(self, i: int, timeout: float = BOOT_TIMEOUT) -> None:
        deadline = time.monotonic() + timeout
        last = ""
        while time.monotonic() < deadline:
            p = self.procs[i]
            assert p is not None
            if p.poll() is not None:
                # Peer-bootstrap timeout exit while the other nodes are
                # still importing on a loaded host — relaunch, exactly
                # as systemd restarts the reference server. A genuine
                # crash loops until the deadline and raises with the log.
                time.sleep(1.0)
                self.start(i)
                continue
            try:
                r = requests.get(self.base(i) + "/minio/health/live",
                                 timeout=2)
                if r.status_code == 200:
                    return
                last = f"HTTP {r.status_code}"
            except requests.RequestException as e:
                last = str(e)
            time.sleep(0.5)
        raise AssertionError(
            f"node{i} not healthy in {timeout}s ({last}); log tail: " +
            (self.work / f"node{i}.log").read_text()[-2000:])

    def client(self, i: int) -> SigV4Client:
        return SigV4Client(self.base(i), ACCESS, SECRET)

    # -- chaos-plane helpers -------------------------------------------

    def fault(self, i: int, doc: dict) -> dict:
        """Program one fault document on node i's guarded admin
        endpoint (network rules, drive programs, clear_all)."""
        r = self.client(i).post("/minio/admin/v3/faults",
                                data=json.dumps(doc).encode(), timeout=15)
        assert r.status_code == 200, f"fault {doc} on node{i}: {r.text}"
        return r.json()

    def clear_faults(self, i: int) -> None:
        self.fault(i, {"op": "clear_all"})

    def admin_info(self, i: int) -> dict:
        r = self.client(i).get("/minio/admin/v3/info", timeout=15)
        assert r.status_code == 200, r.text
        return r.json()

    def deep_heal(self, i: int, bucket: str, timeout: float = 240) -> list:
        r = self.client(i).post(
            f"/minio/admin/v3/heal/{bucket}",
            data=json.dumps({"dryRun": False, "scanMode": "deep"}).encode(),
            timeout=timeout)
        assert r.status_code == 200, r.text
        return r.json()["items"]

    def scrape(self, i: int) -> str:
        r = self.client(i).get("/minio/v2/metrics/node", timeout=15)
        assert r.status_code == 200, r.text
        return r.text


def wait_drives_online(cl: Cluster, want: int, timeout: float = 60) -> None:
    """Until every live node's RPC fabric has reconnected all drives
    (the health plane re-probes at 1 Hz after a peer restart)."""
    deadline = time.monotonic() + timeout
    counts: list = []
    while time.monotonic() < deadline:
        counts = []
        for i in range(N_NODES):
            if cl.procs[i] is None:
                continue
            r = cl.client(i).get("/minio/admin/v3/info")
            counts.append(r.json().get("drivesOnline", 0)
                          if r.status_code == 200 else 0)
        if counts and all(n == want for n in counts):
            return
        time.sleep(0.5)
    raise AssertionError(f"drives did not come online: {counts} != {want}")


def restart_and_wait(cl: Cluster, i: int) -> None:
    cl.start(i)
    cl.wait_healthy(i)
    wait_drives_online(cl, N_NODES * DRIVES_PER_NODE)
