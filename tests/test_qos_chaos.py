"""Noisy-neighbor isolation gate (docs/QOS.md): a multi-tenant client
fleet against the front door, one tenant storming, victims measured by
scrape-delta per-tenant SLOs.

Tenancy is (access key, bucket): every fleet below shares the root
access key and splits into tenants by bucket, which is exactly the
granularity the QoS plane isolates.

Three tiers:
  1. armed gate — aggressor + 2 victim tenants; the storm window must
     move the aggressor's `tenant_quota` shed counter while each
     victim's scrape-delta p99 stays within 2x its unloaded baseline
     and its 5xx delta stays 0;
  2. disarmed oracle — same storm with MTPU_QOS unset: no QoS shed
     slugs move and data round-trips stay bit-exact (per-request
     behavior is the pre-QoS tree);
  3. @pytest.mark.slow soak — hundreds of concurrent lightweight
     clients across 3 tenants through the MixedWorkload ledger: zero
     torn reads, zero victim 5xx.
"""

from __future__ import annotations

import hashlib
import os
import threading
import time

import pytest

from minio_tpu.chaos import invariants
from tests.conftest import S3_ACCESS, S3_SECRET, free_port
from tests.s3client import SigV4Client

AGG_BKT, VIC_BKTS = "aggbkt", ("vicbkt1", "vicbkt2")
AGG_KEY = f"{S3_ACCESS}/{AGG_BKT}"
VIC_KEYS = tuple(f"{S3_ACCESS}/{b}" for b in VIC_BKTS)

# Per-tenant plane-admission quota (submissions/sec at EACH queue —
# the dataplane lane and every per-drive WAL queue meter separately).
# Victims pace well under it (a PUT+GET tick costs ~2 dataplane + ~1
# per-drive WAL submission); the unpaced aggressor's GIL-bound PUT rate
# (~100+/s) clears it by >2x, so the gate discriminates even when CPU
# contention halves the storm's throughput.
QOS_ENV = {"MTPU_QOS": "1", "MTPU_QOS_RATE_OPS": "50",
           "MTPU_QOS_BURST_S": "2"}


def _mk_sup(root, port, extra_env):
    from minio_tpu.frontdoor.supervisor import Supervisor

    env = {"MTPU_ROOT_USER": S3_ACCESS, "MTPU_ROOT_PASSWORD": S3_SECRET,
           "JAX_PLATFORMS": "cpu",
           "MTPU_METAPLANE": "1", "MTPU_BATCHED_DATAPLANE": "1"}
    env.update(extra_env)
    drives = [str(root / f"d{i}") for i in range(4)]
    return Supervisor(drives, f"127.0.0.1:{port}", workers=1, parity=1,
                      shared_lanes=False, log_dir=str(root), env=env)


class _Fleet:
    """Paced per-tenant client threads: PUT then readback-verified GET
    per tick. `pace=0` storms flat out."""

    def __init__(self, base: str, bucket: str, threads: int, pace: float,
                 puts_only: bool = False):
        self.base = base
        self.bucket = bucket
        self.n = threads
        self.pace = pace
        self.puts_only = puts_only
        self.codes: dict[int, int] = {}
        self.torn = 0
        self._mu = threading.Lock()
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []

    def _note(self, code: int) -> None:
        with self._mu:
            self.codes[code] = self.codes.get(code, 0) + 1

    def _worker(self, wid: int) -> None:
        c = SigV4Client(self.base, S3_ACCESS, S3_SECRET)
        body = os.urandom(8 << 10)
        sha = hashlib.sha256(body).hexdigest()
        if self.pace:
            # Stagger paced starts so a big fleet's first tick doesn't
            # land as one burst against the tenant's token bucket.
            self._stop.wait(self.pace * (wid % 8) / 8)
        i = 0
        while not self._stop.is_set():
            i += 1
            key = f"/{self.bucket}/w{wid}-k{i % 4}"
            try:
                r = c.put(key, data=body, timeout=30)
                self._note(r.status_code)
                if r.status_code == 200 and not self.puts_only:
                    g = c.get(key, timeout=30)
                    self._note(g.status_code)
                    if g.status_code == 200 and hashlib.sha256(
                            g.content).hexdigest() != sha:
                        with self._mu:
                            self.torn += 1
            except (ConnectionError, TimeoutError, OSError):
                self._note(599)
            if self.pace:
                self._stop.wait(self.pace)

    def run_for(self, seconds: float) -> "_Fleet":
        self._threads = [threading.Thread(target=self._worker, args=(w,))
                         for w in range(self.n)]
        for t in self._threads:
            t.start()
        time.sleep(seconds)
        self._stop.set()
        for t in self._threads:
            t.join(60)
        return self

    def count(self, lo: int, hi: int) -> int:
        with self._mu:
            return sum(n for c, n in self.codes.items() if lo <= c < hi)


def _scrape(client) -> dict:
    r = client.get("/minio/v2/metrics/node", timeout=15)
    assert r.status_code == 200, r.text
    return invariants.parse_exposition(r.text)


def _tenant_p99(window: dict, tenant: str) -> float:
    return invariants.histogram_quantile(
        window, "minio_tpu_tenant_request_seconds", 0.99,
        {"tenant": tenant})


def _tenant_5xx(window: dict, tenant: str) -> float:
    return invariants.counter_sum(
        window, "minio_tpu_tenant_requests_total",
        {"tenant": tenant, "code": "5xx"})


def _quota_sheds(window: dict, tenant: str) -> float:
    return invariants.counter_sum(
        window, "minio_tpu_admission_shed_total",
        {"cause": "tenant_quota", "tenant": tenant})


@pytest.fixture(scope="module")
def qfd(tmp_path_factory):
    root = tmp_path_factory.mktemp("qosfd")
    port = free_port()
    sup = _mk_sup(root, port, QOS_ENV)
    sup.start()
    base = f"http://127.0.0.1:{port}"
    c = SigV4Client(base, S3_ACCESS, S3_SECRET)
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        try:
            if c.get("/minio/health/live", timeout=5).status_code == 200:
                break
        except Exception:  # noqa: BLE001 - boot poll
            pass
        time.sleep(0.2)
    for b in (AGG_BKT, *VIC_BKTS):
        r = c.put(f"/{b}")
        assert r.status_code in (200, 409), r.text
    yield base, c
    sup.drain()


def test_noisy_neighbor_isolated_by_qos(qfd):
    """THE acceptance gate: under a one-tenant storm the aggressor
    sheds (per-tenant quota counter moves, aggressor eats 503s) while
    each victim's p99 stays within 2x its unloaded baseline and its
    5xx delta is zero."""
    base, admin = qfd

    # Phase 1 — unloaded baseline: victims alone, paced.
    before = _scrape(admin)
    vics = [_Fleet(base, b, threads=3, pace=0.3) for b in VIC_BKTS]
    ths = [threading.Thread(target=f.run_for, args=(6.0,)) for f in vics]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    baseline = invariants.delta(_scrape(admin), before)
    base_p99 = {k: _tenant_p99(baseline, k) for k in VIC_KEYS}
    for k, p in base_p99.items():
        assert 0 < p < float("inf"), f"no baseline signal for {k}: {p}"

    # Phase 2 — the storm: same victim load + an unpaced aggressor.
    before = _scrape(admin)
    vics = [_Fleet(base, b, threads=3, pace=0.3) for b in VIC_BKTS]
    agg = _Fleet(base, AGG_BKT, threads=16, pace=0.0, puts_only=True)
    ths = [threading.Thread(target=f.run_for, args=(8.0,))
           for f in (*vics, agg)]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    window = invariants.delta(_scrape(admin), before)

    # The aggressor shed: per-tenant quota counter moved, and the
    # client saw the 503 SlowDown mapping.
    assert _quota_sheds(window, AGG_KEY) > 0, (
        "aggressor never tripped tenant_quota — storm too weak?")
    assert agg.count(503, 504) > 0, dict(agg.codes)

    # The victims did not: zero 5xx server-side AND client-side, p99
    # within 2x the unloaded baseline (floored: a sub-ms baseline must
    # not turn scheduler jitter into a failure).
    for vic, fleet in zip(VIC_KEYS, vics):
        assert _tenant_5xx(window, vic) == 0, f"{vic} saw 5xx"
        assert fleet.count(500, 600) == 0, dict(fleet.codes)
        assert fleet.torn == 0
        allowed = max(2.0 * base_p99[vic], 0.5)
        got = _tenant_p99(window, vic)
        assert got <= allowed, (
            f"{vic} p99 {got:.3f}s > {allowed:.3f}s "
            f"(baseline {base_p99[vic]:.3f}s)")


def test_disarmed_is_the_pre_qos_tree(tmp_path):
    """MTPU_QOS unset: a storm trips no QoS shed slug (admission is the
    legacy bounded queue) and data stays bit-exact end to end."""
    port = free_port()
    sup = _mk_sup(tmp_path, port, {})
    sup.start()
    try:
        base = f"http://127.0.0.1:{port}"
        c = SigV4Client(base, S3_ACCESS, S3_SECRET)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            try:
                if c.get("/minio/health/live",
                         timeout=5).status_code == 200:
                    break
            except Exception:  # noqa: BLE001 - boot poll
                pass
            time.sleep(0.2)
        for b in (AGG_BKT, VIC_BKTS[0]):
            assert c.put(f"/{b}").status_code in (200, 409)
        before = _scrape(c)
        agg = _Fleet(base, AGG_BKT, threads=8, pace=0.0, puts_only=True)
        vic = _Fleet(base, VIC_BKTS[0], threads=2, pace=0.05)
        ths = [threading.Thread(target=f.run_for, args=(4.0,))
               for f in (agg, vic)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        window = invariants.delta(_scrape(c), before)
        assert invariants.counter_sum(
            window, "minio_tpu_admission_shed_total",
            {"cause": "tenant_quota"}) == 0
        assert vic.torn == 0 and agg.torn == 0
        # Bit-exactness spot check through the storm's aftermath.
        body = os.urandom(32 << 10)
        assert c.put(f"/{VIC_BKTS[0]}/final", data=body,
                     timeout=30).status_code == 200
        g = c.get(f"/{VIC_BKTS[0]}/final", timeout=30)
        assert g.status_code == 200 and g.content == body
    finally:
        sup.drain()


@pytest.mark.slow
def test_hundreds_of_clients_across_tenants_soak(qfd):
    """Scale proof: ~300 concurrent lightweight clients split across
    the 3 tenants (aggressor unpaced), through the armed front door —
    zero torn reads, zero victim 5xx, aggressor quota sheds move."""
    base, admin = qfd
    before = _scrape(admin)
    vics = [_Fleet(base, b, threads=90, pace=6.0) for b in VIC_BKTS]
    agg = _Fleet(base, AGG_BKT, threads=120, pace=0.0, puts_only=True)
    ths = [threading.Thread(target=f.run_for, args=(15.0,))
           for f in (*vics, agg)]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    window = invariants.delta(_scrape(admin), before)
    assert _quota_sheds(window, AGG_KEY) > 0
    for vic, fleet in zip(VIC_KEYS, vics):
        assert fleet.torn == 0
        assert _tenant_5xx(window, vic) == 0, f"{vic} saw 5xx"
