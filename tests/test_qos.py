"""Per-tenant QoS plane (docs/QOS.md): scheduler units, identity
plumbing, and the closed shed-slug vocabulary.

Layout:

- FairQueue/TokenBucket/RingGate units — DRR weight ratios, backlog
  shares, quotas, the control/flush barrier, queue.Queue API parity;
- tenant identity — contextvar bind/reset, shm slot tag round-trip,
  weight-spec parsing, arming factories (disarmed == plain queue);
- shed coverage — every (plane, cause) slug the tree emits has a
  direct test here or in test_pipeline_converged.py asserting the 503
  SlowDown mapping AND the per-tenant metric increment:
    dataplane/lane_full     test_pipeline_converged.py
    metaplane/wal_full      test_pipeline_converged.py
    dataplane/closed        test_closed_dataplane_sheds...
    metaplane/wal_flush_full test_blob_lane_flush_full_sheds...
    dataplane/tenant_quota  test_dataplane_tenant_quota...
    metaplane/tenant_quota  test_metaplane_tenant_quota...
- admin surfaces — top/api tenant column, perf/timeline tenant filter.

The noisy-neighbor isolation gate (multi-tenant fleet against the
front door) lives in test_qos_chaos.py.
"""

from __future__ import annotations

import os
import queue
import threading
import time

import pytest

from minio_tpu import qos
from minio_tpu.obs import flight
from minio_tpu.qos.scheduler import FairQueue, QuotaFull, RingGate, TokenBucket
from minio_tpu.storage.local import LocalDrive
from minio_tpu.utils import admission
from minio_tpu.utils import errors as se


def _shed_value(plane: str, cause: str, tenant: str = "-") -> int:
    return admission._SHED.labels(plane=plane, cause=cause,
                                  tenant=tenant).value


# ---------------------------------------------------------------------------
# TokenBucket
# ---------------------------------------------------------------------------

def test_token_bucket_rate_zero_is_unlimited():
    b = TokenBucket(0, 0)
    assert all(b.take(1.0) for _ in range(10_000))


def test_token_bucket_burst_then_refill():
    b = TokenBucket(1000.0, 2.0)   # 2-token burst, fast refill
    assert b.take(1.0) and b.take(1.0)
    assert not b.take(1.0)          # burst exhausted instantly
    time.sleep(0.01)                # 1000/s refills within 10 ms
    assert b.take(1.0)


# ---------------------------------------------------------------------------
# FairQueue — scheduling
# ---------------------------------------------------------------------------

def _fq(cap=16, **kw):
    kw.setdefault("tenant_of", lambda it: it[0])
    return FairQueue(cap, **kw)


def test_fairqueue_fifo_within_one_tenant():
    q = _fq()
    for i in range(5):
        q.put_nowait(("a", i))
    assert [q.get_nowait()[1] for _ in range(5)] == [0, 1, 2, 3, 4]
    assert q.empty() and q.qsize() == 0


def test_fairqueue_drr_serves_by_weight():
    """Backlogged 2:1-weighted tenants drain 2:1 over any window."""
    q = _fq(cap=64, weights={"a": 2.0, "b": 1.0}, quantum=2)
    for i in range(16):
        q.put_nowait(("a", i))
        q.put_nowait(("b", i))
    first12 = [q.get_nowait()[0] for _ in range(12)]
    assert first12.count("a") == 8 and first12.count("b") == 4


def test_fairqueue_single_tenant_work_conserving():
    """A sole tenant gets the whole cap — plain-queue depth parity."""
    q = _fq(cap=8)
    for i in range(8):
        q.put_nowait(("a", i))
    with pytest.raises(queue.Full):
        q.put_nowait(("a", 99))


def test_fairqueue_newcomer_admitted_past_saturated_tenant():
    """The headroom above cap exists exactly so a tenant that filled
    its (sole-tenant) share cannot Full a newcomer."""
    q = _fq(cap=8)
    for i in range(8):
        q.put_nowait(("a", i))
    q.put_nowait(("b", 0))          # admitted from the 2x-cap headroom
    with pytest.raises(queue.Full):
        q.put_nowait(("a", 99))     # the hog stays capped
    assert q.backlog_by_tenant() == {"a": 8, "b": 1}


def test_fairqueue_share_tracks_weights():
    """With both tenants backlogged, per-tenant admission caps split
    the cap by weight."""
    q = _fq(cap=12, weights={"a": 2.0, "b": 1.0})
    q.put_nowait(("a", 0))
    q.put_nowait(("b", 0))
    for i in range(1, 8):           # a's share: 12 * 2/3 = 8
        q.put_nowait(("a", i))
    with pytest.raises(queue.Full):
        q.put_nowait(("a", 99))
    for i in range(1, 4):           # b's share: 12 * 1/3 = 4
        q.put_nowait(("b", i))
    with pytest.raises(queue.Full):
        q.put_nowait(("b", 99))


def test_fairqueue_starvation_bound():
    """A backlogged lane is served within one DRR round regardless of
    how much the heavy lane holds."""
    q = _fq(cap=64, weights={"heavy": 8.0, "light": 1.0}, quantum=1)
    for i in range(40):
        q.put_nowait(("heavy", i))
    q.put_nowait(("light", 0))
    # One full round serves at most quantum*w(heavy)=8 heavy items
    # before light's visit.
    drained = [q.get_nowait()[0] for _ in range(10)]
    assert "light" in drained


def test_fairqueue_ops_quota_raises_quotafull():
    q = _fq(cap=16, rate_ops=1000.0, burst_s=1 / 1000.0)  # burst = 1
    q.put_nowait(("a", 0))
    with pytest.raises(QuotaFull):
        q.put_nowait(("a", 1))
    # QuotaFull IS queue.Full — legacy except-clauses keep working.
    assert issubclass(QuotaFull, queue.Full)
    # ...and put(block=True) re-raises immediately instead of parking.
    t0 = time.monotonic()
    with pytest.raises(QuotaFull):
        q.put(("a", 2), timeout=5.0)
    assert time.monotonic() - t0 < 1.0


def test_fairqueue_bytes_quota():
    q = FairQueue(16, tenant_of=lambda it: it[0],
                  cost_of=lambda it: it[1],
                  rate_bytes=1000.0, burst_s=1.0)   # 1000-byte burst
    q.put_nowait(("a", 800))
    with pytest.raises(QuotaFull):
        q.put_nowait(("a", 800))    # only ~200 tokens left
    q.put_nowait(("b", 800))        # buckets are per tenant


def test_fairqueue_quota_does_not_meter_other_tenants():
    q = _fq(cap=16, rate_ops=1000.0, burst_s=1 / 1000.0)
    q.put_nowait(("a", 0))
    q.put_nowait(("b", 0))          # a's empty bucket is not b's problem


def test_fairqueue_control_never_quota_checked():
    CTL = ("flush", object())
    q = FairQueue(2, tenant_of=lambda it: it[0],
                  is_control=lambda it: it[0] == "flush",
                  rate_ops=0.001, burst_s=2_000.0)   # burst = 2, ~no refill
    q.put_nowait(("a", 0))
    q.put_nowait(("a", 1))          # lane at cap, bucket empty...
    q.put_nowait(CTL)               # ...control still admitted
    q.get_nowait()                  # free a share slot: quota decides now
    with pytest.raises(QuotaFull):
        q.put_nowait(("a", 2))


def test_fairqueue_control_barrier_orders_after_predecessors():
    """A flush-style control item is released only after every item
    enqueued before it — the WAL barrier survives DRR reordering."""
    q = FairQueue(32, weights={"a": 4.0, "b": 1.0},
                  tenant_of=lambda it: it[0],
                  is_control=lambda it: it[0] == "flush")
    for i in range(4):
        q.put_nowait(("a", i))
        q.put_nowait(("b", i))
    q.put_nowait(("flush", "CTL"))
    # Post-barrier items may legally drain before the control releases
    # (the barrier covers predecessors only) — present to exercise the
    # head-seq comparison, not ordered against CTL.
    q.put_nowait(("a", 99))
    out = [q.get_nowait() for _ in range(10)]
    ctl_at = out.index(("flush", "CTL"))
    before = out[:ctl_at]
    assert {("a", i) for i in range(4)} <= set(before)
    assert {("b", i) for i in range(4)} <= set(before)


def test_fairqueue_barrier_is_full_ordering_fence():
    """A tombstone-style barrier rides its tenant lane but is a strict
    ordering fence: it drains after every item enqueued before it and
    before every item enqueued after it, even when DRR weights would
    otherwise reorder across lanes — WAL replay folds resolve
    dominance by file order, so file order must equal submit order
    exactly at tombstones."""
    q = FairQueue(64, weights={"a": 8.0, "b": 1.0},
                  tenant_of=lambda it: it[0],
                  is_barrier=lambda it: it[1] == "TOMB")
    for i in range(8):
        q.put_nowait(("b", i))          # light lane, enqueued first
    q.put_nowait(("a", "TOMB"))         # tombstone in the heavy lane
    for i in range(4):
        q.put_nowait(("a", i))          # heavy lane, enqueued after
    out = [q.get_nowait() for _ in range(13)]
    at = out.index(("a", "TOMB"))
    assert set(out[:at]) == {("b", i) for i in range(8)}
    assert set(out[at + 1:]) == {("a", i) for i in range(4)}


def test_fairqueue_capacity_reject_does_not_burn_quota():
    """A put bounced off the backlog share must not debit the token
    bucket: a blocking put() re-tries admission on every wakeup, and
    debit-first would push a share-pinned tenant into spurious
    QuotaFull sheds off its own rejected attempts."""
    # rate ~0 so nothing refills during the test; burst carries 3.
    q = _fq(cap=2, rate_ops=0.001, burst_s=3_000.0)
    q.put_nowait(("a", 0))
    q.put_nowait(("a", 1))              # share full; one token left
    for _ in range(5):
        with pytest.raises(queue.Full) as ei:
            q.put_nowait(("a", 2))
        assert not isinstance(ei.value, QuotaFull)   # capacity, not quota
    q.get_nowait()
    q.put_nowait(("a", 2))              # the last token was preserved...
    q.get_nowait()
    with pytest.raises(QuotaFull):
        q.put_nowait(("a", 3))          # ...and only that one


def test_fairqueue_byte_quota_reject_refunds_op_token():
    q = FairQueue(16, tenant_of=lambda it: it[0],
                  cost_of=lambda it: it[1],
                  rate_ops=0.001, burst_s=2_000.0,   # 2 op tokens
                  rate_bytes=0.001)                  # 2 byte tokens
    for _ in range(3):
        with pytest.raises(QuotaFull):
            q.put_nowait(("a", 500))    # byte reject refunds the op take
    q.put_nowait(("a", 1))
    q.put_nowait(("a", 1))              # both op tokens survived


def test_fairqueue_get_timeout_and_blocking_handoff():
    q = _fq()
    with pytest.raises(queue.Empty):
        q.get(timeout=0.05)
    with pytest.raises(queue.Empty):
        q.get_nowait()
    got = []
    t = threading.Thread(target=lambda: got.append(q.get(timeout=5)))
    t.start()
    q.put_nowait(("a", 7))
    t.join(5)
    assert got == [("a", 7)]


def test_fairqueue_blocked_put_wakes_on_get():
    q = _fq(cap=2)
    q.put_nowait(("a", 0))
    q.put_nowait(("a", 1))
    done = threading.Event()

    def blocked_put():
        q.put(("a", 2), timeout=10)
        done.set()

    t = threading.Thread(target=blocked_put)
    t.start()
    time.sleep(0.05)
    assert not done.is_set()
    q.get_nowait()                  # frees a slot -> put completes
    assert done.wait(5)
    t.join(5)


def test_fairqueue_unattributed_items_ride_system_lane():
    q = FairQueue(8)                # no tenant_of at all
    q.put_nowait("x")
    assert q.backlog_by_tenant() == {"-": 1}
    assert q.get_nowait() == "x"


# ---------------------------------------------------------------------------
# RingGate
# ---------------------------------------------------------------------------

def test_ringgate_share_cap_and_release():
    g = RingGate(4)
    assert all(g.acquire("a") for _ in range(4))   # sole tenant: all slots
    assert not g.acquire("a")
    g.release("a")
    assert g.acquire("a")
    for _ in range(4):
        g.release("a")
    # Two active tenants split the slots by (equal) weight.
    assert g.acquire("a") and g.acquire("a")
    assert g.acquire("b") and g.acquire("b")
    assert not g.acquire("a")


def test_ringgate_rate_bucket():
    g = RingGate(64, rate_ops=1000.0, burst_s=2 / 1000.0)  # burst = 2
    assert g.acquire("a") and g.acquire("a")
    assert not g.acquire("a")       # over quota: denied, caller falls back
    g.release("a")
    g.release("a")


# ---------------------------------------------------------------------------
# Tenant identity + knobs
# ---------------------------------------------------------------------------

def test_tenant_bind_reset_and_key_shapes():
    assert qos.current_key() == qos.UNATTRIBUTED
    tok = qos.bind("alice", "photos")
    try:
        assert qos.current_key() == "alice/photos"
        assert qos.current().access_key == "alice"
    finally:
        qos.reset(tok)
    assert qos.current_key() == qos.UNATTRIBUTED
    tok = qos.bind("alice")         # no bucket (ListBuckets, admin)
    try:
        assert qos.current_key() == "alice"
    finally:
        qos.reset(tok)


def test_tenant_tag_round_trip_and_truncation():
    tok = qos.bind("ak", "b")
    try:
        tag = qos.tenant_tag()
        assert tag == b"ak/b" and len(tag) <= qos.TAG_LEN
        assert qos.key_from_tag(tag) == "ak/b"
        assert qos.key_from_tag(tag + b"\x00" * 8) == "ak/b"
    finally:
        qos.reset(tok)
    assert qos.tenant_tag() == b""
    assert qos.key_from_tag(b"") == qos.UNATTRIBUTED
    tok = qos.bind("averylongaccesskey", "bucket")
    try:
        assert len(qos.tenant_tag()) == qos.TAG_LEN   # truncated, not error
    finally:
        qos.reset(tok)


def test_bind_key_round_trip():
    tok = qos.bind_key("ak/bkt")
    try:
        t = qos.current()
        assert (t.access_key, t.bucket) == ("ak", "bkt")
    finally:
        qos.reset(tok)
    tok = qos.bind_key(qos.UNATTRIBUTED)
    try:
        assert qos.current() is None
    finally:
        qos.reset(tok)


def test_metric_key_folds_past_cardinality_cap(monkeypatch):
    """The metric-label backstop: an unauthenticated scanner sweeping
    bucket paths mints tenant keys without bound, but the metric
    registry folds everything past the cap into one overflow label
    (scheduling lanes have their own 4096 backstop; this is the
    time-series side)."""
    monkeypatch.setattr(qos, "_metric_tenants", set())
    monkeypatch.setattr(qos, "_METRIC_TENANTS_CAP", 3)
    assert [qos.metric_key(f"scan/b{i}") for i in range(3)] == \
        ["scan/b0", "scan/b1", "scan/b2"]
    assert qos.metric_key("scan/b3") == qos.METRIC_OVERFLOW
    assert qos.metric_key("scan/b1") == "scan/b1"   # known keys keep labels
    assert qos.metric_key(qos.UNATTRIBUTED) == qos.UNATTRIBUTED
    tok = qos.bind("late", "bkt")
    try:        # no-arg form reads the bound tenant, same fold
        assert qos.metric_key() == qos.METRIC_OVERFLOW
    finally:
        qos.reset(tok)


def test_parse_weights_drops_malformed():
    spec = "a=2,b/photos=0.5,junk,c=notanum,=3,d=-1,*=1.5"
    assert qos.parse_weights(spec) == {"a": 2.0, "b/photos": 0.5,
                                       "*": 1.5}
    assert qos.parse_weights("") == {}


def test_weight_lookup_access_key_prefix_fallback():
    q = FairQueue(8, weights={"ak": 3.0, "*": 0.5})
    assert q._weight_of("ak/somebucket") == 3.0   # access-key fallback
    assert q._weight_of("other/b") == 0.5          # wildcard
    q2 = FairQueue(8)
    assert q2._weight_of("anyone") == 1.0          # default weight


def test_plane_queue_disarmed_is_plain_queue(monkeypatch):
    monkeypatch.delenv("MTPU_QOS", raising=False)
    q = qos.plane_queue("dataplane", 7)
    assert type(q) is queue.Queue and q.maxsize == 7
    assert qos.ring_gate(8) is None
    assert not qos.armed()


def test_plane_queue_armed_reads_knobs(monkeypatch):
    monkeypatch.setenv("MTPU_QOS", "1")
    monkeypatch.setenv("MTPU_QOS_WEIGHTS", "ak=2")
    monkeypatch.setenv("MTPU_QOS_QUANTUM", "9")
    q = qos.plane_queue("dataplane", 7)
    assert isinstance(q, FairQueue)
    assert q.cap == 7 and q.quantum == 9 and q._weights == {"ak": 2.0}
    assert isinstance(qos.ring_gate(8), RingGate)
    assert qos.armed()


# ---------------------------------------------------------------------------
# Closed shed vocabulary + per-cause coverage
# ---------------------------------------------------------------------------

def test_admission_registries_are_the_closed_vocabulary():
    assert admission.ADMISSION_PLANES == {"dataplane", "metaplane"}
    assert admission.ADMISSION_CAUSES == {
        "lane_full", "wal_full", "wal_flush_full", "closed",
        "tenant_quota"}


def test_shed_returns_slowdown_mapped_error_and_counts_tenant():
    tok = qos.bind("shedme", "b")
    try:
        before = _shed_value("dataplane", "lane_full", "shedme/b")
        err = admission.shed("dataplane", "lane_full", "unit probe")
        assert isinstance(err, se.OperationTimedOut)
        assert _shed_value("dataplane", "lane_full",
                           "shedme/b") == before + 1
    finally:
        qos.reset(tok)
    from minio_tpu.s3 import errors as s3err
    assert any(exc is se.OperationTimedOut and code == "SlowDown"
               for exc, code in s3err._EXC_MAP)


def test_closed_dataplane_sheds_slowdown_with_metric():
    """Submitting to a closed plane is a shed (503 SlowDown + metric),
    not a bare error — the `closed` cause slug's direct test."""
    from minio_tpu.dataplane.batcher import BatchPlane

    before = _shed_value("dataplane", "closed")
    p = BatchPlane(queue_cap=4, max_wait_s=0.01)
    p.begin_encode(4, 2, 1 << 12, [os.urandom(64)]).wait()
    p.close()
    with pytest.raises(se.OperationTimedOut):
        p.begin_encode(4, 2, 1 << 12, [os.urandom(64)])
    assert _shed_value("dataplane", "closed") == before + 1


def test_blob_lane_flush_full_sheds_slowdown_with_metric(
        tmp_path, monkeypatch):
    """The flush barrier against a saturated WAL queue sheds
    `wal_flush_full` — the blob-lane slug's direct test (records fill
    the queue via write_all_async, the committer parked in fsync)."""
    monkeypatch.setenv("MTPU_METAPLANE", "1")
    monkeypatch.setenv("MTPU_WAL_QUEUE", "2")
    monkeypatch.setenv("MTPU_WAL_TEST_HOLD_FSYNC_S", "2")
    before = _shed_value("metaplane", "wal_flush_full")
    d = LocalDrive(str(tmp_path / "d0"))
    try:
        d.make_vol("bkt")
        time.sleep(0.1)
        futs = []
        for i in range(3):          # 1 into the hold + 2 fill the queue
            try:
                futs.append(d.write_all_async(
                    ".mtpu.sys", f"config/f{i}.mp", b"x" * 64))
            except se.OperationTimedOut:
                break
            if i == 0:
                # The committer has to be inside the held fsync with the
                # first record before the other two fill the queue: it
                # wakes some 50 us after the put, and a second put that
                # lands sooner rides the same batch (the queue then holds
                # one record, the flush gets in and times out instead).
                end = time.monotonic() + 1.0
                while not d._wal._q.empty() and time.monotonic() < end:
                    time.sleep(0.002)
        with pytest.raises(se.OperationTimedOut):
            d._wal.flush(timeout=0.3)
        assert _shed_value("metaplane", "wal_flush_full") == before + 1
        for f in futs:              # never a deadlock
            f.result(timeout=30)
    finally:
        d.close_wal()


def test_dataplane_tenant_quota_sheds_with_tenant_label(monkeypatch):
    """Armed + a 1-op burst: the second submission from the same tenant
    sheds `tenant_quota` under the tenant's own label while the plane
    keeps serving (the first request completes)."""
    from minio_tpu.dataplane.batcher import BatchPlane

    monkeypatch.setenv("MTPU_QOS", "1")
    monkeypatch.setenv("MTPU_QOS_RATE_OPS", "1000")
    monkeypatch.setenv("MTPU_QOS_BURST_S", "0.001")   # burst = 1 token
    tok = qos.bind("stormy", "b")
    p = BatchPlane(queue_cap=8, max_wait_s=0.01)
    try:
        before = _shed_value("dataplane", "tenant_quota", "stormy/b")
        first = p.begin_encode(4, 2, 1 << 12, [os.urandom(64)])
        with pytest.raises(se.OperationTimedOut):
            p.begin_encode(4, 2, 1 << 12, [os.urandom(64)])
        assert _shed_value("dataplane", "tenant_quota",
                           "stormy/b") == before + 1
        first.wait()                # admitted work still completes
    finally:
        qos.reset(tok)
        p.close()


def test_metaplane_tenant_quota_sheds_with_tenant_label(
        tmp_path, monkeypatch):
    monkeypatch.setenv("MTPU_METAPLANE", "1")
    monkeypatch.setenv("MTPU_QOS", "1")
    monkeypatch.setenv("MTPU_QOS_RATE_OPS", "1000")
    monkeypatch.setenv("MTPU_QOS_BURST_S", "0.001")   # burst = 1 token
    tok = qos.bind("stormy", "b")
    d = LocalDrive(str(tmp_path / "d0"))
    try:
        d.make_vol("bkt")
        before = _shed_value("metaplane", "tenant_quota", "stormy/b")
        fut = d.write_all_async(".mtpu.sys", "config/a.mp", b"x" * 64)
        with pytest.raises(se.OperationTimedOut):
            d.write_all_async(".mtpu.sys", "config/b.mp", b"x" * 64)
        assert _shed_value("metaplane", "tenant_quota",
                           "stormy/b") == before + 1
        fut.result(timeout=30)
        # The flush barrier is control traffic: never quota-metered.
        d._wal.flush(timeout=30)
    finally:
        qos.reset(tok)
        d.close_wal()


def test_wal_commit_record_carries_tenants(tmp_path, monkeypatch):
    """Armed, a WAL batch's trace record lists the distinct tenants
    whose submissions it covered — worker 0's coalesced commits stay
    attributable."""
    monkeypatch.setenv("MTPU_METAPLANE", "1")
    monkeypatch.setenv("MTPU_QOS", "1")
    from minio_tpu import obs

    tok = qos.bind("walt", "b")
    d = LocalDrive(str(tmp_path / "d0"))
    try:
        with obs.trace_bus().subscribe() as sub:
            d.make_vol("bkt")
            d.write_all_async(".mtpu.sys", "config/t.mp",
                              b"y" * 64).result(timeout=30)
            batches = []
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                item = sub.get(timeout=0.25)
                if item is not None and item.get("type") == "batch" \
                        and item.get("plane") == "metaplane":
                    batches.append(item)
                if any("walt/b" in r.get("tenants", ())
                       for r in batches):
                    break
            assert any("walt/b" in r.get("tenants", ())
                       for r in batches), batches
    finally:
        qos.reset(tok)
        d.close_wal()


# ---------------------------------------------------------------------------
# Admin surfaces
# ---------------------------------------------------------------------------

def test_stats_inflight_reports_tenant():
    from minio_tpu.admin.stats import HTTPStats

    st = HTTPStats()
    st.begin("rid-1", "PUT", "127.0.0.1:1",
             tenant_get=lambda: "alice/photos")
    st.begin("rid-2", "GET", "127.0.0.1:2")
    rows = {r["trace_id"]: r for r in st.inflight()}
    assert rows["rid-1"]["tenant"] == "alice/photos"
    assert rows["rid-2"]["tenant"] == "-"


def test_flight_timeline_tenant_filter():
    flight.reset()
    was = flight.armed()
    flight.set_armed(True)
    try:
        for tenant, tid in (("a/b", "t1"), ("c/d", "t2")):
            tl = flight.Timeline(tid, "PutObject")
            tl.tenant = tenant
            flight.finish(tl, 200)
        assert [s["trace_id"]
                for s in flight.collect(tenant="a/b")] == ["t1"]
        assert len(flight.collect()) == 2
        assert flight.collect(tenant="nobody") == []
    finally:
        flight.set_armed(was)
        flight.reset()


def test_flight_set_tenant_binds_current_timeline():
    flight.reset()
    was = flight.armed()
    flight.set_armed(True)
    try:
        tl = flight.begin("t3", "GetObject")
        flight.set_tenant("e/f")
        assert tl.tenant == "e/f"
        flight.end(200)
        assert flight.snapshot(tenant="e/f")[0]["trace_id"] == "t3"
    finally:
        flight.set_armed(was)
        flight.reset()


# ---------------------------------------------------------------------------
# sheds are backpressure, not drive sickness
# ---------------------------------------------------------------------------


def test_shed_is_admission_shed_subclass():
    err = admission.shed("metaplane", "tenant_quota", "over quota")
    assert isinstance(err, se.AdmissionShed)
    assert isinstance(err, se.OperationTimedOut)  # 503 SlowDown mapping


def test_shed_maps_to_slowdown():
    from minio_tpu.s3.errors import from_exception

    assert from_exception(se.AdmissionShed(msg="x")).api.code == "SlowDown"


class _ShedDrive:
    """Stub drive whose write_all is rejected by admission policy."""

    def __init__(self, exc_factory):
        self._exc = exc_factory

    def endpoint(self):
        return "stub:/shed"

    def write_all(self, volume, path, data):
        raise self._exc()

    def close(self):
        pass


def test_quota_shed_never_strikes_drive_health():
    """The noisy-neighbor containment boundary: one tenant's quota
    sheds on a shared drive must count as healthy contact — were they
    strikes, OFFLINE_AFTER sheds would walk the drive OFFLINE and fail
    every OTHER tenant's quorum (the exact cross-tenant contamination
    the QoS plane exists to prevent)."""
    from minio_tpu.storage.healthcheck import ONLINE, HealthChecker

    hc = HealthChecker(
        _ShedDrive(lambda: admission.shed("metaplane", "tenant_quota",
                                          "stormy over quota")),
        offline_after=1)
    for _ in range(5):
        with pytest.raises(se.AdmissionShed):
            hc.write_all("v", "p", b"x")
    assert hc.health_state() == ONLINE
    assert hc.consecutive == 0


def test_shed_durations_never_feed_the_deadline_model():
    """Sheds are healthy contact but NOT IO samples: a sustained quota
    storm produces near-zero turnarounds, and logging them as
    successes would shrink the adaptive deadline toward its floor and
    time out (and strike) the next real drive IO."""
    from minio_tpu.storage.healthcheck import HealthChecker
    from minio_tpu.utils import dyntimeout

    hc = HealthChecker(
        _ShedDrive(lambda: admission.shed("metaplane", "tenant_quota",
                                          "storm")),
        offline_after=1)
    dt = hc._deadlines["meta"]
    before = dt.timeout()
    for _ in range(dyntimeout.LOG_SIZE + 50):   # > one adjust window
        with pytest.raises(se.AdmissionShed):
            hc.write_all("v", "p", b"x")
    assert dt.timeout() == before
    assert not dt._durations        # no shed duration was ever logged


def test_wal_tombstone_file_order_pins_submit_order_when_armed(
        tmp_path, monkeypatch):
    """Armed, skewed weights, parked committer: a forget_subtree
    tombstone must land in the WAL file after every record submitted
    before it (a light lane DRR would otherwise leave behind — replay
    would resurrect the rmtree'd journals) and before every record
    submitted after it (a heavy lane DRR would otherwise promote —
    replay would delete the fresh writes)."""
    monkeypatch.setenv("MTPU_METAPLANE", "1")
    monkeypatch.setenv("MTPU_QOS", "1")
    monkeypatch.setenv("MTPU_QOS_WEIGHTS", "heavy=8,light=1")
    monkeypatch.setenv("MTPU_WAL_TEST_HOLD_FSYNC_S", "0.3")
    from minio_tpu.metaplane import wal as walfmt

    d = LocalDrive(str(tmp_path / "d0"))
    try:
        futs = []
        tok = qos.bind("park", "b")
        try:        # bait record parks the committer in its fsync hold
            futs.append(d.write_all_async(".mtpu.sys", "park.mp", b"p"))
        finally:
            qos.reset(tok)
        time.sleep(0.1)
        # 6 records > one DRR round (quantum 4 x weight 1): without the
        # fence the scheduler would move on to the tombstone's lane
        # with two of these still queued, writing them after it.
        tok = qos.bind("light", "b")
        try:
            for i in range(6):
                futs.append(d.write_all_async(
                    ".mtpu.sys", f"t/sub/before{i}.mp", b"x"))
        finally:
            qos.reset(tok)
        d._wal.forget_subtree(".mtpu.sys", "t/sub")   # system lane
        tok = qos.bind("heavy", "b")
        try:
            for i in range(3):
                futs.append(d.write_all_async(
                    ".mtpu.sys", f"t/sub/after{i}.mp", b"y"))
        finally:
            qos.reset(tok)
        for f in futs:
            f.result(timeout=30)
        recs = [(r.rtype, r.path) for r in walfmt.scan(d._wal.path)
                if r.path.startswith("t/sub")]
        tomb_at = next(i for i, (rt, _p) in enumerate(recs)
                       if rt == walfmt.REC_REMOVE_PREFIX)
        assert {p for _rt, p in recs[:tomb_at]} == {
            f"t/sub/before{i}.mp" for i in range(6)}
        assert {p for _rt, p in recs[tomb_at + 1:]} == {
            f"t/sub/after{i}.mp" for i in range(3)}
    finally:
        d.close_wal()


def test_bare_timeout_still_strikes_drive_health():
    """Contrast case: a real OperationTimedOut (drive stall) still
    indicts the drive under the same accounting."""
    from minio_tpu.storage.healthcheck import ONLINE, HealthChecker

    hc = HealthChecker(
        _ShedDrive(lambda: se.OperationTimedOut(msg="drive stalled")),
        offline_after=99)  # strikes accumulate; don't go OFFLINE here
    assert hc.health_state() == ONLINE
    with pytest.raises(se.OperationTimedOut):
        hc.write_all("v", "p", b"x")
    assert hc.consecutive == 1
