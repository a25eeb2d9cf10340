"""PUT's per-drive shard writers run on parked threads (erasure/
writer_pool.py): a job is handed to a thread that is already there, never
queued behind a running one, and a thread wedged in a hung drive never
comes back for another job."""

import io
import json
import os
import sys
import threading
import time

import pytest

from minio_tpu import obs
from minio_tpu.erasure import writer_pool
from minio_tpu.erasure.metadata import _HUNG_WORKERS
from minio_tpu.erasure.objects import ErasureObjects
from minio_tpu.storage.healthcheck import HealthChecker
from minio_tpu.storage.local import LocalDrive
from minio_tpu.utils import errors as se

PAYLOAD = b"w" * 200_000     # over the inline limit: shard files, 4 writers
WAIT = 10.0
TIGHT = {"meta": (1.0, 0.1), "data": (1.0, 0.1), "walk": (1.0, 0.1)}


class Planted:
    """A drive whose create_file the test steers. Not a LocalDrive, so the
    set takes the Python fan-out (_fan_out_encode), not the native lane."""

    def __init__(self, inner, log):
        self.inner = inner
        self.log = log           # (drive, thread, trace id) per create_file
        self.hold = None         # Event: create_file waits for it first
        self.fail = None         # exception: create_file raises it
        self.entered = 0

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def create_file(self, volume, path, chunks):
        self.log.append((self, threading.current_thread(), obs.trace_id()))
        hold, fail = self.hold, self.fail
        self.entered += 1
        if hold is not None:
            hold.wait()
        if fail is not None:
            raise fail
        return self.inner.create_file(volume, path, chunks)


@pytest.fixture
def pool(monkeypatch):
    """A writer pool of this test's own: what earlier tests left parked
    in the process-wide one takes none of its jobs."""
    p = writer_pool.ParkedThreads()
    monkeypatch.setattr(writer_pool, "_WRITERS", p)
    return p


@pytest.fixture
def planted_set(tmp_path, pool):
    log = []
    drives = [Planted(LocalDrive(str(tmp_path / f"d{i}")), log)
              for i in range(4)]
    holds = []
    es = ErasureObjects(drives)
    es.make_bucket("bkt")
    try:
        yield es, drives, log, holds
    finally:
        for h in holds:
            h.set()
        es.close()


def _put(es, name, payload=PAYLOAD):
    return es.put_object("bkt", name, io.BytesIO(payload), len(payload))


def _get(es, name):
    _info, stream = es.get_object("bkt", name)
    return b"".join(stream)


def _wait_for(cond, what, timeout=WAIT):
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if cond():
            return
        time.sleep(0.005)
    raise AssertionError(f"timed out waiting for {what}")


def _counts():
    return writer_pool._JOBS.value, writer_pool._REUSED.value


def _writer_threads():
    return {t for t in threading.enumerate() if t.name == "shard-writer"}


def test_second_put_starts_no_thread(planted_set, pool):
    es, _drives, log, _ = planted_set
    before = _writer_threads()
    jobs0, reused0 = _counts()
    _put(es, "first")
    first = _writer_threads() - before
    assert len(first) == 4
    assert _counts() == (jobs0 + 4, reused0)
    _wait_for(lambda: pool.parked() == 4, "the four writers to park")
    _put(es, "second")
    assert _counts() == (jobs0 + 8, reused0 + 4)
    assert _writer_threads() - before == first
    assert {t for _d, t, _id in log} == first
    assert _get(es, "first") == PAYLOAD and _get(es, "second") == PAYLOAD


def test_blocked_writers_do_not_delay_a_concurrent_put(planted_set, pool):
    es, drives, _log, holds = planted_set
    hold = threading.Event()
    holds.append(hold)
    for d in drives:
        d.hold = hold
    slow = threading.Thread(target=_put, args=(es, "slow"), daemon=True)
    slow.start()
    _wait_for(lambda: sum(d.entered for d in drives) == 4,
              "the slow PUT's four writers inside create_file")
    for d in drives:
        d.hold = None
    # Every thread the pool has is busy: the next PUT's jobs must each
    # get a thread of their own, at once.
    assert pool.parked() == 0
    t0 = time.monotonic()
    _put(es, "quick")
    assert time.monotonic() - t0 < WAIT
    assert slow.is_alive() and not hold.is_set()
    assert _get(es, "quick") == PAYLOAD
    hold.set()
    slow.join(WAIT)
    assert not slow.is_alive()
    assert _get(es, "slow") == PAYLOAD
    _wait_for(lambda: pool.parked() == 8, "all eight writers to park")


def test_wedged_writer_is_abandoned_and_gets_no_later_job(tmp_path, pool):
    log = []
    planted = [Planted(LocalDrive(str(tmp_path / f"d{i}")), log)
               for i in range(4)]
    drives = [HealthChecker(p, deadlines=TIGHT, probe_interval=60.0)
              for p in planted]
    es = ErasureObjects(drives)
    es.make_bucket("bkt")
    hold = threading.Event()
    try:
        _put(es, "warm")
        _wait_for(lambda: pool.parked() == 4, "the four writers to park")
        hung0 = _HUNG_WORKERS.labels().value
        planted[0].hold = hold
        del log[:]
        t0 = time.monotonic()
        _put(es, "wedged")          # completes at quorum, 3 of 4
        assert time.monotonic() - t0 < 6.0
        assert _HUNG_WORKERS.labels().value >= hung0 + 1
        wedged = next(t for d, t, _id in log if d is planted[0])
        assert wedged.is_alive()
        assert _get(es, "wedged") == PAYLOAD
        planted[0].hold = None
        _wait_for(lambda: pool.parked() == 3, "the three others to park")
        del log[:]
        for n in range(3):          # drive 0 is faulty by now, or writes
            try:
                _put(es, f"after{n}")
            except (se.OperationTimedOut, se.InsufficientWriteQuorum):
                time.sleep(0.2)
        assert log and wedged not in {t for _d, t, _id in log}
        hold.set()                  # its call returns: it parks again
        _wait_for(lambda: pool.parked() >= 4, "the wedged writer to park")
    finally:
        hold.set()
        es.close()


@pytest.mark.parametrize("planted", [se.FaultyDisk("planted"),
                                     SystemExit("planted")],
                         ids=["storage-error", "not-an-exception"])
def test_writer_that_raises_sets_its_error_and_parks(planted_set, pool,
                                                     monkeypatch, planted):
    """What quorum reduction sees is a storage error, also where the job
    ended in something that is no Exception."""
    es, drives, log, _ = planted_set
    seen = []
    fan_out = es._fan_out_encode

    def spy(shuffled, *a, **kw):
        out = fan_out(shuffled, *a, **kw)
        seen.append((shuffled, out[2]))
        return out

    monkeypatch.setattr(es, "_fan_out_encode", spy)
    drives[1].fail = planted
    _put(es, "one-bad")             # 3 of 4: write quorum holds
    shuffled, errs = seen[-1]
    bad = shuffled.index(drives[1])
    assert isinstance(errs[bad], se.FaultyDisk)
    assert [e for i, e in enumerate(errs) if i != bad] == [None] * 3
    assert _get(es, "one-bad") == PAYLOAD
    raised_on = next(t for d, t, _id in log if d is drives[1])
    _wait_for(lambda: pool.parked() == 4, "all four writers to park")
    drives[1].fail = None
    del log[:]
    jobs0, reused0 = _counts()
    _put(es, "healthy")
    assert _counts() == (jobs0 + 4, reused0 + 4)
    assert raised_on in {t for _d, t, _id in log}
    assert seen[-1][1] == [None] * 4


def test_reused_thread_carries_the_new_requests_trace_id(planted_set, pool):
    es, _drives, log, _ = planted_set
    by_request = {}
    for tid in ("trace-one", "trace-two"):
        tokens = obs.set_trace_context(trace_id=tid)
        try:
            del log[:]
            _put(es, tid)
        finally:
            obs.reset_trace_context(tokens)
        assert [i for _d, _t, i in log] == [tid] * 4
        by_request[tid] = {t for _d, t, _i in log}
        _wait_for(lambda: pool.parked() == 4, "the four writers to park")
    assert by_request["trace-one"] == by_request["trace-two"]
    # Nothing of a request stays bound to a parked thread.
    del log[:]
    _put(es, "untraced")
    assert [i for _d, _t, i in log] == [None] * 4


def _bench_scrape():
    """benchmarks/scrape.py by path (tier-1 does not collect the
    benchmark's own tests)."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "scrape.py")
    spec = importlib.util.spec_from_file_location("bench_scrape", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_writer_reuse_pct_reads_the_scrape(planted_set, pool):
    """The benchmark's metric file over scrapes of the process's
    exposition: 0 % over a cold PUT, 100 % over a warm one; nothing, and
    no error, where the families are absent (the parent's program)."""
    from minio_tpu.admin.metrics import PromText

    es, _drives, _log, _ = planted_set
    scrape = _bench_scrape()
    with open(os.path.join(os.path.dirname(scrape.__file__), "layer_metrics",
                           "writer_reuse_pct.json")) as f:
        spec = json.load(f)

    def samples():
        sink = PromText()
        obs.render_into(sink)
        return scrape.parse(sink.render().decode())

    cold = samples()
    _put(es, "cold")
    _wait_for(lambda: pool.parked() == 4, "the four writers to park")
    warm = samples()
    _put(es, "warm")
    after = samples()
    assert scrape.delta_ratio(cold, warm, spec, {}) == 0.0
    assert scrape.delta_ratio(warm, after, spec, {}) == 100.0
    gone = {k: v for k, v in after.items()
            if not k[0].startswith("minio_tpu_shard_writer_")}
    assert len(gone) == len(after) - 2
    assert scrape.delta_ratio(gone, gone, spec, {}) is None


def test_parked_thread_exits_after_the_idle_time(pool, monkeypatch):
    monkeypatch.setattr(writer_pool, "IDLE_EXIT_S", 0.2)
    ran = []
    gate = threading.Event()    # three at once: three threads

    def job(n):
        gate.wait(WAIT)
        ran.append(n)

    before = _writer_threads()
    futs = [pool.submit(job, i) for i in range(3)]
    gate.set()
    for f in futs:
        f.result(timeout=WAIT)
    threads = _writer_threads() - before
    assert len(threads) == 3
    _wait_for(lambda: pool.parked() == 3, "three threads to park")
    # A job inside the idle time keeps its thread; the others leave.
    time.sleep(0.1)
    pool.submit(ran.append, 3).result(timeout=WAIT)
    _wait_for(lambda: pool.parked() == 0, "every parked thread to exit")
    for t in threads:
        t.join(WAIT)
        assert not t.is_alive()
    assert sorted(ran) == [0, 1, 2, 3]
    pool.submit(ran.append, 4).result(timeout=WAIT)     # starts afresh
    assert ran[-1] == 4


def test_jobs_exception_is_the_futures(pool):
    fut = pool.submit(lambda: 1 // 0)
    assert isinstance(fut.exception(timeout=WAIT), ZeroDivisionError)
    _wait_for(lambda: pool.parked() == 1, "the thread to park")
    jobs0, reused0 = _counts()
    assert pool.submit(lambda: "next").result(timeout=WAIT) == "next"
    assert _counts() == (jobs0 + 1, reused0 + 1)


def test_no_job_is_lost_or_queued_under_churn(pool, monkeypatch):
    """Submitters against threads that park and time out all the while:
    each job runs once, on a thread that runs nothing else meanwhile, and
    every job is either a reuse or a thread started."""
    monkeypatch.setattr(writer_pool, "IDLE_EXIT_S", 0.002)
    mu = threading.Lock()
    busy = set()
    overlaps = []
    runs = []

    def job(n):
        me = threading.current_thread()
        with mu:
            if me in busy:
                overlaps.append(n)
            busy.add(me)
        time.sleep(0.0005 * (n % 4))
        with mu:
            busy.discard(me)
            runs.append(n)

    def submitter(base, out):
        for n in range(base, base + 150):
            out.append(pool.submit(job, n))
            if n % 5 == 0:
                time.sleep(0.003)   # lets parked threads reach their exit

    started0 = len(_writer_threads())
    jobs0, reused0 = _counts()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        outs = [[] for _ in range(16)]
        subs = [threading.Thread(target=submitter, args=(i * 150, o))
                for i, o in enumerate(outs)]
        for s in subs:
            s.start()
        for s in subs:
            s.join(60.0)
            assert not s.is_alive()
        for o in outs:
            for f in o:
                f.result(timeout=WAIT)
    finally:
        sys.setswitchinterval(old)
    assert sorted(runs) == list(range(16 * 150))
    assert not overlaps
    jobs, reused = _counts()
    assert jobs - jobs0 == 16 * 150
    assert 0 < reused - reused0 < 16 * 150
    _wait_for(lambda: pool.parked() == 0
              and len(_writer_threads()) <= started0,
              "every thread to exit")
