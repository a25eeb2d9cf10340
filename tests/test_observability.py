"""Observability layer tests: strict Prometheus exposition over a live
scrape, node-scope endpoint, typed trace records with ?type= filtering,
per-drive op records during a PUT, and the zero-overhead span guard
(cmd/metrics-v2_test.go + madmin trace test roles)."""

import json
import re
import socket
import threading
import time

import pytest
import requests
from aiohttp import web

from tests.s3client import SigV4Client

ACCESS = "obsroot"
SECRET = "obsroot-secret1"


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _serve(srv):
    """srv.app on a loop thread of its own -> (base URL, the loop)."""
    import asyncio

    port = _free_port()
    loop = asyncio.new_event_loop()
    started = threading.Event()

    def run():
        asyncio.set_event_loop(loop)

        async def start():
            runner = web.AppRunner(srv.app)
            await runner.setup()
            await web.TCPSite(runner, "127.0.0.1", port).start()
            started.set()

        loop.run_until_complete(start())
        loop.run_forever()

    threading.Thread(target=run, daemon=True).start()
    assert started.wait(30)
    return f"http://127.0.0.1:{port}", loop


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    from minio_tpu.s3.server import build_server

    root = tmp_path_factory.mktemp("obs-drives")
    srv = build_server([str(root / f"d{i}") for i in range(4)], ACCESS,
                       SECRET)
    base, loop = _serve(srv)
    yield base, srv
    loop.call_soon_threadsafe(loop.stop)


@pytest.fixture(scope="module")
def client(server):
    return SigV4Client(server[0], ACCESS, SECRET)


@pytest.fixture(scope="module")
def traffic(client):
    """Seed every request-path family: bucket, inline PUT, streaming PUT
    (> inline limit, exercises encode+commit), GET, and a 404."""
    assert client.put("/obsbkt").status_code == 200
    assert client.put("/obsbkt/small", data=b"tiny").status_code == 200
    assert client.put("/obsbkt/big",
                      data=b"x" * (1 << 20)).status_code == 200
    assert client.get("/obsbkt/small").status_code == 200
    assert client.get("/obsbkt/big").status_code == 200
    assert client.get("/obsbkt/definitely-missing").status_code == 404
    return True


# ---------------------------------------------------------------------------
# strict exposition parsing
# ---------------------------------------------------------------------------

_HELP_RE = re.compile(r"^# HELP ([a-zA-Z_:][a-zA-Z0-9_:]*) .*$")
_TYPE_RE = re.compile(
    r"^# TYPE ([a-zA-Z_:][a-zA-Z0-9_:]*) "
    r"(counter|gauge|histogram|summary|untyped)$")
_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? (\S+)$")
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_exposition(text: str):
    """Strict 0.0.4 text-format parse: every line is HELP, TYPE or a
    sample; samples only for families with a prior TYPE; values numeric.
    Returns (families {name: type}, samples [(name, labels, value)])."""
    families: dict[str, str] = {}
    samples: list = []
    for ln, line in enumerate(text.split("\n"), 1):
        if not line:
            continue
        m = _HELP_RE.match(line)
        if m:
            continue
        m = _TYPE_RE.match(line)
        if m:
            families[m.group(1)] = m.group(2)
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"line {ln} is not HELP/TYPE/sample: {line!r}"
        name, rawlbl, rawval = m.groups()
        base = name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[: -len(suffix)] in families:
                base = name[: -len(suffix)]
        assert base in families, f"line {ln}: sample {name} has no TYPE"
        labels = dict(_LABEL_RE.findall(rawlbl[1:-1])) if rawlbl else {}
        value = float("inf") if rawval == "+Inf" else float(rawval)
        samples.append((name, labels, value))
    return families, samples


def _histogram_series(families, samples, family):
    assert families.get(family) == "histogram", \
        f"{family} missing or not a histogram"
    by_labelset: dict = {}
    for name, labels, value in samples:
        if name != f"{family}_bucket":
            continue
        key = tuple(sorted((k, v) for k, v in labels.items() if k != "le"))
        by_labelset.setdefault(key, []).append((labels["le"], value))
    return by_labelset


def _check_histogram(families, samples, family, want_samples=True):
    series = _histogram_series(families, samples, family)
    if want_samples:
        assert series, f"{family} has no bucket samples"
    counts = {(n, tuple(sorted(lbl.items()))): v
              for n, lbl, v in samples}
    for key, buckets in series.items():
        vals = [v for _le, v in buckets]
        les = [le for le, _v in buckets]
        assert les[-1] == "+Inf", f"{family}{key}: buckets must end at +Inf"
        bounds = [float("inf") if le == "+Inf" else float(le) for le in les]
        assert bounds == sorted(bounds), f"{family}{key}: le not ascending"
        assert vals == sorted(vals), \
            f"{family}{key}: bucket counts not cumulative: {vals}"
        # _count must equal the +Inf bucket.
        cnt = counts.get((f"{family}_count", key))
        assert cnt == vals[-1], f"{family}{key}: _count != +Inf bucket"
        assert (f"{family}_sum", key) in counts, f"{family}{key}: no _sum"


def _scrape(client, path="/minio/v2/metrics/cluster"):
    r = client.get(path)
    assert r.status_code == 200, r.text
    return r


def test_exposition_content_type(client, traffic):
    for path in ("/minio/v2/metrics/cluster", "/minio/v2/metrics/node",
                 "/minio/admin/v3/metrics"):
        r = _scrape(client, path)
        assert r.headers["Content-Type"].startswith(
            "text/plain; version=0.0.4"), (path, r.headers["Content-Type"])


def test_cluster_scrape_strict_and_histograms(client, traffic):
    r = _scrape(client)
    families, samples = parse_exposition(r.text)
    # The four request-path distributions of the acceptance criteria.
    _check_histogram(families, samples,
                     "minio_tpu_s3_requests_latency_seconds")
    _check_histogram(families, samples, "minio_tpu_s3_ttfb_seconds")
    _check_histogram(families, samples, "minio_tpu_drive_latency_seconds")
    # Single-node deployment: the RPC family is registered (HELP/TYPE)
    # but has no peers to sample.
    _check_histogram(families, samples, "minio_tpu_rpc_latency_seconds",
                     want_samples=False)
    hists = [f for f, t in families.items() if t == "histogram"]
    assert len(hists) >= 4, hists
    # Legacy collectors still render.
    assert families.get("minio_tpu_s3_requests_total") == "counter"
    assert families.get("minio_tpu_cluster_health_status") == "gauge"


def test_drive_and_api_labels(server, client, traffic):
    _, srv = server
    _, samples = parse_exposition(_scrape(client).text)
    drive_ops = {lbl["op"] for n, lbl, v in samples
                 if n == "minio_tpu_drive_latency_seconds_bucket"}
    assert "read_version" in drive_ops
    assert "write_metadata_single" in drive_ops
    # The 1 MiB PUT took the streaming path: shard writes + commits.
    assert "create_file" in drive_ops
    assert "rename_data" in drive_ops
    # The obs registry is process-global: other test modules' drives may
    # also appear in the scrape — assert on THIS server's drive set.
    drives = {lbl["drive"] for n, lbl, v in samples
              if n == "minio_tpu_drive_latency_seconds_bucket"}
    ours = {d.root for d in srv.obj.all_drives()}
    assert len(ours) == 4 and ours <= drives
    apis = {lbl["api"] for n, lbl, v in samples
            if n == "minio_tpu_s3_requests_latency_seconds_bucket"}
    assert "PutObject" in apis and "GetObject" in apis


def test_encode_gauge_after_streaming_put(client, traffic):
    """The streaming PUT's encode + fan-out time is on the scrape: the
    flight recorder's `encode` stage (the rolling `minio_tpu_encode_gibps`
    gauge it replaced measured the same wall)."""
    _, samples = parse_exposition(_scrape(client).text)
    vals = [v for n, lbl, v in samples
            if n == "minio_tpu_stage_seconds_sum"
            and lbl.get("stage") == "encode"
            and lbl.get("api") == "PutObject"]
    assert vals and vals[0] > 0
    assert not [n for n, _l, _v in samples
                if n == "minio_tpu_encode_gibps"]


def test_4xx_export(client, traffic):
    _, samples = parse_exposition(_scrape(client).text)
    e4 = sum(v for n, _l, v in samples
             if n == "minio_tpu_s3_requests_4xx_errors_total")
    assert e4 >= 1


def test_node_scope_endpoint(client, traffic):
    families, samples = parse_exposition(
        _scrape(client, "/minio/v2/metrics/node").text)
    assert "minio_tpu_process_uptime_seconds" in families
    _check_histogram(families, samples, "minio_tpu_drive_latency_seconds")
    assert "minio_tpu_rpc_latency_seconds" in families
    assert "minio_tpu_trace_dropped_total" in families
    # Cluster-wide collectors stay off the node scrape.
    assert "minio_tpu_cluster_disk_online_total" not in families
    assert "minio_tpu_bucket_usage_total_bytes" not in families


# ---------------------------------------------------------------------------
# trace stream: typed records + ?type= filter
# ---------------------------------------------------------------------------

def _wait_no_subscribers(bus, deadline=5.0):
    end = time.time() + deadline
    while bus.has_subscribers and time.time() < end:
        time.sleep(0.05)
    return not bus.has_subscribers


def test_zero_overhead_without_subscriber(server, client):
    """The guard of the whole design: no span objects (and no trace
    records) materialize on the hot path unless someone subscribes."""
    from minio_tpu.obs import Span

    _base, srv = server
    assert _wait_no_subscribers(srv.trace_bus), "stale trace subscriber"
    before = Span.allocated
    assert client.put("/obsbkt/guard", data=b"g" * 100).status_code == 200
    assert client.put("/obsbkt/guard-big",
                      data=b"g" * (64 << 10)).status_code == 200
    assert client.get("/obsbkt/guard").status_code == 200
    assert Span.allocated == before, \
        "span allocated with no trace subscriber attached"


def test_trace_type_storage_filter(server, client, traffic):
    """?type=storage during a PUT shows per-drive call records — the
    `mc admin trace --call storage` view. (`traffic` guarantees the
    bucket exists when this test runs alone.)"""
    base, srv = server
    got: list = []
    stop = threading.Event()

    def consume():
        q = {"type": "storage"}
        headers = SigV4Client(base, ACCESS, SECRET)._sign(
            "GET", "/minio/admin/v3/trace", q, {}, b"")
        try:
            with requests.get(f"{base}/minio/admin/v3/trace", params=q,
                              headers=headers, stream=True,
                              timeout=10) as r:
                for line in r.iter_lines():
                    if stop.is_set():
                        return
                    if line:
                        got.append(json.loads(line))
                        if len(got) >= 4:
                            return
        except requests.RequestException:
            pass

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    deadline = time.time() + 5
    while not srv.trace_bus.has_subscribers and time.time() < deadline:
        time.sleep(0.05)
    client.put("/obsbkt/traced", data=b"t" * 100)
    client.get("/obsbkt/traced")
    t.join(timeout=10)
    stop.set()
    assert got, "no storage trace records received"
    assert all(rec["type"] == "storage" for rec in got)
    ops = {rec["op"] for rec in got}
    # Armed default: the inline commit records as the two-phase
    # journal_commit_async; the per-request oracle records the sync
    # store; a cache-missing GET records read_version.
    assert ops & {"write_metadata_single", "read_version",
                  "journal_commit_async", "write_all_async"}, ops
    for rec in got:
        assert rec["drive"]
        assert rec["durationNs"] >= 0
    assert _wait_no_subscribers(srv.trace_bus)


def test_http_and_internal_records_direct(server, client):
    """Direct bus subscription: HTTP records carry type/durationNs/rx/tx
    (the satellite fields) and erasure spans surface as `internal`."""
    _base, srv = server
    with srv.trace_bus.subscribe() as sub:
        client.put("/obsbkt/direct", data=b"d" * (64 << 10))
        client.get("/obsbkt/direct")
        recs = []
        deadline = time.time() + 5
        while time.time() < deadline:
            item = sub.get(timeout=0.25)
            if item is not None:
                recs.append(item)
            http = [r for r in recs if r.get("type") == "http"
                    and r.get("api") == "PutObject"]
            internal = [r for r in recs if r.get("type") == "internal"]
            if http and internal:
                break
    assert http, recs[:5]
    rec = http[0]
    assert rec["durationNs"] > 0
    assert rec["rx"] == 64 << 10
    assert "tx" in rec and "requestId" in rec
    names = {r.get("name") for r in internal}
    assert names & {"quorum-read", "encode", "commit"}, names
    assert _wait_no_subscribers(srv.trace_bus)


def test_trace_dropped_counter(server, client):
    """Slow-consumer drops are counted and exported (satellite: PubSub
    must not lose records silently)."""
    _base, srv = server
    bus = srv.trace_bus
    before = bus.dropped
    sub = bus.subscribe()
    try:
        for i in range(1200):  # queue maxsize is 1000
            bus.publish({"type": "internal", "n": i})
    finally:
        sub.close()
    assert bus.dropped > before
    _, samples = parse_exposition(_scrape(client).text)
    exported = [v for n, _l, v in samples
                if n == "minio_tpu_trace_dropped_total"]
    assert exported and exported[0] >= bus.dropped - before


# ---------------------------------------------------------------------------
# stats satellites
# ---------------------------------------------------------------------------

def test_uptime_is_monotonic_not_wall_clock(server):
    _base, srv = server
    wall = srv.stats.started
    try:
        # A 10-day NTP step backward must not produce negative uptime.
        srv.stats.started = wall - 864000
        snap = srv.stats.snapshot()
        assert 0 <= snap["uptime"] < 86400
    finally:
        srv.stats.started = wall


def test_canceled_counter_wired(server, client):
    _base, srv = server
    t0 = srv.stats.begin()
    srv.stats.end("GetObject", t0, 200, canceled=True)
    snap = srv.stats.snapshot()
    assert snap["apis"]["GetObject"]["canceled"] >= 1
    _, samples = parse_exposition(_scrape(client).text)
    canceled = {lbl.get("api"): v for n, lbl, v in samples
                if n == "minio_tpu_s3_requests_canceled_total"}
    assert canceled.get("GetObject", 0) >= 1


# ---------------------------------------------------------------------------
# device plane: kernel histograms
# ---------------------------------------------------------------------------


def test_kernel_histograms_after_encode_decode(client, traffic):
    """minio_tpu_kernel_seconds{kernel,backend} carries samples after the
    streaming PUT + GET, whichever lane served them (device codec,
    native C++ pipeline, or host hash) — the acceptance criterion's
    'appears in the node scrape after an encode/decode'."""
    for path in ("/minio/v2/metrics/cluster", "/minio/v2/metrics/node"):
        families, samples = parse_exposition(_scrape(client, path).text)
        _check_histogram(families, samples, "minio_tpu_kernel_seconds")
        kernels = {(lbl["kernel"], lbl["backend"])
                   for n, lbl, v in samples
                   if n == "minio_tpu_kernel_seconds_bucket" and v > 0}
        assert kernels, "no kernel launches recorded"
        # Every series names a known lane.
        for k, b in kernels:
            assert b in ("native", "host", "mesh") or ":" in b, (k, b)
        assert families.get("minio_tpu_kernel_launches_total") == "counter"


def test_kernel_trace_records(server, client):
    """Typed `kernel` records ride the bus under the subscriber gate."""
    _base, srv = server
    with srv.trace_bus.subscribe() as sub:
        client.put("/obsbkt/kernelrec", data=b"k" * (1 << 20))
        client.get("/obsbkt/kernelrec")
        recs = []
        deadline = time.time() + 5
        while time.time() < deadline:
            item = sub.get(timeout=0.25)
            if item is not None and item.get("type") == "kernel":
                recs.append(item)
                break
    assert recs, "no kernel trace record"
    assert recs[0]["durationNs"] >= 0 and recs[0]["kernel"]
    assert _wait_no_subscribers(srv.trace_bus)


# ---------------------------------------------------------------------------
# trace context: trace_id + node on records, audit linkage
# ---------------------------------------------------------------------------


def test_records_carry_trace_id_and_node(server, client):
    """Every record of one request — http, storage, internal — shares the
    request id as trace_id and names the emitting node."""
    _base, srv = server
    with srv.trace_bus.subscribe() as sub:
        r = client.put("/obsbkt/tctx", data=b"t" * (64 << 10))
        rid = r.headers["x-amz-request-id"]
        recs = []
        deadline = time.time() + 5
        while time.time() < deadline:
            item = sub.get(timeout=0.25)
            if item is not None:
                recs.append(item)
            if any(x.get("type") == "http" and x.get("requestId") == rid
                   for x in recs):
                break
    mine = [x for x in recs if x.get("trace_id") == rid]
    types = {x["type"] for x in mine}
    assert "http" in types and "storage" in types, types
    assert all(x.get("node") for x in mine)
    http_rec = next(x for x in mine if x["type"] == "http")
    assert http_rec["requestId"] == rid  # audit requestID == trace_id
    assert _wait_no_subscribers(srv.trace_bus)


def test_inflight_gauge_and_top_api(server, client, traffic):
    """The scrape itself is an in-flight `metrics` request; the top/api
    admin view lists the same registry with age + trace_id."""
    _, samples = parse_exposition(_scrape(client).text)
    inflight = {lbl.get("api"): v for n, lbl, v in samples
                if n == "minio_tpu_s3_requests_inflight"}
    assert inflight.get("metrics", 0) >= 1, inflight
    r = client.get("/minio/admin/v3/top/api")
    assert r.status_code == 200, r.text
    reqs = r.json()["requests"]
    assert reqs, "top api view empty during its own request"
    own = [x for x in reqs if x["api"].startswith("admin.top")]
    assert own and own[0]["trace_id"] and own[0]["ageMs"] >= 0


def test_metrics_docs_drift(client, traffic):
    """Docs-drift gate: every family the exporters emit must be listed in
    docs/METRICS.md (the doc drifted silently once in PR 3)."""
    import os

    docs_path = os.path.join(os.path.dirname(__file__), "..",
                             "docs", "METRICS.md")
    with open(docs_path, encoding="utf-8") as f:
        docs = f.read()
    for path in ("/minio/v2/metrics/cluster", "/minio/v2/metrics/node"):
        families, _ = parse_exposition(_scrape(client, path).text)
        missing = sorted(f for f in families if f not in docs)
        assert not missing, (
            f"metric families missing from docs/METRICS.md: {missing}")


def test_madmin_trace_stream_and_metrics_node(server, client):
    """The madmin client can finally reach the server-side filters: a
    typed streaming trace() and the node-scope scrape."""
    base, srv = server
    from minio_tpu.madmin import AdminClient

    adm = AdminClient(base, ACCESS, SECRET)
    text = adm.metrics_node()
    assert "minio_tpu_process_uptime_seconds" in text
    assert "minio_tpu_cluster_disk_online_total" not in text

    got: list = []
    done = threading.Event()

    def watch():
        gen = adm.trace(type="http", all_nodes=False)
        try:
            for rec in gen:
                got.append(rec)
                if len(got) >= 2:
                    return
        finally:
            gen.close()
            done.set()

    t = threading.Thread(target=watch, daemon=True)
    t.start()
    deadline = time.time() + 5
    while not srv.trace_bus.has_subscribers and time.time() < deadline:
        time.sleep(0.05)
    client.put("/obsbkt/madmin-traced", data=b"m" * 128)
    client.get("/obsbkt/madmin-traced")
    assert done.wait(10), "madmin trace stream yielded nothing"
    assert got and all(r["type"] == "http" for r in got)
    assert all(r.get("trace_id") and r.get("node") for r in got)
    top = adm.top_api()
    assert "requests" in top
    assert _wait_no_subscribers(srv.trace_bus)


# ---------------------------------------------------------------------------
# flight recorder: stage timelines, perf endpoint, ?plane= filter
# ---------------------------------------------------------------------------


def _perf_query(client, **q):
    q.setdefault("all", "false")
    r = client.get("/minio/admin/v3/perf/timeline", query=q)
    assert r.status_code == 200, r.text
    return r.json()


def _seq_sum_ns(snap) -> int:
    return sum(s["dur_ns"] for s in snap["stages"] if s["seq"])


def test_stage_timeline_fidelity_put_get(server, client, traffic,
                                         monkeypatch):
    """Acceptance contract: a PUT and a GET through the default-on batch
    planes each yield a queryable stage timeline whose sequential stages
    sum to within 10% of the measured e2e latency."""
    rp = client.put("/obsbkt/stagesum", data=b"s" * (1 << 20))
    assert rp.status_code == 200
    rg = client.get("/obsbkt/stagesum")
    assert rg.status_code == 200
    for resp, api, want in (
            (rp, "PutObject", {"rx_drain", "encode", "commit"}),
            (rg, "GetObject", {"meta_elect"})):
        rid = resp.headers["x-amz-request-id"]
        doc = _perf_query(client, traceid=rid)
        assert doc["node"]
        assert doc["timelines"], f"no timeline recorded for {api}"
        snap = doc["timelines"][0]
        assert snap["trace_id"] == rid and snap["api"] == api
        stages = {s["stage"] for s in snap["stages"]}
        assert ({"auth", "resp_drain"} | want) <= stages, (api, stages)
        seq = _seq_sum_ns(snap)
        assert abs(seq - snap["e2e_ns"]) <= 0.1 * snap["e2e_ns"], (
            f"{api}: sequential stages sum to {seq} ns vs e2e "
            f"{snap['e2e_ns']} ns — the timeline leaks wall clock")
    # A PUT inside the dataplane serving gate (chunk <= the plane's max
    # width) rides the coalescing lanes: plane-measured detail stamps
    # attribute time inside the sequential segments. The native C++ PUT
    # lane would serve this host-side without a CodecRequest, so force
    # the device-codec fan-out (the gate is re-read per call).
    from minio_tpu import dataplane

    if dataplane.enabled():
        monkeypatch.setenv("MTPU_NATIVE_PLANE", "0")
        rd = client.put("/obsbkt/stagesum-dp", data=b"d" * 100_000)
        assert rd.status_code == 200
        doc = _perf_query(client,
                          traceid=rd.headers["x-amz-request-id"])
        assert doc["timelines"]
        details = {s["stage"] for s in doc["timelines"][0]["stages"]
                   if not s["seq"]}
        assert "dp_queue_wait" in details, details
        assert "wal_fsync_wait" in details, details


def test_perf_timeline_api_and_worst_filters(server, client, traffic):
    """?api= narrows to one API newest-first; ?worst= returns the
    slowest N on record, sorted slowest-first."""
    for i in range(3):
        assert client.put(f"/obsbkt/worst-{i}",
                          data=b"w" * 4096).status_code == 200
    doc = _perf_query(client, api="PutObject")
    assert doc["timelines"]
    assert all(s["api"] == "PutObject" for s in doc["timelines"])
    doc = _perf_query(client, worst="2")
    tl = doc["timelines"]
    assert tl and len(tl) <= 2
    assert [s["e2e_ns"] for s in tl] == sorted(
        (s["e2e_ns"] for s in tl), reverse=True)


def test_flight_disarmed_zero_overhead(server, client):
    """Mirror of the trace-bus guard: disarmed, no Timeline objects
    materialize anywhere on the request path."""
    from minio_tpu.obs import flight

    was = flight.armed()
    flight.set_armed(False)
    try:
        before = flight.Timeline.allocated
        assert client.put("/obsbkt/noflight",
                          data=b"n" * (64 << 10)).status_code == 200
        assert client.get("/obsbkt/noflight").status_code == 200
        assert flight.Timeline.allocated == before, \
            "Timeline allocated while the flight recorder was disarmed"
    finally:
        flight.set_armed(was)


def test_exemplar_disarmed_zero_overhead(server, client):
    """Third leg of the zero-overhead contract (docs/SLO.md): with
    exemplar capture disarmed, request traffic must not capture (or
    even count toward) a single exemplar."""
    from minio_tpu import obs

    obs.set_exemplars(False)
    try:
        before = obs.exemplar_captures()
        assert client.put("/obsbkt/noex",
                          data=b"e" * (64 << 10)).status_code == 200
        assert client.get("/obsbkt/noex").status_code == 200
        assert obs.exemplar_captures() == before, \
            "exemplar captured while disarmed"
    finally:
        obs.set_exemplars(True, every=8)


# ---------------------------------------------------------------------------
# flight.span: the one span primitive (timeline, trace bus, device profile)
# ---------------------------------------------------------------------------

MX_BODY = bytes(range(256)) * (5 << 12)     # 5 MiB: five 1 MiB blocks


@pytest.fixture(scope="module")
def mx(tmp_path_factory):
    """A server of its own on the per-object device-codec path that the
    10 MiB cells take on the chip: `mxsum256` given explicitly (the CPU
    default, sip256, takes the native C++ lane and never meets the
    codec), 512 KiB shard rows (wider than the lane gate), and batches
    of two blocks, so a 5 MiB object is three encode launches on PUT and
    three read batches behind the read-ahead thread on GET."""
    from minio_tpu.s3.server import build_server

    root = tmp_path_factory.mktemp("obs-mx-drives")
    srv = build_server([str(root / f"d{i}") for i in range(4)], ACCESS,
                       SECRET)
    for es in srv.obj.pools[0].sets:
        es.bitrot_algorithm = "mxsum256"
        es.batch_blocks = 2
    base, loop = _serve(srv)
    cl = SigV4Client(base, ACCESS, SECRET)
    assert cl.put("/mxbkt").status_code == 200
    # Compile every program the tests below launch, outside any session.
    assert cl.put("/mxbkt/warm", data=MX_BODY).status_code == 200
    assert cl.get("/mxbkt/warm").content == MX_BODY
    yield cl, srv
    loop.call_soon_threadsafe(loop.stop)


def _timeline(cl, resp) -> dict:
    doc = _perf_query(cl, traceid=resp.headers["x-amz-request-id"])
    assert doc["timelines"], "no timeline recorded"
    return doc["timelines"][0]


@pytest.fixture(scope="module")
def mx_profile(mx):
    """One `tpu`-kind session (on this backend: a host trace) around one
    PUT and one GET -> (PUT's id, GET's id, the /host:CPU plane's mtpu/
    events as (name, line index, stats))."""
    import io
    import zipfile

    from jax.profiler import ProfileData

    cl, _srv = mx
    r = cl.request("POST", "/minio/admin/v3/profiling/start",
                   query={"profilerType": "tpu"})
    assert r.status_code == 200, r.text
    rp = cl.put("/mxbkt/profiled", data=MX_BODY)
    rg = cl.get("/mxbkt/profiled")
    assert rp.status_code == 200 and rg.content == MX_BODY
    r = cl.get("/minio/admin/v3/profiling/download")
    assert r.status_code == 200
    outer = zipfile.ZipFile(io.BytesIO(r.content))
    inner = zipfile.ZipFile(io.BytesIO(outer.read("local/tpu_trace.zip")))
    pb = next(n for n in inner.namelist() if n.endswith(".xplane.pb"))
    data = ProfileData.from_serialized_xspace(inner.read(pb))
    host = next(p for p in data.planes if p.name == "/host:CPU")
    events = [(e.name, li, dict(e.stats))
              for li, ln in enumerate(host.lines) for e in ln.events
              if e.name.startswith("mtpu/")]
    return (rp.headers["x-amz-request-id"],
            rg.headers["x-amz-request-id"], events)


@pytest.mark.parametrize("span,verb", [
    ("mtpu/rx_wait", "PUT"), ("mtpu/enc_dispatch", "PUT"),
    ("mtpu/enc_wait", "PUT"), ("mtpu/verify_wait", "GET"),
    ("mtpu/tx_send", "GET")])
def test_device_profile_holds_the_request_spans(mx_profile, span, verb):
    """During a session every span is also a TraceAnnotation on its
    thread's line of the /host:CPU plane, carrying the request's id."""
    put_id, get_id, events = mx_profile
    want = put_id if verb == "PUT" else get_id
    mine = [(li, st) for name, li, st in events
            if name == span and st.get("trace_id") == want]
    assert mine, (span, sorted({n for n, _l, _s in events}))
    api = "PutObject" if verb == "PUT" else "GetObject"
    assert all(st.get("api") == api for _li, st in mine), mine[:3]


def test_device_profile_spans_share_the_id_across_threads(mx_profile):
    """rx_wait runs on the event-loop thread, enc_wait on an executor
    thread, verify_wait on the read-ahead thread: one request's spans on
    different lines carry one trace_id."""
    put_id, get_id, events = mx_profile
    for rid, a, b in ((put_id, "mtpu/rx_wait", "mtpu/enc_wait"),
                      (get_id, "mtpu/tx_send", "mtpu/verify_wait")):
        lines = {name: {li for n, li, st in events
                        if n == name and st.get("trace_id") == rid}
                 for name in (a, b)}
        assert lines[a] and lines[b], lines
        assert lines[a].isdisjoint(lines[b]), lines


def test_no_session_no_annotation_no_bus_span(mx):
    """With no profiling session and no bus subscriber a PUT and a GET
    construct no TraceAnnotation and no obs.Span; the spans still reach
    the timeline."""
    from minio_tpu.obs import Span, flight

    cl, srv = mx
    assert _wait_no_subscribers(srv.trace_bus), "stale trace subscriber"
    assert not flight._PROFILING
    ann, spans = flight.annotations, Span.allocated
    rp = cl.put("/mxbkt/quiet", data=MX_BODY)
    rg = cl.get("/mxbkt/quiet")
    assert rp.status_code == 200 and rg.content == MX_BODY
    assert flight.annotations == ann, "TraceAnnotation built with no session"
    assert Span.allocated == spans, "obs.Span built with no subscriber"
    assert {"enc_wait", "rx_wait"} <= {
        s["stage"] for s in _timeline(cl, rp)["stages"]}


def test_repeated_spans_accumulate_into_one_entry():
    """A stage entered once per body chunk is `flight.span` like any
    other: its repeats land as ONE entry (`dur` summed, `n` counted,
    `start` the first), and with no session no annotation is built."""
    from minio_tpu.obs import flight

    tl = flight.begin("REPEAT", "GetObject")
    assert tl is not None
    try:
        ann = flight.annotations
        for _ in range(5):
            with flight.span("tx_next"):
                pass
        (entry,) = [s for s in tl._stages if s[0] == "tx_next"]
        assert entry[6] == 5 and entry[2] > 0 and not entry[3]
        assert flight.annotations == ann
    finally:
        flight.end()


def test_one_span_primitive_in_the_tree():
    """No call to obs.span( outside minio_tpu/obs/, and TraceAnnotation
    named in obs/flight.py only."""
    import pathlib

    pkg = pathlib.Path(__file__).resolve().parent.parent / "minio_tpu"
    bus, ann = [], []
    for path in pkg.rglob("*.py"):
        rel = path.relative_to(pkg).as_posix()
        text = path.read_text()
        if "obs.span(" in text and not rel.startswith("obs/"):
            bus.append(rel)
        if "TraceAnnotation" in text and rel != "obs/flight.py":
            ann.append(rel)
    # admin/profiling.py's docstring names the class it arms.
    assert bus == [] and ann in ([], ["admin/profiling.py"]), (bus, ann)


@pytest.mark.parametrize("verb,parent,children,counts", [
    ("PUT", "rx_drain", ("rx_wait", "rx_hash", "rx_spool"), {}),
    ("PUT", "encode", ("enc_spawn", "enc_read", "enc_stage",
                       "enc_dispatch", "enc_wait", "enc_feed", "enc_join"),
     {}),
    # A 5 MiB body leaves in two groups (4 MiB, then the rest with the
    # stream's end): two hops, and a send each plus write_eof. One entry
    # a chunk would read 11 and 11 (five blocks of two data chunks).
    ("GET", "resp_drain", ("tx_next", "tx_send"),
     {"tx_next": 2, "tx_send": 3}),
])
def test_detail_spans_fit_inside_their_stage(mx, verb, parent, children,
                                             counts):
    """The new stages split an existing one where the work happens: per
    request they sum to no more than it, each is ONE accumulated entry,
    and the sequential stage keeps its name and extent (the stage-sum
    fidelity test above passes unchanged). GET's two are entered once a
    group of chunks, not once a chunk."""
    cl, _srv = mx
    key = f"/mxbkt/fit-{parent}"
    r = cl.put(key, data=MX_BODY)
    if verb == "GET":
        r = cl.get(key)
    assert r.status_code == 200
    snap = _timeline(cl, r)
    by: dict = {}
    for s in snap["stages"]:
        by.setdefault(s["stage"], []).append(s)
    assert len(by[parent]) == 1 and by[parent][0]["seq"]
    outer = by[parent][0]
    inner = 0
    for name in children:
        assert len(by.get(name, [])) == 1, (name, sorted(by))
        s = by[name][0]
        assert not s["seq"] and s["n"] >= 1
        assert s["n"] == counts.get(name, s["n"]), (name, s["n"])
        assert s["start_ns"] >= outer["start_ns"]
        inner += s["dur_ns"]
    assert 0 < inner <= outer["dur_ns"], (inner, outer)
    seq = _seq_sum_ns(snap)
    assert abs(seq - snap["e2e_ns"]) <= 0.1 * snap["e2e_ns"]


def test_timeline_entries_carry_start_parent_n(mx):
    """/perf/timeline: every entry has its start offset from the
    request's t0, a parent and a count; the GET's object-layer spans sit
    under resp_drain, repeated ones are counted, not repeated."""
    cl, _srv = mx
    assert cl.put("/mxbkt/shape", data=MX_BODY).status_code == 200
    snap = _timeline(cl, cl.get("/mxbkt/shape"))
    assert snap["t0"] > 0
    for s in snap["stages"]:
        assert {"start_ns", "parent", "n"} <= set(s), s
        assert 0 <= s["start_ns"] <= snap["e2e_ns"]
    by = {s["stage"]: s for s in snap["stages"]}
    for name in ("shard_read", "verify_wait", "decode", "readahead_wait"):
        assert by[name]["parent"] == "resp_drain", by[name]
    assert by["shard_read"]["n"] == 3 and by["decode"]["n"] == 3
    assert by["auth"]["parent"] is None and by["auth"]["start_ns"] == 0
    # Sequential segments tile the request: each starts where the last ended.
    seq = [s for s in snap["stages"] if s["seq"]]
    for a, b in zip(seq, seq[1:]):
        assert abs(a["start_ns"] + a["dur_ns"] - b["start_ns"]) <= 1000


def test_exposition_never_tears_under_mutation(client, traffic):
    """A scrape concurrent with registry writes (new label children
    materializing mid-render) must still produce a strictly parseable
    exposition: one HELP/TYPE head per family, no truncated lines."""
    from minio_tpu import obs

    # Deliberately outside the minio_tpu_ namespace: scratch families
    # must not enter the docs-drift contract.
    h = obs.histogram("obs_mutation_scratch_seconds",
                      "scrape-vs-mutation scratch family", ("k",))
    c = obs.counter("obs_mutation_scratch_total",
                    "scrape-vs-mutation scratch counter", ("k",))
    stop = threading.Event()

    def mutate():
        i = 0
        while not stop.is_set():
            h.labels(k=f"m{i % 97}").observe(0.001 * (i % 13))
            c.labels(k=f"m{i % 89}").inc()
            i += 1

    threads = [threading.Thread(target=mutate, daemon=True)
               for _ in range(3)]
    for t in threads:
        t.start()
    try:
        for _ in range(8):
            text = _scrape(client, "/minio/v2/metrics/node").text
            families, samples = parse_exposition(text)  # strict: raises
            assert "obs_mutation_scratch_seconds" in families
    finally:
        stop.set()
        for t in threads:
            t.join(5.0)


def test_trace_plane_filter_batch_records(server, client, traffic,
                                          monkeypatch):
    """?plane=dataplane keeps only dataplane-stamped records; the
    coalesced launch's `batch` record lists its member trace ids — the
    join key between a request timeline and the batch that served it."""
    from minio_tpu import dataplane

    if not dataplane.enabled():
        pytest.skip("batched dataplane off in this environment")
    # Route PUT encodes through the device-codec plane (not the native
    # C++ lane) so coalesced launches emit `batch` records.
    monkeypatch.setenv("MTPU_NATIVE_PLANE", "0")
    base, srv = server
    got: list = []

    def consume():
        q = {"plane": "dataplane", "all": "false"}
        headers = SigV4Client(base, ACCESS, SECRET)._sign(
            "GET", "/minio/admin/v3/trace", q, {}, b"")
        try:
            with requests.get(f"{base}/minio/admin/v3/trace", params=q,
                              headers=headers, stream=True,
                              timeout=10) as r:
                for line in r.iter_lines():
                    if line:
                        got.append(json.loads(line))
                        if any(rec.get("type") == "batch"
                               for rec in got):
                            return
        except requests.RequestException:
            pass

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    deadline = time.time() + 5
    while not srv.trace_bus.has_subscribers and time.time() < deadline:
        time.sleep(0.05)
    end = time.time() + 8
    while t.is_alive() and time.time() < end:
        # Inside the serving gate so the encode rides the plane.
        r = client.put("/obsbkt/planefilter", data=b"p" * 100_000)
        assert r.status_code == 200
        time.sleep(0.1)
    t.join(timeout=10)
    assert got, "no dataplane-plane records received"
    assert all(rec.get("plane") == "dataplane" for rec in got), got[:3]
    batches = [rec for rec in got if rec.get("type") == "batch"]
    assert batches, [rec.get("type") for rec in got]
    members = {tid for rec in batches for tid in rec.get("members", [])}
    assert members, "batch records carry no member trace ids"
    assert _wait_no_subscribers(srv.trace_bus)


# ---------------------------------------------------------------------------
# 2-node cluster: cross-node tracing + metrics federation
# ---------------------------------------------------------------------------

CL_SECRET = "obs-cluster-secret"


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    """Two symmetric ClusterNodes (one 8-drive set, 4 per node) with a
    full S3 front door attached to node 1 — the fixture of the
    acceptance criteria: a GetObject on node 1 reads node 2's drives
    over the storage plane."""
    import asyncio

    from minio_tpu.admin.metrics import collect_node_metrics
    from minio_tpu.admin.stats import HTTPStats
    from minio_tpu.dist.cluster import ClusterNode
    from minio_tpu.s3.server import S3Server
    from minio_tpu.s3 import sigv4

    tmp = tmp_path_factory.mktemp("obs-cluster")
    s3p1, s3p2 = 19701, 19702          # advertised only
    rpc1, rpc2 = _free_port(), _free_port()
    rpc_map = {s3p1: rpc1, s3p2: rpc2}
    args = [[f"http://127.0.0.1:{s3p1}/n1/disk{{1...4}}",
             f"http://127.0.0.1:{s3p2}/n2/disk{{1...4}}"]]
    mk_root = lambda p: str(tmp / p.strip("/").replace("/", "_"))  # noqa: E731

    nodes = []
    for port, rpc in ((s3p1, rpc1), (s3p2, rpc2)):
        nodes.append(ClusterNode(
            args, host="127.0.0.1", port=port, secret=CL_SECRET,
            root_dir_map=mk_root, local_names={"127.0.0.1"},
            rpc_port=rpc, rpc_port_of=lambda h, p: rpc_map[p], parity=2))
    n1, n2 = nodes
    n1.wait_for_peers(timeout=10)
    ol1 = n1.build_object_layer()
    n2.build_object_layer()

    # Node 2 runs no S3 front door; wire its peer metrics hook the way
    # attach_cluster would.
    stats2 = HTTPStats()
    n2.hooks.metrics = lambda: collect_node_metrics(stats2)

    srv = S3Server(ol1, sigv4.Credentials(ACCESS, SECRET),
                   notification_sys=n1.notification)
    srv.attach_cluster(n1)
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
    cl = SigV4Client(f"http://127.0.0.1:{port}", ACCESS, SECRET)
    assert cl.put("/clbkt").status_code == 200
    assert cl.put("/clbkt/obj",
                  data=b"c" * ((1 << 20) + 123)).status_code == 200
    yield {"client": cl, "srv": srv, "n1": n1, "n2": n2,
           "base": f"http://127.0.0.1:{port}"}
    loop.call_soon_threadsafe(loop.stop)
    for n in nodes:
        try:
            n.close()
        except Exception:  # noqa: BLE001
            pass


def test_cluster_one_get_traces_both_nodes(cluster):
    """Acceptance: one GetObject produces trace records on both nodes
    sharing a single trace_id."""
    srv, cl = cluster["srv"], cluster["client"]
    n1, n2 = cluster["n1"], cluster["n2"]
    with srv.trace_bus.subscribe() as sub:
        r = cl.get("/clbkt/obj")
        assert r.status_code == 200
        rid = r.headers["x-amz-request-id"]
        recs = []
        deadline = time.time() + 10
        while time.time() < deadline:
            item = sub.get(timeout=0.25)
            if item is not None:
                recs.append(item)
            nodes_seen = {x.get("node") for x in recs
                          if x.get("trace_id") == rid}
            if {n1.node_name, n2.node_name} <= nodes_seen:
                break
    mine = [x for x in recs if x.get("trace_id") == rid]
    nodes_seen = {x["node"] for x in mine}
    assert {n1.node_name, n2.node_name} <= nodes_seen, (
        f"trace did not span both nodes: {nodes_seen}")
    # Remote shard reads show as storage records emitted on node 2.
    n2_types = {x["type"] for x in mine if x["node"] == n2.node_name}
    assert "storage" in n2_types, n2_types
    assert _wait_no_subscribers(srv.trace_bus)


def test_cluster_admin_stream_merged_and_traceid_filter(cluster):
    """The merged ?all stream carries a request's records, and ?traceid=
    keeps only that request."""
    srv, cl, base = cluster["srv"], cluster["client"], cluster["base"]

    # -- merged ?all stream sees a live request's records --
    got: list = []

    def consume(params, want, timeout=10):
        headers = SigV4Client(base, ACCESS, SECRET)._sign(
            "GET", "/minio/admin/v3/trace", params, {}, b"")
        try:
            with requests.get(f"{base}/minio/admin/v3/trace",
                              params=params, headers=headers,
                              stream=True, timeout=timeout) as r:
                for line in r.iter_lines():
                    if line:
                        got.append(json.loads(line))
                        if len(got) >= want:
                            return
        except requests.RequestException:
            pass

    t = threading.Thread(target=consume, args=({"all": "true"}, 3),
                         daemon=True)
    t.start()
    deadline = time.time() + 5
    while not srv.trace_bus.has_subscribers and time.time() < deadline:
        time.sleep(0.05)
    r = cl.get("/clbkt/obj")
    rid = r.headers["x-amz-request-id"]
    t.join(timeout=10)
    assert any(x.get("trace_id") == rid for x in got), got[:3]

    # -- ?traceid= admits only the matching request --
    got = []
    t = threading.Thread(
        target=consume, args=({"traceid": "FILTER-HIT"}, 1), daemon=True)
    t.start()
    deadline = time.time() + 5
    while not srv.trace_bus.has_subscribers and time.time() < deadline:
        time.sleep(0.05)
    srv.trace_bus.publish({"type": "internal", "name": "miss",
                           "trace_id": "FILTER-MISS"})
    srv.trace_bus.publish({"type": "internal", "name": "hit",
                           "trace_id": "FILTER-HIT"})
    t.join(timeout=10)
    assert got and got[0]["trace_id"] == "FILTER-HIT"
    assert all(x["trace_id"] == "FILTER-HIT" for x in got)
    assert _wait_no_subscribers(srv.trace_bus)


def test_cluster_metrics_federation_both_servers(cluster):
    """Acceptance: /minio/v2/metrics/cluster returns samples labeled
    with both `server` values."""
    cl = cluster["client"]
    n1, n2 = cluster["n1"], cluster["n2"]
    r = _scrape(cl)
    families, samples = parse_exposition(r.text)
    servers = {lbl.get("server") for _n, lbl, _v in samples}
    assert n1.node_name in servers and n2.node_name in servers, servers
    # Histogram invariants survive the merge.
    _check_histogram(families, samples, "minio_tpu_drive_latency_seconds")
    # The node endpoint stays single-node (no server label).
    _, nsamples = parse_exposition(_scrape(cl, "/minio/v2/metrics/node").text)
    assert not {lbl.get("server") for _n, lbl, _v in nsamples} - {None}


def test_cluster_scrape_bounded_with_hung_peer(cluster):
    """Acceptance: the cluster scrape still returns within the deadline
    when one peer's metrics route hangs (naughty-style HANG: the hook
    blocks until released)."""
    cl, n2 = cluster["client"], cluster["n2"]
    from tests.naughty import HANG  # the injection contract  # noqa: F401

    release = threading.Event()
    old = n2.hooks.metrics

    def hang() -> bytes:
        release.wait(30)  # bounded so the leaked handler always exits
        return b""

    n2.hooks.metrics = hang
    try:
        t0 = time.time()
        r = _scrape(cl)
        elapsed = time.time() - t0
        assert elapsed < 8, f"scrape stalled {elapsed:.1f}s on hung peer"
        families, samples = parse_exposition(r.text)
        errs = [v for n, _l, v in samples
                if n == "minio_tpu_peer_scrape_errors_total"]
        assert errs and max(errs) >= 1, "hung peer not counted"
        # The healthy node's samples still render.
        servers = {lbl.get("server") for _n, lbl, _v in samples}
        assert cluster["n1"].node_name in servers
    finally:
        release.set()
        n2.hooks.metrics = old


def test_cluster_trace_stream_survives_peer_death(cluster):
    """The merged stream keeps flowing when one peer dies mid-stream.
    Runs LAST in this module: it takes node 2's RPC fabric down."""
    srv, base = cluster["srv"], cluster["base"]
    n2 = cluster["n2"]
    from minio_tpu.admin.pubsub import PubSub

    peer_bus = PubSub()
    n2.hooks.trace_bus = peer_bus

    got: list = []
    stop = threading.Event()

    def consume():
        params = {"all": "true"}
        headers = SigV4Client(base, ACCESS, SECRET)._sign(
            "GET", "/minio/admin/v3/trace", params, {}, b"")
        try:
            with requests.get(f"{base}/minio/admin/v3/trace", params=params,
                              headers=headers, stream=True,
                              timeout=20) as r:
                for line in r.iter_lines():
                    if stop.is_set():
                        return
                    if line:
                        got.append(json.loads(line))
        except requests.RequestException:
            pass

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    deadline = time.time() + 10
    # Both the local subscription and the peer puller must be live.
    while (not srv.trace_bus.has_subscribers
           or not peer_bus.has_subscribers) and time.time() < deadline:
        time.sleep(0.05)
    assert peer_bus.has_subscribers, "peer puller never subscribed"

    peer_bus.publish({"type": "internal", "name": "from-n2", "node": "n2"})
    deadline = time.time() + 5
    while not any(x.get("name") == "from-n2" for x in got) \
            and time.time() < deadline:
        time.sleep(0.05)
    assert any(x.get("name") == "from-n2" for x in got), "peer record lost"

    # Kill node 2's fabric mid-stream; local records must keep flowing.
    n2.node_server.close()
    time.sleep(0.2)
    srv.trace_bus.publish({"type": "internal", "name": "local-after-death"})
    deadline = time.time() + 5
    while not any(x.get("name") == "local-after-death" for x in got) \
            and time.time() < deadline:
        srv.trace_bus.publish({"type": "internal",
                               "name": "local-after-death"})
        time.sleep(0.2)
    assert any(x.get("name") == "local-after-death" for x in got), \
        "merged stream died with the peer"
    stop.set()
