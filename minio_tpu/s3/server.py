"""The S3 HTTP server: request classification, auth, dispatch.

Reference: cmd/routers.go + cmd/api-router.go + cmd/object-handlers.go /
cmd/bucket-handlers.go. S3 routing is query-string-driven, so instead of a
route table per verb we classify each request once (bucket, key, query,
method) and dispatch from one table — the same effect as the reference's
gorilla/mux Queries() matchers without the mux.

Run: python -m minio_tpu.s3.server --address 127.0.0.1:9000 /tmp/d{0...5}
"""

from __future__ import annotations

import argparse
import asyncio
import hashlib
import os
import tempfile
import time
import urllib.parse
import uuid
from typing import Iterator

from aiohttp import http_writer, web, web_response
from aiohttp.http_writer import StreamWriter

from minio_tpu import obs, qos
from minio_tpu.obs import flight
from minio_tpu.admin.configkv import ConfigSys
from minio_tpu.admin.handlers import ADMIN_PREFIX, AdminAPI
from minio_tpu.admin.metrics import (
    OPENMETRICS_CONTENT_TYPE,
    PROM_CONTENT_TYPE,
    collect_cluster_metrics,
    collect_node_metrics,
    maybe_gzip,
    wants_openmetrics,
)
from minio_tpu.admin.stats import HTTPStats
from minio_tpu.bucket import objectlock as olock
from minio_tpu.crypto import compress as czip
from minio_tpu.crypto import sse
from minio_tpu.bucket.meta import BucketMetadataSys
from minio_tpu.erasure import ErasureObjects
from minio_tpu.erasure.types import CompletePart, ObjectOptions, ObjectToDelete
from minio_tpu.event import EventNotifier, new_object_event
from minio_tpu.event import event as evt
from minio_tpu.iam.actions import action_for
from minio_tpu.iam.policy import Policy, PolicyArgs
from minio_tpu.iam.sys import ANONYMOUS, IAMSys
from minio_tpu.s3 import sigv2, sigv4, xmlutil
from minio_tpu.s3.errors import S3Error, from_exception
from minio_tpu.storage import LocalDrive
from minio_tpu.utils import errors as se


class _MemStore:
    """In-memory sys-config store for backends without one (FS/tests)."""

    def __init__(self):
        self._docs: dict[str, bytes] = {}

    def read_sys_config(self, path: str) -> bytes:
        if path not in self._docs:
            raise se.FileNotFound(path)
        return self._docs[path]

    def write_sys_config(self, path: str, data: bytes) -> None:
        self._docs[path] = data

    def delete_sys_config(self, path: str) -> None:
        if self._docs.pop(path, None) is None:
            raise se.FileNotFound(path)

    def list_sys_config(self, prefix: str = "") -> list[str]:
        return sorted(k for k in self._docs if k.startswith(prefix))

XML_TYPE = "application/xml"
MAX_OBJECT_SIZE = 5 * (1 << 40)

# Request-path latency distributions (reference metrics-v2
# minio_s3_requests_* / minio_s3_ttfb_seconds). TTFB for a streamed GET
# is stamped when the response headers flush; buffered responses fall
# back to handler completion (bytes leave with the return).
_REQ_LATENCY = obs.histogram(
    "minio_tpu_s3_requests_latency_seconds",
    "End-to-end request latency by API", ("api",))
_REQ_TTFB = obs.histogram(
    "minio_tpu_s3_ttfb_seconds",
    "Time to first response byte by API", ("api",))
# How late a 100 ms timer on the server's event loop wakes: the queueing
# every request pays on the one loop thread (_loop_lag_sampler).
_LOOP_LAG = obs.histogram(
    "minio_tpu_entry_loop_lag_seconds",
    "Lateness of a periodic wake-up on the S3 event loop").labels()
_LOOP_LAG_PERIOD_S = 0.1
# Per-tenant SLO families (QoS plane, docs/QOS.md): tenant = the
# "access_key/bucket" key bound in _dispatch. Always on — the noisy-
# neighbor chaos gate reads scrape deltas of these to prove each
# victim's p99/5xx held while an aggressor shed.
_TENANT_LATENCY = obs.histogram(
    "minio_tpu_tenant_request_seconds",
    "End-to-end request latency by tenant", ("tenant",))
_TENANT_REQS = obs.counter(
    "minio_tpu_tenant_requests_total",
    "Requests by tenant and status class", ("tenant", "code"))
# The streamed GET's drain (_get_object): executor hops, and the chunks
# they carried to the loop. chunks / hops says how often grouping engages.
_DRAIN_HOPS = obs.counter(
    "minio_tpu_get_drain_hops_total",
    "Executor round trips of streamed GET bodies").labels()
_DRAIN_CHUNKS = obs.counter(
    "minio_tpu_get_drain_chunks_total",
    "Chunks those round trips handed to the event loop").labels()
_VECTORED_GROUPS = obs.counter(
    "minio_tpu_get_vectored_groups_total",
    "Round trips whose chunks left the event loop as one vectored "
    "write (transport.writelines), not one write a chunk").labels()
# CPython 3.12's selector socket transport sends writelines' buffers as
# they are with one sendmsg (up to IOV_MAX of them), where write() is one
# send a call. Only that writelines is taken for vectored (_vectored_writer):
# the base class's and the TLS transport's join or copy.
try:
    from asyncio.selector_events import _HAS_SENDMSG, _SelectorSocketTransport
except ImportError:  # another asyncio: every group is written chunk by chunk
    _HAS_SENDMSG, _SelectorSocketTransport = False, None
# On CPython 3.12.0-3.12.8 and 3.13.0-3.13.1 that writelines never pauses
# the protocol (CVE-2024-12254): drain() would return at once and a stalled
# client would have the whole object queued. aiohttp keeps the flag for its
# own writelines; an aiohttp without it is not one this was written against.
_SKIP_WRITELINES = getattr(http_writer, "SKIP_WRITELINES", True)
# Inline-object streams are plain list iterators (zero IO behind next()) —
# the GET fast path detects them by type to drain on the event loop.
_LIST_ITER = type(iter([]))
SPOOL_LIMIT = 32 << 20


def _scalar_claim(v) -> str | None:
    """Claim value as a condition string; compound claims (lists, maps)
    don't map to a single condition value and are skipped. The string
    spelling itself is the condition subsystem's (one coercion rule for
    stamping at STS issue time and evaluating at request time)."""
    from minio_tpu.iam.condition import scalar_str

    if isinstance(v, (str, int, float, bool)):
        return scalar_str(v)
    return None


def _int_q(q: dict, name: str, default: int, lo: int = 0, hi: int = 100_000) -> int:
    raw = q.get(name)
    if raw in (None, ""):
        return default
    try:
        v = int(raw)
    except ValueError:
        raise S3Error("InvalidArgument", f"invalid {name}") from None
    if not lo <= v <= hi:
        raise S3Error("InvalidArgument", f"{name} out of range")
    return v


def _drain_group(it, budget: int) -> tuple[list, bool]:
    """Blocking: pull chunks from `it` until they hold `budget` bytes or
    it ends -> (chunks, ended). The end comes back with the last chunks:
    no hop for a sentinel, unless the budget filled on the very last chunk
    (then one more call returns ([], True))."""
    chunks, size = [], 0
    for chunk in it:
        chunks.append(chunk)
        size += len(chunk)
        if size >= budget:
            return chunks, False
    return chunks, True


def _vectored_writer(resp: web.StreamResponse) -> StreamWriter | None:
    """A prepared response's payload writer, if a group of chunks can
    leave it as one vectored write: it sends the bytes as they are (not
    chunked, not compressing) and its transport's writelines is the
    socket transport's sendmsg. None otherwise (TLS, another loop's
    transport, a chunked or compressed response, a runtime whose
    writelines never pauses the protocol, an aiohttp whose payload writer
    is not the one this was written against): write chunk by chunk.
    The vectored write stands in for aiohttp's own `StreamResponse.write`
    only: where that is someone else's code (a subclass's, a wrapper put
    on the class), every chunk goes through it."""
    if _SKIP_WRITELINES or not _HAS_SENDMSG:
        return None
    code = getattr(getattr(type(resp), "write", None), "__code__", None)
    if code is None or code.co_filename != web_response.__file__:
        return None
    w = getattr(resp, "_payload_writer", None)
    if type(w) is not StreamWriter or w.chunked:
        return None
    # aiohttp's private names: one it does not have reads as "in use",
    # so an unknown aiohttp falls back and does not fail.
    if (getattr(w, "_compress", True) is not None
            or getattr(w, "_on_chunk_sent", True) is not None
            or not callable(getattr(w, "_writelines", None))
            or not callable(getattr(w, "send_headers", None))):
        return None
    tr = w.transport
    if tr is None or getattr(
            type(tr), "writelines", None
    ) is not _SelectorSocketTransport.writelines:
        return None
    return w


async def _write_group(w: StreamWriter, chunks: list) -> None:
    """What `await resp.write(chunk)` for every chunk does to the payload
    writer (headers out first if aiohttp still holds them, Content-Length
    counted down and never passed), with aiohttp's own ONE
    transport.writelines of the views as they are (it keeps the sizes and
    refuses a closing transport), then the writer's back-pressure once: a
    slow client holds one group."""
    out, left = [], w.length
    for c in chunks:
        if isinstance(c, memoryview) and c.nbytes != len(c):
            c = c.cast("c")
        if left is not None:
            if len(c) > left:
                c = c[:left]
            left -= len(c)
        if len(c):
            out.append(c)
    w.length = left
    if not out:
        return
    # A StreamResponse's headers left at prepare(); a writer that still
    # holds them (aiohttp >= 3.13 can, until the first write) sends them now.
    w.send_headers()
    w._writelines(out)
    w.buffer_size = 0
    await w.drain()


async def _loop_lag_sampler(_app):
    """Every request's body chunks, signature checks and response writes
    queue on this ONE event-loop thread. A 100 ms timer that observes how
    late it fired measures that queue: what any callback scheduled on
    the loop waits before it runs. (A timer, not a task: a loop that is
    stopped without cleanup drops a timer silently.)"""
    loop = asyncio.get_running_loop()
    handle = None

    def tick(due: float) -> None:
        nonlocal handle
        now = loop.time()
        _LOOP_LAG.observe(max(0.0, now - due))
        handle = loop.call_at(now + _LOOP_LAG_PERIOD_S, tick,
                              now + _LOOP_LAG_PERIOD_S)

    tick(loop.time())
    yield
    handle.cancel()


class S3Server:
    def __init__(self, object_layer, credentials: sigv4.Credentials,
                 region: str = "us-east-1", versioned_buckets: bool = False,
                 notification_sys=None):
        self.obj = object_layer
        self.creds = credentials
        self.region = region
        # Server-level versioning default (tests/simple deployments);
        # per-bucket config from BucketMetadataSys overrides.
        self.versioned_buckets = versioned_buckets
        self.app = web.Application(client_max_size=1 << 30)
        self.app.router.add_route("*", "/{tail:.*}", self._entry)
        self.app.cleanup_ctx.append(_loop_lag_sampler)

        # Security + CORS headers on every response, including prepared
        # streams (reference addSecurityHeaders + CrossDomainPolicy/CORS,
        # cmd/generic-handlers.go). The allowed origin comes from the
        # `api.cors_allow_origin` config ("*" default, "" disables).
        async def _security_headers(request, response):
            response.headers.setdefault("X-Content-Type-Options", "nosniff")
            response.headers.setdefault("X-XSS-Protection", "1; mode=block")
            response.headers.setdefault(
                "Content-Security-Policy", "block-all-mixed-content")
            response.headers.setdefault("Server", "minio-tpu")
            origin = self._cors_origin()
            if origin and request.headers.get("Origin"):
                response.headers.setdefault(
                    "Access-Control-Allow-Origin", origin)
                response.headers.setdefault(
                    "Access-Control-Expose-Headers",
                    "ETag, x-amz-version-id, x-amz-request-id, "
                    "Content-Range, Content-Length")

        self.app.on_response_prepare.append(_security_headers)

        # Subsystems persist into the quorum sys store when the backend
        # provides one (erasure); memory-only otherwise.
        has_store = hasattr(object_layer, "read_sys_config")
        store = object_layer if has_store else _MemStore()
        self.sys_store = store
        # Config + IAM are sealed at rest under the root credential
        # (cmd/config-encrypted.go role); bucket metadata and scanner
        # state stay plaintext, matching the reference's scope.
        from minio_tpu.crypto.configcrypt import SealedSysStore
        # Federated identity: MTPU_ETCD_ENDPOINT moves the IAM store to a
        # shared etcd cluster (reference cmd/etcd.go + iam-etcd-store.go
        # role) so every site sees the same users/policies; bucket
        # metadata and scanner state stay on the drive-quorum store, the
        # reference's scope. Sealing layers identically over either.
        self._etcd = None
        etcd_ep = os.environ.get("MTPU_ETCD_ENDPOINT", "")
        iam_backing = store if has_store else None
        if etcd_ep:
            from minio_tpu.dist.etcdstore import EtcdConfigStore
            self._etcd = EtcdConfigStore(
                etcd_ep,
                username=os.environ.get("MTPU_ETCD_USERNAME", ""),
                password=os.environ.get("MTPU_ETCD_PASSWORD", ""))
            iam_backing = self._etcd
        # IAM alone federates over etcd; per-cluster config, bucket
        # metadata, tiers and scanner state STAY on the drive-quorum
        # store — sharing e.g. storageclass EC:N between a 4-drive and a
        # 12-drive site would corrupt both.
        sealed = (SealedSysStore(store, credentials.secret_key)
                  if has_store else None)
        sealed_iam = (SealedSysStore(iam_backing, credentials.secret_key)
                      if iam_backing is not None else None)
        notify_bm = (notification_sys.invalidate_bucket_metadata
                     if notification_sys is not None else None)
        notify_iam = (notification_sys.reload_iam
                      if notification_sys is not None else None)
        self.bucket_meta = BucketMetadataSys(store, notify=notify_bm)
        self.iam = IAMSys(credentials.access_key, credentials.secret_key,
                          store=sealed_iam, notify=notify_iam)
        if self._etcd is not None:
            # Cross-cluster IAM changes land via the watch: another
            # site's user add/REMOVE shows up here within the poll
            # interval (iam-etcd-store.go watchIAM role). reload, not
            # load: deletions must drop from memory too.
            self._etcd.watch(
                "iam/", self.iam.reload,
                interval=float(os.environ.get(
                    "MTPU_ETCD_WATCH_INTERVAL", "5")))

        # Eventing: durable per-target queues under a local spool dir
        # (reference pkg/event/target/queuestore.go).
        queue_dir = os.environ.get(
            "MTPU_EVENT_QUEUE_DIR",
            os.path.join(tempfile.gettempdir(), f"mtpu-events-{os.getpid()}"))
        self.notifier = EventNotifier(queue_dir=queue_dir)
        self._rules_loaded: set = set()
        self._event_targets_cfg: str = ""
        self.scanner = None
        from minio_tpu.scanner.tracker import UpdateTracker
        self.update_tracker = UpdateTracker(
            store if has_store else None)

        # Admin plane + observability (cmd/admin-router.go, pkg/pubsub,
        # cmd/http-stats.go, cmd/config/).
        self.stats = HTTPStats()
        self.bandwidth: dict[str, dict[str, int]] = {}
        self._bw_mu = __import__("threading").Lock()
        # The PROCESS trace bus (reference globalTrace): storage, RPC and
        # erasure spans publish here too, so one `mc admin trace`
        # subscription sees the whole request path.
        self.trace_bus = obs.trace_bus()
        self.config = ConfigSys(sealed)
        # Per-bucket bandwidth ENFORCEMENT (pkg/bandwidth role) — rates
        # from the `bandwidth` config subsystem, applied to PUT ingest and
        # GET egress streams; the accounting dict above stays the monitor.
        from minio_tpu.utils.bandwidth import BandwidthThrottle
        self.bw_throttle = BandwidthThrottle(self.config)

        # Structured ops + audit logging (reference cmd/logger/): targets
        # come from the config KV subsystems logger_webhook / audit_webhook /
        # audit_file and can be (re)applied at runtime via admin config-set.
        from minio_tpu.logger import get_logger
        self.logger = get_logger()
        self.configure_logging()
        self.configure_event_targets()

        # Storage-class parity from the `storageclass` config (EC:N).
        self.apply_storage_class_config()

        # Replication plane (cmd/bucket-replication.go).
        from minio_tpu.replication.pool import BucketTargetSys, ReplicationPool
        self.bucket_targets = BucketTargetSys(store)
        self.replication = ReplicationPool(object_layer, self.bucket_meta,
                                           self.bucket_targets)
        from minio_tpu.admin.profiling import Profiler
        self.profiler = Profiler()

        # Bucket federation (cmd/etcd.go + pkg/dns role): when enabled,
        # bucket ownership registers in a shared directory and requests
        # for foreign buckets 307-redirect to the owning cluster.
        self.federation = None
        if (self.config.get("federation", "enable") or "") in (
                "on", "1", "true"):
            fdir = self.config.get("federation", "directory") or ""
            fep = self.config.get("federation", "endpoint") or ""
            if fdir and fep:
                from minio_tpu.dist.federation import (
                    FederationError,
                    FederationStore,
                )
                self.federation = FederationStore(fdir, fep)
                # Register buckets that predate federation (the
                # reference's initFederatorBackend does the same at
                # startup) — otherwise another cluster could claim the
                # name and split the namespace. Conflicts are logged,
                # not fatal: the operator must resolve a genuine split.
                for b in object_layer.list_buckets():
                    try:
                        self.federation.register(b.name)
                    except FederationError as e:
                        self.logger.error(
                            f"federation conflict at startup: {e}")

        # KMS for SSE-KMS envelope encryption (cmd/crypto/kes.go role):
        # a networked KES backend when kms.kes_endpoint is configured,
        # else local master keys.
        from minio_tpu.crypto.kes import kms_from_config
        self.kms = kms_from_config(self.config)

        # ILM tiers (transition targets; reference tier subsystem). Tier
        # docs carry remote-storage credentials — sealed like config/IAM.
        from minio_tpu.scanner.tiers import TierRegistry, set_global
        self.tiers = TierRegistry(sealed)
        set_global(self.tiers)
        self.admin = AdminAPI(self)

        # SLO plane (docs/SLO.md): arm the on-node metric ring + burn-
        # rate engine (no-op under MTPU_SLO=0), persist coarse history
        # through the sys store, and feed the exporter-side per-API
        # counters into the ring. Keyed source: a rebuilt server in the
        # same process replaces its predecessor's stats feed.
        from minio_tpu.obs import calibration as _calibration
        from minio_tpu.obs import kernel as _obs_kernel
        from minio_tpu.obs import slo as _slo
        _calibration.publish_build_info()   # initialises the JAX backend
        _obs_kernel.count_compiles()
        _slo_engine = _slo.ensure_started(store=store)
        if _slo_engine is not None:
            _slo_engine.db.add_source(self._slo_stats_source,
                                      key="s3-stats")

        self.local_locker = None  # set by the cluster node when distributed
        self.notification = notification_sys  # peer fan-out (distributed)
        self.cluster_node = None
        # Advertised node identity: the `node` field on trace records and
        # the `server` label in the federated cluster scrape. Standalone
        # servers fall back to the process default (hostname);
        # attach_cluster overrides with the advertised host:port.
        self.node_name = ""

        # upload_id -> user_defined: saves a quorum metadata read per
        # UploadPart/ListParts (SSE decisions are sealed at create time and
        # immutable for the upload's life).
        self._mp_sse_cache: dict[str, dict] = {}

        from minio_tpu.s3.web import WebAPI
        self.web = WebAPI(self)

    def _cluster_scrape(self, openmetrics: bool = False) -> bytes:
        """The federated cluster scrape — ONE definition shared by
        /minio/v2/metrics/cluster and its /minio/admin/v3/metrics mirror
        (docs promise they match). Blocking; run in an executor."""
        return collect_cluster_metrics(
            self.obj, self.stats,
            self.scanner.usage if self.scanner else None,
            notification=self.notification,
            local_name=self.node_name,
            openmetrics=openmetrics)

    def _has_peers(self) -> bool:
        return bool(self.notification is not None
                    and getattr(self.notification, "peers", None))

    def _slo_stats_source(self):
        """TSDB source (obs/tsdb.py): the HTTPStats-derived per-API
        request/error counters only exist exporter-side, so the ring
        samples them through this closure."""
        snap = self.stats.snapshot()
        for api, s in snap["apis"].items():
            lbl = {"api": api}
            yield "minio_tpu_s3_requests_total", lbl, s["count"]
            yield "minio_tpu_s3_requests_errors_total", lbl, s["errors"]
            yield ("minio_tpu_s3_requests_5xx_errors_total", lbl,
                   s["5xx"])

    def _cors_origin(self) -> str:
        """api.cors_allow_origin, cached against the config generation —
        this runs on EVERY response."""
        gen = getattr(self.config, "generation", 0)
        cached = getattr(self, "_cors_cache", None)
        if cached is not None and cached[0] == gen:
            return cached[1]
        try:
            origin = self.config.get("api", "cors_allow_origin")
        except Exception:  # noqa: BLE001 - config not ready yet
            origin = "*"
        self._cors_cache = (gen, origin)
        return origin

    def apply_storage_class_config(self) -> None:
        """Parse storageclass.standard/rrs ("EC:N") and stamp the parity
        map onto every erasure set — live-appliable via admin config-set
        (reference cmd/config/storageclass)."""
        def parse(v: str):
            v = (v or "").strip().upper()
            if v.startswith("EC:"):
                try:
                    return int(v[3:])
                except ValueError:
                    return None
            return None

        sc_map = {}
        for key, name in (("standard", "STANDARD"), ("rrs", "RRS")):
            try:
                m = parse(self.config.get("storageclass", key))
            except Exception:  # noqa: BLE001
                m = None
            if m is not None:
                sc_map[name] = m
        # The per-set clamp (parity <= drives/2, reference
        # validateParity) applies where the geometry is known.
        layer = self.obj
        while layer is not None and not any(
                hasattr(layer, a) for a in ("pools", "sets", "drives")):
            layer = getattr(layer, "inner", None)
        stack = [layer] if layer is not None else []
        while stack:
            node = stack.pop()
            if node is None:
                continue
            for attr in ("pools", "sets"):
                kids = getattr(node, attr, None)
                if kids:
                    stack.extend(kids)
            if hasattr(node, "parity_for_class"):
                node.sc_parity = dict(sc_map)

    def start_scanner(self, interval: float = 60.0,
                      heal_objects: bool = True) -> None:
        """Boot the background data scanner (reference initDataScanner,
        cmd/data-scanner.go:65)."""
        from minio_tpu.scanner import DataScanner

        self.scanner = DataScanner(self.obj, self.bucket_meta,
                                   notifier=self.notifier,
                                   interval=interval,
                                   heal_objects=heal_objects,
                                   tracker=self.update_tracker,
                                   config=self.config,
                                   replication=self.replication)
        self.scanner.start()

    # Set by main() (the CLI entry point); embedded servers either leave it
    # None (restart reports NotImplemented) or override restart().
    restart_cmd: list[str] | None = None

    @property
    def can_restart(self) -> bool:
        return (self.restart_cmd is not None
                or "restart" in self.__dict__           # instance override
                or type(self).restart is not S3Server.restart)  # subclass

    def restart(self) -> None:
        """In-place process restart (`mc admin service restart` role,
        cmd/admin-handlers.go ServiceActionHandler): re-exec the command
        line main() registered; durable state (format, journals, config,
        IAM) is all on disk, so the new process resumes cleanly.
        Overridable hook so embedded/test servers can intercept."""
        if self.restart_cmd:
            os.execv(self.restart_cmd[0], self.restart_cmd)

    def shutdown(self) -> None:
        os._exit(0)

    def attach_cluster(self, node) -> None:
        """Wire this node's observability into the peer plane so every
        peer can pull our trace/console/info/profiles (the NotificationSys
        breadth of cmd/peer-rest-common.go:27-61)."""
        self.cluster_node = node
        self.notification = node.notification
        self.node_name = node.node_name
        # Admin force-unlock operates on THIS node's dsync locker (the
        # reference ForceUnlockHandler clears the local lock-rest
        # server): without this wire the endpoint 501s in exactly the
        # deployment it exists for. The chaos tier leans on it as the
        # documented remedy for a dead node's stale heal lock.
        self.local_locker = node.locker
        # Replication's faultplane identity: partition rules between
        # clusters name this node's advertised host:port as the source.
        self.replication.set_node(node.node_name)
        obs.set_default_node(node.node_name)
        node.hooks.trace_bus = self.trace_bus
        node.hooks.console_bus = self.logger.console_bus
        node.hooks.server_info = self.admin._server_info
        node.hooks.obd_info = self.admin._obd_info
        node.hooks.profiler = self.profiler
        # Flight-recorder federation: the perf/timeline endpoint fans
        # out the same way server_info does — each peer answers with its
        # local ring/worst boards, filtered server-side.
        node.hooks.perf_timeline = self.admin._perf_timelines
        # Metrics federation: peers scrape this node's node-scope
        # exposition over the peer plane and merge it under a `server`
        # label (admin/metrics.collect_cluster_metrics).
        node.hooks.metrics = lambda: collect_node_metrics(self.stats)
        # SLO federation: peers pull this node's worker-merged burn-rate
        # state for the federated GET /minio/admin/v3/slo.
        from minio_tpu.obs import slo as _slo
        node.hooks.slo = _slo.collect_local

    def configure_logging(self) -> None:
        """(Re)build log/audit targets from the config KV store — the
        dynamic subset of cmd/config: logger_webhook.{enable,endpoint,
        auth_token}, audit_webhook.{...}, audit_file.path."""
        from minio_tpu.logger import FileTarget, HTTPTarget

        log_targets: list = []
        audit_targets: list = []
        if (self.config.get("logger_webhook", "enable") or "") in ("on", "1", "true"):
            ep = self.config.get("logger_webhook", "endpoint") or ""
            if ep:
                log_targets.append(HTTPTarget(
                    ep, self.config.get("logger_webhook", "auth_token") or ""))
        if (self.config.get("audit_webhook", "enable") or "") in ("on", "1", "true"):
            ep = self.config.get("audit_webhook", "endpoint") or ""
            if ep:
                audit_targets.append(HTTPTarget(
                    ep, self.config.get("audit_webhook", "auth_token") or ""))
        audit_path = self.config.get("audit_file", "path") or ""
        if audit_path:
            audit_targets.append(FileTarget(audit_path))
        # Close displaced webhook targets — each holds a drain thread and
        # a bounded queue that would otherwise leak on every re-apply.
        for t in self.logger.targets[1:] + self.logger.audit_targets:
            if hasattr(t, "close"):
                t.close()
        self.logger.targets = self.logger.targets[:1] + log_targets
        self.logger.audit_targets = audit_targets

    def configure_event_targets(self) -> None:
        """(Re)apply notification targets from the notify_* config
        subsystems (reference cmd/config/notify + pkg/event/target/*):
        enabled targets register, changed ones are replaced, disabled ones
        unregister. Reads through ConfigSys.get so env overrides keep
        their documented precedence."""
        import json as _json

        from minio_tpu.event.targets import (
            AMQPTarget,
            ElasticsearchTarget,
            KafkaTarget,
            MQTTTarget,
            MySQLTarget,
            NATSTarget,
            NSQTarget,
            PostgresTarget,
            RedisTarget,
            WebhookTarget,
        )

        subsys_keys = {
            "notify_webhook": ("enable", "endpoint", "auth_token"),
            "notify_nats": ("enable", "address", "subject"),
            "notify_redis": ("enable", "address", "key", "password", "format"),
            "notify_mqtt": ("enable", "address", "topic"),
            "notify_elasticsearch": ("enable", "url", "index"),
            "notify_nsq": ("enable", "address", "topic"),
            "notify_kafka": ("enable", "brokers", "topic"),
            "notify_amqp": ("enable", "url", "exchange", "routing_key",
                            "user", "password", "vhost"),
            "notify_postgres": ("enable", "address", "table", "user",
                                "password", "database"),
            "notify_mysql": ("enable", "address", "table", "user",
                             "password", "database"),
        }
        cfg = {s: {k: self.config.get(s, k) or "" for k in keys}
               for s, keys in subsys_keys.items()}
        sig = _json.dumps(cfg, sort_keys=True)
        if sig == self._event_targets_cfg:
            return
        self._event_targets_cfg = sig

        def on(s):
            return cfg[s]["enable"] in ("on", "1", "true")

        targets = []

        def add(factory) -> None:
            # A malformed persisted value (bad URL/port/table name) must
            # degrade to a logged error, never an unbootable server:
            # this runs during __init__ on every start.
            try:
                targets.append(factory())
            except (ValueError, OSError, KeyError) as e:
                self.logger.error(f"event target config invalid: {e}")
        if on("notify_webhook") and cfg["notify_webhook"]["endpoint"]:
            add(lambda: WebhookTarget(
                cfg["notify_webhook"]["endpoint"],
                auth_token=cfg["notify_webhook"]["auth_token"]))
        if on("notify_nats") and cfg["notify_nats"]["address"]:
            add(lambda: NATSTarget(cfg["notify_nats"]["address"],
                                      cfg["notify_nats"]["subject"]))
        if on("notify_redis") and cfg["notify_redis"]["address"]:
            add(lambda: RedisTarget(
                cfg["notify_redis"]["address"], cfg["notify_redis"]["key"],
                password=cfg["notify_redis"]["password"],
                publish=cfg["notify_redis"]["format"] == "channel"))
        if on("notify_mqtt") and cfg["notify_mqtt"]["address"]:
            add(lambda: MQTTTarget(cfg["notify_mqtt"]["address"],
                                      cfg["notify_mqtt"]["topic"]))
        if on("notify_elasticsearch") and cfg["notify_elasticsearch"]["url"]:
            add(lambda: ElasticsearchTarget(
                cfg["notify_elasticsearch"]["url"],
                cfg["notify_elasticsearch"]["index"]))
        if on("notify_nsq") and cfg["notify_nsq"]["address"]:
            add(lambda: NSQTarget(cfg["notify_nsq"]["address"],
                                     cfg["notify_nsq"]["topic"]))
        if on("notify_kafka") and cfg["notify_kafka"]["brokers"]:
            add(lambda: KafkaTarget(cfg["notify_kafka"]["brokers"],
                                       cfg["notify_kafka"]["topic"]))
        if on("notify_amqp") and cfg["notify_amqp"]["url"]:
            add(lambda: AMQPTarget(
                cfg["notify_amqp"]["url"],
                cfg["notify_amqp"]["exchange"],
                cfg["notify_amqp"]["routing_key"],
                user=cfg["notify_amqp"]["user"],
                password=cfg["notify_amqp"]["password"],
                vhost=cfg["notify_amqp"]["vhost"]))
        if on("notify_postgres") and cfg["notify_postgres"]["address"] \
                and cfg["notify_postgres"]["table"]:
            add(lambda: PostgresTarget(
                cfg["notify_postgres"]["address"],
                cfg["notify_postgres"]["table"],
                user=cfg["notify_postgres"]["user"],
                password=cfg["notify_postgres"]["password"],
                database=cfg["notify_postgres"]["database"]))
        if on("notify_mysql") and cfg["notify_mysql"]["address"] \
                and cfg["notify_mysql"]["table"]:
            add(lambda: MySQLTarget(
                cfg["notify_mysql"]["address"],
                cfg["notify_mysql"]["table"],
                user=cfg["notify_mysql"]["user"],
                password=cfg["notify_mysql"]["password"],
                database=cfg["notify_mysql"]["database"]))

        # Replace-or-remove semantics over the config-managed ARN space.
        managed_kinds = ("webhook", "nats", "redis", "mqtt",
                         "elasticsearch", "nsq", "kafka", "amqp",
                         "postgresql", "mysql")
        want = {t.arn: t for t in targets}
        for arn in list(self.notifier.target_arns):
            if arn.rsplit(":", 1)[-1] in managed_kinds and arn not in want:
                self.notifier.unregister_target(arn)
        for arn, t in want.items():
            if arn in self.notifier.target_arns:
                self.notifier.unregister_target(arn)  # config changed
            self.notifier.register_target(t)

    def start_auto_heal(self, interval: float = 10.0) -> None:
        """Boot the background new-drive healer (reference initAutoHeal,
        cmd/background-newdisks-heal-ops.go:241): drives carrying a
        persisted healing tracker get their set rebuilt and the tracker
        resumes across restarts."""
        from minio_tpu.erasure.autoheal import AutoHealer

        target = self.obj
        # unwrap decorators (cache) down to something with sets/drives
        while not hasattr(target, "drives") and hasattr(target, "inner"):
            target = target.inner
        pools = getattr(target, "pools", None)
        load_fn = lambda: self.stats.current_requests  # noqa: E731
        if pools:
            self.auto_healer = [AutoHealer(p, interval=interval,
                                           config=self.config,
                                           load_fn=load_fn)
                                for p in pools]
            for h in self.auto_healer:
                h.start()
        elif hasattr(target, "drives") or hasattr(target, "sets"):
            self.auto_healer = [AutoHealer(target, interval=interval,
                                           config=self.config,
                                           load_fn=load_fn)]
            self.auto_healer[0].start()
        else:
            self.auto_healer = []

    # ------------------------------------------------------------------

    def _lookup(self, access_key: str):
        try:
            return sigv4.Credentials(access_key,
                                     self.iam.get_secret(access_key))
        except se.InvalidAccessKey:
            return None

    def _bucket_versioned(self, bucket: str) -> bool:
        if self.versioned_buckets:
            return True
        return self.bucket_meta.get(bucket).versioning_enabled

    def _condition_context(self, request, identity,
                           q: dict | None = None) -> dict[str, list[str]]:
        """The request's condition values (reference getConditionValues,
        cmd/bucket-policy.go:65-110): every authorized request carries a
        POPULATED context so conditioned statements — above all a
        conditioned Deny — evaluate against real values instead of
        silently not applying. Keys are lowercase (condition keys are
        case-insensitive); values are string lists."""
        now = time.time()
        # Same trust gate as _client_ip: behind a TLS-terminating proxy
        # the backend hop is plaintext, so the canonical enforce-TLS
        # Deny (Bool aws:SecureTransport false) would lock the bucket
        # for everyone unless X-Forwarded-Proto is honored.
        secure = request.secure
        if (self.config.get("api", "trust_proxy_headers") or "") in (
                "on", "1", "true"):
            fwd_proto = request.headers.get("X-Forwarded-Proto", "")
            if fwd_proto:
                secure = fwd_proto.split(",")[0].strip().lower() == "https"
        ctx: dict[str, list[str]] = {
            "aws:sourceip": [self._client_ip(request)],
            "aws:securetransport": ["true" if secure else "false"],
            "aws:currenttime": [time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                              time.gmtime(now))],
            "aws:epochtime": [str(int(now))],
        }
        ua = request.headers.get("User-Agent", "")
        if ua:
            ctx["aws:useragent"] = [ua]
        referer = request.headers.get("Referer", "")
        if referer:
            ctx["aws:referer"] = [referer]
        kind = getattr(identity, "kind", "anonymous")
        if kind == "anonymous":
            ctx["aws:principaltype"] = ["Anonymous"]
        else:
            ctx["aws:principaltype"] = [
                {"root": "Account", "sts": "AssumedRole"}.get(kind, "User")]
            # MinIO usernames ARE access keys; temp/service credentials
            # report their owning user (cmd/iam.go policy variables).
            ctx["aws:username"] = [identity.parent or identity.access_key]
            ctx["aws:userid"] = [identity.access_key]
        # Auth classification (set during signature verification; absent
        # on the web/admin JWT planes, where the keys stay missing).
        auth = request.get("auth-type")
        if auth:
            ctx["s3:authtype"] = [auth[0]]
            ctx["s3:signatureversion"] = [auth[1]]
        # STS claim values ("jwt:sub", "ldap:username", ...) let
        # WebIdentity/LDAP session policies scope by claim.
        for ck, cv in getattr(identity, "claims", {}).items():
            lk = str(ck).lower()
            if lk.startswith(("jwt:", "ldap:")):
                ctx[lk] = [str(cv)]
        if q:
            if q.get("versionId"):
                ctx["s3:versionid"] = [q["versionId"]]
            # Listing scope keys ride only when the client sent them
            # (AWS populates s3:prefix et al. per-request, not with
            # defaults — a policy requiring s3:prefix must see an
            # unprefixed listing as non-matching).
            for qk, ck2 in (("prefix", "s3:prefix"),
                            ("delimiter", "s3:delimiter"),
                            ("max-keys", "s3:max-keys")):
                if qk in q:
                    ctx[ck2] = [q[qk]]
        for hk, ck3 in (
                ("x-amz-object-lock-mode", "s3:object-lock-mode"),
                ("x-amz-object-lock-retain-until-date",
                 "s3:object-lock-retain-until-date"),
                ("x-amz-object-lock-legal-hold",
                 "s3:object-lock-legal-hold"),
                ("x-amz-acl", "s3:x-amz-acl"),
                ("x-amz-copy-source", "s3:x-amz-copy-source"),
                ("x-amz-storage-class", "s3:x-amz-storage-class"),
                ("x-amz-metadata-directive", "s3:x-amz-metadata-directive"),
                ("x-amz-server-side-encryption",
                 "s3:x-amz-server-side-encryption"),
                ("x-amz-server-side-encryption-aws-kms-key-id",
                 "s3:x-amz-server-side-encryption-aws-kms-key-id"),
                ("x-amz-content-sha256", "s3:x-amz-content-sha256"),
        ):
            hv = request.headers.get(hk, "")
            if hv:
                ctx[ck3] = [hv]
        # Already lowercase str-lists — mark it so the PolicyArgs built
        # from this context (one per _check_access; one per KEY on bulk
        # delete) don't each re-copy the dict.
        from minio_tpu.iam.condition import normalize_values
        return normalize_values(ctx)

    def _check_access(self, identity, action: str, bucket: str, key: str,
                      conditions: dict) -> None:
        """Authorize: identity policies ∪ bucket policy; explicit denies in
        either win (cmd/auth-handler.go:274 checkRequestAuthType).
        `conditions` is required — every call site supplies the populated
        per-request context from _condition_context (an empty default here
        made conditioned Deny statements silently inert)."""
        args = PolicyArgs(action=action, bucket=bucket, object=key,
                          conditions=conditions)
        pol_raw = (self.bucket_meta.get(bucket).policy_json
                   if bucket else b"")
        if pol_raw:
            bp = Policy.parse_cached(pol_raw)
            bargs = PolicyArgs(action=action, bucket=bucket, object=key,
                               conditions=conditions,
                               account=identity.access_key or "*")
            # Bucket-policy deny beats everything, including identity allow.
            for st in bp.statements:
                if st.effect == "Deny" and st.applies(bargs):
                    raise S3Error("AccessDenied", resource=f"/{bucket}/{key}")
            if bp.is_allowed(bargs):
                return
        if self.iam.is_allowed(identity, args):
            return
        raise S3Error("AccessDenied", resource=f"/{bucket}/{key}")

    @staticmethod
    def _require_private_acl(request, body: bytes) -> None:
        """PutBucketAcl/PutObjectAcl accept only the private canned ACL
        (header or XML body); grants the policy model can't express are
        refused with NotImplemented (reference acl-handlers.go)."""
        canned = request.headers.get("x-amz-acl", "")
        if canned and canned != "private":
            raise S3Error("NotImplemented",
                          f"canned ACL {canned!r} is not supported")
        try:
            if not xmlutil.acl_body_is_private(body):
                raise S3Error("NotImplemented",
                              "only the private (FULL_CONTROL owner) ACL "
                              "is supported")
        except ValueError:
            raise S3Error("MalformedXML") from None

    async def _entry(self, request: web.Request) -> web.StreamResponse:
        request_id = uuid.uuid4().hex[:16].upper()
        # The request id IS the trace id: bound to the handler's context
        # here, copied into every executor/pool hop (obs.ctx_wrap), and
        # carried to peers as the x-mtpu-trace-id RPC header — every
        # trace record this request causes, on any node, shares it.
        obs.set_trace_context(request_id, node=self.node_name or None)
        # Flight recorder: the stage timeline opens with the trace
        # context and closes (final `resp_drain` segment) in the finally
        # below — queryable via /minio/admin/v3/perf/timeline.
        flight.begin(request_id)
        path = urllib.parse.unquote(request.raw_path.split("?", 1)[0])
        if request.method == "OPTIONS" and request.headers.get("Origin") \
                and self._cors_origin():
            # CORS preflight (reference CorsHandler) — only when CORS is
            # enabled; Authorization must be listed explicitly (the Fetch
            # spec's wildcard excludes it, which would block signed
            # cross-origin requests). Allow-Origin attaches in the shared
            # on_response_prepare hook.
            return web.Response(status=200, headers={
                "Access-Control-Allow-Methods":
                    "GET, PUT, POST, DELETE, HEAD",
                "Access-Control-Allow-Headers":
                    "Authorization, Content-Type, Content-MD5, "
                    "x-amz-date, x-amz-content-sha256, "
                    "x-amz-security-token, x-amz-user-agent, *",
                "Access-Control-Max-Age": "3600"})
        t0 = self.stats.begin(
            request_id=request_id,
            api_hint=request.method.lower(),
            remote=self._client_ip(request),
            # Live API resolution: dispatch stamps request["api"] once it
            # classifies the call; the `top api` view reads it through
            # this getter so an in-flight request shows its real API.
            api_get=lambda: request.get("api"),
            # Same lazy contract for the tenant column: bound by
            # dispatch after auth resolves the identity.
            tenant_get=lambda: request.get("tenant"))
        request["mtpu-t0"] = t0
        resp = None
        canceled = False
        try:
            # Request-concurrency throttle (reference maxClients,
            # cmd/handler-api.go:136): over the configured ceiling new
            # requests shed with 503 + Retry-After rather than queue.
            limit = int(self.config.get("api", "requests_max") or 0)
            if limit and self.stats.current_requests > limit:
                raise S3Error("SlowDown", resource=path)
            resp = await self._dispatch(request, path, request_id)
            return resp
        except S3Error as e:
            if e.api.code == "NoSuchBucket":
                fed = await self._federation_redirect(request, path)
                if fed is not None:
                    resp = fed
                    return resp
            resp = self._error_response(e, path, request_id)
            return resp
        except web.HTTPException as e:  # web-console handlers raise these
            resp = e
            raise
        except asyncio.CancelledError:
            # Client went away mid-request (aiohttp cancels the handler):
            # account it separately — a disconnect is not a server error.
            canceled = True
            raise
        except Exception as e:  # noqa: BLE001 - surface as S3 InternalError
            s3e = from_exception(e, path)
            if s3e.api.code == "NoSuchBucket":
                fed = await self._federation_redirect(request, path)
                if fed is not None:
                    resp = fed
                    return resp
            resp = self._error_response(s3e, path, request_id)
            return resp
        finally:
            status = resp.status if resp is not None else 500
            if canceled and resp is None:
                # Client closed the connection before a response formed —
                # nginx's 499, NOT a server error.
                status = 499
            api = request.get("api", request.method.lower())
            rx = request.content_length or 0
            tx = (resp.content_length or 0) if resp is not None else 0
            dt = time.perf_counter() - t0
            flight.set_api(api)
            flight.end(status=status)
            self.stats.end(api, t0, status, rx=rx, tx=tx, canceled=canceled,
                           request_id=request_id)
            _REQ_LATENCY.labels(api=api).observe(dt)
            tkey = request.get("tenant")
            if tkey:
                # metric_key folds unbounded tenant keys (scanner
                # probes mint "anonymous/<path>" pre-bucket-check) into
                # "~other" past the registry cardinality backstop.
                mkey = qos.metric_key(tkey)
                _TENANT_LATENCY.labels(tenant=mkey).observe(dt)
                _TENANT_REQS.labels(
                    tenant=mkey, code=f"{status // 100}xx").inc()
            # Streamed GETs stamp first-byte at header flush; everything
            # else flushes with the handler return, so TTFB == latency.
            ttfb = request.get("mtpu-ttfb")
            _REQ_TTFB.labels(api=api).observe(dt if ttfb is None else ttfb)
            # Per-bucket bandwidth accounting (pkg/bandwidth role).
            bkt = path.lstrip("/").split("/", 1)[0]
            if bkt and not bkt.startswith("minio") and (rx or tx):
                with self._bw_mu:
                    b = self.bandwidth.setdefault(
                        bkt, {"rx": 0, "tx": 0})
                    b["rx"] += rx
                    b["tx"] += tx
            # Trace record only when someone is watching
            # (cmd/handler-utils.go:362-364 zero-overhead contract).
            if self.trace_bus.has_subscribers:
                import time as _time

                rec = {
                    "type": "http",
                    "time": _time.time(), "api": api,
                    "method": request.method, "path": path,
                    "status": status, "requestId": request_id,
                    "remote": self._client_ip(request),
                    "durationNs": int(dt * 1e9),
                    "rx": rx, "tx": tx,
                }
                if canceled:
                    rec["canceled"] = True
                if ttfb is not None:
                    rec["ttfbNs"] = int(ttfb * 1e9)
                # obs.publish enriches with trace_id + node (the bus is
                # the same object; the gate above already passed).
                obs.publish(rec)
            # Per-request AUDIT record (reference logger.AuditLog at every
            # handler, cmd/object-handlers.go:1378) — zero cost unless an
            # audit target is configured.
            if self.logger.audit_targets:
                import time as _time

                from minio_tpu.logger import audit_entry

                parts = path.lstrip("/").split("/", 1)
                ident = request.get("identity")
                self.logger.audit(audit_entry(
                    api=api,
                    bucket=parts[0] if parts and not parts[0].startswith("minio") else "",
                    object=parts[1] if len(parts) > 1 else "",
                    status_code=status,
                    access_key=getattr(ident, "access_key", "") or "",
                    remote_host=self._client_ip(request),
                    user_agent=request.headers.get("User-Agent", ""),
                    request_id=request_id,
                    rx_bytes=rx, tx_bytes=tx,
                    duration_ms=(_time.perf_counter() - t0) * 1000,
                    query=dict(urllib.parse.parse_qsl(request.query_string)),
                ))

    async def _federation_redirect(self, request, path: str):
        """307 to the owning cluster when the missing bucket is federated
        elsewhere (the server-side analogue of the reference's DNS
        bucket records; clients re-sign and follow)."""
        if self.federation is None:
            return None
        bucket = path.lstrip("/").split("/", 1)[0]
        if not bucket or bucket.startswith("minio"):
            return None
        # Directory lookup is shared-file I/O (possibly NFS): keep it off
        # the event loop like every other blocking call.
        loop = asyncio.get_running_loop()
        owner = await loop.run_in_executor(
            None, self.federation.lookup, bucket)
        if owner is None or owner == self.federation.endpoint:
            return None
        # raw_path keeps the client's percent-encoding — the decoded path
        # would corrupt keys containing '#', '%', '?' or non-ASCII.
        raw = request.raw_path.split("?", 1)[0]
        loc = owner + raw
        if request.query_string:
            loc += "?" + request.query_string
        return web.Response(status=307, headers={"Location": loc})

    def _client_ip(self, request) -> str:
        """Requester IP for audit/trace records. Proxy headers
        (X-Forwarded-For leftmost hop, X-Real-IP) are honored only when
        api.trust_proxy_headers is on — they are client-spoofable
        otherwise (pkg/handlers GetSourceIP role)."""
        if (self.config.get("api", "trust_proxy_headers") or "") in (
                "on", "1", "true"):
            fwd = request.headers.get("X-Forwarded-For", "")
            if fwd:
                return fwd.split(",")[0].strip()
            real = request.headers.get("X-Real-IP", "")
            if real:
                return real.strip()
        return request.remote or ""

    def _error_response(self, e: S3Error, resource: str, request_id: str):
        body = xmlutil.error_xml(e.api.code, e.message, resource, request_id, e.extra)
        return web.Response(
            status=e.api.http_status, body=body, content_type=XML_TYPE,
            headers={"x-amz-request-id": request_id},
        )

    async def _dispatch(self, request: web.Request, path: str,
                        request_id: str) -> web.StreamResponse:
        # ---------- health probes: unauthenticated (healthcheck-router) ----
        if path.startswith("/minio/health/"):
            request["api"] = "healthcheck"
            kind = path.rsplit("/", 1)[-1]
            if kind == "live":
                # Liveness = the process answers; never touches drives
                # (reference LivenessCheckHandler).
                return web.Response(status=200)
            if kind in ("ready", "cluster"):
                # Readiness/cluster = write-quorum aware: every set must
                # keep at least write-quorum drives online. With
                # ?maintenance=true the bar rises by one drive per set —
                # "can I take one more node down without losing quorum"
                # (reference ClusterCheckHandler + maintenance mode).
                maintenance = request.query.get(
                    "maintenance", "").lower() in ("true", "1", "yes")
                health_fn = getattr(self.obj, "health",
                                    lambda: {"healthy": True})
                loop = asyncio.get_running_loop()
                h = await loop.run_in_executor(None, health_fn)
                # Sets layer reports {"sets": [...]}, the pools layer
                # nests per-pool {"pools": [{"sets": [...]}]} — flatten.
                sets = h.get("sets") or [
                    s for p in h.get("pools", [])
                    for s in p.get("sets", [])]
                healthy = bool(h.get("healthy"))
                if maintenance and sets:
                    healthy = all(
                        s.get("online", 0) >= s.get("write_quorum", 0) + 1
                        for s in sets)
                headers = {}
                # Peer fabric: breaker-derived liveness. OPEN breakers
                # already fail drive probes instantly (so the quorum
                # math above is partition-fast); additionally, a node
                # that cannot reach a majority of the cluster is on the
                # minority side of a partition — report 503 so the load
                # balancer drains it even while its local drives alone
                # still clear write quorum.
                node = self.cluster_node
                if node is not None and node.peer_nodes:
                    fabric = node.peer_fabric_info()
                    open_peers = [p["peer"] for p in fabric
                                  if p["state"] == "open"]
                    total = len(fabric) + 1          # peers + self
                    reachable = total - len(open_peers)
                    # Drain only a STRICT minority side. On an exact even
                    # split (2-node cluster losing a node, 2-2 in a
                    # 4-node cluster) there is no minority — draining
                    # both halves would turn a partial failure into a
                    # full outage, so ties stay up and the drive
                    # write-quorum check above remains the arbiter.
                    if reachable * 2 < total:
                        healthy = False
                    headers["X-Minio-Peers-Online"] = str(reachable - 1)
                    headers["X-Minio-Peers-Offline"] = str(len(open_peers))
                if sets:
                    headers["X-Minio-Write-Quorum"] = str(
                        max(s.get("write_quorum", 0) for s in sets))
                    # Status must agree with the response code the caller
                    # gets — maintenance bar included.
                    headers["X-Minio-Server-Status"] = (
                        "online" if healthy else "degraded")
                return web.Response(status=200 if healthy else 503,
                                    headers=headers)
            raise S3Error("MethodNotAllowed", resource=path)

        query_items = [(k, v) for k, v in urllib.parse.parse_qsl(
            request.query_string, keep_blank_values=True)]
        q = dict(query_items)
        # --- auth (reference cmd/auth-handler.go:102 classification) ---
        # The classification also feeds the s3:authtype /
        # s3:signatureversion condition keys (request["auth-type"]).
        if "X-Amz-Signature" in q:
            creds = sigv4.verify_presigned(
                request.method, path, query_items, request.headers,
                self._lookup)
            # Honor a content binding if the signer pinned one in the
            # signed query (else anyone with the URL uploads arbitrary bytes).
            payload_hash = q.get("X-Amz-Content-Sha256", sigv4.UNSIGNED_PAYLOAD)
            auth_sig = None
            identity = self.iam.identify(creds.access_key)
            request["auth-type"] = ("REST-QUERY-STRING", "AWS4-HMAC-SHA256")
        elif request.headers.get("Authorization", "").startswith(sigv4.ALGORITHM):
            _, payload_hash = sigv4.verify_header_auth(
                request.method, path, query_items, request.headers, self._lookup)
            auth_sig = sigv4.parse_auth_header(request.headers["Authorization"])
            identity = self.iam.identify(auth_sig.access_key)
            request["auth-type"] = ("REST-HEADER", "AWS4-HMAC-SHA256")
        elif sigv2.is_v2_header(request.headers):
            # Legacy SigV2 clients (cmd/signature-v2.go).
            creds = sigv2.verify_header_auth(
                request.method, path, query_items, request.headers,
                self._lookup)
            auth_sig = None
            payload_hash = sigv4.UNSIGNED_PAYLOAD
            identity = self.iam.identify(creds.access_key)
            request["auth-type"] = ("REST-HEADER", "AWS")
        elif sigv2.is_v2_presigned(q):
            creds = sigv2.verify_presigned(
                request.method, path, query_items, request.headers,
                self._lookup)
            auth_sig = None
            payload_hash = sigv4.UNSIGNED_PAYLOAD
            identity = self.iam.identify(creds.access_key)
            request["auth-type"] = ("REST-QUERY-STRING", "AWS")
        else:
            # Anonymous: allowed only where the bucket policy grants it.
            identity, payload_hash, auth_sig = (
                ANONYMOUS, sigv4.UNSIGNED_PAYLOAD, None)

        request["identity"] = identity
        # Tenant identity (minio_tpu/qos): (access key, bucket), bound
        # ONCE here next to the trace contextvar — every batch-plane
        # submit, WAL record, shm ring slot and shed counter downstream
        # attributes to it (the contextvar crosses executor hops via
        # obs.ctx_wrap exactly like the trace id). The /minio/ admin
        # and metrics planes stay on the unattributed system lane —
        # the EXACT reserved segment only: a real bucket merely named
        # "minio-..." is a tenant like any other (quotas, metrics,
        # fairness), never the system lane.
        tpath = path.lstrip("/").split("/", 1)[0]
        if tpath != "minio":
            qos.bind(getattr(identity, "access_key", "") or "anonymous",
                     tpath)
            tkey = qos.current_key()
            request["tenant"] = tkey
            flight.set_tenant(tkey)
        # Timeline: everything up to here (header parse + signature
        # verification + identity resolution) is the auth stage.
        flight.mark("auth")

        # Temp (STS) credentials must also present their session token
        # (cmd/auth-handler.go getSessionToken check).
        if identity.kind == "sts":
            token = (request.headers.get("x-amz-security-token", "")
                     or q.get("X-Amz-Security-Token", ""))
            if not self.iam.verify_session_token(identity.access_key, token):
                raise S3Error("InvalidToken")

        # ---------- admin + metrics planes (signed requests only) ----------
        if path.startswith("/minio/"):
            from minio_tpu.admin.handlers import ADMIN_PREFIX

            if path.startswith(ADMIN_PREFIX):
                request["api"] = "admin." + path[len(ADMIN_PREFIX):].split(
                    "/", 1)[0]
                return await self.admin.handle(
                    request, path[len(ADMIN_PREFIX):], identity)
            if path in ("/minio/browser", "/minio/browser/"):
                # Single-file object browser (role of the reference's React
                # console, browser/app/js) — static page; auth happens
                # in-page against /minio/webrpc.
                request["api"] = "browser"
                return web.Response(body=_browser_page(),
                                    content_type="text/html")
            if path == "/minio/webrpc":
                request["api"] = "webrpc"
                return await self.web.rpc(request)
            if path.startswith("/minio/upload/"):
                request["api"] = "webupload"
                b, _, k = path[len("/minio/upload/"):].partition("/")
                return await self.web.upload(request, b, k)
            if path.startswith("/minio/download/"):
                request["api"] = "webdownload"
                b, _, k = path[len("/minio/download/"):].partition("/")
                return await self.web.download(request, b, k)
            if path == "/minio/v2/metrics/cluster":
                request["api"] = "metrics"
                self.admin.authorize_http(request, identity,
                                          "admin:Prometheus")
                loop = asyncio.get_running_loop()
                # OpenMetrics (exemplars) only applies single-node: the
                # multi-node merge relabels samples and cannot carry
                # exemplar suffixes (docs/SLO.md).
                om = (wants_openmetrics(request.headers.get("Accept"))
                      and not self._has_peers())
                # Federated: peer node scrapes merge in under a `server`
                # label, deadline-bounded (a hung peer becomes a scrape
                # error, never a hung scrape).
                body = await loop.run_in_executor(
                    None, self._cluster_scrape, om)
                body, enc = maybe_gzip(
                    body, request.headers.get("Accept-Encoding"))
                headers = {"Content-Type": OPENMETRICS_CONTENT_TYPE
                           if om else PROM_CONTENT_TYPE}
                if enc:
                    headers["Content-Encoding"] = enc
                return web.Response(body=body, headers=headers)
            if path == "/minio/v2/metrics/node":
                # Node-scope scrape: this process's planes only (the
                # reference's cluster/node metrics-v2 split).
                request["api"] = "metrics"
                self.admin.authorize_http(request, identity,
                                          "admin:Prometheus")
                loop = asyncio.get_running_loop()
                om = wants_openmetrics(request.headers.get("Accept"))
                body = await loop.run_in_executor(
                    None, lambda: collect_node_metrics(
                        self.stats, openmetrics=om))
                body, enc = maybe_gzip(
                    body, request.headers.get("Accept-Encoding"))
                headers = {"Content-Type": OPENMETRICS_CONTENT_TYPE
                           if om else PROM_CONTENT_TYPE}
                if enc:
                    headers["Content-Encoding"] = enc
                return web.Response(body=body, headers=headers)
            raise S3Error("MethodNotAllowed", resource=path)

        parts = path.lstrip("/").split("/", 1)
        bucket = parts[0]
        key = parts[1] if len(parts) > 1 else ""

        loop = asyncio.get_running_loop()

        def run(fn, *args, **kw):
            # Copy the request's context (trace id, node) into the
            # executor thread — run_in_executor does not propagate
            # contextvars, and the storage layer's trace records are
            # emitted from there.
            return loop.run_in_executor(
                None, obs.ctx_wrap(lambda: fn(*args, **kw)))

        m = request.method
        hdr = {"x-amz-request-id": request_id}

        # ---------- service level ----------
        if not bucket:
            if m == "POST":  # STS API rides the root path (sts-handlers.go)
                return await self._sts_handler(request, identity, hdr)
            if m == "GET":
                if identity.kind == "anonymous":
                    raise S3Error("AccessDenied", resource=path)
                buckets = await run(self.obj.list_buckets)
                if not identity.is_owner:
                    cond_ctx = self._condition_context(request, identity, q)
                    allowed = []
                    for b in buckets:
                        ok_args = PolicyArgs(action="s3:ListBucket",
                                             bucket=b.name,
                                             conditions=cond_ctx)
                        if self.iam.is_allowed(identity, ok_args):
                            allowed.append(b)
                    buckets = allowed
                return web.Response(body=xmlutil.list_buckets_xml(buckets),
                                    content_type=XML_TYPE, headers=hdr)
            raise S3Error("MethodNotAllowed", resource=path)

        # Auth params travel in the query on presigned requests; they are not
        # S3 subresources and must not affect routing.
        sub = {k for k in q if not k.startswith("X-Amz-")}

        # --- authorization (identity policies ∪ bucket policy) ---
        post_form = (m == "POST" and not key
                     and request.content_type == "multipart/form-data")
        action = action_for(m, sub, bucket, key, request.headers)
        request["api"] = "PostPolicy" if post_form else action.split(":", 1)[-1]
        flight.set_api(request["api"])  # spans name their API from here on
        bulk_delete = m == "POST" and not key and "delete" in q
        # Built once per request, reused by in-handler re-checks
        # (RestoreObject, bulk delete) — the values don't change
        # mid-request.
        cond_ctx = self._condition_context(request, identity, q)
        request["cond-ctx"] = cond_ctx
        if not post_form and not bulk_delete:
            # Browser POST uploads authenticate via the signed policy
            # document inside the form; the handler checks access itself.
            # Bulk delete authorizes per object key (AWS DeleteObjects
            # semantics) — an endpoint-level check against the bare bucket
            # resource would wrongly reject object-scoped policies.
            self._check_access(identity, action, bucket, key, cond_ctx)

        # ---------- bucket config subresources ----------
        if not key:
            resp = await self._bucket_subresource(request, bucket, m, sub,
                                                  q, hdr, run)
            if resp is not None:
                return resp

        # ---------- bucket level ----------
        if not key:
            if m == "PUT" and not sub:
                if self.federation is not None:
                    from minio_tpu.dist.federation import FederationError
                    try:
                        # Claim BEFORE creating: global name uniqueness
                        # (the reference's DNS check on MakeBucket).
                        await run(self.federation.register, bucket)
                    except FederationError:
                        raise S3Error("BucketAlreadyExists",
                                      resource=f"/{bucket}") from None
                    try:
                        await run(self.obj.make_bucket, bucket)
                    except BaseException:
                        # Release the claim — a failed create must not
                        # poison the global name for every cluster.
                        try:
                            await run(self.federation.unregister, bucket)
                        except Exception:  # noqa: BLE001
                            pass
                        raise
                else:
                    await run(self.obj.make_bucket, bucket)
                changes = {"created": __import__("time").time()}
                if request.headers.get(
                        "x-amz-bucket-object-lock-enabled", "").lower() == "true":
                    # Object lock requires versioning (S3 semantics).
                    changes["versioning_status"] = "Enabled"
                    changes["object_lock_xml"] = (
                        b'<ObjectLockConfiguration xmlns="http://s3.amazonaws'
                        b'.com/doc/2006-03-01/"><ObjectLockEnabled>Enabled'
                        b'</ObjectLockEnabled></ObjectLockConfiguration>')
                await run(self.bucket_meta.update, bucket, **changes)
                return web.Response(status=200, headers={**hdr, "Location": f"/{bucket}"})
            if m == "HEAD":
                await run(self.obj.get_bucket_info, bucket)
                return web.Response(status=200, headers=hdr)
            if m == "DELETE" and not sub:
                await run(self.obj.delete_bucket, bucket)
                await run(self.bucket_meta.drop_bucket, bucket)
                if self.federation is not None:
                    await run(self.federation.unregister, bucket)
                return web.Response(status=204, headers=hdr)
            if m == "POST" and "delete" in q:
                return await self._delete_objects(request, bucket, hdr, run)
            if m == "POST" and request.content_type == "multipart/form-data":
                return await self._post_policy_upload(request, bucket, hdr,
                                                      run)
            if m == "GET":
                if "versions" in q:
                    res = await run(
                        self.obj.list_object_versions, bucket,
                        q.get("prefix", ""), q.get("key-marker", ""),
                        q.get("version-id-marker", ""), q.get("delimiter", ""),
                        _int_q(q, "max-keys", 1000),
                    )
                    return web.Response(
                        body=xmlutil.list_versions_xml(bucket, q.get("prefix", ""), res),
                        content_type=XML_TYPE, headers=hdr)
                if "uploads" in q:
                    uploads = await run(
                        self.obj.list_multipart_uploads, bucket,
                        q.get("prefix", ""), _int_q(q, "max-uploads", 1000),
                    )
                    return web.Response(
                        body=xmlutil.list_uploads_xml(bucket, uploads),
                        content_type=XML_TYPE, headers=hdr)
                if "location" in q:
                    await run(self.obj.get_bucket_info, bucket)
                    body = (b'<?xml version="1.0" encoding="UTF-8"?>'
                            b'<LocationConstraint xmlns="http://s3.amazonaws.com/'
                            b'doc/2006-03-01/"></LocationConstraint>')
                    return web.Response(body=body, content_type=XML_TYPE, headers=hdr)
                if q.get("list-type") == "2":
                    token = q.get("continuation-token", "")
                    start_after = q.get("start-after", "")
                    marker = token or start_after
                    res = await run(
                        self.obj.list_objects, bucket, q.get("prefix", ""),
                        marker, q.get("delimiter", ""),
                        _int_q(q, "max-keys", 1000),
                    )
                    return web.Response(
                        body=xmlutil.list_objects_v2_xml(
                            bucket, q.get("prefix", ""), token, start_after,
                            q.get("delimiter", ""), _int_q(q, "max-keys", 1000), res),
                        content_type=XML_TYPE, headers=hdr)
                res = await run(
                    self.obj.list_objects, bucket, q.get("prefix", ""),
                    q.get("marker", ""), q.get("delimiter", ""),
                    _int_q(q, "max-keys", 1000),
                )
                return web.Response(
                    body=xmlutil.list_objects_v1_xml(
                        bucket, q.get("prefix", ""), q.get("marker", ""),
                        q.get("delimiter", ""), _int_q(q, "max-keys", 1000), res),
                    content_type=XML_TYPE, headers=hdr)
            raise S3Error("MethodNotAllowed", resource=path)

        # ---------- object level ----------
        # S3's literal versionId "null" names the null (unversioned)
        # version; the journal resolves it to the empty stored id
        # (storage/xlmeta.py NULL_VERSION_REQ) — passed through verbatim
        # so it can never be mistaken for "latest" on versioned buckets.
        opts = ObjectOptions(
            version_id=q.get("versionId", ""),
            versioned=self._bucket_versioned(bucket),
        )
        if m in ("GET", "HEAD") and "tagging" in q:
            tags = await run(self.obj.get_object_tags, bucket, key, opts)
            return web.Response(body=xmlutil.tagging_xml(tags),
                                content_type=XML_TYPE, headers=hdr)
        if m == "PUT" and "tagging" in q:
            body = await request.read()
            tags = xmlutil.parse_tagging_xml(body)
            await run(self.obj.put_object_tags, bucket, key, tags, opts)
            return web.Response(status=200, headers=hdr)
        if m == "DELETE" and "tagging" in q:
            await run(self.obj.delete_object_tags, bucket, key, opts)
            return web.Response(status=204, headers=hdr)

        # ----- object ACL: canned FULL_CONTROL answer, private-only PUT
        #       (reference cmd/acl-handlers.go GetObjectACLHandler) -----
        if "acl" in q:
            if m in ("GET", "HEAD"):
                await run(self.obj.get_object_info, bucket, key, opts)
                return web.Response(body=xmlutil.acl_xml(),
                                    content_type=XML_TYPE, headers=hdr)
            if m == "PUT":
                self._require_private_acl(request, await request.read())
                await run(self.obj.get_object_info, bucket, key, opts)
                return web.Response(status=200, headers=hdr)
            # Terminal: DELETE ?acl must never fall through to the
            # object-DELETE branch below (S3 has no DeleteObjectAcl).
            raise S3Error("MethodNotAllowed", resource=path)

        # ----- object lock: retention / legal hold (pkg/bucket/object/lock,
        #       cmd/object-handlers.go PutObjectRetentionHandler etc.) -----
        if "retention" in q:
            if m == "PUT":
                try:
                    mode, until = olock.parse_retention_xml(await request.read())
                except ValueError:
                    raise S3Error("MalformedXML") from None
                info = await run(self.obj.get_object_info, bucket, key, opts)
                try:
                    olock.check_worm(
                        info.user_defined,
                        bypass_governance=request.headers.get(
                            "x-amz-bypass-governance-retention", ""
                        ).lower() == "true")
                except olock.WORMProtected as e:
                    raise S3Error("AccessDenied", str(e)) from None
                await run(self.obj.put_object_metadata, bucket, key,
                          {olock.KEY_MODE: mode,
                           olock.KEY_UNTIL: olock.to_iso(until)}, opts)
                return web.Response(status=200, headers=hdr)
            if m in ("GET", "HEAD"):
                info = await run(self.obj.get_object_info, bucket, key, opts)
                mode = info.user_defined.get(olock.KEY_MODE, "")
                until = info.user_defined.get(olock.KEY_UNTIL, "")
                if not mode:
                    raise S3Error("ObjectLockConfigurationNotFoundError",
                                  resource=f"/{bucket}/{key}")
                return web.Response(
                    body=olock.retention_xml(mode, olock.parse_iso(until)),
                    content_type=XML_TYPE, headers=hdr)
        if "legal-hold" in q:
            if m == "PUT":
                try:
                    status = olock.parse_legal_hold_xml(await request.read())
                except ValueError:
                    raise S3Error("MalformedXML") from None
                await run(self.obj.put_object_metadata, bucket, key,
                          {olock.KEY_HOLD: status}, opts)
                return web.Response(status=200, headers=hdr)
            if m in ("GET", "HEAD"):
                info = await run(self.obj.get_object_info, bucket, key, opts)
                status = info.user_defined.get(olock.KEY_HOLD, "")
                if not status:
                    raise S3Error("ObjectLockConfigurationNotFoundError",
                                  resource=f"/{bucket}/{key}")
                return web.Response(body=olock.legal_hold_xml(status),
                                    content_type=XML_TYPE, headers=hdr)

        # ----- S3 Select (reference SelectObjectContentHandler,
        #       cmd/object-handlers.go:95; engine pkg/s3select) -----
        if m == "POST" and "restore" in q:
            # RestoreObject: re-materialize a tiered version's data
            # (reference PostRestoreObjectHandler; our tiers read through,
            # so restore = pull the data back into the cluster).
            request["api"] = "RestoreObject"
            self._check_access(identity, "s3:RestoreObject", bucket, key,
                               request["cond-ctx"])
            if not hasattr(self.obj, "restore_transitioned"):
                raise S3Error("NotImplemented", resource=path)
            try:
                await run(self.obj.restore_transitioned, bucket, key,
                          opts.version_id)
            except se.ObjectError as e:
                raise from_exception(e, path) from None
            return web.Response(status=202, headers=hdr)

        if m == "POST" and "select" in q:
            from minio_tpu.s3select import S3SelectRequest, run_select
            from minio_tpu.s3select.sql import SelectError

            body = await request.read()
            try:
                sel = S3SelectRequest.parse_xml(body)
            except SelectError as e:
                raise S3Error("InvalidArgument", str(e)) from None
            info, stream, _size = await self._open_object_stream(
                request, bucket, key, opts, 0, -1, run)
            reader = _IterReader(stream)
            resp = web.StreamResponse(status=200, headers={
                **hdr, "Content-Type": "application/octet-stream"})
            await resp.prepare(request)

            def frames():
                try:
                    yield from run_select(reader, sel)
                except SelectError:
                    raise
            it = iter(frames())
            try:
                while True:
                    frame = await run(next, it, None)
                    if frame is None:
                        break
                    await resp.write(frame)
            except SelectError as e:
                # Past the prepared response: close the stream; errors
                # before any frame surface normally via the except path.
                await resp.write_eof()
                return resp
            await resp.write_eof()
            return resp

        # ----- multipart (reference cmd/erasure-multipart.go via
        #       object-handlers) -----
        if m == "POST" and "uploads" in q:
            user_defined = _metadata_headers(request)
            self._maybe_sse_multipart_create(request, bucket, key,
                                             user_defined)
            mp_opts = ObjectOptions(user_defined=user_defined)
            upload_id = await run(self.obj.new_multipart_upload, bucket, key, mp_opts)
            self._mp_cache_put(upload_id, dict(user_defined))
            return web.Response(
                body=xmlutil.initiate_multipart_xml(bucket, key, upload_id),
                content_type=XML_TYPE, headers=hdr)
        if "uploadId" in q:
            upload_id = q["uploadId"]
            if m == "PUT":
                part_number = _int_q(q, "partNumber", 0, lo=1, hi=10000)
                src = request.headers.get("x-amz-copy-source")
                if src:
                    return await self._upload_part_copy(
                        request, bucket, key, upload_id, part_number, src, hdr, run)
                return await self._put_part(request, bucket, key, upload_id,
                                            part_number, hdr, payload_hash,
                                            auth_sig, run)
            if m == "GET":
                parts = await run(self.obj.list_parts, bucket, key, upload_id,
                                  _int_q(q, "part-number-marker", 0),
                                  _int_q(q, "max-parts", 1000))
                mp_meta = await run(self._mp_user_defined, bucket, key,
                                    upload_id)
                if sse.META_ALGO in mp_meta:
                    # Report plaintext sizes (the reference reports the
                    # decrypted part size in ListObjectParts) so a client
                    # resuming by summing sizes lands on the right offset.
                    import dataclasses
                    parts = [dataclasses.replace(
                        p, size=sse.part_plain_size(p.size),
                        actual_size=sse.part_plain_size(p.size))
                        for p in parts]
                return web.Response(
                    body=xmlutil.list_parts_xml(bucket, key, upload_id, parts),
                    content_type=XML_TYPE, headers=hdr)
            if m == "DELETE":
                await run(self.obj.abort_multipart_upload, bucket, key, upload_id)
                self._mp_sse_cache.pop(upload_id, None)
                return web.Response(status=204, headers=hdr)
            if m == "POST":
                body = await request.read()
                pairs = xmlutil.parse_complete_multipart_xml(body)
                if not pairs:
                    raise S3Error("MalformedXML")
                parts = [CompletePart(n, e) for n, e in pairs]
                mp_meta = await run(self._mp_user_defined, bucket, key,
                                    upload_id)
                if sse.META_ALGO in mp_meta:
                    # The layer's 5 MiB minimum checks stored sizes; SSE
                    # framing inflates them, so enforce the S3 minimum on
                    # *plaintext* sizes here (AWS validates decrypted).
                    listed = {p.part_number: p for p in await run(
                        self.obj.list_parts, bucket, key, upload_id,
                        0, 10000)}
                    for n, _ in pairs[:-1]:
                        p = listed.get(n)
                        if p is not None and sse.part_plain_size(
                                p.size) < (5 << 20):
                            raise S3Error("EntityTooSmall")
                info = await run(self.obj.complete_multipart_upload, bucket,
                                 key, upload_id, parts, opts)
                self._mp_sse_cache.pop(upload_id, None)
                extra = {}
                if info.version_id:
                    extra["x-amz-version-id"] = info.version_id
                self.update_tracker.mark(bucket)
                self._emit(request, evt.OBJECT_CREATED_COMPLETE_MULTIPART,
                           bucket, key, size=info.size, etag=info.etag,
                           version_id=info.version_id)
                return web.Response(
                    body=xmlutil.complete_multipart_xml(
                        f"/{bucket}/{key}", bucket, key, info.etag),
                    content_type=XML_TYPE, headers={**hdr, **extra})

        if m == "HEAD":
            info = await run(self.obj.get_object_info, bucket, key, opts)
            if sse.META_ALGO in info.user_defined:
                self._sse_unseal(request, bucket, key, info.user_defined)
            if _check_conditional(request, info):
                return web.Response(status=304,
                                    headers={**hdr, "ETag": f'"{info.etag}"'})
            return web.Response(status=200, headers={**hdr, **_object_headers(info)})
        if m == "GET":
            return await self._get_object(request, bucket, key, opts, hdr, run)
        if m == "PUT":
            src = request.headers.get("x-amz-copy-source")
            if src:
                return await self._copy_object(request, bucket, key, src, opts, hdr, run)
            return await self._put_object(request, bucket, key, opts, hdr,
                                          payload_hash, auth_sig, run)
        if m == "DELETE":
            if opts.version_id:
                # Destroying a specific version: WORM check first
                # (cmd/bucket-object-lock.go enforceRetentionForDeletion).
                try:
                    pre = await run(self.obj.get_object_info, bucket, key, opts)
                    olock.check_worm(
                        pre.user_defined,
                        bypass_governance=request.headers.get(
                            "x-amz-bypass-governance-retention", ""
                        ).lower() == "true")
                except olock.WORMProtected as e:
                    raise S3Error("AccessDenied", str(e)) from None
                except S3Error:
                    raise
                except Exception:  # noqa: BLE001 - missing version: fall through
                    pass
            info = await run(self.obj.delete_object, bucket, key, opts)
            extra = {}
            if info.delete_marker:
                extra["x-amz-delete-marker"] = "true"
            if info.version_id:
                extra["x-amz-version-id"] = info.version_id
            self.update_tracker.mark(bucket)
            self._emit(request,
                       evt.OBJECT_REMOVED_DELETE_MARKER if info.delete_marker
                       else evt.OBJECT_REMOVED_DELETE,
                       bucket, key, version_id=info.version_id)
            from minio_tpu.replication.pool import OP_DELETE, ReplicationTask
            self.replication.queue_task(ReplicationTask(
                bucket, key, op=OP_DELETE))
            return web.Response(status=204, headers={**hdr, **extra})
        raise S3Error("MethodNotAllowed", resource=path)

    async def _post_policy_upload(self, request, bucket, hdr, run):
        """Browser form upload (reference PostPolicyBucketHandler,
        cmd/bucket-handlers.go + cmd/postpolicyform.go): the policy
        document IS the auth — signature over its base64, conditions
        enforced against the submitted fields."""
        reader = await request.multipart()
        form: dict[str, str] = {}
        file_bytes = b""
        filename = ""
        async for part in reader:
            name = (part.name or "").lower()
            if name == "file":
                filename = part.filename or ""
                file_bytes = await part.read(decode=False)
                break  # fields after the file are ignored (S3 semantics)
            form[name] = (await part.read(decode=False)).decode(
                "utf-8", "replace")

        creds = sigv4.verify_post_policy(form, self._lookup)
        request["auth-type"] = ("POST", "AWS4-HMAC-SHA256")
        # The "bucket" condition matches the request target, not a form
        # field (cmd/postpolicyform.go injects it the same way).
        form.setdefault("bucket", bucket)
        sigv4.check_post_policy_conditions(
            form.get("policy", ""), form, len(file_bytes))

        key = form.get("key", "")
        if not key:
            raise S3Error("InvalidArgument", "POST form requires key")
        key = key.replace("${filename}", filename)

        identity = self.iam.identify(creds.access_key)
        request["identity"] = identity
        self._check_access(identity, "s3:PutObject", bucket, key,
                           self._condition_context(request, identity))

        opts = ObjectOptions(versioned=self._bucket_versioned(bucket))
        if "content-type" in form:
            opts.user_defined["content-type"] = form["content-type"]
        for k, v in form.items():
            if k.startswith("x-amz-meta-") and not _is_reserved_meta(k):
                opts.user_defined[k] = v
        import io as _io

        info = await run(self.obj.put_object, bucket, key,
                         _io.BytesIO(file_bytes), len(file_bytes), opts)
        self.update_tracker.mark(bucket)
        self._emit(request, evt.OBJECT_CREATED_POST, bucket, key,
                   size=info.size, etag=info.etag,
                   version_id=info.version_id)
        status = int(form.get("success_action_status", "204"))
        if status not in (200, 201, 204):
            status = 204
        if status == 201:
            body = (f'<?xml version="1.0" encoding="UTF-8"?>'
                    f'<PostResponse><Location>/{bucket}/{key}</Location>'
                    f'<Bucket>{bucket}</Bucket><Key>{key}</Key>'
                    f'<ETag>"{info.etag}"</ETag></PostResponse>').encode()
            return web.Response(status=201, body=body,
                                content_type=XML_TYPE, headers=hdr)
        return web.Response(status=status,
                            headers={**hdr, "ETag": f'"{info.etag}"'})

    # ------------------------------------------------------------------
    # bucket config subresources (policy/versioning/lifecycle/... —
    # reference per-feature files cmd/bucket-policy-handlers.go etc.)
    # ------------------------------------------------------------------

    async def _bucket_subresource(self, request, bucket, m, sub, q, hdr, run):
        """Handle ?policy/?versioning/?lifecycle/?tagging/?encryption/
        ?object-lock/?notification/?replication. Returns None if the
        request isn't a config subresource."""
        # Stored-verbatim XML configs: (query key, metadata field,
        # GET-miss error code).
        verbatim = {
            "lifecycle": ("lifecycle_xml", "NoSuchLifecycleConfiguration"),
            "tagging": ("tagging_xml", "NoSuchTagSet"),
            "encryption": ("sse_xml",
                           "ServerSideEncryptionConfigurationNotFoundError"),
            "replication": ("replication_xml",
                            "ReplicationConfigurationNotFoundError"),
        }
        config_subs = ({"policy", "versioning", "object-lock", "notification",
                        "acl", "website", "accelerate", "requestPayment",
                        "logging"}
                       | set(verbatim))
        if not (sub & config_subs):
            return None

        await run(self.obj.get_bucket_info, bucket)  # 404 before config

        # ----- ACL: canned answers only (reference cmd/acl-handlers.go:
        # 120-287 — access control is policy-based; ACL probes from SDK
        # tooling like gsutil `ls -L` / boto get_acl get the FULL_CONTROL
        # owner document, and only the private canned ACL is writable) --
        if "acl" in sub:
            if m in ("GET", "HEAD"):
                return web.Response(body=xmlutil.acl_xml(),
                                    content_type=XML_TYPE, headers=hdr)
            if m == "PUT":
                self._require_private_acl(request, await request.read())
                return web.Response(status=200, headers=hdr)
            raise S3Error("MethodNotAllowed", resource=f"/{bucket}")

        # ----- dummy subresources (reference cmd/dummy-handlers.go):
        # harmless defaults so SDK probes succeed instead of erroring ----
        if "website" in sub:
            if m in ("GET", "HEAD"):
                raise S3Error("NoSuchWebsiteConfiguration",
                              resource=f"/{bucket}")
            if m == "DELETE":
                return web.Response(status=204, headers=hdr)
            raise S3Error("NotImplemented", resource=f"/{bucket}")
        if "accelerate" in sub:
            if m in ("GET", "HEAD"):
                body = (b'<?xml version="1.0" encoding="UTF-8"?>'
                        b'<AccelerateConfiguration xmlns="http://s3.amazon'
                        b'aws.com/doc/2006-03-01/"></AccelerateConfiguration>')
                return web.Response(body=body, content_type=XML_TYPE,
                                    headers=hdr)
            raise S3Error("NotImplemented", resource=f"/{bucket}")
        if "requestPayment" in sub:
            if m in ("GET", "HEAD"):
                body = (b'<?xml version="1.0" encoding="UTF-8"?>'
                        b'<RequestPaymentConfiguration xmlns="http://s3.'
                        b'amazonaws.com/doc/2006-03-01/"><Payer>BucketOwner'
                        b'</Payer></RequestPaymentConfiguration>')
                return web.Response(body=body, content_type=XML_TYPE,
                                    headers=hdr)
            raise S3Error("NotImplemented", resource=f"/{bucket}")
        if "logging" in sub:
            if m in ("GET", "HEAD"):
                body = (b'<?xml version="1.0" encoding="UTF-8"?>'
                        b'<BucketLoggingStatus xmlns="http://s3.amazonaws'
                        b'.com/doc/2006-03-01/"></BucketLoggingStatus>')
                return web.Response(body=body, content_type=XML_TYPE,
                                    headers=hdr)
            raise S3Error("NotImplemented", resource=f"/{bucket}")

        if "policy" in sub:
            if m == "PUT":
                body = await request.read()
                pol = Policy.parse(body)
                pol.validate()
                if any(s.principals is None for s in pol.statements):
                    raise S3Error("MalformedPolicy",
                                  "bucket policy requires Principal")
                await run(self.bucket_meta.update, bucket, policy_json=body)
                return web.Response(status=204, headers=hdr)
            if m == "GET":
                raw = self.bucket_meta.get(bucket).policy_json
                if not raw:
                    raise S3Error("NoSuchBucketPolicy", resource=f"/{bucket}")
                return web.Response(body=raw, content_type="application/json",
                                    headers=hdr)
            if m == "DELETE":
                await run(self.bucket_meta.update, bucket, policy_json=b"")
                return web.Response(status=204, headers=hdr)

        if "versioning" in sub:
            if m == "PUT":
                body = await request.read()
                try:
                    status = xmlutil.parse_versioning_xml(body)
                except ValueError:
                    raise S3Error("MalformedXML") from None
                meta = self.bucket_meta.get(bucket)
                if meta.object_lock_xml and status == "Suspended":
                    raise S3Error("InvalidBucketState",
                                  "object lock requires versioning")
                await run(self.bucket_meta.update, bucket,
                          versioning_status=status)
                return web.Response(status=200, headers=hdr)
            if m == "GET":
                status = self.bucket_meta.get(bucket).versioning_status
                if self.versioned_buckets and not status:
                    status = "Enabled"
                return web.Response(body=xmlutil.versioning_xml(status),
                                    content_type=XML_TYPE, headers=hdr)

        if "object-lock" in sub:
            if m == "PUT":
                body = await request.read()
                meta = self.bucket_meta.get(bucket)
                if not meta.versioning_enabled:
                    raise S3Error("InvalidBucketState",
                                  "object lock requires versioning")
                await run(self.bucket_meta.update, bucket,
                          object_lock_xml=body)
                return web.Response(status=200, headers=hdr)
            if m == "GET":
                raw = self.bucket_meta.get(bucket).object_lock_xml
                if not raw:
                    raise S3Error("ObjectLockConfigurationNotFoundError",
                                  resource=f"/{bucket}")
                return web.Response(body=raw, content_type=XML_TYPE,
                                    headers=hdr)

        if "notification" in sub:
            if m == "PUT":
                body = await request.read()
                try:
                    await run(self.notifier.set_bucket_rules, bucket, body)
                except ValueError as e:
                    raise S3Error("InvalidArgument", str(e)) from None
                self._rules_loaded.add(bucket)
                await run(self.bucket_meta.update, bucket,
                          notification_xml=body)
                return web.Response(status=200, headers=hdr)
            if m == "GET":
                raw = self.bucket_meta.get(bucket).notification_xml
                if not raw:
                    raw = (b'<?xml version="1.0" encoding="UTF-8"?>'
                           b'<NotificationConfiguration xmlns="http://s3.'
                           b'amazonaws.com/doc/2006-03-01/">'
                           b'</NotificationConfiguration>')
                return web.Response(body=raw, content_type=XML_TYPE,
                                    headers=hdr)

        for name, (attr, miss_code) in verbatim.items():
            if name not in sub:
                continue
            if m == "PUT":
                body = await request.read()
                _validate_xml(body)
                await run(self.bucket_meta.update, bucket, **{attr: body})
                return web.Response(status=200, headers=hdr)
            if m == "GET":
                raw = getattr(self.bucket_meta.get(bucket), attr)
                if not raw:
                    raise S3Error(miss_code, resource=f"/{bucket}")
                return web.Response(body=raw, content_type=XML_TYPE,
                                    headers=hdr)
            if m == "DELETE":
                await run(self.bucket_meta.update, bucket, **{attr: b""})
                return web.Response(status=204, headers=hdr)

        return None

    # ------------------------------------------------------------------
    # STS (reference cmd/sts-handlers.go — AssumeRole on the root path)
    # ------------------------------------------------------------------

    async def _sts_handler(self, request, identity, hdr):
        form = urllib.parse.parse_qs((await request.read()).decode())
        action = form.get("Action", [""])[0]
        duration = int(form.get("DurationSeconds", ["3600"])[0])
        session_policy = form.get("Policy", [""])[0]

        if action == "AssumeRole":
            if identity.kind == "anonymous":
                raise S3Error("AccessDenied", "STS requires signed credentials")
            if identity.kind in ("sts", "svc"):
                raise S3Error("AccessDenied",
                              "temporary credentials cannot assume roles")
            tc = self.iam.assume_role(identity.access_key, duration,
                                      session_policy)
            subject = ""
        elif action in ("AssumeRoleWithWebIdentity",
                        "AssumeRoleWithClientGrants"):
            # Federated: unauthenticated call carrying an IdP-signed JWT
            # (cmd/sts-handlers.go:49-102). The token IS the credential.
            from minio_tpu.iam.oidc import OIDCError, OpenIDValidator

            token = form.get(
                "WebIdentityToken" if action.endswith("WebIdentity")
                else "Token", [""])[0]
            if not token:
                raise S3Error("InvalidRequest", "missing identity token")
            try:
                validator = OpenIDValidator.from_config(self.config)
                if validator is None:
                    raise S3Error("STSNotImplemented",
                                  "identity_openid is not configured")
                claims = validator.validate(token)
                policies = validator.policies_from(claims)
            except OIDCError as e:
                raise S3Error("AccessDenied", str(e)) from None
            if not policies:
                raise S3Error(
                    "AccessDenied",
                    f"token carries no {validator.claim_name!r} claim")
            subject = str(claims.get("sub", ""))
            # Credentials never outlive the identity token itself
            # (cmd/sts-handlers.go caps at the JWT expiry).
            remaining = int(float(claims["exp"]) - time.time())
            if remaining <= 0:
                raise S3Error("AccessDenied", "identity token expired")
            duration = min(max(900, duration), remaining)
            # Scalar token claims travel namespaced ("jwt:sub", ...) so
            # session/identity policies can condition on them.
            jwt_claims = {f"jwt:{k}": s for k, v in claims.items()
                          if (s := _scalar_claim(v)) is not None}
            tc = self.iam.assume_role_with_claims(
                subject, policies, duration, session_policy,
                claims=jwt_claims)
        elif action == "AssumeRoleWithLDAPIdentity":
            from minio_tpu.iam.ldap import LDAPError, LDAPValidator

            username = form.get("LDAPUsername", [""])[0]
            password = form.get("LDAPPassword", [""])[0]
            if not username or not password:
                raise S3Error("InvalidRequest",
                              "LDAPUsername and LDAPPassword required")
            try:
                validator = LDAPValidator.from_config(self.config)
            except LDAPError as e:  # enabled-but-misconfigured: say so
                raise S3Error("InvalidRequest", str(e)) from None
            if validator is None:
                raise S3Error("STSNotImplemented",
                              "identity_ldap is not configured")
            policies = validator.policies
            if not policies:
                # Check BEFORE binding: an always-denied setup must not
                # hammer the directory with real authentications.
                raise S3Error("AccessDenied",
                              "no sts_policy configured for LDAP identities")
            try:
                # Blocking directory I/O stays off the event loop.
                loop = asyncio.get_running_loop()
                subject = await loop.run_in_executor(
                    None, validator.authenticate, username, password)
            except LDAPError as e:
                raise S3Error("AccessDenied", str(e)) from None
            tc = self.iam.assume_role_with_claims(
                subject, policies, max(900, duration), session_policy,
                claims={"ldap:username": username, "ldap:user": subject})
        else:
            raise S3Error("STSNotImplemented")

        import datetime
        exp = datetime.datetime.fromtimestamp(
            tc.expiry, datetime.timezone.utc).strftime("%Y-%m-%dT%H:%M:%SZ")
        body = xmlutil.sts_assume_role_xml(
            tc.access_key, tc.secret_key, tc.session_token, exp,
            hdr["x-amz-request-id"], action=action, subject=subject)
        return web.Response(body=body, content_type=XML_TYPE, headers=hdr)

    # ------------------------------------------------------------------
    # SSE (cmd/encryption-v1.go EncryptRequest/DecryptObjectInfo roles)
    # ------------------------------------------------------------------

    def _sse_master_key(self) -> bytes:
        """SSE-S3 master key: MTPU_KMS_SECRET_KEY env, else derived from
        the root secret (the reference requires a KMS; a derived local
        master keeps SSE-S3 usable out of the box)."""
        import hashlib as _hl

        secret = os.environ.get("MTPU_KMS_SECRET_KEY",
                                "mtpu-sse-s3:" + self.creds.secret_key)
        return _hl.sha256(secret.encode()).digest()

    def _maybe_compress_put(self, request, bucket: str, key: str, opts,
                            spool, size: int):
        """Wrap the upload in the streaming compressor when the
        compression config matches (isCompressible role). Returns
        (reader, size) — size becomes -1 (stream length unknown)."""
        if self.config.get("compression", "enable") != "on":
            return spool, size
        # SSE and compression don't stack (compressed-then-encrypted sizes
        # become doubly virtual; the reference also refuses).
        if (request.headers.get("x-amz-server-side-encryption")
                or request.headers.get(
                    "x-amz-server-side-encryption-customer-algorithm")):
            return spool, size
        exts = [e for e in self.config.get(
            "compression", "extensions").split(",") if e]
        mimes = [m for m in self.config.get(
            "compression", "mime_types").split(",") if m]
        ct = opts.user_defined.get("content-type", "")
        if not czip.is_compressible(key, ct, exts, mimes):
            return spool, size
        if size >= 0:
            opts.user_defined[czip.META_ACTUAL_SIZE] = str(size)
        scheme = czip.default_scheme()
        opts.user_defined[czip.META_COMPRESSION] = scheme
        return czip.CompressReader(spool, scheme), -1

    def _sse_setup(self, request, bucket: str, key: str,
                   user_defined: dict) -> bytes | None:
        """Decide SSE applicability (request headers or bucket default),
        then generate + seal a fresh per-object data key into metadata.
        Returns the plaintext object key, or None when SSE does not apply.
        Shared by single PUT and CreateMultipartUpload so their encryption
        decisions can never diverge."""
        import base64 as _b64
        import hashlib as _hl

        try:
            ssec_key = sse.parse_ssec_headers(request.headers)
        except sse.SSEError as e:
            raise S3Error("InvalidArgument", str(e)) from None
        sse_hdr = request.headers.get("x-amz-server-side-encryption", "")
        sse_s3 = sse_hdr == "AES256"
        sse_kms = sse_hdr == "aws:kms"
        kms_key_id = request.headers.get(
            "x-amz-server-side-encryption-aws-kms-key-id", "")
        if not sse_s3 and not sse_kms and ssec_key is None:
            # Bucket default SSE config (PUT ?encryption).
            default = self.bucket_meta.get(bucket).sse_xml
            if b"aws:kms" in default:
                sse_kms = True
            elif b"AES256" in default:
                sse_s3 = True
        if ssec_key is None and not sse_s3 and not sse_kms:
            return None
        aad = f"{bucket}/{key}"
        if sse_kms:
            # Envelope encryption: the KMS mints the per-object data key
            # and only the sealed blob is stored (cmd/encryption-v1.go:195
            # + cmd/crypto/kes.go GenerateKey role).
            from minio_tpu.crypto.kms import KMSError

            try:
                kid, object_key, sealed = self.kms.generate_data_key(
                    kms_key_id, context=aad)
            except KMSError as e:
                raise S3Error("InvalidRequest", f"KMS: {e}") from None
            user_defined[sse.META_ALGO] = "SSE-KMS"
            user_defined[sse.META_SEALED_KEY] = sealed
            user_defined[sse.META_KMS_KEY_ID] = kid
            return object_key
        object_key = os.urandom(32)
        if ssec_key is not None:
            user_defined[sse.META_ALGO] = "SSE-C"
            user_defined[sse.META_SEALED_KEY] = sse.seal_key(
                object_key, ssec_key, aad)
            user_defined[sse.META_KEY_MD5] = _b64.b64encode(
                _hl.md5(ssec_key).digest()).decode()
        else:
            user_defined[sse.META_ALGO] = "SSE-S3"
            user_defined[sse.META_SEALED_KEY] = sse.seal_key(
                object_key, self._sse_master_key(), aad)
        return object_key

    def _maybe_encrypt_put(self, request, bucket: str, key: str, opts,
                           spool, size: int):
        """Wrap the upload stream in a DARE encryptor when SSE applies.
        Returns (reader, stored_size)."""
        import base64 as _b64

        staged: dict = {}
        object_key = self._sse_setup(request, bucket, key, staged)
        if object_key is None:
            return spool, size
        if size < 0:
            raise S3Error("MissingContentLength",
                          "SSE requires a known content length")
        opts.user_defined.update(staged)
        nonce = os.urandom(12)
        opts.user_defined[sse.META_NONCE] = _b64.b64encode(nonce).decode()
        opts.user_defined[sse.META_ACTUAL_SIZE] = str(size)
        return (sse.EncryptReader(spool, object_key, nonce),
                sse.encrypted_size(size))

    def _maybe_sse_multipart_create(self, request, bucket: str, key: str,
                                    user_defined: dict) -> None:
        """Seal a per-upload object key at CreateMultipartUpload time when
        SSE applies; every part is then encrypted under it (reference
        newMultipartUpload encryption setup, cmd/erasure-multipart.go:269 +
        cmd/object-handlers.go NewMultipartUploadHandler). No META_NONCE is
        stored: parts are independent streams, each carrying its own
        random nonce as a 12-byte prefix."""
        self._sse_setup(request, bucket, key, user_defined)

    def _mp_cache_put(self, upload_id: str, meta: dict) -> None:
        if len(self._mp_sse_cache) > 2048:
            self._mp_sse_cache.clear()
        self._mp_sse_cache[upload_id] = meta

    def _mp_user_defined(self, bucket: str, key: str,
                         upload_id: str) -> dict:
        """The upload session's user metadata, cached per upload_id —
        immutable after CreateMultipartUpload, so UploadPart/ListParts
        skip the per-call quorum metadata read."""
        meta = self._mp_sse_cache.get(upload_id)
        if meta is None:
            meta = self.obj.get_multipart_info(
                bucket, key, upload_id).user_defined
            self._mp_cache_put(upload_id, meta)
        return meta

    def _maybe_encrypt_part(self, request, bucket: str, key: str,
                            upload_id: str, reader, size: int):
        """Wrap one part's stream in DARE encryption under the upload's
        sealed object key, with a fresh per-part nonce carried as a stream
        prefix. Returns (reader, stored_size)."""
        mp_meta = self._mp_user_defined(bucket, key, upload_id)
        if sse.META_ALGO not in mp_meta:
            return reader, size
        if size < 0:
            raise S3Error("MissingContentLength",
                          "SSE requires a known content length")
        object_key = self._sse_object_key(request, bucket, key, mp_meta)
        nonce = os.urandom(sse.NONCE_SIZE)
        part_key = sse.derive_part_key(object_key, nonce)
        return (_PrefixReader(nonce,
                              sse.EncryptReader(reader, part_key, nonce)),
                sse.encrypted_part_size(size))

    @staticmethod
    def _visible_size(info) -> int:
        """Client-visible (plaintext/uncompressed) byte count of an object
        — info.size is the stored size, which SSE and compression inflate
        or shrink."""
        if sse.META_ACTUAL_SIZE in info.user_defined:
            return int(info.user_defined[sse.META_ACTUAL_SIZE])
        if czip.META_ACTUAL_SIZE in info.user_defined:
            return int(info.user_defined[czip.META_ACTUAL_SIZE])
        if sse.META_ALGO in info.user_defined and info.parts:
            # Multipart SSE: derivable from the fixed DARE framing of each
            # independently-encrypted part.
            return sum(sse.part_plain_size(s) for _, s in info.parts)
        return info.size

    def _mp_sse_stream(self, request, bucket, key, opts, pre,
                       offset, length, copy_source=False):
        """(info, iterator, actual_size) for a multipart SSE object —
        parts are independently encrypted [nonce | DARE] streams laid
        back-to-back; decrypt only the chunks each part-range touches."""
        object_key = self._sse_object_key(request, bucket, key,
                                          pre.user_defined,
                                          copy_source=copy_source)
        if pre.version_id and not opts.version_id:
            # Pin the version across the per-part reads — a concurrent
            # overwrite mid-download must not splice replacement bytes
            # into the stream (single-PUT SSE reads in one backend call
            # and has no such window).
            import dataclasses
            opts = dataclasses.replace(opts, version_id=pre.version_id)
        plains = [sse.part_plain_size(stored) for _, stored in pre.parts]
        actual = sum(plains)
        if length < 0:
            length = actual - offset
        if offset < 0 or length < 0 or offset + length > actual:
            raise S3Error("InvalidRange", resource=f"/{bucket}/{key}")

        get = self.obj.get_object

        def gen():
            pos = 0        # plaintext cursor at current part start
            enc_pos = 0    # stored-byte cursor at current part start
            for (_, stored), plain in zip(pre.parts, plains):
                lo = max(offset - pos, 0)
                hi = min(offset + length - pos, plain)
                if hi > lo:
                    enc_off, enc_len, skip = sse.decrypted_range(
                        lo, hi - lo, plain)
                    if enc_off == 0:
                        # Nonce and data are adjacent: one backend read,
                        # peel the 12-byte nonce off the front.
                        _, raw = get(bucket, key, enc_pos,
                                     sse.NONCE_SIZE + enc_len, opts)
                        estream, nonce = _peel_prefix(raw, sse.NONCE_SIZE)
                        estream = _CloseProxy(estream, raw)
                    else:
                        _, nstream = get(bucket, key, enc_pos,
                                         sse.NONCE_SIZE, opts)
                        nonce = bytearray()
                        for piece in nstream:
                            nonce += piece
                        if len(nonce) != sse.NONCE_SIZE:
                            raise sse.SSEError(
                                f"part nonce truncated: {len(nonce)} bytes")
                        _, estream = get(
                            bucket, key, enc_pos + sse.NONCE_SIZE + enc_off,
                            enc_len, opts)
                    dec = sse.DecryptReader(
                        estream, sse.derive_part_key(object_key, nonce),
                        nonce, start_chunk=enc_off // sse.ENC_CHUNK,
                        total_chunks=sse.total_chunks(plain))
                    yield from _trim_iter(dec, skip, hi - lo, estream)
                pos += plain
                enc_pos += stored
                if pos >= offset + length:
                    return

        return pre, gen(), actual

    def _sse_object_key(self, request, bucket: str, key: str, meta: dict,
                        copy_source: bool = False) -> bytes:
        """Unseal the per-object data key; verifies SSE-C key headers."""
        algo = meta.get(sse.META_ALGO, "")
        aad = f"{bucket}/{key}"
        try:
            if algo == "SSE-C":
                ssec_key = sse.parse_ssec_headers(request.headers,
                                                  copy_source=copy_source)
                if ssec_key is None:
                    raise S3Error("InvalidRequest",
                                  "object is SSE-C encrypted: key required")
                return sse.unseal_key(
                    meta[sse.META_SEALED_KEY], ssec_key, aad)
            if algo == "SSE-KMS":
                from minio_tpu.crypto.kms import KMSError

                try:
                    return self.kms.decrypt_data_key(
                        meta[sse.META_SEALED_KEY], context=aad)
                except KMSError as e:
                    raise S3Error("AccessDenied", f"KMS: {e}") from None
            return sse.unseal_key(
                meta[sse.META_SEALED_KEY], self._sse_master_key(), aad)
        except sse.SSEError as e:
            raise S3Error("AccessDenied", str(e)) from None

    def _sse_unseal(self, request, bucket: str, key: str, meta: dict,
                    copy_source: bool = False) -> tuple:
        """(object_key, nonce, actual_size) for an encrypted object;
        verifies SSE-C key headers match."""
        import base64 as _b64

        object_key = self._sse_object_key(request, bucket, key, meta,
                                          copy_source=copy_source)
        nonce = (_b64.b64decode(meta[sse.META_NONCE])
                 if sse.META_NONCE in meta else b"")
        actual = int(meta.get(sse.META_ACTUAL_SIZE, "0"))
        return object_key, nonce, actual

    def _get_reader(self, bucket, key, opts):
        """(info, open_range) from the layer — via its single-quorum-read
        get_object_reader when it has one, else the two-call fallback
        (gateways and other duck-typed layers)."""
        gr = getattr(self.obj, "get_object_reader", None)
        if gr is not None:
            return gr(bucket, key, opts)
        info = self.obj.get_object_info(bucket, key, opts)

        def open_range(offset=0, length=-1):
            return self.obj.get_object(bucket, key, offset, length, opts)[1]

        return info, open_range

    def _open_stream_sync(self, request, bucket, key, opts, offset, length,
                          copy_source=False, pre=None, open_range=None):
        """Blocking core of the object read path: get_object_reader (ONE
        quorum metadata read) + transparent SSE/compression unwrap. Runs in
        a single executor hop — the previous shape paid a quorum read for
        the info and a second for the data, plus an executor round trip for
        each. Returns (info, iterator, plaintext_size)."""
        if pre is None:
            pre, open_range = self._get_reader(bucket, key, opts)

        def open_plain(off, ln):
            if open_range is not None:
                return open_range(off, ln)
            # Caller passed a pre-fetched info without a reader: fall back
            # to the two-call path for the data bytes.
            return self.obj.get_object(bucket, key, off, ln, opts)[1]

        if czip.META_COMPRESSION in pre.user_defined:
            actual = int(pre.user_defined.get(czip.META_ACTUAL_SIZE, "-1"))
            if length < 0:
                length = (actual - offset) if actual >= 0 else -1
            stream = open_plain(0, -1)
            return (pre,
                    czip.decompress_iter(
                        stream, offset, length,
                        scheme=pre.user_defined[czip.META_COMPRESSION]),
                    actual if actual >= 0 else pre.size)
        if sse.META_ALGO not in pre.user_defined:
            if length < 0:
                length = pre.size - offset
            return pre, open_plain(offset, length), pre.size
        if sse.META_NONCE not in pre.user_defined and pre.parts:
            # Multipart SSE: no object-level nonce; parts are independent
            # [nonce | DARE] streams.
            return self._mp_sse_stream(request, bucket, key, opts, pre,
                                       offset, length, copy_source)
        object_key, nonce, actual = self._sse_unseal(
            request, bucket, key, pre.user_defined, copy_source=copy_source)
        if length < 0:
            length = actual - offset
        if offset < 0 or length < 0 or offset + length > actual:
            raise S3Error("InvalidRange", resource=f"/{bucket}/{key}")
        if length == 0:
            return pre, iter([]), actual
        enc_off, enc_len, skip = sse.decrypted_range(offset, length, actual)
        enc_stream = open_plain(enc_off, enc_len)
        dec = sse.DecryptReader(
            enc_stream, object_key, nonce,
            start_chunk=enc_off // sse.ENC_CHUNK,
            total_chunks=sse.total_chunks(actual))
        return pre, _trim_iter(dec, skip, length, enc_stream), actual

    async def _open_object_stream(self, request, bucket, key, opts,
                                  offset, length, run, copy_source=False,
                                  pre=None):
        """Async wrapper: one executor hop around _open_stream_sync. Pass
        `pre` when the caller already paid the quorum metadata read."""
        return await run(self._open_stream_sync, request, bucket, key,
                         opts, offset, length, copy_source, pre)

    def _apply_object_lock(self, request, bucket: str, opts) -> None:
        """Stamp retention/legal-hold from request headers, falling back to
        the bucket's default retention (putOpts from object lock config,
        cmd/bucket-object-lock.go getObjectRetentionMeta)."""
        import time as _time

        mode = request.headers.get("x-amz-object-lock-mode", "").upper()
        until = request.headers.get("x-amz-object-lock-retain-until-date", "")
        hold = request.headers.get("x-amz-object-lock-legal-hold", "").upper()
        if mode and until:
            opts.user_defined[olock.KEY_MODE] = mode
            opts.user_defined[olock.KEY_UNTIL] = until
        else:
            default = olock.parse_default_retention(
                self.bucket_meta.get(bucket).object_lock_xml)
            if default is not None:
                dmode, seconds = default
                opts.user_defined[olock.KEY_MODE] = dmode
                opts.user_defined[olock.KEY_UNTIL] = olock.to_iso(
                    _time.time() + seconds)
        if hold:
            opts.user_defined[olock.KEY_HOLD] = hold

    # ------------------------------------------------------------------
    # eventing glue (reference sendEvent calls at the end of each handler)
    # ------------------------------------------------------------------

    def _ensure_rules(self, bucket: str) -> None:
        if bucket in self._rules_loaded:
            return
        self._rules_loaded.add(bucket)
        xml_cfg = self.bucket_meta.get(bucket).notification_xml
        if xml_cfg:
            try:
                self.notifier.set_bucket_rules(bucket, xml_cfg)
            except ValueError:
                pass  # stored config references a target gone from config

    def _emit(self, request, event_name: str, bucket: str, key: str,
              size: int = 0, etag: str = "", version_id: str = "") -> None:
        self._ensure_rules(bucket)
        if not self.notifier.has_rules(bucket):
            return
        ident = request.get("identity")
        self.notifier.send(new_object_event(
            event_name, bucket, key, size=size, etag=etag,
            version_id=version_id,
            user=getattr(ident, "access_key", "") or "anonymous",
            host=request.remote or "", region=self.region))

    # ------------------------------------------------------------------

    async def _spool_body(self, request, payload_hash, auth_sig,
                          bucket: str = ""):
        """Stream the request body into a spooled temp file, verifying the
        content sha256 or per-chunk streaming signatures. Returns
        (spool, size); caller closes the spool. `bucket` engages the
        per-bucket ingest bandwidth limiter."""
        if request.content_length is None and \
                "x-amz-decoded-content-length" not in request.headers:
            raise S3Error("MissingContentLength")
        size = request.content_length or 0
        decoded_len = request.headers.get("x-amz-decoded-content-length")
        streaming = payload_hash == sigv4.STREAMING_PAYLOAD
        if streaming:
            if auth_sig is None:
                # Chunk signatures chain off the header-auth seed signature;
                # a presigned URL has none, so streaming is undefined there.
                raise S3Error("InvalidArgument",
                              "streaming payload requires header authorization")
            if decoded_len is None:
                raise S3Error("MissingContentLength")
            try:
                size = int(decoded_len)
            except ValueError:
                raise S3Error("InvalidArgument",
                              "malformed x-amz-decoded-content-length") from None
        if size > MAX_OBJECT_SIZE:
            raise S3Error("EntityTooLarge")

        spool = tempfile.SpooledTemporaryFile(max_size=SPOOL_LIMIT)
        sha = hashlib.sha256() if payload_hash not in (
            sigv4.UNSIGNED_PAYLOAD, sigv4.STREAMING_PAYLOAD) else None
        chunked = None
        if streaming:
            amz_date = request.headers.get("x-amz-date", "")
            # The chunk signing key derives from the *requester's* secret
            # (reference calculateSeedSignature, streaming-signature-v4.go:77),
            # not the root credential — otherwise every aws-chunked PUT by a
            # non-root IAM/STS user fails with SignatureDoesNotMatch.
            req_creds = self._lookup(auth_sig.access_key) or self.creds
            chunked = sigv4.ChunkedSigV4Reader(
                req_creds, auth_sig.signature, amz_date, auth_sig.scope_date,
                auth_sig.region, auth_sig.service)
        # rx_drain, where the work happens: the socket wait (and the wait
        # for this one event-loop thread), the hashing, the spool write.
        chunks = aiter(request.content.iter_chunked(1 << 20))
        try:
            while True:
                with flight.span("rx_wait"):
                    chunk = await anext(chunks, None)
                if chunk is None:
                    break
                delay = self.bw_throttle.delay(bucket, len(chunk), "rx")
                if delay > 0:
                    await asyncio.sleep(delay)
                if chunked is not None:
                    # Verified chunk views stream straight to the spool
                    # (valid until the next feed — written before it).
                    with flight.span("rx_hash"):
                        pieces = chunked.feed(chunk)
                    with flight.span("rx_spool"):
                        for piece in pieces:
                            spool.write(piece)
                else:
                    if sha is not None:
                        with flight.span("rx_hash"):
                            sha.update(chunk)
                    with flight.span("rx_spool"):
                        spool.write(chunk)
            if chunked is not None and not chunked.done:
                raise S3Error("IncompleteBody")
            if sha is not None and sha.hexdigest() != payload_hash:
                raise S3Error("XAmzContentSHA256Mismatch")
        except BaseException:
            spool.close()
            raise
        spool.seek(0)
        return spool, size

    async def _put_object(self, request, bucket, key, opts, hdr,
                          payload_hash, auth_sig, run):
        opts.user_defined = _metadata_headers(request)
        if "content-type" not in opts.user_defined:
            # Extension-based inference (the pkg/mimedb role).
            import mimetypes

            guessed, _ = mimetypes.guess_type(key)
            opts.user_defined["content-type"] = (
                guessed or "application/octet-stream")
        self._apply_object_lock(request, bucket, opts)
        repl_cfg = self.replication.config_for(bucket)
        if repl_cfg is not None and repl_cfg.rule_for(key) is not None:
            from minio_tpu.replication.rules import META_STATUS, STATUS_PENDING
            opts.user_defined[META_STATUS] = STATUS_PENDING
        spool, size = await self._spool_body(request, payload_hash,
                                             auth_sig, bucket)
        reader, size2 = self._maybe_compress_put(
            request, bucket, key, opts, spool, size)
        reader, stored_size = self._maybe_encrypt_put(
            request, bucket, key, opts, reader, size2)
        try:
            # PUT always hops to the executor — even an inline-sized write
            # takes the namespace WRITE lock (30s timeout under contention)
            # and fsyncs; either on the event loop would stall every
            # connection on the server. (GET's on-loop fast path is safe
            # because reads are lockless and cache-backed.)
            info = await run(self.obj.put_object, bucket, key, reader,
                             stored_size, opts)
        finally:
            spool.close()
        extra = {"ETag": f'"{info.etag}"'}
        if info.version_id:
            extra["x-amz-version-id"] = info.version_id
        self.update_tracker.mark(bucket)
        self._emit(request, evt.OBJECT_CREATED_PUT, bucket, key,
                   size=info.size, etag=info.etag, version_id=info.version_id)
        if repl_cfg is not None:
            from minio_tpu.replication.pool import OP_PUT, ReplicationTask
            self.replication.queue_task(ReplicationTask(
                bucket, key, info.version_id, op=OP_PUT))
        return web.Response(status=200, headers={**hdr, **extra})

    async def _put_part(self, request, bucket, key, upload_id, part_number,
                        hdr, payload_hash, auth_sig, run):
        spool, size = await self._spool_body(request, payload_hash,
                                             auth_sig, bucket)
        try:
            reader, stored_size = await run(
                self._maybe_encrypt_part, request, bucket, key, upload_id,
                spool, size)
            res = await run(self.obj.put_object_part, bucket, key, upload_id,
                            part_number, reader, stored_size)
        finally:
            spool.close()
        return web.Response(status=200, headers={**hdr, "ETag": f'"{res.etag}"'})

    async def _upload_part_copy(self, request, bucket, key, upload_id,
                                part_number, src, hdr, run):
        src_bucket, src_key, src_opts = _parse_copy_source(src)
        # Read the *client-visible* bytes — decrypt/decompress the source
        # (the reference decrypts the source in CopyObjectPartHandler;
        # reading raw shards here would store ciphertext as a plain part).
        rng = request.headers.get("x-amz-copy-source-range")
        if rng:
            pre = await run(self.obj.get_object_info, src_bucket, src_key,
                            src_opts)
            offset, length = _parse_range(rng, self._visible_size(pre))
        else:
            pre, offset, length = None, 0, -1
        info, stream, visible_size = await self._open_object_stream(
            request, src_bucket, src_key, src_opts, offset, length, run,
            copy_source=True, pre=pre)
        if length < 0:
            length = visible_size - offset
        try:
            reader, stored_size = await run(
                self._maybe_encrypt_part, request, bucket, key, upload_id,
                _IterReader(stream), length)
            res = await run(self.obj.put_object_part, bucket, key, upload_id,
                            part_number, reader, stored_size)
        finally:
            close = getattr(stream, "close", None)
            if close is not None:
                await run(close)
        return web.Response(
            body=xmlutil.copy_object_xml(res.etag, res.last_modified),
            content_type=XML_TYPE, headers=hdr)

    async def _copy_object(self, request, bucket, key, src, opts, hdr, run):
        src_bucket, src_key, src_opts = _parse_copy_source(src)
        info, stream, src_visible = await self._open_object_stream(
            request, src_bucket, src_key, src_opts, 0, -1, run,
            copy_source=True)
        directive = request.headers.get("x-amz-metadata-directive", "COPY")
        user_defined = dict(info.user_defined)
        user_defined["content-type"] = info.content_type
        if directive == "REPLACE":
            user_defined = sanitize_user_meta({
                hk.lower(): hv for hk, hv in request.headers.items()
                if hk.lower().startswith("x-amz-meta-")
            })
            if request.headers.get("Content-Type"):
                user_defined["content-type"] = request.headers["Content-Type"]
        # Strip source encryption bookkeeping; destination re-encrypts per
        # its own headers/bucket config.
        for k in (sse.META_ALGO, sse.META_SEALED_KEY, sse.META_NONCE,
                  sse.META_KEY_MD5, sse.META_ACTUAL_SIZE,
                  sse.META_KMS_KEY_ID):
            user_defined.pop(k, None)
        opts.user_defined = user_defined

        reader, stored_size = self._maybe_encrypt_put(
            request, bucket, key, opts, _IterReader(stream), src_visible)
        try:
            new_info = await run(self.obj.put_object, bucket, key, reader,
                                 stored_size, opts)
        finally:
            # put_object reads exactly info.size bytes, leaving the source
            # generator paused before its cleanup — drive close() so shard
            # readers release and heal triggers fire.
            close = getattr(stream, "close", None)
            if close is not None:
                await run(close)
        return web.Response(body=xmlutil.copy_object_xml(new_info.etag,
                                                         new_info.mod_time),
                            content_type=XML_TYPE, headers=hdr)

    # Objects at or below this client-visible size are drained inside the
    # same executor hop that opened them and returned as one body — the
    # per-chunk executor round trips dominate small-object GET latency.
    _GET_DRAIN_LIMIT = 256 << 10
    # Larger bodies stream: one executor hop hands the loop every chunk
    # the stream has up to this many bytes (_drain_group). An erasure
    # stream's chunks are views of a read batch that is resident until
    # its last view is sent, so grouping them holds nothing more; a
    # transformed stream (decrypt, decompress, tier, gateway) yields fresh
    # buffers, and this is the most a request keeps of them. 4 MiB: a
    # quarter of a 16-block read batch, so an aligned read never waits on
    # the next batch with chunks in hand; a 10 MiB object is 3 hops.
    _GET_GROUP_BYTES = 4 << 20

    async def _get_object(self, request, bucket, key, opts, hdr, run):
        rng = request.headers.get("Range")

        def open_sync(drain_all):
            """Quorum read + range math + stream open in one call; for
            small responses, the full drain too. `drain_all=False` (the
            on-loop fast path) only drains zero-IO inline streams."""
            status = 200
            if rng:
                # Range needs the size before the read — with the single
                # reader the info and the data still cost ONE quorum round.
                pre, open_range = self._get_reader(bucket, key, opts)
                offset, length = _parse_range(rng, self._visible_size(pre))
                status = 206
                info, stream, visible = self._open_stream_sync(
                    request, bucket, key, opts, offset, length,
                    pre=pre, open_range=open_range)
            else:
                offset, length = 0, -1
                info, stream, visible = self._open_stream_sync(
                    request, bucket, key, opts, 0, -1)
            if length < 0:
                length = visible
            body = None
            if length <= self._GET_DRAIN_LIMIT \
                    and (drain_all or type(stream) is _LIST_ITER) \
                    and not _check_conditional(request, info):
                # Drain to a chunk LIST, not one joined buffer: the
                # chunks flow to the socket as-is (zero coalesce pass).
                body = list(stream)
            return status, offset, length, info, stream, visible, body

        if getattr(self.obj, "fast_local_reads", False):
            # All-local fast media: the open is ~100us of cached metadata
            # work — cheaper than an executor round trip, so run it on the
            # loop (inline streams drain here too; anything with real IO
            # still hops below).
            status, offset, length, info, stream, visible, body = \
                open_sync(False)
            if body is None and length <= self._GET_DRAIN_LIMIT \
                    and not _check_conditional(request, info):
                body = await run(lambda: list(stream))
        else:
            status, offset, length, info, stream, visible, body = \
                await run(open_sync, True)
        if _check_conditional(request, info):
            return web.Response(status=304, headers={
                **hdr, "ETag": f'"{info.etag}"',
            })
        headers = {**hdr, **_object_headers(info)}
        headers["Content-Length"] = str(length)
        if status == 206:
            headers["Content-Range"] = f"bytes {offset}-{offset + length - 1}/{visible}"
        if body is not None:
            delay = self.bw_throttle.delay(bucket, length)
            if delay > 0:
                await asyncio.sleep(delay)
            if len(body) == 1:
                return web.Response(status=status, body=body[0],
                                    headers=headers)
            # Multi-chunk drained body: write each chunk through the
            # stream writer (Content-Length is already set above) —
            # payload bytes go socket-ward without ever being joined.
            resp = web.StreamResponse(status=status, headers=headers)
            await resp.prepare(request)
            for c in body:
                await resp.write(c)
            await resp.write_eof()
            return resp
        resp = web.StreamResponse(status=status, headers=headers)
        await resp.prepare(request)
        # First response bytes (the headers) just flushed: this is the
        # stream's TTFB, picked up by _entry's finally.
        t0_req = request.get("mtpu-t0")
        if t0_req is not None:
            request["mtpu-ttfb"] = time.perf_counter() - t0_req
        loop = asyncio.get_running_loop()
        it = iter(stream)
        # One context copy for the whole drain (the awaits are
        # sequential, so the copy is never entered concurrently): shard
        # reads run inside next() on the executor and their storage/RPC
        # records must keep this request's trace id.
        drain_group = obs.ctx_wrap(
            lambda: _drain_group(it, self._GET_GROUP_BYTES))
        # resp_drain, split: the loop waits for the object layer's next
        # group of chunks (drive read, verify, decode), then sends them
        # as they are: the views stay views, nothing is joined. A group
        # is one vectored write where the connection allows it.
        vectored = _vectored_writer(resp)
        done = False
        while not done:
            with flight.span("tx_next"):
                chunks, done = await loop.run_in_executor(None, drain_group)
            _DRAIN_HOPS.inc()
            _DRAIN_CHUNKS.inc(len(chunks))
            delay = self.bw_throttle.delay(
                bucket, sum(len(c) for c in chunks))
            if delay > 0:
                await asyncio.sleep(delay)
            with flight.span("tx_send"):
                if vectored is not None:
                    # Counted beside the hop, before the next await: two
                    # scrapes read the same number of both.
                    _VECTORED_GROUPS.inc()
                    await _write_group(vectored, chunks)
                else:
                    for chunk in chunks:
                        await resp.write(chunk)
        with flight.span("tx_send"):
            await resp.write_eof()
        return resp

    async def _delete_objects(self, request, bucket, hdr, run):
        body = await request.read()
        objects, quiet = xmlutil.parse_delete_xml(body)
        identity = request.get("identity")

        base_ctx = request.get("cond-ctx") or self._condition_context(
            request, identity)

        def authorize():
            ok, den = [], []
            for k, v in objects:
                action = ("s3:DeleteObjectVersion" if v
                          else "s3:DeleteObject")
                ctx = base_ctx
                if v:  # per-key version scope (s3:versionid conditions)
                    # NormalizedContext copy keeps the already-normalized
                    # marker — a plain {**base_ctx} would make every
                    # PolicyArgs re-normalize the full context per key.
                    from minio_tpu.iam.condition import NormalizedContext
                    ctx = NormalizedContext(base_ctx)
                    ctx["s3:versionid"] = [v]
                try:
                    self._check_access(identity, action, bucket, k, ctx)
                    ok.append((k, v))
                except S3Error:
                    den.append((k, "AccessDenied", "Access Denied."))
            return ok, den

        # Off the event loop: N policy evaluations for N keys.
        authorized, denied = await run(authorize)
        objects = authorized
        todo = [ObjectToDelete(k, v) for k, v in objects]
        results = await run(self.obj.delete_objects, bucket, todo,
                            ObjectOptions(versioned=self.versioned_buckets))
        deleted, errors = [], list(denied)
        for (k, v), r in zip(objects, results):
            if isinstance(r, Exception):
                s3e = from_exception(r, k)
                if s3e.api.code == "NoSuchKey":
                    # S3 semantics: deleting a missing key succeeds.
                    if not quiet:
                        from minio_tpu.erasure.types import DeletedObject
                        deleted.append(DeletedObject(object_name=k, version_id=v))
                else:
                    errors.append((k, s3e.api.code, s3e.message))
            elif not quiet:
                deleted.append(r)
        return web.Response(body=xmlutil.delete_result_xml(deleted, errors),
                            content_type=XML_TYPE, headers=hdr)


class _CloseProxy:
    """Iterator wrapper whose close() also closes the underlying source
    stream (generators can't carry extra attributes)."""

    def __init__(self, it, source):
        self._it = iter(it)
        self._source = source

    def __iter__(self):
        return self._it

    def close(self) -> None:
        close = getattr(self._source, "close", None)
        if close is not None:
            close()


def _peel_prefix(stream, n: int):
    """Take the first n bytes off a bytes-iterator; returns (rest_iter,
    prefix memoryview). rest_iter preserves the remaining bytes and
    close(); nothing is re-joined — the accumulated head is sliced as
    memoryviews (the backing bytearray is never resized after export)."""
    it = iter(stream)
    acc = bytearray()
    while len(acc) < n:
        try:
            acc += next(it)
        except StopIteration:
            # PEP 479: letting this escape into a consuming generator
            # becomes RuntimeError mid-response; surface a clean error.
            raise sse.SSEError(
                f"stream truncated: {len(acc)} of {n} prefix bytes"
            ) from None
    mv = memoryview(acc)
    prefix, rest = mv[:n], mv[n:]

    def gen():
        if len(rest):
            yield rest
        yield from it

    return gen(), prefix


def _trim_iter(it, skip: int, length: int, source=None):
    """Yield `length` bytes from `it` after dropping the first `skip`
    (chunk-aligned decrypt streams overshoot a byte range on both ends);
    closes `source` when done."""
    remaining = length
    drop = skip
    for chunk in it:
        cv = memoryview(chunk)
        if drop:
            if len(cv) <= drop:
                drop -= len(cv)
                continue
            cv = cv[drop:]
            drop = 0
        if len(cv) >= remaining:
            yield cv[:remaining]
            remaining = 0
            break
        remaining -= len(cv)
        yield cv
    close = getattr(source, "close", None)
    if close is not None:
        close()


class _PrefixReader:
    """File-like that serves a fixed prefix, then an inner reader — carries
    a part's random nonce at the head of its encrypted stream."""

    def __init__(self, prefix: bytes, inner):
        self._prefix = prefix
        self._inner = inner

    def read(self, n: int = -1) -> bytes:
        if self._prefix:
            if n < 0 or n >= len(self._prefix):
                out, self._prefix = self._prefix, b""
                rest = self._inner.read(n - len(out) if n >= 0 else -1)
                return out + rest
            out, self._prefix = self._prefix[:n], self._prefix[n:]
            return out
        return self._inner.read(n)

    def close(self) -> None:
        close = getattr(self._inner, "close", None)
        if close is not None:
            close()


# File-like over a bytes iterator — canonical home: utils/streams.py.
from minio_tpu.utils.streams import IterReader as _IterReader  # noqa: E402

_BROWSER_HTML: bytes | None = None


def _browser_page() -> bytes:
    """browser.html, read once (immutable bytes; no per-request disk IO)."""
    global _BROWSER_HTML
    if _BROWSER_HTML is None:
        import importlib.resources as _res

        _BROWSER_HTML = (_res.files("minio_tpu.s3")
                         / "browser.html").read_bytes()
    return _BROWSER_HTML


def _validate_xml(body: bytes) -> None:
    import xml.etree.ElementTree as _ET

    try:
        _ET.fromstring(body)
    except _ET.ParseError:
        raise S3Error("MalformedXML") from None


def _metadata_headers(request) -> dict:
    """User-controlled object metadata extracted from request headers."""
    user_defined = {}
    ct = request.headers.get("Content-Type")
    if ct:
        user_defined["content-type"] = ct
    sc = request.headers.get("x-amz-storage-class")
    if sc:
        user_defined["x-amz-storage-class"] = sc
    tags = request.headers.get("x-amz-tagging")
    if tags:
        user_defined["x-amz-tagging"] = tags
    repl = request.headers.get("x-amz-replication-status")
    if repl:
        user_defined["x-amz-replication-status"] = repl
    for hk, hv in request.headers.items():
        lk = hk.lower()
        if lk.startswith("x-amz-meta-") and not _is_reserved_meta(lk):
            user_defined[lk] = hv
    return user_defined


def _is_reserved_meta(key: str) -> bool:
    """Reserved-metadata filter (reference filterReservedMetadata,
    cmd/generic-handlers.go): internal bookkeeping namespaces must never be
    client-settable — a crafted header could otherwise forge SSE/transition
    state, including via the gateway's packed meta key (whose payload
    unpack_internal_meta would inject as x-mtpu-internal-*)."""
    lk = key.lower()
    suffix = lk[len("x-amz-meta-"):] if lk.startswith("x-amz-meta-") else lk
    return suffix.startswith(("mtpu", "x-mtpu")) or "mtpu-internal" in suffix


def sanitize_user_meta(meta: dict) -> dict:
    """Drop reserved-namespace keys from client-supplied metadata — the
    single sanitizer every metadata ingestion path (PUT headers, CopyObject
    REPLACE, POST-policy forms) runs through."""
    return {k: v for k, v in meta.items() if not _is_reserved_meta(k)}


def _parse_copy_source(src: str):
    """x-amz-copy-source → (bucket, key, ObjectOptions with versionId)."""
    src = urllib.parse.unquote(src)
    src_vid = ""
    if "?versionId=" in src:
        src, src_vid = src.split("?versionId=", 1)
    src = src.lstrip("/")
    if "/" not in src:
        raise S3Error("InvalidArgument", "bad x-amz-copy-source")
    src_bucket, src_key = src.split("/", 1)
    return src_bucket, src_key, ObjectOptions(version_id=src_vid)


def _object_headers(info) -> dict:
    size = S3Server._visible_size(info)
    h = {
        "ETag": f'"{info.etag}"',
        "Last-Modified": _http_time(info.mod_time),
        "Content-Type": info.content_type or "binary/octet-stream",
        "Accept-Ranges": "bytes",
        "Content-Length": str(size),
    }
    h.update(sse.sse_headers_for(info.user_defined))
    if info.version_id:
        h["x-amz-version-id"] = info.version_id
    for k, v in info.user_defined.items():
        if k.startswith("x-amz-meta-"):
            h[k] = v
    repl = info.user_defined.get("x-amz-replication-status")
    if repl:
        h["x-amz-replication-status"] = repl
    tags = info.user_defined.get("x-amz-tagging")
    if tags:
        h["x-amz-tagging-count"] = str(len(urllib.parse.parse_qsl(tags)))
    return h


def _http_time(ts: float) -> str:
    import email.utils

    return email.utils.formatdate(ts, usegmt=True)


def _parse_range(value: str, size: int) -> tuple[int, int]:
    if not value.startswith("bytes="):
        raise S3Error("InvalidRange")
    spec = value[6:].split(",")[0].strip()
    try:
        if spec.startswith("-"):
            suffix = int(spec[1:])
            if suffix == 0:
                raise S3Error("InvalidRange")
            start = max(0, size - suffix)
            end = size - 1
        else:
            se_ = spec.split("-")
            start = int(se_[0])
            end = int(se_[1]) if len(se_) > 1 and se_[1] else size - 1
    except ValueError:
        raise S3Error("InvalidRange") from None
    if start >= size or end < start:
        raise S3Error("InvalidRange")
    end = min(end, size - 1)
    return start, end - start + 1


def _check_conditional(request, info) -> bool:
    """Returns True for a 304 Not Modified outcome; raises for 412."""
    im = request.headers.get("If-Match")
    if im and im != "*" and im.strip('"') != info.etag:
        raise S3Error("PreconditionFailed", "ETag does not match If-Match")
    inm = request.headers.get("If-None-Match")
    if inm and (inm == "*" or inm.strip('"') == info.etag):
        if request.method in ("GET", "HEAD"):
            return True  # cache revalidation hit
        raise S3Error("PreconditionFailed", "ETag matches If-None-Match")
    return False


# ----------------------------------------------------------------------


def build_server(drive_paths: list[str], access_key: str, secret_key: str,
                 versioned: bool = False, parity: int | None = None,
                 set_drive_count: int | None = None,
                 enable_mrf: bool = True,
                 server_addr: str = "", certs_dir: str = "") -> S3Server:
    """Assemble the full backend stack: drives → sets (sipHash routing) →
    pools (capacity placement) → S3 front door (reference newObjectLayer,
    cmd/server-main.go:557). URL endpoints (http://host/disk) boot the
    distributed path: RPC fabric + bootstrap handshake + dsync locks
    (reference serverMain distributed branch, cmd/server-main.go:484-500)."""
    from minio_tpu.erasure.pools import ErasureServerPools
    from minio_tpu.erasure.sets import ErasureSets

    # Single plain path -> FS backend (reference newObjectLayer: one
    # endpoint means NewFSObjectLayer, cmd/server-main.go:557).
    if len(drive_paths) == 1 and "://" not in drive_paths[0]:
        from minio_tpu.fs import FSObjects

        layer = FSObjects(drive_paths[0])
        return S3Server(layer, sigv4.Credentials(access_key, secret_key),
                        versioned_buckets=versioned)

    if any("://" in p for p in drive_paths):
        from minio_tpu.dist.cluster import ClusterNode
        from minio_tpu.logger import get_logger as _get_logger

        host, _, port = server_addr.rpartition(":")
        node = ClusterNode([drive_paths], host=host or "127.0.0.1",
                           port=int(port or 9000), secret=secret_key,
                           set_drive_count=set_drive_count or 0,
                           parity=parity, certs_dir=certs_dir)
        # The reference retries cluster bootstrap until the fleet
        # converges (verifyServerSystemConfig / waitForFormatErs loop)
        # rather than dying when peers boot slowly or out of order; a
        # node that crashed here would just be restarted by the
        # supervisor anyway. Same for the first config/IAM quorum reads:
        # peers may be seconds away from serving their drives.
        boot_deadline = time.monotonic() + float(
            os.environ.get("MTPU_BOOT_TIMEOUT", "600"))
        while True:
            layer = None
            try:
                node.wait_for_peers()
                layer = node.build_object_layer(enable_mrf=enable_mrf)
                srv = S3Server(layer,
                               sigv4.Credentials(access_key, secret_key),
                               versioned_buckets=versioned,
                               notification_sys=node.notification)
                break
            except (se.OperationTimedOut, se.InsufficientReadQuorum,
                    se.InsufficientWriteQuorum) as e:
                if layer is not None:
                    try:
                        layer.close()
                    except Exception:  # noqa: BLE001 — teardown only
                        pass
                if time.monotonic() > boot_deadline:
                    raise
                _get_logger().warning(
                    f"boot: waiting for cluster quorum ({e}); retrying")
                time.sleep(2.0)
        srv.attach_cluster(node)
        return srv

    # Drives sharing one physical device lose failure independence
    # (pkg/mountinfo CheckCrossDevice role) — warn loudly, keep serving.
    from minio_tpu.logger import get_logger
    from minio_tpu.utils.mounts import check_cross_device

    for w in check_cross_device(drive_paths):
        get_logger().warning(w)

    drives = [LocalDrive(p) for p in drive_paths]
    # Calibration profile on drive 0 (docs/SLO.md): write-or-compare
    # the host fingerprint + tuned gates; a mismatch raises
    # minio_tpu_calibration_stale instead of silently serving gates
    # tuned for other hardware.
    from minio_tpu.obs import calibration as _calibration

    _calibration.boot(drive_paths[0])
    sets = ErasureSets(drives, set_drive_count=set_drive_count, parity=parity,
                       enable_mrf=enable_mrf)
    layer = ErasureServerPools([sets])
    return S3Server(layer, sigv4.Credentials(access_key, secret_key),
                    versioned_buckets=versioned)


def build_gateway_server(kind: str, target: str, access_key: str,
                         secret_key: str,
                         remote_access: str = "", remote_secret: str = ""
                         ) -> S3Server:
    """Gateway modes (reference StartGateway, cmd/gateway-main.go:155):
    nas <path> | s3 <endpoint> | gcs [<endpoint>] | azure <endpoint>
    | hdfs <namenode endpoint>. Remote credentials come from
    MTPU_GATEWAY_ACCESS_KEY/SECRET_KEY (azure: account/base64 key;
    hdfs: access=user)."""
    from minio_tpu.gateway import (
        AzureGateway,
        HDFSGateway,
        S3Gateway,
        gcs_gateway,
        nas_gateway,
    )

    if kind == "nas":
        layer = nas_gateway(target)
    elif kind == "s3":
        layer = S3Gateway(target, remote_access or access_key,
                          remote_secret or secret_key)
    elif kind == "gcs":
        layer = gcs_gateway(remote_access or access_key,
                            remote_secret or secret_key,
                            endpoint=target or
                            "https://storage.googleapis.com")
    elif kind == "azure":
        layer = AzureGateway(target, remote_access or access_key,
                             remote_secret or secret_key)
    elif kind == "hdfs":
        layer = HDFSGateway(target, user=remote_access or "minio")
    else:
        raise ValueError(f"unknown gateway {kind!r} (nas|s3|gcs|azure|hdfs)")
    return S3Server(layer, sigv4.Credentials(access_key, secret_key))


def main(argv=None):
    ap = argparse.ArgumentParser(description="minio_tpu S3 server")
    ap.add_argument("drives", nargs="+", help="drive directories")
    ap.add_argument("--gateway", default="",
                    help="gateway mode: nas|s3 (drives arg becomes the "
                         "path/endpoint)")
    ap.add_argument("--address", default="0.0.0.0:9000")
    ap.add_argument("--versioned", action="store_true")
    ap.add_argument("--parity", type=int, default=None)
    ap.add_argument("--set-drives", type=int, default=None,
                    help="drives per erasure set (default: all drives, one set)")
    ap.add_argument("--scan-interval", type=float, default=60.0,
                    help="background scanner cycle pause (seconds; 0 disables)")
    ap.add_argument("--cache-dir", default="",
                    help="local SSD cache directory (enables the disk cache)")
    ap.add_argument("--cache-quota", type=int, default=1 << 30,
                    help="disk cache quota in bytes")
    ap.add_argument("--certs-dir", default=os.environ.get("MTPU_CERTS_DIR", ""),
                    help="TLS certs dir (public.crt + private.key, "
                         "hot-reloaded); empty serves plaintext HTTP")
    args = ap.parse_args(argv)
    import sys as _sys

    # Compiled codec programs persist across restarts (placement rule:
    # utils/compile_cache.py). The backend is whatever JAX_PLATFORMS
    # names; an accelerator belongs to ONE process at a time.
    from minio_tpu.utils import compile_cache

    compile_cache.enable()

    # Raise the fd soft limit to the hard limit (reference pkg/sys
    # setMaxResources) — a drive fleet + RPC fan-out outgrows the default
    # 1024 fast.
    from minio_tpu.utils import sysres

    sysres.maximize_nofile()

    # The exact re-exec line `admin service restart` uses (module entry —
    # script-mode exec would lose the package root from sys.path).
    restart_cmd = [_sys.executable, "-m", "minio_tpu.s3.server"] + (
        list(argv) if argv is not None else _sys.argv[1:])
    host, _, port = args.address.rpartition(":")
    access = os.environ.get("MTPU_ROOT_USER", "minioadmin")
    secret = os.environ.get("MTPU_ROOT_PASSWORD", "minioadmin")
    if args.gateway:
        srv = build_gateway_server(
            args.gateway, args.drives[0], access, secret,
            remote_access=os.environ.get("MTPU_GATEWAY_ACCESS_KEY", ""),
            remote_secret=os.environ.get("MTPU_GATEWAY_SECRET_KEY", ""))
        srv.restart_cmd = restart_cmd
        web.run_app(srv.app, host=(args.address.rpartition(":")[0]
                                   or "0.0.0.0"),
                    port=int(args.address.rpartition(":")[2]))
        return
    srv = build_server(args.drives, access, secret,
                       versioned=args.versioned, parity=args.parity,
                       set_drive_count=args.set_drives,
                       server_addr=args.address,
                       certs_dir=args.certs_dir or "")
    srv.restart_cmd = restart_cmd
    if args.cache_dir:
        from minio_tpu.cache import CacheObjects

        srv.obj = CacheObjects(
            srv.obj, args.cache_dir, quota_bytes=args.cache_quota,
            commit=os.environ.get("MTPU_CACHE_COMMIT", "writethrough"))
    if args.scan_interval > 0:
        srv.start_scanner(interval=args.scan_interval)
    srv.start_auto_heal()
    ssl_context = None
    if args.certs_dir:
        from minio_tpu.utils.certs import CertManager

        ssl_context = CertManager(args.certs_dir).ssl_context
    web.run_app(srv.app, host=host or "0.0.0.0", port=int(port),
                ssl_context=ssl_context)


if __name__ == "__main__":
    main()
