"""docs/KNOBS.md generator + registry parser (rule MTPU010).

`python -m tools.check --knobs` regenerates docs/KNOBS.md from two
sources:

- the pass-1 scan (ProjectIndex `env_reads`): every `MTPU_*` read
  under minio_tpu/ with its static default and the modules that
  consume it — the mechanical truth;
- `KNOB_DOCS` below: the curated one-line purpose and doc cross-link
  per knob — the part a scan cannot know.

A knob the scan finds with no KNOB_DOCS entry renders an UNDOCUMENTED
placeholder row, which rule MTPU010 fails — so a new knob cannot ship
silently. A KNOB_DOCS entry the scan no longer sees simply stops
rendering (and a stale committed row fails the rule the other way).

Dynamic families (`MTPU_DRIVE_DEADLINE_{cls}`) render one row per
documented expansion: KNOB_DOCS carries the concrete names and the
generator matches them against the scanned prefix.
"""

from __future__ import annotations

import re
from pathlib import Path

_ROW_RE = re.compile(r"^\|\s*`(MTPU_[A-Z0-9_]+)`\s*\|")

# name -> (doc link relative to docs/, one-line purpose). Keep sorted.
KNOB_DOCS: dict[str, tuple[str, str]] = {
    "MTPU_BATCHED_DATAPLANE": (
        "DATAPLANE.md",
        "Batch-dataplane gate: coalesced encode/decode/verify lanes "
        "(default); `0` falls back to per-request fused launches."),
    "MTPU_BOOT_TIMEOUT": (
        "RESILIENCE.md",
        "Seconds the boot loop waits for pool quorum (peers may be "
        "seconds away from serving their drives) before failing."),
    "MTPU_CACHE_COMMIT": (
        "",
        "Gateway disk-cache commit mode for `--cache-dir`: "
        "`writethrough` or `writeback`."),
    "MTPU_CERTS_DIR": (
        "",
        "TLS certificate directory (public.crt/private.key) — the "
        "`--certs-dir` default."),
    "MTPU_CHAOS_DRIVE_WRAP": (
        "CHAOS.md",
        "`1` marks this process as running chaos fault injectors in "
        "the drive chain, so erasure submits route through the "
        "injector-aware path instead of the pure-memory inline one."),
    "MTPU_CHAOS_SEED": (
        "CHAOS.md",
        "Deterministic seed for chaos storms — reproduces a failing "
        "storm schedule exactly."),
    "MTPU_DP_LANE_BLOCKS": (
        "DATAPLANE.md",
        "Encode/reconstruct rows coalesced per device launch."),
    "MTPU_DP_MAX_WAIT_US": (
        "DATAPLANE.md",
        "Lone-request latency bound: microseconds a lane waits to "
        "fill a batch before launching anyway."),
    "MTPU_DP_QUEUE": (
        "DATAPLANE.md",
        "Bounded batch-lane submission queue (requests); a full queue "
        "is backpressure, never unbounded RAM."),
    "MTPU_DP_RING_DEPTH": (
        "DATAPLANE.md",
        "Staging slots per lane (double-buffer and beyond): host "
        "fills slot N+1 while the device runs slot N."),
    "MTPU_DP_VERIFY_ROWS": (
        "DATAPLANE.md",
        "Bitrot-verify chunks coalesced per device launch."),
    "MTPU_DRIVE_DEADLINE_DATA": (
        "RESILIENCE.md",
        "Drive-op deadline override (seconds) for the `data` class "
        "(shard streams). The chaos harness tightens it so an "
        "injected hang walks a drive OFFLINE within its storm window."),
    "MTPU_DRIVE_DEADLINE_META": (
        "RESILIENCE.md",
        "Drive-op deadline override (seconds) for the `meta` class "
        "(journal/volume round trips)."),
    "MTPU_DRIVE_DEADLINE_WALK": (
        "RESILIENCE.md",
        "Drive-op deadline override (seconds) for the `walk` class "
        "(gap between listing entries)."),
    "MTPU_DSYNC_REFRESH_INTERVAL": (
        "RESILIENCE.md",
        "Distributed-lock refresh interval (seconds); locks go stale "
        "at 60 s without a refresh."),
    "MTPU_ETCD_ENDPOINT": (
        "",
        "etcd endpoint for bucket-metadata federation; empty disables "
        "the etcd integration."),
    "MTPU_ETCD_PASSWORD": (
        "",
        "etcd authentication password (credential — set via the "
        "environment, never a config file)."),
    "MTPU_ETCD_USERNAME": (
        "",
        "etcd authentication username."),
    "MTPU_ETCD_WATCH_INTERVAL": (
        "",
        "Seconds between etcd bucket-metadata poll sweeps."),
    "MTPU_EVENT_QUEUE_DIR": (
        "",
        "On-disk spool directory for bucket-notification events "
        "(survives target outages; per-pid temp dir by default)."),
    "MTPU_EXEMPLAR": (
        "SLO.md",
        "`0`/`false`/`off` disarms OpenMetrics exemplar capture; armed "
        "(default) latency histograms sample the active trace id so "
        "scrapes can deep-link a slow bucket to its flight-recorder "
        "timeline."),
    "MTPU_EXEMPLAR_EVERY": (
        "SLO.md",
        "Exemplar sampling stride: capture the trace id on every Nth "
        "traced observation per histogram child (default 8)."),
    "MTPU_FAULT_INJECTION": (
        "CHAOS.md",
        "`1` opts this PROCESS into the admin faultplane handlers — "
        "beyond admin:* policy, because the faultplane can sever a "
        "production cluster."),
    "MTPU_FLIGHT": (
        "TRACING.md",
        "`0`/`false`/`off` disarms the per-request flight recorder; "
        "armed (default) every request keeps a stage timeline, "
        "queryable via `GET /minio/admin/v3/perf/timeline`."),
    "MTPU_FLIGHT_RING": (
        "TRACING.md",
        "Flight-recorder ring depth: the last N completed request "
        "timelines kept per process (default 256)."),
    "MTPU_FLIGHT_SPOOL": (
        "TRACING.md",
        "Flight-spool shm base name, stamped into workers by the "
        "front-door supervisor; worker i writes snapshots into "
        "`<base>w<i>` so any worker can answer for the pool."),
    "MTPU_FLIGHT_WORST": (
        "TRACING.md",
        "Slowest-N board depth: how many worst-case timelines the "
        "flight recorder retains per API (default 8)."),
    "MTPU_FRONTDOOR_CONTROL": (
        "FRONTDOOR.md",
        "Router control-socket path, stamped into workers by the "
        "front-door supervisor (router shard policy only)."),
    "MTPU_FRONTDOOR_DRAIN_S": (
        "FRONTDOOR.md",
        "Graceful-drain window (seconds) a worker gets on SIGTERM "
        "before escalation."),
    "MTPU_FRONTDOOR_RING": (
        "FRONTDOOR.md",
        "shm submission-ring name, stamped into workers by the "
        "supervisor; empty means no ring (single-process mode)."),
    "MTPU_FRONTDOOR_RING_TIMEOUT_S": (
        "FRONTDOOR.md",
        "Seconds a ring client waits for slot completion before "
        "abandoning the slot (worker crash containment)."),
    "MTPU_FRONTDOOR_SHARD": (
        "FRONTDOOR.md",
        "Connection shard policy: `router` (userspace pre-accept "
        "round-robin, deterministic everywhere) or `reuseport` "
        "(zero-hop kernel dispatch where SO_REUSEPORT balances)."),
    "MTPU_FRONTDOOR_SHARED_LANES": (
        "FRONTDOOR.md",
        "`1` converges worker dataplane traffic onto the shared shm "
        "ring so batches coalesce ACROSS processes."),
    "MTPU_FRONTDOOR_SLOT_BYTES": (
        "FRONTDOOR.md",
        "Payload bytes per shm ring slot; larger ops split across "
        "chained slots."),
    "MTPU_FRONTDOOR_WORKER": (
        "FRONTDOOR.md",
        "This process's worker id, stamped by the supervisor; its "
        "presence is what marks a process as a front-door worker."),
    "MTPU_FRONTDOOR_WORKERS": (
        "FRONTDOOR.md",
        "Front-door worker-pool width; `1` is the classic "
        "single-process server."),
    "MTPU_GATEWAY_ACCESS_KEY": (
        "",
        "Upstream S3 access key for gateway mode (`--gateway`)."),
    "MTPU_GATEWAY_SECRET_KEY": (
        "",
        "Upstream S3 secret key for gateway mode (credential)."),
    "MTPU_HOTTIER": (
        "HOTTIER.md",
        "`1` enables the HBM-resident hot-object tier (device-side "
        "GET serving); the drive path stays as miss fallback and "
        "bit-exactness oracle."),
    "MTPU_HOTTIER_ADMIT_COOLDOWN_S": (
        "HOTTIER.md",
        "Per-key admission-attempt cooldown (seconds): one oracle "
        "read per churny key per window."),
    "MTPU_HOTTIER_BYTES": (
        "HOTTIER.md",
        "HBM budget (bytes) for resident hot objects."),
    "MTPU_HOTTIER_HALFLIFE_S": (
        "HOTTIER.md",
        "Heat-decay half-life (seconds) for the admission/eviction "
        "policy."),
    "MTPU_HOTTIER_MAX_OBJECT": (
        "HOTTIER.md",
        "Largest object (bytes) the tier will admit."),
    "MTPU_HOTTIER_MIN_HEAT": (
        "HOTTIER.md",
        "Minimum decayed heat before a key is considered for "
        "admission."),
    "MTPU_HOTTIER_VERIFY": (
        "HOTTIER.md",
        "Admit-time verification that the RESIDENT copy re-hashes to "
        "the host staging baseline (default on); `0` trusts the "
        "admit transfer."),
    "MTPU_KERNEL_SYNC": (
        "METRICS.md",
        "`1` makes kernel observability block until device-complete "
        "(true kernel seconds); default times host dispatch only."),
    "MTPU_KMS_DEFAULT_KEY": (
        "",
        "Default SSE-KMS key id used when a request names none."),
    "MTPU_KMS_KEY_FILE": (
        "",
        "Path to the KMS master-key file; overrides the derived "
        "default."),
    "MTPU_KMS_SECRET_KEY": (
        "",
        "Static KMS master secret (credential); defaults to a "
        "root-credential derivation."),
    "MTPU_MESH_CODEC": (
        "DATAPLANE.md",
        "`1` opts the mesh-sharded codec lane in on CPU, whose "
        "\"devices\" are virtual — how the test suite exercises the "
        "multi-device path; real accelerator meshes enable it "
        "automatically."),
    "MTPU_METAPLANE": (
        "METAPLANE.md",
        "Group-commit metadata plane gate (default on); `0` falls "
        "back to per-op direct drive writes."),
    "MTPU_METAPLANE_CACHE": (
        "METAPLANE.md",
        "Set-level FileInfo LRU cache capacity (objects)."),
    "MTPU_METRICS_PEER_DEADLINE": (
        "METRICS.md",
        "Deadline (seconds) for the cluster-metrics peer scrape "
        "fan-out; hung peers count into the scrape-error metric."),
    "MTPU_MRF_RETRY_CAP": (
        "RESILIENCE.md",
        "MRF heal-retry exponential-backoff cap (seconds)."),
    "MTPU_MRF_RETRY_INTERVAL": (
        "RESILIENCE.md",
        "MRF heal-retry initial interval (seconds)."),
    "MTPU_MRF_RETRY_MAX": (
        "RESILIENCE.md",
        "MRF heal-retry attempt bound before an entry is dropped to "
        "the background scanner."),
    "MTPU_NATIVE_PLANE": (
        "DATAPLANE.md",
        "Native fused encode/decode pipeline gate (default on); `0` "
        "falls back to the composed per-stage ops."),
    "MTPU_PEER_BREAKER_FAILURES": (
        "RESILIENCE.md",
        "Consecutive failures before a peer's circuit breaker opens."),
    "MTPU_PEER_RETRIES": (
        "RESILIENCE.md",
        "Retry attempts per peer RPC (idempotent routes only)."),
    "MTPU_PEER_RETRY_BUDGET": (
        "RESILIENCE.md",
        "Token-bucket budget shared by peer-RPC retries — bounds "
        "retry amplification under brownout."),
    "MTPU_PEER_RETRY_REFILL": (
        "RESILIENCE.md",
        "Peer-retry token-bucket refill rate (tokens/second)."),
    "MTPU_QOS": (
        "QOS.md",
        "`1` arms the per-tenant QoS plane: fair queues at both batch "
        "planes plus the OP_HOTGET ring gate; disarmed (default) "
        "admission is bit-identical to the pre-QoS tree."),
    "MTPU_QOS_BURST_S": (
        "QOS.md",
        "Seconds of rate a tenant's token buckets accumulate as burst "
        "headroom."),
    "MTPU_QOS_HOTGET_OPS": (
        "QOS.md",
        "Per-tenant OP_HOTGET ring probes/second (token bucket); over "
        "quota falls back to the local drive path, never a 503. "
        "`0` = unlimited."),
    "MTPU_QOS_MIN_SHARE": (
        "QOS.md",
        "Per-tenant backlog floor (queued items) below which the "
        "weighted share cap never bites."),
    "MTPU_QOS_QUANTUM": (
        "QOS.md",
        "Deficit-round-robin quantum: items granted per weight unit "
        "per scheduler round (bounds starvation to one round)."),
    "MTPU_QOS_RATE_BYTES": (
        "QOS.md",
        "Per-tenant payload bytes/second quota at plane admission "
        "(token bucket); over quota sheds 503 SlowDown "
        "(`tenant_quota`). `0` = unlimited."),
    "MTPU_QOS_RATE_OPS": (
        "QOS.md",
        "Per-tenant submissions/second quota at plane admission "
        "(token bucket); over quota sheds 503 SlowDown "
        "(`tenant_quota`). `0` = unlimited."),
    "MTPU_QOS_WEIGHTS": (
        "QOS.md",
        "Tenant weights, `key=weight,...` — key is "
        "`access_key/bucket`, `access_key`, or `*`; unlisted tenants "
        "weigh 1. Weights set DRR service ratio and backlog share."),
    "MTPU_REPL_JOURNAL": (
        "REPLICATION.md",
        "`1` (default) journals every replication intent durably "
        "before enqueue (replay on remount); `0` disables the journal "
        "— a crash may then lose queued-but-unattempted replication."),
    "MTPU_REPL_QUEUE_SIZE": (
        "REPLICATION.md",
        "Total in-memory replication queue capacity, split across "
        "workers. Overflow sheds (counted) — journaled intents are "
        "re-discovered by replay/resync."),
    "MTPU_REPL_RESYNC_BPS": (
        "REPLICATION.md",
        "Resync (MRF) bandwidth meter in bytes/sec for requeued "
        "object payloads; `0` (default) unmetered."),
    "MTPU_REPL_RESYNC_INTERVAL": (
        "REPLICATION.md",
        "Seconds between automatic resync passes over the journal "
        "backlog and PENDING/FAILED statuses; `0` disables the timer "
        "(scanner and admin triggers still work)."),
    "MTPU_REPL_RETRY_CAP": (
        "REPLICATION.md",
        "Upper bound in seconds on the per-task replication retry "
        "backoff (exponential, jittered)."),
    "MTPU_REPL_RETRY_INTERVAL": (
        "REPLICATION.md",
        "Base seconds for the per-task replication retry backoff "
        "(doubles per attempt up to MTPU_REPL_RETRY_CAP)."),
    "MTPU_REPL_RETRY_MAX": (
        "REPLICATION.md",
        "Bounded per-task replication attempts before the task parks "
        "in the persistent backlog (journal intent + FAILED status) "
        "for resync to requeue."),
    "MTPU_REPL_TEST_HOLD_S": (
        "REPLICATION.md",
        "Test-only: worker holds this many seconds between dequeue "
        "and the replication attempt — pins the ack-to-attempt crash "
        "window for the SIGKILL replay matrix."),
    "MTPU_REPL_WORKERS": (
        "REPLICATION.md",
        "Replication worker threads; tasks route to workers by key "
        "hash, so per-key PUT/DELETE order holds at any width."),
    "MTPU_REQUIRE_AESGCM": (
        "",
        "`1` turns the stdlib-AEAD fallback (cryptography wheel "
        "missing) into a boot failure instead of a warning — an image "
        "rebuild must never switch SSE providers unnoticed."),
    "MTPU_ROOT_PASSWORD": (
        "",
        "Root (admin) secret key; the `minioadmin` default is for "
        "development only."),
    "MTPU_ROOT_USER": (
        "",
        "Root (admin) access key."),
    "MTPU_SLO": (
        "SLO.md",
        "`0`/`false`/`off` disarms the on-node SLO plane (metric "
        "history ring + burn-rate evaluation); armed is the default."),
    "MTPU_SLO_BURN_THRESHOLD": (
        "SLO.md",
        "Burn-rate multiple that counts as a breach when BOTH windows "
        "exceed it (default 14.4 — the classic 2%-of-monthly-budget-"
        "in-an-hour page)."),
    "MTPU_SLO_COARSE_WINDOW_S": (
        "SLO.md",
        "Retention (seconds) of the 1-minute downsampled tier of the "
        "on-node metric history ring (default 86400)."),
    "MTPU_SLO_FAMILIES": (
        "SLO.md",
        "Comma-separated metric-family allowlist the SLO sampler "
        "snapshots each tick; empty = the built-in serving-path set."),
    "MTPU_SLO_FAST_WINDOW_S": (
        "SLO.md",
        "Fast burn-rate window (seconds, default 300): catches "
        "budget-torching incidents within minutes."),
    "MTPU_SLO_PERSIST_S": (
        "SLO.md",
        "Cadence (seconds, default 60) at which the coarse history "
        "tier is persisted through the sys-store blob lane so burn "
        "context survives a restart."),
    "MTPU_SLO_PERSIST_SAMPLES": (
        "SLO.md",
        "Cap on persisted coarse-tier entries (default 120) so the "
        "sys-store snapshot stays bounded."),
    "MTPU_SLO_RAW_WINDOW_S": (
        "SLO.md",
        "Retention (seconds) of the full-resolution tier of the "
        "on-node metric history ring (default 3900 — one slow window "
        "plus slack)."),
    "MTPU_SLO_SAMPLE_S": (
        "SLO.md",
        "SLO sampler cadence (seconds, default 5): how often the "
        "history ring snapshots the selected metric families."),
    "MTPU_SLO_SLOW_WINDOW_S": (
        "SLO.md",
        "Slow burn-rate window (seconds, default 3600): confirms the "
        "fast window is a sustained burn, not a blip."),
    "MTPU_SLO_SPOOL": (
        "SLO.md",
        "SLO state-spool shm base name, stamped into workers by the "
        "front-door supervisor; worker i publishes its burn state "
        "into `<base>slo<i>` so any worker can answer `/slo` for the "
        "pool."),
    "MTPU_USE_PALLAS": (
        "",
        "Force (`1`) or forbid (`0`) the Pallas TPU RS kernels on the "
        "serving/bench path; default auto-selects by backend (on for "
        "TPU)."),
    "MTPU_WAL_EAGER": (
        "METAPLANE.md",
        "`1` materializes each WAL batch before its futures resolve "
        "even in single-owner mode (multi-worker mode forces this for "
        "cross-process read-your-write)."),
    "MTPU_WAL_LAZY_MATERIALIZE": (
        "METAPLANE.md",
        "`1` never materializes between checkpoints — reads serve "
        "from the pending overlay; pins the fsynced-but-not-"
        "materialized state for the crash matrix, also a valid "
        "operating point for pure write bursts."),
    "MTPU_WAL_MAX_BATCH": (
        "METAPLANE.md",
        "Records per WAL group commit (writev bound; IOV_MAX "
        "headroom)."),
    "MTPU_WAL_MAX_BYTES": (
        "METAPLANE.md",
        "Checkpoint threshold: WAL size (bytes) that triggers "
        "materialize-all + sync + truncate."),
    "MTPU_WAL_MAX_PENDING": (
        "METAPLANE.md",
        "Materialization backlog bound (distinct pending keys) above "
        "which the committer drains even under sustained load."),
    "MTPU_WAL_QUEUE": (
        "METAPLANE.md",
        "Per-drive bounded WAL submission queue; full is "
        "backpressure (FaultyDisk into quorum), never unbounded RAM."),
    "MTPU_WAL_SEGMENT": (
        "FRONTDOOR.md",
        "Journal segment suffix (`journal.<seg>.wal`) the supervisor "
        "stamps per worker so each per-drive WAL file keeps exactly "
        "one writer process; empty = classic single-owner journal."),
    "MTPU_WAL_TEST_HOLD_FSYNC_S": (
        "METAPLANE.md",
        "Test-only: seconds the committer parks before each batch "
        "fsync so the crash matrix can land a SIGKILL between append "
        "and fsync."),
}


def registry_rows(doc_path: Path) -> list[dict]:
    """Parse the committed registry: [{name, line, text,
    undocumented}]. Missing file -> empty registry (every read is then
    undocumented, which is the bootstrapping failure mode we want)."""
    try:
        lines = doc_path.read_text().splitlines()
    except OSError:
        return []
    rows = []
    for i, line in enumerate(lines, 1):
        m = _ROW_RE.match(line.strip())
        if m:
            rows.append({"name": m.group(1), "line": i,
                         "text": line.strip(),
                         "undocumented": "UNDOCUMENTED" in line})
    return rows


def scan_knobs(index) -> dict[str, dict]:
    """Mechanical side of the registry: name -> {defaults: [..],
    files: [..], prefix_only: bool} from the pass-1 env-read scan.
    Dynamic prefix reads expand to every KNOB_DOCS name under the
    prefix (or surface the bare prefix when none is documented yet)."""
    exact: dict[str, dict] = {}
    prefixes: dict[str, set[str]] = {}
    for rel, read in index.env_reads():
        if read["prefix"]:
            prefixes.setdefault(read["name"], set()).add(rel)
            continue
        row = exact.setdefault(read["name"],
                               {"defaults": [], "files": set()})
        row["files"].add(rel)
        d = _clean_default(read["default"])
        if d is not None and d not in row["defaults"]:
            row["defaults"].append(d)
    for prefix, rels in prefixes.items():
        expansions = [n for n in KNOB_DOCS if n.startswith(prefix)]
        for name in expansions or [prefix + "*"]:
            row = exact.setdefault(name, {"defaults": [], "files": set()})
            row["files"] |= rels
    return {n: {"defaults": row["defaults"],
                "files": sorted(row["files"])}
            for n, row in sorted(exact.items())}


def _clean_default(src: str | None) -> str | None:
    """Render a static default expression: string/number constants come
    through bare, anything computed stays as the source snippet."""
    if src is None:
        return None
    s = src.strip()
    if len(s) >= 2 and s[0] == s[-1] and s[0] in "\"'":
        inner = s[1:-1]
        return inner if inner else '""'
    return s


def _short(rel: str) -> str:
    s = rel
    if s.startswith("minio_tpu/"):
        s = s[len("minio_tpu/"):]
    if s.endswith(".py"):
        s = s[:-3]
    return s


def render(index) -> str:
    """The full docs/KNOBS.md text (generated, do not hand-edit)."""
    knobs = scan_knobs(index)
    lines = [
        "# MTPU_* environment knobs (generated)",
        "",
        "Every `MTPU_*` environment variable read under `minio_tpu/`,",
        "found by the pass-1 analyzer scan and described by",
        "`tools/check/knobs.py` (`python -m tools.check --knobs` to",
        "regenerate — hand edits will be overwritten). Rule",
        "[MTPU010](ANALYSIS.md#mtpu010) gates both directions in",
        "tier-1: an undocumented read fails at the read site, a row no",
        "code reads any more fails as stale.",
        "",
        "Defaults are the static fallback at the read site (`—` means",
        "the knob has no default: unset disables the feature or the",
        "code requires it). \"Read in\" paths are relative to",
        "`minio_tpu/`.",
        "",
        f"**{len(knobs)} knobs.**",
        "",
        "| Knob | Default | Read in | Docs | Purpose |",
        "|---|---|---|---|---|",
    ]
    for name, row in knobs.items():
        doc = KNOB_DOCS.get(name)
        defaults = " / ".join(f"`{d}`" for d in row["defaults"]) or "—"
        files = ", ".join(f"`{_short(f)}`" for f in row["files"])
        if doc is None:
            link, purpose = "—", "**UNDOCUMENTED** — add a KNOB_DOCS " \
                "entry in tools/check/knobs.py"
        else:
            link_target, purpose = doc
            link = f"[{link_target.split('.md')[0].split('#')[0]}]" \
                   f"({link_target})" if link_target else "—"
        lines.append(f"| `{name}` | {defaults} | {files} | {link} "
                     f"| {purpose} |")
    lines += [
        "",
        "Related: [ANALYSIS.md](ANALYSIS.md) (the drift gate),",
        "[METAPLANE.md](METAPLANE.md), [DATAPLANE.md](DATAPLANE.md),",
        "[FRONTDOOR.md](FRONTDOOR.md), [HOTTIER.md](HOTTIER.md),",
        "[CHAOS.md](CHAOS.md), [RESILIENCE.md](RESILIENCE.md) (the",
        "subsystems the knobs tune).",
    ]
    return "\n".join(lines) + "\n"


def write_knobs(root: Path, out_path: Path) -> int:
    from tools.check.project import ProjectIndex

    index = ProjectIndex.build(Path(root))
    out_path.write_text(render(index))
    n = len(scan_knobs(index))
    print(f"wrote {out_path} ({n} knobs)")
    return 0
