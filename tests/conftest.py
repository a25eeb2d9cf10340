"""Test configuration: force an 8-device virtual CPU mesh.

Tests never require real TPU hardware; multi-chip sharding is validated on a
virtual CPU mesh. The suite runs on the CPU whatever JAX_PLATFORMS the shell
exports: the jax.config override below wins over the env var because
backends initialize lazily, after conftest runs. The persistent compile
cache stays off here (utils/compile_cache.py is for the entry points).
"""

import faulthandler
import os
import signal

# A future hang (a deadlock or an unreleased injected stall) must dump
# every thread's stack instead of timing out silently: dump on fatal
# signals AND on the harness's SIGTERM (`timeout` still SIGKILLs after
# its grace period, so termination is never lost).
faulthandler.enable()
try:
    faulthandler.register(signal.SIGTERM, chain=True)
except (AttributeError, ValueError, OSError):
    pass  # non-main thread / platform without register()

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# ---------------------------------------------------------------------------
# Runtime sanitizers (minio_tpu/utils/sanitize.py, docs/ANALYSIS.md):
# arm the lock-order tracker BEFORE any minio_tpu module is imported so
# module-level and instance locks are created through the patched
# factories. MTPU_SANITIZE=0 disarms both sanitizers (e.g. when
# bisecting whether the tracker itself perturbs a timing-sensitive
# repro).
# ---------------------------------------------------------------------------

from minio_tpu.utils import sanitize  # noqa: E402

SANITIZE = os.environ.get("MTPU_SANITIZE", "1") != "0"
if SANITIZE:
    sanitize.install()

# The boto3 conformance tier only exists where boto3 is installed; in
# images without it the module is not collected at all rather than
# reported as a permanent skip — the EXECUTING third-party tier in this
# image is tests/test_thirdparty_conformance.py (vendored boto 2.49 +
# curl --aws-sigv4, the mint role).
import importlib.util  # noqa: E402

collect_ignore = []
if importlib.util.find_spec("boto3") is None:
    collect_ignore.append("test_boto3_conformance.py")

# ---------------------------------------------------------------------------
# Shared in-process S3 server fixtures (SURVEY.md §4 tier 3). Modules that
# need a different topology define their own overriding fixtures.
# ---------------------------------------------------------------------------

import socket  # noqa: E402
import threading  # noqa: E402

import pytest  # noqa: E402

S3_ACCESS, S3_SECRET = "testadmin", "testsecret123"

# Isolate KMS key persistence per test session (LocalKMS would otherwise
# write runtime-created keys to ~/.mtpu/kms-keys, colliding across runs).
import tempfile  # noqa: E402

os.environ.setdefault(
    "MTPU_KMS_KEY_FILE",
    os.path.join(tempfile.mkdtemp(prefix="mtpu-test-kms-"), "keys"))


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture(scope="session")
def server(tmp_path_factory):
    import asyncio

    from aiohttp import web

    from minio_tpu.s3.server import build_server

    root = tmp_path_factory.mktemp("shared-drives")
    srv = build_server([str(root / f"d{i}") for i in range(4)], S3_ACCESS,
                       S3_SECRET, versioned=False)
    port = free_port()
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

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert started.wait(30)
    yield f"http://127.0.0.1:{port}"
    loop.call_soon_threadsafe(loop.stop)


@pytest.fixture(scope="session")
def client(server):
    from tests.s3client import SigV4Client

    return SigV4Client(server, S3_ACCESS, S3_SECRET)


@pytest.fixture(scope="session")
def bucket(client):
    r = client.put("/apitest")
    assert r.status_code in (200, 409), r.text
    return "apitest"


@pytest.fixture(scope="session")
def crash_cluster(tmp_path_factory):
    """The OS-process 3-node cluster (tests/crash_cluster.py), booted
    lazily once per session and shared by the crash-recovery and
    composed-chaos tiers — process boot (3× jax import) is the dominant
    cost, the storm itself is cheap."""
    from tests import crash_cluster as cc

    work = tmp_path_factory.mktemp("crashwork")
    cl = cc.Cluster(work)
    for i in range(cc.N_NODES):
        cl.start(i)
    for i in range(cc.N_NODES):
        cl.wait_healthy(i)
    yield cl
    cl.stop_all()


@pytest.fixture(autouse=True)
def _chaos_fault_hygiene():
    """Composed-chaos teardown hygiene: an aborted chaos test must not
    leak faults into the next test. After every test, if ANY fault
    plane is still armed (network plane installed, a NaughtyDisk
    program — HANG sentinels included — or a forced-open breaker),
    release it all. A clean test pays two module-attribute reads."""
    yield
    from minio_tpu import chaos

    if chaos.anything_armed():
        chaos.clear_all()


@pytest.fixture(autouse=True)
def _thread_leak_guard():
    """Thread-leak sanitizer: no non-daemon, non-exempt thread born
    during a test may survive it (sanitize.ALLOWED_THREAD_PREFIXES
    exempts pools owned by session-lived engine objects)."""
    if not SANITIZE:
        yield
        return
    before = sanitize.thread_snapshot()
    yield
    leaks = sanitize.leaked_threads(before)
    assert not leaks, (
        "test leaked non-daemon threads (missing close()/join()/"
        f"shutdown path): {[t.name for t in leaks]}")


@pytest.fixture(scope="session", autouse=True)
def _lock_order_guard():
    """Deadlock sanitizer: the lock acquisition graph recorded across
    the whole session must stay a DAG — a cycle is a latent ABBA
    deadlock even if this run never interleaved into it."""
    yield
    if not SANITIZE:
        return
    cycles = sanitize.check_lock_cycles()
    assert not cycles, (
        "lock-order cycles recorded (latent ABBA deadlock): "
        + "; ".join(" -> ".join(c) for c in cycles))


def pytest_configure(config):
    # Tier-1 runs `-m 'not slow'` (ROADMAP.md): long chaos soaks and
    # multi-minute stress tiers opt out of the window with this marker.
    config.addinivalue_line(
        "markers", "slow: long-running soak/stress tests excluded from "
        "the tier-1 window")
    config.addinivalue_line(
        "markers", "chaos: composed multi-fault storm tests "
        "(docs/CHAOS.md); deselect with -m 'not chaos' when iterating "
        "on unrelated code")


def pytest_report_header(config):
    # Every chaos plane (network jitter, drive fault placement, crash
    # timing, workload streams) derives from this one integer — a chaos
    # failure message names it, this header makes the active value
    # visible up front.
    from minio_tpu import chaos

    return (f"chaos seed: MTPU_CHAOS_SEED="
            f"{chaos.master_seed()} (one integer replays the storm)")
