"""One front-door worker: the full asyncio S3 server on a shared port.

Spawned by the supervisor (`python -m minio_tpu.frontdoor.worker`) with
its identity in the environment: `MTPU_FRONTDOOR_WORKER` (id),
`MTPU_FRONTDOOR_WORKERS` (pool width), `MTPU_WAL_SEGMENT` (per-worker
WAL journal segment) and optionally `MTPU_FRONTDOOR_RING` (shared lane
ring). Each worker binds its own `SO_REUSEPORT` listener on the shared
address — the kernel balances accepts — and:

- threads its identity into obs (`node` = `<addr>#w<id>` on every
  trace record, `X-Mtpu-Worker` on every response,
  `minio_tpu_frontdoor_requests_total{worker}`),
- worker 0 hosts the cross-process lane server and the auto-healer;
  the others route dataplane submissions over the ring,
- drains gracefully on SIGTERM: stop accepting, let in-flight requests
  finish inside the drain window, checkpoint the WAL segments, exit 0.
"""

from __future__ import annotations

import argparse
import asyncio
import os
import signal
import sys

from minio_tpu import frontdoor, obs

_REQS = obs.counter(
    "minio_tpu_frontdoor_requests_total",
    "Requests served, by front-door worker", ("worker",))
_UP = obs.gauge(
    "minio_tpu_frontdoor_worker_up",
    "1 while this front-door worker is serving", ("worker",))


def _local_drives(layer) -> list:
    """Every LocalDrive in the layer stack (for WAL checkpoint at
    drain)."""
    out, stack, seen = [], [layer], set()
    while stack:
        node = stack.pop()
        if node is None or id(node) in seen:
            continue
        seen.add(id(node))
        if hasattr(node, "close_wal"):
            out.append(node)
            continue
        for attr in ("pools", "sets", "drives"):
            kids = getattr(node, attr, None)
            if kids:
                stack.extend(kids)
        inner = getattr(node, "inner", None)
        if inner is not None:
            stack.append(inner)
    return out


def _arm_shared_lanes(wid: int, srv=None):
    """Wire this worker into the cross-process lane ring (worker 0
    serves it, the rest submit to it). Returns a stop callable.

    The hot-object tier rides the same ring: worker 0 owns the ONE
    device-resident tier (and registers its object layer as the
    tier's admit reader); siblings route hot GETs through OP_HOTGET
    (hottier.set_router) so every worker's hot traffic coalesces into
    shared residence and shared launches (docs/HOTTIER.md)."""
    from minio_tpu import dataplane, hottier
    from minio_tpu.frontdoor import laneserver, shm

    name = frontdoor.ring_name()
    if not (frontdoor.shared_lanes() and name and dataplane.enabled()):
        return lambda: None
    try:
        ring = shm.Ring.attach(name)
    except (OSError, ValueError):
        return lambda: None  # no ring, no coalescing: local plane serves
    if wid == 0:
        server = laneserver.LaneServer(ring, worker=wid)
        if hottier.enabled() and srv is not None:
            hottier.set_reader(
                lambda b, o, _l=srv.obj: _l.get_object(b, o))

        def stop():
            hottier.set_reader(None)
            server.stop()
            ring.close()

        return stop
    client = laneserver.LaneClient(ring, wid, frontdoor.worker_count())
    dataplane.set_router(lambda: client)
    if hottier.enabled():
        hot = laneserver.HotRingClient(client)
        hottier.set_router(lambda: hot)

    def stop():
        dataplane.set_router(None)
        hottier.set_router(None)
        client.close()

    return stop


def _arm_flight(wid: int):
    """Wire this worker's flight recorder into the cross-worker spool
    fabric: the worker owns one shm FlightSpool (`<base>w<id>`, base
    supervisor-stamped via MTPU_FLIGHT_SPOOL) that every finished
    timeline also lands in, and reads its siblings' spools on query —
    so the admin perf endpoint answers for the whole pool no matter
    which worker the kernel routed the query to. Returns a stop
    callable."""
    from minio_tpu.obs import flight

    flight.set_worker(wid)
    base = os.environ.get("MTPU_FLIGHT_SPOOL", "")
    if not (base and flight.armed()):
        return lambda: None
    from minio_tpu.frontdoor import shm

    try:
        spool = shm.FlightSpool.create(f"{base}w{wid}")
    except (OSError, ValueError):
        return lambda: None  # no spool: local recorder still works
    flight.attach_sink(spool.put)
    nworkers = frontdoor.worker_count()

    def read_siblings() -> list[dict]:
        # Attach-per-query (not cached): a sibling may have respawned
        # and recreated its spool since the last read.
        out = []
        for o in range(nworkers):
            if o == wid:
                continue
            try:
                sib = shm.FlightSpool.attach(f"{base}w{o}")
            except (OSError, ValueError):
                continue
            try:
                out.extend(sib.read_all())
            finally:
                sib.close()
        return out

    flight.set_sibling_reader(read_siblings)

    def stop():
        flight.attach_sink(None)
        flight.set_sibling_reader(None)
        spool.close()
        spool.unlink()

    return stop


def _arm_slo(wid: int):
    """Wire this worker's SLO engine into the cross-worker fabric,
    mirroring _arm_flight: one shm StateSpool mailbox (`<base>slo<id>`,
    base supervisor-stamped via MTPU_SLO_SPOOL) holds the worker's
    latest burn-rate evaluation, and the /slo endpoint merges siblings'
    mailboxes at query time (obs.slo.collect_local). Returns a stop
    callable."""
    from minio_tpu.obs import slo, tsdb

    slo.set_worker(wid)
    base = os.environ.get("MTPU_SLO_SPOOL", "")
    if not (base and tsdb.armed()):
        return lambda: None
    from minio_tpu.frontdoor import shm

    try:
        spool = shm.StateSpool.create(f"{base}slo{wid}")
    except (OSError, ValueError):
        return lambda: None  # no spool: local state still serves

    slo.attach_sink(spool.put)
    nworkers = frontdoor.worker_count()

    def read_siblings() -> list[dict]:
        # Attach-per-query, same respawn reasoning as _arm_flight.
        out = []
        for o in range(nworkers):
            if o == wid:
                continue
            try:
                sib = shm.StateSpool.attach(f"{base}slo{o}")
            except (OSError, ValueError):
                continue
            try:
                out.extend(sib.read_all())
            finally:
                sib.close()
        return out

    slo.set_sibling_reader(read_siblings)

    def stop():
        slo.attach_sink(None)
        slo.set_sibling_reader(None)
        spool.close()
        spool.unlink()

    return stop


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description="minio_tpu front-door worker")
    ap.add_argument("drives", nargs="+")
    ap.add_argument("--address", default="0.0.0.0:9000")
    ap.add_argument("--parity", type=int, default=None)
    ap.add_argument("--set-drives", type=int, default=None)
    ap.add_argument("--versioned", action="store_true")
    args = ap.parse_args(argv)

    from minio_tpu.utils import compile_cache, sysres

    compile_cache.enable()

    sysres.maximize_nofile()

    from minio_tpu.frontdoor import listener as fdl
    from minio_tpu.s3.server import build_server

    wid = frontdoor.worker_id() or 0
    wlabel = str(wid)
    host, _, port = args.address.rpartition(":")
    access = os.environ.get("MTPU_ROOT_USER", "minioadmin")
    secret = os.environ.get("MTPU_ROOT_PASSWORD", "minioadmin")
    srv = build_server(args.drives, access, secret,
                       versioned=args.versioned, parity=args.parity,
                       set_drive_count=args.set_drives,
                       server_addr=args.address)
    # Worker identity on every trace record this process emits.
    obs.set_default_node(f"{args.address}#w{wid}")
    srv.node_name = f"{args.address}#w{wid}"
    up = _UP.labels(worker=wlabel)
    up.set(1)
    reqs = _REQS.labels(worker=wlabel)

    async def _stamp_worker(request, response):
        response.headers.setdefault("X-Mtpu-Worker", wlabel)
        reqs.inc()

    srv.app.on_response_prepare.append(_stamp_worker)

    stop_lanes = _arm_shared_lanes(wid, srv)
    stop_flight = _arm_flight(wid)
    stop_slo = _arm_slo(wid)
    if wid == 0:
        # One healer per pool of workers: N auto-healers racing the
        # same sets would duplicate every heal fan-out.
        srv.start_auto_heal()

    control = frontdoor.control_path()
    routed = frontdoor.shard_policy() == "router" and control
    sock = None
    if not routed:
        sock = fdl.make_listener(host or "0.0.0.0", int(port or 9000),
                                 reuse_port=fdl.supports_reuseport())

    from aiohttp import web

    loop = asyncio.new_event_loop()
    asyncio.set_event_loop(loop)
    draining = asyncio.Event()

    async def serve():
        runner = web.AppRunner(srv.app)
        await runner.setup()
        receiver = site = None
        if routed:
            # Router shard: no listener here — adopt connection fds the
            # supervisor passes over the control socket.
            from minio_tpu.frontdoor.router import WorkerReceiver

            # Supervisor gone (drain OR death) = no new connections can
            # ever arrive: finish in-flight work and exit instead of
            # lingering as an orphan.
            receiver = WorkerReceiver(control, wid, loop, runner.server,
                                      on_eof=draining.set)
        else:
            site = web.SockSite(runner, sock,
                                shutdown_timeout=frontdoor.drain_timeout())
            await site.start()
        await draining.wait()
        # Stop accepting first (listener / control socket), then let
        # in-flight requests run out inside the drain window.
        if receiver is not None:
            receiver.stop()
        if site is not None:
            await site.stop()
        await runner.cleanup()

    def _drain(*_a) -> None:
        draining.set()

    loop.add_signal_handler(signal.SIGTERM, _drain)
    loop.add_signal_handler(signal.SIGINT, _drain)
    try:
        loop.run_until_complete(serve())
    finally:
        up.set(0)
        stop_lanes()
        stop_flight()
        stop_slo()
        # Checkpoint this worker's WAL segments so a clean drain leaves
        # nothing for the next mount's replay fold.
        from minio_tpu.logger import get_logger

        for d in _local_drives(srv.obj):
            try:
                d.close_wal()
            except Exception as e:  # noqa: BLE001 - drain is
                # best-effort; replay-on-mount converges whatever is left
                get_logger().warning(f"frontdoor drain: wal close: {e}")


if __name__ == "__main__":
    main()
    sys.exit(0)
