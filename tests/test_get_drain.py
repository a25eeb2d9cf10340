"""The streamed GET's drain (`s3/server.py` `_get_object`): one executor
hop hands the event loop every chunk the stream has up to a byte budget,
and says in the same hop whether the stream ended."""

import base64
import hashlib
import os
import socket
import struct
import threading
import time
import urllib.parse

import pytest
from aiohttp import web

from minio_tpu.s3 import server as s3server
from tests.conftest import free_port
from tests.s3client import SigV4Client

ACCESS, SECRET = "drainroot", "drainroot-secret"
MIB = 1 << 20
BUDGET = s3server.S3Server._GET_GROUP_BYTES


def _drain(chunks, budget):
    """Every group of one stream, as the handler's loop would pull them."""
    it, groups = iter(chunks), []
    while True:
        got, done = s3server._drain_group(it, budget)
        groups.append((got, done))
        if done:
            return groups


# ---------------- (a) the group function over plain iterators ----------------

@pytest.mark.parametrize("n,size,budget,hops", [
    (0, 0, 4 * MIB, 1),            # an empty stream is one hop
    (1, 300_000, 4 * MIB, 1),
    (120, 87382, 4 * MIB, 3),      # a 10 MiB object at EC 12+4: 48 + 48 + 24
    (120, 87382, 3 * MIB, 4),
    (13, 87382, 4 * MIB, 1),       # a 1 MiB object (13 hops before)
    (10, 100, 1, 11),              # budget under a chunk: the old loop, one
                                   # chunk a hop and one for the end
    (8, 1 << 20, 4 * MIB, 3),      # the budget fills on the last chunk: the
                                   # end needs a hop of its own
    (5, 5 * MIB, 4 * MIB, 6),      # chunks over the budget are never cut
])
def test_drain_group_hops_bytes_order(n, size, budget, hops):
    chunks = [bytes([i % 251]) * size for i in range(n)]
    groups = _drain((memoryview(c) for c in chunks), budget)
    assert len(groups) == hops
    assert [d for _g, d in groups] == [False] * (hops - 1) + [True]
    flat = [c for g, _d in groups for c in g]
    # The very objects the stream yielded, in order: nothing joined or cut.
    assert len(flat) == n and all(
        isinstance(c, memoryview) and c.obj is src
        for c, src in zip(flat, chunks))
    assert b"".join(flat) == b"".join(chunks)
    for g, done in groups:
        held = sum(len(c) for c in g)
        if not done:
            # Full, and not over by more than the chunk that filled it.
            assert held >= budget and held - len(g[-1]) < budget


def test_drain_group_leaves_the_rest_in_the_stream():
    """A group stops pulling once it is full: what the stream has not
    yielded stays unread (the read-ahead of the next batch is the object
    layer's, not the drain's)."""
    pulled = []

    def stream():
        for i in range(10):
            pulled.append(i)
            yield b"x" * 100

    it = stream()
    got, done = s3server._drain_group(it, 250)
    assert (len(got), done, pulled) == (3, False, [0, 1, 2])
    got, done = s3server._drain_group(it, 10_000)
    assert (len(got), done, len(pulled)) == (7, True, 10)


def test_drain_group_relays_the_streams_error():
    def stream():
        yield b"a"
        raise OSError("shard went away")

    with pytest.raises(OSError):
        s3server._drain_group(stream(), 100)


# ---------------- served GETs ----------------

def _serve(srv):
    import asyncio

    port = free_port()
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


def _body(n: int, salt: int) -> bytes:
    """n bytes that differ from block to block and chunk to chunk, so a
    swapped or repeated chunk cannot compare equal."""
    words = (n + 7) // 8
    return b"".join(struct.pack("<Q", (i * 0x9E3779B97F4A7C15 + salt)
                                & 0xFFFFFFFFFFFFFFFF)
                    for i in range(words))[:n]


@pytest.fixture(scope="module")
def ec(tmp_path_factory):
    """The GET cell's deployment at the cell's geometry: one set of 16
    drives, EC 12+4, 1 MiB blocks, `mxsum256` given explicitly (the CPU
    default, sip256, takes the native C++ lane, whose stream is cut
    otherwise), read batches of 16 blocks."""
    root = tmp_path_factory.mktemp("drain-drives")
    srv = s3server.build_server([str(root / f"d{i}") for i in range(16)],
                                ACCESS, SECRET, parity=4)
    for es in srv.obj.pools[0].sets:
        es.bitrot_algorithm = "mxsum256"
        assert es.batch_blocks == 16
    base, loop = _serve(srv)
    cl = SigV4Client(base, ACCESS, SECRET)
    assert cl.put("/drain").status_code == 200
    yield cl, srv, str(root)
    loop.call_soon_threadsafe(loop.stop)


def _counters() -> tuple[int, int]:
    return s3server._DRAIN_HOPS.value, s3server._DRAIN_CHUNKS.value


def _ssec(key: bytes) -> dict:
    return {
        "x-amz-server-side-encryption-customer-algorithm": "AES256",
        "x-amz-server-side-encryption-customer-key":
            base64.b64encode(key).decode(),
        "x-amz-server-side-encryption-customer-key-md5":
            base64.b64encode(hashlib.md5(key).digest()).decode(),
    }


SSE_KEY = bytes(range(32))

# name -> (object bytes, PUT headers, GET headers, first and last byte sent,
#          most hops, chunks: exact, or None for "at least one a hop")
SERVED = {
    # The cell's object: one read batch, 10 blocks x 12 data chunks.
    "whole-10MiB": (10 * MIB, {}, {}, None, 4, 120),
    # 400 KiB across the first block boundary (over _GET_DRAIN_LIMIT, so
    # it streams): the tail chunks of block 0, the head chunks of block 1.
    "range-two-blocks": (10 * MIB, {}, {},
                         (MIB - 200 * 1024, MIB + 200 * 1024 - 1), 1, None),
    # Two read batches (16 + 2 blocks) behind the read-ahead thread; the
    # last group straddles nothing: 4 + 4 + 4 + 4 MiB, then the rest.
    "multi-batch": (17 * MIB + 12345, {}, {}, None, 5, 18 * 12),
    # Transformed streams: fresh buffers, not views of resident rows.
    "sse-c": (3 * MIB + 17, _ssec(SSE_KEY), _ssec(SSE_KEY), None, 1, None),
    "compressed": (3 * MIB + 5, {}, {}, None, 1, None),
}


@pytest.mark.parametrize("case", sorted(SERVED))
def test_served_get_is_byte_exact_in_a_few_hops(ec, case):
    cl, srv, _root = ec
    size, put_h, get_h, rng, most_hops, chunks = SERVED[case]
    body = _body(size, salt=len(case))
    key = f"/drain/{case}" + (".log" if case == "compressed" else "")
    if case == "compressed":
        # Half of every 8-byte word is zero: it compresses.
        body = bytes(b if i % 8 < 4 else 0 for i, b in enumerate(body[:4096])
                     ) * (size // 4096 + 1)
        body = body[:size]
        srv.config.set_kv("compression", {"enable": "on",
                                          "extensions": ".log"})
    try:
        r = cl.put(key, data=body, headers=put_h)
        assert r.status_code == 200, r.text
    finally:
        srv.config.set_kv("compression", {"enable": "off"})
    want, headers = body, dict(get_h)
    if rng is not None:
        headers["Range"] = f"bytes={rng[0]}-{rng[1]}"
        want = body[rng[0]:rng[1] + 1]
    hops0, chunks0 = _counters()
    r = cl.get(key, headers=headers)
    hops1, chunks1 = _counters()
    assert r.status_code == (206 if rng else 200), r.text
    assert int(r.headers["Content-Length"]) == len(want)
    assert r.content == want, "bytes or their order differ"
    hops, got = hops1 - hops0, chunks1 - chunks0
    assert 1 <= hops <= most_hops, (hops, got)
    assert hops <= -(-len(want) // BUDGET) + 1
    if chunks is not None:
        assert got == chunks, (hops, got)
    else:
        assert got >= hops
    if case == "compressed":
        stored = sum(len(c) for c in srv.obj.get_object(
            "drain", key.split("/", 2)[2])[1])
        assert stored < size // 2, "the object was not stored compressed"


def _bench_module(name: str):
    """benchmarks/<name>.py by path (tier-1 does not collect the
    benchmark's own tests)."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", name + ".py")
    spec = importlib.util.spec_from_file_location("bench_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_get_chunks_per_hop_reads_the_scrape(ec):
    """The benchmark's metric file over two scrapes of the node endpoint:
    40 chunks a hop for the cell's object; nothing, and no error, where
    the families are absent (the parent's program)."""
    import json

    scrape = _bench_module("scrape")
    with open(os.path.join(os.path.dirname(scrape.__file__), "layer_metrics",
                           "get_chunks_per_hop.json")) as f:
        spec = json.load(f)
    cl, _srv, _root = ec
    body = _body(10 * MIB, 7)
    assert cl.put("/drain/scraped", data=body).status_code == 200
    before = scrape.parse(cl.get("/minio/v2/metrics/node").text)
    assert cl.get("/drain/scraped").content == body
    after = scrape.parse(cl.get("/minio/v2/metrics/node").text)
    assert scrape.delta_ratio(before, after, spec, {}) == 40.0
    gone = {k: v for k, v in after.items()
            if not k[0].startswith("minio_tpu_get_drain_")}
    assert len(gone) == len(after) - 2
    assert scrape.delta_ratio(gone, gone, spec, {}) is None


def test_small_get_still_drains_inside_the_open_hop(ec):
    """At or under _GET_DRAIN_LIMIT nothing reaches the streaming loop."""
    cl, _srv, _root = ec
    body = _body(s3server.S3Server._GET_DRAIN_LIMIT, 3)
    assert cl.put("/drain/small", data=body).status_code == 200
    before = _counters()
    assert cl.get("/drain/small").content == body
    assert _counters() == before


# ---------------- (d) the bandwidth throttle ----------------

def test_throttle_is_charged_the_objects_bytes(ec, monkeypatch):
    cl, srv, _root = ec
    body = _body(10 * MIB, 11)
    assert cl.put("/drain/throttled", data=body).status_code == 200
    charged = []
    real = srv.bw_throttle.delay

    def delay(bucket, n, direction="tx"):
        charged.append((bucket, n, direction))
        return real(bucket, n, direction)

    monkeypatch.setattr(srv.bw_throttle, "delay", delay)
    hops0, _ = _counters()
    assert cl.get("/drain/throttled").content == body
    hops = _counters()[0] - hops0
    tx = [n for b, n, d in charged if (b, d) == ("drain", "tx")]
    assert sum(tx) == len(body)
    assert len(tx) == hops, "charged once a group"


def test_throttle_holds_its_rate_over_groups(ec):
    """A configured limit still paces a streamed body: 1 MiB past the
    one-second burst at 2 MiB/s takes about half a second."""
    cl, srv, _root = ec
    body = _body(3 * MIB, 13)
    assert cl.put("/drain/paced", data=body).status_code == 200
    srv.config.set_kv("bandwidth", {"drain": str(2 * MIB)})
    try:
        t = time.monotonic()
        assert cl.get("/drain/paced").content == body
        took = time.monotonic() - t
    finally:
        srv.config.set_kv("bandwidth", {"drain": "0"})
    assert 0.4 <= took < 10, took


# ---------------- (c) a client that goes away mid-body ----------------

def _shard_fds(root: str) -> list[str]:
    """Open files of this process under the object's directories."""
    out = []
    for fd in os.listdir("/proc/self/fd"):
        try:
            target = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:
            continue
        if target.startswith(root) and "/drain/gone/" in target:
            out.append(target)
    return out


def _readahead_threads() -> list[threading.Thread]:
    return [t for t in threading.enumerate() if t.name == "shard-readahead"]


def test_disconnect_mid_body_runs_the_streams_cleanup(ec):
    """The client reads the head of a three-batch object and resets the
    connection: the stream's generator is closed (its read-ahead thread
    joined, its shard readers closed), and the server serves on."""
    cl, srv, root = ec
    body = _body(40 * MIB, 17)
    assert cl.put("/drain/gone", data=body).status_code == 200
    assert not _readahead_threads()
    url = urllib.parse.urlparse(cl.endpoint)
    signed = cl._sign("GET", "/drain/gone", {}, {}, b"")
    head = "GET /drain/gone HTTP/1.1\r\n" + "".join(
        f"{k}: {v}\r\n" for k, v in signed.items()) + "\r\n"
    s = socket.socket()
    # A small receive buffer, so the server cannot park the body in the
    # kernel and finish before the reset.
    s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 64 * 1024)
    s.connect((url.hostname, url.port))
    hops0, _ = _counters()
    s.sendall(head.encode())
    got = b""
    while len(got) < 256 * 1024:
        got += s.recv(65536)
    assert got.startswith(b"HTTP/1.1 200")
    sent = got.split(b"\r\n\r\n", 1)[1]
    assert body.startswith(sent)
    mid, mid_fds = _readahead_threads(), _shard_fds(root)
    # SO_LINGER 0: close() sends a reset, the server's next write fails.
    s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER, struct.pack("ii", 1, 0))
    s.close()
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline and (
            _readahead_threads() or _shard_fds(root)):
        time.sleep(0.05)
    assert mid and mid_fds, "no read-ahead thread or no open shard file " \
        "mid-body: the test proves nothing"
    assert not _readahead_threads(), "read-ahead thread left behind"
    assert not _shard_fds(root), _shard_fds(root)[:4]
    # It stopped early: fewer hops than the whole body would take.
    assert _counters()[0] - hops0 < len(body) // BUDGET
    assert cl.get("/drain/whole-after").status_code == 404
    r = cl.get("/drain/gone", headers={"Range": "bytes=0-1048575"})
    assert r.content == body[:MIB]
