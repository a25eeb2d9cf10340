"""The streamed GET's drain (`s3/server.py` `_get_object`): one executor
hop hands the event loop every chunk the stream has up to a byte budget,
and says in the same hop whether the stream ended."""

import asyncio
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

_RUNNERS: dict = {}  # base URL -> (loop, AppRunner), for a second listener


def _serve(srv):
    port = free_port()
    loop = asyncio.new_event_loop()
    started = threading.Event()

    def run():
        asyncio.set_event_loop(loop)

        async def start():
            runner = web.AppRunner(srv.app)
            await runner.setup()
            await web.TCPSite(runner, "127.0.0.1", port).start()
            _RUNNERS[f"http://127.0.0.1:{port}"] = (loop, runner)
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


def _vectored() -> int:
    return s3server._VECTORED_GROUPS.value


@pytest.fixture
def wire(monkeypatch):
    """Every call that reaches a server connection's transport, as
    (transport kind, method, [bytes of each buffer], the transport):
    "tcp" is the selector socket transport (write = one socket.send,
    writelines = one sendmsg of the buffers), "tls" the transport a TLS
    listener hands aiohttp. A count of calls, not a timing. (The client
    is `requests`: no asyncio transport of its own.)"""
    from asyncio import selector_events, sslproto

    calls = []

    def record(cls, kind):
        write, writelines = cls.write, cls.writelines

        def rec_write(self, data):
            calls.append((kind, "write", [len(data)], self))
            return write(self, data)

        def rec_writelines(self, bufs):
            bufs = list(bufs)
            calls.append((kind, "writelines", [len(b) for b in bufs], self))
            return writelines(self, bufs)

        monkeypatch.setattr(cls, "write", rec_write)
        monkeypatch.setattr(cls, "writelines", rec_writelines)

    record(selector_events._SelectorSocketTransport, "tcp")
    record(sslproto._SSLProtocolTransport, "tls")
    return calls


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
#          most hops, chunks: exact, or None for "at least one a hop",
#          vectored writes: exact, or None for "one a hop that had chunks").
# Over plain HTTP with Content-Length set every kind of stream goes out
# vectored; what falls back is in FALLBACK, further down.
SERVED = {
    # The cell's object: one read batch, 10 blocks x 12 data chunks, sent
    # as three writelines of 48 + 48 + 24 views.
    "whole-10MiB": (10 * MIB, {}, {}, None, 4, 120, 3),
    # 400 KiB across the first block boundary (over _GET_DRAIN_LIMIT, so
    # it streams): the tail chunks of block 0, the head chunks of block 1.
    "range-two-blocks": (10 * MIB, {}, {},
                         (MIB - 200 * 1024, MIB + 200 * 1024 - 1), 1, None, 1),
    # Two read batches (16 + 2 blocks) behind the read-ahead thread; the
    # last group straddles nothing: 4 + 4 + 4 + 4 MiB, then the rest.
    "multi-batch": (17 * MIB + 12345, {}, {}, None, 5, 18 * 12, 5),
    # Transformed streams: fresh buffers, not views of resident rows.
    "sse-c": (3 * MIB + 17, _ssec(SSE_KEY), _ssec(SSE_KEY), None, 1, None, 1),
    "compressed": (3 * MIB + 5, {}, {}, None, 1, None, 1),
}


@pytest.mark.parametrize("case", sorted(SERVED))
def test_served_get_is_byte_exact_in_a_few_hops(ec, case, wire, monkeypatch):
    cl, srv, _root = ec
    size, put_h, get_h, rng, most_hops, chunks, writes = SERVED[case]
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
    # What aiohttp's access log and keep-alive read once the body is out.
    lengths = []
    eof = web.StreamResponse.write_eof

    async def write_eof(self, *a):
        writer = self._payload_writer  # gone after the first write_eof
        await eof(self, *a)
        if writer is not None:
            lengths.append((self.body_length, writer))

    monkeypatch.setattr(web.StreamResponse, "write_eof", write_eof)
    hops0, chunks0 = _counters()
    vec0 = _vectored()
    del wire[:]
    r = cl.get(key, headers=headers)
    hops1, chunks1 = _counters()
    assert r.status_code == (206 if rng else 200), r.text
    assert int(r.headers["Content-Length"]) == len(want)
    assert r.content == want, "bytes or their order differ"
    hops, got = hops1 - hops0, chunks1 - chunks0
    # The wire: the headers (a StreamResponse's leave at prepare), then one
    # writelines (one sendmsg) for each hop that brought chunks, and no
    # write of a chunk.
    sent = [(kind, lens) for tr, kind, lens, _t in wire if tr == "tcp"]
    assert [kind for kind, _l in sent] == ["write"] + ["writelines"] * writes
    (head,) = sent[0][1]
    assert head < 2048 and sum(sum(ls) for _k, ls in sent) == head + len(want)
    assert sum(len(ls) for _k, ls in sent[1:]) == got, "a chunk cut or joined"
    assert _vectored() - vec0 == hops >= writes
    # The client has the last byte before the handler is back from
    # write_eof: wait for it.
    deadline = time.monotonic() + 10
    while not lengths and time.monotonic() < deadline:
        time.sleep(0.01)
    (body_length, writer), = lengths
    assert body_length == head + len(want)
    assert (writer.length, writer.output_size) == (0, head + len(want))
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


def _raw_get_head(cl, path: str) -> tuple[socket.socket, bytes]:
    """A signed GET on a bare socket, read as far as the first 256 KiB:
    -> (the socket, what came). A small receive buffer, so the server
    cannot park the body in the kernel and be done before the test acts."""
    url = urllib.parse.urlparse(cl.endpoint)
    signed = cl._sign("GET", path, {}, {}, b"")
    head = f"GET {path} HTTP/1.1\r\n" + "".join(
        f"{k}: {v}\r\n" for k, v in signed.items()) + "\r\n"
    s = socket.socket()
    s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 64 * 1024)
    s.connect((url.hostname, url.port))
    s.sendall(head.encode())
    got = b""
    while len(got) < 256 * 1024:
        got += s.recv(65536)
    assert got.startswith(b"HTTP/1.1 200")
    return s, got


# ---------------- connections that cannot take a vectored write ----------------

class _JoiningTransport(asyncio.Transport):
    """A connection whose transport has no vectored writelines (another
    event loop's, or asyncio's before 3.12): writelines is
    asyncio.WriteTransport's, which joins the buffers and calls write."""

    def __init__(self, inner, calls):
        super().__init__()
        self._inner, self._calls = inner, calls

    def write(self, data):
        self._calls.append(len(data))
        self._inner.write(data)


for _name in ("is_closing", "close", "abort", "get_extra_info",
              "pause_reading", "resume_reading", "is_reading",
              "set_write_buffer_limits", "get_write_buffer_size",
              "get_write_buffer_limits", "set_protocol", "get_protocol",
              "write_eof", "can_write_eof"):
    setattr(_JoiningTransport, _name, (
        lambda n: lambda self, *a, **kw: getattr(self._inner, n)(*a, **kw)
    )(_name))


@pytest.fixture(scope="module")
def tls(ec, tmp_path_factory):
    """A TLS listener on the same app: a client that verifies the
    self-signed pair (its SAN is `localhost`)."""
    from minio_tpu.utils.certs import CertManager, self_signed

    cl, _srv, _root = ec
    certs = str(tmp_path_factory.mktemp("drain-certs"))
    self_signed(certs)
    loop, runner = _RUNNERS[cl.endpoint]
    port = free_port()
    asyncio.run_coroutine_threadsafe(
        web.TCPSite(runner, "127.0.0.1", port,
                    ssl_context=CertManager(certs).ssl_context).start(),
        loop).result(30)
    tcl = SigV4Client(f"https://localhost:{port}", ACCESS, SECRET)
    tcl.session.verify = os.path.join(certs, "public.crt")
    tcl.session.trust_env = False  # a CA bundle in the environment wins else
    return tcl


# A 5 MiB object is 2 hops of 48 + 12 chunks at EC 12+4; a block's twelfth
# chunk is 8 bytes short (12 x 87382 = 1 MiB + 8).
FALLBACK = ("chunked", "compressing", "joining-transport", "tls",
            "wrapped-write", "unpaused-writelines", "unknown-aiohttp")
FALLBACK_CHUNKS = ([87382] * 11 + [87374]) * 5


@pytest.mark.parametrize("case", FALLBACK)
def test_group_falls_back_to_a_write_a_chunk(ec, tls, case, wire, monkeypatch):
    """Where one writelines would not be one sendmsg of the bytes as they
    are (a chunked or compressing payload writer, a transport whose
    writelines joins, TLS), where `StreamResponse.write` is no longer
    aiohttp's own (a wrapper that wants to see every chunk), on a runtime
    whose writelines never pauses the protocol (CPython before 3.12.9 and
    3.13.2: aiohttp's `SKIP_WRITELINES`), or with a payload writer that
    lacks what the vectored write leans on (another aiohttp), every chunk
    goes through `resp.write` as before: same bytes, no vectored group
    counted, no error."""
    cl, _srv, _root = ec
    body = _body(5 * MIB, 23)
    body = body[:MIB] + bytes(MIB) + body[2 * MIB:]  # some of it compresses
    assert cl.put("/drain/fallback", data=body).status_code == 200
    get, stub_calls = cl, []
    if case in ("chunked", "compressing"):
        prepare = web.StreamResponse.prepare

        async def prepare_other(self, request):
            if request.path == "/drain/fallback" and not self.prepared:
                if case == "chunked":
                    del self.headers["Content-Length"]
                    self.enable_chunked_encoding()
                else:
                    from aiohttp.web_response import ContentCoding
                    self.enable_compression(force=ContentCoding.gzip)
            return await prepare(self, request)

        monkeypatch.setattr(web.StreamResponse, "prepare", prepare_other)
    elif case == "wrapped-write":
        import functools

        write = web.StreamResponse.write

        @functools.wraps(write)
        async def seen_write(self, data):
            stub_calls.append(len(data))
            return await write(self, data)

        monkeypatch.setattr(web.StreamResponse, "write", seen_write)
    elif case == "joining-transport":
        from aiohttp.web_protocol import RequestHandler

        made = RequestHandler.connection_made
        monkeypatch.setattr(
            RequestHandler, "connection_made",
            lambda self, tr: made(self, _JoiningTransport(tr, stub_calls)))
        get = SigV4Client(cl.endpoint, ACCESS, SECRET)  # a new connection
    elif case == "tls":
        get = tls
    elif case == "unpaused-writelines":
        monkeypatch.setattr(s3server, "_SKIP_WRITELINES", True)
    elif case == "unknown-aiohttp":
        from aiohttp.http_writer import StreamWriter

        monkeypatch.delattr(StreamWriter, "_writelines")
    (hops0, chunks0), vec0 = _counters(), _vectored()
    del wire[:]
    r = get.get("/drain/fallback")
    hops = _counters()[0] - hops0
    chunks = _counters()[1] - chunks0
    assert r.status_code == 200 and r.content == body
    assert (hops, chunks) == (2, 60)
    assert _vectored() == vec0, "counted a vectored group"
    kind = "tls" if case == "tls" else "tcp"
    calls = [(m, lens) for tr, m, lens, _t in wire if tr == kind]
    if case == "chunked":
        assert r.headers.get("Transfer-Encoding") == "chunked"
        # aiohttp frames each chunk itself: (size line, chunk, CRLF).
        framed = [lens for m, lens in calls if m == "writelines"]
        assert [ls[1] for ls in framed] == FALLBACK_CHUNKS
    elif case == "compressing":
        assert r.raw.headers.get("Content-Encoding") == "gzip"
        assert sum(sum(lens) for _m, lens in calls) < len(body)
    elif case == "joining-transport":
        # headers, then a write a chunk; nothing reached the real
        # transport but through them.
        assert stub_calls[1:] == FALLBACK_CHUNKS and stub_calls[0] < 2048
        assert [m for m, _l in calls] == ["write"] * 61
    elif case == "wrapped-write":
        assert stub_calls == FALLBACK_CHUNKS, "the wrapper missed chunks"
    else:
        assert [lens for _m, lens in calls][1:] == [[n] for n in FALLBACK_CHUNKS]


class _WireStub:
    """The least of a protocol and its transport that aiohttp's payload
    writer touches."""
    _paused = False

    def __init__(self, closing=False):
        self.transport, self.closing, self.sent = self, closing, []

    def is_closing(self):
        return self.closing

    def write(self, data):
        self.sent.append(data)

    def writelines(self, bufs):
        self.sent.extend(bufs)


# name -> (Content-Length or None, the groups' chunk sizes)
# (3000-byte chunks: aiohttp joins a writelines of under 2048 bytes.)
BOOKKEEPING = {
    "exact": (9000, [[3000, 3000], [3000]]),
    "no-length": (None, [[3000, 3000], [3000]]),
    # A stream that yields more than Content-Length never overruns it.
    "overrun-mid-chunk": (7500, [[3000, 3000], [3000], [3000]]),
    "overrun-at-a-chunk": (6000, [[3000, 3000], [3000]]),
    "empty-group": (3000, [[3000], []]),
    "empty-chunks": (3000, [[0, 3000, 0]]),
}


@pytest.mark.parametrize("case", sorted(BOOKKEEPING))
def test_write_group_keeps_the_payload_writers_books(case):
    """`_write_group` against `await writer.write(chunk)` a chunk, on twin
    aiohttp payload writers: the same bytes on the wire, the same
    `length`, `output_size` (what `resp.body_length`, the access log and
    keep-alive read); and the views go to the transport as they are."""
    from aiohttp.http_writer import StreamWriter

    length, groups = BOOKKEEPING[case]
    data = [[memoryview(bytes([7 * g + i + 1]) * n) for i, n in enumerate(grp)]
            for g, grp in enumerate(groups)]

    async def run():
        loop = asyncio.get_running_loop()
        out = []
        for vectored in (True, False):
            stub = _WireStub()
            w = StreamWriter(stub, loop)
            w.length = length
            for grp in data:
                if vectored:
                    await s3server._write_group(w, grp)
                else:
                    for c in grp:
                        await w.write(c)
            out.append((b"".join(stub.sent), w.length, w.output_size,
                        stub.sent))
        return out

    (wire_v, len_v, size_v, sent), (wire_c, len_c, size_c, _s) = \
        asyncio.run(run())
    everything = b"".join(bytes(c) for grp in data for c in grp)
    assert wire_v == wire_c == (everything if length is None
                                else everything[:length])
    assert (len_v, size_v) == (len_c, size_c)
    whole = [c for grp in data for c in grp]
    assert all(any(s is c for c in whole) or len(s) < len(whole[0])
               for s in sent), "a chunk was copied on its way"


def test_write_group_sends_held_headers_first_and_casts_shaped_views():
    """A writer that still holds the headers (aiohttp >= 3.13 buffers them
    until `send_headers` or the first write) sends them before the group;
    a view whose items are wider than a byte counts by its bytes."""
    from aiohttp.http_writer import StreamWriter
    from multidict import CIMultiDict

    async def run():
        stub = _WireStub()
        w = StreamWriter(stub, asyncio.get_running_loop())
        await w.write_headers("HTTP/1.1 200 OK",
                              CIMultiDict({"Content-Length": "16"}))
        assert stub.sent == []
        w.length = 16
        words = memoryview(struct.pack("<4I", 1, 2, 3, 4)).cast("I")
        await s3server._write_group(w, [words])
        return stub.sent, w

    sent, w = asyncio.run(run())
    assert sent[0].startswith(b"HTTP/1.1 200 OK\r\n") and len(sent) == 2
    assert bytes(sent[1]) == struct.pack("<4I", 1, 2, 3, 4)
    assert (w.length, w.output_size) == (0, len(sent[0]) + 16)


def test_write_group_refuses_a_closing_transport():
    from aiohttp import ClientConnectionResetError
    from aiohttp.http_writer import StreamWriter

    async def run():
        w = StreamWriter(_WireStub(closing=True), asyncio.get_running_loop())
        with pytest.raises(ClientConnectionResetError):
            await s3server._write_group(w, [b"x" * 10])

    asyncio.run(run())


def test_slow_reader_holds_one_group(ec, wire):
    """A client that stops reading: the transport's buffer holds what is
    left of one group, the handler waits in the writer's drain and pulls
    no further group meanwhile; then the client reads on, byte-exact."""
    cl, _srv, _root = ec
    body = _body(24 * MIB, 29)
    assert cl.put("/drain/slow", data=body).status_code == 200
    hops0, _ = _counters()
    del wire[:]
    s, got = _raw_get_head(cl, "/drain/slow")
    held = []
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline and len(held) < 2:
        time.sleep(0.5)
        tr = next((t for k, m, _l, t in wire
                   if (k, m) == ("tcp", "writelines")), None)
        now = (_counters()[0] - hops0,
               tr.get_write_buffer_size() if tr else -1)
        # Two samples half a second apart that agree: it stands still.
        held = held + [now] if not held or held[-1] == now else [now]
    assert len(held) == 2, "the drain never came to rest"
    hops, buffered = held[-1]
    assert 0 < buffered <= BUDGET + 87382, held
    assert hops < len(body) // BUDGET, "groups were pulled with nobody reading"
    s.settimeout(30)
    want = len(got.split(b"\r\n\r\n", 1)[0]) + 4 + len(body)
    while len(got) < want:
        more = s.recv(1 << 20)
        assert more, "the server closed early"
        got += more
    s.close()
    assert got.split(b"\r\n\r\n", 1)[1] == body


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


def test_get_vectored_send_pct_reads_the_scrape(ec):
    """100 x vectored groups over hops: 100.0 for a GET over plain HTTP;
    nothing, and no error, without the families; 0.0 for a program that
    has the hops and not the vectored counter (the parent's: it sends no
    group vectored, and that is what the number says)."""
    import json

    scrape = _bench_module("scrape")
    with open(os.path.join(os.path.dirname(scrape.__file__), "layer_metrics",
                           "get_vectored_send_pct.json")) as f:
        spec = json.load(f)
    cl, _srv, _root = ec
    body = _body(10 * MIB, 31)
    assert cl.put("/drain/scraped-v", data=body).status_code == 200
    before = scrape.parse(cl.get("/minio/v2/metrics/node").text)
    assert cl.get("/drain/scraped-v").content == body
    after = scrape.parse(cl.get("/minio/v2/metrics/node").text)
    assert scrape.delta_ratio(before, after, spec, {}) == 100.0

    def without(samples, prefix):
        return {k: v for k, v in samples.items()
                if not k[0].startswith(prefix)}

    gone = without(without(after, "minio_tpu_get_drain_"),
                   "minio_tpu_get_vectored_")
    assert len(gone) == len(after) - 3
    assert scrape.delta_ratio(gone, gone, spec, {}) is None
    parent = (without(before, "minio_tpu_get_vectored_"),
              without(after, "minio_tpu_get_vectored_"))
    assert scrape.delta_ratio(*parent, spec, {}) == 0.0


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
    hops0, _ = _counters()
    s, got = _raw_get_head(cl, "/drain/gone")
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
