"""The control: the plain reference put in the program's place.

    python benchmarks/reference_server.py <run_dir> reference <chips> <drive dirs...> --parity <m> --address <host:port>

Takes the launcher's arguments (`serve.py`), holds no device, and serves
PutObject and GetObject by the formats' definitions alone
(`reference.py`): a PUT writes the object's k+m `[digest][chunk]` shard
files to the drives the key names and answers with the body's MD5, a GET
reads the data shards back. `BENCH_CONTROL_BREAK` makes it break ONE
guarantee that the configurations state, the step that would tempt a later
change; the comparison has to come out not correct for each:

    quorum     an acknowledged PUT reaches one drive fewer than write quorum
    bitrot     frames carry BLAKE2b-256 digests in place of mxsum256
    bit-exact  a GET returns the object with one byte altered

With nothing broken it has to come out correct: the comparison then agrees
with a second implementation that shares no code with the program.
"""

from __future__ import annotations

import hashlib
import http.server
import json
import os
import signal
import sys
import threading
import uuid

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import reference  # noqa: E402

BLOCK = 1 << 20


def blake(chunk) -> bytes:
    return hashlib.blake2b(bytes(chunk), digest_size=32).digest()


class Store:
    def __init__(self, drives: list[str], parity: int, broken: str):
        self.drives, self.m = drives, parity
        self.k = len(drives) - parity
        self.broken = broken
        self.sizes: dict[str, int] = {}
        self._files: dict[bytes, list[bytes]] = {}
        self._mu = threading.Lock()

    def put(self, bucket: str, key: str, body: bytes) -> str:
        md5 = hashlib.md5(body)
        with self._mu:
            files = self._files.get(md5.digest())
        if files is None:
            files = reference.shard_files(
                body, self.k, self.m, BLOCK,
                digest=blake if self.broken == "bitrot" else reference.mxsum256)
            with self._mu:
                self._files[md5.digest()] = files
        n = self.k + self.m
        reach = n if self.broken != "quorum" else \
            reference.write_quorum(self.k, self.m) - 1
        data_dir = str(uuid.uuid4())
        for drive, shard in list(zip(
                self.drives, reference.shard_of_drive(bucket, key, n)))[:reach]:
            d = os.path.join(drive, bucket, key, data_dir)
            os.makedirs(d)
            with open(os.path.join(d, "part.1"), "wb") as f:
                f.write(files[shard])
        with self._mu:
            self.sizes[f"{bucket}/{key}"] = len(body)
        return md5.hexdigest()

    def get(self, bucket: str, key: str) -> bytes | None:
        with self._mu:
            size = self.sizes.get(f"{bucket}/{key}")
        if size is None:
            return None
        n = self.k + self.m
        rows: dict[int, bytes] = {}
        for drive, shard in zip(self.drives,
                                reference.shard_of_drive(bucket, key, n)):
            if shard >= self.k:
                continue
            d = os.path.join(drive, bucket, key)
            with open(os.path.join(d, os.listdir(d)[0], "part.1"), "rb") as f:
                rows[shard] = f.read()
        out = bytearray()
        pos = [0] * self.k
        left = size
        while left > 0:
            block = min(left, BLOCK)
            w = -(-block // self.k)
            piece = bytearray()
            for s in range(self.k):
                at = pos[s] + reference.DIGEST_LEN
                piece += rows[s][at:at + w]
                pos[s] = at + w
            out += piece[:block]
            left -= block
        if self.broken == "bit-exact" and out:
            out[len(out) // 2] ^= 1
        return bytes(out)


def handler(store: Store):
    class H(http.server.BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):
            pass

        def _send(self, code: int, body: bytes = b"", headers=()):
            self.send_response(code)
            for k, v in headers:
                self.send_header(k, v)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_PUT(self):
            n = int(self.headers.get("Content-Length", 0))
            body = self.rfile.read(n)
            parts = self.path.split("?")[0].lstrip("/").split("/", 1)
            if len(parts) == 1:
                return self._send(200)
            etag = store.put(parts[0], parts[1], body)
            self._send(200, headers=[("ETag", f'"{etag}"')])

        def do_GET(self):
            path = self.path.split("?")[0]
            if path.startswith("/minio/"):
                return self._send(200)  # live; an empty exposition
            bucket, key = path.lstrip("/").split("/", 1)
            try:
                body = store.get(bucket, key)
            except FileNotFoundError:
                # A data shard is not there, and this plain reference does
                # not reconstruct: the read fails, as the client sees it.
                return self._send(503)
            if body is None:
                return self._send(404)
            self._send(200, body)

    return H


def main(argv: list[str]) -> int:
    run_dir, _platform, chips = argv[1], argv[2], int(argv[3])
    rest = argv[4:]
    drives = rest[:rest.index("--parity")]
    parity = int(rest[rest.index("--parity") + 1])
    host, _, port = rest[rest.index("--address") + 1].rpartition(":")
    with open(os.path.join(run_dir, "device.json"), "w") as f:
        json.dump({"platform": "reference", "kind": "none", "count": chips}, f)
    store = Store(drives, parity, os.environ.get("BENCH_CONTROL_BREAK", ""))
    srv = http.server.ThreadingHTTPServer((host, int(port)), handler(store))
    srv.daemon_threads = True
    signal.signal(signal.SIGTERM, lambda *_: threading.Thread(
        target=srv.shutdown).start())
    srv.serve_forever()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
