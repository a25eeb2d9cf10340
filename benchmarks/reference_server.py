"""The control: the plain reference put in the program's place.

    python benchmarks/reference_server.py <run_dir> reference <chips> <drive dirs...> --parity <m> --address <host:port>

Takes the launcher's arguments (`serve.py`), holds no device, and serves
PutObject, GetObject and the admin API's heal of a prefix by the formats'
definitions alone (`reference.py`): a PUT writes the object's k+m
`[digest][chunk]` shard files to the drives the key names and answers with
the body's MD5, a GET reads the data shards back. A drive whose root is not
a directory is a drive that is gone (a configuration's `state`): a PUT
leaves it out, and a GET that misses a data shard (its drive gone, or blank)
checks every frame of the shards that are left against its digest and
rebuilds the missing rows from any k of them (`reference.decode_rows`). A
heal does the same for every object under its prefix that a drive which is
there holds nothing of: it checks the frames of k shards that are left,
rebuilds the data rows, encodes the parity rows anew, and writes each absent
shard file, framed, to its drive. `BENCH_CONTROL_BREAK` makes it break ONE
guarantee that the configurations state, the step that would tempt a later
change; the comparison has to come out not correct for each:

    quorum     an acknowledged PUT reaches one drive fewer than write quorum
    bitrot     frames carry BLAKE2b-256 digests in place of mxsum256
    bit-exact  a GET returns the object with one byte altered
    rebuild    a GET sends the data rows it had to rebuild as zeros
    state      a GET that meets a lost drive makes its root anew: the state
               the configuration names no longer holds
    heal-zeros a heal writes the shards it rebuilt as rows of zeros
    heal-skip  a heal answers that it healed and writes nothing

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

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import reference  # noqa: E402

BLOCK = 1 << 20
JOURNAL = "meta.mp"   # where the program keeps an object's journal on a drive


def blake(chunk) -> bytes:
    return hashlib.blake2b(bytes(chunk), digest_size=32).digest()


class Store:
    def __init__(self, drives: list[str], parity: int, broken: str):
        self.drives, self.m = drives, parity
        self.k = len(drives) - parity
        self.broken = broken
        self.digest = blake if broken == "bitrot" else reference.mxsum256
        self.sizes: dict[str, int] = {}
        self._files: dict[bytes, list[bytes]] = {}
        self._mu = threading.Lock()

    def put(self, bucket: str, key: str, body: bytes) -> str:
        md5 = hashlib.md5(body)
        with self._mu:
            files = self._files.get(md5.digest())
        if files is None:
            files = reference.shard_files(
                body, self.k, self.m, BLOCK, digest=self.digest)
            with self._mu:
                self._files[md5.digest()] = files
        n = self.k + self.m
        reach = n if self.broken != "quorum" else \
            reference.write_quorum(self.k, self.m) - 1
        data_dir = str(uuid.uuid4())
        for drive, shard in list(zip(
                self.drives, reference.shard_of_drive(bucket, key, n)))[:reach]:
            if not os.path.isdir(drive):
                continue
            self._write_shard(os.path.join(drive, bucket, key), data_dir,
                              files[shard], len(body))
        with self._mu:
            self.sizes[f"{bucket}/{key}"] = len(body)
        return md5.hexdigest()

    @staticmethod
    def _write_shard(obj_dir: str, data_dir: str, shard_file: bytes,
                     size: int) -> None:
        """One drive's part of an object: its shard file, and a journal
        beside the data directory that says what the object is."""
        os.makedirs(os.path.join(obj_dir, data_dir))
        with open(os.path.join(obj_dir, data_dir, "part.1"), "wb") as f:
            f.write(shard_file)
        with open(os.path.join(obj_dir, JOURNAL), "w") as f:
            json.dump({"size": size, "data_dir": data_dir}, f)

    @staticmethod
    def _data_dir(obj_dir: str) -> str:
        return next(e.path for e in os.scandir(obj_dir) if e.is_dir())

    def get(self, bucket: str, key: str) -> bytes | None:
        with self._mu:
            size = self.sizes.get(f"{bucket}/{key}")
        if size is None:
            return None
        n = self.k + self.m
        held = []
        for drive, shard in zip(self.drives,
                                reference.shard_of_drive(bucket, key, n)):
            if os.path.isdir(os.path.join(drive, bucket, key)):
                held.append((drive, shard))   # not gone, and not blank
            elif self.broken == "state" and not os.path.isdir(drive):
                with self._mu:
                    if os.path.islink(drive):   # what `run.apply_state` left
                        os.unlink(drive)
                    os.makedirs(drive, exist_ok=True)
        whole = sum(shard < self.k for _, shard in held) == self.k
        files: dict[int, bytes] = {}
        for drive, shard in held:
            if whole and shard >= self.k:
                continue
            d = self._data_dir(os.path.join(drive, bucket, key))
            with open(os.path.join(d, "part.1"), "rb") as f:
                files[shard] = f.read()
        out = bytearray()
        pos = 0
        left = size
        while left > 0:
            block = min(left, BLOCK)
            w = -(-block // self.k)
            at = pos + reference.DIGEST_LEN
            if whole:
                rows = [files[s][at:at + w] for s in range(self.k)]
            else:
                rows = self._rebuilt(files, pos, w)
            out += b"".join(rows)[:block]
            pos = at + w
            left -= block
        if self.broken == "bit-exact" and out:
            out[len(out) // 2] ^= 1
        return bytes(out)

    def heal(self, bucket: str, prefix: str) -> list[dict]:
        """-> one item an object under the prefix, as the admin API's."""
        with self._mu:
            keys = sorted(k[len(bucket) + 1:] for k in self.sizes
                          if k.startswith(f"{bucket}/{prefix}"))
        return [self._heal_object(bucket, key) for key in keys]

    def _heal_object(self, bucket: str, key: str) -> dict:
        n = self.k + self.m
        with self._mu:
            size = self.sizes[f"{bucket}/{key}"]
        held, absent = {}, {}
        for drive, shard in zip(self.drives,
                                reference.shard_of_drive(bucket, key, n)):
            d = os.path.join(drive, bucket, key)
            if os.path.isdir(d):
                held[shard] = self._data_dir(d)
            elif os.path.isdir(drive):
                absent[shard] = d      # a drive that is there, and blank
        item = {"bucket": bucket, "object": key, "objectSize": size,
                "diskCount": n,
                "before": [{"endpoint": d, "state": "ok" if os.path.isdir(
                    os.path.join(d, bucket, key)) else "missing"}
                    for d in self.drives]}
        if absent and self.broken != "heal-skip":
            if len(held) < self.k:
                return {**item, "error": f"{len(held)} of {self.k} shards"}
            data_dir = os.path.basename(next(iter(held.values())))
            files = {}
            for shard in sorted(held)[:self.k]:
                with open(os.path.join(held[shard], "part.1"), "rb") as f:
                    files[shard] = f.read()
            out = {shard: bytearray() for shard in absent}
            pos, left = 0, size
            while left > 0:
                block = min(left, BLOCK)
                w = -(-block // self.k)
                data = np.stack([np.frombuffer(r, dtype=np.uint8)
                                 for r in self._rebuilt(files, pos, w)])
                rows = np.concatenate(
                    [data, reference.encode_block(data, self.m)])
                for shard, buf in out.items():
                    row = rows[shard]
                    if self.broken == "heal-zeros":
                        row = np.zeros_like(row)
                    buf += self.digest(row)
                    buf += row.tobytes()
                pos += reference.DIGEST_LEN + w
                left -= block
            for shard, d in absent.items():
                self._write_shard(d, data_dir, bytes(out[shard]), size)
        item["after"] = [{"endpoint": d, "state": "ok"} for d in self.drives
                         if os.path.isdir(d)]
        return item

    def _rebuilt(self, files: dict[int, bytes], pos: int, w: int) -> list:
        """One block's k data rows from the shards that are left, each
        frame checked against its digest before it is used."""
        at = pos + reference.DIGEST_LEN
        present = {}
        for s, raw in files.items():
            row = np.frombuffer(raw, dtype=np.uint8, count=w, offset=at)
            if self.digest(row) != raw[pos:at]:
                raise OSError(f"shard {s}: a frame fails its digest")
            present[s] = row
        data = reference.decode_rows(present, self.k, self.m)
        if self.broken == "rebuild":
            data[[s for s in range(self.k) if s not in present]] = 0
        return [r.tobytes() for r in data]


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

        def do_POST(self):
            n = int(self.headers.get("Content-Length", 0))
            self.rfile.read(n)
            path = self.path.split("?")[0]
            if not path.startswith("/minio/admin/v3/heal/"):
                return self._send(404)
            bucket, _, prefix = path[len("/minio/admin/v3/heal/"):].partition(
                "/")
            try:
                items = store.heal(bucket, prefix)
            except (OSError, ValueError) as e:
                return self._send(503, str(e).encode())
            self._send(200, json.dumps({"items": items}).encode())

        def do_GET(self):
            path = self.path.split("?")[0]
            if path.startswith("/minio/"):
                return self._send(200)  # live; an empty exposition
            bucket, key = path.lstrip("/").split("/", 1)
            try:
                body = store.get(bucket, key)
            except (OSError, ValueError):
                # Fewer than k shards are there, or one fails its digest:
                # the read fails, as the client sees it.
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
    for d in drives:   # the program makes its drives' roots at its boot too
        os.makedirs(d, exist_ok=True)
    store = Store(drives, parity, os.environ.get("BENCH_CONTROL_BREAK", ""))
    srv = http.server.ThreadingHTTPServer((host, int(port)), handler(store))
    srv.daemon_threads = True
    signal.signal(signal.SIGTERM, lambda *_: threading.Thread(
        target=srv.shutdown).start())
    srv.serve_forever()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
