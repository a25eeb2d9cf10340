"""SigV4 over http.client: the benchmark's own S3 client.

A copy in spirit of tests/s3client.py (the signing is the same AWS
algorithm), rebuilt on http.client so that one request's three instants are
the client's own: the send of the first byte, the first body byte, the last
body byte. Imports nothing of the program.
"""

from __future__ import annotations

import datetime
import hashlib
import hmac
import http.client
import time
import urllib.parse

EMPTY_SHA256 = hashlib.sha256(b"").hexdigest()


def _quote(s: str, safe: str = "-._~") -> str:
    return urllib.parse.quote(str(s), safe=safe)


class Reply:
    """One answered request. Times are time.monotonic() of this process."""

    __slots__ = ("status", "headers", "first", "rest", "t_send", "t_first",
                 "t_last")

    def __init__(self, status, headers, first, rest, t_send, t_first,
                 t_last):
        self.status = status
        self.headers = headers
        self.first, self.rest = first, rest
        self.t_send = t_send
        self.t_first = t_first
        self.t_last = t_last

    @property
    def body(self) -> bytes:
        return self.first + self.rest

    @property
    def size(self) -> int:
        return len(self.first) + len(self.rest)

    def matches(self, want: bytes) -> bool:
        """Whole body equal to `want`, with no copy of a large body."""
        return (self.size == len(want) and self.first == want[:1]
                and memoryview(want)[1:] == self.rest)

    @property
    def ok(self) -> bool:
        return 200 <= self.status < 300


class S3Http:
    """One keep-alive connection, one thread."""

    def __init__(self, host: str, port: int, access: str, secret: str,
                 region: str = "us-east-1", timeout: float = 300.0):
        self.host, self.port = host, port
        self.hostport = f"{host}:{port}"
        self.ak, self.sk, self.region = access, secret, region
        self.timeout = timeout
        self._conn: http.client.HTTPConnection | None = None
        self._key_day = ""
        self._key = b""

    def close(self) -> None:
        if self._conn is not None:
            self._conn.close()
            self._conn = None

    def _signing_key(self, day: str) -> bytes:
        if day != self._key_day:
            key = ("AWS4" + self.sk).encode()
            for part in (day, self.region, "s3", "aws4_request"):
                key = hmac.new(key, part.encode(), hashlib.sha256).digest()
            self._key_day, self._key = day, key
        return self._key

    def _signed_headers(self, method: str, path: str, query: dict,
                        headers: dict, payload_sha256: str) -> dict:
        now = datetime.datetime.now(datetime.timezone.utc)
        amz_date = now.strftime("%Y%m%dT%H%M%SZ")
        day = amz_date[:8]
        h = {k.lower(): v for k, v in headers.items()}
        h.update({"host": self.hostport, "x-amz-date": amz_date,
                  "x-amz-content-sha256": payload_sha256})
        signed = sorted(h)
        cq = "&".join(f"{_quote(k)}={_quote(v)}"
                      for k, v in sorted(query.items()))
        canonical = "\n".join([
            method, _quote(path, "/-._~"), cq,
            "".join(f"{k}:{' '.join(str(h[k]).split())}\n" for k in signed),
            ";".join(signed), payload_sha256])
        scope = f"{day}/{self.region}/s3/aws4_request"
        sts = "\n".join(["AWS4-HMAC-SHA256", amz_date, scope,
                         hashlib.sha256(canonical.encode()).hexdigest()])
        sig = hmac.new(self._signing_key(day), sts.encode(),
                       hashlib.sha256).hexdigest()
        h["authorization"] = (
            f"AWS4-HMAC-SHA256 Credential={self.ak}/{scope}, "
            f"SignedHeaders={';'.join(signed)}, Signature={sig}")
        return h

    def request(self, method: str, path: str, query: dict | None = None,
                body: bytes = b"", body_sha256: str | None = None,
                headers: dict | None = None) -> Reply:
        """Send one request and read the whole answer. `body_sha256` is the
        payload's SHA-256 where the caller has it already (a prepared
        body), so hashing is not in the timed path."""
        query = query or {}
        if body_sha256 is None:
            body_sha256 = (hashlib.sha256(body).hexdigest() if body
                           else EMPTY_SHA256)
        h = self._signed_headers(method, path, query, headers or {},
                                 body_sha256)
        url = _quote(path, "/-._~")
        if query:
            url += "?" + "&".join(f"{_quote(k)}={_quote(v)}"
                                  for k, v in sorted(query.items()))
        h["content-length"] = str(len(body))
        for attempt in (0, 1):
            if self._conn is None:
                self._conn = http.client.HTTPConnection(
                    self.host, self.port, timeout=self.timeout)
            conn = self._conn
            t_send = time.monotonic()
            try:
                conn.putrequest(method, url, skip_host=True,
                                skip_accept_encoding=True)
                for k, v in h.items():
                    conn.putheader(k, v)
                conn.endheaders(body if body else None)
                resp = conn.getresponse()
            except (ConnectionError, http.client.BadStatusLine,
                    http.client.CannotSendRequest):
                # A keep-alive connection the server closed while idle:
                # one retry on a new connection, timed from its own send.
                self.close()
                if attempt:
                    raise
                continue
            first = resp.read(1)
            t_first = time.monotonic()
            rest = resp.read()
            t_last = time.monotonic()
            if resp.will_close:
                self.close()
            return Reply(resp.status, dict(resp.getheaders()),
                         first, rest, t_send, t_first, t_last)
        raise AssertionError("unreachable")
