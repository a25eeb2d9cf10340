"""One client worker process: a few closed-loop client threads.

    python benchmarks/worker.py <spec.json>

The spec (written by run.py) names the endpoint, the seed, this worker's
index, its thread count, the traffic mix and three paths: a `ready` file this
process creates when its bodies are prepared, a `go` file it waits for, and a
`stop` file that ends the loops; for a mix that heals, `blank_roots`: the
drives that came back blank, whose objects of a group this process takes
off again before that group's heal, into `blank_aside` (`blank.py`). Every request is recorded with
the client's own clock (time.monotonic(), one clock for all processes of a
machine) and written to `out` when the threads have ended. Imports nothing
of the program and never touches JAX.
"""

from __future__ import annotations

import http.client
import itertools
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import blank  # noqa: E402
import traffic  # noqa: E402
from s3http import S3Http  # noqa: E402


# After a 503 only, by minio-go's schedule (warp's client: MaxRetry 10,
# DefaultRetryUnit 200 ms doubled on each retry, DefaultRetryCap 1 s; its
# jitter left out): ten sends over 7.4 s, which outlasts the second or more
# that the program's drive probes take after the host has stood still.
RETRIES = 9
BACKOFF_S = 0.2
BACKOFF_CAP_S = 1.0
# What `mc admin heal -r bucket/prefix` sends (madmin HealOpts; scanMode 1
# is the normal scan).
HEAL_OPTS = json.dumps({"dryRun": False, "scanMode": 1}).encode()
VERBS = ("PUT", "GET", "HEAL")


def healed(reply_body: bytes, keys: list[str], n_blank: int) -> bool:
    """The heal's reply calls every one of these objects healed: one item
    each, no `error`, `n_blank` drives not `ok` before it (it met the state
    the configuration names, and had that much to do) and every drive `ok`
    after it."""
    try:
        items = json.loads(reply_body)["items"]
        said = {}
        for it in items:
            if it.get("object"):
                said.setdefault(it["object"], []).append(
                    not it.get("error") and bool(it.get("after"))
                    and all(d["state"] == "ok" for d in it["after"])
                    and sum(d["state"] != "ok"
                            for d in it["before"]) == n_blank)
    except (ValueError, KeyError, TypeError, AttributeError):
        return False
    return set(said) == set(keys) and all(v == [True] for v in said.values())


def one_request(c: S3Http, bucket: str, op: traffic.Op,
                body: traffic.Body | None, group: list[str] = (),
                blank_roots: list[str] = ()):
    """-> (reply, good, wrong): `wrong` is an answer that says the wrong
    thing, `good` a complete 2xx. A HEAL is given its group's keys and the
    roots of the drives that came back blank: its answer is wrong where it
    calls an object healed of which a shard file is not on such a drive
    (looked for after the reply, outside the request's times)."""
    if op.verb == "PUT":
        r = c.request("PUT", f"/{bucket}/{op.key}", body=body.data,
                      body_sha256=body.sha256)
        return r, r.ok, r.ok and r.headers.get(
            "ETag", "").strip('"') != body.md5
    if op.verb == "GET":
        r = c.request("GET", f"/{bucket}/{op.key}")
        return (r, r.status == 200 and r.size == op.size,
                r.status == 200 and not r.matches(body.data))
    if op.verb == "HEAL":
        r = c.request("POST", f"/minio/admin/v3/heal/{bucket}/{op.key}",
                      body=HEAL_OPTS)
        good = r.status == 200 and healed(r.body, group, len(blank_roots))
        return r, good, good and blank.shard_files_absent(
            blank_roots, bucket, group) > 0
    raise ValueError(f"verb {op.verb!r} is not generated: this worker "
                     f"sends {', '.join(VERBS)}")


def client_loop(spec: dict, thread: int, pool: traffic.BodyPool,
                records: list, progress_fd: int) -> None:
    mix = spec["traffic"]
    gen = traffic.OpStream(mix, spec["seed"], spec["worker"], thread)
    c = S3Http(spec["host"], spec["port"], spec["access"], spec["secret"])
    bucket = spec["bucket"]
    stop = spec["stop"]
    blank_roots = spec.get("blank_roots", [])
    aside_names = (f"w{spec['worker']}t{thread}.{n}"
                   for n in itertools.count())
    try:
        while not os.path.exists(stop):
            op = gen.next()
            group: list[str] = []
            if op.verb == "HEAL":
                # the drives are blank again for this group, then its heal
                if not blank_roots:
                    raise ValueError("a mix with HEALs needs a configuration "
                                     "whose state names drives_blank")
                body = None
                group = [o.key for o in gen.groups[op.body_index]]
                blank.blank_objects(blank_roots, bucket, group,
                                    spec["blank_aside"], aside_names)
            else:
                body = pool.get(op.size, op.body_index)
            try:
                # A 503 is the server shedding load and asks for a retry:
                # S3 clients (warp's minio-go among them) back off and send
                # again, and the operation's time runs from its first send.
                for attempt in range(RETRIES + 1):
                    r, good, wrong = one_request(c, bucket, op, body, group,
                                                 blank_roots)
                    if attempt == 0:
                        t_send = r.t_send
                    if r.status != 503:
                        break
                    print(f"worker {spec['worker']}.{thread}: {op.verb} "
                          f"{op.key} -> 503 {r.body[:300]!r}",
                          file=sys.stderr)
                    if attempt < RETRIES:
                        time.sleep(min(BACKOFF_CAP_S,
                                       BACKOFF_S * 2 ** attempt))
                records.append((op.verb, op.key, op.size, op.body_index,
                                t_send, r.t_first, r.t_last, r.status,
                                bool(good and not wrong), bool(wrong),
                                attempt))
                if (not r.ok and r.status != 503) or (
                        op.verb == "HEAL" and r.ok and not good):
                    print(f"worker {spec['worker']}.{thread}: {op.verb} "
                          f"{op.key} -> {r.status} {r.body[:300]!r}",
                          file=sys.stderr)
            except (OSError, http.client.HTTPException) as e:
                c.close()
                now = time.monotonic()
                records.append((op.verb, op.key, op.size, op.body_index,
                                now, now, now, 0, False, False, 0))
                print(f"worker {spec['worker']}.{thread}: {op.verb} "
                      f"{op.key}: {type(e).__name__}: {e}", file=sys.stderr)
            # This thread's count of answered requests, for the parent's
            # warm-up: eight bytes in a slot of its own, no lock.
            os.pwrite(progress_fd, len(records).to_bytes(8, "little"),
                      8 * thread)
    finally:
        c.close()


def main(argv: list[str]) -> int:
    with open(argv[1]) as f:
        spec = json.load(f)
    pool = traffic.BodyPool(spec["traffic"], spec["seed"])
    per_thread: list[list] = [[] for _ in range(spec["threads"])]
    progress_fd = os.open(spec["progress"], os.O_RDWR | os.O_CREAT, 0o600)
    threads = [threading.Thread(target=client_loop, name=f"client-{t}",
                                args=(spec, t, pool, per_thread[t],
                                      progress_fd))
               for t in range(spec["threads"])]
    open(spec["ready"], "w").close()
    deadline = time.monotonic() + 600
    while not os.path.exists(spec["go"]):
        if os.path.exists(spec["stop"]) or time.monotonic() > deadline:
            return 1
        time.sleep(0.01)
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    os.close(progress_fd)
    tmp = spec["out"] + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"worker": spec["worker"], "threads": per_thread}, f)
    os.replace(tmp, spec["out"])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
