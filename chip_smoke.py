#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path runs on the chip.

    python chip_smoke.py            # one TPU chip, every phase
    python chip_smoke.py --chips 4  # the mesh path on a four-chip host, only

One process holds the chip from the first phase to the last: the S3 server
is `build_server` (what `python -m minio_tpu.s3.server` calls) on a thread
of this process, driven over real HTTP with SigV4.

Phases, one JSON line each on stdout, in order:

  device    jax.devices(); anything but a TPU stops the run, non-zero
  native    native/*.so rebuilt from the sources present, and loaded
  kernels   fused encode/reconstruct/verify vs ops/gf.py and mxsum.digest_np,
            Pallas compiled (tpu_custom_call in the lowered text)
  serve     PUT 64 x 10 KiB, 32 x 128 KiB, 24 x 10 MiB and one 4 x 64 MiB
            multipart upload on one 16-drive EC 12+4 set; GET all, one
            ranged GET, LIST. (10 KiB objects are inlined in the journal and
            10 MiB objects encode in 1 MiB blocks, wider than the lanes'
            gate: the 128 KiB class is what rides the coalescing lanes.)
  degraded  shard files removed on 4 of 16 drives (2 data + 2 parity) for
            two 128 KiB objects, two 10 MiB objects and the multipart
            object; GET bit-exact through the device reconstruct; the
            background heal those GETs queue is awaited and checked; the
            same files removed again and healed through the admin API;
            every healed shard file verified frame by frame, both times
  metrics   /minio/v2/metrics/node says it was the device: tpu:* kernel
            observations for encode/verify/reconstruct, none under cpu:* or
            the host lane, objects stored with mxsum256, lane launches > 0

Timings on those lines are observations of one run, not metrics.

The LAST line of stdout is exactly
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
and nothing follows it: fd 1 is pointed at stderr before anything else runs
(so no library, banner or child can write to stdout), the phases write
their lines to the saved descriptor, and the process leaves through
os._exit right after the last line. A phase that fails ends the run with a
non-zero code and no last line.
"""

from __future__ import annotations

import argparse
import dataclasses
import faulthandler
import hashlib
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
import traceback
import xml.etree.ElementTree as ET
import zlib
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))
ACCESS, SECRET = "smokeadmin", "smokesecret123"
BUCKET = "smoke"
S3NS = "{http://s3.amazonaws.com/doc/2006-03-01/}"
DEADLINE_S = 1150  # the contract allows 1200 s; past this, dump and die


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# stdout discipline
# ---------------------------------------------------------------------------


def final_line(device: dict) -> dict:
    """The contract's last line, and nothing else in it."""
    return {"ok": True,
            "device": {"platform": device["platform"],
                       "kind": device["kind"],
                       "count": device["count"]}}


def claim_stdout() -> int:
    """Keep the real stdout for this script's own lines and point fd 1 at
    stderr, so nothing else (a banner, a C library, a child that inherits
    fd 1) can put a byte on stdout before or after the last line."""
    sys.stdout.flush()
    out_fd = os.dup(1)
    os.dup2(2, 1)
    sys.stdout = sys.stderr
    return out_fd


def emit(out_fd: int, obj: dict) -> None:
    data = (json.dumps(obj) + "\n").encode()
    while data:
        data = data[os.write(out_fd, data):]


def need(ok, msg: str) -> None:
    """A check of the smoke: failing it ends the run, non-zero."""
    if not ok:
        raise SystemExit(f"chip_smoke: {msg}")


def finish(phases, out_fd: int) -> None:
    """Run (name, fn) phases in order — each fn(state) returns the dict
    that becomes its stdout line — then the last line from
    state["device"], then leave. Never returns. The first phase error ends
    the run: traceback on stderr, non-zero exit, no last line."""
    state: dict = {}
    try:
        for name, fn in phases:
            t0 = time.perf_counter()
            log(f"--- phase {name}")
            line = fn(state) or {}
            emit(out_fd, {"phase": name, **line,
                          "seconds": round(time.perf_counter() - t0, 3)})
        last = final_line(state["device"])
    except BaseException:  # noqa: BLE001 - report it, then exit non-zero
        traceback.print_exc()
        sys.stderr.flush()
        os._exit(1)
    sys.stderr.flush()
    emit(out_fd, last)
    # Leaked pool workers can hold the interpreter open, and an atexit hook
    # could print: leave without running either.
    os._exit(0)


# ---------------------------------------------------------------------------
# workload
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Plan:
    drives: int
    parity: int
    small: tuple[int, int]    # (count, bytes): inlined in the journal
    lane: tuple[int, int]     # chunk width inside the lanes' gates
    medium: tuple[int, int]   # full 1 MiB blocks: per-object launches
    parts: tuple[int, int]    # one multipart upload: (parts, bytes per part)
    degrade: int              # lane AND medium objects to damage each
    clients: int


FULL = Plan(drives=16, parity=4, small=(64, 10 << 10), lane=(32, 128 << 10),
            medium=(24, 10 << 20), parts=(4, 64 << 20), degrade=2, clients=8)
MP_KEY = "mp/big"
MIB = 1 << 20


def payload(seed: int, name: str, size: int) -> bytes:
    """Object bytes from (--seed, name): data is never stored, only made."""
    import numpy as np

    return np.random.default_rng(
        [seed, zlib.crc32(name.encode())]).bytes(size)


def body_digest(*chunks: bytes) -> str:
    h = hashlib.blake2b(digest_size=16)
    for c in chunks:
        h.update(c)
    return h.hexdigest()


def object_names(plan: Plan) -> tuple[list[str], list[str], list[str]]:
    return ([f"s/{i:04d}" for i in range(plan.small[0])],
            [f"l/{i:04d}" for i in range(plan.lane[0])],
            [f"m/{i:04d}" for i in range(plan.medium[0])])


# ---------------------------------------------------------------------------
# phases: device, native, kernels
# ---------------------------------------------------------------------------


def _entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def phase_device(state: dict, chips: int = 1) -> dict:
    """First touch of JAX. The platform is whatever the environment names
    or JAX finds — never defaulted here; anything but a TPU stops the run."""
    import jax

    from minio_tpu.utils import compile_cache

    cache = state["cache_dir"] = compile_cache.enable()
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    need(dev["platform"] == "tpu", f"no TPU: jax.devices() -> {dev}")
    need(dev["count"] == chips,
         f"wanted {chips} chip(s), host has {dev['count']}")
    state["device"] = dev
    return {**dev, "jax": jax.__version__, "compile_cache": cache,
            "cache_entries_at_start": _entries(cache)}


def phase_native(state: dict) -> dict:
    """Rebuild native/*.so from the sources in this checkout (a library
    copied in from another machine was built -march=native for ITS CPU),
    then require that it loads: WAL crc32c, O_DIRECT and the shard file
    engine are on the served path."""
    r = subprocess.run(["make", "-B", "-C", os.path.join(HERE, "native")],
                       stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                       timeout=300)
    need(r.returncode == 0, "native build failed:\n"
         + r.stdout.decode(errors="replace")[-2000:])
    from minio_tpu import native
    from minio_tpu.native import lib

    need(native.available(), "native library built but not loaded")
    need(lib.crc32c(b"123456789") == 0xE3069283,
         "native crc32c gives a wrong answer")
    return {"loaded": True, "pyext": lib.pyext() is not None}


def _twice(fn) -> tuple:
    """-> (result, first_s, second_s): the first call holds trace + compile
    (or cache load) + run, the second the run alone."""
    import jax

    times = []
    for _ in range(2):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return out, round(times[0], 3), round(times[1], 4)


def phase_kernels(state: dict, seed: int = 0) -> dict:
    """The fused launches at the widths the 12+4 and 8+4 geometries use,
    against the table-lookup references of ops/gf.py and the numpy digest
    of ops/mxsum.py, with Pallas compiled in."""
    import jax.numpy as jnp
    import numpy as np

    from minio_tpu.ops import fused, gf, mxsum, rs_pallas

    rng = np.random.default_rng([seed, 1])
    shapes = []
    need(rs_pallas.use_pallas(), "Pallas route not selected on the chip")

    def has_kernel(observed_jit, *a, **kw) -> None:
        text = observed_jit.__wrapped__.lower(*a, **kw).as_text()
        need("tpu_custom_call" in text, "no tpu_custom_call in the lowered "
             f"{observed_jit.__name__}: Pallas did not compile in")

    # 12+4 full blocks stage at ceil(1 MiB / 12) = 87382, which the Pallas
    # dispatch pads in-graph to 87552: the launch the server makes.
    for b, k, m, s in ((16, 12, 4, 87382), (16, 8, 4, 131072)):
        n, at = k + m, f"at [{b},{k},{s}]"
        data = rng.integers(0, 256, (b, k, s), dtype=np.uint8)
        ddev = jnp.asarray(data)
        # Lengths as an array, as the server passes them: the same
        # compiled program its first PUT then finds.
        lens = jnp.full((b,), s, dtype=jnp.int32)
        has_kernel(fused.encode_with_digests, ddev, k=k, m=m,
                   chunk_lens=lens)
        (parity, digs), first, second = _twice(
            lambda: fused.encode_with_digests(ddev, k, m, lens))
        parity, digs = np.asarray(parity), np.asarray(digs)
        shards = np.concatenate([data, parity], axis=1)
        for bi in range(b):
            need(np.array_equal(parity[bi], gf.encode_ref(data[bi], m)),
                 f"parity != encode_ref {at} block {bi}")
        for bi in (0, b - 1):
            for si in range(n):
                need(digs[bi, si].tobytes()
                     == mxsum.digest_np(shards[bi, si]),
                     f"digest != digest_np {at} block {bi} shard {si}")
        shapes.append({"kernel": "encode_with_digests", "shape": [b, k, s],
                       "m": m, "first_s": first, "second_s": second})

        # Two shards missing (one data, one parity), rebuilt with the
        # decode matrix as runtime data: the launch heal and the wide
        # degraded GET make.
        targets = (1, k + 1)
        survivors = tuple(i for i in range(n) if i not in targets)[:k]
        w_t = jnp.asarray(rs_pallas._decode_weights_t(
            k, n, survivors, targets))
        surv = jnp.asarray(shards[:, list(survivors), :])
        has_kernel(fused.reconstruct_weights_digests, surv, w_t, lens,
                   out_shards=len(targets))
        (rebuilt, rdigs), first, second = _twice(
            lambda: fused.reconstruct_weights_digests(
                surv, w_t, lens, len(targets)))
        rebuilt, rdigs = np.asarray(rebuilt), np.asarray(rdigs)
        for bi in range(b):
            want = gf.reconstruct_ref(shards[bi], k, survivors, targets)
            need(np.array_equal(rebuilt[bi], want)
                 and np.array_equal(want, shards[bi, list(targets)]),
                 f"rebuilt != reconstruct_ref {at} block {bi}")
        for ti in range(len(targets)):
            need(rdigs[0, ti].tobytes() == mxsum.digest_np(rebuilt[0, ti]),
                 f"rebuilt digest != digest_np {at}")
        shapes.append({"kernel": "reconstruct_weights_digests",
                       "shape": [b, k, s], "missing": len(targets),
                       "first_s": first, "second_s": second})

    # Read-path verify: full and ragged lengths in one launch.
    rows, s = 128, 131072
    chunks = rng.integers(0, 256, (rows, s), dtype=np.uint8)
    lens_np = np.full(rows, s, dtype=np.int32)
    lens_np[1::2] = rng.integers(1, s, rows // 2)
    for i in range(1, rows, 2):
        chunks[i, lens_np[i]:] = 0
    cdev, ldev = jnp.asarray(chunks), jnp.asarray(lens_np)
    got, first, second = _twice(lambda: fused.verify_digests(cdev, ldev))
    got = np.asarray(got)
    for i in range(0, rows, 7):  # odd step: full and ragged rows alike
        need(got[i].tobytes() == mxsum.digest_np(chunks[i, :lens_np[i]]),
             f"verify_digests != digest_np at row {i}")
    shapes.append({"kernel": "verify_digests", "shape": [rows, s],
                   "first_s": first, "second_s": second})
    return {"backend": fused._backend(), "shapes": shapes,
            "first_s_total": round(sum(x["first_s"] for x in shapes), 3)}


# ---------------------------------------------------------------------------
# the server, in this process
# ---------------------------------------------------------------------------


class Server:
    """`build_server` + aiohttp on a thread (web.run_app would print its
    banner to stdout and wants the main thread's signal handlers)."""

    def __init__(self, root: str, plan: Plan, bitrot_algorithm: str = ""):
        import asyncio
        import socket

        from aiohttp import web

        from minio_tpu.s3.server import build_server

        self.drive_roots = [os.path.join(root, f"d{i}")
                            for i in range(plan.drives)]
        self.srv = build_server(self.drive_roots, ACCESS, SECRET,
                                versioned=False, parity=plan.parity)
        self.sets = self.srv.obj.pools[0].sets
        if bitrot_algorithm:
            # CPU rehearsals only: on a chip the default flips by itself,
            # and the smoke must see that it did.
            for es in self.sets:
                es.bitrot_algorithm = bitrot_algorithm
        sock = socket.socket()
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
        sock.close()
        self.base = f"http://127.0.0.1:{port}"
        self._loop = asyncio.new_event_loop()
        self._runner = web.AppRunner(self.srv.app)
        started = threading.Event()

        def run():
            asyncio.set_event_loop(self._loop)

            async def start():
                await self._runner.setup()
                await web.TCPSite(self._runner, "127.0.0.1", port).start()
                started.set()

            self._loop.run_until_complete(start())
            self._loop.run_forever()

        self._thread = threading.Thread(target=run, name="smoke-http",
                                        daemon=True)
        self._thread.start()
        need(started.wait(60), "server did not start")

    def client(self):
        """A SigV4 client whose requests wait long enough for a first
        launch that compiles (the test client defaults to 30 s)."""
        from tests.s3client import SigV4Client

        c = SigV4Client(self.base, ACCESS, SECRET)
        request = c.request
        c.request = lambda *a, timeout=900, **kw: request(
            *a, timeout=timeout, **kw)
        return c

    def stop(self) -> None:
        import asyncio

        asyncio.run_coroutine_threadsafe(
            self._runner.cleanup(), self._loop).result(60)
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(30)
        self.srv.obj.close()


def _ok(r, code: int = 200):
    # The message is built on failure only: r.text sniffs the charset of
    # the whole body, seconds for a 256 MiB object.
    if r.status_code != code:
        need(False, f"{r.request.method} {r.request.url} -> "
             f"{r.status_code} {r.text[:300]}")
    return r


def _each(plan: Plan, server: Server, names: list, fn) -> float:
    """fn(client, name) over names on plan.clients threads, one client per
    thread; the first error propagates. -> seconds taken."""
    local = threading.local()

    def one(name):
        if not hasattr(local, "c"):
            local.c = server.client()
        fn(local.c, name)

    t0 = time.perf_counter()
    with ThreadPoolExecutor(plan.clients,
                            thread_name_prefix="smoke-cli") as ex:
        list(ex.map(one, names))
    return time.perf_counter() - t0


def phase_serve(state: dict, server: Server, plan: Plan, seed: int) -> dict:
    """Load the state, read all of it back, compare digests of the bodies
    with the generator's."""
    c = server.client()
    _ok(c.put(f"/{BUCKET}"))
    smalls, lanes, mediums = object_names(plan)
    sizes = {**dict.fromkeys(smalls, plan.small[1]),
             **dict.fromkeys(lanes, plan.lane[1]),
             **dict.fromkeys(mediums, plan.medium[1])}
    want: dict[str, str] = {}

    def put(cli, name):
        body = payload(seed, name, sizes[name])
        _ok(cli.put(f"/{BUCKET}/{name}", data=body))
        want[name] = body_digest(body)

    t_small = _each(plan, server, smalls, put)
    t_lane = _each(plan, server, lanes, put)
    t_medium = _each(plan, server, mediums, put)

    # One multipart upload, parts in parallel.
    nparts, psize = plan.parts
    part_nums = list(range(1, nparts + 1))
    r = _ok(c.post(f"/{BUCKET}/{MP_KEY}", query={"uploads": ""}))
    uid = ET.fromstring(r.content).findtext(f"{S3NS}UploadId")
    etags: dict[int, str] = {}

    def put_part(cli, num):
        r = _ok(cli.put(f"/{BUCKET}/{MP_KEY}",
                        data=payload(seed, f"{MP_KEY}#{num}", psize),
                        query={"uploadId": uid, "partNumber": str(num)}))
        etags[num] = r.headers["ETag"]

    t_mp = _each(plan, server, part_nums, put_part)
    cx = "<CompleteMultipartUpload>" + "".join(
        f"<Part><PartNumber>{n}</PartNumber><ETag>{etags[n]}</ETag></Part>"
        for n in part_nums) + "</CompleteMultipartUpload>"
    t0 = time.perf_counter()
    _ok(c.post(f"/{BUCKET}/{MP_KEY}", data=cx.encode(),
               query={"uploadId": uid}))
    t_mp += time.perf_counter() - t0
    want[MP_KEY] = body_digest(
        *(payload(seed, f"{MP_KEY}#{n}", psize) for n in part_nums))
    sizes[MP_KEY] = nparts * psize

    def get(cli, name):
        r = _ok(cli.get(f"/{BUCKET}/{name}"))
        need(body_digest(r.content) == want[name],
             f"GET {name}: body differs")

    t_get = _each(plan, server, list(want), get)

    # One ranged GET across erasure-block boundaries.
    name = mediums[0]
    lo, hi = sizes[name] // 3 + 1, sizes[name] // 3 * 2
    r = _ok(c.get(f"/{BUCKET}/{name}",
                  headers={"Range": f"bytes={lo}-{hi}"}), 206)
    need(r.content == payload(seed, name, sizes[name])[lo:hi + 1],
         f"ranged GET {name}: body differs")

    listed = {}
    for prefix, n in (("s/", len(smalls)), ("l/", len(lanes)),
                      ("m/", len(mediums)), ("mp/", 1)):
        r = _ok(c.get(f"/{BUCKET}", query={
            "list-type": "2", "prefix": prefix, "max-keys": "1000"}))
        listed[prefix] = len(list(ET.fromstring(r.content).iter(
            f"{S3NS}Key")))
        need(listed[prefix] == n,
             f"LIST {prefix}: {listed[prefix]} keys, wanted {n}")

    state.update(want=want, sizes=sizes)
    return {
        "objects": len(want), "logical_mib": sum(sizes.values()) // MIB,
        "listed": listed,
        "put_small_ops_s": round(len(smalls) / t_small, 1),
        "put_lane_ops_s": round(len(lanes) / t_lane, 1),
        "put_medium_mib_s": round(
            len(mediums) * plan.medium[1] / MIB / t_medium, 1),
        "put_multipart_mib_s": round(nparts * psize / MIB / t_mp, 1),
        "get_all_mib_s": round(sum(sizes.values()) / MIB / t_get, 1),
    }


def _file_sha(path: str) -> str:
    with open(path, "rb") as f:
        return hashlib.file_digest(f, "sha256").hexdigest()


def _verify_frames(path: str, shard_size: int, data_size: int) -> int:
    """A shard file is [digest][chunk] records (ops/bitrot.py): recompute
    every chunk's mxsum256 on the host (numpy — independent of the device
    that wrote it) and compare. Returns the record count."""
    from minio_tpu.ops import mxsum

    n = 0
    left = data_size
    with open(path, "rb") as f:
        while left > 0:
            want = f.read(mxsum.DIGEST_LEN)
            chunk = f.read(min(shard_size, left))
            need(len(want) == mxsum.DIGEST_LEN
                 and len(chunk) == min(shard_size, left),
                 f"{path}: short frame {n}")
            need(mxsum.digest_np(chunk) == want,
                 f"{path}: frame {n} digest does not match its chunk")
            left -= len(chunk)
            n += 1
        need(not f.read(1), f"{path}: bytes after the last frame")
    return n


def _lose_shards(server: Server, victims: list[str]) -> dict:
    """Remove, for each victim, every part's shard file at m positions
    (half data, half parity). -> {path: (sha256, shard_size, data_size)}
    of what was removed."""
    es = server.sets[0]
    # Damage at rest: the group-commit journal must be on the drive, not
    # in the committer's overlay, before files vanish behind its back.
    for d in es.drives:
        wal = getattr(d, "_wal", None)
        if wal is not None:
            wal.flush()
    removed: dict[str, tuple[str, int, int]] = {}
    for key in victims:
        fi = es.latest_fileinfo(BUCKET, key)
        k, m = fi.erasure.data_blocks, fi.erasure.parity_blocks
        lose = set(range(1, m // 2 + 1)) | set(
            range(k + 1, k + 1 + (m - m // 2)))
        for di, si in enumerate(fi.erasure.distribution):
            if si not in lose:
                continue
            for part in fi.parts:
                p = os.path.join(server.drive_roots[di], BUCKET, key,
                                 fi.data_dir, f"part.{part.number}")
                removed[p] = (_file_sha(p), fi.erasure.shard_size(),
                              fi.erasure.shard_file_size(part.size))
                os.unlink(p)
    return removed


def _check_healed(removed: dict) -> int:
    """Every removed shard file is back, its [digest][chunk] frames verify
    on the host, and it is byte-identical to what was removed. -> frames."""
    frames = 0
    for p, (sha, shard_size, data_size) in removed.items():
        need(os.path.exists(p), f"not healed: {p}")
        frames += _verify_frames(p, shard_size, data_size)
        need(_file_sha(p) == sha,
             f"healed {p} differs from the file that was removed")
    return frames


def phase_degraded_heal(state: dict, server: Server, plan: Plan) -> dict:
    """Lose m shards of each victim and GET it through the device
    reconstruct. A degraded GET also hands the object to the background
    MRF healer, so wait for that heal and check its files; then lose the
    same shards again and heal through the admin API, with no GET in
    between, and check those."""
    from minio_tpu.madmin import AdminClient

    _, lanes, mediums = object_names(plan)
    victims = (lanes[1:1 + plan.degrade] + mediums[1:1 + plan.degrade]
               + [MP_KEY])
    c = server.client()
    vmib = sum(state["sizes"][v] for v in victims) / MIB

    def get_all(what: str) -> float:
        t0 = time.perf_counter()
        for key in victims:
            r = _ok(c.get(f"/{BUCKET}/{key}"))
            need(body_digest(r.content) == state["want"][key],
                 f"{what} {key}: body differs")
        return time.perf_counter() - t0

    removed = _lose_shards(server, victims)
    t_get = get_all("degraded GET")
    t0 = time.perf_counter()
    while not all(os.path.exists(p) for p in removed):
        need(time.perf_counter() - t0 < 300, "the MRF healer did not "
             "restore the shards of the degraded GETs")
        time.sleep(0.2)
    t_mrf = time.perf_counter() - t0
    frames = _check_healed(removed)

    removed = _lose_shards(server, victims)
    adm = AdminClient(server.base, ACCESS, SECRET, timeout=900.0)
    t0 = time.perf_counter()
    items = 0
    for key in victims:
        for item in adm.heal(BUCKET, key)["items"]:
            need(not item.get("error"), f"heal {key}: {item.get('error')}")
            items += 1
    t_heal = time.perf_counter() - t0
    frames += _check_healed(removed)
    get_all("GET after heal")
    return {"victims": victims, "shard_files_removed": len(removed),
            "frames_verified": frames, "heal_items": items,
            "degraded_get_mib_s": round(vmib / t_get, 1),
            "mrf_heal_s": round(t_mrf, 2),
            "admin_heal_mib_s": round(vmib / t_heal, 1)}


def kernel_launches(server: Server) -> dict[str, dict[str, tuple]]:
    """backend -> kernel -> (launches, host-observed seconds in all) from
    the node scrape's minio_tpu_kernel_seconds. Backends:
    `<platform>:<pallas|xla>` for the fused launches and the lanes, `mesh`,
    and `host`/`native` for codec work that ran on the host."""
    from minio_tpu.chaos.invariants import parse_exposition

    samples = parse_exposition(
        _ok(server.client().get("/minio/v2/metrics/node")).text)
    out: dict[str, dict[str, tuple]] = {}
    for (name, labels), count in samples.items():
        if name == "minio_tpu_kernel_seconds_count" and count > 0:
            lb = dict(labels)
            secs = samples.get(("minio_tpu_kernel_seconds_sum", labels), 0.0)
            out.setdefault(lb["backend"], {})[lb["kernel"]] = (
                count, round(secs, 3))
    return out


def phase_metrics(state: dict, server: Server, plan: Plan,
                  platform: str = "tpu") -> dict:
    """It was the device: what the server itself reports about where its
    codec ran. A smoke that passes on the host lane has failed."""
    launches = kernel_launches(server)
    # The registry is the process's: a rehearsal inside a test run takes a
    # snapshot first and only what came after it counts.
    for be, ks in state.get("kernel_launches_before", {}).items():
        for k, (n0, s0) in ks.items():
            n, secs = launches[be][k]
            launches[be][k] = (n - n0, round(secs - s0, 3))
    launches = {be: kept for be, ks in launches.items()
                if (kept := {k: v for k, v in ks.items() if v[0] > 0})}
    on_dev = {k for be, ks in launches.items()
              if be.startswith(f"{platform}:") for k in ks}
    wrong = [be for be in launches
             if be.split(":")[0] in ("cpu", "tpu", "gpu")
             and not be.startswith(f"{platform}:")]
    need(not wrong, f"kernel observations under {wrong}")
    need("host" not in launches,
         f"codec work on the host lane: {launches.get('host')}")
    for kernels in ({"encode_digests", "dp_encode"},
                    {"verify_digests", "dp_verify"},
                    {"reconstruct_weights", "reconstruct_digests",
                     "reconstruct", "dp_reconstruct"}):
        need(kernels & on_dev, f"no {platform}:* observation for any of "
             f"{sorted(kernels)}; saw {launches}")
    dp_launches = sum(n for be, ks in launches.items()
                      if be.startswith(f"{platform}:")
                      for k, (n, _s) in ks.items() if k.startswith("dp_"))
    need(dp_launches > 0, "the dataplane lanes launched nothing")

    # Every stored object names the device checksum.
    es = server.sets[0]
    algos: dict[str, int] = {}
    inline = 0
    for key in state["want"]:
        fi = es.latest_fileinfo(BUCKET, key)
        if fi.inline_data:  # lives in the journal: no shard, no checksum
            inline += 1
            continue
        need(fi.erasure.checksums, f"{key} stored with no checksum")
        for ci in fi.erasure.checksums:
            algos[ci.algorithm] = algos.get(ci.algorithm, 0) + 1
    need(set(algos) == {"mxsum256"},
         f"stored checksums {algos}, wanted mxsum256 only")
    from minio_tpu.dataplane import ring

    return {"kernel_launches": launches, "lane_launches": dp_launches,
            "lane_programs": ring.trace_count(),
            "stored_checksums": algos, "inline_objects": inline}


# ---------------------------------------------------------------------------
# --chips 4: the mesh path, and nothing else
# ---------------------------------------------------------------------------


def phase_mesh(state: dict, seed: int = 0) -> dict:
    """The mesh-sharded fused encode and a sharded reconstruct against the
    single-device launch and gf.encode_ref, bit-exact, with inputs and
    outputs spread over every device of the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from minio_tpu.erasure import codec
    from minio_tpu.ops import fused, gf
    from minio_tpu.parallel import (sharded_encode_with_mxsum,
                                    sharded_reconstruct)

    mesh = codec.serving_mesh()
    need(mesh is not None, "serving_mesh() is None on this host")
    ndev = len(jax.devices())
    in_sharding = NamedSharding(mesh, P("dp", "tp", "sp"))
    rng = np.random.default_rng([seed, 4])
    rows = []

    def spread(arr, what: str) -> None:
        devs = {s.device for s in arr.addressable_shards}
        need(len(devs) == ndev,
             f"{what} lives on {len(devs)} device(s), wanted {ndev}")

    for b, k, m, s in ((16, 8, 4, 131072), (16, 12, 4, 87382)):
        n, at = k + m, f"at [{b},{k},{s}]"
        data = rng.integers(0, 256, (b, k, s), dtype=np.uint8)
        x = jax.device_put(data, in_sharding)
        spread(x, f"encode input {at}")
        (parity, digs), first, second = _twice(
            lambda: sharded_encode_with_mxsum(mesh, x, k, m))
        spread(parity, f"parity {at}")
        spread(digs, f"digests {at}")
        p1, d1 = fused.encode_with_digests(jnp.asarray(data), k, m)
        parity, digs = np.asarray(parity), np.asarray(digs)
        need(np.array_equal(parity, np.asarray(p1))
             and np.array_equal(digs, np.asarray(d1)),
             f"mesh encode != single-device launch {at}")
        for bi in range(b):
            need(np.array_equal(parity[bi], gf.encode_ref(data[bi], m)),
                 f"mesh parity != encode_ref {at} block {bi}")
        rows.append({"op": "sharded_encode_with_mxsum", "shape": [b, k, s],
                     "first_s": first, "second_s": second})

        shards = np.concatenate([data, parity], axis=1)
        targets = (1, 2, k, k + 1)
        survivors = tuple(i for i in range(n) if i not in targets)[:k]
        sv = jax.device_put(shards[:, list(survivors), :], in_sharding)
        spread(sv, f"reconstruct input {at}")
        rebuilt, first, second = _twice(lambda: sharded_reconstruct(
            mesh, sv, k, n, survivors, targets))
        spread(rebuilt, f"rebuilt {at}")
        r1 = fused.reconstruct_only(jnp.asarray(shards), k, n,
                                    survivors, targets)
        rebuilt = np.asarray(rebuilt)
        need(np.array_equal(rebuilt, np.asarray(r1))
             and np.array_equal(rebuilt, shards[:, list(targets), :]),
             f"mesh reconstruct != single-device launch {at}")
        rows.append({"op": "sharded_reconstruct", "shape": [b, k, s],
                     "missing": len(targets), "first_s": first,
                     "second_s": second})
    return {"mesh": dict(mesh.shape), "devices": ndev, "launches": rows}


def phase_mesh_serve(state: dict, server: Server, seed: int = 0,
                     size: int = 64 << 20, plan: Plan = FULL) -> dict:
    """One served PUT + GET on the multi-chip host: full-block PUT batches
    must launch on the mesh (erasure/codec.py), and concurrent 128 KiB
    PUTs on lanes whose rows split over the devices (dataplane/ring.py)."""
    c = server.client()
    _ok(c.put(f"/{BUCKET}"))
    _, lanes, _ = object_names(plan)

    def put_get(cli, name):
        body = payload(seed, name, plan.lane[1])
        _ok(cli.put(f"/{BUCKET}/{name}", data=body))
        need(_ok(cli.get(f"/{BUCKET}/{name}")).content == body,
             f"lane GET {name}: body differs")

    _each(plan, server, lanes, put_get)
    body = payload(seed, "mesh/obj", size)
    t0 = time.perf_counter()
    _ok(c.put(f"/{BUCKET}/mesh/obj", data=body))
    t_put = time.perf_counter() - t0
    t0 = time.perf_counter()
    r = _ok(c.get(f"/{BUCKET}/mesh/obj"))
    t_get = time.perf_counter() - t0
    need(r.content == body, "mesh-served GET: body differs")
    launches = kernel_launches(server)
    need(launches.get("mesh", {}).get("encode_digests"),
         f"no mesh encode_digests observation; saw {launches}")
    need(not any(be.startswith("cpu:") for be in launches),
         f"cpu:* kernel observations: {launches}")
    need(any(ks.get("dp_encode") for be, ks in launches.items()
             if be.startswith("tpu:")),
         f"no tpu:* dp_encode lane launch; saw {launches}")
    return {"kernel_launches": launches,
            "put_mib_s": round(size / MIB / t_put, 1),
            "get_mib_s": round(size / MIB / t_get, 1)}


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def build_phases(args) -> list:
    """The phase list of one run. The server lives from its first phase to
    `stop`; its drives sit under one root that is removed before the last
    line."""
    box: dict = {}

    def served(fn, *extra):
        def run(state):
            if not box:
                box["root"] = tempfile.mkdtemp(prefix="chip_smoke_",
                                               dir=args.root or None)
                box["server"] = Server(box["root"], FULL)
            return fn(state, box["server"], *extra)
        return run

    def phase_stop(state):
        box["server"].stop()
        shutil.rmtree(box["root"], ignore_errors=True)
        return {"wall_s": round(time.time() - args.t_start, 1),
                "cache_entries_at_end": _entries(state["cache_dir"])}

    if args.chips == 4:
        middle = [("mesh", lambda st: phase_mesh(st, args.seed)),
                  ("mesh_serve", served(phase_mesh_serve, args.seed))]
    else:
        middle = [("kernels", lambda st: phase_kernels(st, args.seed)),
                  ("serve", served(phase_serve, FULL, args.seed)),
                  ("degraded_heal", served(phase_degraded_heal, FULL)),
                  ("metrics", served(phase_metrics, FULL))]
    return [("device", lambda st: phase_device(st, args.chips)),
            ("native", phase_native), *middle, ("stop", phase_stop)]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=22)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the mesh path on a four-chip host, only")
    ap.add_argument("--root", default="",
                    help="parent directory for the drive root "
                         "(default: the system temp directory)")
    args = ap.parse_args(argv)
    args.t_start = time.time()
    out_fd = claim_stdout()
    faulthandler.enable()
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)
    sys.path.insert(0, HERE)
    finish(build_phases(args), out_fd)


if __name__ == "__main__":
    main()
