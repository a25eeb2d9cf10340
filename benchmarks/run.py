#!/usr/bin/env python3
"""One cell, once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process never imports JAX. It starts one server child
(`benchmarks/serve.py` -> `minio_tpu.s3.server.main`, the user's entry point,
program defaults, no `MTPU_*` variable but the root credentials), client
worker processes (`benchmarks/worker.py`) outside the server's process, and
for a traced run a reduction child after the server has gone. Everything
about a cell is data found by name: `BENCHMARK.json` names the workload's
configuration and traffic mix, `configs/<config>.json`,
`traffic/<traffic>.json` and `layer_metrics/<metric>.json` hold them. A
configuration may state a `state`, one of two: drives lost after the
preload, or drives that came back blank after it (online, formatted, with
the bucket and no object; made blank again for a group of objects before
each heal of it). Set-up brings it about, and the comparison and the
reckoning of codec work are made in it.

The last line of stdout is the result object; earlier lines say what the
run saw. A run that finds no TPU (or not the cell's number of chips), a
profiler marker in place of a device trace, or codec work observed on a host
lane exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import collections
import ctypes
import http.client
import io
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import zipfile

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import blank  # noqa: E402
import reference  # noqa: E402
import scrape  # noqa: E402
import traffic  # noqa: E402
import verify  # noqa: E402
import work  # noqa: E402
from s3http import Reply, S3Http  # noqa: E402

ACCESS, SECRET = "benchadmin", "benchsecret123"
BUCKET = "bench"
READBACK_WAIT_S = 60.0   # past the window's close, for answers that are late
READBACK_STALL_S = 10.0  # no byte of a read back for so long: ask again
AT_REST_WAIT_S = 30.0    # for acknowledged journals to be files on the drives
MIB = 1 << 20
TRACE_SLICE_S = 3.0   # traces are large and the tracer slows the host
RECORD_FIELDS = ("verb", "key", "size", "body_index", "t_send", "t_first",
                 "t_last", "status", "ok", "wrong", "retries")


class RunFailed(Exception):
    """The run cannot give a result: exit non-zero, print none."""


def say(msg: str) -> None:
    """An earlier line of stdout."""
    print(msg, flush=True)


def note(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, root: str = ROOT) -> dict:
    """The workload's entry, configuration, mix and metric files, by name."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise RunFailed(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    here = os.path.join(root, os.path.dirname(bench["command"][1]))

    def in_cell(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    end_to_end = [m for m in bench["end_to_end"] if in_cell(m)]
    reported = {m["name"] for m in end_to_end}
    return {
        "cell": cell,
        "config": load_json(os.path.join(root, conf["file"])),
        "mix": load_json(os.path.join(here, "traffic",
                                      cell["traffic"] + ".json")),
        "end_to_end": end_to_end,
        # a layer's metric belongs where the number it should move is read
        "per_layer": [
            {**m, **load_json(os.path.join(here, "layer_metrics",
                                           m["name"] + ".json"))}
            for m in bench["per_layer"]
            if in_cell(m) and m["moves"] in reported],
    }


# --- set-up ----------------------------------------------------------------


def ensure_native(root: str) -> float:
    """Build native/*.so where absent or not loadable here. -> seconds."""
    t0 = time.monotonic()
    lib = os.path.join(root, "native", "libmtpu_native.so")
    force = []
    if os.path.exists(lib):
        try:
            ctypes.CDLL(lib)
            return 0.0
        except OSError:
            force = ["-B"]
    r = subprocess.run(["make", *force, "-C", os.path.join(root, "native")],
                       stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    if r.returncode != 0 or not os.path.exists(lib):
        raise RunFailed("native build failed:\n"
                        + r.stdout.decode(errors="replace")[-2000:])
    return time.monotonic() - t0


def cache_dir(root: str) -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")


def cache_entries(path: str) -> int:
    try:
        return len(os.listdir(path))
    except FileNotFoundError:
        return 0


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def written_bytes(mix: dict, config: dict, seconds: float,
                  rate_mibps: float = 150.0,
                  heal_rate_mibps: float = 500.0) -> int:
    """What a run may write at the most: the preload, and PUTs at twice the
    highest goodput a cell has shown (PERF.md) over warm-up and window, all
    of it times (k+m)/k; and of the bytes healed, at twice what heal has
    shown, the blank drives' share, (drives blank)/k."""
    pre = mix.get("preload", {})
    total = pre.get("objects", 0) * max(
        [s for s, _ in pre.get("sizes", [])] or [0])
    if any(o["verb"] == "PUT" for o in mix["ops"]):
        total += int(rate_mibps * MIB * (seconds + 20))
    total = total * int(config["drives"]) // int(config["data_shards"])
    if any(o["verb"] == "HEAL" for o in mix["ops"]):
        total += (int(heal_rate_mibps * MIB * (seconds + 20))
                  * int((config.get("state") or {}).get("drives_blank", 0))
                  // int(config["data_shards"]))
    return total


class Server:
    """The server child and its drives."""

    def __init__(self, run_dir: str, config: dict, chips: int, platform: str,
                 launcher: str, root: str):
        self.run_dir = run_dir
        self.drive_roots = [os.path.join(run_dir, "drives", f"d{i}")
                            for i in range(int(config["drives"]))]
        self.port = free_port()
        env = dict(os.environ)
        env["MTPU_ROOT_USER"], env["MTPU_ROOT_PASSWORD"] = ACCESS, SECRET
        self.log = open(os.path.join(run_dir, "server.log"), "wb")
        self.proc = subprocess.Popen(
            [sys.executable, launcher, run_dir, platform, str(chips),
             *self.drive_roots, "--parity", str(config["parity_shards"]),
             "--address", f"127.0.0.1:{self.port}"],
            stdout=self.log, stderr=subprocess.STDOUT, stdin=subprocess.DEVNULL,
            env=env, cwd=root)

    def client(self) -> S3Http:
        return S3Http("127.0.0.1", self.port, ACCESS, SECRET, timeout=900)

    def log_tail(self, n: int = 3000) -> str:
        self.log.flush()
        with open(self.log.name, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode(errors="replace")

    def wait_device(self, deadline_s: float = 300) -> dict:
        path = os.path.join(self.run_dir, "device.json")
        t_end = time.monotonic() + deadline_s
        while not os.path.exists(path):
            if self.proc.poll() is not None or time.monotonic() > t_end:
                raise RunFailed("the server child gave no device:\n"
                                + self.log_tail())
            time.sleep(0.05)
        return load_json(path)

    def wait_live(self, deadline_s: float = 300) -> None:
        c = self.client()
        t_end = time.monotonic() + deadline_s
        while True:
            if self.proc.poll() is not None:
                raise RunFailed("the server child ended "
                                f"({self.proc.returncode}):\n"
                                + self.log_tail())
            try:
                if c.request("GET", "/minio/health/live").status == 200:
                    c.close()
                    return
            except OSError:
                c.close()
            if time.monotonic() > t_end:
                raise RunFailed("the server never answered "
                                "/minio/health/live:\n" + self.log_tail())
            time.sleep(0.1)

    def stop(self) -> dict:
        """SIGTERM, reap, and what the child wrote about device memory."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self.log.close()
        path = os.path.join(self.run_dir, "memory.json")
        return load_json(path) if os.path.exists(path) else {}


class Workers:
    """The client worker processes of one run."""

    def __init__(self, run_dir: str, mix: dict, seed: int, port: int,
                 blank_roots: list[str] = ()):
        self.run_dir = run_dir
        self.go = os.path.join(run_dir, "go")
        self.stop_file = os.path.join(run_dir, "stop")
        self.procs, self.specs, self.logs = [], [], []
        for w, threads in enumerate(traffic.split_clients(mix)):
            spec = {"host": "127.0.0.1", "port": port, "access": ACCESS,
                    "secret": SECRET, "bucket": BUCKET, "seed": seed,
                    "worker": w, "threads": threads, "traffic": mix,
                    "ready": os.path.join(run_dir, f"ready.{w}"),
                    "go": self.go, "stop": self.stop_file,
                    "progress": os.path.join(run_dir, f"progress.{w}"),
                    "out": os.path.join(run_dir, f"records.{w}.json")}
            if blank_roots:   # and where what is taken off them goes
                spec["blank_roots"] = list(blank_roots)
                spec["blank_aside"] = os.path.join(run_dir, "taken")
                os.makedirs(spec["blank_aside"], exist_ok=True)
            path = os.path.join(run_dir, f"worker.{w}.json")
            with open(path, "w") as f:
                json.dump(spec, f)
            self.specs.append(spec)
            self.logs.append(open(
                os.path.join(run_dir, f"worker.{w}.log"), "wb"))
            self.procs.append(subprocess.Popen(
                [sys.executable, os.path.join(HERE, "worker.py"), path],
                stdout=subprocess.DEVNULL, stdin=subprocess.DEVNULL,
                stderr=self.logs[-1]))
        self.clients = sum(s["threads"] for s in self.specs)

    def wait_ready(self, deadline_s: float = 300) -> None:
        t_end = time.monotonic() + deadline_s
        while not all(os.path.exists(s["ready"]) for s in self.specs):
            if any(p.poll() is not None for p in self.procs):
                raise RunFailed("a client worker ended before it was ready")
            if time.monotonic() > t_end:
                raise RunFailed("client workers were not ready in time")
            time.sleep(0.02)

    def start(self) -> None:
        open(self.go, "w").close()

    def done_ops(self) -> int:
        n = 0
        for s in self.specs:
            try:
                with open(s["progress"], "rb") as f:
                    raw = f.read()
            except FileNotFoundError:
                continue
            n += sum(int.from_bytes(raw[i:i + 8], "little")
                     for i in range(0, len(raw) - 7, 8))
        return n

    def finish(self, deadline_s: float = 120) -> list[list[dict]]:
        """Stop the loops, wait for each request in flight (an answer that
        comes late is late, not wrong) -> per client thread, its records."""
        open(self.stop_file, "w").close()
        threads = []
        for p, s in zip(self.procs, self.specs):
            try:
                p.wait(deadline_s)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
                raise RunFailed(f"client worker {s['worker']} did not end")
            if p.returncode != 0 or not os.path.exists(s["out"]):
                raise RunFailed(f"client worker {s['worker']} failed "
                                f"({p.returncode})")
            for recs in load_json(s["out"])["threads"]:
                threads.append([dict(zip(RECORD_FIELDS, r)) for r in recs])
        return threads

    def log_lines(self, n: int) -> list[str]:
        """The first lines the workers wrote about requests that failed."""
        out: list[str] = []
        for s in self.specs:
            path = os.path.join(self.run_dir, f"worker.{s['worker']}.log")
            with open(path, errors="replace") as f:
                out += [ln.rstrip()[:400] for ln in f][:n]
        return out[:n]

    def kill(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.kill()
            p.wait()
        for log in self.logs:
            log.close()


def preload(server: Server, pool: traffic.BodyPool, objects: list,
            threads: int = 8) -> None:
    """Set-up's PUTs, on a few threads of this process."""
    errors: list[str] = []
    it = iter(objects)
    lock = threading.Lock()

    def run() -> None:
        c = server.client()
        while True:
            with lock:
                op = next(it, None)
            if op is None or errors:
                break
            body = pool.get(op.size, op.body_index)
            r = c.request("PUT", f"/{BUCKET}/{op.key}", body=body.data,
                          body_sha256=body.sha256)
            if not r.ok:
                errors.append(f"preload PUT {op.key} -> {r.status}")
        c.close()

    ts = [threading.Thread(target=run) for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    if errors:
        raise RunFailed(errors[0])


def apply_state(drive_roots: list[str], state: dict) -> list[str]:
    """Bring about what a configuration states -> the roots of the drives
    it names, lost or blank.

    `drives_blank`: the first n drives hold no object any more (`blank.py`:
    the bucket's directory emptied, the root and the format left), as after
    a node was rebuilt; they stay online, and the client workers make them
    blank again for a group of objects before each heal of that group.

    `drives_lost`: a lost drive's tree is removed and a symbolic link to itself
    left in its place: every system call under it then fails (ELOOP), as on
    a disk that died or a node that is down, the program takes the drive
    offline and can heal nothing onto it. (A root that is merely missing is
    made anew by the heal a degraded GET queues, and a shard file removed
    from a drive that stays is healed back: either way the cell would
    measure a healthy set after its first seconds.)"""
    if "drives_blank" in state:
        roots = blank.blank_roots(drive_roots, state)
        if (state.get("which"), state.get("when"), state.get("again")) != (
                "first", "after_preload", "before_each_heal") \
                or not 0 < len(roots) < len(drive_roots):
            raise RunFailed("a state this harness cannot bring about: "
                            f"{state}")
        blank.blank_drives(roots, BUCKET)
        return roots
    n = int(state["drives_lost"])
    if (state.get("which"), state.get("when")) != ("first", "after_preload") \
            or not 0 < n < len(drive_roots):
        raise RunFailed(f"a state this harness cannot bring about: {state}")
    lost = drive_roots[:n]
    for root in lost:
        shutil.rmtree(root)
        os.symlink(os.path.basename(root), root)
    return lost


def roots_present(roots: list[str]) -> set[str]:
    """Those of the roots that are a directory (again)."""
    return {r for r in roots if os.path.isdir(r)}


# --- the traced slice ------------------------------------------------------


class TraceSlice:
    """The program's own profiler, through its admin API, for a short slice
    of the same steady load: it is started when the window has closed, while
    the clients go on. Starting it stalls the server's threads for a second
    or two (section 7 of PERF.md), which must not reach into the window's
    numbers or its answers; what the slice's own operations answered is
    printed, not hidden."""

    def __init__(self, server: Server, out_path: str):
        self.server, self.out_path = server, out_path
        self.t_begin = self.t_stop = 0.0

    def take(self) -> None:
        c = self.server.client()
        try:
            # The trace runs from inside the first call to the start of the
            # second one's handling: the slice is [t_begin, t_stop].
            self.t_begin = time.monotonic()
            r = c.request("POST", "/minio/admin/v3/profiling/start",
                          query={"profilerType": "tpu"})
            if not r.ok:
                raise RunFailed(f"profiling/start -> {r.status}")
            time.sleep(TRACE_SLICE_S)
            self.t_stop = time.monotonic()
            r = c.request("GET", "/minio/admin/v3/profiling/download")
            if not r.ok:
                raise RunFailed(f"profiling/download -> {r.status}")
            self._keep(r.body)
        except OSError as e:
            raise RunFailed(f"traced run failed: {type(e).__name__}: {e}")
        finally:
            c.close()

    def _keep(self, archive: bytes) -> None:
        outer = zipfile.ZipFile(io.BytesIO(archive))
        names = outer.namelist()
        marker = [n for n in names if n.endswith("MARKER.txt")]
        if marker:
            raise RunFailed(
                "the profiler left a marker, not a device trace: "
                + outer.read(marker[0]).decode(errors="replace"))
        inner = [n for n in names if n.endswith("tpu_trace.zip")]
        if not inner:
            raise RunFailed(f"no tpu_trace.zip in the archive: {names}")
        z = zipfile.ZipFile(io.BytesIO(outer.read(inner[0])))
        planes = [n for n in z.namelist() if n.endswith(".xplane.pb")]
        if not planes:
            raise RunFailed(f"no .xplane.pb in the trace: {z.namelist()}")
        with open(self.out_path, "wb") as f:
            f.write(z.read(planes[0]))


def reduce_trace(xplane: str, platform: str) -> dict:
    """The reduction child: the only process of a run besides the server
    that imports JAX, started after the server has gone, on the CPU."""
    out = xplane + ".json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(HERE, "trace_reduce.py"),
                        xplane, out, platform], env=env,
                       stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                       stdin=subprocess.DEVNULL)
    if r.returncode != 0:
        raise RunFailed("the trace reduction failed:\n"
                        + r.stdout.decode(errors="replace")[-3000:])
    return load_json(out)


# --- metrics ---------------------------------------------------------------


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile of all the values."""
    s = sorted(values)
    return s[min(len(s) - 1, max(0, -(-len(s) * q // 100) - 1))]


def end_to_end(done: list[dict], window_s: float, setup_s: float) -> dict:
    """Every end-to-end number this harness knows, over ALL operations that
    completed in the window; a cell reports those BENCHMARK.json lists."""
    good = [r for r in done if r["ok"]]
    times = [(r["t_last"] - r["t_send"]) * 1e3 for r in good]
    ttfb = [(r["t_first"] - r["t_send"]) * 1e3 for r in good
            if r["verb"] == "GET"]
    out = {"setup_s": setup_s,
           "goodput_mibps": sum(r["size"] for r in good) / MIB / window_s,
           "ops_per_s": len(good) / window_s}
    if times:
        out["op_p90_ms"] = percentile(times, 90)
    if ttfb:
        out["ttfb_p90_ms"] = percentile(ttfb, 90)
    return out


def client_gap_pct(threads: list[list[dict]], t0: float, t1: float) -> float:
    """Share of client-thread time in the window spent outside a request."""
    busy = 0.0
    for recs in threads:
        for r in recs:
            busy += max(0.0, min(r["t_last"], t1) - max(r["t_send"], t0))
    return 100.0 * (1.0 - busy / (len(threads) * (t1 - t0)))


def require_device_backends(seen: dict[str, float], platform: str) -> None:
    """It was the device, or the run has failed: every observation of
    minio_tpu_kernel_seconds sits under `<platform>:*` or `mesh`."""
    bad = [b for b in seen if b in ("host", "native") or (
        ":" in b and not b.startswith(platform + ":"))]
    if bad:
        raise RunFailed(f"codec work was observed under {bad}: the cell "
                        "was served from a host lane")
    if not any(b.startswith(platform + ":") or b == "mesh" for b in seen):
        raise RunFailed(f"no kernel observation on the device: {seen}")


def layer_value(spec: dict, ctx: dict) -> float | None:
    """One per-layer metric, by the arithmetic its file names. None: there
    was nothing to read, and the metric stays out of the line."""
    kind = spec["arithmetic"]
    if kind == "delta_ratio":
        return scrape.delta_ratio(ctx["before"], ctx["after"], spec,
                                  ctx["window"])
    if kind == "client":
        return ctx["window"].get(spec["field"])
    if kind == "trace":
        return ctx["trace"].get(spec["field"])
    raise RunFailed(f"{spec['name']}: unknown arithmetic {kind!r}")


# --- one run ---------------------------------------------------------------


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             platform: str = "tpu", launcher: str | None = None,
             root: str = ROOT,
             readback_wait_s: float = READBACK_WAIT_S) -> dict:
    """-> the result object. `platform` and `launcher` are for the tests
    and the control: the tests rehearse on the CPU and plant faults under
    the program, the control puts the plain reference in its place
    (platform "reference": no device, so no look at the kernels)."""
    loaded = load_cell(workload, root)
    cell, config, mix = loaded["cell"], loaded["config"], loaded["mix"]
    chips = int(cell["chips"])
    phases: dict[str, float] = {}
    run_dir = tempfile.mkdtemp(prefix="bench_run_")
    server = workers = None
    try:
        free0 = shutil.disk_usage(run_dir).free
        need = written_bytes(mix, config, seconds)
        say(f"disk: {free0 / 2**30:.2f} GiB free under {run_dir}, "
            f"the run may write {need / 2**30:.2f} GiB")
        if free0 < need:
            raise RunFailed(f"not enough free disk under {run_dir}: "
                            f"{free0} < {need} bytes")
        phases["native_build"] = ensure_native(root)
        t = time.monotonic()
        server = Server(run_dir, config, chips, platform,
                        launcher or os.path.join(HERE, "serve.py"), root)
        blank_roots = blank.blank_roots(server.drive_roots,
                                        config.get("state"))
        workers = Workers(run_dir, mix, seed, server.port, blank_roots)
        pool = traffic.BodyPool(mix, seed)   # while the backend comes up
        device = server.wait_device()
        phases["backend_init"] = time.monotonic() - t
        say(f"device: platform={device['platform']} "
            f"device_kind={device['kind']!r} count={device['count']}")
        t = time.monotonic()
        server.wait_live()
        phases["boot"] = time.monotonic() - t
        admin = server.client()
        if not admin.request("PUT", f"/{BUCKET}").ok:
            raise RunFailed("could not make the bucket")
        t = time.monotonic()
        objects = traffic.preload_objects(mix, seed)
        preload(server, pool, objects)
        phases["preload"] = time.monotonic() - t
        lost: list[str] = []
        if config.get("state"):
            t = time.monotonic()
            if blank_roots:
                # Files at rest: what the program acknowledged from memory
                # is written out before anything is taken from under it.
                keys = [o.key for o in objects]
                while not blank.journals_at_rest(blank_roots, BUCKET, keys):
                    if time.monotonic() - t > AT_REST_WAIT_S:
                        raise RunFailed("the preloaded objects' journals "
                                        "did not come to rest on the drives")
                    time.sleep(0.1)
                say(f"state: the journals of {len(keys)} objects were at "
                    f"rest after {time.monotonic() - t:.2f} s")
            roots = apply_state(server.drive_roots, config["state"])
            phases["state"] = time.monotonic() - t
            if blank_roots:
                say(f"state: {config['state']}: no object left on "
                    + " ".join(os.path.basename(r) for r in roots))
            else:
                lost = roots
                say(f"state: {config['state']}: removed "
                    + " ".join(os.path.basename(r) for r in lost))
        t = time.monotonic()
        workers.wait_ready()
        phases["clients_ready"] = time.monotonic() - t

        # Warm-up: the clients run as they will in the window; it ends when
        # they have done enough and no program has compiled for a while.
        cdir = cache_dir(root)
        entries0 = cache_entries(cdir)
        t_warm = time.monotonic()
        workers.start()
        wu = mix["warmup"]
        seen, t_seen = entries0, t_warm
        while True:
            time.sleep(0.25)
            now = time.monotonic()
            n = cache_entries(cdir)
            if n != seen:
                seen, t_seen = n, now
            if server.proc.poll() is not None:
                raise RunFailed("the server ended in warm-up:\n"
                                + server.log_tail())
            if (now - t_warm >= wu["min_seconds"]
                    and workers.done_ops() >= wu["min_ops"]
                    and now - t_seen >= wu["quiet_seconds"]):
                break
            if now - t_warm > wu["max_seconds"]:
                raise RunFailed("warm-up did not settle: "
                                f"{workers.done_ops()} ops, {n} cache entries")
        phases["warmup"] = time.monotonic() - t_warm
        entries1 = cache_entries(cdir)
        before = scrape.parse(
            admin.request("GET", "/minio/v2/metrics/node").body.decode())

        # The window: opens without a pause, closes `seconds` later.
        lost_seen = roots_present(lost)
        t0 = time.monotonic()
        setup_s = t0 - T_START
        time.sleep(max(0.0, t0 + seconds - time.monotonic()))
        t1 = time.monotonic()
        lost_seen |= roots_present(lost)
        after = scrape.parse(
            admin.request("GET", "/minio/v2/metrics/node").body.decode())
        entries2 = cache_entries(cdir)
        tracer = None
        if trace:
            tracer = TraceSlice(server, os.path.join(run_dir, "xplane.pb"))
            tracer.take()
        threads = workers.finish()
        say(f"compile cache: {entries0} entries before warm-up, {entries1} at "
            f"the window's open, {entries2} at its close"
            + (": A PROGRAM COMPILED INSIDE THE WINDOW"
               if entries2 != entries1 else ""))

        # What the window did.
        everything = [r for recs in threads for r in recs]
        done = [r for r in everything if t0 <= r["t_last"] <= t1]
        compared = verify.answers(done)
        if lost:
            compared.update(verify.state_held(lost_seen))
        for r in [r for r in everything if not r["ok"]][:8]:
            say(f"failed: {r['verb']} {r['key']} -> status {r['status']}"
                + (", answer wrong" if r["wrong"] else "")
                + f", {r['t_last'] - t0:+.2f} s from the window's open"
                + ("" if r in done else " (outside the window)"))
        for line in workers.log_lines(5):
            say("client: " + line)
        say(f"retried after a 503: {sum(r['retries'] > 0 for r in done)} "
            f"operations of the window, {sum(r['retries'] for r in done)} "
            "retries")

        # Sampled PUTs of the window: read back, then their drives against
        # the plain reference.
        heals = any(o["verb"] == "HEAL" for o in mix["ops"])
        if heals:   # the preloaded objects, once the last heal has answered
            sample = verify.sample_preloaded(
                objects, int(mix.get("verify_sample", 0)), seed)
        else:
            sample = verify.sample_puts(
                done, int(mix.get("verify_sample", 0)), seed)
        if sample:
            t = time.monotonic()

            reader = S3Http("127.0.0.1", server.port, ACCESS, SECRET,
                            timeout=READBACK_STALL_S)

            def ask(path: str) -> Reply:
                """An answer cut short, or one that stands still (drives
                lost under a body that is being sent), is an answer too:
                status 0."""
                try:
                    return reader.request("GET", path)
                except (OSError, http.client.HTTPException) as e:
                    reader.close()
                    now = time.monotonic()
                    return Reply(0, {}, b"", f"cut short: {e!r}".encode(),
                                 now, now, now)

            fetch = verify.PatientFetch(
                lambda key: ask(f"/{BUCKET}/{key}"),
                lambda: ask("/minio/health/cluster").status == 200,
                t1 + readback_wait_s)
            compared.update(verify.compare_puts(
                sample, pool, fetch, config, server.drive_roots, BUCKET,
                lost, "heal_drives" if heals else "write_quorum_drives"))
            say(f"read back: asked again {fetch.asked_again} times, "
                f"waited {fetch.waited_first_s:.2f} s for the program to "
                "say it is healthy, the "
                f"last answer {fetch.t_last_answer - t1:.2f} s past the "
                f"window's close; compile cache: {cache_entries(cdir)} "
                "entries after it")
            for line in fetch.log[:6]:
                note(line)
            reader.close()
            phases["compare"] = time.monotonic() - t
            if compared["readback_wrong"]["value"]:
                note("the server's log, its end:\n" + server.log_tail(2000))
            say(f"compared {len(sample)} "
                + ("preloaded objects" if heals else "sampled PUTs")
                + f" with the plain reference in {phases['compare']:.1f} s")

        admin.close()
        memory = server.stop()
        free1 = shutil.disk_usage(run_dir).free
        say(f"disk: {free1 / 2**30:.2f} GiB free at the end, "
            f"{(free0 - free1) / 2**30:.2f} GiB used by the run")
        shutil.rmtree(os.path.join(run_dir, "drives"), ignore_errors=True)

        seen_backends = scrape.backends(after)
        say(f"kernel observations by backend: {seen_backends}")
        if platform != "reference":
            require_device_backends(seen_backends, device["platform"])

        window_s = t1 - t0
        e2e = end_to_end(done, window_s, setup_s)
        # What the clients' own clocks say, for the layer metrics too.
        groups = traffic.preload_groups(mix, objects)   # a HEAL is of one
        window = {**e2e,
                  "client_ops": len([r for r in done if r["ok"]]),
                  "client_objects": sum(
                      len(groups[r["body_index"]]) if r["verb"] == "HEAL"
                      else 1 for r in done if r["ok"]),
                  "client_gap_pct": client_gap_pct(threads, t0, t1)}
        say("set-up: " + ", ".join(f"{k} {v:.2f} s" for k, v in phases.items()
                                   if k != "compare")
            + f"; setup_s {setup_s:.2f}")
        say(f"window: {window_s:.3f} s, {len(done)} operations completed, "
            f"{window['client_ops']} good, clients idle "
            f"{window['client_gap_pct']:.2f} %")
        say("the clients' clock, whether or not the cell reports it: "
            + json.dumps(e2e))

        say("drive operations past their health deadline: "
            f"{scrape.total(before, 'minio_tpu_drive_timeouts_total'):.0f} "
            "before the window, "
            f"{scrape.total(after, 'minio_tpu_drive_timeouts_total'):.0f} "
            "at its close; drives the program holds offline: "
            f"{scrape.count_at(before, 'minio_tpu_drive_state', 2)} and "
            f"{scrape.count_at(after, 'minio_tpu_drive_state', 2)}")
        if blank_roots:
            say("drives the program holds online: "
                f"{scrape.count_at(before, 'minio_tpu_drive_state', 0)} and "
                f"{scrape.count_at(after, 'minio_tpu_drive_state', 0)} of "
                f"{len(server.drive_roots)}")
        say("stages, ms per request over the window: " + json.dumps(
            scrape.stage_table(before, after)))

        result_device = {"platform": device["platform"],
                         "kind": device["kind"], "count": device["count"],
                         "memory_peak_bytes": int(
                             memory.get("memory_peak_bytes", 0))}
        metrics: dict[str, dict] = {}
        breakdown = None
        if not trace:
            for m in loaded["end_to_end"]:
                if m["name"] in e2e:
                    metrics[m["name"]] = {"value": e2e[m["name"]],
                                          "unit": m["unit"]}
        else:
            sliced = [r for r in everything
                      if tracer.t_begin <= r["t_last"] <= tracer.t_stop]
            good = [r for r in sliced if r["ok"]]
            in_slice = [(r["verb"], r["size"]) for r in good]
            if lost:   # and how many data shards each object has lost
                k, n = int(config["data_shards"]), int(config["drives"])
                lost_drives = [server.drive_roots.index(r) for r in lost]
                in_slice = [(r["verb"], r["size"], work.lost_data_shards(
                    reference.shard_of_drive(BUCKET, r["key"], n),
                    lost_drives, k)) for r in good]
                say("traced slice: operations by data shards lost: "
                    + json.dumps(dict(sorted(collections.Counter(
                        o[2] for o in in_slice).items()))))
            if heals:   # object by object, each rebuilt on every blank drive
                in_slice = [op for op in in_slice if op[0] != "HEAL"] + [
                    ("HEAL", o.size, len(blank_roots))
                    for r in good if r["verb"] == "HEAL"
                    for o in groups[r["body_index"]]]
                say(f"traced slice: {len(in_slice)} objects healed or "
                    "otherwise served")
            say(f"traced slice: {len(sliced)} operations answered, "
                f"{len(sliced) - len(good)} of them failed")
            red = reduce_trace(tracer.out_path, device["platform"])
            least = work.least_seconds(
                in_slice, int(config["data_shards"]),
                int(config["parity_shards"]), int(config["block_size"]),
                device["kind"]) if device["platform"] == "tpu" else None
            tr = dict(red["metrics"])
            # No operation completed in the slice: nothing to set against
            # the busy time, so no share is reported (never a 0).
            if least is not None and least["bytes"] > 0:
                tr["codec_roofline"] = (100.0 * least["hbm_s"]
                                            / red["busy_s_busiest"])
                say(f"traced slice: {len(good)} operations completed, "
                    f"{least['bytes']} codec bytes -> {least['hbm_s']:.6f} s "
                    f"at HBM speed, {least['int_ops']} int8 ops -> "
                    f"{least['int8_s']:.6f} s at the int8 peak; busiest "
                    f"device busy {red['busy_s_busiest']:.6f} s of "
                    f"{red['window_s']:.3f} s")
            ctx = {"before": before, "after": after, "window": window,
                   "trace": tr}
            for spec in loaded["per_layer"]:
                v = layer_value(spec, ctx)
                if v is not None:
                    metrics[spec["name"]] = {"value": v,
                                             "unit": spec["unit"]}
            result_device["busy_s"] = red["busy_s"]
            result_device["window_s"] = red["window_s"]
            breakdown = red["breakdown"]
            say("what the host did in an idle gap cannot be said: the "
                "program has no trace annotations yet")

        correct = verify.verdict(compared)
        for name, c in compared.items():
            note(f"compared {name}: {c['value']} (limit "
                 f"{'<=' if c['better'] == 'lower' else '>='} {c['limit']})")
        result = {"correct": correct, "attempted": len(done),
                  "failed": sum(not r["ok"] for r in done),
                  "metrics": metrics, "device": result_device}
        if breakdown is not None:
            result["breakdown"] = breakdown
        result["compared"] = {k: {"value": c["value"], "limit": c["limit"]}
                              for k, c in compared.items()}
        return result
    finally:
        if workers is not None:
            workers.kill()
        if server is not None:
            server.stop()
        shutil.rmtree(run_dir, ignore_errors=True)


def print_result(result: dict) -> None:
    """The last line of stdout, and nothing after it."""
    sys.stdout.flush()
    sys.stdout.write(json.dumps(result) + "\n")
    sys.stdout.flush()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except RunFailed as e:
        note(f"run.py: {e}")
        return 3
    print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
