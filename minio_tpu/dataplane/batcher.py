"""Submission plane: coalesce concurrent codec work into lane launches.

Request threads (PUT shard-encodes, GET reconstructions, bitrot
verifies) enqueue `CodecRequest`s and immediately get futures back; ONE
dispatcher thread drains the queue into fixed-shape lane batches
bucketed by (op, k, m|t, shard-width bucket) and launches each batch as
a single fused kernel (ring.lane_kernel) instead of one dispatch per
object — the serving-layer form of the restructure-many-small-codec-
calls-into-batches move (PAPERS.md, XOR-EC program optimization), and
the "device ring buffer" PAPER.md's north star names.

Batching policy (adaptive, env-tunable — docs/DATAPLANE.md):
  * launch when the lane FILLS (a burst rides one launch), OR
  * when the oldest request in the lane has waited MTPU_DP_MAX_WAIT_US
    (default 500 us) — a lone request keeps bounded latency.

Backpressure: the submission queue is bounded (MTPU_DP_QUEUE requests);
a full queue rejects the submit with `OperationTimedOut`, which the S3
layer already maps to 503 SlowDown — the front door degrades instead of
buffering unbounded batches in memory.

Pipeline: the dispatcher only STAGES (memcpy into a recycled ring slot)
and DISPATCHES (async JAX launch); a separate completion thread
materializes outputs, resolves futures and recycles slots, so host
staging of batch N+1 overlaps the device kernel of batch N (ring depth
2 = classic double buffering; `SlotRing.acquire` is the throttle when
the device falls a full ring behind).

Bit-exactness: lane padding is invisible in results — parity columns
never mix (zero-padded shard tails encode to zero parity and are sliced
off) and mxsum digests are cap-invariant (length rides as data) — so
batched output is bit-identical to the per-object dispatch, which stays
both the fallback and the oracle (tests/test_dataplane.py).
"""

from __future__ import annotations

import queue
import threading
import time
from concurrent.futures import Future, InvalidStateError

import numpy as np

from minio_tpu.dataplane import ring
from minio_tpu import obs
from minio_tpu.obs import flight
from minio_tpu.obs import kernel as obs_kernel
from minio_tpu.ops import staging
from minio_tpu import qos
from minio_tpu.utils import admission
from minio_tpu.utils import errors as se
from minio_tpu.utils.shardmath import ceil_div as _ceil_div, pow2_bucket

_CLOSE = object()

DEFAULT_LANE_BLOCKS = 32    # encode/reconstruct rows per launch
DEFAULT_VERIFY_ROWS = 128   # verify chunks per launch
DEFAULT_MAX_WAIT_US = 500   # lone-request latency bound (microseconds)
DEFAULT_QUEUE_CAP = 256     # bounded submission queue (requests)
DEFAULT_RING_DEPTH = 4      # staging slots per lane (double buffer+)


def _backend() -> str:
    """The shared kernel-metrics backend label (ops/fused.py owns the
    format — dp_* rows must join with every other kernel row)."""
    from minio_tpu.ops import fused

    return fused._backend()


class _BaseKey(tuple):
    """Accumulation key: LaneKey minus the row bucket (rows are decided
    at launch time from the fill)."""

    __slots__ = ()

    def __new__(cls, op: str, k: int, aux: int, width: int, digests: bool):
        return super().__new__(cls, (op, k, aux, width, digests))

    @property
    def op(self) -> str:
        return self[0]


class CodecRequest:
    """One submitted unit of codec work: `rows` staging slots, a stage
    callback run by the dispatcher, a finish callback run by the
    completion thread, and the future request threads wait on."""

    __slots__ = ("base", "rows", "stage", "finish", "future", "t_submit",
                 "trace_id", "tl", "tenant")

    def __init__(self, base: _BaseKey, rows: int, stage, finish):
        self.base = base
        self.rows = rows
        self.stage = stage
        self.finish = finish
        self.future: Future = Future()
        self.t_submit = time.perf_counter()
        # Critical-path attribution: the submitting request's trace id
        # and flight-recorder timeline ride the request through the
        # dispatcher/completion threads (which have no request context).
        self.trace_id = obs.trace_id()
        self.tl = flight.current()
        # QoS attribution: whose lane slots this work consumes. Captured
        # at construction like the trace id — worker 0's coalesced lanes
        # schedule rows by this key even when the submitting context is
        # a ring worker restoring identity from the slot header.
        self.tenant = qos.current_key()


class _OpenBatch:
    __slots__ = ("base", "reqs", "fill", "first_ts")

    def __init__(self, base: _BaseKey):
        self.base = base
        self.reqs: list[CodecRequest] = []
        self.fill = 0
        self.first_ts = time.perf_counter()


class PendingBatchedEncode:
    """Drop-in for codec.PendingEncode on the batched plane: wait()
    returns the same (per-block chunk rows, per-block digests | None)
    shape, with data chunks aliasing the caller's block buffers and
    parity chunks aliasing the batch launch output."""

    def __init__(self, k: int, m: int, groups):
        # groups: list of (request, blocks, chunk_lens, flats)
        self._k = k
        self._m = m
        self._groups = groups

    def wait(self):
        k, m = self._k, self._m
        out_chunks: list[list[memoryview]] = []
        out_digs: list[list[bytes]] | None = None
        for req, blocks, lens, flats in self._groups:
            parity, digs = req.future.result()
            if digs is not None and out_digs is None:
                out_digs = []
            staging.encode_rows(k, m, blocks, lens, flats, parity, digs,
                                out_chunks, out_digs)
        return out_chunks, out_digs


class PendingBatchedReconstruct:
    """Drop-in for codec.PendingDecode on the batched plane: wait()
    returns the same (per block: rebuilt chunk per target, per block:
    digest per target | None) shape. Rebuilt chunks AND their mxsum
    digests come out of one digest-fused reconstruct-lane launch
    (ring.lane_kernel) shared with every concurrent heal, not one
    dispatch per object — parity with codec.begin_reconstruct's fused
    digests."""

    def __init__(self, targets: tuple[int, ...], chunk_lens: list[int],
                 groups, with_digests: bool):
        self.targets = targets
        self._lens = chunk_lens
        self._groups = groups  # list of (request, nrows)
        self._digests = with_digests

    def wait(self):
        t = len(self.targets)
        out_chunks: list[list[bytes]] = []
        out_digs: list[list[bytes]] | None = [] if self._digests else None
        bi = 0
        for req, nrows in self._groups:
            res = req.future.result()
            rebuilt, digs = res if isinstance(res, tuple) else (res, None)
            staging.rebuilt_rows(rebuilt, digs, self._lens[bi:bi + nrows],
                                 t, out_chunks, out_digs)
            bi += nrows
        return out_chunks, out_digs


class BatchPlane:
    """The process-wide batched device data plane (docs/DATAPLANE.md).

    One dispatcher + one completion thread; request threads only enqueue
    and wait futures. All knobs resolve env vars at construction so the
    global plane follows deployment config and tests can pin values."""

    def __init__(self, *, lane_blocks: int | None = None,
                 verify_rows: int | None = None,
                 max_wait_s: float | None = None,
                 queue_cap: int | None = None,
                 ring_depth: int | None = None,
                 name: str = "mtpu-dataplane"):
        import os

        env = os.environ.get
        self.lane_blocks = lane_blocks if lane_blocks is not None else int(
            env("MTPU_DP_LANE_BLOCKS", str(DEFAULT_LANE_BLOCKS)))
        self.verify_rows = verify_rows if verify_rows is not None else int(
            env("MTPU_DP_VERIFY_ROWS", str(DEFAULT_VERIFY_ROWS)))
        self.max_wait_s = max_wait_s if max_wait_s is not None else float(
            env("MTPU_DP_MAX_WAIT_US", str(DEFAULT_MAX_WAIT_US))) / 1e6
        cap = queue_cap if queue_cap is not None else int(
            env("MTPU_DP_QUEUE", str(DEFAULT_QUEUE_CAP)))
        depth = ring_depth if ring_depth is not None else int(
            env("MTPU_DP_RING_DEPTH", str(DEFAULT_RING_DEPTH)))
        # Admission queue: plain bounded queue, or a tenant-fair DRR
        # queue when the QoS plane is armed (MTPU_QOS=1). Cost model:
        # rows x block width ~ staged bytes, so byte quotas meter real
        # lane occupancy, not request counts.
        self._q = qos.plane_queue(
            "dataplane", cap,
            tenant_of=lambda r: r.tenant,
            cost_of=lambda r: r.rows * max(1, r.base[3]),
            is_control=lambda it: it is _CLOSE)
        self._done_q: queue.Queue = queue.Queue()
        self._rings = ring.RingPool(depth=depth)
        self._open: dict[_BaseKey, _OpenBatch] = {}  # dispatcher-only
        self._closed = False
        self._close_mu = threading.Lock()
        self._broken: BaseException | None = None
        # Test hook: clearing the gate parks the dispatcher so the
        # bounded queue can be filled deterministically.
        self._gate = threading.Event()
        self._gate.set()
        # Plane-local stats: launch/request/row counters are written by
        # the dispatcher thread only; "rejected" is written by request
        # threads under _close_mu. Readable anywhere.
        self._stats = {"launches": 0, "requests": 0, "rows": 0,
                       "capacity": 0, "rejected": 0}
        self._dispatch_t = threading.Thread(
            target=self._dispatch_loop, daemon=True,
            name=f"{name}-dispatch")
        self._complete_t = threading.Thread(
            target=self._complete_loop, daemon=True,
            name=f"{name}-complete")
        self._dispatch_t.start()
        self._complete_t.start()

    # ------------------------------------------------------------------
    # submission API (request threads)
    # ------------------------------------------------------------------

    def begin_encode(self, k: int, m: int, block_size: int,
                     blocks: list[bytes],
                     with_digests: bool = False) -> PendingBatchedEncode:
        """Queue a batch of erasure blocks for coalesced encode (+fused
        mxsum digests). Same result contract as codec.begin_encode."""
        if m <= 0:
            raise ValueError("batched plane needs parity shards (m > 0)")
        if not blocks:
            return PendingBatchedEncode(k, m, [])
        # Validate EVERY block before submitting any group — exactly
        # like codec.begin_encode stages nothing on a bad batch; a
        # mid-list reject must not leave earlier groups already queued.
        for bi, block in enumerate(blocks):
            if not 0 < len(block) <= block_size:
                raise ValueError(f"block {bi} size {len(block)}")
        # Width-bucket by the batch's ACTUAL chunk length, not the
        # codec's full shard width: a 10 KiB object rides a narrow lane
        # instead of a 1 MiB-block-wide one. Bit-exact either way —
        # parity columns never mix and digests are cap-invariant — but
        # the device stops paying for padded zeros.
        s_max = max(_ceil_div(len(b), k) for b in blocks)
        width = ring.width_bucket(s_max)
        base = _BaseKey(ring.OP_ENCODE, k, m, width, with_digests)
        groups = []
        for g0 in range(0, len(blocks), self.lane_blocks):
            grp = blocks[g0:g0 + self.lane_blocks]
            lens: list[int] = []
            flats: list[np.ndarray | None] = []
            views: list[np.ndarray] = []
            for block in grp:
                s, flat, view = staging.split_block(block, k)
                lens.append(s)
                flats.append(flat)
                views.append(view)

            def stage(slot, row0, views=views, lens=lens):
                for bi, v in enumerate(views):
                    s = lens[bi]
                    r = row0 + bi
                    slot.data[r, :, :s] = v
                    slot.data[r, :, s:] = 0
                    slot.lens[r] = s

            def finish(outs, row0, nrows=len(grp)):
                parity, digs = outs
                return (parity[row0:row0 + nrows],
                        digs[row0:row0 + nrows] if digs is not None
                        else None)

            req = CodecRequest(base, len(grp), stage, finish)
            self._submit(req)
            groups.append((req, grp, lens, flats))
        return PendingBatchedEncode(k, m, groups)

    def digest_chunks(self, chunks: list, cap: int) -> list[bytes]:
        """Coalesced mxsum256 digests of a ragged list of byte chunks
        (each <= cap) — same contract as fused.digest_chunks_host, but
        many concurrent readers share one launch."""
        if not chunks:
            return []
        # Width from the longest chunk actually present (<= cap): the
        # digest of a chunk is identical under any staging cap, so the
        # lane only needs to fit the bytes it carries.
        width = ring.width_bucket(max(len(c) for c in chunks) or 1)
        base = _BaseKey(ring.OP_VERIFY, 0, 0, width, True)
        reqs = []
        for g0 in range(0, len(chunks), self.verify_rows):
            grp = chunks[g0:g0 + self.verify_rows]

            def stage(slot, row0, grp=grp):
                for ci, c in enumerate(grp):
                    r = row0 + ci
                    ln = len(c)
                    slot.data[r, :ln] = np.frombuffer(c, dtype=np.uint8)
                    slot.data[r, ln:] = 0
                    slot.lens[r] = ln

            def finish(outs, row0, nrows=len(grp)):
                return outs[row0:row0 + nrows]

            req = CodecRequest(base, len(grp), stage, finish)
            self._submit(req)
            reqs.append(req)
        out: list[bytes] = []
        for req in reqs:
            digs = req.future.result()
            out.extend(digs[i].tobytes() for i in range(req.rows))
        return out

    def decode_blocks(self, k: int, m: int, block_size: int,
                      shard_chunks: list[list[bytes | None]],
                      block_lens: list[int],
                      need_all: bool = False) -> list[list[bytes]]:
        """codec.decode_blocks through the coalesced plane. Mixed failure
        patterns batch natively: every row carries its own decode matrix
        as runtime DATA (gf2_matmul_multi), so concurrent GETs with
        different dead drives still share one launch."""
        from minio_tpu.ops import rs_xla

        n = k + m
        if not shard_chunks:
            return []
        want, per_block, t_max = staging.plan_rebuild(
            shard_chunks, k, n, need_all)
        if t_max == 0:
            return [[row[i] for i in want] for row in shard_chunks]  # type: ignore[misc]
        chunk_lens = [_ceil_div(bl, k) for bl in block_lens]

        t_pad = pow2_bucket(t_max)  # pow2 target-count lane
        width = ring.width_bucket(max(chunk_lens))
        base = _BaseKey(ring.OP_RECONSTRUCT, k, t_pad, width, False)
        groups = []
        for g0 in range(0, len(shard_chunks), self.lane_blocks):
            rows_grp = shard_chunks[g0:g0 + self.lane_blocks]
            pb_grp = per_block[g0:g0 + self.lane_blocks]
            weights = []
            for (survivors, targets) in pb_grp:
                if targets:
                    weights.append(rs_xla._decode_weights_np(
                        k, n, survivors, targets))
                else:
                    weights.append(None)

            def stage(slot, row0, rows_grp=rows_grp, pb_grp=pb_grp,
                      weights=weights):
                for bi, row in enumerate(rows_grp):
                    r = row0 + bi
                    survivors, targets = pb_grp[bi]
                    for ci, si in enumerate(survivors):
                        c = row[si]
                        slot.data[r, ci, :len(c)] = np.frombuffer(
                            c, dtype=np.uint8)
                        slot.data[r, ci, len(c):] = 0
                    w = weights[bi]
                    if w is None:
                        slot.weights[r] = 0
                    else:
                        tw = w.shape[1]
                        slot.weights[r, :, :tw] = w
                        slot.weights[r, :, tw:] = 0

            def finish(outs, row0, nrows=len(rows_grp)):
                return outs[row0:row0 + nrows]

            req = CodecRequest(base, len(rows_grp), stage, finish)
            self._submit(req)
            groups.append((req, rows_grp, pb_grp,
                           chunk_lens[g0:g0 + self.lane_blocks]))

        out: list[list[bytes]] = []
        for req, rows_grp, pb_grp, lens_grp in groups:
            out += staging.patch_rows(rows_grp, pb_grp, lens_grp,
                                      req.future.result(), want)
        return out

    def begin_reconstruct(self, k: int, m: int, block_size: int,
                          shard_chunks: list[list[bytes | None]],
                          block_lens: list[int],
                          targets: tuple[int, ...],
                          with_digests: bool = False
                          ) -> "PendingBatchedReconstruct":
        """codec.begin_reconstruct through the coalesced plane — the
        heal shape: every block in the batch shares ONE failure pattern
        (fixed survivors, fixed rebuild targets), but concurrent heals
        of different objects with DIFFERENT patterns still share a lane
        launch because each row carries its own decode matrix as data
        (gf2_matmul_multi), and with_digests fuses the rebuilt chunks'
        mxsum digests into the SAME launch — a whole-set heal issues
        coalesced single launches instead of one dispatch per object.
        Same result contract as codec.begin_reconstruct."""
        from minio_tpu.ops import rs_xla
        n = k + m
        if not shard_chunks:
            return PendingBatchedReconstruct(tuple(targets), [], [], False)
        survivors = staging.one_pattern_survivors(shard_chunks, k, n)
        targets = tuple(targets)
        chunk_lens = [_ceil_div(bl, k) for bl in block_lens]
        t_pad = pow2_bucket(max(1, len(targets)))
        width = ring.width_bucket(max(chunk_lens))
        base = _BaseKey(ring.OP_RECONSTRUCT, k, t_pad, width,
                        with_digests)
        w = rs_xla._decode_weights_np(k, n, survivors, targets) \
            if targets else None
        groups = []
        for g0 in range(0, len(shard_chunks), self.lane_blocks):
            rows_grp = shard_chunks[g0:g0 + self.lane_blocks]
            lens_grp = chunk_lens[g0:g0 + self.lane_blocks]

            def stage(slot, row0, rows_grp=rows_grp, lens_grp=lens_grp,
                      w=w):
                for bi, row in enumerate(rows_grp):
                    r = row0 + bi
                    for ci, si in enumerate(survivors):
                        c = row[si]
                        slot.data[r, ci, :len(c)] = np.frombuffer(
                            c, dtype=np.uint8)
                        slot.data[r, ci, len(c):] = 0
                    slot.lens[r] = lens_grp[bi]
                    if w is None:
                        slot.weights[r] = 0
                    else:
                        tw = w.shape[1]
                        slot.weights[r, :, :tw] = w
                        slot.weights[r, :, tw:] = 0

            def finish(outs, row0, nrows=len(rows_grp)):
                if isinstance(outs, tuple):  # digest-fused heal lane
                    rebuilt, digs = outs
                    return (rebuilt[row0:row0 + nrows],
                            digs[row0:row0 + nrows])
                return outs[row0:row0 + nrows]

            req = CodecRequest(base, len(rows_grp), stage, finish)
            self._submit(req)
            groups.append((req, len(rows_grp)))
        return PendingBatchedReconstruct(targets, chunk_lens, groups,
                                         with_digests)

    # ------------------------------------------------------------------
    # plumbing
    # ------------------------------------------------------------------

    def _submit(self, req: CodecRequest) -> None:
        if self._closed:
            raise admission.shed(
                "dataplane", "closed", "batched dataplane is closed")
        if self._broken is not None:
            raise se.OperationTimedOut(
                msg=f"batched dataplane failed: {self._broken}")
        try:
            self._q.put_nowait(req)
        except queue.Full as e:
            with self._close_mu:  # rejected count: cross-thread writes
                self._stats["rejected"] += 1
            obs_kernel.dataplane_rejected(req.base.op)
            # Unified admission: a full lane sheds exactly like a full
            # WAL queue — OperationTimedOut -> 503 SlowDown, one shared
            # shed family (utils/admission.py). A QoS token-bucket
            # reject is the same wire contract, distinct cause slug.
            if isinstance(e, qos.QuotaFull):
                raise admission.shed(
                    "dataplane", "tenant_quota",
                    "tenant over dataplane rate quota") from None
            raise admission.shed(
                "dataplane", "lane_full",
                "batched dataplane saturated (bounded queue full)"
            ) from None
        if self._closed and not self._dispatch_t.is_alive():
            # TOCTOU with close(): the pre-put closed check passed, but
            # close() drained the queue and joined the dispatcher before
            # our put landed — nothing will ever consume it. Fail every
            # straggler (FIFO: anything still queued after the
            # dispatcher exited is post-close) so no future is orphaned.
            self._drain_failed(se.OperationTimedOut(
                msg="batched dataplane closed"))

    def _capacity(self, base: _BaseKey) -> int:
        return (self.verify_rows if base.op == ring.OP_VERIFY
                else self.lane_blocks)

    def _next_deadline(self) -> float | None:
        """Seconds until the oldest open batch must launch (None: no
        open batches — block on the queue)."""
        if not self._open:
            return None
        now = time.perf_counter()
        first = min(b.first_ts for b in self._open.values())
        return max(0.0, first + self.max_wait_s - now)

    def _dispatch_loop(self) -> None:
        try:
            while True:
                self._gate.wait()
                timeout = self._next_deadline()
                try:
                    item = self._q.get(timeout=timeout)
                except queue.Empty:
                    item = None
                if item is _CLOSE:
                    self._flush(force=True)
                    break
                if item is not None:
                    self._add(item)
                self._flush(force=False)
        except BaseException as e:  # noqa: BLE001 - relay to waiters
            self._broken = e
            self._fail_open(e)
            self._drain_failed(e)
        finally:
            self._done_q.put(_CLOSE)

    def _add(self, req: CodecRequest) -> None:
        cap = self._capacity(req.base)
        batch = self._open.get(req.base)
        if batch is not None and batch.fill + req.rows > cap:
            self._launch(batch)
            batch = None
        if batch is None:
            batch = self._open[req.base] = _OpenBatch(req.base)
        batch.reqs.append(req)
        batch.fill += req.rows

    def _flush(self, force: bool) -> None:
        now = time.perf_counter()
        for base in list(self._open):
            batch = self._open[base]
            if (force or batch.fill >= self._capacity(base)
                    or now - batch.first_ts >= self.max_wait_s):
                self._launch(batch)

    def _launch(self, batch: _OpenBatch) -> None:
        self._open.pop(batch.base, None)
        op, k, aux, width, digests = batch.base
        cap = self._capacity(batch.base)
        rb = ring.rows_bucket(batch.fill, cap)
        slot_key = ring.LaneKey(op, k, aux, width, cap, digests)
        slot = self._rings.ring(slot_key).acquire()
        try:
            row0 = 0
            for req in batch.reqs:
                req.stage(slot, row0)
                row0 += req.rows
            kern = ring.lane_kernel(
                ring.LaneKey(op, k, aux, width, rb, digests))
            t0 = time.perf_counter()
            # The dispatcher's own span around the shared launch: bus
            # record and device-profile annotation; the members' timeline
            # entries are the stamps below.
            with flight.span("dp_launch", "dataplane", timeline=False,
                             op=op, rows=rb):
                if op == ring.OP_RECONSTRUCT and digests:
                    # Heal lane: rebuilt chunks + their mxsum digests in
                    # ONE launch (lens drive the cap-invariant digest).
                    outs = kern(slot.data[:rb], slot.weights[:rb],
                                slot.lens[:rb])
                elif op == ring.OP_RECONSTRUCT:
                    outs = kern(slot.data[:rb], slot.weights[:rb])
                else:
                    outs = kern(slot.data[:rb], slot.lens[:rb])
            obs_kernel.observe(
                f"dp_{op}", _backend(), t0, blocks=rb,
                nbytes=int(slot.data[:rb].size),
                out=outs)
            now = time.perf_counter()
            obs_kernel.dataplane_launch(
                op, batch.fill, cap,
                [now - r.t_submit for r in batch.reqs])
            for r in batch.reqs:
                if r.tl is not None:
                    # Queue wait = submit → kernel dispatch (batching
                    # wait + staging memcpy); launch = the device
                    # dispatch for the whole batch.
                    r.tl.stamp("dp_queue_wait", t0 - r.t_submit,
                               "dataplane", end=t0)
                    r.tl.stamp("dp_launch", now - t0, "dataplane", end=now)
            if obs.has_subscribers():
                obs.publish({
                    "type": "batch", "plane": "dataplane", "op": op,
                    "rows": batch.fill, "capacity": cap,
                    "requests": len(batch.reqs),
                    "members": [r.trace_id for r in batch.reqs
                                if r.trace_id],
                    "time": time.time(),
                    "durationNs": int((now - t0) * 1e9)})
            st = self._stats
            st["launches"] += 1
            st["requests"] += len(batch.reqs)
            st["rows"] += batch.fill
            st["capacity"] += cap
        except BaseException as e:  # noqa: BLE001 - fail this batch only
            for req in batch.reqs:
                if not req.future.done():
                    req.future.set_exception(e)
            self._rings.ring(slot_key).release(slot)
            if not isinstance(e, Exception):
                raise
            return
        self._done_q.put((slot_key, slot, outs, batch.reqs))

    def _complete_loop(self) -> None:
        while True:
            item = self._done_q.get()
            if item is _CLOSE:
                return
            self._finish_host(*item)

    def _finish_host(self, slot_key, slot, outs, reqs) -> None:
        """Materialize one launch (the only device->host sync point),
        resolve its requests' futures, recycle the slot."""
        try:
            t0 = time.perf_counter()
            with flight.span("dp_materialize", "dataplane", timeline=False,
                             op=slot_key.op, rows=slot_key.rows):
                if slot_key.op == ring.OP_ENCODE:
                    parity, digs = outs
                    mat = (np.asarray(parity),
                           np.asarray(digs) if digs is not None else None)
                elif (slot_key.op == ring.OP_RECONSTRUCT
                      and slot_key.digests):
                    rebuilt, digs = outs
                    mat = (np.asarray(rebuilt), np.asarray(digs))
                else:
                    mat = np.asarray(outs)
            t1 = time.perf_counter()
            for req in reqs:
                if req.tl is not None:
                    req.tl.stamp("dp_materialize", t1 - t0, "dataplane",
                                 end=t1)
            row0 = 0
            for req in reqs:
                try:
                    req.future.set_result(req.finish(mat, row0))
                except Exception as e:  # noqa: BLE001 - per-request
                    if not req.future.done():
                        req.future.set_exception(e)
                row0 += req.rows
        except BaseException as e:  # noqa: BLE001 - fail the whole batch
            for req in reqs:
                if not req.future.done():
                    req.future.set_exception(
                        e if isinstance(e, Exception)
                        else RuntimeError(repr(e)))
        finally:
            self._rings.ring(slot_key).release(slot)

    def _fail_open(self, e: BaseException) -> None:
        err = e if isinstance(e, Exception) else RuntimeError(repr(e))
        for batch in self._open.values():
            for req in batch.reqs:
                if not req.future.done():
                    req.future.set_exception(err)
        self._open.clear()

    def _drain_failed(self, e: BaseException) -> None:
        err = e if isinstance(e, Exception) else RuntimeError(repr(e))
        while True:
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                return
            if item is _CLOSE:
                continue
            try:
                item.future.set_exception(err)
            except InvalidStateError:
                pass  # a racing drainer already resolved this future

    # ------------------------------------------------------------------
    # lifecycle / introspection
    # ------------------------------------------------------------------

    def close(self, timeout: float = 30.0) -> None:
        """Stop accepting work, drain every in-flight batch (all futures
        resolve — none orphaned), then join both threads."""
        with self._close_mu:
            if self._closed:
                return
            self._closed = True
        self._gate.set()
        self._q.put(_CLOSE)
        self._dispatch_t.join(timeout)
        self._complete_t.join(timeout)
        # Late racers that slipped into the queue after _CLOSE: fail
        # them rather than leaving futures forever pending.
        self._drain_failed(se.OperationTimedOut(
            msg="batched dataplane closed"))
        self._rings.clear()

    @property
    def closed(self) -> bool:
        return self._closed

    def stats(self) -> dict:
        st = dict(self._stats)
        st["mean_occupancy"] = (st["rows"] / st["capacity"]
                                if st["capacity"] else 0.0)
        return st
