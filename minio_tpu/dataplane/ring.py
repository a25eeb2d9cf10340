"""Device ring buffer: pre-allocated staging slots + lane kernel cache.

The staging half of the batched data plane (docs/DATAPLANE.md). A *lane*
is a fixed launch geometry — (op, k, m|t, shard-width bucket, rows) — and
every launch on a lane reuses one of a small ring of pre-allocated host
staging slots, so the steady-state path performs **zero per-batch
allocation** on the host side (MTPU005 discipline): request bytes are
memcpy'd into a recycled numpy slot, the H2D transfer reads straight out
of it, and the slot returns to the ring once the launch's outputs have
materialized (np.asarray on a launch OUTPUT blocks until the INPUT was
consumed — the same safe-reuse contract as utils/bufpool.py).

Double buffering falls out of the ring depth: with depth 2 the
dispatcher stages batch N+1 into the free slot while the device still
runs batch N's kernel; `acquire` blocks only when the device is a full
ring behind, which is exactly the throttle the submission plane wants.

Lane kernels are jitted once per lane shape (the shape set is bounded by
the pow-2 bucketing in `width_bucket`, so the jit cache cannot churn
under mixed object sizes — the MTPU recompilation audit in
tests/test_dataplane.py counts traces). Nothing is donated: no output
has the staged batch's shape, so there is nothing XLA could alias
(`_jit_lane`).
"""

from __future__ import annotations

import functools
import queue
import threading
from typing import NamedTuple

import numpy as np

OP_ENCODE = "encode"
OP_VERIFY = "verify"
OP_RECONSTRUCT = "reconstruct"

_MIN_WIDTH = 512  # narrowest staged shard width (bytes)


def width_bucket(s: int) -> int:
    """Shard-width bucket: next power of two >= s (floor _MIN_WIDTH).
    Zero padding is free for every lane op — parity columns never mix
    (erasure/codec.py), and mxsum digests are cap-invariant under the
    per-row length term (ops/mxsum.py) — so one compiled program serves
    every shard width inside the bucket. Delegates to THE pow-2 rule
    (utils/shardmath.pow2_bucket) shared with the per-object dispatch
    layer, so lane keys and codec staging can never round apart."""
    from minio_tpu.utils.shardmath import pow2_bucket

    return pow2_bucket(s, floor=_MIN_WIDTH)


def rows_bucket(b: int, cap: int) -> int:
    """Row-count bucket: next power of two >= b, capped at the lane
    capacity. Bounds the trace count per lane to log2(cap)+1."""
    from minio_tpu.utils.shardmath import pow2_bucket

    return min(pow2_bucket(b), cap)


class LaneKey(NamedTuple):
    """One launch geometry. `aux` is m for encode lanes, the padded
    target count for reconstruct lanes, 0 for verify lanes; `digests`
    only distinguishes encode lanes (fused digest output or not)."""

    op: str
    k: int
    aux: int
    width: int
    rows: int
    digests: bool


class Slot:
    """One pre-allocated staging slot: `data` is the batch array the
    kernel consumes, `lens` the per-row chunk lengths (encode/verify),
    `weights` the per-row decode matrices (reconstruct only)."""

    __slots__ = ("data", "lens", "weights")

    def __init__(self, key: LaneKey):
        if key.op == OP_VERIFY:
            self.data = np.zeros((key.rows, key.width), dtype=np.uint8)
        else:
            self.data = np.zeros((key.rows, key.k, key.width),
                                 dtype=np.uint8)
        self.lens = np.zeros((key.rows,), dtype=np.int32)
        self.weights = (
            np.zeros((key.rows, key.k * 8, key.aux * 8), dtype=np.int8)
            if key.op == OP_RECONSTRUCT else None)


class SlotRing:
    """Fixed pool of staging slots for one lane. acquire() blocks while
    every slot is in flight — the back half of the double buffer."""

    def __init__(self, key: LaneKey, depth: int):
        self._free: queue.Queue[Slot] = queue.Queue()
        for _ in range(depth):
            self._free.put(Slot(key))

    def acquire(self, timeout: float | None = None) -> Slot:
        return self._free.get(timeout=timeout)

    def release(self, slot: Slot) -> None:
        self._free.put(slot)


class RingPool:
    """Lazily-built SlotRing per lane key. The lane key space is bounded
    (pow-2 width/rows buckets x the deployment's (k, m) geometries), so
    rings persist for the plane's lifetime; close() drops them."""

    def __init__(self, depth: int = 2):
        self.depth = depth
        self._mu = threading.Lock()
        self._rings: dict[LaneKey, SlotRing] = {}

    def ring(self, key: LaneKey) -> SlotRing:
        with self._mu:
            ring = self._rings.get(key)
            if ring is None:
                ring = self._rings[key] = SlotRing(key, self.depth)
            return ring

    def clear(self) -> None:
        with self._mu:
            self._rings.clear()


@functools.lru_cache(maxsize=1)
def _row_sharding():
    """Batch-dim NamedSharding over every local device, or None on a
    single-device host. A coalesced lane launch is embarrassingly
    row-parallel (no cross-row op anywhere in the fused kernels), so
    dp-sharding it spreads one launch across the whole local device set
    — the serving-lane form of the mesh codec's dp axis. On the forced
    8-device CPU mesh (tests/bench) this is also what lets one big
    launch use 8 cores instead of one."""
    import jax

    devs = jax.devices()
    if len(devs) <= 1:
        return None
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    import numpy as _np

    mesh = Mesh(_np.array(devs), ("dp",))
    return NamedSharding(mesh, PartitionSpec("dp"))


@functools.lru_cache(maxsize=256)
def lane_kernel(key: LaneKey):
    """The lane's jitted launch fn. Cached per lane key — fixed shapes
    in, fixed shapes out, so exactly one trace per lane.

    encode      (data [R,k,W], lens [R]) -> (parity [R,m,W], digs|None)
    verify      (data [R,W],   lens [R]) -> digs [R,32]
    reconstruct (data [R,k,W], w [R,k*8,t*8]) -> rebuilt [R,t,W]
    reconstruct+digests adds lens [R] and fuses the rebuilt chunks'
    mxsum digests into the SAME launch (the heal lane — parity with
    codec.begin_reconstruct's fused digests, so a heal batch never
    pays a second queued launch for its bitrot frames)
    """
    from minio_tpu.ops import fused, rs_xla

    k, m = key.k, key.aux
    nargs = 2
    if key.op == OP_ENCODE and key.digests:
        def launch(data, lens):
            return fused.encode_with_digests(data, k, m, lens)
    elif key.op == OP_ENCODE:
        def launch(data, lens):
            return fused.encode_only(data, k, m), None
    elif key.op == OP_VERIFY:
        def launch(data, lens):
            return fused.verify_digests(data, lens)
    elif key.op == OP_RECONSTRUCT and key.digests:
        t = key.aux
        nargs = 3

        def launch(data, weights, lens):
            import jax.numpy as jnp

            rebuilt = rs_xla.gf2_matmul_multi(data, weights, t)
            r, _t, w = rebuilt.shape
            digs = fused.verify_digests(
                rebuilt.reshape(r * t, w), jnp.repeat(lens, t))
            return rebuilt, digs.reshape(r, t, -1)
    else:
        t = key.aux

        def launch(data, weights):
            return rs_xla.gf2_matmul_multi(data, weights, t)

    return _jit_lane(launch, nargs, key.rows, _row_sharding(),
                     name=lane_name(key))


def lane_name(key: LaneKey) -> str:
    """The lane program's name in a device trace and in the compile
    counters: `lane_encode_k8m4_w16384_r4_d` (jit prefixes `jit_`). `m`
    reads `t` on reconstruct lanes (the padded target count), a trailing
    `_d` says the launch fuses the digests."""
    aux = {OP_ENCODE: f"m{key.aux}", OP_RECONSTRUCT: f"t{key.aux}"}.get(
        key.op, "")
    return (f"lane_{key.op}_k{key.k}{aux}_w{key.width}_r{key.rows}"
            + ("_d" if key.digests else ""))


def _jit_lane(launch, nargs: int, rows: int, shard, name: str = ""):
    """jit a lane launch, dp-sharded over `shard`'s devices when the row
    count divides over them. The split is an explicit shard_map, not
    in_shardings on a plain jit: the SPMD partitioner cannot split a
    Pallas (Mosaic) kernel — the TPU compiler refuses the program with
    "Mosaic kernels cannot be automatically partitioned" — and a lane
    launch has no cross-row op, so each device simply runs the launch on
    its own rows.

    The staged batch is not donated: no output of a lane launch has the
    input's shape, so XLA can alias nothing to it, and on the chip every
    lane compile only warned "Some donated buffers were not usable"."""
    import jax

    if shard is not None and rows % shard.mesh.size == 0:
        launch = jax.shard_map(
            launch, mesh=shard.mesh, in_specs=(shard.spec,) * nargs,
            out_specs=shard.spec, check_vma=False)
    if name:
        # jit names the program after the function: `jit_launch` for every
        # lane says nothing in a trace.
        launch.__name__ = launch.__qualname__ = name
    return jax.jit(launch)


def trace_count() -> int:
    """Compiled lane-program count (recompilation probe for tests)."""
    return lane_kernel.cache_info().currsize
