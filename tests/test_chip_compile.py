"""The served path's device programs, compiled for a DESCRIBED TPU v5e.

No chip is attached here: the TPU compiler that ships with JAX compiles for
a topology that is only described, and raises what the chip's compiler
would raise (a tile that does not align, too much VMEM, a program that
does not fit HBM, a kernel that cannot be partitioned). Nothing runs, so
these tests say nothing about results or speed — chip_smoke.py does that
on the chip. The shapes are the ones a CPU rehearsal of chip_smoke.py
launches on a 16-drive EC 12+4 set with 1 MiB blocks (full blocks stage at
W = ceil(1 MiB / 12) = 87382, which the Pallas dispatch pads in-graph to
87552; lane launches at pow2 widths), plus the 8+4 north-star geometry and
the four-chip mesh program.

Rules this file keeps (on-chip-measurement guide, section 2): the topology
is described inside a module-scoped fixture, never at import, in a skipif or
in parametrize arguments; compiles run in this process; the persistent
compile cache is off around them; everything lives in this ONE file so one
xdist worker owns libtpu.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described device would be written to the persistent
    # cache and could never be read back without a chip.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def pallas_route(monkeypatch):
    """rs_pallas.use_pallas() asks jax.default_backend(), which is the CPU
    here; the existing MTPU_USE_PALLAS switch selects the route the chip
    takes. Traces are made through FRESH jits of the raw functions so a
    trace another test cached under the XLA route cannot be reused."""
    monkeypatch.setenv("MTPU_USE_PALLAS", "1")


def _raw(observed_jit):
    """fused.X is _observed(jax.jit(fn)): unwrap to fn."""
    return observed_jit.__wrapped__.__wrapped__


def _compile(fn, *shapes, **static):
    lowered = fn.lower(*shapes, **static)
    compiled = lowered.compile()
    return lowered.as_text(), compiled


@pytest.mark.parametrize("b,k,m,s", [
    (16, 12, 4, 87382),    # served 12+4 full blocks (pads to 87552)
    (16, 8, 4, 131072),    # 8+4, 1 MiB blocks
    (8, 12, 4, 16384),     # a 128 KiB object's lane launch
    (16, 2, 2, 524288),    # 2+2: the shallowest contraction
    (1, 8, 4, 1280),       # tiny and unaligned
])
def test_fused_encode_compiles_with_pallas(one_chip, pallas_route, b, k, m, s):
    from minio_tpu.ops import fused

    fn = jax.jit(_raw(fused.encode_with_digests), static_argnames=("k", "m"))
    text, compiled = _compile(
        fn, jax.ShapeDtypeStruct((b, k, s), jnp.uint8, sharding=one_chip),
        k=k, m=m,
        chunk_lens=jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip))
    assert "tpu_custom_call" in text
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().output_size_in_bytes >= b * m * s


def test_heal_reconstruct_compiles_with_pallas(one_chip, pallas_route):
    """reconstruct_weights_digests, 12+4 with 4 targets: what heal and the
    wide degraded GET launch (decode matrix as runtime data)."""
    from minio_tpu.ops import fused

    b, k, t, s = 16, 12, 4, 87382
    fn = jax.jit(_raw(fused.reconstruct_weights_digests),
                 static_argnames=("out_shards", "with_digests"))
    text, _ = _compile(
        fn, jax.ShapeDtypeStruct((b, k, s), jnp.uint8, sharding=one_chip),
        jax.ShapeDtypeStruct((t * 8, k * 8), jnp.int8, sharding=one_chip),
        jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip),
        out_shards=t)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows,s", [(256, 87382), (128, 131072)])
def test_verify_digests_compiles(one_chip, rows, s):
    from minio_tpu.ops import fused

    fn = jax.jit(_raw(fused.verify_digests))
    _, compiled = _compile(
        fn, jax.ShapeDtypeStruct((rows, s), jnp.uint8, sharding=one_chip),
        jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip))
    assert compiled.memory_analysis().output_size_in_bytes >= rows * 32


def test_reconstruct_lane_program_compiles(one_chip):
    """The coalescing reconstruct lane (per-row decode matrices as data,
    rebuilt chunks' digests fused in): the program behind
    ring.lane_kernel for a degraded GET / heal of a 128 KiB object."""
    from minio_tpu.dataplane import ring

    r, k, t, w = 1, 12, 4, 16384
    kern = ring.lane_kernel(
        ring.LaneKey(ring.OP_RECONSTRUCT, k, t, w, r, True))
    _, compiled = _compile(
        kern, jax.ShapeDtypeStruct((r, k, w), jnp.uint8, sharding=one_chip),
        jax.ShapeDtypeStruct((r, k * 8, t * 8), jnp.int8, sharding=one_chip),
        jax.ShapeDtypeStruct((r,), jnp.int32, sharding=one_chip))
    assert compiled.memory_analysis().output_size_in_bytes >= r * t * w


def test_encode_lane_compiles_row_sharded_over_four_chips(topo, pallas_route):
    """On a multi-chip host the lanes split each launch's rows over the
    local devices. With Pallas selected that split must be a shard_map: the
    TPU compiler refuses to partition a Mosaic kernel automatically (it
    did, for in_shardings on a plain jit — ring._jit_lane)."""
    import numpy as np
    from jax.sharding import Mesh

    from minio_tpu.dataplane import ring
    from minio_tpu.ops import fused

    shard = NamedSharding(Mesh(np.array(topo.devices), ("dp",)), P("dp"))
    enc = _raw(fused.encode_with_digests)
    kern = ring._jit_lane(lambda data, lens: enc(data, 12, 4, lens),
                          nargs=2, rows=8, shard=shard)
    _, compiled = _compile(
        kern, jax.ShapeDtypeStruct((8, 12, 16384), jnp.uint8, sharding=shard),
        jax.ShapeDtypeStruct((8,), jnp.int32, sharding=shard))
    assert "tpu_custom_call" in compiled.as_text()
    parity_sh, digs_sh = compiled.output_shardings
    assert parity_sh.spec == P("dp") and len(parity_sh.device_set) == 4


@pytest.mark.parametrize("b,k,m,s", [(16, 8, 4, 131072), (16, 12, 4, 87382)])
def test_mesh_codec_compiles_over_four_chips(topo, b, k, m, s):
    """What serving_mesh() engages on a four-chip host: the fused encode
    sharded (dp, tp, sp) = (1, 4, 1) with the GF(2) contraction completed
    by an integer all-reduce over tp."""
    from minio_tpu.parallel import make_mesh, sharded_encode_with_mxsum

    mesh = make_mesh(devices=list(topo.devices))
    assert dict(mesh.shape) == {"dp": 1, "tp": 4, "sp": 1}
    fn = jax.jit(lambda d: sharded_encode_with_mxsum(mesh, d, k, m))
    x = jax.ShapeDtypeStruct(
        (b, k, s), jnp.uint8,
        sharding=NamedSharding(mesh, P("dp", "tp", "sp")))
    _, compiled = _compile(fn, x)
    assert "all-reduce" in compiled.as_text()
    # 16 GB of HBM per chip; one 16-block batch must sit far below it.
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 4 << 30
