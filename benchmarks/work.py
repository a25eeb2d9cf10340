"""The codec work a window's operations need, and the chips' peaks.

Reckoned from the traffic and the geometry alone, so the number reads the
same whatever implements the kernels: a PUT of S bytes at k+m reads S bytes,
writes S*m/k parity bytes and one 32-byte digest per shard row; a GET reads
the k data shards' bytes once (to checksum them) and writes their digests.
In a configuration's `state` an object has lost t of its data shards (which
ones follows from its key): a GET then reads k rows that are left once, to
checksum them and to rebuild from them, and writes the t rebuilt rows too.
A HEAL of one object onto t drives that hold none of it does the same to
every block of the object, whether the t rows are data or parity: k rows
read once, t written, and a digest for each of the k + t.
"""

from __future__ import annotations

DIGEST_LEN = 32

# Published peaks per chip, keyed by jax's device_kind. Source: Google Cloud
# documentation, "TPU v5e": 16 GB HBM at 819 GB/s, 197 TFLOP/s bf16,
# 393 TOP/s int8. A kind that is not here is an error, not a default.
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12,
                    "bf16_flops_per_s": 197e12,
                    "source": "Google Cloud documentation, TPU v5e"},
    "TPU v5e": {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12,
                "bf16_flops_per_s": 197e12,
                "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it "
                       "to benchmarks/work.py with its source")
    return PEAKS[device_kind]


def _rows(size: int, k: int, block_size: int) -> tuple[int, int]:
    """(blocks, shard bytes per shard) of one object."""
    full, last = divmod(size, block_size)
    shard = full * -(-block_size // k) + (-(-last // k) if last else 0)
    return full + (1 if last else 0), shard


def lost_data_shards(shard_of_drive: list[int], lost_drives: list[int],
                     k: int) -> int:
    """t: how many of an object's k data shards lay on the lost drives.
    `shard_of_drive[i]` is the shard that drive i of the set holds."""
    return sum(shard_of_drive[i] < k for i in lost_drives)


def codec_bytes(verb: str, size: int, k: int, m: int,
                block_size: int, lost_data: int = 0) -> int:
    """HBM bytes the codec has to move for one operation, at the least.
    `lost_data` is t: a GET's lost data shards, a HEAL's rebuilt shards."""
    blocks, shard = _rows(size, k, block_size)
    if verb == "PUT":
        return size + shard * m + blocks * (k + m) * DIGEST_LEN
    if verb == "GET":
        return shard * k + shard * lost_data + blocks * k * DIGEST_LEN
    if verb == "HEAL":
        return (shard * k + shard * lost_data
                + blocks * (k + lost_data) * DIGEST_LEN)
    return 0


def codec_int_ops(verb: str, size: int, k: int, m: int,
                  block_size: int, lost_data: int = 0) -> int:
    """Integer operations of the bit-matrix form, for the earlier line:
    encode is a [w, k*8] x [k*8, m*8] contraction per block (2*(k*8)*(m*8)
    per data column of k bytes), a rebuild of t rows one of [k*8, t*8],
    mxsum256 16 per byte hashed."""
    _, shard = _rows(size, k, block_size)
    if verb == "PUT":
        return shard * 2 * (k * 8) * (m * 8) + shard * (k + m) * 16
    if verb == "GET":
        return shard * k * 16 + shard * 2 * (k * 8) * (lost_data * 8)
    if verb == "HEAL":
        return (shard * (k + lost_data) * 16
                + shard * 2 * (k * 8) * (lost_data * 8))
    return 0


def least_seconds(ops: list[tuple], k: int, m: int,
                  block_size: int, device_kind: str) -> dict:
    """The least time one chip could take for these (verb, size) or (verb,
    size, lost data shards) operations: by HBM bytes (the metric's bound)
    and by int8 operations (printed)."""
    p = peaks(device_kind)
    nbytes = sum(codec_bytes(v, s, k, m, block_size, *t) for v, s, *t in ops)
    nops = sum(codec_int_ops(v, s, k, m, block_size, *t) for v, s, *t in ops)
    return {"bytes": nbytes, "int_ops": nops,
            "hbm_s": nbytes / p["hbm_bytes_per_s"],
            "int8_s": nops / p["int8_ops_per_s"]}
