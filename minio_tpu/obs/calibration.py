"""Per-host calibration profiles: was this node tuned for THIS host?

The performance gates shipped as constants (the two lane-width gates
of dataplane/route.py, the hedge-delay policy) were measured on a
specific host class; a node image moved to different hardware silently
serves with the wrong crossover points. This module makes that drift
observable:

- `fingerprint()` — the hardware identity the gates were tuned against:
  cores, page size, accelerator platform + device count, and (when a
  drive root is given) an fsync medium probe classifying the journal
  medium by measured fsync latency.
- `boot(drive0_root)` — at server boot, write the current profile
  (fingerprint + active gates) to `<drive0>/.mtpu.sys/calibration.json`
  the first time, and on later boots compare against the stored one:
  a mismatch raises `minio_tpu_calibration_stale` to 1 (the stored
  profile is left in place as the tuning evidence) instead of silently
  serving gates tuned for other hardware.
- `publish_build_info()` exposes the standing
  `minio_tpu_build_info{version,platform,devices,device_kind}`
  info-gauge (a benchmark run, `python3 benchmarks/run.py`, names its
  device in every result line itself).

Schema is documented in docs/SLO.md (calibration section).
"""

from __future__ import annotations

import json
import mmap
import os
import sys
import tempfile
import time

from minio_tpu import __version__
from minio_tpu.obs.histogram import gauge

SYS_VOL = ".mtpu.sys"
PROFILE_NAME = "calibration.json"

# Fingerprint keys that must match for a stored profile to still apply
# to this host. `fsync_medium` is the probe's *class* (order-of-
# magnitude bands), not the raw latency, so normal run-to-run jitter
# cannot flip a profile stale.
COMPARE_KEYS = ("cores", "page_size", "platform", "devices",
                "fsync_medium")

_STALE = gauge(
    "minio_tpu_calibration_stale",
    "1 when the stored calibration profile was tuned on different "
    "hardware than this host")
_BUILD = gauge(
    "minio_tpu_build_info",
    "Constant 1; labels carry build/runtime identity",
    ("version", "platform", "devices", "device_kind"))


def _accel() -> tuple[str, int]:
    """(platform, local device count). A backend that fails to
    initialise raises: a fingerprint of a host that does not exist
    would make `minio_tpu_build_info` lie about where the codec runs."""
    import jax

    return jax.default_backend(), len(jax.devices())


def _probe_fsync(root: str) -> tuple[str, float]:
    """(medium class, median fsync microseconds) measured by fsyncing a
    small file on the drive medium itself. Bands are order-of-magnitude
    wide on purpose (see COMPARE_KEYS)."""
    # mtpu: allow(MTPU003) - an unprobeable medium (read-only fs,
    # exotic mount) degrades to "unknown"; boot must not fail on it.
    try:
        fd, path = tempfile.mkstemp(prefix=".mtpu-cal-", dir=root)
        try:
            os.write(fd, b"\0" * 4096)
            lats = []
            for _ in range(3):
                os.write(fd, b"\1")
                t0 = time.perf_counter()
                os.fsync(fd)
                lats.append((time.perf_counter() - t0) * 1e6)
        finally:
            os.close(fd)
            os.unlink(path)
        med = sorted(lats)[len(lats) // 2]
        if med < 300.0:
            return "nvme-or-cache", med
        if med < 3000.0:
            return "ssd", med
        return "disk", med
    except OSError:
        return "unknown", 0.0


def fingerprint(probe_root: str | None = None) -> dict:
    """The host identity dict. With `probe_root`, includes the fsync
    medium probe of that directory's filesystem."""
    platform, devices = _accel()
    fp = {
        "cores": os.cpu_count() or 1,
        "page_size": mmap.PAGESIZE,
        "platform": platform,
        "devices": devices,
        "python": ".".join(str(v) for v in sys.version_info[:2]),
    }
    if probe_root is not None:
        medium, med_us = _probe_fsync(probe_root)
        fp["fsync_medium"] = medium
        fp["fsync_us"] = round(med_us, 1)
    return fp


def gates() -> dict:
    """The tuned performance gates currently in force — the values the
    fingerprint vouches for: the two lane-width constants of
    dataplane/route.py, under the names of the options they replaced so
    that a stored profile still compares, and the hedge policy in
    erasure/objects.py."""
    from minio_tpu.dataplane import route

    return {
        "MTPU_DP_MAX_WIDTH": route.ENCODE_GATE,
        "MTPU_DP_MAX_RECON_WIDTH": route.RECON_GATE,
        # The hedge delay is an EWMA policy (4x rolling shard latency),
        # only a fixed number when an operator pins it.
        "hedge_delay": "adaptive-ewma-4x",
    }


def profile(probe_root: str | None = None) -> dict:
    return {"v": 1, "time": time.time(), "mtpu_version": __version__,
            "fingerprint": fingerprint(probe_root), "gates": gates()}


def stale_against(stored: dict, current: dict) -> list[str]:
    """COMPARE_KEYS whose stored/current fingerprints disagree (keys
    missing on either side are ignored: an older-schema profile is not
    retroactively stale)."""
    sf = (stored or {}).get("fingerprint") or {}
    cf = (current or {}).get("fingerprint") or {}
    return [k for k in COMPARE_KEYS
            if k in sf and k in cf and sf[k] != cf[k]]


def boot(drive0_root: str) -> dict:
    """Write-or-compare the calibration profile on drive 0 at server
    boot. Returns {"profile": current, "stored": previous-or-None,
    "stale": [mismatched keys]} and sets minio_tpu_calibration_stale."""
    sys_dir = os.path.join(drive0_root, SYS_VOL)
    # mtpu: allow(MTPU003) - the sys dir normally already exists
    # (journals live there); a brand-new drive gets it here.
    try:
        os.makedirs(sys_dir, exist_ok=True)
    except OSError:
        pass
    path = os.path.join(sys_dir, PROFILE_NAME)
    cur = profile(probe_root=sys_dir if os.path.isdir(sys_dir)
                  else drive0_root)
    stored = None
    # mtpu: allow(MTPU003) - a corrupt stored profile is treated as
    # absent and rewritten; calibration must never block boot.
    try:
        with open(path, encoding="utf-8") as f:
            stored = json.load(f)
    except (OSError, ValueError):
        stored = None
    stale = stale_against(stored, cur) if stored else []
    if stored is None:
        try:
            tmp = path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(cur, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
        except OSError:
            pass
    _STALE.labels().set(1.0 if stale else 0.0)
    return {"profile": cur, "stored": stored, "stale": stale}


def publish_build_info() -> None:
    """Expose minio_tpu_build_info{version,platform,devices,device_kind}
    = 1. `device_kind` is the accelerator's own name as JAX reports it
    (`TPU v5 lite`): the platform alone cannot tell one chip generation
    from the next."""
    import jax

    platform, devices = _accel()
    _BUILD.set(1.0, version=__version__, platform=platform,
               devices=str(devices),
               device_kind=jax.devices()[0].device_kind)
