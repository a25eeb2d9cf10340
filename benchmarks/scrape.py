"""Prometheus text exposition -> samples, and the arithmetic over two of them.

A layer metric that reads the server's counters is a data file under
`layer_metrics/`: a family, label filters, and one arithmetic by name. The
server's counters run from its boot, so every reading is a delta between the
scrape at the window's open and the one at its close.
"""

from __future__ import annotations

import re

_SAMPLE = re.compile(r"^([A-Za-z_:][A-Za-z0-9_:]*)(?:\{(.*)\})?\s+(\S+)")
_LABEL = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')

Samples = dict[tuple[str, tuple[tuple[str, str], ...]], float]


def parse(text: str) -> Samples:
    """{(name, sorted label pairs): value}; comments and exemplars dropped."""
    out: Samples = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = _SAMPLE.match(line)
        if m is None:
            raise ValueError(f"not an exposition line: {line[:120]!r}")
        name, labels, value = m.groups()
        pairs = tuple(sorted(_LABEL.findall(labels or "")))
        out[(name, pairs)] = float(value)
    return out


def total(samples: Samples, family: str, labels: dict | None = None) -> float:
    """Sum of the samples of `family` whose labels match. A label's wanted
    value is a string or a list of strings."""
    want = {k: ([v] if isinstance(v, str) else list(v))
            for k, v in (labels or {}).items()}
    s = 0.0
    for (name, pairs), value in samples.items():
        if name != family:
            continue
        have = dict(pairs)
        if all(have.get(k) in vs for k, vs in want.items()):
            s += value
    return s


def count_at(samples: Samples, family: str, value: float) -> int:
    """How many samples of a gauge `family` read `value`."""
    return sum(v == value for (name, _), v in samples.items()
               if name == family)


def delta(before: Samples, after: Samples, term: dict) -> float:
    """One term of a metric file: {"family":, "labels":, "scale":}."""
    d = (total(after, term["family"], term.get("labels"))
         - total(before, term["family"], term.get("labels")))
    return d * term.get("scale", 1.0)


def delta_ratio(before: Samples, after: Samples, spec: dict,
                window: dict) -> float | None:
    """delta(numerator) / delta(denominator). The denominator may be the
    name of a count the clients took ("client_ops") instead of a family.
    Nothing to divide by -> nothing to report."""
    num = sum(delta(before, after, t) for t in spec["numerator"])
    den_spec = spec["denominator"]
    if isinstance(den_spec, str):
        den = float(window[den_spec])
    else:
        den = sum(delta(before, after, t) for t in den_spec)
    if den <= 0:
        return None
    return num / den


def backends(samples: Samples) -> dict[str, float]:
    """backend label -> observations, of minio_tpu_kernel_seconds."""
    out: dict[str, float] = {}
    for (name, pairs), value in samples.items():
        if name == "minio_tpu_kernel_seconds_count" and value > 0:
            be = dict(pairs).get("backend", "")
            out[be] = out.get(be, 0.0) + value
    return out


def stage_table(before: Samples, after: Samples) -> dict[str, dict]:
    """api -> stage -> [ms per observation, observations] over the window,
    of minio_tpu_stage_seconds: where a request's time went, by the
    program's own flight recorder."""
    out: dict[str, dict] = {}
    for (name, pairs), value in after.items():
        if name != "minio_tpu_stage_seconds_count":
            continue
        n = value - before.get((name, pairs), 0.0)
        if n <= 0:
            continue
        key = ("minio_tpu_stage_seconds_sum", pairs)
        secs = after.get(key, 0.0) - before.get(key, 0.0)
        lb = dict(pairs)
        out.setdefault(lb.get("api", ""), {})[lb.get("stage", "")] = [
            round(1e3 * secs / n, 3), int(n)]
    return out
