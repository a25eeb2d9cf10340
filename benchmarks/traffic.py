"""The one general traffic generator: a mix is a data file of parameters.

A mix (`traffic/<name>.json`) gives

    clients, processes   closed-loop clients in all, and the worker processes
                         they are spread over
    ops                  [{"verb": "PUT"|"GET"|"HEAL", "weight": w,
                           "sizes": [[bytes, weight], ...]}]
    body_pool            distinct bodies per size, made from the seed
    preload              {"objects": n, "sizes": [[bytes, weight], ...]}:
                         objects that set-up PUTs and that GETs draw from,
                         uniformly. With "group_objects": g the objects
                         lie in groups of g, each under a prefix of its own
                         (none a prefix of another's); a HEAL names one
                         group's prefix, its size is the group's object
                         bytes, and a client goes round the groups in an
                         order drawn from the seed
    warmup               {"min_seconds":, "min_ops":, "quiet_seconds":,
                          "max_seconds":}
    verify_sample        PUTs of the window (in a mix that heals: preloaded
                         objects) whose drives are compared with the plain
                         reference

Everything a client sends follows from (--seed, worker, thread): the order
of verbs, sizes and keys. Bodies come from a pool made from the seed once,
in set-up, with their MD5 and SHA-256, so that making and hashing a body is
not in the timed path; keys are unique, bodies repeat.
"""

from __future__ import annotations

import dataclasses
import hashlib

import numpy as np


@dataclasses.dataclass(frozen=True)
class Body:
    data: bytes
    md5: str
    sha256: str


@dataclasses.dataclass(frozen=True)
class Op:
    verb: str
    key: str
    size: int
    body_index: int


def _sizes(mix: dict) -> list[int]:
    out = {int(s) for op in mix["ops"] for s, _w in op.get("sizes", [])}
    out |= {int(s) for s, _w in mix.get("preload", {}).get("sizes", [])}
    return sorted(out)


def make_body(seed: int, size: int, index: int) -> bytes:
    return np.random.default_rng([seed, size, index]).bytes(size)


class BodyPool:
    """`body_pool` bodies for every size the mix names."""

    def __init__(self, mix: dict, seed: int):
        self.n = int(mix["body_pool"])
        self._bodies: dict[tuple[int, int], Body] = {}
        for size in _sizes(mix):
            for i in range(self.n):
                data = make_body(seed, size, i)
                self._bodies[(size, i)] = Body(
                    data, hashlib.md5(data).hexdigest(),
                    hashlib.sha256(data).hexdigest())

    def get(self, size: int, index: int) -> Body:
        return self._bodies[(size, index)]


def _draw(rng: np.random.Generator, pairs: list) -> int:
    """Index into weighted [[value, weight], ...]."""
    if len(pairs) == 1:
        return 0
    w = np.array([p[1] for p in pairs], dtype=float)
    return int(rng.choice(len(pairs), p=w / w.sum()))


def preload_objects(mix: dict, seed: int) -> list[Op]:
    """The objects set-up PUTs, in order; the same list in every process."""
    pre = mix.get("preload") or {}
    n = int(pre.get("objects", 0))
    rng = np.random.default_rng([seed, 0x9E3779B9])
    pool = int(mix["body_pool"])
    group = int(pre.get("group_objects", 0))
    out = []
    for i in range(n):
        size = int(pre["sizes"][_draw(rng, pre["sizes"])][0])
        under = group_prefix(seed, i // group) if group else f"s{seed}/pre/"
        out.append(Op("PUT", f"{under}{i:06d}", size, i % pool))
    return out


def group_prefix(seed: int, g: int) -> str:
    """The prefix of group g: of one width and closed by a slash, so that
    none is a prefix of another's."""
    return f"s{seed}/pre/g{g:04d}/"


def preload_groups(mix: dict, pre: list[Op]) -> list[list[Op]]:
    """The preloaded objects `pre` by group, in order; [] where the mix has
    no groups."""
    group = int((mix.get("preload") or {}).get("group_objects", 0))
    return [pre[i:i + group] for i in range(0, len(pre), group)] \
        if group else []


class OpStream:
    """The operations of one client thread, in order, without end."""

    def __init__(self, mix: dict, seed: int, worker: int, thread: int):
        self.mix = mix
        self.seed = seed
        self.rng = np.random.default_rng([seed, worker, thread])
        self.prefix = f"s{seed}/w{worker}t{thread}"
        self.pool = int(mix["body_pool"])
        self.pre = preload_objects(mix, seed)
        self.groups = preload_groups(mix, self.pre)
        # a HEAL goes round the groups, in this client's own order
        self.round = [int(g) for g in np.random.default_rng(
            [seed, worker, thread, 0x4EA1]).permutation(len(self.groups))]
        self.heals = 0
        self.count = 0

    def next(self) -> Op:
        ops = self.mix["ops"]
        spec = ops[_draw(self.rng, [[o["verb"], o["weight"]] for o in ops])]
        self.count += 1
        if spec["verb"] == "GET":
            if not self.pre:
                raise ValueError("a mix with GETs needs preload.objects")
            src = self.pre[int(self.rng.integers(len(self.pre)))]
            return Op("GET", src.key, src.size, src.body_index)
        if spec["verb"] == "HEAL":
            if not self.groups:
                raise ValueError("a mix with HEALs needs preload.objects "
                                 "and preload.group_objects")
            g = self.round[self.heals % len(self.round)]
            self.heals += 1
            return Op("HEAL", group_prefix(self.seed, g),
                      sum(o.size for o in self.groups[g]), g)
        size = int(spec["sizes"][_draw(self.rng, spec["sizes"])][0])
        return Op(spec["verb"], f"{self.prefix}/{self.count:07d}", size,
                  int(self.rng.integers(self.pool)))


def split_clients(mix: dict) -> list[int]:
    """Threads per worker process, adding up to the mix's clients."""
    clients, procs = int(mix["clients"]), int(mix["processes"])
    base, extra = divmod(clients, procs)
    return [base + (1 if i < extra else 0) for i in range(procs)
            if base + (1 if i < extra else 0) > 0]
