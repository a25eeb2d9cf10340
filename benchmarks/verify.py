"""The comparison that decides `correct`.

Numbers compared, each with its limit (all exact, so every limit is 0 but
the quorum's, which the configuration states):

    failed_ops          operations of the window that were refused, cut
                        short or never answered                       <= 0
    wrong_answers       PUTs of the window whose ETag is not the MD5 of the
                        body sent, GETs whose bytes are not the object's <= 0
    readback_wrong      sampled PUTs of the window read back after its
                        close whose bytes differ                      <= 0
    shards_wrong        shard files of the sampled PUTs that are on a drive
                        and differ from the plain reference's file for that
                        drive (data, parity, every [digest][chunk] frame) <= 0
    drives_holding_min  over the sampled PUTs, the least number of drives
                        that hold the reference's shard file   >= write quorum
    lost_drives_present in a configuration's `state` only: the lost drives
                        whose root directory is there at the window's open
                        or at its close (the state did not hold)        <= 0

In a state the drives' check does not look under the lost drives' roots, and
`drives_holding_min` keeps the configuration's limit. Where the mix heals,
the sample is of the preloaded objects, which the window healed again and
again; it is taken once the last heal has answered, every drive is looked
at, and `drives_holding_min` has the limit the configuration states for a
healed object (`guarantees.heal_drives`: every drive of the set).

The reference encodes each distinct body once (bodies repeat, keys do not);
which drive holds which shard follows from the key.
"""

from __future__ import annotations

import glob
import os
import time

import reference


def _drawn(items: list, n: int, seed: int, salt: int) -> list:
    """n of the items (all where there are no more), drawn from the seed,
    the first and the last among them, in their order."""
    import numpy as np

    if n <= 0 or len(items) <= n:
        return items if n > 0 else []
    rng = np.random.default_rng([seed, salt])
    idx = set(rng.choice(len(items), size=n, replace=False).tolist())
    idx |= {0, len(items) - 1}
    return [items[i] for i in sorted(idx)]


def sample_puts(records: list[dict], n: int, seed: int) -> list[dict]:
    """n acknowledged PUTs of the window, drawn from the seed, the first and
    the last acknowledged among them."""
    puts = sorted((r for r in records if r["verb"] == "PUT" and r["ok"]),
                  key=lambda r: r["t_last"])
    return _drawn(puts, n, seed, 0x5A17)


def sample_preloaded(objects: list, n: int, seed: int) -> list[dict]:
    """n of the preloaded objects, drawn likewise, as records to compare."""
    return _drawn([{"key": o.key, "size": o.size, "body_index": o.body_index}
                   for o in objects], n, seed, 0x4EA1)


class DriveCheck:
    """Shard files on the drives against the reference's."""

    def __init__(self, config: dict, drive_roots: list[str], bucket: str,
                 lost_roots: list[str] = ()):
        self.k = int(config["data_shards"])
        self.m = int(config["parity_shards"])
        self.block = int(config["block_size"])
        self.roots = drive_roots
        self.lost = set(lost_roots)
        self.bucket = bucket
        self._files: dict[tuple[int, int], list[bytes]] = {}

    def expected(self, size: int, body_index: int, body: bytes) -> list[bytes]:
        key = (size, body_index)
        if key not in self._files:
            self._files[key] = reference.shard_files(
                body, self.k, self.m, self.block)
        return self._files[key]

    def check(self, key: str, want_files: list[bytes]) -> tuple[int, int]:
        """-> (drives holding the right file, shard files present but
        wrong) for one object."""
        shard_of = reference.shard_of_drive(self.bucket, key, self.k + self.m)
        right = wrong = 0
        for root, shard in zip(self.roots, shard_of):
            if root in self.lost:
                continue
            found = glob.glob(os.path.join(
                glob.escape(os.path.join(root, self.bucket, key)),
                "*", "part.1"))
            if not found:
                continue
            ok = False
            if len(found) == 1:
                with open(found[0], "rb") as f:
                    ok = f.read() == want_files[shard]
            if ok:
                right += 1
            else:
                wrong += 1
        return right, wrong


class PatientFetch:
    """`fetch(key) -> Reply` for the read back, which waits for an answer
    that is late: late is not wrong.

    A host that stood still takes the drives' health deadlines with it, and
    until the program's own probes bring the drives back it answers 503 (or,
    with no drive left to ask, 404 NoSuchBucket), or cuts a body short. So a
    read back is asked again, until `give_up` (a minute past the window's
    close), while the answer is a 503, which asks for just that, or while
    the program says it is not healthy (`healthy()`: MinIO's own probe,
    /minio/health/cluster). Any other answer than a 200 is judged by what
    it says once it has come twice running, each time with the program
    healthy before it and after it: an outage can begin and end under one
    request. A 200 is judged at once, by its bytes; an object that is lost
    or altered stays so however often it is asked for."""

    def __init__(self, ask, healthy, give_up: float,
                 clock=time.monotonic, sleep=time.sleep):
        self.ask, self.healthy, self.give_up = ask, healthy, give_up
        self.clock, self.sleep = clock, sleep
        self.asked_again = 0
        self.t_last_answer = clock()
        self.log: list[str] = []
        self.was_healthy = self._wait_healthy()
        self.waited_first_s = clock() - self.t_last_answer

    def _wait_healthy(self) -> bool:
        while not self.healthy():
            if self.clock() > self.give_up:
                return False
            self.sleep(0.25)
        return True

    def __call__(self, key: str):
        said_in_health = 0
        while True:
            got = self.ask(key)
            self.t_last_answer = self.clock()
            if got.status == 200:
                return got
            now_healthy = self.healthy()
            if got.status != 503 and self.was_healthy and now_healthy:
                said_in_health += 1
            else:
                said_in_health = 0
            final = self.clock() > self.give_up or said_in_health >= 2
            self.log.append(
                f"read back {key} -> status {got.status} "
                f"{got.body[:200]!r}, the program healthy before: "
                f"{self.was_healthy}, after: {now_healthy}"
                + ("" if final else ", asked again"))
            if final:
                return got
            self.asked_again += 1
            self.sleep(0.25)
            self.was_healthy = self._wait_healthy()


def answers(done: list[dict]) -> dict:
    """What the clients saw of every operation of the window."""
    return {
        "failed_ops": {"value": sum(not r["ok"] and not r["wrong"]
                                    for r in done),
                       "limit": 0, "better": "lower"},
        "wrong_answers": {"value": sum(r["wrong"] for r in done),
                          "limit": 0, "better": "lower"},
    }


def state_held(seen: set[str]) -> dict:
    """`seen`: the lost roots that were there when the harness looked."""
    return {"lost_drives_present": {
        "value": len(seen), "limit": 0, "better": "lower"}}


def compare_puts(sample: list[dict], bodies, fetch, config: dict,
                 drive_roots: list[str], bucket: str,
                 lost_roots: list[str] = (),
                 guarantee: str = "write_quorum_drives") -> dict:
    """The sampled PUTs read back through `fetch(key) -> Reply`, and their
    drives against the plain reference. `bodies.get(size, index).data` is
    what was sent. `guarantee` names the one that `drives_holding_min` is
    held to."""
    check = DriveCheck(config, drive_roots, bucket, lost_roots)
    readback_wrong = shards_wrong = 0
    holding = []
    for r in sample:
        body = bodies.get(r["size"], r["body_index"]).data
        got = fetch(r["key"])
        readback_wrong += not (got.status == 200 and got.matches(body))
        right, wrong = check.check(r["key"], check.expected(
            r["size"], r["body_index"], body))
        shards_wrong += wrong
        holding.append(right)
    return {
        "readback_wrong": {"value": readback_wrong, "limit": 0,
                           "better": "lower"},
        "shards_wrong": {"value": shards_wrong, "limit": 0,
                         "better": "lower"},
        "drives_holding_min": {
            "value": min(holding),
            "limit": int(config["guarantees"][guarantee]),
            "better": "higher"},
    }


def verdict(compared: dict) -> bool:
    """compared: name -> {"value":, "limit":, "better": "lower"|"higher"}."""
    for c in compared.values():
        if c["better"] == "lower" and not c["value"] <= c["limit"]:
            return False
        if c["better"] == "higher" and not c["value"] >= c["limit"]:
            return False
    return True
