"""Helpers of the benchmark's tests: a temp checkout that holds a copy of
the benchmark, the program by symlink, and a tiny CPU cell added as DATA
(a config file, a traffic file, entries in BENCHMARK.json) with no edit to
any file that was there."""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)

TINY_CONFIG = {
    "name": "ec2p2-4d", "source": "test", "drives": 4, "data_shards": 2,
    "parity_shards": 2, "block_size": 1048576,
    "guarantees": {"write_quorum_drives": 3},
}
# The same set with both parity's worth of drives lost after the preload:
# every key has lost 0, 1 or 2 of its 2 data shards.
TINY_LOST = {**TINY_CONFIG, "name": "ec2p2-4d-2lost",
             "state": {"drives_lost": 2, "which": "first",
                       "when": "after_preload"}}


def tiny_mix(name: str, verb: str, size: int, preload: int = 0) -> dict:
    return {
        "name": name, "loop": "closed", "clients": 4, "processes": 2,
        "ops": [{"verb": verb, "weight": 1,
                 "sizes": [[size, 1]] if verb == "PUT" else []}],
        "body_pool": 3,
        "preload": {"objects": preload,
                    "sizes": [[size, 1]] if preload else []},
        "warmup": {"min_seconds": 1, "min_ops": 4, "quiet_seconds": 1,
                   "max_seconds": 120},
        "verify_sample": 6 if verb == "PUT" else 0,
    }


def make_checkout(tmp: str, mixes: list[dict],
                  extra_metrics: dict | None = None,
                  lost_mixes: list[str] = ()) -> str:
    """-> root of a temp checkout with one cell per mix on the tiny config,
    and for each mix named in `lost_mixes` a second cell, `<cell>.2lost`, on
    the tiny config with its state."""
    root = os.path.join(tmp, "checkout")
    os.makedirs(root)
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in ("minio_tpu", "native"):
        os.symlink(os.path.join(REPO, name), os.path.join(root, name))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for conf in (TINY_CONFIG, TINY_LOST):
        file = f"benchmarks/configs/{conf['name']}.json"
        with open(os.path.join(root, file), "w") as f:
            json.dump(conf, f)
        bench["configs"].append({
            "name": conf["name"], "source": "test", "file": file,
            "reduced": [], "why": "test"})
    for mix in mixes:
        with open(os.path.join(root, "benchmarks", "traffic",
                               mix["name"] + ".json"), "w") as f:
            json.dump(mix, f)
        bench["workloads"].append({
            "name": f"ec2p2-4d.{mix['name']}", "config": "ec2p2-4d",
            "traffic": mix["name"], "chips": 1, "why": "test"})
        if mix["name"] in lost_mixes:
            bench["workloads"].append({
                "name": f"ec2p2-4d.{mix['name']}.2lost",
                "config": "ec2p2-4d-2lost", "traffic": mix["name"],
                "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:     # those that name their cells
        if "workloads" in m and m["name"] != "ops_per_s":
            m["workloads"] += [w["name"] for w in bench["workloads"]
                               if w["config"].startswith("ec2p2-4d")]
    for name, (entry, spec) in (extra_metrics or {}).items():
        with open(os.path.join(root, "benchmarks", "layer_metrics",
                               name + ".json"), "w") as f:
            json.dump(spec, f)
        bench["per_layer"].append({"name": name, **entry})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def recorded_scrapes():
    """The two recorded expositions beside the tests, parsed."""
    import scrape

    out = []
    for name in ("scrape_before.txt", "scrape_after.txt"):
        with open(os.path.join(HERE, "data", name)) as f:
            out.append(scrape.parse(f.read()))
    return out
