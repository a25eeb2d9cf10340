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
                  extra_metrics: dict | None = None) -> str:
    """-> root of a temp checkout with one cell per mix on the tiny config."""
    root = os.path.join(tmp, "checkout")
    os.makedirs(root)
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in ("minio_tpu", "native"):
        os.symlink(os.path.join(REPO, name), os.path.join(root, name))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(root, "benchmarks", "configs",
                           "ec2p2-4d.json"), "w") as f:
        json.dump(TINY_CONFIG, f)
    bench["configs"].append({
        "name": "ec2p2-4d", "source": "test",
        "file": "benchmarks/configs/ec2p2-4d.json", "reduced": [],
        "why": "test"})
    for mix in mixes:
        with open(os.path.join(root, "benchmarks", "traffic",
                               mix["name"] + ".json"), "w") as f:
            json.dump(mix, f)
        bench["workloads"].append({
            "name": f"ec2p2-4d.{mix['name']}", "config": "ec2p2-4d",
            "traffic": mix["name"], "chips": 1, "why": "test"})
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
