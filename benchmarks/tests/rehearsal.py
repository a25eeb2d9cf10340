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
# The same set after half of it came back blank: every object has 2 of its 4
# shards, and a heal has to put all 4 back.
TINY_BLANK = {**TINY_CONFIG, "name": "ec2p2-4d-2blank",
              "guarantees": {"write_quorum_drives": 3, "heal_drives": 4},
              "state": {"drives_blank": 2, "which": "first",
                        "when": "after_preload",
                        "again": "before_each_heal"}}


def tiny_heal_mix(name: str, size: int, groups: int = 2,
                  group_objects: int = 2) -> dict:
    return {
        "name": name, "loop": "closed", "clients": 1, "processes": 1,
        "ops": [{"verb": "HEAL", "weight": 1, "sizes": []}],
        "body_pool": 3,
        "preload": {"objects": groups * group_objects,
                    "group_objects": group_objects, "sizes": [[size, 1]]},
        "warmup": {"min_seconds": 1, "min_ops": groups, "quiet_seconds": 1,
                   "max_seconds": 120},
        "verify_sample": groups * group_objects,
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
                  extra_metrics: dict | None = None,
                  lost_mixes: list[str] = (),
                  blank_mixes: list[str] = ()) -> str:
    """-> root of a temp checkout with one cell per mix on the tiny config,
    and for each mix named in `lost_mixes` a second cell, `<cell>.2lost`, on
    the tiny config with its state; a mix named in `blank_mixes` gets one
    cell only, `<cell>.2blank`, on the tiny config that came back blank,
    which like the cell it rehearses reports no `op_p90_ms`."""
    root = os.path.join(tmp, "checkout")
    os.makedirs(root)
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in ("minio_tpu", "native"):
        os.symlink(os.path.join(REPO, name), os.path.join(root, name))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for conf in (TINY_CONFIG, TINY_LOST, TINY_BLANK):
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
        if mix["name"] in blank_mixes:
            continue
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
    for name in blank_mixes:     # after the lists above were filled
        bench["workloads"].append({
            "name": f"ec2p2-4d.{name}.2blank", "config": "ec2p2-4d-2blank",
            "traffic": name, "chips": 1, "why": "test"})
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
