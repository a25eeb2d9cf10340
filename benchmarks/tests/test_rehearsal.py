"""Whole runs on the CPU at 4 drives, EC 2+2: the parent, the launcher, the
client workers, the comparison with the plain reference. No number of these
runs is a measurement. Each run takes a quarter of a minute."""

import filecmp
import json
import os
import shutil
import subprocess
import sys

import pytest

import rehearsal

KEYS = ["correct", "attempted", "failed", "metrics", "device", "compared"]
SIZE = 3 * 1048576 + 5            # three blocks and a ragged fourth
SEED = 2147483659                 # more than 32 signed bits hold
LOST = "ec2p2-4d.get-tiny.2lost"   # d0 and d1 gone after the preload
BLANK = "ec2p2-4d.heal-tiny.2blank"   # d0 and d1 back, blank, after it
ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}
ENV.pop("BENCH_TEST_FAULT", None)

EXTRA_METRIC = {"commit_ms_per_op": (
    {"unit": "ms", "better": "lower", "source": "program_span",
     "layer": "object layer", "moves": "op_p90_ms",
     "workloads": ["ec2p2-4d.put-tiny"]},
    {"arithmetic": "delta_ratio",
     "numerator": [{"family": "minio_tpu_stage_seconds_sum", "scale": 1000.0,
                    "labels": {"api": "PutObject", "stage": "commit"}}],
     "denominator": [{"family": "minio_tpu_stage_seconds_count",
                      "labels": {"api": "PutObject", "stage": "commit"}}]})}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("bench"))
    return rehearsal.make_checkout(
        tmp, [rehearsal.tiny_mix("put-tiny", "PUT", SIZE),
              rehearsal.tiny_mix("get-tiny", "GET", SIZE, preload=4),
              rehearsal.tiny_heal_mix("heal-tiny", SIZE)],
        EXTRA_METRIC, lost_mixes=["get-tiny"], blank_mixes=["heal-tiny"])


def cpu_run(checkout, workload, fault=""):
    env = dict(ENV)
    if fault:
        env["BENCH_TEST_FAULT"] = fault
    p = subprocess.run(
        [sys.executable,
         os.path.join(checkout, "benchmarks", "tests", "cpu_run.py"),
         checkout, workload, str(SEED), "3"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=300)
    return p


def last_line(p):
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.endswith("\n") and not p.stdout.endswith("\n\n")
    return json.loads(p.stdout.splitlines()[-1])


def test_cli_ends_non_zero_for_want_of_a_chip(checkout):
    """The command itself, here: everything up to the server child's look
    at jax.devices() runs, then the run ends non-zero with no result."""
    p = subprocess.run(
        [sys.executable, os.path.join(checkout, "benchmarks", "run.py"),
         "--workload", "ec2p2-4d.put-tiny", "--seed", str(SEED),
         "--seconds", "2", "--trace", "0"],
        env=ENV, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=300, cwd=checkout)
    assert p.returncode != 0
    assert "wanted 1 x tpu" in p.stderr
    assert '"correct"' not in p.stdout and '"metrics"' not in p.stdout
    assert p.stdout.startswith("disk:")   # it got as far as the child


def test_bare_directory_ends_non_zero(tmp_path):
    """Only BENCHMARK.json and the files under `paths`: no program."""
    shutil.copy(os.path.join(rehearsal.REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(rehearsal.BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "ec12p4-16d.put-10MiB", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=ENV, cwd=tmp_path, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, timeout=120)
    assert p.returncode != 0 and '"correct"' not in p.stdout


def test_put_cell_whole_and_its_last_line(checkout):
    r = last_line(cpu_run(checkout, "ec2p2-4d.put-tiny"))
    assert list(r) == KEYS                       # and `compared` comes last
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"goodput_mibps", "op_p90_ms", "setup_s"}
    assert set(r["device"]) == {"platform", "kind", "count",
                                "memory_peak_bytes"}
    assert r["device"]["platform"] == "cpu"      # never written as a TPU's
    c = r["compared"]
    assert c["drives_holding_min"] == {"value": 4, "limit": 3}
    assert all(c[k] == {"value": 0, "limit": 0} for k in (
        "failed_ops", "wrong_answers", "readback_wrong", "shards_wrong"))


def test_get_cell_whole(checkout):
    p = cpu_run(checkout, "ec2p2-4d.get-tiny")
    assert "drives the program holds offline: 0 and 0\n" in p.stdout
    r = last_line(p)
    assert r["correct"] is True and r["attempted"] > 0
    assert set(r["compared"]) == {"failed_ops", "wrong_answers"}


def test_degraded_get_cell_whole(checkout):
    """Two of the four drives lost after the preload: the state is brought
    about in set-up, holds, and every GET is rebuilt bit-exact."""
    p = cpu_run(checkout, LOST)
    r = last_line(p)
    assert list(r) == KEYS
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["compared"] == {k: {"value": 0, "limit": 0} for k in (
        "failed_ops", "wrong_answers", "lost_drives_present")}
    assert set(r["metrics"]) == {"goodput_mibps", "op_p90_ms", "setup_s"}
    assert "\nstate: {'drives_lost': 2" in p.stdout
    assert "removed d0 d1\n" in p.stdout
    setup = next(ln for ln in p.stdout.splitlines()
                 if ln.startswith("set-up: "))
    assert ", preload " in setup and ", state " in setup
    assert "drives the program holds offline: 2 and 2\n" in p.stdout
    # the rebuild ran: the healthy twin's `decode` is a few hundredths of a ms
    stages = json.loads(next(
        ln for ln in p.stdout.splitlines()
        if ln.startswith("stages, ")).split(": ", 1)[1])
    assert stages["GetObject"]["decode"][0] > 1.0


def test_heal_cell_whole(checkout):
    """Two of the four drives blank after the preload and again before each
    heal of a group: they stay online, every HEAL of the window puts a
    group's two objects back on them, and after the last one all four
    drives hold every object as the plain reference encodes it."""
    p = cpu_run(checkout, BLANK)
    r = last_line(p)
    assert list(r) == KEYS
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"goodput_mibps", "setup_s"}
    c = r["compared"]
    assert list(c) == ["failed_ops", "wrong_answers", "readback_wrong",
                       "shards_wrong", "drives_holding_min"]
    assert c["drives_holding_min"] == {"value": 4, "limit": 4}
    assert all(c[k] == {"value": 0, "limit": 0} for k in list(c)[:4])
    assert "\nstate: the journals of 4 objects were at rest after " in p.stdout
    assert "\nstate: {'drives_blank': 2" in p.stdout
    assert "no object left on d0 d1\n" in p.stdout
    assert "drives the program holds offline: 0 and 0\n" in p.stdout
    assert "drives the program holds online: 4 and 4 of 4\n" in p.stdout
    assert "compared 4 preloaded objects with the plain reference" in p.stdout
    window = next(ln for ln in p.stdout.splitlines()
                  if ln.startswith("the clients' clock"))
    e2e = json.loads(window.split(": ", 1)[1])
    # an operation is a group: two objects' bytes
    assert e2e["goodput_mibps"] == pytest.approx(
        e2e["ops_per_s"] * 2 * SIZE / 1048576)
    stages = json.loads(next(
        ln for ln in p.stdout.splitlines()
        if ln.startswith("stages, ")).split(": ", 1)[1])
    assert stages["admin.heal"]["auth"][1] == r["attempted"]


@pytest.mark.parametrize("workload,fault,caught_by", [
    ("ec2p2-4d.put-tiny", "parity", "shards_wrong"),
    ("ec2p2-4d.put-tiny", "lost-drives", "drives_holding_min"),
    ("ec2p2-4d.get-tiny", "get-body", "wrong_answers"),
    (LOST, "rebuilt-row", "wrong_answers"),
    (LOST, "drive-back", "lost_drives_present"),
    (BLANK, "healed-shard", "shards_wrong"),
    (BLANK, "heal-withheld", "wrong_answers"),
    (BLANK, "heal-leaves-blank", "drives_holding_min"),
])
def test_a_fault_under_the_timed_path_reads_not_correct(
        checkout, workload, fault, caught_by):
    r = last_line(cpu_run(checkout, workload, fault))
    assert r["correct"] is False
    c = r["compared"][caught_by]
    assert (c["value"] < c["limit"] if caught_by == "drives_holding_min"
            else c["value"] > c["limit"])


def test_drives_offline_at_the_read_back_make_it_late_not_wrong(checkout):
    """Every drive offline (health) for two seconds from the read back's
    first look at the program's health, and again from its first read of a
    shard file, under a body that is being sent: the read back waits, asks
    again, and the run stays correct."""
    p = cpu_run(checkout, "ec2p2-4d.put-tiny", "outage")
    r = last_line(p)
    assert r["correct"] is True, p.stderr[-2000:]
    assert r["compared"]["readback_wrong"] == {"value": 0, "limit": 0}
    said = [ln.split() for ln in p.stdout.splitlines()
            if ln.startswith("read back: asked again ")]
    assert int(said[0][4]) >= 1               # asked again
    assert 1.0 < float(said[0][7]) < 10.0     # waited for health
    assert ", asked again" in p.stderr


@pytest.mark.parametrize("workload,broken,correct,caught_by", [
    ("ec2p2-4d.put-tiny", "none", True, None),
    ("ec2p2-4d.put-tiny", "quorum", False, "drives_holding_min"),
    ("ec2p2-4d.put-tiny", "bitrot", False, "shards_wrong"),
    ("ec2p2-4d.get-tiny", "bit-exact", False, "wrong_answers"),
    (LOST, "none", True, None),
    (LOST, "bit-exact", False, "wrong_answers"),
    (LOST, "rebuild", False, "wrong_answers"),
    (LOST, "state", False, "lost_drives_present"),
    (BLANK, "none", True, None),
    (BLANK, "bit-exact", False, "readback_wrong"),
    (BLANK, "heal-zeros", False, "shards_wrong"),
    (BLANK, "heal-skip", False, "wrong_answers"),
    (BLANK, "heal-skip", False, "drives_holding_min"),
])
def test_the_control_reads_not_correct(checkout, workload, broken, correct,
                                       caught_by):
    """The plain reference in the program's place, one stated guarantee
    broken (reference_server.py); unbroken, it has to pass."""
    p = subprocess.run(
        [sys.executable, os.path.join(checkout, "benchmarks", "control.py"),
         "--workload", workload, "--seeds", str(SEED), "--seconds", "3",
         "--break", broken, "--readback-wait", "2"], env=ENV,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    r = json.loads(p.stdout.splitlines()[-1])
    assert r["correct"] is correct and r["attempted"] > 0
    if caught_by:
        c = r["compared"][caught_by]
        assert c["value"] != c["limit"]


def test_a_cell_is_added_as_data_alone(checkout):
    """The temp checkout got a configuration, two mixes and a scrape-delta
    metric as files and entries; no file that was there differs."""
    cmp = filecmp.dircmp(rehearsal.BENCH,
                         os.path.join(checkout, "benchmarks"),
                         ignore=["__pycache__"])

    def walk(c):
        assert not c.diff_files and not c.left_only, (c.diff_files,
                                                      c.left_only)
        for sub in c.subdirs.values():
            walk(sub)

    walk(cmp)
    sys.path.insert(0, os.path.join(checkout, "benchmarks"))
    import run

    loaded = run.load_cell("ec2p2-4d.put-tiny", checkout)
    spec = next(m for m in loaded["per_layer"]
                if m["name"] == "commit_ms_per_op")
    before, after = rehearsal.recorded_scrapes()
    ctx = {"before": before, "after": after, "window": {}, "trace": {}}
    assert run.layer_value(spec, ctx) == pytest.approx(10.0)
    # and the same metric is not read in a cell that does not list it
    other = run.load_cell("ec2p2-4d.get-tiny", checkout)
    assert "commit_ms_per_op" not in [m["name"] for m in other["per_layer"]]
