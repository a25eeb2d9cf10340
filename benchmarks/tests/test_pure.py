"""The benchmark's arithmetic, each against numbers worked by hand."""

import json
import os

import numpy as np
import pytest

import blank
import reference
import rehearsal
import run
import scrape
import trace_reduce
import traffic
import verify
import work
import worker

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


# --- the plain reference ---------------------------------------------------


def test_gf_and_the_2_plus_2_code_by_hand():
    assert reference.gf_mul(0x80, 2) == 0x1D          # x^8 = x^4+x^3+x^2+1
    assert reference.gf_mul(3, 3) == 5 and reference.gf_mul(3, 4) == 12
    # V = [[1,0],[1,1],[1,2],[1,3]]; its top block is its own inverse, so
    # the parity rows are [1^2, 2] and [1^3, 3].
    assert reference.parity_rows(2, 2) == ((3, 2), (2, 3))
    data = np.array([[1, 2], [3, 4]], dtype=np.uint8)
    # p0 = 3*d0 ^ 2*d1 = [3^6, 6^8]; p1 = 2*d0 ^ 3*d1 = [2^5, 4^12]
    assert reference.encode_block(data, 2).tolist() == [[5, 14], [7, 8]]


ROWS_2P2 = np.array([[1, 2], [3, 4], [5, 14], [7, 8]], dtype=np.uint8)


@pytest.mark.parametrize("lost", [(0, 1), (0, 2), (0, 3), (1, 2), (1, 3),
                                  (2, 3)])
def test_decode_rows_of_the_2_plus_2_code_by_hand(lost):
    """The rows of the block above, two of four lost. By hand for (0, 2):
    what is left is d1 and p1 = 2*d0 ^ 3*d1, so d0 = inv(2) * (p1 ^ 3*d1)
    with inv(2) = 142 (2*142 = 0x11C = 0x11D ^ 1), and 142*3 = 142 ^ 1."""
    assert reference.gf_mul(2, 142) == 1 and reference.gf_mul(142, 3) == 143
    if lost == (0, 2):
        assert reference.decode_matrix(2, 2, (1, 3)) == ((143, 142), (1, 0))
    if lost == (0, 1):      # the parity block [[3, 2], [2, 3]] is its own
        assert reference.decode_matrix(2, 2, (2, 3)) == ((3, 2), (2, 3))
    present = {i: ROWS_2P2[i] for i in range(4) if i not in lost}
    assert reference.decode_rows(present, 2, 2).tolist() == [[1, 2], [3, 4]]


@pytest.mark.parametrize("start", range(16))
def test_decode_rows_at_12_plus_4_for_every_four_drives_in_a_row(start):
    """Drives d0-d3 lost: an object whose shards start at `start` has lost
    shards start .. start+3 (mod 16); the twelve left give the data back."""
    rng = np.random.default_rng([33, start])
    data = rng.integers(0, 256, (12, 257), dtype=np.uint8)
    rows = np.concatenate([data, reference.encode_block(data, 4)])
    lost = {(start + i) % 16 for i in range(4)}
    present = {i: rows[i] for i in range(16) if i not in lost}
    assert np.array_equal(reference.decode_rows(present, 12, 4), data)
    # and from the program's own ground truth, which reference.py never
    # imports
    from minio_tpu.ops import gf

    targets = tuple(sorted(lost & set(range(12))))
    if targets:
        got = gf.reconstruct_ref(rows, 12, tuple(sorted(present)), targets)
        assert np.array_equal(got, data[list(targets)])


def test_decode_rows_wants_k_rows():
    with pytest.raises(ValueError):
        reference.decode_rows({1: ROWS_2P2[1]}, 2, 2)
    with pytest.raises(ValueError):
        reference.decode_rows({1: ROWS_2P2[1], 4: ROWS_2P2[3]}, 2, 2)


def test_shard_files_layout_by_hand():
    body = bytes(range(1, 8))                          # 7 bytes, block 4, k=2
    files = reference.shard_files(body, 2, 2, 4, digest=lambda row: b"D")
    # block 1 = 1,2,3,4 -> rows [1,2] [3,4]; block 2 = 5,6,7 padded to
    # [5,6] [7,0].
    assert files[0] == b"D" + bytes([1, 2]) + b"D" + bytes([5, 6])
    assert files[1] == b"D" + bytes([3, 4]) + b"D" + bytes([7, 0])
    assert files[2][:3] == b"D" + bytes([5, 14])


def test_reference_agrees_with_the_program_it_never_imports():
    from minio_tpu.erasure.metadata import hash_order
    from minio_tpu.ops import gf, mxsum

    rng = np.random.default_rng(7)
    for k, m in ((12, 4), (8, 4), (2, 2)):
        d = rng.integers(0, 256, (k, 999), dtype=np.uint8)
        assert np.array_equal(reference.encode_block(d, m),
                              gf.encode_ref(d, m))
    for n in (0, 1, 16384, 87382):
        c = rng.integers(0, 256, n, dtype=np.uint8)
        assert reference.mxsum256(c) == mxsum.digest_np(c)
    for key in ("bench/a", "bench/s1/w0t0/0000001"):
        want = [s - 1 for s in hash_order(key, 16)]
        assert reference.shard_of_drive(*key.split("/", 1), 16) == want
    assert reference.write_quorum(12, 4) == 12
    assert reference.write_quorum(2, 2) == 3


# --- work and bytes --------------------------------------------------------


def test_codec_bytes_by_hand():
    mib = 1 << 20
    # 12+4, 10 MiB: 10 blocks; a shard row is ceil(2^20/12) = 87382 bytes.
    assert work.codec_bytes("PUT", 10 * mib, 12, 4, mib) == (
        10 * mib + 10 * 87382 * 4 + 10 * 16 * 32)
    assert work.codec_bytes("GET", 10 * mib, 12, 4, mib) == (
        10 * 87382 * 12 + 10 * 12 * 32)
    # 8+4, 128 KiB: one block, rows of exactly 16384 bytes.
    assert work.codec_bytes("PUT", 131072, 8, 4, mib) == (
        131072 + 16384 * 4 + 12 * 32)
    # 512 int-ops per input byte at m = 4, plus 16 per byte hashed.
    assert work.codec_int_ops("PUT", 131072, 8, 4, mib) == (
        16384 * 2 * 64 * 32 + 16384 * 12 * 16)
    least = work.least_seconds([("PUT", 131072)], 8, 4, mib, "TPU v5 lite")
    assert least["hbm_s"] == pytest.approx(196992 / 819e9)
    with pytest.raises(KeyError):
        work.peaks("TPU v9")


def test_codec_work_with_no_data_shard_lost_is_what_it_was():
    """The numbers every line of the ledger was reckoned with (pinned), and
    t = 0 said aloud gives the same."""
    mib = 1 << 20
    pinned = {("PUT", 10 * mib, 12, 4): (13986160, 5592448000),
              ("GET", 10 * mib, 12, 4): (10489680, 167773440),
              ("PUT", 131072, 8, 4): (196992, 70254592),
              ("GET", 131072, 8, 4): (131328, 2097152),
              ("GET", 3 * mib + 5, 2, 2): (3145990, 50331744)}
    for (verb, size, k, m), (nbytes, nops) in pinned.items():
        for t in ((), (0,)):
            assert work.codec_bytes(verb, size, k, m, mib, *t) == nbytes
            assert work.codec_int_ops(verb, size, k, m, mib, *t) == nops
    two = work.least_seconds([("GET", 10 * mib), ("GET", 10 * mib, 0)],
                             12, 4, mib, "TPU v5 lite")
    assert two["bytes"] == 2 * 10489680 and two["int_ops"] == 2 * 167773440


@pytest.mark.parametrize("t", [1, 2, 3, 4])
def test_codec_work_of_a_get_that_rebuilds_t_rows(t):
    """12+4, 10 MiB: the 12 rows that are left are read once, t rebuilt rows
    written, 12 digests a block; the rebuild is a [w, 96] x [96, 8t]
    contraction a block beside the 16 operations a byte hashed."""
    mib, shard = 1 << 20, 10 * 87382
    assert work.codec_bytes("GET", 10 * mib, 12, 4, mib, t) == (
        shard * 12 + shard * t + 10 * 12 * 32)
    assert work.codec_int_ops("GET", 10 * mib, 12, 4, mib, t) == (
        shard * 12 * 16 + shard * 2 * 96 * 8 * t)
    # a PUT encodes every parity row whatever is lost
    assert work.codec_bytes("PUT", 10 * mib, 12, 4, mib, t) == 13986160
    least = work.least_seconds([("GET", 10 * mib, t)], 12, 4, mib,
                               "TPU v5 lite")
    assert least["hbm_s"] == pytest.approx(
        (10489680 + shard * t) / 819e9)


def test_lost_data_shards_follow_from_the_key():
    """Drives d0-d3 lost of 16: t is 4 for nine starts of the rotation, 3, 2
    and 1 for two each, 0 for one (start 12: d0-d3 hold the parity)."""
    by_start = {}
    for i in range(400):
        shard_of = reference.shard_of_drive("bench", f"s1/pre/{i:06d}", 16)
        by_start[shard_of[0]] = work.lost_data_shards(shard_of, [0, 1, 2, 3],
                                                      12)
    assert [by_start[s] for s in range(16)] == [
        4, 4, 4, 4, 4, 4, 4, 4, 4, 3, 2, 1, 0, 1, 2, 3]
    assert work.lost_data_shards(list(range(16)), [], 12) == 0


def test_codec_work_of_a_heal_by_hand():
    """12+4, 10 MiB healed onto four blank drives: the 12 rows that are left
    are read once and checked, 4 rows written with their digests, whatever
    kernel does it and whether the rows are data or parity; pinned beside
    the t = 0 numbers above."""
    mib, shard = 1 << 20, 10 * 87382
    assert work.codec_bytes("HEAL", 10 * mib, 12, 4, mib, 4) == (
        shard * 12 + shard * 4 + 10 * 16 * 32) == 13986240
    assert work.codec_int_ops("HEAL", 10 * mib, 12, 4, mib, 4) == (
        shard * 16 * 16 + shard * 2 * 96 * 32) == 5592448000
    # one blank drive of the tiny set, a ragged object
    assert work.codec_bytes("HEAL", 3 * mib + 5, 2, 2, mib, 1) == (
        (3 * 524288 + 3) * 3 + 4 * 3 * 32)
    assert work.codec_bytes("HEAL", 10 * mib, 12, 4, mib) == (
        work.codec_bytes("GET", 10 * mib, 12, 4, mib))   # nothing to rebuild
    group = work.least_seconds([("HEAL", 10 * mib, 4)] * 8, 12, 4, mib,
                               "TPU v5 lite")
    assert group["bytes"] == 8 * 13986240
    assert group["hbm_s"] == pytest.approx(8 * 13986240 / 819e9)


# --- a configuration's state -----------------------------------------------


def _tree(tmp_path, n=4):
    roots = [str(tmp_path / "drives" / f"d{i}") for i in range(n)]
    for r in roots:
        os.makedirs(os.path.join(r, "bench", "key", "dir"))
        with open(os.path.join(r, "bench", "key", "dir", "part.1"), "wb") as f:
            f.write(b"x")
    return roots


def test_apply_state_leaves_nothing_to_write_under_a_lost_root(tmp_path):
    roots = _tree(tmp_path)
    lost = run.apply_state(roots, {"drives_lost": 2, "which": "first",
                                   "when": "after_preload"})
    assert lost == roots[:2]
    assert run.roots_present(lost) == set()
    assert run.roots_present(roots) == set(roots[2:])
    for r in lost:
        assert not os.path.exists(r) and os.path.lexists(r)
        # what the program's heal does to a root that is merely missing
        with pytest.raises(OSError):
            os.makedirs(os.path.join(r, ".mtpu.sys", "tmp"), exist_ok=True)
        with pytest.raises(OSError):
            open(os.path.join(r, "bench", "key", "dir", "part.1"), "rb")
        with pytest.raises(OSError):
            os.statvfs(r)
    assert os.path.isfile(os.path.join(roots[2], "bench", "key", "dir",
                                       "part.1"))
    # a blank drive mounted in a lost one's place is seen
    os.unlink(lost[0])
    os.mkdir(lost[0])
    assert run.roots_present(lost) == {lost[0]}
    assert verify.state_held({lost[0]}) == {"lost_drives_present": {
        "value": 1, "limit": 0, "better": "lower"}}
    assert verify.verdict(verify.state_held(set())) is True
    assert verify.verdict(verify.state_held({lost[0]})) is False


@pytest.mark.parametrize("state", [
    {"drives_lost": 0, "which": "first", "when": "after_preload"},
    {"drives_lost": 4, "which": "first", "when": "after_preload"},
    {"drives_lost": 1, "which": "last", "when": "after_preload"},
    {"drives_lost": 1, "which": "first", "when": "in_window"},
])
def test_apply_state_refuses_what_it_cannot_bring_about(tmp_path, state):
    roots = _tree(tmp_path)
    with pytest.raises(run.RunFailed):
        run.apply_state(roots, state)
    assert run.roots_present(roots) == set(roots)


BLANK_STATE = {"drives_blank": 2, "which": "first", "when": "after_preload",
               "again": "before_each_heal"}


def test_apply_state_leaves_a_blank_drive_its_root_and_its_bucket(tmp_path):
    roots = _tree(tmp_path)
    for r in roots:
        os.makedirs(os.path.join(r, ".mtpu.sys"))
        with open(os.path.join(r, ".mtpu.sys", "format.json"), "w") as f:
            f.write("{}")
    assert blank.shard_files_absent(roots, "bench", ["key"]) == 0
    assert run.apply_state(roots, BLANK_STATE) == roots[:2]
    assert blank.blank_roots(roots, BLANK_STATE) == roots[:2]
    for r in roots[:2]:   # online, formatted, with the bucket, no object
        assert os.listdir(os.path.join(r, "bench")) == []
        assert os.path.isfile(os.path.join(r, ".mtpu.sys", "format.json"))
    for r in roots[2:]:
        assert os.path.isfile(os.path.join(r, "bench", "key", "dir",
                                           "part.1"))
    assert run.roots_present(roots) == set(roots)
    assert blank.shard_files_absent(roots, "bench", ["key"]) == 2
    assert blank.shard_files_absent(roots[2:], "bench", ["key", "no"]) == 2
    # the worker's part: again, for some objects, where they are back
    aside = str(tmp_path / "taken")
    os.mkdir(aside)
    blank.blank_objects(roots[2:3], "bench", ["key", "never-there"], aside,
                        iter(["a", "b"]))
    assert os.listdir(os.path.join(roots[2], "bench")) == []
    assert os.listdir(aside) == ["a"] and os.path.isfile(
        os.path.join(aside, "a", "dir", "part.1"))
    assert blank.shard_files_absent(roots, "bench", ["key"]) == 3
    # no other state names blank drives
    assert blank.blank_roots(roots, None) == []
    assert blank.blank_roots(roots, {"drives_lost": 2}) == []


def test_journals_at_rest_wants_every_journal_as_a_file(tmp_path):
    roots = _tree(tmp_path)
    assert not blank.journals_at_rest(roots[:2], "bench", ["key"])
    for r in roots[:2]:
        open(os.path.join(r, "bench", "key", "meta.mp"), "w").close()
    assert blank.journals_at_rest(roots[:2], "bench", ["key"])
    assert not blank.journals_at_rest(roots[:3], "bench", ["key"])
    assert not blank.journals_at_rest(roots[:2], "bench", ["key", "other"])


@pytest.mark.parametrize("state", [
    {**BLANK_STATE, "drives_blank": 0}, {**BLANK_STATE, "drives_blank": 4},
    {**BLANK_STATE, "which": "last"}, {**BLANK_STATE, "when": "in_window"},
    {**BLANK_STATE, "again": "never"},
    {k: v for k, v in BLANK_STATE.items() if k != "again"},
])
def test_apply_state_refuses_a_blank_state_it_cannot_bring_about(tmp_path,
                                                                 state):
    roots = _tree(tmp_path)
    with pytest.raises(run.RunFailed):
        run.apply_state(roots, state)
    assert blank.shard_files_absent(roots, "bench", ["key"]) == 0


def test_drive_check_does_not_look_under_lost_roots(tmp_path):
    config = {"data_shards": 2, "parity_shards": 2, "block_size": 4}
    roots = [str(tmp_path / f"d{i}") for i in range(4)]
    want = reference.shard_files(bytes(range(1, 8)), 2, 2, 4)
    key = "s1/w0t0/0000001"
    for root, shard in zip(roots, reference.shard_of_drive("bench", key, 4)):
        d = os.path.join(root, "bench", key, "datadir")
        os.makedirs(d)
        with open(os.path.join(d, "part.1"), "wb") as f:
            # d0 holds a wrong file, d1 the right one: both are lost
            f.write(b"wrong" if root == roots[0] else want[shard])
    assert verify.DriveCheck(config, roots, "bench").check(key, want) == (
        3, 1)
    assert verify.DriveCheck(config, roots, "bench", roots[:2]).check(
        key, want) == (2, 0)


def test_the_degraded_cell_loads_by_name():
    loaded = run.load_cell("ec12p4-16d.get-10MiB.4lost")
    assert loaded["config"]["state"] == {
        "drives_lost": 4, "which": "first", "when": "after_preload"}
    assert loaded["config"]["guarantees"]["write_quorum_drives"] == 12
    twin = run.load_cell("ec12p4-16d.get-10MiB")
    assert "state" not in twin["config"]
    assert loaded["mix"] == twin["mix"]
    for key in ("drives", "data_shards", "parity_shards", "block_size"):
        assert loaded["config"][key] == twin["config"][key]
    # `op_p90_ms` is not this cell's (its spread does not fit the bound,
    # PERF.md section 2), so neither are the layer metrics that move it
    assert [m["name"] for m in loaded["end_to_end"]] == [
        "goodput_mibps", "setup_s"]
    assert [m["name"] for m in loaded["per_layer"]] == [
        "client_gap_pct", "codec_roofline", "device_idle_pct",
        "get_read_ms_per_op", "get_verify_ms_per_op", "get_send_ms_per_op",
        "get_chunks_per_hop", "get_vectored_send_pct",
        "get_decode_ms_per_op", "mrf_requeues_per_op"]
    assert all(m["moves"] == "goodput_mibps" for m in loaded["per_layer"])
    twin_names = [m["name"] for m in twin["per_layer"]]
    assert {"entry_ms_per_op", "ttfb_p90_ms", "loop_lag_ms",
            "get_decode_ms_per_op", "mrf_requeues_per_op"} <= set(twin_names)
    put = [m["name"] for m in run.load_cell("ec12p4-16d.put-10MiB")[
        "per_layer"]]
    assert "get_decode_ms_per_op" not in put


def test_the_heal_cell_loads_by_name_and_is_data_alone():
    """Configuration, mix and the four metrics it brings are files found by
    name; nothing that stood lists the cell."""
    cell = "ec12p4-16d.heal-10MiB.4blank"
    loaded = run.load_cell(cell)
    assert loaded["cell"]["chips"] == 1
    assert loaded["config"]["state"] == {
        "drives_blank": 4, "which": "first", "when": "after_preload",
        "again": "before_each_heal"}
    assert loaded["config"]["guarantees"]["heal_drives"] == 16
    twin = run.load_cell("ec12p4-16d.get-10MiB.4lost")
    for key in ("drives", "data_shards", "parity_shards", "block_size",
                "bitrot", "versioned", "pools", "nodes"):
        assert loaded["config"][key] == twin["config"][key]
    mix = loaded["mix"]
    assert [o["verb"] for o in mix["ops"]] == ["HEAL"]
    assert (mix["clients"], mix["processes"]) == (1, 1)
    assert mix["preload"]["objects"] == mix["verify_sample"] == 64
    assert [m["name"] for m in loaded["end_to_end"]] == [
        "goodput_mibps", "setup_s"]
    assert [m["name"] for m in loaded["per_layer"]] == [
        "client_gap_pct", "codec_roofline", "device_idle_pct",
        "heal_rebuild_ms_per_object", "heal_verify_ms_per_object",
        "heal_launches_per_object", "heal_compile_ms_per_object"]
    with open(os.path.join(rehearsal.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if cell in m.get("workloads", [])]
    assert listed == [m["name"] for m in loaded["per_layer"][3:]]
    for other in bench["workloads"]:
        if other["name"] != cell:
            assert not any(m["name"].startswith("heal_") for m in
                           run.load_cell(other["name"])["per_layer"])


def test_the_heal_metrics_on_expositions_by_hand():
    loaded = run.load_cell("ec12p4-16d.heal-10MiB.4blank")
    specs = {m["name"]: m for m in loaded["per_layer"]}

    def kernel(kind, name):
        return (f"minio_tpu_kernel_seconds_{kind}",
                (("backend", "tpu:pallas"), ("kernel", name)))

    before = {kernel("sum", "reconstruct_weights"): 1.0,
              kernel("count", "reconstruct_weights"): 100.0,
              kernel("sum", "verify_digests"): 2.0,
              kernel("count", "verify_digests"): 100.0,
              kernel("sum", "encode_digests"): 5.0,
              kernel("count", "encode_digests"): 64.0}
    after = {**before,
             kernel("sum", "reconstruct_weights"): 1.048,
             kernel("count", "reconstruct_weights"): 116.0,
             kernel("sum", "verify_digests"): 2.024,
             kernel("count", "verify_digests"): 116.0,
             ("minio_tpu_jit_compile_seconds_total",
              (("program", "jit(verify_digests)"),)): 0.032}
    # two HEALs of eight objects each
    ctx = {"before": before, "after": after, "trace": {},
           "window": {"client_ops": 2, "client_objects": 16}}
    assert run.layer_value(specs["heal_rebuild_ms_per_object"],
                           ctx) == pytest.approx(3.0)
    assert run.layer_value(specs["heal_verify_ms_per_object"],
                           ctx) == pytest.approx(1.5)
    assert run.layer_value(specs["heal_launches_per_object"], ctx) == 2.0
    assert run.layer_value(specs["heal_compile_ms_per_object"],
                           ctx) == pytest.approx(2.0)
    # no object healed in the window: nothing to divide by, nothing reported
    none = {**ctx, "window": {"client_ops": 0, "client_objects": 0}}
    assert all(run.layer_value(specs[n], none) is None for n in specs
               if n.startswith("heal_"))


def test_written_bytes_knows_heals_share():
    """What the cells that stood may write is what it was; a heal writes
    the blank drives' share, 4/12, of the bytes it heals."""
    mib = 1 << 20
    for cell, want in (("ec12p4-16d.put-10MiB", 8388608000),
                       ("ec8p4-12d.put-128KiB", 9437184000),
                       ("ec12p4-16d.get-10MiB", 894784853),
                       ("ec12p4-16d.get-10MiB.4lost", 894784853)):
        loaded = run.load_cell(cell)
        assert run.written_bytes(loaded["mix"], loaded["config"], 20) == want
    loaded = run.load_cell("ec12p4-16d.heal-10MiB.4blank")
    assert run.written_bytes(loaded["mix"], loaded["config"], 20) == (
        64 * 10 * mib * 16 // 12 + 500 * mib * 40 * 4 // 12)


@pytest.mark.parametrize("cell,n_end_to_end,n_per_layer", [
    ("ec12p4-16d.put-10MiB", 3, 14), ("ec8p4-12d.put-128KiB", 4, 15),
    ("ec12p4-16d.get-10MiB", 3, 15)])
def test_the_cells_that_stood_keep_their_metrics(cell, n_end_to_end,
                                                 n_per_layer):
    """What they reported at the parent, and for the GET cell the two of
    the degraded read."""
    loaded = run.load_cell(cell)
    assert len(loaded["end_to_end"]) == n_end_to_end
    assert len(loaded["per_layer"]) == n_per_layer


def test_the_two_metrics_of_the_degraded_read_on_recorded_expositions():
    before, after = rehearsal.recorded_scrapes()
    loaded = run.load_cell("ec12p4-16d.get-10MiB.4lost")
    specs = {m["name"]: m for m in loaded["per_layer"]}
    ctx = {"before": before, "after": after, "window": {"client_ops": 4},
           "trace": {}}
    # no GET between the two scrapes: nothing to divide by, nothing reported
    assert run.layer_value(specs["get_decode_ms_per_op"], ctx) is None
    # the counter is in neither scrape: no heal was put back
    assert run.layer_value(specs["mrf_requeues_per_op"], ctx) == 0.0
    after = dict(after)
    after[("minio_tpu_mrf_requeues_total", ())] = 6.0
    assert run.layer_value(specs["mrf_requeues_per_op"],
                           {**ctx, "after": after}) == pytest.approx(1.5)
    g = (("api", "GetObject"), ("plane", "s3"))
    for stage, secs in (("auth", 0.001), ("decode", 0.25)):
        after[("minio_tpu_stage_seconds_sum", g + (("stage", stage),))] = (
            before.get(("minio_tpu_stage_seconds_sum",
                        g + (("stage", stage),)), 0.0) + secs)
        after[("minio_tpu_stage_seconds_count", g + (("stage", stage),))] = (
            before.get(("minio_tpu_stage_seconds_count",
                        g + (("stage", stage),)), 0.0) + 2)
    assert run.layer_value(specs["get_decode_ms_per_op"],
                           {**ctx, "after": after}) == pytest.approx(125.0)


# --- scrape deltas ---------------------------------------------------------


def test_scrape_parser_and_deltas_on_two_recorded_expositions():
    before, after = rehearsal.recorded_scrapes()
    key = ("minio_tpu_stage_seconds_sum",
           (("api", "PutObject"), ("plane", "s3"), ("stage", "rx_drain")))
    assert before[key] == 0.022415 and after[key] == 0.122415
    here = os.path.dirname(DATA)
    with open(os.path.join(os.path.dirname(here), "layer_metrics",
                           "entry_ms_per_op.json")) as f:
        spec = json.load(f)
    # PutObject: auth +0.001, rx_drain +0.1, resp_drain +0.004 over 4 more
    # requests; GetObject unchanged -> 105 ms / 4.
    assert scrape.delta_ratio(before, after, spec, {}) == pytest.approx(26.25)
    fsyncs = {"numerator": [{"family": "minio_tpu_metaplane_fsyncs_total"}],
              "denominator": "client_ops"}
    assert scrape.delta_ratio(before, after, fsyncs,
                              {"client_ops": 4}) == pytest.approx(4.0)
    assert scrape.delta_ratio(before, after, fsyncs,
                              {"client_ops": 0}) is None
    assert scrape.backends(after) == {"native": 7.0}
    table = scrape.stage_table(before, after)
    assert table["PutObject"]["rx_drain"] == [25.0, 4]
    assert "GetObject" not in table


# --- the trace reduction ---------------------------------------------------


def test_union_counts_overlap_once():
    assert trace_reduce.union([(0, 10), (5, 12), (20, 30), (30, 31)]) == [
        (0, 12), (20, 31)]


def _planes(devices=1):
    ops = [("%fusion.1 = s32[8] fusion(...)", 100, 50),
           ("%all-reduce.2 = u8[4] all-reduce(...)", 140, 30),   # overlaps
           ("%copy.3 = u8[4] copy(...)", 400, 100)]
    mods = [("jit_encode(123)", 100, 80), ("jit_other(9)", 400, 100)]
    host = {"name": "/host:CPU", "lines": [{"name": "", "events": [
        ("$run", 0, 1000)]}]}
    devs = [{"name": f"/device:TPU:{i}", "lines": [
        {"name": "XLA Modules", "events": mods},
        {"name": "XLA Ops", "events": ops if i == 0 else ops[:1]}]}
        for i in range(devices)]
    return [host, *devs]


def test_reduction_by_hand_one_device():
    red = trace_reduce.reduce_planes(_planes(1), "tpu")
    # busy = [100,170) + [400,500) = 170 ns of a 1000 ns slice
    assert red["busy_s"] == pytest.approx(170e-9)
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["metrics"]["device_idle_pct"] == pytest.approx(83.0)
    assert "collective_pct" not in red["metrics"]
    assert red["breakdown"]["device_ops"][0] == [
        "jit_other/%copy.3", pytest.approx(100e-9)]
    gaps = dict(map(tuple, red["breakdown"]["idle_gaps"]))
    assert gaps["before jit_encode"] == pytest.approx(100e-9)
    assert gaps["before jit_other"] == pytest.approx(230e-9)
    assert gaps["before the slice's end"] == pytest.approx(500e-9)


def test_reduction_by_hand_four_devices():
    red = trace_reduce.reduce_planes(_planes(4), "tpu")
    # device 0 is the busiest (170 ns); the others ran one op (50 ns)
    assert red["busy_s_busiest"] == pytest.approx(170e-9)
    assert red["busy_s"] == pytest.approx((170 + 3 * 50) / 4 * 1e-9)
    assert red["metrics"]["collective_pct"] == pytest.approx(100 * 30 / 170)


def test_no_device_plane_is_a_failed_reduction():
    with pytest.raises(SystemExit):
        trace_reduce.reduce_planes(_planes(1)[:1], "tpu")
    with pytest.raises(SystemExit):
        trace_reduce.reduce_planes(_planes(1), "gpu")


# --- the generator ---------------------------------------------------------


def test_traffic_follows_from_the_seed():
    with open(os.path.join(os.path.dirname(os.path.dirname(DATA)), "traffic",
                           "get-10MiB.json")) as f:
        mix = json.load(f)
    a = traffic.OpStream(mix, 3000000000, 1, 2)
    b = traffic.OpStream(mix, 3000000000, 1, 2)
    assert [a.next() for _ in range(5)] == [b.next() for _ in range(5)]
    pre = traffic.preload_objects(mix, 3000000000)
    assert len(pre) == 64 and {o.size for o in pre} == {10485760}
    assert sum(traffic.split_clients(mix)) == 20
    assert traffic.make_body(5, 64, 1) == traffic.make_body(5, 64, 1)
    assert traffic.make_body(5, 64, 1) != traffic.make_body(6, 64, 1)


def _heal_mix():
    with open(os.path.join(os.path.dirname(os.path.dirname(DATA)), "traffic",
                           "heal-10MiB.json")) as f:
        return json.load(f)


def test_a_heal_is_of_a_group_and_goes_round_them_in_a_seeded_order():
    mix, seed = _heal_mix(), 3000000000
    pre = traffic.preload_objects(mix, seed)
    groups = traffic.preload_groups(mix, pre)
    assert [len(g) for g in groups] == [8] * 8
    assert [o for g in groups for o in g] == pre
    prefixes = [traffic.group_prefix(seed, g) for g in range(8)]
    assert prefixes[3] == "s3000000000/pre/g0003/"
    for g, (prefix, objs) in enumerate(zip(prefixes, groups)):
        assert all(o.key.startswith(prefix) for o in objs)
        assert not any(o.key.startswith(prefix) for o in pre
                       if o not in objs)
        assert not any(p.startswith(prefix) for p in prefixes if p != prefix)
    a = traffic.OpStream(mix, seed, 0, 0)
    ops = [a.next() for _ in range(24)]
    assert {op.verb for op in ops} == {"HEAL"}
    assert all(op.key == prefixes[op.body_index] and op.size == 8 * 10485760
               for op in ops)
    order = [op.body_index for op in ops]
    assert sorted(order[:8]) == list(range(8))       # every group once,
    assert order[8:16] == order[:8] == order[16:]    # then round again
    b = traffic.OpStream(mix, seed, 0, 0)
    assert [b.next() for _ in range(24)] == ops
    # every seed the same work, in another order
    def first_round(s):
        gen = traffic.OpStream(mix, s, 0, 0)
        return tuple(gen.next().body_index for _ in range(8))

    orders = {first_round(s) for s in range(1, 7)}
    assert len(orders) > 1 and all(sorted(o) == list(range(8))
                                   for o in orders)
    # a mix without groups keeps the keys it had
    with open(os.path.join(os.path.dirname(os.path.dirname(DATA)), "traffic",
                           "get-10MiB.json")) as f:
        get = json.load(f)
    assert traffic.preload_groups(
        get, traffic.preload_objects(get, seed)) == []
    assert traffic.preload_objects(get, seed)[5].key == (
        "s3000000000/pre/000005")
    with pytest.raises(ValueError, match="group_objects"):
        traffic.OpStream({**mix, "preload": get["preload"]}, seed, 0,
                         0).next()


def _item(key, before, after, **more):
    return {"bucket": "bench", "object": key, **more,
            "before": [{"endpoint": f"d{i}", "state": s}
                       for i, s in enumerate(before)],
            "after": [{"endpoint": f"d{i}", "state": s}
                      for i, s in enumerate(after)]}


@pytest.mark.parametrize("items,good", [
    ([_item("a", ["missing", "ok", "ok"], ["ok"] * 3),
      _item("b", ["corrupt", "ok", "ok"], ["ok"] * 3)], True),
    # the bucket's own item names no object
    ([{"bucket": "bench", "object": ""},
      _item("a", ["missing", "ok", "ok"], ["ok"] * 3),
      _item("b", ["missing", "ok", "ok"], ["ok"] * 3)], True),
    ([_item("a", ["missing", "ok", "ok"], ["ok"] * 3)], False),   # b?
    ([_item("a", ["missing", "ok", "ok"], ["ok"] * 3),
      _item("b", ["missing", "ok", "ok"], ["ok"] * 3),
      _item("c", ["missing", "ok", "ok"], ["ok"] * 3)], False),   # c?
    ([_item("a", ["missing", "ok", "ok"], ["ok"] * 3),
      _item("b", ["missing", "ok", "ok"], ["ok"] * 3, error="Lock")],
     False),
    ([_item("a", ["missing", "ok", "ok"], ["ok"] * 3),
      _item("b", ["missing", "ok", "ok"], ["missing", "ok", "ok"])], False),
    # found nothing to heal: the state did not hold before it
    ([_item("a", ["ok"] * 3, ["ok"] * 3),
      _item("b", ["missing", "ok", "ok"], ["ok"] * 3)], False),
    ([_item("a", ["missing", "ok", "ok"], ["ok"] * 3)] * 2
     + [_item("b", ["missing", "ok", "ok"], ["ok"] * 3)], False),
])
def test_what_a_heals_reply_has_to_say(items, good):
    body = json.dumps({"items": items}).encode()
    assert worker.healed(body, ["a", "b"], 1) is good


def test_a_heals_reply_that_is_no_such_reply():
    for body in (b"", b"<Error/>", b"[]", b'{"items": 3}',
                 b'{"items": [{"object": "a"}]}'):
        assert worker.healed(body, ["a"], 1) is False
    with pytest.raises(ValueError, match="sends PUT, GET, HEAL"):
        worker.one_request(None, "bench", traffic.Op("HEAD", "k", 1, 0),
                           None)


def test_the_sample_of_a_mix_that_heals_is_of_the_preloaded_objects():
    mix = _heal_mix()
    pre = traffic.preload_objects(mix, 7)
    whole = verify.sample_preloaded(pre, 64, 7)
    assert [(r["key"], r["size"], r["body_index"]) for r in whole] == [
        (o.key, o.size, o.body_index) for o in pre]
    some = verify.sample_preloaded(pre, 10, 7)
    assert 10 <= len(some) <= 12 and some[0] == whole[0] \
        and some[-1] == whole[-1]
    assert some == verify.sample_preloaded(pre, 10, 7)
    assert verify.sample_preloaded(pre, 0, 7) == []


def test_reduction_of_a_recorded_v5e_trace(tmp_path):
    """A cut of a real trace (one v5e, `ec12p4-16d.put-10MiB`, PR 24): six
    `jit_encode_with_digests` launches of 654.9 us each, read through
    jax.profiler.ProfileData as the reduction child reads it."""
    out = tmp_path / "red.json"
    assert trace_reduce.main(
        ["trace_reduce.py", os.path.join(DATA, "put-10MiB.v5e.xplane.pb"),
         str(out), "tpu"]) == 0
    red = json.loads(out.read_text())
    assert list(red["devices"]) == ["/device:TPU:0"]
    assert red["busy_s"] == pytest.approx(0.003930865, rel=1e-6)
    assert red["busy_s"] == red["busy_s_busiest"]
    assert dict(map(tuple, red["modules"]))[
        "jit_encode_with_digests"] == pytest.approx(6 * 654.9e-6, rel=1e-3)
    assert red["breakdown"]["device_ops"][0][0] == (
        "jit_encode_with_digests/%gf2_matmul_with_weights.1")
    assert red["metrics"]["device_idle_pct"] == pytest.approx(
        100 * (1 - red["busy_s"] / red["window_s"]))
    assert 99 < red["metrics"]["device_idle_pct"] < 100
    assert len(red["breakdown"]["idle_gaps"]) <= 10


class _Answer:
    def __init__(self, status):
        self.status, self.body = status, b""


def _patient(answers, health, give_up=60.0):
    """A read back against a program that gives `answers` in turn and says
    of its health what `health` holds in turn; a quarter second a sleep."""
    now = [0.0]
    answers, health = iter(answers), iter(health)
    fetch = verify.PatientFetch(
        lambda key: _Answer(next(answers)), lambda: next(health), give_up,
        clock=lambda: now[0],
        sleep=lambda s: now.__setitem__(0, now[0] + s))
    return fetch, now


@pytest.mark.parametrize("answers,health,status,asked_again", [
    # the answer, at once
    ([200], [True], 200, 0),
    # a 503 asks for a retry, healthy or not
    ([503, 503, 200], [True, True, True, True, True], 200, 2),
    # no drive to ask: 404 from a program that says it is not healthy is
    # late, not wrong; asked again once it is healthy
    ([404, 200], [True, False, False, True], 200, 1),
    # not healthy at the close: the first question waits
    ([200], [False, False, True], 200, 0),
    # an outage that began and ended under one request
    ([0, 200], [True, True, True], 200, 1),
    # twice running with the program healthy before and after: judged by
    # what it says
    ([404, 404], [True, True, True, True], 404, 1),
    ([404, 503, 500, 500], [True] * 8, 500, 3),
])
def test_a_read_back_waits_for_a_late_answer_only(answers, health, status,
                                                  asked_again):
    fetch, _ = _patient(answers, health)
    assert fetch("k").status == status
    assert fetch.asked_again == asked_again
    assert len(fetch.log) == sum(a != 200 for a in answers)


def test_a_read_back_gives_up_a_minute_past_the_close():
    forever = iter(lambda: 503, None)
    fetch, now = _patient(forever, iter(lambda: True, None), give_up=2.0)
    assert fetch("k").status == 503
    assert 2.0 < now[0] <= 2.5
    # and every later one is asked once
    assert fetch("k2").status == 503 and now[0] <= 2.5
