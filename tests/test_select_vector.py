"""Vectorized S3 Select equivalence tests: for every supported query
shape, the vector lane's event-stream output must be byte-identical to
the row engine's (exactness contract of s3select/vector.py)."""

import io
import os
import random

import pytest

from minio_tpu.native import lib as nativelib
from minio_tpu.s3select import vector
from minio_tpu.s3select.engine import S3SelectRequest, run_select
from minio_tpu.s3select.sql import parse

pytestmark = pytest.mark.skipif(
    not nativelib.csv_index_available(), reason="native lib unavailable")


def _req(expr, **kw):
    r = S3SelectRequest.__new__(S3SelectRequest)
    r.expression = expr
    r.input_format = kw.get("input_format", "CSV")
    r.compression = kw.get("compression", "NONE")
    r.csv_header = kw.get("csv_header", "USE")
    r.csv_delimiter = kw.get("csv_delimiter", ",")
    r.csv_quote = kw.get("csv_quote", '"')
    r.csv_comments = kw.get("csv_comments", "")
    r.json_type = "LINES"
    r.output_format = kw.get("output_format", "CSV")
    r.out_csv_delimiter = kw.get("out_csv_delimiter", ",")
    r.out_record_delimiter = kw.get("out_record_delimiter", "\n")
    return r


def _run_capture(data: bytes, req):
    """Frames (or the error class name) — errors must match across
    engines too (e.g. CAST over a dirty column raises in both)."""
    from minio_tpu.s3select.sql import SelectError

    try:
        return b"".join(run_select(io.BytesIO(data), req))
    except SelectError as e:
        return f"SelectError:{e}"


def _both(data: bytes, expr: str, **kw):
    """(vector result, row result) for the same request. BOTH plan
    compilers must be disabled for the row run — patching only the CSV
    one would make JSON comparisons tautological."""
    req = _req(expr, **kw)
    vec = _run_capture(data, req)
    real_csv = vector.compile_plan
    real_json = vector.compile_plan_json
    real_pq = vector.compile_plan_parquet
    # ALL plan compilers off for the row run — patching only some would
    # make the other formats' comparisons tautological.
    vector.compile_plan = lambda *_a, **_k: None
    vector.compile_plan_json = lambda *_a, **_k: None
    vector.compile_plan_parquet = lambda *_a, **_k: None
    try:
        row = _run_capture(data, req)
    finally:
        vector.compile_plan = real_csv
        vector.compile_plan_json = real_json
        vector.compile_plan_parquet = real_pq
    return vec, row


DATA = (b"id,price,qty,name\n"
        + b"".join(b"%d,%d.25,%d,item-%d\n" % (i, i % 97, i % 7, i)
                   for i in range(5000))
        + b'5000,,3,"quoted, name"\n'
        + b"5001,not-a-number,2,weird\n"
        + b'5002,"12.5",1,"say ""hi"""\n')


@pytest.mark.parametrize("expr", [
    "SELECT COUNT(*) FROM S3Object",
    "SELECT COUNT(*) FROM S3Object s WHERE CAST(s.price AS FLOAT) > 50",
    "SELECT COUNT(*), SUM(s.price), MIN(s.price), MAX(s.price), "
    "AVG(s.qty) FROM S3Object s",
    "SELECT SUM(s.price) FROM S3Object s WHERE s.qty >= 3 AND s.id < 4000",
    "SELECT COUNT(s.price) FROM S3Object s",      # counts non-missing
    "SELECT * FROM S3Object s WHERE s.price > 90",
    "SELECT * FROM S3Object s WHERE s.id >= 4995",  # hits odd tail rows
    "SELECT s.id, s.name FROM S3Object s WHERE s.qty = 0 AND s.id < 100",
    "SELECT * FROM S3Object s WHERE s.name = 'item-17'",
    "SELECT * FROM S3Object s WHERE NOT (s.price > 5) AND s.id < 50",
    "SELECT * FROM S3Object s WHERE s.id > 10 OR s.price < 1",
    "SELECT * FROM S3Object s WHERE s.id < 20 LIMIT 7",
    "SELECT COUNT(*) FROM S3Object s WHERE s.missingcol > 5",
    "SELECT COUNT(*) FROM S3Object s WHERE NOT (s.missingcol > 5)",
])
def test_vector_equals_row_engine(expr):
    vec, row = _both(DATA, expr)
    assert vec == row, expr


@pytest.mark.parametrize("kw", [
    {"output_format": "JSON"},
    {"csv_header": "NONE"},
    {"out_csv_delimiter": ";"},
])
def test_vector_equals_row_engine_variants(kw):
    expr = ("SELECT * FROM S3Object s WHERE s._2 > 90"
            if kw.get("csv_header") == "NONE"
            else "SELECT * FROM S3Object s WHERE s.price > 90")
    vec, row = _both(DATA, expr, **kw)
    assert vec == row, kw


def test_vector_handles_chunk_boundaries():
    # Force many chunk splits, incl. a quoted field containing newlines.
    rows = []
    rng = random.Random(5)
    for i in range(2000):
        if i % 97 == 0:
            rows.append(b'%d,"multi\nline\nfield",%d\n' % (i, i % 5))
        else:
            rows.append(b"%d,plain-%d,%d\n" % (i, rng.randrange(100), i % 5))
    data = b"a,b,c\n" + b"".join(rows)
    old = vector.CHUNK
    vector.CHUNK = 512
    try:
        vec, row = _both(data, "SELECT COUNT(*) FROM S3Object s "
                               "WHERE s.c >= 3")
        assert vec == row
        vec, row = _both(data, "SELECT * FROM S3Object s WHERE s.a < 300")
        assert vec == row
    finally:
        vector.CHUNK = old


@pytest.mark.parametrize("data", [
    b"a,b\r1,2\r3,4\r5,6\r",                  # CR-only terminators
    b"a,b\r\n1,2\r\n3,4\r\n",                # CRLF
    b"a,b\n\n1,2\n\n\n3,4\n\n",              # blank lines interleaved
])
def test_vector_handles_terminator_variants(data):
    for expr in ("SELECT COUNT(*) FROM S3Object s",
                 "SELECT * FROM S3Object s WHERE s.a > 2"):
        vec, row = _both(data, expr)
        assert vec == row, (expr, data[:20])


def test_unsupported_shapes_decline():
    # LIKE 'x%' / IN (...) now vectorize; shapes the lanes still can't
    # mirror exactly must keep declining.
    req = _req("SELECT * FROM S3Object s WHERE s.name LIKE '%x'")
    assert vector.compile_plan(parse(req.expression), req) is None
    # Wildcard-free LIKE is NOT byte equality ('$' also matches before a
    # trailing newline) — must stay on the row path.
    req = _req("SELECT * FROM S3Object s WHERE s.name LIKE 'abc'")
    assert vector.compile_plan(parse(req.expression), req) is None
    # CAST-wrapped string compares keep the cast's error semantics.
    req = _req("SELECT * FROM S3Object s "
               "WHERE CAST(s.name AS FLOAT) LIKE 'x%'")
    assert vector.compile_plan(parse(req.expression), req) is None
    req = _req("SELECT * FROM S3Object s "
               "WHERE CAST(s.name AS FLOAT) = 'paris'")
    assert vector.compile_plan(parse(req.expression), req) is None
    req = _req("SELECT * FROM S3Object s WHERE s.name LIKE 'a_c'")
    assert vector.compile_plan(parse(req.expression), req) is None
    req = _req("SELECT * FROM S3Object s "
               "WHERE s.name LIKE 'x!%' ESCAPE '!'")
    assert vector.compile_plan(parse(req.expression), req) is None
    req = _req("SELECT * FROM S3Object s WHERE s.id IN (1, s.other)")
    assert vector.compile_plan(parse(req.expression), req) is None
    # Numeric-ish string in IN: coercion rules differ -> decline.
    req = _req("SELECT * FROM S3Object s WHERE s.name IN ('500', 'x')")
    assert vector.compile_plan(parse(req.expression), req) is None
    req = _req("SELECT UPPER(s.name) FROM S3Object s")
    assert vector.compile_plan(parse(req.expression), req) is None
    # Numeric-looking string literal: coercion rules differ -> decline.
    req = _req("SELECT * FROM S3Object s WHERE s.name = '500'")
    assert vector.compile_plan(parse(req.expression), req) is None


def _best_of(fn, reps: int = 2) -> tuple[float, bytes]:
    """min-of-N wall time: under full-suite load a single-shot timing
    measures the scheduler, not the engine — the minimum is the run
    that dodged preemption, which is the engine's actual cost (the
    PR 12 flake note)."""
    import time

    best, out = float("inf"), b""
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return best, out


def test_vector_is_actually_faster():
    data = b"id,price,qty\n" + b"".join(
        b"%d,%d.5,%d\n" % (i, i % 1000, i % 7) for i in range(300_000))
    req = _req("SELECT COUNT(*), SUM(s.price) FROM S3Object s "
               "WHERE CAST(s.price AS FLOAT) > 500")
    t_vec, vec = _best_of(
        lambda: b"".join(run_select(io.BytesIO(data), req)))
    real_compile = vector.compile_plan
    vector.compile_plan = lambda *_a, **_k: None
    try:
        t_row, row = _best_of(
            lambda: b"".join(run_select(io.BytesIO(data), req)))
    finally:
        vector.compile_plan = real_compile
    assert vec == row
    # 2x on the min-of-N floor (standalone the engine measures ~10x):
    # the margin absorbs load-noise in the FLOOR itself, while a
    # vector-path regression to row-engine speed still fails by 2x.
    assert t_vec * 2 < t_row, (t_vec, t_row)


@pytest.mark.parametrize("expr", [
    "SELECT MIN(s.qty), MAX(s.id), MIN(s.price) FROM S3Object s",
    "SELECT MIN(s.id) FROM S3Object s WHERE s.id > 100",
])
def test_vector_minmax_integer_formatting(expr):
    # MIN/MAX over integer columns must serialize as ints ('0'), not
    # floats ('0.0') — the row engine keeps Python number types.
    vec, row = _both(DATA, expr)
    assert vec == row, expr


def test_vector_ragged_rows_match_row_engine():
    data = b"a,b\n1,2\n3,4,5\n6\n7,8\n"
    for expr in ("SELECT * FROM S3Object s WHERE s.a > 0",
                 "SELECT * FROM S3Object s"):
        vec, row = _both(data, expr)
        assert vec == row, expr


def test_vector_bigint_exact_comparison():
    # Integers beyond 2^53: float64 would collapse them; the row engine
    # compares exact ints. The vector lane must match.
    data = (b"a\n9007199254740993\n9007199254740992\n123\n")
    for expr in ("SELECT COUNT(*) FROM S3Object s "
                 "WHERE s.a = 9007199254740992",
                 "SELECT * FROM S3Object s WHERE s.a > 9007199254740992"):
        vec, row = _both(data, expr)
        assert vec == row, expr


def test_vector_on_gzip_compressed_input():
    import gzip

    data = b"a,b\n" + b"".join(b"%d,%d\n" % (i, i * 2) for i in range(5000))
    gz = gzip.compress(data)
    vec_req = _req("SELECT COUNT(*), SUM(s.b) FROM S3Object s "
                   "WHERE s.a >= 1000", compression="GZIP")
    vec = _run_capture(gz, vec_req)
    real = vector.compile_plan
    vector.compile_plan = lambda *a, **k: None
    try:
        row = _run_capture(gz, vec_req)
    finally:
        vector.compile_plan = real
    assert vec == row


# ---------------- JSON-LINES vector lane ----------------

JDATA = (b'{"id": 0, "price": 1.5, "name": "a"}\n'
         + b"".join(b'{"id": %d, "price": %d.25, "qty": %d, "name": "item-%d"}\n'
                    % (i, i % 97, i % 7, i) for i in range(1, 4000))
         + b'{"id": 4000, "price": "12.5", "name": "strnum"}\n'
         + b'{"id": 4001, "price": null, "name": "nullprice"}\n'
         + b'{"id": 4002, "name": "missing-price"}\n'
         + b'{"id": 4003, "price": true}\n'
         + b'{"id": 4004, "price": {"nested": 1}, "name": "complex"}\n'
         + b'{"id": 4005, "price": 3, "name": "say \\"hi\\""}\n'   # escape -> pyrow
         + b'\n'
         + b'{"id": 4006, "id": 4007, "price": 9}\n')              # dup key


@pytest.mark.parametrize("expr", [
    "SELECT COUNT(*) FROM S3Object",
    "SELECT COUNT(*) FROM S3Object s WHERE s.price > 50",
    "SELECT COUNT(s.price), SUM(s.price), MIN(s.price), MAX(s.price), "
    "AVG(s.price) FROM S3Object s",
    "SELECT COUNT(*) FROM S3Object s WHERE s.qty >= 3 AND s.id < 2000",
    "SELECT * FROM S3Object s WHERE s.price > 90",
    "SELECT s.id, s.name FROM S3Object s WHERE s.qty = 0 AND s.id < 100",
    "SELECT * FROM S3Object s WHERE s.name = 'item-17'",
    "SELECT * FROM S3Object s WHERE NOT (s.price > 5) AND s.id < 40",
    "SELECT * FROM S3Object s WHERE s.id >= 4000",   # all the odd tail rows
    "SELECT * FROM S3Object s WHERE s.id < 30 LIMIT 7",
    "SELECT COUNT(*) FROM S3Object s WHERE s.nope > 5",
    "SELECT MIN(s.id), MAX(s.id) FROM S3Object s",
])
@pytest.mark.parametrize("outfmt", ["JSON", "CSV"])
def test_json_vector_equals_row_engine(expr, outfmt):
    vec, row = _both(JDATA, expr, input_format="JSON",
                     output_format=outfmt)
    assert vec == row, (expr, outfmt)


def test_json_vector_malformed_lines_match():
    # Leading-zero numbers, trailing garbage, bare arrays: the row engine
    # raises SelectError — the vector lane must do exactly the same.
    for doc in (b'{"a": 05}\n', b'{"a": 1} trailing\n', b'[1, 2]\n',
                b'{"a": +3}\n', b'{"a": .5}\n'):
        vec, row = _both(b'{"a": 1}\n' + doc,
                         "SELECT COUNT(*) FROM S3Object s WHERE s.a > 0",
                         input_format="JSON")
        assert vec == row, doc


def test_json_vector_chunk_boundaries():
    old = vector.CHUNK
    vector.CHUNK = 256
    try:
        vec, row = _both(JDATA, "SELECT COUNT(*), SUM(s.price) FROM "
                                "S3Object s WHERE s.id < 3500",
                         input_format="JSON")
        assert vec == row
    finally:
        vector.CHUNK = old


def test_json_vector_faster():
    data = b"".join(b'{"id": %d, "price": %d.5, "qty": %d}\n'
                    % (i, i % 1000, i % 7) for i in range(200_000))
    req = _req("SELECT COUNT(*), SUM(s.price) FROM S3Object s "
               "WHERE s.price > 500", input_format="JSON")
    t_vec, vec = _best_of(lambda: _run_capture(data, req))
    realc, realj = vector.compile_plan, vector.compile_plan_json
    vector.compile_plan = lambda *a, **k: None
    vector.compile_plan_json = lambda *a, **k: None
    try:
        t_row, row = _best_of(lambda: _run_capture(data, req))
    finally:
        vector.compile_plan, vector.compile_plan_json = realc, realj
    assert vec == row
    # min-of-N + 1.5x margin: see _best_of — the JSON vector lane's
    # standalone ratio is ~4x, so a real regression still fails wide.
    assert t_vec * 1.5 < t_row, (t_vec, t_row)


def test_json_vector_nested_fields_exact():
    # Dotted columns addressing NESTED fields (flattened one level by the
    # row engine) plus a decoy literal top-level "s.price" key.
    data = (b'{"name": "alice", "nested": {"x": 1}}\n'
            b'{"name": "bob", "nested": {"x": 2}}\n'
            b'{"name": "carol", "s.price": 7}\n'
            b'{"name": "dave", "s": {"price": 9}}\n')
    for expr in ("SELECT name FROM S3Object WHERE nested.x = 1",
                 "SELECT * FROM S3Object s WHERE s.price > 5",
                 "SELECT COUNT(*), SUM(s.price) FROM S3Object s"):
        vec, row = _both(data, expr, input_format="JSON",
                         output_format="JSON")
        assert vec == row, expr


def test_json_vector_review_repros():
    """Exact reproductions from review: flattened-key shadowing of
    top-level candidates, and malformed values under NON-queried keys."""
    data = b'{"price": 5, "s": {"price": 9}}\n'
    vec, row = _both(data, "SELECT COUNT(*) FROM S3Object s "
                           "WHERE s.price > 6", input_format="JSON")
    assert vec == row  # flattened "s.price"=9 shadows top-level "price"=5
    data = b'{"x": 5, "nested": {"x": 7}}\n'
    vec, row = _both(data, "SELECT COUNT(*) FROM S3Object s "
                           "WHERE nested.x = 7", input_format="JSON")
    assert vec == row
    for doc in (b'{"a": 05, "price": 1}\n', b'{"id" 5, "price": 2}\n'):
        vec, row = _both(doc, "SELECT COUNT(*) FROM S3Object s "
                              "WHERE s.price > 0", input_format="JSON")
        assert vec == row, doc
        assert isinstance(vec, str) and vec.startswith("SelectError"), doc


# ---------------- Parquet column-chunk lane ----------------

def _parquet_blob():
    from minio_tpu.s3select.parquet import write_parquet

    rows = [{"id": i, "price": (i % 97) + 0.25, "qty": i % 7,
             "name": f"item-{i}"} for i in range(2000)]
    rows.append({"id": 2000, "price": None, "qty": 3, "name": "null-price"})
    rows.append({"id": 2001, "price": 1e18, "qty": 2, "name": "big"})
    schema = [("id", "int64"), ("price", "double"), ("qty", "int64"),
              ("name", "string")]
    return write_parquet(rows, schema)


@pytest.mark.parametrize("expr", [
    "SELECT COUNT(*) FROM S3Object",
    "SELECT COUNT(*), SUM(s.price) FROM S3Object s WHERE s.price > 50",
    "SELECT MIN(s.price), MAX(s.price), AVG(s.qty) FROM S3Object s "
    "WHERE s.qty <= 3",
    "SELECT s.id, s.name FROM S3Object s WHERE s.price > 90 LIMIT 7",
    "SELECT s.id FROM S3Object s WHERE s.name = 'item-42'",
    "SELECT SUM(s.id) FROM S3Object s",
])
def test_parquet_column_lane_matches_row_engine(expr):
    blob = _parquet_blob()
    vec, row = _both(blob, expr, input_format="PARQUET")
    assert vec == row, expr


def test_parquet_lane_engaged():
    """The column lane actually compiles for the aggregate shape (guards
    against silently comparing the row engine to itself)."""
    from minio_tpu.s3select.sql import parse

    req = _req("SELECT COUNT(*), SUM(s.price) FROM S3Object s "
               "WHERE s.price > 50", input_format="PARQUET")
    assert vector.compile_plan_parquet(parse(req.expression), req) is not None


def test_fused_leading_blank_line_header():
    """A blank first line must not become the header — the header is the
    first NON-blank record, as the batch filter implies."""
    data = b"\ncolname\n1\n2\n3\n"
    vec, row = _both(data, "SELECT SUM(s.colname) FROM S3Object s")
    assert vec == row


def test_fused_inf_nan_fields_take_exact_path():
    """Digit-free numeric spellings (inf/nan) parse via the row engine's
    float() — the fused lane must not count-without-summing them."""
    for field in (b"inf", b"nan", b"Infinity", b"-inf", b"NAN"):
        data = b"x\n1\n" + field + b"\n2\n"
        vec, row = _both(
            data, "SELECT SUM(s.x), COUNT(s.x), MAX(s.x) FROM S3Object s")
        assert vec == row, field


def test_parquet_bool_vs_string_literal():
    """Booleans compared to string literals take the row engine's
    coercion, both for = and <>."""
    from minio_tpu.s3select.parquet import write_parquet

    rows = [{"id": 1, "flag": True}, {"id": 2, "flag": False},
            {"id": 3, "flag": None}]
    blob = write_parquet(rows, [("id", "int64"), ("flag", "boolean")])
    for expr in ("SELECT s.id FROM S3Object s WHERE s.flag = 'True'",
                 "SELECT s.id FROM S3Object s WHERE s.flag <> 'True'"):
        vec, row = _both(blob, expr, input_format="PARQUET")
        assert vec == row, expr


def _parquet_edge_blob():
    from minio_tpu.s3select.parquet import write_parquet

    rows = [
        {"id": (1 << 53) + 3, "price": 1.5, "name": "café"},   # big int
        {"id": -(1 << 53) - 7, "price": 2.5, "name": ""},      # empty str
        {"id": 5, "price": None, "name": None},                # nulls
        {"id": 6, "price": 0.25, "name": "plain"},
        {"id": 7, "price": float("nan"), "name": "plain"},     # NaN
        {"id": 8, "price": -1.75, "name": "x" * 40},
    ]
    schema = [("id", "int64"), ("price", "double"), ("name", "string")]
    return write_parquet(rows, schema)


@pytest.mark.parametrize("expr", [
    # Big int64 beyond 2^53: fast accumulate must refuse; MIN/MAX exact.
    "SELECT SUM(s.id), MIN(s.id), MAX(s.id) FROM S3Object s",
    # NaN in the column: fast accumulate must refuse (min/max ordering).
    "SELECT SUM(s.price), MIN(s.price) FROM S3Object s",
    "SELECT COUNT(s.name), COUNT(s.price), COUNT(*) FROM S3Object s",
    # Non-ASCII page: bytes-level eq must refuse; exact path decides.
    "SELECT s.id FROM S3Object s WHERE s.name = 'café'",
    "SELECT s.id FROM S3Object s WHERE s.name = ''",
    "SELECT s.id FROM S3Object s WHERE s.name <> 'plain'",
    "SELECT AVG(s.price) FROM S3Object s WHERE s.id >= 5",
])
def test_parquet_fastpath_edges_match_row_engine(expr):
    blob = _parquet_edge_blob()
    vec, row = _both(blob, expr, input_format="PARQUET")
    assert vec == row, expr


def test_parquet_int_minmax_stays_int():
    """MIN/MAX over an int64 chunk must serialize as ints (the row
    engine's element type), not floats from a widened array."""
    from minio_tpu.s3select.parquet import write_parquet

    rows = [{"v": i} for i in (5, -3, 42)]
    blob = write_parquet(rows, [("v", "int64")])
    vec, row = _both(blob, "SELECT MIN(s.v), MAX(s.v) FROM S3Object s",
                     input_format="PARQUET")
    assert vec == row
    assert b"-3,42" in vec


def test_parquet_string_eq_long_values():
    """Values 128-255 bytes long put >=0x80 bytes in their length
    prefixes — the bytes-level eq must still engage (prefix bytes are not
    value bytes) and match the row engine."""
    from minio_tpu.s3select.parquet import write_parquet

    long_a = "a" * 200
    rows = [{"k": long_a}, {"k": "b" * 150}, {"k": "short"}] * 5
    blob = write_parquet(rows, [("k", "string")])
    expr = f"SELECT COUNT(*) FROM S3Object s WHERE s.k = '{long_a}'"
    vec, row = _both(blob, expr, input_format="PARQUET")
    assert vec == row
    assert b"\n5\n" in vec or b"5" in vec


CSV_STR = (b"id,name,city\n"
           + b"".join(b"%d,name%d,%s\n" % (i, i % 30,
                                           [b"paris", b"nyc", b"", b"lille"][i % 4])
                      for i in range(400)))


def test_like_prefix_vectorizes_and_matches_row():
    req = _req("SELECT COUNT(*) FROM S3Object s WHERE s.name LIKE 'name1%'")
    assert vector.compile_plan(parse(req.expression), req) is not None
    for expr in (
        "SELECT COUNT(*) FROM S3Object s WHERE s.name LIKE 'name1%'",
        "SELECT s.id FROM S3Object s WHERE s.city LIKE 'par%'",
        "SELECT s.id FROM S3Object s WHERE s.name NOT LIKE 'name2%'",
        "SELECT s.id FROM S3Object s WHERE s.city LIKE 'paris'",
        "SELECT s.id FROM S3Object s WHERE s.city LIKE '%'",
    ):
        vec, row = _both(CSV_STR, expr)
        assert vec == row, expr


def test_in_list_vectorizes_and_matches_row():
    req = _req("SELECT COUNT(*) FROM S3Object s WHERE s.id IN (1, 2, 3)")
    assert vector.compile_plan(parse(req.expression), req) is not None
    for expr in (
        "SELECT COUNT(*) FROM S3Object s WHERE s.id IN (1, 2, 3)",
        "SELECT s.id FROM S3Object s WHERE s.city IN ('paris', 'lille')",
        "SELECT s.id FROM S3Object s WHERE s.city NOT IN ('nyc')",
        "SELECT s.id FROM S3Object s "
        "WHERE s.id IN (7) OR s.city IN ('paris')",
    ):
        vec, row = _both(CSV_STR, expr)
        assert vec == row, expr


def test_like_trailing_newline_value_matches_row():
    # '$' in the row engine's LIKE regex matches before a trailing
    # newline; quoted CSV fields can carry one. Equivalence must hold.
    data = (b"id,city\n"
            b'1,paris\n'
            b'2,"paris\n"\n'
            b"3,lille\n")
    for expr in ("SELECT s.id FROM S3Object s WHERE s.city LIKE 'paris'",
                 "SELECT s.id FROM S3Object s WHERE s.city LIKE 'par%'"):
        vec, row = _both(data, expr)
        assert vec == row, expr


def test_like_in_jsonl_matches_row():
    import json as _json

    docs = b"".join(
        _json.dumps({"id": i, "name": f"name{i % 30}",
                     "city": ["paris", "nyc", None, "lille"][i % 4]}
                    ).encode() + b"\n"
        for i in range(300))
    for expr in (
        "SELECT COUNT(*) FROM S3Object s WHERE s.name LIKE 'name1%'",
        "SELECT s.id FROM S3Object s WHERE s.city IN ('paris', 'lille')",
        "SELECT s.id FROM S3Object s WHERE s.name NOT LIKE 'name2%'",
    ):
        vec, row = _both(docs, expr, input_format="JSON",
                         output_format="JSON")
        assert vec == row, expr
