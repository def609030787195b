"""Ingest's independent units on the one host pool (``shared.host_pool``):
the string columns of a frame (``table._frame_arrays``), the part files of a
read (``data_ingest.read_host_frame``) and the buckets of a long column
(``table._bucketed_encode``).  Side by side they must give what the loops
gave: codes, vocab, dtypes, file order, the guard's verdicts, and the rows of
the pass's phase tree; with one thread in the pool nothing may wait on it."""

import os
import threading
import time

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from anovos_tpu import obs
from anovos_tpu.data_ingest import data_ingest, guard
from anovos_tpu.shared import host_pool
from anovos_tpu.shared import table as table_mod
from anovos_tpu.shared.table import Table

ROWS = 3000


@pytest.fixture
def pool(request, monkeypatch):
    """The process's pool replaced by one of ``request.param`` threads (the
    calling thread counted), and every frame and read long enough for it."""
    made = host_pool.HostPool(request.param)
    monkeypatch.setattr(host_pool, "_POOL", made)
    monkeypatch.setattr(table_mod, "_POOLED_COLUMNS_MIN_ROWS", 1)
    monkeypatch.setattr(data_ingest, "_POOLED_DECODE_MIN_BYTES", 1)
    yield made
    if made._executor is not None:
        made._executor.shutdown(wait=True)


def _inline(monkeypatch):
    monkeypatch.setattr(table_mod, "_POOLED_COLUMNS_MIN_ROWS", 1 << 40)
    monkeypatch.setattr(data_ingest, "_POOLED_DECODE_MIN_BYTES", 1 << 60)


# ------------------------------------------------------------------ the pool itself ----
@pytest.mark.parametrize("threads", [1, 2, 4])
def test_units_come_back_in_order_and_a_unit_may_hand_units_to_the_pool(threads):
    """Nested calls from every unit, with fewer threads than units waiting:
    a unit that waited for a queue would hang here."""
    p = host_pool.HostPool(threads)

    def outer(i):
        return [i * 10 + j for j in p.run(lambda j: j, list(range(5))).results]

    ran = p.run(outer, list(range(7)))
    assert ran.results == [[i * 10 + j for j in range(5)] for i in range(7)]
    assert (ran.workers == 0) if threads == 1 else (1 <= ran.workers <= threads)
    assert ran.wall_s >= 0.0
    inline = p.run(outer, list(range(7)), side_by_side=False)
    assert inline.results == ran.results and inline.workers == 0


def test_units_overlap_and_the_first_error_in_order_is_raised():
    p = host_pool.HostPool(4)
    seen = set()

    def sleeper(i):
        seen.add(threading.get_ident())
        time.sleep(0.05)
        return i

    t0 = time.perf_counter()
    ran = p.run(sleeper, list(range(16)))
    assert time.perf_counter() - t0 < 0.6 and ran.workers == len(seen) > 1  # 0.8 s one after the other
    assert ran.wall_s <= time.perf_counter() - t0
    started = []

    def failing(i):
        started.append(i)
        if i in (1, 2):
            time.sleep(0.05 if i == 1 else 0.0)  # the later unit fails first
            raise ValueError(f"unit {i}")
        time.sleep(0.2)
        return i

    with pytest.raises(ValueError, match="unit 1"):
        p.run(failing, list(range(40)))
    assert len(started) < 40  # a failed unit stops further units from starting


# ------------------------------------------------------------------ columns ----
def _frame(n=ROWS, seed=5):
    g = np.random.default_rng(seed)

    def pick(values, null=0.0):
        out = np.array(values, dtype=object)[g.integers(0, len(values), n)]
        out[g.random(n) < null] = None
        return out

    mixed = np.empty(n, dtype=object)
    mixed[:] = [[1, "1", 1.0, True, b"x", None][i % 6] for i in range(n)]
    ids = np.array([f"id{int(i):06d}" for i in g.integers(0, n, n)], dtype=object)
    ids[g.random(n) < 0.01] = None
    return pd.DataFrame({
        "nulls": pd.Series(pick(["x", "y", "é", ""], null=0.3), dtype="str"),
        "number": g.normal(size=n),
        "category": pd.Series(pick([3, 1, 2, 10], null=0.1)).astype("category"),
        "object_loop": pd.Series(mixed),
        "surrogate": pd.Series(pick(["\ud800", "a", "\ud800b"], null=0.1), dtype=pd.StringDtype("python")),
        "bucketed": pd.Series(ids, dtype="str"),
        "integer": g.integers(0, 9, n),
    })


COLUMNS = ["nulls", "category", "object_loop", "surrogate", "bucketed"]


@pytest.mark.parametrize("pool", [1, 4], indirect=True)
@pytest.mark.parametrize("column", COLUMNS)
def test_pooled_encode_equals_the_loop(pool, column, monkeypatch):
    monkeypatch.setattr(table_mod, "_BUCKETED_ENCODE_MIN_ROWS", 1000)
    bucketed = []
    real = table_mod._bucketed_encode
    monkeypatch.setattr(table_mod, "_bucketed_encode", lambda s: bucketed.append(len(s)) or real(s))
    df = _frame()
    got = Table.from_pandas(df)
    assert bucketed == [ROWS]  # the ids, their buckets units of the same pool
    _inline(monkeypatch)
    want = Table.from_pandas(df)
    assert got.col_names == want.col_names == list(df.columns)
    a, b = got[column], want[column]
    assert a.kind == b.kind == "cat" and a.dtype_name == b.dtype_name
    assert a.vocab.dtype == b.vocab.dtype == object and list(a.vocab) == list(b.vocab)
    assert all(type(v) is str for v in a.vocab)
    assert np.asarray(a.data).dtype == np.asarray(b.data).dtype
    assert np.array_equal(np.asarray(a.data), np.asarray(b.data))
    assert np.array_equal(np.asarray(a.mask), np.asarray(b.mask))
    for name in ("number", "integer"):
        assert np.array_equal(np.asarray(got[name].data), np.asarray(want[name].data))


# ------------------------------------------------------------------ parts ----
def _write_parts(d, nparts=5, rows=40):
    os.makedirs(d, exist_ok=True)
    paths = []
    for i in range(nparts):
        p = os.path.join(d, f"part-{i:05d}.parquet")
        pd.DataFrame({"part": np.full(rows, i), "row": np.arange(rows),
                      "word": [f"w{i}_{j % 7}" for j in range(rows)]}).to_parquet(p, index=False)
        paths.append(p)
    return paths


@pytest.fixture
def fresh_guard(monkeypatch):
    monkeypatch.setenv("ANOVOS_INGEST_RETRIES", "0")
    guard.reset()
    yield
    guard.reset()


PART_CASES = ["file_order", "bad_middle_quarantined", "fail_raises_the_first_bad_part", "all_bad"]


@pytest.mark.parametrize("pool", [1, 4], indirect=True)
@pytest.mark.parametrize("case", PART_CASES)
def test_pooled_read_host_frame_is_the_loops(pool, case, tmp_path, monkeypatch, fresh_guard):
    paths = _write_parts(str(tmp_path / "d"))
    bad = {"file_order": [], "bad_middle_quarantined": [2], "fail_raises_the_first_bad_part": [3, 1],
           "all_bad": range(5)}[case]
    for i in bad:
        with open(paths[i], "wb") as f:
            f.write(b"garbage")
    if case == "fail_raises_the_first_bad_part":
        monkeypatch.setenv("ANOVOS_INGEST_ON_CORRUPT", "raise")
        with pytest.raises(guard.IngestError, match="part-00001.parquet"):
            data_ingest.read_host_frame(paths, "parquet", {})
        assert guard.records() == []
        return
    if case == "all_bad":
        with pytest.raises(guard.IngestError, match="every parquet part was quarantined"):
            data_ingest.read_host_frame(paths, "parquet", {})
        assert sorted(os.path.basename(r.file) for r in guard.records()) == [os.path.basename(p) for p in paths]
        return
    got = data_ingest.read_host_frame(paths, "parquet", {})
    kept = [i for i in range(5) if i not in bad]
    assert list(got["part"]) == [i for i in kept for _ in range(40)]  # file order
    assert list(got["row"]) == list(range(40)) * len(kept)
    assert [os.path.basename(r.file) for r in guard.records()] == [f"part-{i:05d}.parquet" for i in bad]
    _inline(monkeypatch)
    guard.reset()
    want = data_ingest.read_host_frame(paths, "parquet", {})
    pd.testing.assert_frame_equal(got, want)
    assert list(got.dtypes) == list(want.dtypes)


# ------------------------------------------------------------------ a frame's columns ----
def _loop_assemble(frames, cfg, pol):
    """The reference: ``_assemble_frames`` as it was before a column became a
    unit: one ``pd.concat`` of the part frames, then a loop over the columns
    for each of the Arrow-typed conversion, ``inferSchema``'s re-coercion and
    the sanitization (``guard.sanitize_frame``)."""
    aligned = guard.reconcile_frames(frames, pol)
    df = aligned[0] if len(aligned) == 1 else pd.concat(aligned, ignore_index=True)
    converted = {c: table_mod.arrow_typed_to_numpy(df[c]) for c in df.columns
                 if table_mod.arrow_typed_kind(df[c].dtype)}
    if converted:
        df = pd.DataFrame({c: converted.get(c, df[c]) for c in df.columns}, copy=False)
    if str(cfg.get("inferSchema", True)).lower() in ("true", "1", "none"):
        for c in df.columns:
            if df[c].dtype == object or str(df[c].dtype) in ("string", "str"):
                nonnull = df[c].notna()
                if nonnull.any():
                    head = df[c][nonnull].iloc[:1024]
                    if pd.to_numeric(head, errors="coerce").isna().any():
                        continue
                    coerced = pd.to_numeric(df[c], errors="coerce")
                    if coerced[nonnull].notna().all():
                        df[c] = coerced
                else:
                    df[c] = pd.to_numeric(df[c], errors="coerce")
    return guard.sanitize_frame(df, pol), host_pool.UnitsRun([], 0, 0.0)


def _hostile(n, seed):
    g = np.random.default_rng(seed)
    wide, narrow = g.normal(size=n), g.normal(size=n).astype(np.float32)
    if seed == 1:  # the middle part alone: the gate is taken a part at a time
        wide[[3, 9]], wide[4], wide[[5, 6, 7]], wide[8] = np.inf, -np.inf, [1e39, -1e39, 3.5e38], np.nan
        narrow[2] = -np.inf
    text = [str(v) for v in g.integers(0, 50, n)]  # a float column only once inferSchema has looked
    if seed == 1:
        text[1] = "1e39"
    return pd.DataFrame({"wide": wide, "narrow": narrow, "clean": g.normal(size=n), "count": np.arange(n),
                         "as_text": pd.Series(text, dtype="str")})


def _typed(n, seed):
    """A decimal and a date (they stay Arrow in a part frame) beside a float."""
    import datetime
    import decimal

    g = np.random.default_rng(seed)
    price = pa.array([None if i % 7 == seed else decimal.Decimal(int(v)) / 100
                      for i, v in enumerate(g.integers(-10**9, 10**9, n))], pa.decimal128(12, 2))
    day = pa.array([None if i % 5 == seed else datetime.date(1992, 1, 1) + datetime.timedelta(int(v))
                    for i, v in enumerate(g.integers(0, 2500, n))], pa.date32())
    return pa.table({"price": price, "day": day, "x": g.normal(size=n)})


def _ints(n, seed):
    """``some``: nulls in the middle part only (numpy integer + nullable -> nullable)."""
    g = np.random.default_rng(seed)
    some = pd.array(g.integers(-2**40, 2**40, n), dtype="Int64")
    if seed == 1:
        some[[0, 5]] = pd.NA
    return pd.DataFrame({"some": some, "big": g.integers(-2**62, 2**62, n),
                         "small": g.integers(0, 99, n).astype(np.int32), "flag": g.random(n) < 0.5})


def _floats(n, seed):
    g = np.random.default_rng(seed)
    x = g.normal(size=n)
    x[g.random(n) < 0.1] = np.nan
    return pd.DataFrame({"x": x, "y": g.normal(size=n).astype(np.float32), "when": pd.Timestamp("2015-01-01")
                         + pd.to_timedelta(g.integers(0, 10**6, n), unit="s")})


def _strings(n, seed):
    g = np.random.default_rng(seed)
    words = np.array(["x", "y", "é", "", None], dtype=object)
    return pd.DataFrame({"word": pd.Series(words[g.integers(0, 5, n)], dtype="str"),
                         "digits": pd.Series([str(v) for v in g.integers(0, 50, n)], dtype="str"),
                         "key": pd.Series([f"k{v:05d}" for v in g.integers(0, n, n)], dtype="str")})


def _all_null_part(n, seed):
    """``late``: numeric but for the middle part, which holds nothing;
    ``first``: nothing in the first part, so the schema's side is a string
    column and the numbers after it arrive as strings; ``never``: no value
    in any part; ``words``: nothing, then strings (an ``object`` column of
    the frame, not a ``str`` one)."""
    g = np.random.default_rng(seed)
    return pd.DataFrame({"late": [None] * n if seed == 1 else g.normal(size=n),
                         "first": [None] * n if seed == 0 else g.integers(0, 9, n).astype(float),
                         "never": [None] * n, "x": g.normal(size=n),
                         "words": [None] * n if seed == 0 else _strings(n, seed)["word"]})


def _drifted(n, seed):
    df = _floats(n, seed).assign(word=_strings(n, seed)["word"])
    return df.drop(columns="y") if seed == 1 else df.assign(extra=1.0) if seed == 2 else df


# case: (a part's frame or Arrow table from (rows, part number), parts, the guard's environment)
ASSEMBLE_CASES = {
    "float": (_floats, 3, {}),
    "int_and_nulls_in_one_part": (_ints, 3, {}),
    "strings_keep_their_chunks": (_strings, 4, {}),
    "decimal_and_date": (_typed, 3, {}),
    "all_null_part": (_all_null_part, 3, {}),
    "missing_and_extra_column": (_drifted, 3, {}),
    "hostile_masked": (_hostile, 3, {}),
    "hostile_clipped": (_hostile, 3, {"ANOVOS_INGEST_SANITIZE": "clip"}),
    "hostile_kept": (_hostile, 3, {"ANOVOS_INGEST_SANITIZE": "keep"}),
    "single_part_typed": (_typed, 1, {}),
    "single_part_hostile": (lambda n, seed: _hostile(n, 1), 1, {}),
}
_COUNTERS = ("ingest_schema_drift_total", "ingest_sanitized_values_total")


def _write_case(case, d, rows=60):
    make, nparts, env = ASSEMBLE_CASES[case]
    os.makedirs(d, exist_ok=True)
    paths = []
    for i in range(nparts):
        part = make(rows + i, i)
        paths.append(os.path.join(d, f"part-{i:05d}.parquet"))
        pq.write_table(part if isinstance(part, pa.Table) else pa.Table.from_pandas(part, preserve_index=False),
                       paths[-1])
    return paths, env


def _read_and_what_the_guard_said(paths, caplog):
    obs.get_metrics().reset()
    caplog.clear()
    with caplog.at_level("WARNING", logger="anovos_tpu.data_ingest.guard"):
        df = data_ingest.read_host_frame(paths, "parquet", {})
    return df, {name: obs.get_metrics().counter(name).series() for name in _COUNTERS}, sorted(caplog.messages)


@pytest.mark.parametrize("pool", [1, 4], indirect=True)
@pytest.mark.parametrize("case", ASSEMBLE_CASES)
def test_a_frame_assembled_a_column_at_a_time_is_the_loops(pool, case, tmp_path, monkeypatch, fresh_guard, caplog):
    paths, env = _write_case(case, str(tmp_path / "d"))
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    ran = []
    real = data_ingest.record_units
    monkeypatch.setattr(data_ingest, "record_units", lambda what, r, **c: ran.append((what, r, c)) or real(what, r, **c))
    got, got_counts, got_warnings = _read_and_what_the_guard_said(paths, caplog)
    (assembled,) = [(r, c) for what, r, c in ran if what == "assemble"]
    assert assembled[1] == {"columns": got.shape[1]} and len(assembled[0].results) == got.shape[1]
    assert (assembled[0].workers == 0) if pool.threads == 1 else (1 <= assembled[0].workers <= 4)
    monkeypatch.setattr(data_ingest, "_assemble_frames", _loop_assemble)
    want, want_counts, want_warnings = _read_and_what_the_guard_said(paths, caplog)
    pd.testing.assert_frame_equal(got, want)
    assert got.equals(want) and list(got.dtypes) == list(want.dtypes) and got.index.equals(want.index)
    assert got_counts == want_counts and got_warnings == want_warnings
    for c in got.columns:  # an Arrow-backed column: the chunks pd.concat of the frames leaves (test_string_encode)
        if hasattr(want[c].array, "_pa_array"):
            assert [len(k) for k in got[c].array._pa_array.chunks] == [len(k) for k in want[c].array._pa_array.chunks]
    # what the case is there for
    if case == "int_and_nulls_in_one_part":
        assert str(got["some"].dtype) == "Int64" and got["some"].isna().sum() == 2 and got["small"].dtype == np.int32
    if case == "strings_keep_their_chunks":
        assert got["word"].array._pa_array.num_chunks == 4 and got["digits"].dtype == np.int64
    if case in ("decimal_and_date", "single_part_typed"):
        assert got["price"].dtype == np.float64 and got["day"].dtype == np.dtype("M8[s]")
    if case == "all_null_part":
        assert [got[c].dtype for c in ("late", "first", "never")] == [np.float64] * 3 and got["never"].isna().all()
        assert got["late"].isna().sum() == 61 and got["first"].isna().sum() == 60 and got["words"].dtype == object
    if case == "missing_and_extra_column":
        assert "extra" not in got and got["y"].isna().sum() == 61
        assert got_counts["ingest_schema_drift_total"] == {'kind="extra_col"': 1.0, 'kind="missing_col"': 1.0}
    if case.startswith("hostile") or case == "single_part_hostile":
        n = {kind: got_counts["ingest_sanitized_values_total"].get(f'column="wide",kind="{kind}"', 0)
             for kind in ("posinf", "neginf", "overflow")}
        kept = case == "hostile_kept"
        assert n == ({"posinf": 0, "neginf": 0, "overflow": 0} if kept else {"posinf": 2, "neginf": 1, "overflow": 3})
        assert len(got_warnings) == (0 if kept else 3) and got["narrow"].dtype == (np.float32 if kept else np.float64)
        assert np.isinf(got["wide"]).sum() == (3 if kept else 0)


@pytest.mark.parametrize("rows", [table_mod._POOLED_COLUMNS_MIN_ROWS - 1, table_mod._POOLED_COLUMNS_MIN_ROWS])
def test_the_rows_of_the_frame_decide_who_assembles_it(rows, monkeypatch):
    """The uploads' rule, on the parts' lengths summed: one row under it the
    columns run on the calling thread, at it side by side; the frame is the
    loop's either way."""
    made = host_pool.HostPool(4)
    monkeypatch.setattr(host_pool, "_POOL", made)
    frames = [(f"p{i}", _floats(n, i).assign(word=_strings(n, i)["word"])) for i, n in enumerate((rows - 70_000, 70_000))]
    on = set()
    real = data_ingest._assemble_column
    monkeypatch.setattr(data_ingest, "_assemble_column",
                        lambda *a: on.add(threading.current_thread().name) or time.sleep(0.02) or real(*a))
    pol = guard.policy_from_env()
    got, ran = data_ingest._assemble_frames(frames, {}, pol)
    want, _ = _loop_assemble(frames, {}, pol)
    assert len(got) == rows and got.equals(want) and list(got.dtypes) == list(want.dtypes)
    if rows < table_mod._POOLED_COLUMNS_MIN_ROWS:
        assert ran.workers == 0 and on == {threading.current_thread().name}
    else:
        assert 1 < ran.workers <= 4 and len(on) == ran.workers
    made._executor.shutdown(wait=True)


@pytest.mark.parametrize("pool", [1, 4], indirect=True)
def test_a_column_that_fails_raises_the_first_columns_error_in_column_order(pool, monkeypatch):
    frames = [(f"p{i}", pd.DataFrame({c: np.arange(5.0) for c in "abcdefgh"})) for i in range(2)]
    real = data_ingest._assemble_column

    def failing(name, *a):
        if name in "cf":
            time.sleep(0.05 if name == "c" else 0.0)  # the later column fails first
            raise ValueError(f"column {name}")
        return real(name, *a)

    monkeypatch.setattr(data_ingest, "_assemble_column", failing)
    with pytest.raises(ValueError, match="column c"):
        data_ingest._assemble_frames(frames, {}, guard.policy_from_env())


# ------------------------------------------------------------------ the pass's tree ----
def _read_in_a_pass(path):
    tr = obs.get_tracer()
    with tr.run_pass():
        with tr.phase("ingest"):
            tbl = data_ingest.read_dataset(path, "parquet")
    return tbl, tr.phases()


@pytest.mark.parametrize("pool", [1, 4], indirect=True)
def test_rows_written_from_pool_threads_are_in_the_tree(pool, tmp_path, monkeypatch):
    paths = _write_parts(str(tmp_path / "d"), nparts=6, rows=500)
    df = pd.concat([pd.read_parquet(p) for p in paths], ignore_index=True)
    for i, p in enumerate(paths):  # four string columns, so that there is something to overlap
        part = df[df["part"] == i].assign(key=lambda d: "k" + d["row"].astype(str), flag="f",
                                          twin=lambda d: d["word"])
        part.to_parquet(p, index=False)
    tbl, rows = _read_in_a_pass(str(tmp_path / "d"))
    assert tbl.nrows == 3000
    (ingest,) = [r for r in rows if r["name"] == "ingest"]
    (read,) = [r for r in rows if r["name"] == "io:read_dataset"]
    decode = [r for r in rows if r["name"] == "ingest/decode"]
    encode = [r for r in rows if r["name"] == "ingest/encode"]
    assert len(decode) == 6 and len(encode) == 4
    for r in decode + encode:
        assert r["parent"] == "io:read_dataset"
        assert ingest["start_s"] <= read["start_s"] <= r["start_s"] <= r["end_s"] <= read["end_s"] <= ingest["end_s"]
    assert sorted(r["counts"]["bytes"] for r in decode) == sorted(os.path.getsize(p) for p in paths)
    assert all(r["counts"]["rows"] == 500 for r in decode)
    for counts in (r["counts"] for r in encode):
        assert set(counts) == {"rows", "distinct", "hashed", "native_sort", "hash_s", "sort_s"}
        assert counts["rows"] == 3000 and counts["hashed"] == 1 and counts["native_sort"] == 1
    assert sorted(r["counts"]["distinct"] for r in encode) == [1, 42, 42, 500]
    counts = read["counts"]
    threads = {r["thread"] for r in decode + encode}
    if pool.threads == 1:
        assert counts["decode_workers"] == counts["encode_workers"] == 0 and len(threads) == 1
    else:
        assert 1 <= counts["decode_workers"] <= 4 and 1 <= counts["encode_workers"] <= 4
        assert len({r["thread"] for r in decode}) == counts["decode_workers"]
        assert len({r["thread"] for r in encode}) == counts["encode_workers"]
    for what, found in (("decode", decode), ("encode", encode)):
        wall = counts[f"{what}_wall_s"]
        assert 0.0 < wall <= read["end_s"] - read["start_s"]
        assert wall == pytest.approx(max(r["end_s"] for r in found) - min(r["start_s"] for r in found), abs=2e-3)
    # the same rows from this thread alone, the uploads' apart: a column's row there, one row a table here
    _inline(monkeypatch)
    _, loop_rows = _read_in_a_pass(str(tmp_path / "d"))

    def tree(rs):
        return sorted((r["name"], r["parent"], r["counts"].get("rows"), r["counts"].get("distinct"),
                       r["counts"].get("bytes")) for r in rs
                      if r["name"].startswith(("ingest/", "io:")) and r["name"] != "ingest/h2d")

    def h2d(rs):
        found = [r for r in rs if r["name"] == "ingest/h2d"]
        return {r["parent"] for r in found}, sum(r["counts"]["bytes"] for r in found)
    assert tree(rows) == tree(loop_rows) and h2d(rows) == h2d(loop_rows)
    assert len([r for r in rows if r["name"] == "ingest/h2d"]) == tbl.ncols


@pytest.mark.parametrize("pool", [1, 4], indirect=True)
def test_a_pooled_assemble_is_one_row_and_its_converts_are_its_children(pool, tmp_path, monkeypatch):
    """ONE ``ingest/assemble`` around the columns' units, a decimal's and a
    date's ``ingest/convert`` under it from whichever thread ran them, and
    how the units ran on ``io:read_dataset``."""
    paths, _ = _write_case("decimal_and_date", str(tmp_path / "d"), rows=500)
    real = data_ingest._assemble_column
    # long enough for a pool thread to have come and claimed the next column
    monkeypatch.setattr(data_ingest, "_assemble_column", lambda *a: time.sleep(0.05) or real(*a))
    obs.get_tracer().clear()
    tbl, rows = _read_in_a_pass(str(tmp_path / "d"))
    assert tbl.nrows == 1503
    (read,) = [r for r in rows if r["name"] == "io:read_dataset"]
    (assemble,) = [r for r in rows if r["name"] == "ingest/assemble"]
    converts = [r for r in rows if r["name"] == "ingest/convert"]
    assert assemble["parent"] == "io:read_dataset" and assemble["counts"] == {"arrow_typed": 2}
    assert len(converts) == 2 and all(r["parent"] == "ingest/assemble" and r["counts"] == {"rows": 1503} for r in converts)
    assert all(assemble["start_s"] <= r["start_s"] <= r["end_s"] <= assemble["end_s"] for r in converts)
    assert sorted(sp.args["kind"] for sp in obs.get_tracer().snapshot() if sp.name == "ingest/convert") == ["date", "decimal"]
    counts = read["counts"]
    assert counts["assemble_columns"] == 3
    assert 0.0 < counts["assemble_wall_s"] <= assemble["end_s"] - assemble["start_s"]
    if pool.threads == 1:
        assert counts["assemble_workers"] == 0 and {r["thread"] for r in converts} == {assemble["thread"]}
    else:
        assert 1 < counts["assemble_workers"] <= 3


def test_a_frame_under_the_threshold_opens_no_pool_task(tmp_path, monkeypatch):
    """The thresholds as the program has them: 1,200 rows and 40 kB of parts
    are read in a loop on the calling thread, and the read's row says so."""
    paths = _write_parts(str(tmp_path / "d"), nparts=3, rows=400)
    made = host_pool.HostPool(4)
    monkeypatch.setattr(host_pool, "_POOL", made)
    submitted = []
    monkeypatch.setattr(made._executor, "submit", lambda fn, *a: submitted.append(fn))
    assert sum(os.path.getsize(p) for p in paths) < data_ingest._POOLED_DECODE_MIN_BYTES
    assert 1200 < table_mod._POOLED_COLUMNS_MIN_ROWS <= 400_000 and 32_561 < table_mod._POOLED_COLUMNS_MIN_ROWS
    tbl, rows = _read_in_a_pass(str(tmp_path / "d"))
    (read,) = [r for r in rows if r["name"] == "io:read_dataset"]
    assert tbl.nrows == 1200 and submitted == []
    assert read["counts"]["encode_workers"] == 0 and read["counts"]["decode_workers"] == 0
    assert read["counts"]["assemble_workers"] == 0 and read["counts"]["assemble_columns"] == 3
    assert read["counts"]["encode_wall_s"] > 0.0 and read["counts"]["decode_wall_s"] > 0.0
    assert read["counts"]["assemble_wall_s"] > 0.0
    assert {r["thread"] for r in rows if r["name"].startswith("ingest/")} == {threading.current_thread().name}
    made._executor.shutdown(wait=True)
