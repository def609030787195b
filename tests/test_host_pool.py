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
    assert read["counts"]["encode_wall_s"] > 0.0 and read["counts"]["decode_wall_s"] > 0.0
    assert {r["thread"] for r in rows if r["name"].startswith("ingest/")} == {threading.current_thread().name}
    made._executor.shutdown(wait=True)
