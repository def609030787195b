"""``Table.to_pandas``: the device→host copies of a bounded window of columns
in flight ahead of the column being converted, the columns' units in a loop
(a short table) or side by side on the host pool (a long one).  Either way
the frame is what fetching and converting one column after the other gives
(the reference kept here), an error of one unit surfaces with nothing left
running, and two callers at once get their own frames."""

import io
import threading
import time
from collections import OrderedDict

import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from anovos_tpu.shared import host_pool
from anovos_tpu.shared import table as table_mod
from anovos_tpu.shared.table import Column, Table

THREADS = 4
LOOPED, POOLED = 1_000, 200_000
KINDS = ["float_nulls", "int_plain", "int_nullable", "cat_unseen", "ts_nat", "wide_int",
         "wide_int_nullable", "wide_float", "flag"]


def _frame(rows: int) -> pd.DataFrame:
    rng = np.random.default_rng(rows)
    wide = rng.integers(1 << 40, 1 << 41, rows)
    return pd.DataFrame({
        "float_nulls": np.where(rng.random(rows) < 0.2, np.nan, rng.normal(size=rows)).astype("float32"),
        "int_plain": rng.integers(-5, 50, rows).astype("int32"),
        "int_nullable": pd.array(rng.integers(0, 9, rows), dtype="Int64").copy(),
        "cat_unseen": rng.choice(np.array(["a", "bb", "ccc", None], dtype=object), rows),
        "ts_nat": pd.to_datetime(rng.integers(0, 2_000_000_000, rows), unit="s"),
        "wide_int": wide,
        "wide_int_nullable": pd.array(wide, dtype="Int64").copy(),
        "wide_float": rng.random(rows) * 1e3 + 1e-9,  # no float32 holds these
        "flag": rng.random(rows) < 0.5,
    })


def _table(rows: int) -> Table:
    df = _frame(rows)
    df.loc[df.index % 7 == 0, ["int_nullable", "wide_int_nullable"]] = pd.NA
    df.loc[df.index % 11 == 0, "ts_nat"] = pd.NaT
    tbl = Table.from_pandas(df)
    assert tbl.columns["wide_int"].is_wide_int and tbl.columns["wide_int_nullable"].is_wide_int
    assert tbl.columns["wide_float"].wide_kind == "float" and tbl.columns["wide_float"].is_wide
    # a code no vocab entry stands for, on a valid row: what an encoder leaves for a value it has not seen
    cat = tbl.columns["cat_unseen"]
    unseen = jnp.where(jnp.arange(cat.padded_len) % 13 == 0, -1, cat.data)
    tbl.columns["cat_unseen"] = Column("cat", unseen, cat.mask | (jnp.arange(cat.padded_len) % 13 == 0),
                                       vocab=cat.vocab, dtype_name=cat.dtype_name)
    return tbl


def _reference(tbl: Table) -> pd.DataFrame:
    """One column after the other, each fetched whole before it is converted."""
    out = {name: table_mod._host_column_to_pandas(c.to_host(tbl.nrows)) for name, c in tbl.columns.items()}
    return pd.DataFrame(out, columns=list(tbl.columns))


def _parquet(df: pd.DataFrame) -> bytes:
    buf = io.BytesIO()
    df.to_parquet(buf, index=False)
    return buf.getvalue()


@pytest.fixture
def pool(monkeypatch):
    made = host_pool.HostPool(THREADS)
    monkeypatch.setattr(host_pool, "_POOL", made)
    yield made
    made._executor.shutdown(wait=True)


@pytest.fixture(scope="module")
def fetched():
    """Per row count: the frame ``to_pandas`` gave, the reference's, and the
    threads the columns' units ran on."""
    made = host_pool.HostPool(THREADS)
    out = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(host_pool, "_POOL", made)
        real = table_mod._host_column_to_pandas
        for rows in (LOOPED, POOLED):
            tbl, threads = _table(rows), set()

            def noting(hc, threads=threads):
                threads.add(threading.get_ident())
                return real(hc)

            mp.setattr(table_mod, "_host_column_to_pandas", noting)
            got = tbl.to_pandas()
            mp.setattr(table_mod, "_host_column_to_pandas", real)
            out[rows] = (got, _reference(tbl), threads)
    made._executor.shutdown(wait=True)
    return out


# ------------------------------------------------------------ the same frame ----
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("rows", [LOOPED, POOLED])
def test_a_column_of_every_kind_is_the_references(fetched, rows, kind):
    got, ref, _ = fetched[rows]
    assert got[kind].dtype == ref[kind].dtype
    pd.testing.assert_series_equal(got[kind], ref[kind], check_exact=True)
    if kind == "cat_unseen":
        assert pd.isna(got[kind][0]) and got[kind].isna().sum() > rows // 13
    if kind == "ts_nat":
        assert got[kind].isna().sum() == len(range(0, rows, 11))
    if kind == "wide_int_nullable":
        assert got[kind].dtype == "Int64" and got[kind].isna().sum() == len(range(0, rows, 7))


@pytest.mark.parametrize("rows", [LOOPED, POOLED])
def test_the_frame_and_its_parquet_bytes_are_the_references(fetched, rows):
    got, ref, threads = fetched[rows]
    assert list(got.columns) == KINDS and len(got) == rows
    pd.testing.assert_frame_equal(got, ref, check_exact=True)
    assert _parquet(got) == _parquet(ref)
    # 1,000 rows: the loop, on the calling thread; 200,000: the pool's threads beside it
    assert rows >= table_mod._POOLED_COLUMNS_MIN_ROWS or threads == {threading.get_ident()}
    assert rows < table_mod._POOLED_COLUMNS_MIN_ROWS or 1 < len(threads) <= THREADS


# ------------------------------------------------------ the copies in flight ----
def _narrow(rows: int, ncols: int, wide: int = -1) -> Table:
    cols = {f"c{i}": np.arange(rows, dtype="float32") + i for i in range(ncols)}
    if wide >= 0:
        cols[f"c{wide}"] = np.arange(rows, dtype="int64") + (1 << 40)
    return Table.from_numpy(cols)


@pytest.fixture
def copies(monkeypatch):
    """Every ``copy_to_host_async`` and every ``__array__`` of a ``jax.Array``
    noted by the array's id, in the order they were first called."""
    cls = type(jnp.zeros(1))
    noted = {"lock": threading.Lock(), "started": [], "awaited": set(), "on_start": None}
    real_copy, real_array = cls.copy_to_host_async, cls.__array__

    def copy(self):
        with noted["lock"]:
            if id(self) not in noted["started"]:
                noted["started"].append(id(self))
                if noted["on_start"] is not None:
                    noted["on_start"](id(self))
        return real_copy(self)

    def array(self, *a, **k):
        with noted["lock"]:
            noted["awaited"].add(id(self))
        return real_array(self, *a, **k)

    monkeypatch.setattr(cls, "copy_to_host_async", copy)
    monkeypatch.setattr(cls, "__array__", array)
    return noted


@pytest.mark.parametrize("rows", [LOOPED, POOLED])
def test_copies_run_a_window_ahead_of_the_columns_being_converted_and_no_further(pool, copies, rows, monkeypatch):
    ncols = 30
    tbl = _narrow(rows, ncols, wide=9)
    cols = list(tbl.columns.values())
    column_of = {id(a): i for i, c in enumerate(cols) for a in c.device_arrays()}
    entered, violations = [], []
    side_by_side = THREADS if rows >= table_mod._POOLED_COLUMNS_MIN_ROWS else 1

    def on_start(array_id):  # under the fixture's lock
        # a copy is started by a unit that was taken up: one of those whose fetch has begun, or one
        # that each thread may hold besides; the window is the pool's width past that unit
        if column_of[array_id] >= len(entered) + side_by_side + THREADS:
            violations.append((column_of[array_id], len(entered)))

    copies["on_start"] = on_start
    real = Column.to_host

    def to_host(self, n):
        i = next(j for j, c in enumerate(cols) if c is self)
        with copies["lock"]:
            started = {column_of[a] for a in copies["started"]}
            complete = {j for j in started if all(id(a) in copies["started"] for a in cols[j].device_arrays())}
            entered.append((i, complete))
        return real(self, n)

    monkeypatch.setattr(Column, "to_host", to_host)
    df = tbl.to_pandas()
    assert df.shape == (rows, ncols) and sorted(i for i, _ in entered) == list(range(ncols))
    assert violations == []
    for i, complete in entered:  # before column i is waited for, the window past it is in flight
        assert set(range(min(i + THREADS + 1, ncols))) <= complete, (i, sorted(complete))
    assert entered[0][0] == 0 and len(entered[0][1]) >= THREADS + 1
    if side_by_side == 1:  # the loop: exactly the window, column by column
        assert [len(c) for _, c in entered] == [min(i + THREADS + 1, ncols) for i in range(ncols)]
    assert len(copies["started"]) == 2 * ncols + 2 and set(copies["started"]) <= copies["awaited"]


# -------------------------------------------------------------------- errors ----
@pytest.mark.parametrize("rows", [LOOPED, POOLED])
def test_a_unit_that_raises_surfaces_and_leaves_nothing_running_or_unawaited(pool, copies, rows, monkeypatch):
    ncols, bad = 30, 10
    tbl = _narrow(rows, ncols)
    real = table_mod._host_column_to_pandas
    state = {"lock": threading.Lock(), "running": 0, "converted": 0}

    def convert(hc):
        with state["lock"]:
            state["running"] += 1
        try:
            if hc.data[0] == bad:
                raise ValueError("column 10 cannot be converted")
            time.sleep(0.01)
            return real(hc)
        finally:
            with state["lock"]:
                state["running"] -= 1
                state["converted"] += 1

    monkeypatch.setattr(table_mod, "_host_column_to_pandas", convert)
    with pytest.raises(ValueError, match="column 10 cannot be converted"):
        tbl.to_pandas()
    done = state["converted"]
    assert state["running"] == 0 and bad < done <= bad + THREADS
    column_of = {id(a): i for i, c in enumerate(tbl.columns.values()) for a in c.device_arrays()}
    started = {column_of[a] for a in copies["started"]}
    assert max(started) < ncols - 1  # the units past the error never ran, nor started their windows
    assert set(copies["started"]) <= copies["awaited"]  # what was in flight has landed
    time.sleep(0.1)
    assert state["converted"] == done and state["running"] == 0  # and nothing goes on behind the error
    assert len(copies["started"]) == 2 * len(started)
    # the pool is whole again: the next call is served
    monkeypatch.setattr(table_mod, "_host_column_to_pandas", real)
    assert tbl.to_pandas().shape == (rows, ncols)


# ------------------------------------------------------------- two callers ----
def test_two_threads_fetching_two_tables_at_once_get_their_own_frames(pool):
    tables = [_narrow(POOLED, 12, wide=3), _table(POOLED)]
    refs = [_reference(t) for t in tables]
    got, errors = [None, None], []
    barrier = threading.Barrier(2)

    def fetch(k):
        try:
            barrier.wait(timeout=30)
            for _ in range(3):
                got[k] = tables[k].to_pandas()
                pd.testing.assert_frame_equal(got[k], refs[k], check_exact=True)
        except BaseException as e:  # handed to the test's thread below
            errors.append(e)

    threads = [threading.Thread(target=fetch, args=(k,)) for k in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads) and errors == []
    assert list(got[0].columns) == list(tables[0].columns) and list(got[1].columns) == KINDS


def test_an_empty_table_and_a_one_row_table_come_back(pool):
    assert Table(OrderedDict(), 0).to_pandas().shape == (0, 0)
    one = Table.from_pandas(pd.DataFrame({"x": [1.5], "s": ["a"]})).to_pandas()
    assert one.shape == (1, 2) and one["x"][0] == 1.5 and one["s"][0] == "a"
