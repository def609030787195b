"""``Table.from_numpy`` / ``Table.from_pandas`` (``table._upload_columns``): from
``_POOLED_COLUMNS_MIN_ROWS`` rows up a column's encode, conversion, padding and
``device_put``s are one unit of the host pool, side by side; under it the
table's arrays go over on the calling thread by typed block
(``table._upload_in_blocks``: ``_BLOCK_ARRAYS`` arrays of a dtype in one
``device_put`` and a split on the device, what does not fill a block in one
``device_put`` of a list).  Either way the table is what one column after the
other gives (the reference kept here: the host half, ``np.concatenate``'s
padding, one ``device_put`` an array on the row sharding), the pass's tree has
its ``ingest/h2d`` rows with their counts (one a column, or one a table), an
error of one column leaves no table, and ``host_table_frame`` gives the frame
it gave."""

import os
import threading
import time
from collections import OrderedDict

import jax
import numpy as np
import pandas as pd
import pytest

from anovos_tpu import obs
from anovos_tpu.obs import compile_census
from anovos_tpu.data_ingest import data_ingest
from anovos_tpu.shared import host_pool
from anovos_tpu.shared import table as table_mod
from anovos_tpu.shared.native import NativeEncodedStrings
from anovos_tpu.shared.runtime import get_runtime, init_runtime
from anovos_tpu.shared.table import Table

THREADS = 4
POOLED, LOOPED = 140_000, 2_000  # either side of the 131,072 rows the program has
SMALL_BLOCK = 4  # arrays a block where a test wants blocks from a table of nine columns
KINDS = ["double_f32_exact", "double_wide", "int64_narrow", "int64_wide", "flag", "ts_nat",
         "int_nullable", "string", "encoded"]


def _arrays(rows: int) -> "OrderedDict[str, object]":
    """A column of every kind, as ``Table.from_numpy`` takes them."""
    rng = np.random.default_rng(rows)
    ts = (np.datetime64("2015-01-01T00:00:00", "ms") + rng.integers(0, 31 * 86_400_000, rows).astype("timedelta64[ms]"))
    ts[::11] = np.datetime64("NaT")
    words = np.array([f"w{i:03d}" for i in range(300)] + [None], dtype=object)
    exact = rng.integers(-1000, 1000, rows) / 4.0  # quarters: every float32 holds them
    exact[::17] = np.nan
    return OrderedDict([
        ("double_f32_exact", exact),
        ("double_wide", np.round(rng.gamma(2.0, 9.0, rows), 2)),  # money to the cent: no float32 holds it
        ("int64_narrow", rng.integers(-5, 50, rows).astype(np.int64)),
        ("int64_wide", rng.integers(1 << 40, 1 << 41, rows)),
        ("flag", rng.random(rows) < 0.5),
        ("ts_nat", ts),
        ("int_nullable", np.ma.MaskedArray(rng.integers(0, 9, rows), rng.random(rows) < 0.15)),
        ("string", words[rng.integers(0, len(words), rows)]),
        ("encoded", NativeEncodedStrings(rng.integers(-1, 3, rows).astype(np.int32),
                                         np.array(["N", "Y", "Z"], dtype=object))),
    ])


def _frame(rows: int) -> pd.DataFrame:
    """The same columns as a pandas frame has them after a read: the string
    column of pandas' ``str`` dtype, the nullable integers ``Int64``, the
    encoded one a ``category``."""
    a = _arrays(rows)
    enc = a["encoded"]
    return pd.DataFrame({
        **{k: a[k] for k in ("double_f32_exact", "double_wide", "int64_narrow", "int64_wide", "flag", "ts_nat")},
        "int_nullable": pd.arrays.IntegerArray(np.ma.getdata(a["int_nullable"]).copy(),
                                               np.ma.getmaskarray(a["int_nullable"]).copy()),
        "string": pd.Series(a["string"], dtype="str"),
        "encoded": pd.Categorical.from_codes(enc.codes, categories=list(enc.vocab)),
    })


def _reference(arr, n: int, npad: int):
    """One column as the loop made it: the host half, each array padded by
    ``np.concatenate`` with the fill the program documents."""
    if not isinstance(arr, NativeEncodedStrings):
        arr = np.asanyarray(arr)
        if arr.dtype.kind in "OUS":
            arr = table_mod._loop_encode(arr[:n])
    hc = table_mod._plain_to_host(arr, n)

    def padded(a, fill):
        if a is None:
            return None
        return np.concatenate([a, np.full(npad - len(a), fill, dtype=a.dtype)])

    return hc, {"data": padded(hc.data, -1 if hc.kind == "cat" else 0), "mask": padded(hc.mask, False),
                "wide_hi": padded(hc.wide_hi, np.int32(0)), "wide_lo": padded(hc.wide_lo, np.int32(-(1 << 31)))}


def _same_column(col, hc, want, sharding=None):
    assert (col.kind, col.dtype_name, col.wide_kind) == (hc.kind, hc.dtype_name, hc.wide_kind)
    assert (col.vocab is None) == (hc.vocab is None)
    if hc.vocab is not None:
        assert list(col.vocab) == list(hc.vocab)
    for name, host in want.items():
        dev = getattr(col, name)
        assert (dev is None) == (host is None), name
        if host is not None:
            assert dev.dtype == host.dtype and dev.shape == host.shape, name
            np.testing.assert_array_equal(np.asarray(dev), host, err_msg=name)
            if sharding is not None:  # the very sharding, not an equivalent one: programs are keyed on it
                assert dev.sharding == sharding and dev.committed, name


def _loop_table(arrays, rows: int) -> Table:
    """The table as the per-array loop built it: the reference's padded host
    arrays, one ``device_put`` each through ``Runtime.shard_rows``."""
    rt = get_runtime()
    cols = OrderedDict()
    for name, arr in arrays.items():
        hc, want = _reference(arr, rows, rt.pad_rows(rows))
        cols[name] = table_mod._device_column(hc, {f: rt.shard_rows(a) for f, a in want.items() if a is not None})
    return Table(cols, rows)


def _wide_arrays(rows: int, cols: int) -> "OrderedDict[str, object]":
    """``cols`` columns that cycle through the kinds, each with values of its own."""
    out = OrderedDict()
    for i in range(-(-cols // len(KINDS))):
        for kind, arr in _arrays(rows + i).items():
            if len(out) < cols:
                out[f"{kind}_{i}"] = arr if isinstance(arr, NativeEncodedStrings) else arr[:rows]
    return out


def _arrays_by_dtype(tbl: Table) -> dict:
    by_dtype = {}
    for col in tbl.columns.values():
        for a in col.device_arrays():
            by_dtype[str(a.dtype)] = by_dtype.get(str(a.dtype), 0) + 1
    return by_dtype


def _transfers(tbl: Table) -> int:
    """How many ``device_put`` calls the block branch makes for ``tbl``: a
    dtype's arrays in blocks and one call for what is left."""
    return sum(-(-k // table_mod._BLOCK_ARRAYS) for k in _arrays_by_dtype(tbl).values())


def _row_sharding(rt):
    """``Runtime.shard_rows``' sharding, stated here and not asked of the program."""
    return jax.sharding.NamedSharding(rt.mesh, jax.sharding.PartitionSpec(rt.data_axis))


@pytest.fixture(scope="module")
def pool():
    """The process's pool replaced by one of four threads, for the module."""
    made = host_pool.HostPool(THREADS)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(host_pool, "_POOL", made)
        yield made
    made._executor.shutdown(wait=True)


@pytest.fixture(scope="module")
def built(pool):
    """``{(entry, rows): (table, the arrays it was built from)}``."""
    out = {}
    for rows in (POOLED, LOOPED):
        out["from_numpy", rows] = Table.from_numpy(_arrays(rows)), _arrays(rows)
        out["from_pandas", rows] = Table.from_pandas(_frame(rows)), _arrays(rows)
    return out


# ------------------------------------------------------------------ the table ----
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("entry", ["from_numpy", "from_pandas"])
def test_a_column_of_every_kind_is_what_the_loop_made(built, entry, kind):
    """Side by side (140,000 rows) and looped (2,000): arrays, masks, wide
    pairs, vocab, dtype name, kind, padding and row sharding, and the columns
    in the input's order although the string columns are claimed first."""
    rt = get_runtime()
    for rows in (POOLED, LOOPED):
        tbl, arrays = built[entry, rows]
        npad = rt.pad_rows(rows)
        assert tbl.nrows == rows and tbl.padded_rows == npad and tbl.col_names == KINDS
        hc, want = _reference(arrays[kind], rows, npad)
        sharding = _row_sharding(rt)
        _same_column(tbl.columns[kind], hc, want, sharding)
    assert (hc.wide_hi is not None) == (kind in ("double_wide", "int64_wide"))
    assert hc.kind == {"ts_nat": "ts", "string": "cat", "encoded": "cat"}.get(kind, "num")


def test_on_a_four_device_mesh_every_array_has_the_loops_row_sharding(pool):
    init_runtime(devices=jax.devices()[:4])
    try:
        rt = get_runtime()
        assert rt.n_data == 4
        arrays = _arrays(POOLED)
        tbl = Table.from_numpy(arrays)
        npad = rt.pad_rows(POOLED)
        sharding = _row_sharding(rt)
        for kind in KINDS:
            hc, want = _reference(arrays[kind], POOLED, npad)
            _same_column(tbl.columns[kind], hc, want, sharding)
            for a in tbl.columns[kind].device_arrays():
                assert len(a.addressable_shards) == 4 and {s.data.shape[0] for s in a.addressable_shards} == {npad // 4}
    finally:
        init_runtime()  # the suite's 8-device mesh again


@pytest.mark.parametrize("devices", [4, 8])
def test_on_a_mesh_every_array_of_a_short_table_has_the_loops_row_sharding(pool, monkeypatch, devices):
    """The block's row axis is the sharded one: every array that comes out of
    a block, and every one of the rest, lies on the devices as the loop's."""
    monkeypatch.setattr(table_mod, "_BLOCK_ARRAYS", SMALL_BLOCK)
    init_runtime(devices=jax.devices()[:devices])
    try:
        rt = get_runtime()
        assert rt.n_data == devices
        arrays = _arrays(LOOPED)
        tbl = Table.from_numpy(arrays)
        npad = rt.pad_rows(LOOPED)
        sharding = _row_sharding(rt)
        for kind in KINDS:
            hc, want = _reference(arrays[kind], LOOPED, npad)
            _same_column(tbl.columns[kind], hc, want, sharding)
            for a in tbl.columns[kind].device_arrays():
                assert len(a.addressable_shards) == devices
                assert {s.data.shape for s in a.addressable_shards} == {(npad // devices,)}
                assert [s.index for s in a.addressable_shards] == [
                    s.index for s in rt.shard_rows(np.zeros(npad, a.dtype)).addressable_shards]
    finally:
        init_runtime()  # the suite's 8-device mesh again


def test_a_table_wider_than_a_block_with_a_ragged_last_one_is_what_the_loop_made(pool):
    """The program's own block width: 400 columns are 133 f32 arrays (a block
    and 5), 444 int32 (three and 60) and 400 masks (three and 16)."""
    rt = get_runtime()
    arrays = _wide_arrays(LOOPED, 400)
    tbl = Table.from_numpy(arrays)
    npad = rt.pad_rows(LOOPED)
    assert tbl.col_names == list(arrays) and tbl.padded_rows == npad
    sharding = _row_sharding(rt)
    for name, arr in arrays.items():
        hc, want = _reference(arr, LOOPED, npad)
        _same_column(tbl.columns[name], hc, want, sharding)
    counts, block = _arrays_by_dtype(tbl), table_mod._BLOCK_ARRAYS
    assert all(k > block and k % block for k in counts.values()), counts  # every dtype: blocks and a ragged rest


@pytest.mark.parametrize("block", [1, None])
@pytest.mark.parametrize("kind", ["double_wide", "string", "flag"])
def test_a_table_of_one_column_is_what_the_loop_made(pool, monkeypatch, kind, block):
    """Every array a block of its own, then the program's width, where the
    column's arrays are what is left of their dtypes."""
    if block:
        monkeypatch.setattr(table_mod, "_BLOCK_ARRAYS", block)
    rt = get_runtime()
    arr = _arrays(LOOPED)[kind]
    tbl = Table.from_numpy({kind: arr})
    assert tbl.col_names == [kind] and tbl.nrows == LOOPED
    sharding = _row_sharding(rt)
    _same_column(tbl.columns[kind], *_reference(arr, LOOPED, rt.pad_rows(LOOPED)), sharding)


def test_a_program_warmed_by_the_loops_table_compiles_nothing_for_a_blocks_table(pool, monkeypatch):
    """What a program is keyed on (shape, dtype, sharding, committed) is the
    loop's on every array that came out of a block: a program that a table
    built array by array has compiled is found again."""
    monkeypatch.setattr(table_mod, "_BLOCK_ARRAYS", SMALL_BLOCK)
    arrays = _arrays(LOOPED)
    numeric = ["double_f32_exact", "double_wide", "int64_narrow", "int64_wide", "flag", "int_nullable"]

    def work(tbl):
        X, M = tbl.numeric_block(numeric)
        moved = tbl.gather_rows(np.arange(tbl.nrows)[::-1])
        return np.asarray(X).sum(where=np.asarray(M)), moved.to_pandas()

    compile_census.install()
    want_sum, want_frame = work(_loop_table(arrays, LOOPED))
    tbl = Table.from_numpy(arrays)  # the split programs compile here
    jax.block_until_ready([a for c in tbl.columns.values() for a in c.device_arrays()])
    mark = compile_census.mark()
    got_sum, got_frame = work(tbl)
    assert compile_census.census(since=mark)["compiles_total"] == 0
    assert got_sum == want_sum
    pd.testing.assert_frame_equal(got_frame, want_frame)


def test_a_table_of_no_columns_and_a_table_of_one_row_come_back(pool):
    assert Table.from_numpy({}).ncols == 0 and Table.from_pandas(pd.DataFrame()).nrows == 0
    one = Table.from_pandas(pd.DataFrame({"x": [1.5], "s": pd.Series(["a"], dtype="str")}))
    assert one.nrows == 1 and one.col_names == ["x", "s"]
    assert list(one.to_pandas()["s"]) == ["a"] and list(one.to_pandas()["x"]) == [1.5]
    cut = Table.from_numpy({"x": np.arange(10.0), "o": np.array(list("abcdefghij"), dtype=object)}, nrows=4)
    assert cut.nrows == 4 and list(cut.to_pandas()["o"]) == list("abcd")


# ------------------------------------------------------------------ the pass's tree ----
def _write_parts(d: str, rows: int, nparts: int = 3) -> None:
    os.makedirs(d)
    df = _frame(rows).drop(columns=["encoded"]).assign(word=lambda f: f["string"].fillna("none") + "_x")
    for i in range(nparts):
        df.iloc[i * rows // nparts:(i + 1) * rows // nparts].to_parquet(
            os.path.join(d, f"part-{i:05d}.parquet"), index=False)


def _read_in_a_pass(path):
    tr = obs.get_tracer()
    before = sum(v for _, v in obs.get_metrics().counter("transfer_h2d_bytes_total").items())
    with tr.run_pass():
        with tr.phase("ingest"):
            tbl = data_ingest.read_dataset(path, "parquet")
    moved = sum(v for _, v in obs.get_metrics().counter("transfer_h2d_bytes_total").items()) - before
    return tbl, tr.phases(), moved


def test_a_long_read_has_one_h2d_row_a_column_and_says_how_many_threads_ran_them(pool, tmp_path, monkeypatch):
    _write_parts(str(tmp_path / "d"), POOLED)
    real = table_mod._plain_to_host
    meet = threading.Barrier(2, timeout=30)  # two conversions side by side, whatever the machine's load

    def convert(arr, n):
        if meet.n_waiting or not meet.broken:
            try:
                meet.wait()
            except threading.BrokenBarrierError:
                pass
            meet.abort()  # once: every later unit passes
        return real(arr, n)

    monkeypatch.setattr(table_mod, "_plain_to_host", convert)
    tbl, rows, moved = _read_in_a_pass(str(tmp_path / "d"))
    (read,) = [r for r in rows if r["name"] == "io:read_dataset"]
    h2d = [r for r in rows if r["name"] == "ingest/h2d"]
    encode = [r for r in rows if r["name"] == "ingest/encode"]
    assert tbl.nrows == POOLED and len(h2d) == tbl.ncols == 9 and len(encode) == 2
    for r in h2d + encode:
        assert r["parent"] == "io:read_dataset"
        assert read["start_s"] <= r["start_s"] <= r["end_s"] <= read["end_s"]
    # every byte handed to device_put is on a column's row, and the padded arrays are what was handed
    assert sum(r["counts"]["bytes"] for r in h2d) == moved > 0
    assert moved == sum(a.nbytes for c in tbl.columns.values() for a in c.device_arrays())
    for counts in (r["counts"] for r in h2d):
        assert set(counts) == {"bytes", "shards", "enqueue_s", "convert_s", "pad_s"}
    for r in h2d:  # the three are this thread's seconds inside the row
        c = r["counts"]
        assert min(c["convert_s"], c["pad_s"], c["enqueue_s"]) > 0.0
        assert c["convert_s"] + c["pad_s"] + c["enqueue_s"] <= r["end_s"] - r["start_s"] + 1e-4
    counts = read["counts"]
    assert 2 <= counts["h2d_workers"] <= THREADS and counts["h2d_workers"] == len({r["thread"] for r in h2d})
    # a column is a unit: one device_put an array (two a column, four where it carries a wide pair)
    assert counts["h2d_transfers"] == counts["h2d_arrays"] == 22
    assert counts["h2d_arrays"] == sum(len(c.device_arrays()) for c in tbl.columns.values())
    # first start to last end: a string column's upload follows its encode, so this is no sum of the rows
    assert 0.0 < counts["h2d_wall_s"] <= read["end_s"] - read["start_s"]
    assert counts["h2d_wall_s"] == pytest.approx(max(r["end_s"] for r in h2d) - min(r["start_s"] for r in h2d), abs=2e-2)
    assert 1 <= counts["encode_workers"] == len({r["thread"] for r in encode}) <= 2
    assert counts["encode_wall_s"] == pytest.approx(
        max(r["end_s"] for r in encode) - min(r["start_s"] for r in encode), abs=2e-2)


@pytest.mark.parametrize("block", [SMALL_BLOCK, None])
def test_a_short_read_goes_by_block_on_this_thread_and_says_so(pool, tmp_path, monkeypatch, block):
    """Blocks of four arrays, then the program's own width, at which nine
    columns fill none and every dtype's arrays go in one call."""
    if block:
        monkeypatch.setattr(table_mod, "_BLOCK_ARRAYS", block)
    submitted = []
    monkeypatch.setattr(pool._executor, "submit", lambda fn, *a: submitted.append(fn))
    puts = []
    real_put = jax.device_put
    monkeypatch.setattr(jax, "device_put", lambda x, *a, **k: puts.append(x) or real_put(x, *a, **k))
    _write_parts(str(tmp_path / "d"), LOOPED)
    tbl, rows, moved = _read_in_a_pass(str(tmp_path / "d"))
    (read,) = [r for r in rows if r["name"] == "io:read_dataset"]
    (h2d,) = [r for r in rows if r["name"] == "ingest/h2d"]  # one row a table
    encode = [r for r in rows if r["name"] == "ingest/encode"]
    assert tbl.nrows == LOOPED and tbl.ncols == 9 and len(encode) == 2 and submitted == []
    counts = read["counts"]
    assert counts["h2d_workers"] == 0 and counts["encode_workers"] == 0
    assert 0.0 < counts["h2d_wall_s"] <= read["end_s"] - read["start_s"]
    assert h2d["thread"] == threading.current_thread().name and h2d["parent"] == "io:read_dataset"
    assert all(r["parent"] == "io:read_dataset" and r["end_s"] <= h2d["start_s"] for r in encode)  # no row inside another
    # every byte handed to device_put is on the table's row, once, and the padded arrays are what was handed
    assert h2d["counts"]["bytes"] == moved == sum(a.nbytes for c in tbl.columns.values() for a in c.device_arrays())
    assert moved == sum(sum(a.nbytes for a in x) if isinstance(x, list) else x.nbytes for x in puts)
    # fewer calls than arrays: 22 arrays (nine columns, two with a wide pair) of three dtypes
    assert counts["h2d_arrays"] == 22 and counts["h2d_transfers"] == len(puts) == _transfers(tbl)
    assert counts["h2d_transfers"] == (7 if block else 3) < counts["h2d_arrays"]
    c = h2d["counts"]
    assert set(c) == {"bytes", "shards", "enqueue_s", "convert_s", "pad_s"} | ({"split_s"} if block else set())
    assert c["shards"] == counts["h2d_transfers"] * get_runtime().n_data
    assert min(c["convert_s"], c["pad_s"], c["enqueue_s"]) > 0.0
    assert c["convert_s"] + c["pad_s"] + c["enqueue_s"] + c.get("split_s", 0.0) <= h2d["end_s"] - h2d["start_s"] + 1e-4


def test_the_other_columns_are_uploaded_while_the_longest_encode_runs(pool, monkeypatch):
    """The string columns are claimed first and the rest do not wait for
    them: with the encode held up, every other column's upload has ended
    before the encode does."""
    real = table_mod.encode_strings
    uploaded, encode_ended = [], []
    real_upload = table_mod._upload_column

    def slow_encode(values):
        deadline = time.perf_counter() + 10.0
        while len(uploaded) < len(KINDS) - 2 and time.perf_counter() < deadline:
            time.sleep(0.005)
        encode_ended.append(len(uploaded))
        return real(values)

    def upload(arr, n, npad, rt):
        col = real_upload(arr, n, npad, rt)
        uploaded.append(col.kind)
        return col

    monkeypatch.setattr(table_mod, "encode_strings", slow_encode)
    monkeypatch.setattr(table_mod, "_upload_column", upload)
    tbl = Table.from_pandas(_frame(POOLED))
    assert tbl.col_names == KINDS and len(uploaded) == len(KINDS)
    assert encode_ended and min(encode_ended) == len(KINDS) - 2  # the two string columns' own uploads apart


def test_a_unit_that_raises_leaves_no_table_and_raises_the_first_columns_error(pool, monkeypatch):
    real = table_mod._plain_to_host
    started = []

    def failing(arr, n):
        started.append(threading.get_ident())
        if getattr(arr, "dtype", None) == np.int64 and arr[0] in (-1, -2):
            time.sleep(0.05 if arr[0] == -1 else 0.0)  # the later column fails first
            raise ValueError(f"column {arr[0]}")
        return real(arr, n)

    monkeypatch.setattr(table_mod, "_plain_to_host", failing)
    data = OrderedDict((f"c{i}", np.full(POOLED, i, dtype=np.int64)) for i in range(40))
    data["c1"], data["c2"] = np.full(POOLED, -1, dtype=np.int64), np.full(POOLED, -2, dtype=np.int64)
    with pytest.raises(ValueError, match="column -1"):
        Table.from_numpy(data)
    assert len(started) < len(data)  # the units not yet started never were
    short = OrderedDict((k, v[:LOOPED]) for k, v in data.items())
    started.clear()
    with pytest.raises(ValueError, match="column -1"):
        Table.from_numpy(short)
    assert len(started) == 2  # the loop stops at the first


def test_two_threads_building_two_tables_at_once_get_their_own(pool):
    frames = [_frame(POOLED), _frame(POOLED + 1)]
    got = [None, None]

    def build(i):
        got[i] = Table.from_pandas(frames[i])

    threads = [threading.Thread(target=build, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    for i, rows in enumerate((POOLED, POOLED + 1)):
        assert got[i].nrows == rows and got[i].col_names == KINDS
        hc, want = _reference(_arrays(rows)["double_wide"], rows, get_runtime().pad_rows(rows))
        _same_column(got[i].columns["double_wide"], hc, want)


# ------------------------------------------------------------------ the frame that stays on the host ----
@pytest.mark.parametrize("rows", [LOOPED, POOLED])
def test_host_table_frame_is_the_round_trip_through_the_device(pool, rows):
    df = _frame(rows)
    want = Table.from_pandas(df).to_pandas()
    got = table_mod.host_table_frame(df)
    pd.testing.assert_frame_equal(got, want)
    assert list(got.dtypes) == list(want.dtypes) and list(got.columns) == KINDS
    arrays = table_mod._frame_arrays(df, table_mod.encode_strings)
    assert list(arrays) == KINDS
    assert isinstance(arrays["string"], NativeEncodedStrings) and isinstance(arrays["encoded"], NativeEncodedStrings)
    assert list(arrays["encoded"].vocab) == ["N", "Y", "Z"] and len(arrays["string"].vocab) == 300
