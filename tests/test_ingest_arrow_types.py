"""A parquet column's Arrow type decides its way into a ``Table``:
``decimal128`` becomes a numeric column through float64, ``date32`` /
``date64`` / ``timestamp`` a ``ts`` column (an ``other`` column, as the
upstream's ``attributeType_segregation`` has dates), and no value becomes a
Python object on the way (no ``decimal.Decimal``, no ``datetime.date``): the
per-value loop and ``pandas.to_numeric`` are never called, which is shown by
counting their calls, not by a clock."""

import datetime
import decimal
import os

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from anovos_tpu.data_ingest import data_ingest
from anovos_tpu.data_ingest.data_ingest import read_dataset, write_dataset
from anovos_tpu.shared import table as table_mod
from anovos_tpu.shared.table import Table, arrow_typed_kind, arrow_typed_to_numpy, host_table_frame

D = decimal.Decimal
DAY = datetime.date


def _typed_table(n=10):
    """``n`` rows and a null in each typed column; decimals that f32 does and does not hold."""
    price = [D(f"{1000 + i}.{i % 100:02d}") for i in range(n)]
    whole = [D(i) for i in range(n)]
    day = [DAY(1995, 6, 17) + datetime.timedelta(days=i) for i in range(n)]
    at = [datetime.datetime(1998, 8, 2, 12, 30, i, 250_000) for i in range(n)]
    price[3] = whole[4] = day[5] = at[6] = None
    return pa.table({
        "key": pa.array(range(n), pa.int64()),
        "price": pa.array(price, pa.decimal128(15, 2)),
        "whole": pa.array(whole, pa.decimal128(15, 2)),
        "day": pa.array(day, pa.date32()),
        "day64": pa.array(day, pa.date64()),
        "at": pa.array(at, pa.timestamp("us")),
        "flag": pa.array(["a", "b"] * (n // 2), pa.string()),
    })


@pytest.fixture
def parts(tmp_path):
    """Two part files of the same schema, as a writer of part files leaves them."""
    t = _typed_table()
    os.makedirs(tmp_path / "in")
    pq.write_table(t.slice(0, 6), str(tmp_path / "in" / "part-00000.parquet"))
    pq.write_table(t.slice(6), str(tmp_path / "in" / "part-00001.parquet"))
    return str(tmp_path / "in"), t


@pytest.fixture
def counted(monkeypatch):
    """Counts of the calls that make an object a value: the per-value loop
    of the string encoder and ``pandas.to_numeric`` over a whole column."""
    calls = {"loop": 0, "to_numeric": 0}
    real_loop, real_numeric = table_mod._loop_encode, pd.to_numeric

    def loop(vals):
        calls["loop"] += 1
        return real_loop(vals)

    def to_numeric(arg, *a, **k):
        # a column of objects: inferSchema's look at the head of a string column (dtype str) is not one
        calls["to_numeric"] += getattr(arg, "dtype", None) == object
        return real_numeric(arg, *a, **k)

    monkeypatch.setattr(table_mod, "_loop_encode", loop)
    monkeypatch.setattr(pd, "to_numeric", to_numeric)
    return calls


def test_arrow_types_decide_the_kind_and_no_object_is_made(parts, counted):
    path, src = parts
    t = read_dataset(path, "parquet")
    assert counted == {"loop": 0, "to_numeric": 0}
    kinds = {k: c.kind for k, c in t.columns.items()}
    assert kinds == {"key": "num", "price": "num", "whole": "num", "day": "ts", "day64": "ts", "at": "ts",
                     "flag": "cat"}
    assert t.attribute_type_segregation() == (["key", "price", "whole"], ["flag"], ["day", "day64", "at"])
    assert t.columns["price"].dtype_name == "double" and t.columns["day"].dtype_name == "timestamp"
    assert t.columns["price"].is_wide and t.columns["price"].wide_kind == "float"  # 1001.01 has no f32
    assert not t.columns["whole"].is_wide
    out = t.to_pandas()
    ref = src.to_pandas()  # Decimal and date objects: the plain way
    for c in ("price", "whole"):
        want = np.array([np.nan if v is None else float(v) for v in ref[c]])
        np.testing.assert_array_equal(out[c].to_numpy(), want)  # to the bit, NaN where null
    for c in ("day", "day64"):
        want = pd.to_datetime(pd.Series(ref[c].to_numpy(dtype=object))).astype("datetime64[ns]")
        pd.testing.assert_series_equal(out[c], want, check_names=False)
    want = ref["at"].astype("datetime64[s]").astype("datetime64[ns]")  # a ts column holds seconds
    pd.testing.assert_series_equal(out["at"], want, check_names=False)
    assert out["at"].isna().sum() == out["day"].isna().sum() == 1
    assert out["flag"].tolist() == ref["flag"].tolist()


def test_a_write_and_a_read_back_keep_kinds_and_values(parts, tmp_path, counted):
    path, _ = parts
    t = read_dataset(path, "parquet")
    write_dataset(t, str(tmp_path / "out"), "parquet", {"mode": "overwrite"})
    back = read_dataset(str(tmp_path / "out"), "parquet")
    assert counted == {"loop": 0, "to_numeric": 0}
    assert {k: c.kind for k, c in back.columns.items()} == {k: c.kind for k, c in t.columns.items()}
    pd.testing.assert_frame_equal(back.to_pandas(), t.to_pandas())
    stored = pq.read_table(str(tmp_path / "out"))
    assert pa.types.is_timestamp(stored.schema.field("day").type)  # a date comes back as a date-time at midnight
    assert pa.types.is_floating(stored.schema.field("price").type)


def test_the_spans_say_what_was_converted(parts):
    from anovos_tpu.obs import get_tracer

    path, _ = parts
    tracer = get_tracer()
    tracer.clear()
    read_dataset(path, "parquet")
    spans = tracer.snapshot()
    converts = [sp for sp in spans if sp.name == "ingest/convert"]
    assert sorted(sp.args["kind"] for sp in converts) == ["date", "date", "decimal", "decimal"]
    assert all(sp.args["rows"] == 10 and sp.args["parent"] == "ingest/assemble" for sp in converts)
    (assemble,) = [sp for sp in spans if sp.name == "ingest/assemble"]
    assert assemble.args["arrow_typed"] == 4
    encodes = [sp for sp in spans if sp.name == "ingest/encode"]
    assert len(encodes) == 1 and encodes[0].args["hashed"] == 1  # the one string column, and no other


def test_decimal_to_float64_is_float_of_decimal_to_the_bit():
    rng = np.random.default_rng(7)
    cents = np.concatenate([rng.integers(-10**15 + 1, 10**15, 5000), [0, 7, -7, 10**15 - 1, -(10**15) + 1]])
    words = np.zeros((len(cents), 2), dtype=np.int64)
    words[:, 0], words[:, 1] = cents, np.where(cents < 0, -1, 0)  # two's complement, 128 bits
    arr = pa.Array.from_buffers(pa.decimal128(15, 2), len(cents), [None, pa.py_buffer(words)])
    want = np.array([float(v) for v in arr.to_pylist()])
    np.testing.assert_array_equal(table_mod._decimal_to_float64(pa.chunked_array([arr])), want)
    # chunks, a slice (an offset into the buffer) and nulls
    with_null = pa.array([D("0.07"), None, D("-1.10"), D("123456.78")], pa.decimal128(15, 2))
    chunked = pa.chunked_array([arr.slice(100, 50), arr.slice(0, 0), with_null.slice(1), arr.slice(0, 3)])
    got = table_mod._decimal_to_float64(chunked)
    np.testing.assert_array_equal(got, np.concatenate([want[100:150], [np.nan, -1.10, 123456.78], want[:3]]))
    # more digits than an int64 or a float64 holds: Arrow's own cast, close and not exact
    wide = pa.chunked_array([pa.array([D("12345678901234567890.123"), None], pa.decimal128(30, 3))])
    got = table_mod._decimal_to_float64(wide)
    assert got[0] == pytest.approx(1.2345678901234567e19, rel=1e-15) and np.isnan(got[1])
    d256 = pa.chunked_array([pa.array([D("1.5")], pa.decimal256(40, 1))])
    assert table_mod._decimal_to_float64(d256)[0] == 1.5


def test_a_frame_with_arrow_typed_columns_goes_the_same_way(counted):
    src = _typed_table()
    df = src.to_pandas(types_mapper=data_ingest._keep_arrow_typed)
    assert [arrow_typed_kind(df[c].dtype) for c in df.columns] == [
        None, "decimal", "decimal", "date", "date", None, None]
    assert arrow_typed_to_numpy(df["price"]).dtype == np.float64
    assert arrow_typed_to_numpy(df["day64"]).dtype == np.dtype("datetime64[s]")
    t = Table.from_pandas(df)
    assert t.attribute_type_segregation() == (["key", "price", "whole"], ["flag"], ["day", "day64", "at"])
    pd.testing.assert_frame_equal(host_table_frame(df), t.to_pandas())
    assert counted == {"loop": 0, "to_numeric": 0}


def test_stats_measures_describe_no_date_and_count_its_rows(parts):
    """A date is an ``other`` column: no statistic describes it; the two
    count measures, which take ``all`` as every column of the table, count
    its filled rows (as they do for a timestamp that ``ts_auto_detection``
    made, whose row the ``full`` mix's tables have always held)."""
    from anovos_tpu.data_analyzer import stats_generator as sg

    path, _ = parts
    t = read_dataset(path, "parquet")
    described = ["key", "price", "whole", "flag"]
    counts = sg.measures_of_counts(t).set_index("attribute")
    assert sorted(counts.index) == sorted(described + ["day", "day64", "at"])
    assert counts.loc["day", "fill_count"] == 9 and counts.loc["price", "missing_count"] == 1
    assert np.isnan(counts.loc["day", "nonzero_count"])  # a numeric column's measure
    assert sg.missingCount_computation(t)["attribute"].tolist() == list(t.col_names)
    gs = dict(sg.global_summary(t).to_numpy().tolist())
    assert (gs["numcols_count"], gs["catcols_count"], gs["othercols_count"]) == ("3", "1", "3")
    assert gs["othercols_name"] == "day, day64, at"
    for fn in (sg.measures_of_centralTendency, sg.measures_of_cardinality, sg.measures_of_percentiles,
               sg.measures_of_dispersion, sg.measures_of_shape):
        assert set(fn(t)["attribute"]) <= set(described)
    ct = sg.measures_of_centralTendency(t).set_index("attribute")
    assert ct.loc["price", "median"] == 1005.05  # the fifth of nine values, from the exact pair
    card = sg.measures_of_cardinality(t).set_index("attribute")
    assert card.loc["price", "unique_values"] == 9 and card.loc["flag", "unique_values"] == 2


# ------------------------------------------------- integers with nulls ----
def _nullable_parts(tmp_path):
    """Two parts: ``n`` has nulls in the first only and a value beyond 2^24,
    ``w`` nulls and a value beyond 2^31, ``k`` and ``small`` none."""
    os.makedirs(tmp_path / "in")
    first = pa.table({"n": pa.array([1, None, 30_000_001, -5], pa.int64()), "k": pa.array([1, 2, 3, 4], pa.int64()),
                      "w": pa.array([None, 2**40 + 1, 5, None], pa.int64()),
                      "small": pa.array([7, None, 9, 9], pa.int16()), "s": ["x", None, "y", "x"]})
    second = pa.table({"n": pa.array([7, 8, 9, 2**24 + 1], pa.int64()), "k": pa.array([5, 6, 7, 8], pa.int64()),
                       "w": pa.array([1, 2, 3, 4], pa.int64()),
                       "small": pa.array([1, 2, 3, 4], pa.int16()), "s": ["x", None, "y", "x"]})
    pq.write_table(first, str(tmp_path / "in" / "part-00000.parquet"))
    pq.write_table(second, str(tmp_path / "in" / "part-00001.parquet"))
    return str(tmp_path / "in")


def test_a_parquet_integer_with_nulls_stays_an_integer_with_a_mask(tmp_path, counted):
    t = read_dataset(_nullable_parts(tmp_path), "parquet")
    assert t.attribute_type_segregation() == (["n", "k", "w", "small"], ["s"], [])
    n, k, w, small = (t.columns[c] for c in ("n", "k", "w", "small"))
    assert (n.dtype_name, k.dtype_name, w.dtype_name, small.dtype_name) == ("bigint", "bigint", "bigint", "int")
    assert str(n.data.dtype) == str(k.data.dtype) == str(small.data.dtype) == "int32" and not n.is_wide
    assert np.asarray(n.data)[:8].tolist() == [1, 0, 30_000_001, -5, 7, 8, 9, 2**24 + 1]  # to the unit, not f32's
    assert np.asarray(n.mask)[:8].tolist() == [True, False, True, True, True, True, True, True]
    assert np.asarray(k.mask)[:8].all() and np.asarray(small.mask)[:8].tolist() == [True, False] + [True] * 6
    assert w.is_wide_int and np.asarray(w.mask)[:8].tolist() == [False, True, True, False, True, True, True, True]
    assert w.exact_host(8)[[1, 2, 4]].tolist() == [2**40 + 1, 5, 1]
    back = t.to_pandas()
    assert back["w"].tolist()[1:3] == [2**40 + 1, 5] and back["w"].isna().tolist()[:4] == [True, False, False, True]
    assert back["k"].dtype == np.int32 and back["n"].isna().sum() == 1 and back["n"][2] == 30_000_001
    assert counted == {"loop": 0, "to_numeric": 0}  # no value became a Python object on the way


def test_the_types_mapper_touches_only_an_integer_type_with_a_null_in_that_part(tmp_path):
    path = _nullable_parts(tmp_path)
    first, second = (pq.read_table(os.path.join(path, f)) for f in sorted(os.listdir(path)))
    assert data_ingest._part_types_mapper(second) is data_ingest._keep_arrow_typed  # no null: as it always read
    mapper = data_ingest._part_types_mapper(first)
    assert mapper(pa.int64()) == pd.Int64Dtype() and mapper(pa.int16()) == pd.Int16Dtype()
    assert mapper(pa.int32()) is None and mapper(pa.string()) is None and mapper(pa.float64()) is None
    assert isinstance(mapper(pa.decimal128(15, 2)), pd.ArrowDtype)
    df = first.to_pandas(types_mapper=mapper)
    assert str(df["n"].dtype) == "Int64" and str(df["k"].dtype) == "Int64" and str(df["small"].dtype) == "Int16"
    assert str(second.to_pandas(types_mapper=data_ingest._part_types_mapper(second))["n"].dtype) == "int64"


def test_a_nullable_integer_beyond_2_to_the_24_survives_read_impute_and_write(tmp_path):
    from anovos_tpu.data_transformer import transformers as T

    t = read_dataset(_nullable_parts(tmp_path), "parquet")
    filled = T.imputation_MMM(t, list_of_cols=["n", "small"], method_type="median")
    write_dataset(filled, str(tmp_path / "out"), "parquet", {"mode": "overwrite"})
    back = pd.read_parquet(str(tmp_path / "out" / "part-00000.parquet"))
    # n: the lower median of -5 1 7 8 9 16777217 30000001 is 8; small: of 1 2 3 4 7 9 9 it is 4
    assert back["n"].tolist() == [1, 8, 30_000_001, -5, 7, 8, 9, 2**24 + 1] and back["n"].dtype == np.int32
    assert back["small"].tolist() == [7, 4, 9, 9, 1, 2, 3, 4]
    assert back["k"].tolist() == list(range(1, 9)) and back["s"].isna().sum() == 2  # untouched, row order kept
    assert back["w"].tolist()[1:3] == [2**40 + 1, 5] and back["w"].isna().sum() == 2


def test_the_multi_host_reader_takes_the_same_masked_integers(tmp_path):
    """One process of ``read_dataset_distributed`` against ``read_dataset`` on
    the same parts: the same kind, dtype name, device dtype, mask and exact
    values, column by column (a part without a null reads a plain integer
    there too, and the schema agreement calls both ``num_i``)."""
    from anovos_tpu.data_ingest.distributed_ingest import read_dataset_distributed

    path = _nullable_parts(tmp_path)
    one, many = read_dataset(path, "parquet"), read_dataset_distributed(path, "parquet")
    assert many.nrows == one.nrows == 8 and many.col_names == one.col_names
    for name in ("n", "k", "w", "small"):
        a, b = one.columns[name], many.columns[name]
        assert (b.kind, b.dtype_name, str(b.data.dtype), b.is_wide_int) == \
            (a.kind, a.dtype_name, str(a.data.dtype), a.is_wide_int), name
        mask = np.asarray(a.mask)[:8]
        assert np.asarray(b.mask)[:8].tolist() == mask.tolist(), name
        assert a.exact_host(8)[mask].tolist() == b.exact_host(8)[mask].tolist(), name
    assert np.asarray(many.columns["n"].data)[:8].tolist() == [1, 0, 30_000_001, -5, 7, 8, 9, 2**24 + 1]


def test_a_frame_of_pandas_nullable_integers_goes_the_same_way(counted):
    df = pd.DataFrame({"n": pd.array([1, None, 2**24 + 1], "Int64"), "u": pd.array([3, 4, None], "UInt8"),
                       "full": pd.array([1, 2, 3], "Int32")})
    t = Table.from_pandas(df)
    assert np.asarray(t.columns["n"].data)[:3].tolist() == [1, 0, 2**24 + 1]
    assert np.asarray(t.columns["n"].mask)[:3].tolist() == [True, False, True]
    assert np.asarray(t.columns["u"].mask)[:3].tolist() == [True, True, False]
    assert np.asarray(t.columns["full"].mask)[:3].all() and t.columns["full"].dtype_name == "int"
    pd.testing.assert_frame_equal(host_table_frame(df), t.to_pandas())
    assert counted == {"loop": 0, "to_numeric": 0}
