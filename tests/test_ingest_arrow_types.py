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
