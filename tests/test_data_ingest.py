"""Ingest tests (style mirrors the reference's
src/test/anovos/data_ingest/test_data_ingest_integration.py — read all
formats, write round-trips, combination ops on small frames)."""

import os

import numpy as np
import pandas as pd
import pytest

from anovos_tpu.data_ingest import (
    concatenate_dataset,
    data_sample,
    delete_column,
    join_dataset,
    read_dataset,
    recast_column,
    recommend_type,
    rename_column,
    select_column,
    write_dataset,
)
from anovos_tpu.shared.table import Table

from anovos_tpu.data_ingest.synthetic import DEFAULT_DIR

INCOME_PARQUET = str(DEFAULT_DIR / "parquet")
INCOME_AVRO = str(DEFAULT_DIR / "join")


def test_read_parquet_dir():
    t = read_dataset(INCOME_PARQUET, "parquet")
    assert t.nrows == 32561
    assert "age" in t and "workclass" in t


def test_read_avro(income_df):
    t = read_dataset(INCOME_AVRO, "avro")
    assert t.nrows == 32561
    assert set(t.col_names) == {"ifa", "age", "workclass"}
    df = t.to_pandas()
    assert df["workclass"].head(100).tolist() == income_df["workclass"].head(100).tolist()


def test_write_roundtrip(tmp_path):
    df = pd.DataFrame({"a": [1.0, 2.0, np.nan], "c": ["x", None, "z"]})
    t = Table.from_pandas(df)
    for ftype in ("csv", "parquet", "json", "avro"):
        path = str(tmp_path / f"out_{ftype}")
        write_dataset(t, path, ftype, {"mode": "overwrite", "header": True, "repartition": 2})
        back = read_dataset(path, ftype)
        assert back.nrows == 3
        bdf = back.to_pandas()
        np.testing.assert_allclose(bdf["a"].to_numpy(), df["a"].to_numpy())
        assert bdf["c"].iloc[0] == "x" and bdf["c"].iloc[2] == "z"


def test_write_mode_error(tmp_path):
    t = Table.from_pandas(pd.DataFrame({"a": [1.0]}))
    path = str(tmp_path / "dup")
    write_dataset(t, path, "csv", {"mode": "overwrite"})
    with pytest.raises(FileExistsError):
        write_dataset(t, path, "csv", {"mode": "error"})


def test_concatenate_name_method():
    t1 = Table.from_pandas(pd.DataFrame({"a": [1.0, 2.0], "c": ["x", "y"]}))
    t2 = Table.from_pandas(pd.DataFrame({"c": ["z", "x"], "a": [3.0, 4.0]}))
    out = concatenate_dataset(t1, t2, method_type="name")
    assert out.nrows == 4
    df = out.to_pandas()
    assert df["a"].tolist() == [1.0, 2.0, 3.0, 4.0]
    assert df["c"].tolist() == ["x", "y", "z", "x"]


def test_concatenate_missing_col_errors():
    t1 = Table.from_pandas(pd.DataFrame({"a": [1.0]}))
    t2 = Table.from_pandas(pd.DataFrame({"b": [2.0]}))
    with pytest.raises(ValueError):
        concatenate_dataset(t1, t2, method_type="name")


def test_join_inner_and_left():
    left = Table.from_pandas(pd.DataFrame({"k": ["a", "b", "c"], "x": [1.0, 2.0, 3.0]}))
    right = Table.from_pandas(pd.DataFrame({"k": ["b", "c", "d"], "y": [20.0, 30.0, 40.0]}))
    inner = join_dataset(left, right, join_cols="k", join_type="inner").to_pandas()
    assert sorted(inner["k"].tolist()) == ["b", "c"]
    assert inner.set_index("k")["y"].to_dict() == {"b": 20.0, "c": 30.0}
    lj = join_dataset(left, right, join_cols="k", join_type="left").to_pandas()
    assert len(lj) == 3
    assert np.isnan(lj.set_index("k")["y"]["a"])
    anti = join_dataset(left, right, join_cols="k", join_type="left_anti").to_pandas()
    assert anti["k"].tolist() == ["a"]


def test_join_validations():
    t1 = Table.from_pandas(pd.DataFrame({"k": ["a"], "x": [1.0]}))
    t2 = Table.from_pandas(pd.DataFrame({"k": ["a"], "x": [2.0]}))
    with pytest.raises(ValueError):
        join_dataset(t1, t2, join_cols="k", join_type="inner")  # duplicate non-join col


def test_column_ops():
    t = Table.from_pandas(pd.DataFrame({"a": [1.0], "b": [2.0], "c": ["x"]}))
    assert delete_column(t, ["b"]).col_names == ["a", "c"]
    assert select_column(t, "a|c").col_names == ["a", "c"]
    assert rename_column(t, ["a"], ["aa"]).col_names == ["aa", "b", "c"]


def test_recast_cat_to_num():
    t = Table.from_pandas(pd.DataFrame({"s": ["1", "2", "bad", None]}))
    out = recast_column(t, ["s"], ["double"])
    df = out.to_pandas()
    np.testing.assert_allclose(df["s"][:2].to_numpy(), [1.0, 2.0])
    assert np.isnan(df["s"][2]) and np.isnan(df["s"][3])


def test_recast_wide_float_to_int_is_exact():
    """float-wide → integer truncates the EXACT double, not the f32
    approximation (ADVICE r3 low #1): these values differ from their f32
    round-trip by more than 1, so an approximate cast would be visibly off."""
    vals = np.array([123456789.75, 2**30 + 0.5, -987654321.25, 16777217.0])
    assert not np.array_equal(vals.astype(np.float32).astype(np.float64), vals)
    t = Table.from_pandas(pd.DataFrame({"w": vals}))
    assert t["w"].is_wide
    out = recast_column(t, ["w"], ["bigint"])
    got = out["w"].exact_host(t.nrows)
    np.testing.assert_array_equal(got, np.trunc(vals).astype(np.int64))
    out32 = recast_column(t, ["w"], ["int"])
    got32 = out32["w"].exact_host(t.nrows)
    np.testing.assert_array_equal(
        got32, np.clip(np.trunc(vals), -(2**31), 2**31 - 1).astype(np.int64)
    )


def test_csv_checkpoint_preserves_float_dtype(tmp_path):
    """The pyarrow checkpoint writer renders whole-valued floats without a
    decimal point; the writer must pre-format those columns so a null-free
    all-integral float64 column rereads as double, not bigint (code-review
    r4 finding — the write_intermediate path hits this on imputed columns)."""
    t = Table.from_pandas(pd.DataFrame({
        "f_whole": [1.0, 2.0, 3.0],
        "f_frac": [1.5, np.nan, 3.25],
        "f_big": [2.0**40, 2.0**40 + 1, 0.0],
        "i": [1, 2, 3],
        "s": ["a", "b", None],
        "b": [True, False, True],
    }))
    write_dataset(t, str(tmp_path / "x"), "csv", {"mode": "overwrite", "header": True})
    back = read_dataset(str(tmp_path / "x"), "csv", {"header": True})
    assert back.columns["f_whole"].dtype_name in ("double", "float")
    assert back.columns["f_frac"].dtype_name in ("double", "float")
    assert back.columns["f_big"].dtype_name in ("double", "float")
    assert back.columns["i"].dtype_name in ("int", "bigint")
    np.testing.assert_allclose(
        np.asarray(back.columns["f_whole"].data)[:3], [1.0, 2.0, 3.0])
    # 2^40+1 is f32-lossy: the reread column must carry the exact wide pair
    # and reproduce the value bit-for-bit in float64
    np.testing.assert_array_equal(
        back.columns["f_big"].exact_host(3),
        np.array([2.0**40, 2.0**40 + 1, 0.0], np.float64))


def test_recast_num_to_string():
    t = Table.from_pandas(pd.DataFrame({"n": [1, 2, 3]}))
    out = recast_column(t, ["n"], ["string"])
    assert out["n"].kind == "cat"
    assert out.to_pandas()["n"].tolist() == ["1", "2", "3"]


def test_recommend_type():
    n = 500
    df = pd.DataFrame(
        {
            "lowcard": np.tile(np.arange(3), n // 3 + 1)[:n].astype(float),
            "highcard": np.arange(n).astype(float),
            "cat": np.tile(["a", "b"], n // 2),
        }
    )
    out = recommend_type(Table.from_pandas(df), static_threshold=100, dynamic_threshold=0.5)
    rec = out.set_index("attribute")["recommended_form"].to_dict()
    assert rec["lowcard"] == "categorical"
    assert rec["highcard"] == "numerical"
    assert rec["cat"] == "categorical"


def test_data_sample_random():
    df = pd.DataFrame({"a": np.arange(10000, dtype=float)})
    t = Table.from_pandas(df)
    s = data_sample(t, fraction=0.2, method_type="random", seed_value=7)
    assert 0.15 * 10000 < s.nrows < 0.25 * 10000


def test_data_sample_stratified_population():
    n = 9000
    df = pd.DataFrame({"g": np.repeat(["a", "b", "c"], n // 3), "v": np.arange(n, dtype=float)})
    t = Table.from_pandas(df)
    s = data_sample(t, strata_cols=["g"], fraction=0.3, method_type="stratified")
    out = s.to_pandas()["g"].value_counts()
    for g in ("a", "b", "c"):
        assert 0.2 * n / 3 < out[g] < 0.4 * n / 3


def test_data_sample_balanced():
    df = pd.DataFrame({"g": ["a"] * 8000 + ["b"] * 1000, "v": np.arange(9000, dtype=float)})
    t = Table.from_pandas(df)
    s = data_sample(
        t, strata_cols=["g"], fraction=0.9, method_type="stratified", stratified_type="balanced"
    )
    out = s.to_pandas()["g"].value_counts()
    assert abs(out["a"] - out["b"]) < 0.25 * max(out["a"], out["b"])


# ----------------------------------------------------------------------
# mixed-format checkpoint directories + the pandas-CSV-fallback one-shot
# (round-10 satellite: the module-global flag is now lock-guarded)
# ----------------------------------------------------------------------
def test_csv_fallback_notice_is_thread_safe_one_shot():
    import threading

    from anovos_tpu.data_ingest import data_ingest as di

    with di._PANDAS_CSV_FALLBACK_LOCK:
        di._PANDAS_CSV_FALLBACK_LOGGED = False
    hits, barrier = [], threading.Barrier(8)

    def racer():
        barrier.wait()
        if di._csv_fallback_first_notice():
            hits.append(1)

    threads = [threading.Thread(target=racer) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(hits) == 1  # exactly one thread wins the one-shot


def test_mixed_format_csv_directory_reads_consistently(tmp_path):
    """Regression: a checkpoint directory holding BOTH pyarrow-written and
    pandas-written CSV parts (the fallback scenario the one-shot notice
    warns about) must read back as one consistent frame — the guard's
    schema reconciliation absorbs the dtype wobble between the writers."""
    from anovos_tpu.data_ingest import data_ingest as di

    d = tmp_path / "ckpt"
    d.mkdir()
    part = pd.DataFrame({"x": [1.0, 2.0, 3.0], "flag": [True, False, True],
                         "s": ["a", "b", "c"]})
    # part 0 through the pyarrow writer (write_dataset's fast path:
    # lowercase booleans, pre-formatted whole floats)
    write_dataset(Table.from_pandas(part), str(d / "_tmp0"), "csv",
                  {"mode": "overwrite"})
    os.replace(str(d / "_tmp0" / "part-00000.csv"), str(d / "part-00000.csv"))
    # part 1 via the pandas fallback writer's format (True/False casing)
    part2 = pd.DataFrame({"x": [4.0, 5.0], "flag": [False, True], "s": ["d", "e"]})
    part2.to_csv(d / "part-00001.csv", index=False)

    t = read_dataset(str(d), "csv")
    df = t.to_pandas()
    assert t.nrows == 5
    assert sorted(df["x"].tolist()) == [1.0, 2.0, 3.0, 4.0, 5.0]
    # both writers' rows decode; boolean-ish strings survive as values
    assert df["s"].tolist() == ["a", "b", "c", "d", "e"]
    from anovos_tpu.data_ingest import guard

    assert guard.records() == []  # format wobble is NOT corruption


def test_pandas_fallback_writer_books_metric(tmp_path, monkeypatch):
    """A part the pyarrow CSV writer cannot convert falls back to pandas,
    books csv_pandas_fallback_total, and still round-trips.  The arrow
    failure is simulated (the conversion limits that trigger it — exotic
    object columns, duplicate names — cannot flow through a Table)."""
    from anovos_tpu.data_ingest import data_ingest as di
    from anovos_tpu.obs import get_metrics

    get_metrics().reset()
    with di._PANDAS_CSV_FALLBACK_LOCK:
        di._PANDAS_CSV_FALLBACK_LOGGED = False

    def arrow_limit(*a, **k):
        raise ValueError("simulated arrow conversion limit")

    monkeypatch.setattr(di.pacsv, "write_csv", arrow_limit)
    df = pd.DataFrame({"v": [1.0, 2.0, 3.0], "s": ["a", "b", "c"]})
    out = tmp_path / "fb"
    write_dataset(Table.from_pandas(df), str(out), "csv",
                  {"mode": "overwrite", "repartition": 2})
    # one fallback per part, counted per occurrence; notice logged once
    assert get_metrics().counter("csv_pandas_fallback_total").value() == 2
    assert di._PANDAS_CSV_FALLBACK_LOGGED
    monkeypatch.undo()
    t = read_dataset(str(out), "csv")
    assert t.nrows == 3
    assert sorted(t.to_pandas()["s"].tolist()) == ["a", "b", "c"]
