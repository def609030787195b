"""The encoding plan of a parquet part (``data_ingest._dictionary_columns``):
a float column is left out of pyarrow's ``use_dictionary`` list where the
part's own values say that a dictionary cannot pay, every other column and
every frame the rule does not touch go through the default call, and the
``write/parquet`` row and ``parquet_plain_columns_total`` say how often it
engaged."""

import os

import numpy as np
import pandas as pd
import pyarrow.parquet as pq
import pytest

from anovos_tpu import obs, workflow
from anovos_tpu.data_ingest import data_ingest
from anovos_tpu.shared.artifact_store import AsyncArtifactWriter
from anovos_tpu.shared.table import host_table_frame

ROWS = 4096
DICTIONARY = {"PLAIN_DICTIONARY", "RLE_DICTIONARY"}


def _distinct(n=ROWS, seed=0):
    return np.random.default_rng(seed).standard_normal(n)


def _repeated(n=ROWS, values=10):
    return (np.arange(n) % values) * 0.25


def _with_nans(values, keep_every):
    out = np.full(len(values), np.nan)
    out[::keep_every] = values[::keep_every]
    return out


# name -> (the frame, {column: whether it is written plain})
CASES = {
    "an_all_distinct_float_column_is_plain": lambda: (
        pd.DataFrame({"x": _distinct()}), {"x": True}),
    "a_float_column_of_ten_values_keeps_its_dictionary": lambda: (
        pd.DataFrame({"x": _repeated()}), {"x": False}),
    "float32_is_judged_like_float64": lambda: (
        pd.DataFrame({"x": _distinct().astype(np.float32), "y": _repeated().astype(np.float32)}),
        {"x": True, "y": False}),
    "each_column_of_a_mixed_frame_by_its_own_values": lambda: (
        pd.DataFrame({"a": _distinct(seed=1), "label": np.arange(ROWS, dtype=np.int32) % 2,
                      "b": _repeated(), "c": _distinct(seed=2)}),
        {"a": True, "label": False, "b": False, "c": True}),
    "distinct_ints_keep_the_default": lambda: (
        pd.DataFrame({"x": np.arange(ROWS, dtype=np.int64) * 7919 + (1 << 40)}), {"x": False}),
    "bools_keep_the_default": lambda: (
        pd.DataFrame({"x": np.arange(ROWS) % 3 == 0}), {"x": False}),
    "distinct_strings_keep_the_default": lambda: (
        pd.DataFrame({"x": np.array([f"k{i:05d}" for i in range(ROWS)], dtype=object)}),
        {"x": False}),
    "distinct_timestamps_keep_the_default": lambda: (
        pd.DataFrame({"x": pd.Timestamp("2020-01-01") + pd.to_timedelta(np.arange(ROWS) * 61, "s")}),
        {"x": False}),
    "nans_are_dropped_before_the_count": lambda: (
        pd.DataFrame({"half_null_distinct": _with_nans(_distinct(), 2),
                      "half_null_repeated": _with_nans(_repeated(), 2)}),
        {"half_null_distinct": True, "half_null_repeated": False}),
    "under_64_non_null_sampled_values_the_dictionary_stays": lambda: (
        pd.DataFrame({"x": _with_nans(_distinct(), 100), "all_null": np.full(ROWS, np.nan)}),
        {"x": False, "all_null": False}),
    "a_frame_of_20_rows_is_not_touched": lambda: (
        pd.DataFrame({"x": _distinct(20), "n": np.arange(20)}), {"x": False, "n": False}),
    # repeats among 1,024 of 100,000 rows are few even where the part holds
    # each value twenty times over: the bar follows the sample's fraction
    "5000_values_in_100000_rows_keep_their_dictionary": lambda: (
        pd.DataFrame({"x": np.random.default_rng(3).permutation(np.arange(100_000) % 5000) * 0.37,
                      "y": _distinct(100_000)}),
        {"x": False, "y": True}),
}


def _write(df, path, **file_configs):
    data_ingest.write_dataset(df, str(path), "parquet", {"mode": "overwrite", **file_configs})
    return sorted(str(p) for p in path.glob("part-*.parquet"))


def _plain(part_file):
    """{column: whether no page of it is dictionary-encoded}"""
    meta = pq.ParquetFile(part_file).metadata
    assert meta.num_row_groups == 1
    group = meta.row_group(0)
    return {group.column(i).path_in_schema: not DICTIONARY & set(group.column(i).encodings)
            for i in range(group.num_columns)}


def _bytes(path):
    with open(path, "rb") as f:
        return f.read()


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_column_is_plain_only_where_its_values_say_so(case, tmp_path):
    df, want_plain = CASES[case]()
    (part,) = _write(df, tmp_path / "planned")
    assert _plain(part) == want_plain
    # the default writer's file of the same frame: what a reader gets is the same
    frame = host_table_frame(df)
    default = str(tmp_path / "default.parquet")
    frame.to_parquet(default, index=False)
    back = pd.read_parquet(part)
    assert back.equals(frame) and back.equals(pd.read_parquet(default))
    for c in frame.columns:  # nan and -0.0: bit for bit
        if frame[c].dtype.kind == "f":
            assert back[c].to_numpy().tobytes() == frame[c].to_numpy().tobytes()
    ours, theirs = pq.ParquetFile(part), pq.ParquetFile(default)
    assert ours.schema_arrow.equals(theirs.schema_arrow, check_metadata=True)
    assert ours.schema_arrow.pandas_metadata == theirs.schema_arrow.pandas_metadata
    assert ours.metadata.num_rows == theirs.metadata.num_rows == len(df)
    for i in range(len(frame.columns)):
        mine, his = ours.metadata.row_group(0).column(i), theirs.metadata.row_group(0).column(i)
        assert mine.compression == his.compression == "SNAPPY"
        assert mine.statistics == his.statistics or mine.statistics.equals(his.statistics)
    if not any(want_plain.values()):  # a frame the rule leaves alone: today's call, today's bytes
        assert _bytes(part) == _bytes(default)
    else:
        assert os.path.getsize(part) != os.path.getsize(default)


@pytest.mark.parametrize("case", ["each_column_of_a_mixed_frame_by_its_own_values",
                                  "nans_are_dropped_before_the_count",
                                  "a_frame_of_20_rows_is_not_touched"])
def test_two_writes_of_one_frame_have_equal_bytes(case, tmp_path):
    df, _ = CASES[case]()
    (one,) = _write(df, tmp_path / "one")
    (two,) = _write(df.copy(), tmp_path / "two")
    assert _bytes(one) == _bytes(two)


def test_every_part_is_planned_from_its_own_rows(tmp_path):
    """``repartition`` 3: the column is distinct in the first part's rows,
    constant in the second's and null in most of the third's."""
    n = 3 * 2048
    x = _distinct(n)
    x[2048:4096] = 1.5
    x[4096:] = _with_nans(x[4096:], 64)
    df = pd.DataFrame({"x": x, "y": _distinct(n, seed=5)})
    parts = _write(df, tmp_path / "out", repartition=3)
    assert [_plain(p) for p in parts] == [{"x": True, "y": True}, {"x": False, "y": True},
                                          {"x": False, "y": True}]
    assert pd.concat([pd.read_parquet(p) for p in parts], ignore_index=True).equals(df)
    written, plain_columns = data_ingest._write_parts(df, str(tmp_path / "out"), "parquet", {}, 3)
    assert written == parts and plain_columns == 4


@pytest.mark.parametrize("columns", [["x", "x"], ["x", 7], [("a", "b"), ("a", "c")]],
                         ids=["duplicate", "not_a_string", "tuples"])
def test_a_frame_whose_names_are_not_unique_strings_is_not_planned(columns):
    df = pd.DataFrame(np.column_stack([_distinct(seed=1), _distinct(seed=2)]))
    df.columns = columns
    assert data_ingest._dictionary_columns(df) is None


def test_the_plan_reads_a_sample_and_never_a_whole_column(monkeypatch):
    """At most 1,024 rows of the part reach the sort, whatever its length."""
    seen = []
    real = np.sort

    def sort(a, *args, **kw):
        seen.append(a.shape)
        return real(a, *args, **kw)

    monkeypatch.setattr(data_ingest.np, "sort", sort)
    df = pd.DataFrame({f"c{i}": _distinct(50_000, seed=i) for i in range(4)})
    assert data_ingest._dictionary_columns(df) == []
    assert len(seen) == 1 and seen[0][1] == 4 and 512 <= seen[0][0] <= 1024


# ------------------------------------------------------------ the tracing ----
def _plain_total():
    return sum(v for _, v in obs.get_metrics().counter("parquet_plain_columns_total").items())


@pytest.mark.parametrize("queued", [False, True], ids=["on_the_pass_thread", "queued"])
def test_the_write_says_how_many_columns_went_plain(queued, tmp_path):
    """On the pass's own thread the ``write/parquet`` row carries
    ``plain_columns`` and ``dict_columns``; a queued write on a writer
    thread opens no phase row, and the counter covers it."""
    df, want_plain = CASES["each_column_of_a_mixed_frame_by_its_own_values"]()
    write = {"file_path": str(tmp_path), "file_type": "parquet", "file_configs": {"mode": "overwrite"}}
    tracer = obs.get_tracer()
    writer = AsyncArtifactWriter(workers=2) if queued else None
    before = _plain_total()
    with tracer.run_pass():
        with tracer.phase("write_main"):
            workflow.save(df, write, "out", writer=writer, key="final")
        if writer is not None:
            writer.close()
    assert _plain_total() - before == sum(want_plain.values()) == 2
    rows = [r for r in tracer.phases() if r["name"] == "write/parquet"]
    if queued:
        assert rows == []
        return
    (row,) = rows
    assert row["parent"] == "write_main"
    assert row["counts"]["plain_columns"] == 2 and row["counts"]["dict_columns"] == 2
    assert row["counts"]["plain_columns"] + row["counts"]["dict_columns"] == df.shape[1]
    assert row["counts"]["rows"] == ROWS and row["counts"]["bytes"] > 0
