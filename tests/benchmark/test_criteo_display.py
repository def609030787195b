"""CPU rehearsal of the cell ``criteo_display.encode`` at 2,000 rows: the
pipeline driver is ``correct`` against float64 pandas, every comparison of
``supervised_encode`` fails once its answer is moved and the bfloat16 control
is not correct; the generator keeps the source's schema, its published
category counts as ceilings, its null columns and its click rate, and is a
function of ``(rows, seed)``; a pass leaves the new spans with their counts
and the final dataset in the input's row order; and the four readers the
cell brings (``transform_s``, ``write_main_s``, ``segment_device_s``,
``segment_hbm_pct``) on what such a pass left, on hand-built rows and on a
hand-built event list.  One file, one process, no child."""

import json
import os
import sys

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest
import yaml

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.drivers import pipeline  # noqa: E402
from benchmark.harness.frames import Frames  # noqa: E402
from benchmark.harness.names import load_module  # noqa: E402

ROWS = 2000
PADDED = 2048
CELL = "criteo_display.encode"
INTEGERS = [f"I{i}" for i in range(1, 14)]
CATEGORICALS = [f"C{i}" for i in range(1, 27)]
READERS = ("transform_s", "write_main_s", "segment_device_s", "segment_hbm_pct")
TRANSFORMERS = ("imputation_MMM", "z_standardization", "cat_to_num_supervised")

criteo = load_module("datasets", "criteo_display")
check = load_module("checks", "supervised_encode")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs", "criteo_display.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traffic():
    with open(os.path.join(ROOT, "benchmark", "traffic", "supervised_encode.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def run(config, traffic, tmp_path_factory):
    """One run of the driver as run.py would start it, at 2,000 rows on the CPU."""
    return pipeline.run({
        "workload": CELL, "config": dict(config, rows=ROWS), "traffic": traffic,
        "traffic_yaml": os.path.join(ROOT, "benchmark", "traffic", "supervised_encode.yaml"),
        "work_dir": str(tmp_path_factory.mktemp("criteo_display")), "seed": 2**31 + 34, "seconds": 0.0,
        "trace": False, "platform": "cpu", "t_start": bench_run.T_START, "say": lambda msg: None,
    })


def _frames(tmp_path, seed, rows=ROWS):
    data_dir = str(tmp_path / "d")
    criteo.generate(data_dir, seed, ["parquet"], rows=rows)
    with open(os.path.join(ROOT, "benchmark", "traffic", "supervised_encode.yaml")) as f:
        return Frames(pipeline._rebase(yaml.safe_load(f), "DATASET/", data_dir + "/"))


# ------------------------------------------------------- the data files ----
def test_the_configuration_states_the_source_its_cut_and_income_32ks_event_rate(bench, config, traffic):
    entry = next(c for c in bench["configs"] if c["name"] == "criteo_display")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("criteo_display", "supervised_encode", 1)
    assert [w["name"] for w in bench["workloads"] if w["config"] == "criteo_display"] == [CELL]  # no second cell
    assert config["source"] == entry["source"] and "45,840,617 rows x 40" in entry["source"]
    assert len(entry["source"]) <= 200 and len(cell["why"]) <= 200
    assert config["published"]["rows"] == criteo.SOURCE_ROWS == 45_840_617
    assert config["published"]["category_counts"] == "-".join(map(str, criteo.CATEGORY_COUNTS))
    assert sum(criteo.CATEGORY_COUNTS) == 33_762_577
    assert config["rows"] in (criteo.SOURCE_ROWS // 64 + 1, criteo.SOURCE_ROWS // 32, criteo.SOURCE_ROWS // 16)
    assert config["reduced"] == entry["reduced"] == ["rows"] and "45,840,617" in config["reduced_why"]["rows"]
    assert config["baseline_rows"] == 0 and config["chips"] == 1 and config["driver"] == "pipeline"
    assert config["dataset"] == {"module": "criteo_display"} and config["columns"] == 40 == len(criteo.SCHEMA)
    assert len(config["assumed"]) >= 6
    with open(os.path.join(ROOT, "benchmark", "configs", "income_32k.json")) as f:
        theirs = json.load(f)["guarantees"]
    mine = config["guarantees"]
    assert set(mine["tolerances"]) == {"event_rate", "zscore"}
    assert mine["tolerances"]["event_rate"] == theirs["tolerances"]["event_rate"]
    assert all(mine[k] == theirs[k] for k in ("durable", "precision", "repeatable"))
    assert "zscore" in mine["tolerances_why"] and "row_order" in mine
    args = traffic["compare"]["supervised_encode"]
    assert (args["label"], args["integers"], args["categoricals"]) == ("label", INTEGERS, CATEGORICALS)
    e2e = {m["name"] for m in bench["end_to_end"] if bench_run._in_cell(m, CELL, set())}
    assert e2e == {"pass_s", "rows_per_s", "setup_s"}
    layers = {"transform_s": "blocks", "write_main_s": "artifact writes", "segment_device_s": "kernels",
              "segment_hbm_pct": "kernels"}
    ours = [m for m in bench["per_layer"] if m["name"] in READERS]  # by name: a later PR appends its own
    assert [m["name"] for m in ours] == list(READERS)
    for m in ours:  # listed where their readers find something: the two cells that run transformers and write_main
        assert m["layer"] == layers[m["name"]] and m["moves"] == "pass_s"
        assert m["workloads"] == ["income_32k.full", CELL]
    # the describe's three find nothing here (the mix describes no table): held to the cells they had
    for m in bench["per_layer"]:
        if m["name"] in ("describe_s", "describe_device_s", "describe_hbm_pct"):
            assert CELL not in m["workloads"] and len(m["workloads"]) == 5


def test_the_mix_is_three_transformers_between_a_plain_read_and_the_final_write():
    with open(os.path.join(ROOT, "benchmark", "traffic", "supervised_encode.yaml")) as f:
        ours = yaml.safe_load(f)
    assert set(ours) == {"input_dataset", "transformers", "write_main"}
    assert set(ours["input_dataset"]) == {"read_dataset"}  # no column deleted, renamed or recast
    sections = [(k, list(v)) for k, v in ours["transformers"].items()]
    assert sections == [("numerical_imputation", ["imputation_MMM"]), ("numerical_rescaling", ["z_standardization"]),
                        ("categorical_encoding", ["cat_to_num_supervised"])]
    mmm = ours["transformers"]["numerical_imputation"]["imputation_MMM"]
    assert (mmm["list_of_cols"], mmm["method_type"]) == ("missing", "median")
    assert ours["transformers"]["numerical_rescaling"]["z_standardization"] == {"list_of_cols": "all", "drop_cols": ["label"]}
    assert ours["transformers"]["categorical_encoding"]["cat_to_num_supervised"] == {
        "list_of_cols": "all", "label_col": "label", "event_label": 1}
    assert ours["write_main"]["file_type"] == "parquet" and ours["write_main"]["file_configs"] == {"mode": "overwrite"}


# ------------------------------------------------------- the generator ----
@pytest.mark.parametrize("rows,per_part,parts", [(300, None, [300]), (2000, 700, [700, 700, 600]),
                                                 (12_345, 5000, [5000, 5000, 2345])])
def test_generator_writes_exactly_the_rows_and_the_stated_types(tmp_path, config, monkeypatch, rows, per_part, parts):
    assert criteo.ROWS_PER_PART == 500_000 and -(-config["rows"] // criteo.ROWS_PER_PART) == 3
    if per_part is not None:  # the split of the cell's cut, at a size a test can write
        monkeypatch.setattr(criteo, "ROWS_PER_PART", per_part)
    criteo.generate(str(tmp_path / "d"), 2**31 + 5, ["parquet"], rows=rows, source_rows=7)
    assert sorted(os.listdir(tmp_path / "d")) == ["parquet"]
    # full parts and the rest, in the order of the rows: 500,000 + 500,000 + 432,519 at the cell's cut
    files = sorted(os.listdir(tmp_path / "d" / "parquet"))
    assert [pq.read_metadata(str(tmp_path / "d" / "parquet" / f)).num_rows for f in files] == parts
    whole = pa.concat_tables(pq.read_table(str(tmp_path / "d" / "parquet" / f)) for f in files)
    assert whole.equals(criteo.arrow_table(criteo.synthesize(rows, 2**31 + 5), 0, rows))
    table = pq.read_table(str(tmp_path / "d" / "parquet"))
    assert table.num_rows == rows and table.schema.equals(criteo.SCHEMA) and table.num_columns == 40
    assert table.column_names == ["label"] + INTEGERS + CATEGORICALS
    assert str(table.schema.field("label").type) == "int32" and table["label"].null_count == 0
    assert all(str(table.schema.field(c).type) == "int64" for c in INTEGERS)
    assert all(str(table.schema.field(c).type) == "string" for c in CATEGORICALS)
    with pytest.raises(ValueError):
        criteo.generate(str(tmp_path / "d"), 1, ["source"], rows=10)


def test_generator_is_a_function_of_rows_and_seed(tmp_path):
    big = 2**31 + 12345
    a, b, c = (criteo.synthesize(500, s) for s in (big, big, big + 1))
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert any(not np.array_equal(a[k], c[k]) for k in a)
    criteo.generate(str(tmp_path / "a"), big, ["parquet"], rows=500)
    criteo.generate(str(tmp_path / "b"), big, ["parquet"], rows=500)
    assert pq.read_table(str(tmp_path / "a" / "parquet")).equals(pq.read_table(str(tmp_path / "b" / "parquet")))


@pytest.mark.parametrize("rows,seed", [(ROWS, 11), (60_000, 2**31 + 9)])
def test_generator_keeps_the_published_counts_the_null_columns_and_the_click_rate(tmp_path, rows, seed):
    df = _frames(tmp_path, seed, rows).main
    assert len(df) == rows and set(df["label"].unique()) <= {0, 1}
    assert abs(df["label"].mean() - criteo.CLICK_RATE) < (0.05 if rows < 10_000 else 0.02)
    for c, published in zip(CATEGORICALS, criteo.CATEGORY_COUNTS):
        values = df[c].dropna()
        assert values.nunique() <= published  # the published count is the ceiling
        assert values.str.fullmatch("[0-9a-f]{8}").all()
        rate = criteo.CATEGORICAL_NULLS.get(c, 0.0)
        assert abs(df[c].isna().mean() - rate) < 0.05 and (rate > 0 or not df[c].isna().any())
    for c, rate, (_, _, _, low, high) in zip(INTEGERS, criteo.INTEGER_NULLS, criteo.INTEGER_LAWS):
        assert abs(df[c].isna().mean() - rate) < 0.05 and (rate > 0 or not df[c].isna().any())
        assert df[c].min() >= low and df[c].max() <= high
        assert (df[c].dropna() == df[c].dropna().round()).all()
    assert df["I2"].min() < 0 and df["I12"].isna().mean() == max(df[c].isna().mean() for c in INTEGERS)
    if rows >= 60_000:  # the small vocabularies are all there, the large ones are a prefix's share
        small = [c for c, n in zip(CATEGORICALS, criteo.CATEGORY_COUNTS) if n <= 30]
        assert [df[c].nunique() for c in small] == [n for n in criteo.CATEGORY_COUNTS if n <= 30]
        assert 10_000 < df["C3"].nunique() < rows and df["I5"].max() > 2**24 // 4


def test_category_ids_do_not_collide_and_render_as_eight_hex_characters():
    ranks = np.arange(200_000)
    ids = criteo.category_ids(ranks, 2, 2**31 + 1)
    assert ids.dtype == np.uint32 and len(np.unique(ids)) == len(ranks)
    assert not np.array_equal(ids, criteo.category_ids(ranks, 3, 2**31 + 1))
    rendered = criteo._hex_strings(np.array([0, 255, 0xDEADBEEF], np.uint32), np.array([False, True, False]))
    assert rendered.to_pylist() == ["00000000", None, "deadbeef"]
    r = criteo.zipf_ranks(np.random.default_rng(1), 100_000, 1000)
    assert r.min() == 0 and r.max() <= 999 and np.bincount(r)[0] > np.bincount(r, minlength=1000)[500]


# ---------------------------------------------------- driver, on the CPU ----
def test_the_cell_is_correct_on_the_cpu_and_reports_its_metrics(run, bench):
    assert run["correct"], [r for r in run["checks"] if not r["ok"]]
    assert run["failed"] == 0 and run["attempted"] == 2
    assert [r["name"] for r in run["checks"]] == [
        "rows", "column_names", "column_kinds", "nulls_left", "label_rows_changed", "fill_values", "modes",
        "zscore", "event_rate", "files_with_other_bytes"]
    line = bench_run.report(bench, CELL, run, False)
    assert set(line["metrics"]) == {"pass_s", "rows_per_s", "setup_s"} and line["correct"] is True
    traced = bench_run.report(bench, CELL, dict(run, trace_dir=""), True)["metrics"]
    assert {"transform_s", "write_main_s", "ingest_s", "ingest_encode_s", "dag_s", "window_compiles"} <= set(traced)
    assert not {"segment_device_s", "segment_hbm_pct", "device_busy_s", "describe_s", "fresh_pass_s"} & set(traced)
    assert traced["window_compiles"]["value"] == 0
    assert 0 < traced["transform_s"]["value"] <= traced["dag_s"]["value"]
    assert 0 < traced["write_main_s"]["value"] < traced["after_dag_s"]["value"]


def test_the_final_dataset_keeps_the_row_order_and_has_no_null(run, traffic):
    last = run["passes"][-1]["out_dir"]
    with open(os.path.join(os.path.dirname(last), "pipeline.yaml")) as f:
        main = Frames(yaml.safe_load(f)).main
    ans = check.read(last, traffic, traffic["compare"]["supervised_encode"])
    assert ans["rows"] == ROWS and list(ans["names"].values()) == list(main.columns)
    assert np.array_equal(ans["label"].to_numpy(), main["label"].to_numpy())
    assert sum(ans["nulls"].values()) == 0
    # a row's I2 has no null, so its z-score orders the rows as the input's I2 does
    order = np.argsort(main["I2"].to_numpy(), kind="stable")
    assert (np.diff(ans["zscore"]["I2"].to_numpy()[order]) >= 0).all()
    assert set(ans["fill_values"]) == {c for c in INTEGERS if main[c].isna().any()}
    assert set(ans["modes"]) == {c for c in CATEGORICALS if main[c].isna().any()}


def test_a_pass_leaves_the_new_spans_with_their_counts(run):
    rows = run["passes"][-1]["manifest"]["phases"]
    nodes = [r["name"] for r in rows if r["parent"] == "dag"]
    assert nodes == ["transformers/" + t for t in TRANSFORMERS]
    for t in TRANSFORMERS:
        kids = [r for r in rows if r["parent"] == "transformers/" + t]
        assert [r["name"] for r in kids] == ["transform/fit", "transform/apply"]
        assert all(r["counts"]["rows"] == PADDED and r["counts"]["cols"] > 0 for r in kids)
    by = {(r["parent"].split("/")[1], r["name"].split("/")[1]): r["counts"] for r in rows
          if r["name"].startswith("transform/")}
    assert by[("z_standardization", "fit")] == {"cols": 13, "rows": PADDED}
    assert by[("imputation_MMM", "apply")]["cols"] == by[("imputation_MMM", "fit")]["cols"] >= 20
    fit, apply = by[("cat_to_num_supervised", "fit")], by[("cat_to_num_supervised", "apply")]
    assert fit["cols"] == apply["cols"] == 26
    assert 1024 < fit["vocab_max"] <= ROWS and fit["segments_max"] == 4096  # the coarse class of a small vocabulary
    assert fit["count_rows"] == fit["label_rows"] == 26 * PADDED and fit["seg_lanes"] % 16 == 0
    assert apply["gather_rows"] == 2 * 26 * PADDED  # a rate and its validity a column
    # a column's LUTs (bool and f32) are as long as its two count vectors (f32 each)
    assert apply["gather_out_bytes"] == 26 * PADDED * (4 + 1) and 2 * apply["lut_bytes"] == 5 * fit["seg_lanes"]
    mmm = by[("imputation_MMM", "fit")]
    assert mmm["count_rows"] % PADDED == 0 and 0 < mmm["count_rows"] <= 12 * PADDED and "label_rows" not in mmm
    write = {r["name"]: r["counts"] for r in rows if r["parent"] == "write_main"}
    assert list(write) == ["write/d2h", "write/parquet"]
    assert write["write/d2h"] == {"arrays": 80, "bytes": 40 * PADDED * 5}
    assert write["write/parquet"]["rows"] == ROWS and write["write/parquet"]["bytes"] > 0


# ------------------------------- correct has to be able to come out false ----
def _nudge(x):
    """An answer moved by more than any tolerance: a number by 1 % and 0.01, a
    count by one, a label by a character, a table or dict in each of its entries."""
    if isinstance(x, dict):
        return {k: _nudge(v) for k, v in x.items()}
    if isinstance(x, pd.Series) and x.dtype.kind == "i":
        return x + 1
    if isinstance(x, (pd.Series, pd.DataFrame)):
        return x * 1.01 + 0.01
    return x + "?" if isinstance(x, str) else x + 1


def test_the_comparison_passes_on_what_a_pass_left_and_fails_when_it_is_moved(run, traffic, config):
    last = run["passes"][-1]["out_dir"]
    with open(os.path.join(os.path.dirname(last), "pipeline.yaml")) as f:
        frames = Frames(yaml.safe_load(f))
    tol, args = config["guarantees"]["tolerances"], traffic["compare"]["supervised_encode"]
    ans, ref = check.read(last, traffic, args), check.reference(frames, args)
    assert all(r["ok"] for r in check.compare(ans, ref, tol, args))
    moved = check.compare(_nudge(ans), ref, tol, args)
    assert len(moved) == 9 and not any(r["ok"] for r in moved), [r["name"] for r in moved if r["ok"]]
    # each answer alone: only its own row turns
    for key, row in (("zscore", "zscore"), ("event_rate", "event_rate"), ("fill_values", "fill_values"),
                     ("modes", "modes"), ("label", "label_rows_changed"), ("kinds", "column_kinds")):
        rows = check.compare(dict(ans, **{key: _nudge(ans[key])}), ref, tol, args)
        assert [r["name"] for r in rows if not r["ok"]] == [row]
    # rows in another order: the label and both toleranced comparisons see it
    back = dict(ans, label=ans["label"][::-1].reset_index(drop=True), zscore=ans["zscore"][::-1].reset_index(drop=True),
                event_rate=ans["event_rate"][::-1].reset_index(drop=True))
    assert [r["name"] for r in check.compare(back, ref, tol, args) if not r["ok"]] == [
        "label_rows_changed", "zscore", "event_rate"]


def test_the_reference_fills_by_the_lower_median_and_settles_a_tie_by_code_point_order(tmp_path):
    os.makedirs(tmp_path / "p")
    df = pd.DataFrame({"label": np.array([1, 0, 1, 0, 1, 0], np.int32),
                       "I1": pd.array([4, None, 1, 10, None, 3], "Int64"),
                       "C1": ["b", None, "a", "b", "a", None]})
    df.to_parquet(tmp_path / "p" / "part-0.parquet", index=False)
    frames = Frames({"input_dataset": {"read_dataset": {"file_path": str(tmp_path / "p"), "file_type": "parquet"}},
                     "transformers": {"categorical_encoding": {"cat_to_num_supervised": {
                         "label_col": "label", "event_label": 1}}}})
    ref = check.reference(frames, {"label": "label", "integers": ["I1"], "categoricals": ["C1"]})
    assert ref["fill_values"] == {"I1": 3.0}  # of 1 3 4 10: the lower of the two middle values
    assert ref["modes"] == {"C1": "a"}  # a and b twice each: the first in code-point order
    filled = np.array([4, 3, 1, 10, 3, 3.0])
    assert np.allclose(ref["zscore"]["I1"], (filled - filled.mean()) / filled.std(ddof=1), rtol=1e-15)
    # after the fill a holds rows 1 2 4 5 (labels 0 1 1 0), b rows 0 3 (labels 1 0)
    assert ref["event_rate"]["C1"].tolist() == [0.5] * 6
    assert ref["kinds"] == {"label": "integer", "I1": "float", "C1": "float"} and ref["rows"] == 6


@pytest.mark.parametrize("seed", [5, 2**31 + 7, 99])
def test_the_control_in_bfloat16_fails_on_this_table(config, traffic, tmp_path, seed):
    frames = _frames(tmp_path, seed)
    tol, args = config["guarantees"]["tolerances"], traffic["compare"]["supervised_encode"]
    ref = check.reference(frames, args)
    assert all(r["ok"] for r in check.compare(ref, ref, tol, args))
    rows = {r["name"]: r for r in check.compare(check.control(ref, frames, args), ref, tol, args)}
    assert not rows["zscore"]["ok"] and rows["zscore"]["value"] > 100
    assert not rows["event_rate"]["ok"] and rows["event_rate"]["value"] > 2
    assert rows["rows"]["ok"] and rows["label_rows_changed"]["ok"] and rows["modes"]["ok"]


# -------------------------------------------------- the four new readers ----
def _row(name, parent, start, end, **counts):
    return {"name": name, "parent": parent, "start_s": start, "end_s": end, "thread": "t", "counts": counts}


RECORDED = [  # a pass as the program records it: ingest 0-4 s, three transformer nodes, the final write 6.1-7.6 s
    _row("run", None, 0.0, 7.7), _row("ingest", "run", 0.0, 4.0), _row("dag", "run", 4.1, 6.0),
    _row("transformers/imputation_MMM", "dag", 4.1, 4.6),
    _row("transform/fit", "transformers/imputation_MMM", 4.1, 4.5, cols=24, rows=1000, count_rows=12_000, seg_lanes=5000),
    _row("transform/apply", "transformers/imputation_MMM", 4.5, 4.6, cols=24, rows=1000),
    _row("transformers/z_standardization", "dag", 4.6, 4.9),
    _row("transformers/cat_to_num_supervised", "dag", 4.9, 6.0),
    _row("transform/fit", "transformers/cat_to_num_supervised", 4.9, 5.6, cols=26, rows=1000, count_rows=26_000,
         label_rows=26_000, seg_lanes=20_000),
    _row("transform/apply", "transformers/cat_to_num_supervised", 5.6, 6.0, cols=26, rows=1000, gather_rows=52_000,
         lut_bytes=50_000, gather_out_bytes=130_000),
    _row("write_main", "run", 6.1, 7.6), _row("write/d2h", "write_main", 6.1, 6.4, arrays=80, bytes=200_000),
    _row("write/parquet", "write_main", 6.4, 7.6, rows=1000, bytes=60_000),
]


def _pass(rows, wall=7.7):
    return {"wall_s": wall, "manifest": {"phases": rows}}


def test_span_readers_on_a_recorded_manifest():
    transform_s = load_module("layer_metrics", "transform_s").read
    write_main_s = load_module("layer_metrics", "write_main_s").read
    run = {"passes": [_pass(RECORDED)]}
    assert transform_s(run) == pytest.approx(0.5 + 0.3 + 1.1) and write_main_s(run) == pytest.approx(1.5)
    # a stats pass: nodes of another block, and a write_main phase that wrote nothing
    stats = [_row("run", None, 0.0, 1.0), _row("dag", "run", 0.5, 0.8),
             _row("stats_generator/measures_of_counts", "dag", 0.5, 0.8), _row("write_main", "run", 0.9, 0.9001)]
    assert transform_s({"passes": [_pass(stats)]}) is None and write_main_s({"passes": [_pass(stats)]}) is None
    # a program from before the write spans (the parent): the transformer nodes are there, the write is not read
    before = [r for r in RECORDED if not r["name"].startswith(("write/", "transform/"))]
    assert transform_s({"passes": [_pass(before)]}) == pytest.approx(1.9)
    assert write_main_s({"passes": [_pass(before)]}) is None
    for rows in ([], [_row("run", None, 0.0, 1.0)]):
        assert transform_s({"passes": [_pass(rows)]}) is None and write_main_s({"passes": [_pass(rows)]}) is None
    assert transform_s({"passes": []}) is None and write_main_s({"passes": []}) is None


def test_device_readers_on_a_hand_built_event_list(monkeypatch):
    """Chip 0: ``jit__code_counts_p`` runs a fusion 1.0-1.4 s, ``jit__code_label_counts_p`` one 2.0-2.5 s,
    ``jit__lut_gather`` a gather 3.0-3.1 s, ``jit__impute_cat_program`` a fusion 4.0-4.9 s (not a segment
    program).  Chip 1: the first alone.  By hand: chip 0 = 0.4 + 0.5 + 0.1 = 1.0, chip 1 = 0.4, mean 0.7 s."""
    device_s = load_module("layer_metrics", "segment_device_s")
    devices = {
        "/device:TPU:0": [(1.0, 1.4, "jit__code_counts_p/fusion"), (2.0, 2.5, "jit__code_label_counts_p/fusion.1"),
                          (3.0, 3.1, "jit__lut_gather/gather.2"), (4.0, 4.9, "jit__impute_cat_program/fusion")],
        "/device:TPU:1": [(1.0, 1.4, "jit__code_counts_p/fusion")],
    }
    assert device_s.segment_seconds(devices) == pytest.approx(0.7)
    assert device_s.segment_seconds({"/device:TPU:0": devices["/device:TPU:0"][3:]}) is None
    assert device_s.segment_seconds({}) is None
    assert device_s.read({"trace_dir": ""}) is None and device_s.read({}) is None
    hbm = load_module("layer_metrics", "segment_hbm_pct")
    by_hand = (12_000 + 26_000) * 5 + 26_000 * 9 + 25_000 * 4 + 52_000 * 4 + 50_000 + 130_000
    assert hbm.segment_bytes(RECORDED) == by_hand
    assert hbm.segment_bytes([r for r in RECORDED if not r["name"].startswith("transform/")]) == 0
    assert hbm.share_pct(819e9 * 0.01, 2.0, 819e9) == pytest.approx(0.5)
    # no trace, no counts, or a device the peaks do not know (the CPU): nothing, and no error
    assert hbm.read({"trace_dir": "", "traced": _pass(RECORDED)}) is None
    assert hbm.read({"segment_device_s": 0.7, "traced": _pass([])}) is None
    assert hbm.read({"segment_device_s": 0.7, "traced": _pass(RECORDED)}) is None
    import jax

    class V5e:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a: [V5e()])
    monkeypatch.setattr(jax, "device_count", lambda *a: 1)
    assert hbm.read({"segment_device_s": 0.7, "traced": _pass(RECORDED)}) == pytest.approx(
        100.0 * by_hand / (0.7 * 819e9))


def test_readers_on_the_live_run(run):
    rows = run["passes"][-1]["manifest"]["phases"]
    nodes = [r for r in rows if r["parent"] == "dag"]
    assert load_module("layer_metrics", "transform_s").read(dict(run, passes=run["passes"][-1:])) == pytest.approx(
        sum(r["end_s"] - r["start_s"] for r in nodes))
    hbm = load_module("layer_metrics", "segment_hbm_pct")
    counted = {k: sum(r["counts"].get(k, 0) for r in rows) for k in hbm.BYTES}
    assert counted["count_rows"] > counted["label_rows"] == 26 * PADDED and counted["gather_rows"] == 52 * PADDED
    assert hbm.segment_bytes(rows) == sum(hbm.BYTES[k] * v for k, v in counted.items())
