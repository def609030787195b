"""CPU rehearsal of the cell ``tpch_lineitem.stats`` at 2,001 rows: the
pipeline driver is ``correct`` against float64 pandas, every comparison of
the mix fails once its answer is moved and the bfloat16 control is not
correct; the generator is a pure function of ``(rows, seed)`` and holds the
rules of TPC-H clause 4.2.3; a pass reads the 16 columns as 8 numeric, 5
categorical and 3 other with no Python object a value; and the four readers
the cell brings (``describe_s``, ``ingest_convert_s``, ``describe_device_s``,
``describe_hbm_pct``) on what such a pass left, on hand-built rows and on a
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

# odd: the program's median is a pick ('lower'), pandas' the mean of the two middle
# values where the count is even, and a price column of 2,000 rows is sparse enough
# for the two to differ by more than the tolerance (SF 1 has 6,001,215 rows, odd too)
ROWS = 2001
CELL = "tpch_lineitem.stats"
NUMERIC = ["l_orderkey", "l_partkey", "l_suppkey", "l_linenumber", "l_quantity", "l_extendedprice",
           "l_discount", "l_tax"]
STRINGS = ["l_returnflag", "l_linestatus", "l_shipinstruct", "l_shipmode", "l_comment"]
DATES = ["l_shipdate", "l_commitdate", "l_receiptdate"]
READERS = ("describe_s", "ingest_convert_s", "describe_device_s", "describe_hbm_pct")

lineitem = load_module("datasets", "tpch_lineitem")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs", "tpch_lineitem.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traffic():
    with open(os.path.join(ROOT, "benchmark", "traffic", "lineitem_stats.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def run(config, traffic, tmp_path_factory):
    """One run of the driver as run.py would start it, at 2,001 rows on the CPU."""
    return pipeline.run({
        "workload": CELL, "config": dict(config, rows=ROWS, baseline_rows=ROWS // 4), "traffic": traffic,
        "traffic_yaml": os.path.join(ROOT, "benchmark", "traffic", "lineitem_stats.yaml"),
        "work_dir": str(tmp_path_factory.mktemp("tpch_lineitem")), "seed": 2**31 + 32, "seconds": 0.0,
        "trace": False, "platform": "cpu", "t_start": bench_run.T_START, "say": lambda msg: None,
    })


def _frames(tmp_path, seed, rows=ROWS):
    data_dir = str(tmp_path / "d")
    lineitem.generate(data_dir, seed, ["parquet"], rows=rows)
    with open(os.path.join(ROOT, "benchmark", "traffic", "lineitem_stats.yaml")) as f:
        return Frames(pipeline._rebase(yaml.safe_load(f), "DATASET/", data_dir + "/"))


# ------------------------------------------------------- the data files ----
def test_the_configuration_states_the_source_and_income_400ks_tolerances(bench, config, traffic):
    entry = next(c for c in bench["configs"] if c["name"] == "tpch_lineitem")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("tpch_lineitem", "lineitem_stats", 1)
    assert [w["name"] for w in bench["workloads"] if w["config"] == "tpch_lineitem"] == [CELL]  # no second cell
    assert config["source"] == entry["source"] and "clause 1.4.1" in entry["source"] and "4.2.3" in entry["source"]
    assert config["rows"] == lineitem.SF1_ROWS == 6_001_215 and config["scale_factor"] == 1
    assert config["baseline_rows"] == 0 and config["chips"] == 1 and config["driver"] == "pipeline"
    assert config["dataset"] == {"module": "tpch_lineitem"} and config["columns"] == 16
    assert config["reduced"] == entry["reduced"] == [] and len(config["assumed"]) >= 4
    assert config["distinct_at_sf1"]["l_comment"] > 1_000_000
    assert {k: v for k, v in config["schema"].items() if k != "nulls"} == {
        f.name: str(f.type).replace(", ", ",").replace("[day]", "") for f in lineitem.SCHEMA}
    with open(os.path.join(ROOT, "benchmark", "configs", "income_400k.json")) as f:
        theirs = json.load(f)["guarantees"]
    ours = config["guarantees"]
    assert set(ours["tolerances"]) == {"mean", "stddev", "median", "min", "max"}
    assert all(ours["tolerances"][k] == theirs["tolerances"][k] for k in ours["tolerances"])
    assert all(ours[k] == theirs[k] for k in ("durable", "precision", "repeatable"))
    assert traffic["compare"]["summary"]["columns"] == NUMERIC
    assert traffic["compare"]["distinct"]["columns"] == STRINGS[:4] + ["l_comment"] + NUMERIC[:3]
    assert traffic["compare"]["column_kinds"]["mode_columns"] == STRINGS[:4]
    e2e = {m["name"] for m in bench["end_to_end"] if bench_run._in_cell(m, CELL, set())}
    assert e2e == {"pass_s", "rows_per_s", "setup_s"}  # not fresh_pass_s: its list is not this PR's to edit
    layers = {"describe_s": "blocks", "ingest_convert_s": "ingest", "describe_device_s": "kernels",
              "describe_hbm_pct": "kernels"}
    for m in bench["per_layer"][-4:]:
        assert m["layer"] == layers[m["name"]] and m["moves"] == "pass_s" and "workloads" not in m
    assert [m["name"] for m in bench["per_layer"][-4:]] == list(READERS)


def test_the_mix_is_the_stats_mix_without_its_column_edits():
    with open(os.path.join(ROOT, "benchmark", "traffic", "lineitem_stats.yaml")) as f:
        ours = yaml.safe_load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic", "stats.yaml")) as f:
        stats = yaml.safe_load(f)
    assert ours["input_dataset"] == {"read_dataset": stats["input_dataset"]["read_dataset"]}
    assert ours["write_stats"] == stats["write_stats"]
    assert ours["stats_generator"]["metric"] == stats["stats_generator"]["metric"]
    assert ours["stats_generator"]["metric_args"] == {"list_of_cols": "all", "drop_cols": []}


# ------------------------------------------------------- the generator ----
@pytest.mark.parametrize("rows", [300, 2000, 12_345])
def test_generator_writes_exactly_the_rows_and_the_stated_types(tmp_path, rows):
    lineitem.generate(str(tmp_path / "d"), 2**31 + 5, ["parquet"], rows=rows, source_rows=7)
    assert sorted(os.listdir(tmp_path / "d")) == ["parquet"]
    files = sorted(os.listdir(tmp_path / "d" / "parquet"))
    assert len(files) == max(4, -(-rows // 500_000))
    table = pq.read_table(str(tmp_path / "d" / "parquet"))
    assert table.num_rows == rows and table.schema.equals(lineitem.SCHEMA) and table.num_columns == 16
    assert [str(table.schema.field(c).type) for c in NUMERIC] == ["int64"] * 3 + ["int32"] + ["decimal128(15, 2)"] * 4
    assert all(str(table.schema.field(c).type) == "date32[day]" for c in DATES)
    assert all(table[c].null_count == 0 for c in table.column_names)
    with pytest.raises(ValueError):
        lineitem.generate(str(tmp_path / "d"), 1, ["source"], rows=10)


def test_generator_is_a_function_of_rows_and_seed(tmp_path):
    big = 2**31 + 12345
    a, b, c = (lineitem.synthesize(500, s) for s in (big, big, big + 1))
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert any(not np.array_equal(a[k], c[k]) for k in a)
    lineitem.generate(str(tmp_path / "a"), big, ["parquet"], rows=500)
    lineitem.generate(str(tmp_path / "b"), big, ["parquet"], rows=500)
    assert pq.read_table(str(tmp_path / "a" / "parquet")).equals(pq.read_table(str(tmp_path / "b" / "parquet")))
    pool = lineitem.text_pool(np.random.default_rng(3), 1 << 16)
    assert len(pool) == 1 << 16 and np.array_equal(pool, lineitem.text_pool(np.random.default_rng(3), 1 << 16))


@pytest.mark.parametrize("rows,seed", [(ROWS, 11), (40_000, 2**31 + 9)])
def test_generator_holds_the_rules_of_clause_4_2_3(tmp_path, rows, seed):
    df = _frames(tmp_path, seed, rows).main
    sc = lineitem.scale(rows)
    n_part, n_supp = sc["parts"], sc["suppliers"]
    assert n_part >= 1 and n_supp >= 1 and sc["scale_factor"] == rows / 6_001_215
    # orders: the first 8 of every 32 keys, 1-7 lines each, numbered from 1
    key = df["l_orderkey"].to_numpy()
    assert ((key - 1) % 32 < 8).all() and (np.diff(key) >= 0).all()
    lines = df.groupby("l_orderkey")["l_linenumber"]
    assert lines.max().between(1, 7).all() and (lines.max() == lines.count()).all() and (lines.min() == 1).all()
    # part and supplier keys: l_suppkey = (partkey + j (S/4 + (partkey - 1)/S)) mod S + 1 for a j of 0..3
    part, supp = df["l_partkey"].to_numpy(), df["l_suppkey"].to_numpy()
    assert part.min() >= 1 and part.max() <= n_part and supp.min() >= 1 and supp.max() <= n_supp
    allowed = np.stack([(part + j * (n_supp // 4 + (part - 1) // n_supp)) % n_supp + 1 for j in range(4)])
    assert (allowed == supp).any(axis=0).all()
    # quantity 1..50; price = quantity x the part's retail price; discount 0..0.10; tax 0..0.08
    cents = {c: (df[c].astype("float64") * 100).round().astype("int64").to_numpy()
             for c in ("l_quantity", "l_extendedprice", "l_discount", "l_tax")}
    assert cents["l_quantity"].min() >= 100 and cents["l_quantity"].max() <= 5000 and (cents["l_quantity"] % 100 == 0).all()
    retail = 90000 + (part // 10) % 20001 + 100 * (part % 1000)
    assert np.array_equal(cents["l_extendedprice"], cents["l_quantity"] // 100 * retail)
    assert set(cents["l_discount"]) <= set(range(11)) and set(cents["l_tax"]) <= set(range(9))
    # dates: ship = order + 1..121, commit = order + 30..90, receipt = ship + 1..30, one order date an order
    ship, commit, receipt = (pd.to_datetime(df[c]).to_numpy().astype("datetime64[D]") for c in DATES)
    assert (receipt > ship).all() and ((receipt - ship).astype(int) <= 30).all()
    assert ((ship - commit).astype(int) <= 121 - 30).all() and ((commit - ship).astype(int) <= 90 - 1).all()
    assert ship.min() > np.datetime64("1992-01-01") and ship.max() <= np.datetime64("1998-08-02") + 121
    # flags: R or A where received by 1995-06-17, else N; status O where shipped after it, else F
    current = np.datetime64("1995-06-17")
    flag, status = df["l_returnflag"].to_numpy(dtype=object), df["l_linestatus"].to_numpy(dtype=object)
    assert set(flag[receipt <= current]) <= {"R", "A"} and set(flag[receipt > current]) <= {"N"}
    assert set(status[ship > current]) <= {"O"} and set(status[ship <= current]) <= {"F"}
    assert set(df["l_shipinstruct"]) <= set(lineitem.SHIPINSTRUCT) and set(df["l_shipmode"]) <= set(lineitem.SHIPMODE)
    length = df["l_comment"].str.len()
    assert length.min() >= 10 and length.max() <= 43 and df["l_comment"].nunique() > 0.9 * rows
    if rows >= 40_000:
        assert set(flag) == {"R", "A", "N"} and set(status) == {"O", "F"}
        assert df["l_shipinstruct"].nunique() == 4 and df["l_shipmode"].nunique() == 7


# ---------------------------------------------------- driver, on the CPU ----
def test_the_cell_is_correct_on_the_cpu_and_reports_its_metrics(run, bench):
    assert run["correct"], [r for r in run["checks"] if not r["ok"]]
    assert run["failed"] == 0 and run["attempted"] == 2
    assert {r["name"] for r in run["checks"]} == {
        "rows", "count", "mean", "stddev", "min", "max", "median", "distinct", "column_kinds",
        "column_kind_counts", "mode", "mode_rows", "files_with_other_bytes"}
    line = bench_run.report(bench, CELL, run, False)
    assert set(line["metrics"]) == {"pass_s", "rows_per_s", "setup_s"} and line["correct"] is True
    assert all(v["value"] > 0 for v in line["metrics"].values())
    traced = bench_run.report(bench, CELL, dict(run, trace_dir=""), True)["metrics"]
    assert {"describe_s", "ingest_convert_s", "ingest_s", "ingest_encode_s", "dag_s"} <= set(traced)
    assert not {"describe_device_s", "describe_hbm_pct", "device_busy_s", "fresh_pass_s"} & set(traced)
    # window_compiles is not held to 0 here: at this size measures_of_counts may find the describe
    # memoized or not (PERF.md section 7), and the pass that first finds it not compiles the count-only path
    assert 0 < traced["describe_s"]["value"] < traced["dag_s"]["value"]
    assert 0 < traced["ingest_convert_s"]["value"] < traced["ingest_s"]["value"]


def test_a_pass_reads_eight_numeric_five_categorical_and_three_other_columns(run, traffic):
    from benchmark.harness import check

    last = run["passes"][-1]
    gs = check.table(last["out_dir"], traffic["tables"]["global_summary"])
    gs = dict(zip(gs["metric"], gs["value"]))
    assert (gs["rows_count"], gs["columns_count"]) == (str(ROWS), "16")
    assert (gs["numcols_count"], gs["catcols_count"], gs["othercols_count"]) == ("8", "5", "3")
    assert gs["numcols_name"] == ", ".join(NUMERIC) and gs["othercols_name"] == ", ".join(DATES)
    assert sorted(gs["catcols_name"].split(", ")) == sorted(STRINGS)
    # the tables hold the 13 described columns; a date is described by none of them, and only
    # measures_of_counts, which takes "all" as every column, counts its filled rows besides
    for name, rel in traffic["tables"].items():
        if name not in ("global_summary", "measures_of_counts"):
            attrs = set(check.table(last["out_dir"], rel)["attribute"])
            assert attrs <= set(NUMERIC + STRINGS) and not attrs & set(DATES), name
    counts = check.table(last["out_dir"], traffic["tables"]["measures_of_counts"]).set_index("attribute")
    assert set(counts.index) == set(NUMERIC + STRINGS + DATES) and (counts["fill_count"] == ROWS).all()
    for name in ("measures_of_centralTendency", "measures_of_cardinality"):
        assert set(check.table(last["out_dir"], traffic["tables"][name])["attribute"]) == set(NUMERIC + STRINGS)
    rows = last["manifest"]["phases"]
    encodes = [r for r in rows if r["name"] == "ingest/encode"]
    assert len(encodes) == 5  # the strings; no date and no decimal is dictionary-encoded
    assert all(r["counts"]["hashed"] == 1 and r["counts"]["native_sort"] == 1 and r["counts"]["rows"] == ROWS
               for r in encodes)
    converts = [r for r in rows if r["name"] == "ingest/convert"]
    assert len(converts) == 7 and all(r["parent"] == "ingest/assemble" and r["counts"]["rows"] == ROWS for r in converts)
    (assemble,) = [r for r in rows if r["name"] == "ingest/assemble"]
    assert assemble["counts"]["arrow_typed"] == 7


def test_the_describe_is_one_span_under_the_node_that_computes_it(run):
    rows = run["passes"][-1]["manifest"]["phases"]
    nodes = {r["name"] for r in rows if r["parent"] == "dag"}
    (describe,) = [r for r in rows if r["name"] == "describe"]
    assert describe["parent"] in nodes and describe["counts"] == {"num_cols": 8, "cat_cols": 5}
    kids = {r["name"]: r for r in rows if r["parent"] == "describe"}
    assert list(kids) == ["describe/numeric", "describe/wide", "describe/cat_sweep", "describe/cat_sort"]
    padded = 2048
    assert kids["describe/numeric"]["counts"] == {"rows": padded, "cols": 8}
    assert kids["describe/wide"]["counts"] == {"rows": padded, "cols": 3}  # price, discount, tax: f32 holds none exact
    assert kids["describe/cat_sweep"]["counts"] == {"rows": padded, "cols": 4, "vocab_max": 16}
    assert kids["describe/cat_sort"]["counts"]["cols"] == 1 and kids["describe/cat_sort"]["counts"]["vocab_max"] > 1024
    for r in kids.values():
        assert describe["start_s"] <= r["start_s"] <= r["end_s"] <= describe["end_s"]


# ------------------------------- correct has to be able to come out false ----
def _nudge(x):
    """An answer moved by more than any tolerance: a number by 1 % and 0.01, a
    count by one, a label by a character, a table or dict in each of its entries."""
    if isinstance(x, dict):
        return {k: _nudge(v) for k, v in x.items()}
    if isinstance(x, (pd.Series, pd.DataFrame)):
        return x * 1.01 + 0.01
    return x + "?" if isinstance(x, str) else x + 1


@pytest.mark.parametrize("name", ["summary", "distinct", "column_kinds"])
def test_each_comparison_passes_on_what_a_pass_left_and_fails_when_it_is_moved(run, traffic, config, name):
    last = run["passes"][-1]["out_dir"]
    with open(os.path.join(os.path.dirname(last), "pipeline.yaml")) as f:
        frames = Frames(yaml.safe_load(f))
    tol = config["guarantees"]["tolerances"]
    mod, args = load_module("checks", name), traffic["compare"][name]
    ans, ref = mod.read(last, traffic, args), mod.reference(frames, args)
    assert all(r["ok"] for r in mod.compare(ans, ref, tol, args))
    moved = mod.compare(_nudge(ans), ref, tol, args)
    assert moved and not any(r["ok"] for r in moved), [r["name"] for r in moved if r["ok"]]


def test_column_kinds_settles_a_tie_by_the_count_and_reads_the_parquet_schema(tmp_path):
    kinds = load_module("checks", "column_kinds")
    os.makedirs(tmp_path / "p")
    pq.write_table(pa.table({"k": pa.array([1, 2, 3, 4], pa.int64()), "flag": ["a", "b", "b", "a"],
                             "ok": [True, False, True, True],
                             "day": pa.array([1, 2, 3, 4], pa.int32()).cast(pa.date32()),
                             "at": pa.array([1, 2, 3, 4], pa.timestamp("us"))}),
                   str(tmp_path / "p" / "part-0.parquet"))
    frames = Frames({"input_dataset": {"read_dataset": {"file_path": str(tmp_path / "p"), "file_type": "parquet"}}})
    ref = kinds.reference(frames, {"mode_columns": ["flag"]})
    assert ref["kinds"] == {"numcols": "k", "catcols": "flag, ok", "othercols": "day, at"}
    assert ref["modes"] == {"flag": ["a", "b"]} and ref["mode_rows"] == {"flag": 2}
    ans = {"kinds": ref["kinds"], "kind_counts": ref["kind_counts"], "mode": {"flag": "b"}, "mode_rows": {"flag": 2}}
    assert all(r["ok"] for r in kinds.compare(ans, ref, {}, {}))
    failing = kinds.compare(dict(ans, mode={"flag": "c"}, kinds=dict(ref["kinds"], catcols="flag, ok, day")), ref, {}, {})
    assert [r["name"] for r in failing if not r["ok"]] == ["column_kinds", "mode"]


@pytest.mark.parametrize("seed", [5, 2**31 + 7, 99])
def test_the_control_in_bfloat16_fails_on_this_table_too(config, traffic, tmp_path, seed):
    frames = _frames(tmp_path, seed)
    tol, args = config["guarantees"]["tolerances"], traffic["compare"]["summary"]
    summary = load_module("checks", "summary")
    ref = summary.reference(frames, args)
    assert ref["rows"] == ROWS and list(ref["summary"].index) == NUMERIC
    assert all(r["ok"] for r in summary.compare(ref, ref, tol, args))
    rows = {r["name"]: r for r in summary.compare(summary.control(ref, frames, args), ref, tol, args)}
    assert not rows["mean"]["ok"] and rows["mean"]["value"] > 3
    assert not rows["max"]["ok"]  # a price of five figures and two decimals has no bfloat16
    assert rows["rows"]["ok"] and rows["count"]["ok"]


# -------------------------------------------------- the four new readers ----
def _row(name, parent, start, end, **counts):
    return {"name": name, "parent": parent, "start_s": start, "end_s": end, "thread": "t", "counts": counts}


RECORDED = [  # a pass as the program records it: ingest 0-6 s, the describe 6.5-8.5 s under a node
    _row("run", None, 0.0, 9.0), _row("ingest", "run", 0.0, 6.0), _row("io:read_dataset", "ingest", 0.0, 6.0),
    _row("ingest/assemble", "io:read_dataset", 2.0, 3.0, arrow_typed=3),
    _row("ingest/convert", "ingest/assemble", 2.5, 2.6, rows=100), _row("ingest/convert", "ingest/assemble", 2.6, 2.75, rows=100),
    _row("ingest/convert", "ingest/assemble", 2.75, 2.8, rows=100),
    _row("dag", "run", 6.2, 8.8), _row("stats_generator/measures_of_counts", "dag", 6.4, 8.6),
    _row("describe", "stats_generator/measures_of_counts", 6.5, 8.5, num_cols=8, cat_cols=5),
    _row("describe/numeric", "describe", 6.5, 7.3, rows=1000, cols=8),
    _row("describe/wide", "describe", 7.3, 8.0, rows=1000, cols=3),
    _row("describe/cat_sweep", "describe", 8.0, 8.1, rows=1000, cols=4, vocab_max=16),
    _row("describe/cat_sort", "describe", 8.1, 8.5, rows=1000, cols=1, vocab_max=900),
]


def _pass(rows, wall=9.0):
    return {"wall_s": wall, "manifest": {"phases": rows}}


def test_span_readers_on_a_recorded_manifest():
    describe_s = load_module("layer_metrics", "describe_s").read
    convert_s = load_module("layer_metrics", "ingest_convert_s").read
    run = {"passes": [_pass(RECORDED)]}
    assert describe_s(run) == pytest.approx(2.0) and convert_s(run) == pytest.approx(0.3)
    # a pass that describes two tables: the sum; one that converts nothing: 0.0, not nothing
    twice = RECORDED + [_row("describe", "quality_checker/x", 8.6, 8.7)]
    assert describe_s({"passes": [_pass(twice)]}) == pytest.approx(2.1)
    plain = [dict(r, counts={"arrow_typed": 0}) if r["name"] == "ingest/assemble" else r
             for r in RECORDED if r["name"] != "ingest/convert"]
    assert convert_s({"passes": [_pass(plain)]}) == 0.0
    # a program from before the spans (the parent): nothing, and no error
    before = [dict(r, counts={}) for r in RECORDED if not r["name"].startswith(("describe", "ingest/convert"))]
    for rows in (before, []):
        assert describe_s({"passes": [_pass(rows)]}) is None and convert_s({"passes": [_pass(rows)]}) is None
    assert describe_s({"passes": []}) is None and convert_s({"passes": []}) is None


def test_device_readers_on_a_hand_built_event_list(monkeypatch):
    """Chip 0: ``jit__describe_numeric`` runs a sort 1.0-2.0 s inside a while
    1.0-2.5 s, ``jit_describe_cat`` a fusion 3.0-3.2 s, ``jit__stack_cast`` a
    copy 0.5-0.9 s (not the describe's).  Chip 1: the sort alone, 1.0-1.8 s.
    By hand: chip 0 = 1.5 + 0.2 = 1.7, chip 1 = 0.8, mean 1.25 s."""
    device_s = load_module("layer_metrics", "describe_device_s")
    devices = {
        "/device:TPU:0": [(0.5, 0.9, "jit__stack_cast/copy.1"), (1.0, 2.5, "jit__describe_numeric/while"),
                          (1.0, 2.0, "jit__describe_numeric/sort.6"), (3.0, 3.2, "jit_describe_cat/fusion.2")],
        "/device:TPU:1": [(1.0, 1.8, "jit__describe_wide_int/sort.0")],
    }
    assert device_s.describe_seconds(devices) == pytest.approx(1.25)
    assert device_s.describe_seconds({"/device:TPU:0": devices["/device:TPU:0"][:1]}) is None
    assert device_s.describe_seconds({}) is None
    assert device_s.read({"trace_dir": ""}) is None and device_s.read({}) is None
    hbm = load_module("layer_metrics", "describe_hbm_pct")
    # 1000 rows: 8 cols x 5 + 3 x 9 + 4 x 5 + 1 x 5 bytes a row
    assert hbm.describe_bytes(RECORDED) == 1000 * (40 + 27 + 20 + 5)
    assert hbm.describe_bytes([r for r in RECORDED if not r["name"].startswith("describe/")]) == 0
    assert hbm.share_pct(819e9 * 0.01, 2.0, 819e9) == pytest.approx(0.5)
    assert hbm.share_pct(819e9 * 0.04, 2.0, 819e9, chips=4) == pytest.approx(0.5)
    # no trace, no spans, or a device the peaks do not know (the CPU): nothing, and no error
    assert hbm.read({"trace_dir": "", "traced": _pass(RECORDED)}) is None
    assert hbm.read({"describe_device_s": 1.25, "traced": _pass([])}) is None
    assert hbm.read({"describe_device_s": 1.25, "traced": _pass(RECORDED)}) is None
    import jax

    class V5e:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a: [V5e()])
    monkeypatch.setattr(jax, "device_count", lambda *a: 1)
    assert hbm.read({"describe_device_s": 1.25, "traced": _pass(RECORDED)}) == pytest.approx(
        100.0 * 92_000 / (1.25 * 819e9))


def test_readers_on_the_live_run(run, bench):
    describe_s = load_module("layer_metrics", "describe_s").read(run)
    rows = run["passes"][-1]["manifest"]["phases"]
    (span,) = [r for r in rows if r["name"] == "describe"]
    assert describe_s == pytest.approx(span["end_s"] - span["start_s"])
    hbm = load_module("layer_metrics", "describe_hbm_pct")
    assert hbm.describe_bytes(rows) == 2048 * (8 * 5 + 3 * 9 + 4 * 5 + 1 * 5)
