"""CPU rehearsal of the cell ``home_credit.association`` at 2,500 rows: the
pipeline driver is ``correct`` against the plain reference, the bfloat16
control, each of the four named faults and each moved answer are not; the
configuration states its source, every assumption and that nothing is cut; the
generator keeps the source's 122 columns in order, name and type, the 16
numbers of values and the joint null blocks, and is a function of ``(rows,
seed)``; every per-layer metric that ``run._in_cell`` admits to the cell is in
the traced line; and the four readers the cell brings (``association_s``,
``assoc_host_rows``, ``assoc_device_s``, ``assoc_group_hbm_pct``) on what such
a pass left, on hand-built rows and on a hand-built event list.  One file, one
process, no child."""

import json
import os
import shutil
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

ROWS = 2500
PADDED = 3072
CELL = "home_credit.association"
READERS = ("association_s", "assoc_host_rows", "assoc_device_s", "assoc_group_hbm_pct")
NODES = ["correlation_matrix", "IV_calculation", "IG_calculation", "variable_clustering"]
HOUSING = ["APARTMENTS", "BASEMENTAREA", "YEARS_BEGINEXPLUATATION", "YEARS_BUILD", "COMMONAREA", "ELEVATORS", "ENTRANCES",
           "FLOORSMAX", "FLOORSMIN", "LANDAREA", "LIVINGAPARTMENTS", "LIVINGAREA", "NONLIVINGAPARTMENTS", "NONLIVINGAREA"]
STRINGS = {"NAME_CONTRACT_TYPE": 2, "CODE_GENDER": 3, "FLAG_OWN_CAR": 2, "FLAG_OWN_REALTY": 2, "NAME_TYPE_SUITE": 7,
           "NAME_INCOME_TYPE": 8, "NAME_EDUCATION_TYPE": 5, "NAME_FAMILY_STATUS": 6, "NAME_HOUSING_TYPE": 6,
           "OCCUPATION_TYPE": 18, "WEEKDAY_APPR_PROCESS_START": 7, "ORGANIZATION_TYPE": 58, "FONDKAPREMONT_MODE": 4,
           "HOUSETYPE_MODE": 3, "WALLSMATERIAL_MODE": 7, "EMERGENCYSTATE_MODE": 2}  # in file order
INTEGERS = (["SK_ID_CURR", "TARGET", "CNT_CHILDREN", "DAYS_BIRTH", "DAYS_EMPLOYED", "DAYS_ID_PUBLISH", "FLAG_MOBIL",
             "FLAG_EMP_PHONE", "FLAG_WORK_PHONE", "FLAG_CONT_MOBILE", "FLAG_PHONE", "FLAG_EMAIL", "REGION_RATING_CLIENT",
             "REGION_RATING_CLIENT_W_CITY", "HOUR_APPR_PROCESS_START", "REG_REGION_NOT_LIVE_REGION",
             "REG_REGION_NOT_WORK_REGION", "LIVE_REGION_NOT_WORK_REGION", "REG_CITY_NOT_LIVE_CITY",
             "REG_CITY_NOT_WORK_CITY", "LIVE_CITY_NOT_WORK_CITY"] + [f"FLAG_DOCUMENT_{i}" for i in range(2, 22)])
BUREAU = ["AMT_REQ_CREDIT_BUREAU_" + s for s in ("HOUR", "DAY", "WEEK", "MON", "QRT", "YEAR")]
SOCIAL = ["OBS_30_CNT_SOCIAL_CIRCLE", "DEF_30_CNT_SOCIAL_CIRCLE", "OBS_60_CNT_SOCIAL_CIRCLE", "DEF_60_CNT_SOCIAL_CIRCLE"]
HOUSING_COLUMNS = ([m + s for s in ("_AVG", "_MODE", "_MEDI") for m in HOUSING]
                   + ["FONDKAPREMONT_MODE", "HOUSETYPE_MODE", "TOTALAREA_MODE", "WALLSMATERIAL_MODE", "EMERGENCYSTATE_MODE"])
WITH_NULLS = (["AMT_ANNUITY", "AMT_GOODS_PRICE", "NAME_TYPE_SUITE", "OWN_CAR_AGE", "OCCUPATION_TYPE", "CNT_FAM_MEMBERS",
               "EXT_SOURCE_1", "EXT_SOURCE_2", "EXT_SOURCE_3", "DAYS_LAST_PHONE_CHANGE"] + HOUSING_COLUMNS + SOCIAL + BUREAU)
LAYOUT = (["SK_ID_CURR", "TARGET", "NAME_CONTRACT_TYPE", "CODE_GENDER", "FLAG_OWN_CAR", "FLAG_OWN_REALTY", "CNT_CHILDREN",
           "AMT_INCOME_TOTAL", "AMT_CREDIT", "AMT_ANNUITY", "AMT_GOODS_PRICE", "NAME_TYPE_SUITE", "NAME_INCOME_TYPE",
           "NAME_EDUCATION_TYPE", "NAME_FAMILY_STATUS", "NAME_HOUSING_TYPE", "REGION_POPULATION_RELATIVE", "DAYS_BIRTH",
           "DAYS_EMPLOYED", "DAYS_REGISTRATION", "DAYS_ID_PUBLISH", "OWN_CAR_AGE", "FLAG_MOBIL", "FLAG_EMP_PHONE",
           "FLAG_WORK_PHONE", "FLAG_CONT_MOBILE", "FLAG_PHONE", "FLAG_EMAIL", "OCCUPATION_TYPE", "CNT_FAM_MEMBERS",
           "REGION_RATING_CLIENT", "REGION_RATING_CLIENT_W_CITY", "WEEKDAY_APPR_PROCESS_START", "HOUR_APPR_PROCESS_START",
           "REG_REGION_NOT_LIVE_REGION", "REG_REGION_NOT_WORK_REGION", "LIVE_REGION_NOT_WORK_REGION",
           "REG_CITY_NOT_LIVE_CITY", "REG_CITY_NOT_WORK_CITY", "LIVE_CITY_NOT_WORK_CITY", "ORGANIZATION_TYPE",
           "EXT_SOURCE_1", "EXT_SOURCE_2", "EXT_SOURCE_3"] + HOUSING_COLUMNS[:42] + HOUSING_COLUMNS[42:] + SOCIAL
          + ["DAYS_LAST_PHONE_CHANGE"] + [f"FLAG_DOCUMENT_{i}" for i in range(2, 22)] + BUREAU)

credit = load_module("datasets", "home_credit")
check = load_module("checks", "association_binned")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs", "home_credit.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traffic():
    with open(os.path.join(ROOT, "benchmark", "traffic", "association.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def run(config, traffic, tmp_path_factory):
    """One run of the driver as run.py would start it, at 2,500 rows on the CPU."""
    return pipeline.run({
        "workload": CELL, "config": dict(config, rows=ROWS), "traffic": traffic,
        "traffic_yaml": os.path.join(ROOT, "benchmark", "traffic", "association.yaml"),
        "work_dir": str(tmp_path_factory.mktemp("home_credit")), "seed": 2**31 + 46, "seconds": 0.0,
        "trace": False, "platform": "cpu", "t_start": bench_run.T_START, "say": lambda msg: None,
    })


@pytest.fixture(scope="module")
def table(tmp_path_factory):
    """The generator's table at 40,000 rows, beside its Arrow schema."""
    dest = str(tmp_path_factory.mktemp("credit") / "d")
    credit.generate(dest, 2**31 + 11, ["parquet"], rows=40_000)
    return pd.read_parquet(os.path.join(dest, "parquet")), pq.read_table(os.path.join(dest, "parquet")).schema


def _frames(data_dir):
    with open(os.path.join(ROOT, "benchmark", "traffic", "association.yaml")) as f:
        return Frames(pipeline._rebase(yaml.safe_load(f), "DATASET/", data_dir + "/"))


# ------------------------------------------------------- the data files ----
def test_the_configuration_states_its_source_that_nothing_is_cut_and_every_assumption(bench, config, traffic):
    entry = next(c for c in bench["configs"] if c["name"] == "home_credit")  # by name, not by position
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["file"] == "benchmark/configs/home_credit.json" and entry["reduced"] == [] == config["reduced"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    for what in ("Home Credit Default Risk", "application_train.csv", "HomeCredit_columns_description.csv", "307,511",
                 "122", "association_evaluator"):
        assert what in entry["source"], what
    assert cell == {"name": CELL, "config": "home_credit", "traffic": "association", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "307,511" in cell["why"] and "GB" in cell["why"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1 and len(bench["workloads"]) >= 9
    assert config["chips"] == 1 and config["driver"] == "pipeline" and config["baseline_rows"] == 0
    assert config["rows"] == config["published"]["rows"] == credit.SOURCE_ROWS == 307_511
    assert config["columns"] == config["published"]["columns"] == len(credit.SCHEMA) == 122
    assert config["published"]["layout"].split(", ") == LAYOUT and "memory" in config["published"]["written_from"]
    assert "393,216" in config["deployment"] and "nothing is cut" in config["reduced_why"]
    told = " ".join(config["assumed"])
    for what in ("parquet", "YEARS_BEGINEXPLUATATION", "STRINGS", "4,500", "HOUSING_NULLS", "ONE draw", "COUPLING",
                 "EXT_SOURCE", "0.02", "float32", "random streams", "one at least", "every one of its values",
                 "FLAG_DOCUMENT_4", "half-row correction"):
        assert what in told, what
    g = config["guarantees"]
    assert set(g["tolerances"]) == {"correlation", "iv", "ig"}
    # under income_32k's 1e-4: the counts are whole numbers, so a sound answer is off by its table's fourth decimal
    # alone (5e-5), and the correction left out moves one by 1.3e-4
    assert g["tolerances"]["iv"] == g["tolerances"]["ig"] == {"rtol": 0.0, "atol": 7e-5}
    for name in ("correlation", "iv", "ig", "bfloat16", "nulls_dropped", "strict_cutoff", "no_correction",
                 "pairwise_correlation"):
        assert name in g["tolerances_why"], name
    for word in ("no sampling", "100,000", "group of their own", "complete in all 105", "disk", "f32", "same bytes", "B11"):
        assert any(word in v for v in g.values() if isinstance(v, str)), word


def test_the_mix_is_the_upstreams_association_section_on_this_tables_names(traffic):
    with open(os.path.join(ROOT, "benchmark", "traffic", "association.yaml")) as f:
        mix = yaml.safe_load(f)
    assert set(mix) == {"input_dataset", "association_evaluator", "write_stats"}
    assert mix["input_dataset"] == {"read_dataset": {"file_path": "DATASET/parquet", "file_type": "parquet"}}
    with open(os.path.join(ROOT, "config", "configs_full.yaml")) as f:
        theirs = yaml.safe_load(f)

    def renamed(node):  # the upstream's id, label and event are this table's
        if isinstance(node, dict):
            return {k: renamed(v) for k, v in node.items()}
        if isinstance(node, list):
            return [renamed(v) for v in node]
        return {"ifa": "SK_ID_CURR", "income": "TARGET", ">50K": 1, "ifa|income": "SK_ID_CURR|TARGET"}.get(node, node)

    assert renamed(theirs["association_evaluator"]) == mix["association_evaluator"]
    assert list(mix["association_evaluator"]) == NODES and mix["write_stats"] == theirs["write_stats"]
    args = traffic["compare"]["association_binned"]
    assert list(traffic["compare"]) == ["association_binned"] and args["sure_rows"] == 100
    assert args["numeric"] == credit.NUMERIC and len(args["numeric"]) == 104 and args["categorical"] == list(STRINGS)
    assert args["correlation"] == ["TARGET"] + credit.NUMERIC and len(args["correlation"]) == 105
    assert list(traffic["tables"]) == NODES and traffic["manifest"] == "obs/run_manifest.json"
    assert traffic["dataset_parts"] == ["parquet"] and traffic["not_repeatable"] == ["obs/*"]
    e2e = {m["name"] for m in bench_run_e2e()}
    assert e2e == {"pass_s", "rows_per_s", "setup_s"}


def bench_run_e2e():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return [m for m in bench["end_to_end"] if bench_run._in_cell(m, CELL, set())]


def test_benchmark_json_appends_the_cell_and_its_four_readers(bench):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    want = {"association_s": ("s", "lower", "program_span", "blocks"),
            "assoc_host_rows": ("count", "lower", "program_counter", "blocks"),
            "assoc_device_s": ("s", "lower", "device_trace", "kernels"),
            "assoc_group_hbm_pct": ("%", "higher", "device_trace", "kernels")}
    for name, (unit, better, source, layer) in want.items():
        m = by_name[name]
        assert m == {"name": name, "unit": unit, "better": better, "source": source, "layer": layer,
                     "moves": "pass_s", "workloads": m["workloads"]}
        assert CELL in m["workloads"] and set(m["workloads"]) <= {CELL, "income_32k.full"}
        assert callable(load_module("layer_metrics", name).read)
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index("ae_fit_hbm_pct") < min(names.index(n) for n in READERS)  # after what was there
    # the cell has 16 string columns and ingest/encode spans to read, but ingest_encode_s's list is pinned to the
    # seven cells it had by a test this PR may not edit (test_epsilon_2k.py): the cell does not report it
    assert CELL not in by_name["ingest_encode_s"]["workloads"]
    assert [w["name"] for w in bench["workloads"]].index(CELL) == 8


def test_generator_writes_the_122_columns_in_order_name_and_type(table):
    df, schema = table
    assert schema.names == LAYOUT and len(LAYOUT) == 122 == len(set(LAYOUT))
    for f in schema:
        want = pa.string() if f.name in STRINGS else pa.int64() if f.name in INTEGERS else pa.float64()
        assert f.type == want, f.name
    assert sum(f.type == pa.float64() for f in schema) == 65 and sum(f.type == pa.int64() for f in schema) == 41
    assert [c for c in LAYOUT if c in STRINGS] == list(STRINGS)
    assert {c: df[c].nunique() for c in STRINGS} == STRINGS  # the published numbers of values, at every size
    assert df["SK_ID_CURR"].is_unique and df["SK_ID_CURR"].min() == 100_002
    with open(credit.__file__) as f:
        src = f.read()
    assert "anovos_tpu" not in src.replace("from anovos_tpu.data_analyzer import association_evaluator", "")


def test_generator_keeps_the_null_structure(table):
    df, _ = table
    nulls = df.isna()
    assert sorted(nulls.columns[nulls.any()]) == sorted(WITH_NULLS) and len(WITH_NULLS) == 67
    assert not nulls[INTEGERS].any().any()
    housing = nulls[HOUSING_COLUMNS]
    rates = housing.mean()
    assert 0.46 < rates.min() and rates.max() < 0.71 and len(HOUSING_COLUMNS) == 47
    # missing together: ordered by rate, a row that lacks a column lacks every column of a higher rate
    ordered = housing[rates.sort_values().index].to_numpy()
    assert (ordered[:, :-1] <= ordered[:, 1:]).all()
    assert (housing["APARTMENTS_AVG"] == housing["APARTMENTS_MODE"]).all() and (housing["APARTMENTS_AVG"] == housing["APARTMENTS_MEDI"]).all()
    assert (nulls["OWN_CAR_AGE"] == (df["FLAG_OWN_CAR"] == "N")).all() and 0.64 < nulls["OWN_CAR_AGE"].mean() < 0.68
    assert nulls[BUREAU].nunique(axis=1).eq(1).all() and 0.12 < nulls[BUREAU[0]].mean() < 0.15
    assert nulls[SOCIAL].nunique(axis=1).eq(1).all() and nulls[SOCIAL[0]].sum() == round(40_000 * 1021 / 307_511)
    assert 0.54 < nulls["EXT_SOURCE_1"].mean() < 0.59 and 0.18 < nulls["EXT_SOURCE_3"].mean() < 0.22
    assert 0.28 < nulls["OCCUPATION_TYPE"].mean() < 0.35 and nulls["DAYS_LAST_PHONE_CHANGE"].sum() == 1
    complete = (~nulls[[c for c in LAYOUT if c not in STRINGS and c != "SK_ID_CURR"]]).all(axis=1).mean()
    assert 0.015 < complete < 0.06  # the correlation's complete cases are a few per cent of the rows


def test_generator_keeps_the_conventions_and_the_labels_dependence(table):
    df, _ = table
    assert 0.07 < df["TARGET"].mean() < 0.092 and set(df["TARGET"]) == {0, 1}
    retired = df["DAYS_EMPLOYED"] == 365_243
    assert 0.15 < retired.mean() < 0.22 and (df.loc[~retired, "DAYS_EMPLOYED"] <= 0).all()
    assert (df.loc[retired, "NAME_INCOME_TYPE"] == "Pensioner").all() and (df.loc[retired, "ORGANIZATION_TYPE"] == "XNA").all()
    assert df.loc[retired, "OCCUPATION_TYPE"].isna().all() and (df.loc[retired, "FLAG_EMP_PHONE"] == 0).all()
    assert not (df.loc[~retired, "ORGANIZATION_TYPE"] == "XNA").any()
    for c in ("DAYS_BIRTH", "DAYS_REGISTRATION", "DAYS_ID_PUBLISH", "DAYS_LAST_PHONE_CHANGE"):
        assert (df[c].dropna() <= 0).all(), c
    assert df["DAYS_BIRTH"].between(-25_300, -7_400).all()
    floats = [c for c in LAYOUT if c not in STRINGS and c not in INTEGERS]
    for c in floats:  # every value one a float32 holds: the program and the reference bin the same numbers
        x = df[c].dropna().to_numpy()
        assert (x.astype(np.float32).astype(np.float64) == x).all(), c
    for c in ("AMT_INCOME_TOTAL", "AMT_CREDIT", "AMT_ANNUITY", "AMT_GOODS_PRICE"):  # to the cent, then to the float32
        x = df[c].dropna().to_numpy()
        assert (np.round(x, 2).astype(np.float32) == x.astype(np.float32)).all(), c
    assert df["AMT_INCOME_TOTAL"].max() > 2_000_000 and (df["AMT_CREDIT"] * 2 == (df["AMT_CREDIT"] * 2).round()).all()
    for m in HOUSING:
        assert df[m + "_AVG"].dropna().between(0, 1).all()
    assert (df["DEF_30_CNT_SOCIAL_CIRCLE"].dropna() <= df["OBS_30_CNT_SOCIAL_CIRCLE"].dropna()).all()
    assert set(df["FLAG_DOCUMENT_3"]) == {0, 1} and df["FLAG_DOCUMENT_12"].mean() < 1e-3
    # groups without an event, of the size the source has them: 25 of 307,511 hold document 4
    spared = (df["FLAG_DOCUMENT_4"] == 1) | df["NAME_INCOME_TYPE"].isin(["Student", "Businessman"])
    assert df["FLAG_DOCUMENT_4"].sum() == round(40_000 * 25 / 307_511) and spared.sum() >= 5 and df.loc[spared, "TARGET"].sum() == 0
    # the answers span the range a screen cuts in
    args = {"numeric": credit.NUMERIC, "categorical": list(STRINGS), "correlation": ["TARGET", "EXT_SOURCE_2"], "sure_rows": 100}
    iv = check.answers(df, args, "TARGET", 1, 10)["iv"]
    assert iv[["EXT_SOURCE_1", "EXT_SOURCE_2", "EXT_SOURCE_3"]].min() > 0.1 and (iv < 0.02).sum() >= 60


@pytest.mark.parametrize("rows,per_part,parts", [(300, None, [300]), (2000, 700, [700, 700, 600])])
def test_generator_is_a_function_of_rows_and_seed_at_any_size(tmp_path, monkeypatch, rows, per_part, parts):
    if per_part:
        monkeypatch.setattr(credit, "ROWS_PER_PART", per_part)
    frames = []
    for name, seed in (("a", 2**31 + 9), ("b", 2**31 + 9), ("c", 2**31 + 10)):
        credit.generate(str(tmp_path / name), seed, ["parquet"], rows=rows)
        frames.append(pd.read_parquet(tmp_path / name / "parquet"))
    assert frames[0].equals(frames[1]) and not frames[0].equals(frames[2])
    files = sorted(os.listdir(tmp_path / "a" / "parquet"))
    assert [pq.read_metadata(tmp_path / "a" / "parquet" / f).num_rows for f in files] == parts
    assert {c: frames[0][c].nunique() for c in STRINGS} == STRINGS and frames[0].isna().any().sum() == 67
    with pytest.raises(ValueError):
        credit.generate(str(tmp_path / "e"), 1, ["source"], rows=10)


def test_a_program_that_states_no_complete_rows_is_refused_before_any_data(tmp_path, monkeypatch):
    from anovos_tpu.data_analyzer import association_evaluator

    monkeypatch.delattr(association_evaluator, "COMPLETE_ROWS_ROW")
    with pytest.raises(SystemExit, match="COMPLETE_ROWS_ROW"):
        credit.generate(str(tmp_path / "d"), 1, ["parquet"], rows=10)
    assert not os.path.exists(tmp_path / "d")


# ------------------------------------------------------------ the cell ----
def test_the_cell_is_correct_on_the_cpu_and_reports_its_metrics(run, bench):
    assert run["correct"] and run["failed"] == 0 and run["attempted"] == 2
    assert [r["name"] for r in run["checks"] if not r["ok"]] == []
    assert [r["name"] for r in run["checks"]] == ["complete_rows", "correlation_undefined", "correlation", "iv", "ig",
                                                  "varclus_attributes", "files_with_other_bytes"]
    out = bench_run.report(bench, CELL, run, traced=False)
    assert set(out["metrics"]) == {"pass_s", "rows_per_s", "setup_s"} and out["correct"]


def test_every_admitted_per_layer_metric_is_in_the_traced_line(run, bench):
    """PR 41 was refused for one name that its traced line lacked.  Off the chip there is no trace,
    so the metrics read from one are left aside; every other admitted metric has to be in the line."""
    traced = bench_run.report(bench, CELL, dict(run, trace_dir="", traced=run["passes"][-1]), True)["metrics"]
    reporting = {m["name"] for m in bench["end_to_end"] if bench_run._in_cell(m, CELL, set())}
    admitted = [m for m in bench["per_layer"] if bench_run._in_cell(m, CELL, reporting)]
    assert {m["name"] for m in admitted} >= set(READERS) and len(admitted) == 18 + 4
    from_trace = {m["name"] for m in admitted if m["source"] == "device_trace"}
    assert from_trace == {"device_busy_s", "device_idle_share", "idle_unnamed_share", "assoc_device_s", "assoc_group_hbm_pct"}
    host_side = {m["name"] for m in admitted} - from_trace - {"peak_hbm_gb"}  # the CPU backend keeps no peak
    assert host_side <= set(traced), sorted(host_side - set(traced))
    assert not set(traced) - {m["name"] for m in admitted}
    assert traced["assoc_host_rows"]["value"] == 0 and traced["window_compiles"]["value"] == 0
    assert traced["ingest_convert_s"]["value"] == 0.0 and "ingest_encode_s" not in traced
    assert 0 < traced["association_s"]["value"] <= traced["dag_s"]["value"] + 1e-6


def test_a_pass_leaves_the_four_tables_and_the_stage_rows(run, traffic):
    last = run["passes"][-1]
    for rel in traffic["tables"].values():
        assert os.path.exists(os.path.join(last["out_dir"], rel)), rel
    rows = last["manifest"]["phases"]
    nodes = [r["name"] for r in rows if r["parent"] == "dag"]
    assert sorted(nodes) == sorted("association_evaluator/" + n for n in NODES)
    kids = {n: [r["name"] for r in rows if r["parent"] == "association_evaluator/" + n and r["name"].startswith("assoc/")]
            for n in NODES}  # on the suite's mesh a node's table is copied to its device first: a place/d2d row
    assert kids["correlation_matrix"] == ["assoc/corr", "assoc/write"]
    assert kids["variable_clustering"] == ["assoc/prep", "assoc/corr", "assoc/varclus", "assoc/write"]
    # on one chip both measures have the same table, and one computes the counts while the other waits or finds
    # them; on the suite's mesh each node has a copy of its own on its device, and counts for itself
    both = sorted(kids["IV_calculation"] + kids["IG_calculation"])
    assert both in (["assoc/bin", "assoc/group_counts", "assoc/wait", "assoc/write", "assoc/write"],
                    ["assoc/bin", "assoc/group_counts", "assoc/write", "assoc/write"],
                    ["assoc/bin", "assoc/bin", "assoc/group_counts", "assoc/group_counts", "assoc/write", "assoc/write"])
    by_name = {}
    for r in rows:
        by_name.setdefault(r["name"], []).append(r["counts"])
    assert by_name["assoc/bin"][0] == {"columns": 104, "lanes": 128, "rows": PADDED}
    assert by_name["assoc/group_counts"][0] == {"columns": 120, "cells": PADDED * (128 + 16), "block_rows": 2 * PADDED,
                                                "count_lanes": 2 * (16 * 128 + 256 * 16), "fetches": 2, "host_rows": 0}
    corr = by_name["assoc/corr"]
    assert corr[0]["lanes"] == 128 and corr[0]["rows"] == PADDED and 0 < corr[0]["complete_rows"] < ROWS // 10
    assert corr[1]["complete_rows"] == by_name["assoc/prep"][0]["sample_rows"] == ROWS  # under the sample size: all rows, filled
    assert by_name["assoc/varclus"][0]["clusters"] >= 2 and by_name["assoc/varclus"][0]["sample_rows"] == ROWS
    assert [c["rows"] for c in by_name["assoc/write"]].count(120) == 2


def _moved(out_dir, work, name, edit):
    """A copy of a pass's output with one table edited."""
    shutil.rmtree(work, ignore_errors=True)
    shutil.copytree(out_dir, work)
    path = os.path.join(work, "stats", "data_analyzer", "association_evaluator", name, "part-00000.parquet")
    t = pd.read_parquet(path)
    edit(t)
    t.to_parquet(path, index=False)
    return work


def test_each_moved_answer_fails_its_row_and_no_other(run, traffic, config, tmp_path):
    last = run["passes"][-1]["out_dir"]
    with open(os.path.join(os.path.dirname(last), "pipeline.yaml")) as f:
        frames = Frames(yaml.safe_load(f))
    tol, args = config["guarantees"]["tolerances"], traffic["compare"]["association_binned"]
    ref = check.reference(frames, args)
    assert all(r["ok"] for r in check.compare(check.read(last, traffic, args), ref, tol, args))
    assert all(r["ok"] for r in check.compare(ref, ref, tol, args))

    def failing(name, edit):
        rows = check.compare(check.read(_moved(last, str(tmp_path / "m"), name, edit), traffic, args), ref, tol, args)
        return [r["name"] for r in rows if not r["ok"]]

    def iv_up(t):
        t.loc[t["attribute"] == "EXT_SOURCE_2", "iv"] += 0.0003

    def ig_lost(t):
        t.drop(t.index[t["attribute"] == "AMT_CREDIT"], inplace=True)

    def corr_off(t):
        t.loc[t["attribute"] == "AMT_CREDIT", "AMT_GOODS_PRICE"] += 3e-4

    def corr_filled(t):  # a pair that has no correlation given one
        pair = next(k for k, v in ref["correlation"].items() if np.isnan(v)).split("~")
        t.loc[t["attribute"] == pair[0], pair[1]] = 0.0

    def listed_twice(t):
        t.loc[len(t)] = t.loc[0]

    def attribute_lost(t):
        t.drop(t.index[t["Attribute"] == "EXT_SOURCE_2"], inplace=True)

    assert failing("IV_calculation", iv_up) == ["iv"] and failing("IG_calculation", ig_lost) == ["ig"]
    assert failing("correlation_matrix", corr_off) == ["correlation"]
    assert failing("correlation_matrix", corr_filled) == ["correlation_undefined"]
    assert failing("variable_clustering", listed_twice) == ["varclus_attributes"]
    assert failing("variable_clustering", attribute_lost) == ["varclus_attributes"]
    # a manifest whose correlation states no count, or another count
    work = _moved(last, str(tmp_path / "m"), "IV_calculation", lambda t: None)
    path = os.path.join(work, traffic["manifest"])
    for edit in (lambda c: c.pop("complete_rows"), lambda c: c.update(complete_rows=c["complete_rows"] + 1)):
        with open(os.path.join(last, traffic["manifest"])) as f:
            manifest = json.load(f)
        for r in manifest["phases"]:
            if r["name"] == "assoc/corr" and r["parent"] == "association_evaluator/correlation_matrix":
                edit(r["counts"])
        with open(path, "w") as f:
            json.dump(manifest, f)
        rows = check.compare(check.read(work, traffic, args), ref, tol, args)
        assert [r["name"] for r in rows if not r["ok"]] == ["complete_rows"]


@pytest.mark.parametrize("fault", ["bfloat16"] + list(check.FAULTS))
def test_the_control_and_each_named_fault_are_not_correct(config, traffic, tmp_path, fault):
    credit.generate(str(tmp_path / "d"), 2**31 + 7, ["parquet"], rows=20_000)
    frames = _frames(str(tmp_path / "d"))
    tol, args = config["guarantees"]["tolerances"], traffic["compare"]["association_binned"]
    ref = check.reference(frames, args)
    wrong = check.control(ref, frames, args) if fault == "bfloat16" else check.reference(frames, args, fault=fault)
    rows = {r["name"]: r for r in check.compare(wrong, ref, tol, args)}
    failed = {k for k, r in rows.items() if not r["ok"]}
    if fault == "pairwise_correlation":
        assert failed == {"correlation_undefined", "correlation"} and rows["correlation"]["value"] > 100
    elif fault == "bfloat16":  # ties move between bins, and a product of rounded values is off in the third decimal
        assert {"iv", "correlation"} <= failed and rows["correlation"]["value"] > 20 and rows["iv"]["value"] > 10
    elif fault == "no_correction":  # the information gain has no such term
        assert failed == {"iv"} and rows["iv"]["value"] > 10
    else:
        assert failed == {"iv", "ig"} and rows["iv"]["value"] > 10 and rows["ig"]["value"] > 2, (fault, rows)
    assert rows["complete_rows"]["ok"] and rows["varclus_attributes"]["ok"]


def test_the_reference_on_a_table_small_enough_to_do_by_hand():
    x = np.array([1.0, 2, 2, 3, 4, 5, 6, 7, 8, 9, 10, np.nan])
    assert list(check.cutoffs(x, 5)) == [2.0, 4.0, 6.0, 8.0]  # v[(j * 10) // 5] of the 11 values present, sorted
    assert list(check.bins(x, 5)) == [1, 1, 1, 2, 2, 3, 3, 4, 4, 5, 5, 0]  # a value on a cut-off stays under it
    assert list(check.bins(x, 5, strict=True)) == [1, 2, 2, 2, 3, 3, 4, 4, 5, 5, 5, 0]
    assert np.isnan(check.cutoffs(np.array([np.nan, np.nan]), 3)).all()
    flags = np.array([0.0] * 9 + [1.0])
    assert list(check.cutoffs(flags, 10)) == [0.0] * 9 and list(check.bins(flags, 10)) == [1] * 9 + [10]
    # two groups, 4 and 6 rows, 1 and 3 events: by hand
    groups, event = np.array([1] * 4 + [2] * 6), np.array([1, 0, 0, 0, 1, 1, 1, 0, 0, 0], float)
    non, ev = check.group_counts(groups, event, np.ones(10, bool))
    assert list(non) == [3, 3] and list(ev) == [1, 3]
    want = (3 / 6 - 1 / 4) * np.log((3 / 6) / (1 / 4)) + (3 / 6 - 3 / 4) * np.log((3 / 6) / (3 / 4))
    assert check.information_value(non, ev) == pytest.approx(want)
    h = lambda p: -(p * np.log2(p) + (1 - p) * np.log2(1 - p))  # noqa: E731
    assert check.information_gain(non, ev, 0.4) == pytest.approx(h(0.4) - 0.4 * h(0.25) - 0.6 * h(0.5))
    # a group without an event takes the half-row correction, and is left out by the fault
    non, ev = np.array([5.0, 5.0]), np.array([0.0, 2.0])
    want = (0.5 - 0.0) * np.log((5.5 / 10) / (0.5 / 2)) + (0.5 - 1.0) * np.log(0.5 / 1.0)
    assert check.information_value(non, ev) == pytest.approx(want)
    assert check.information_value(non, ev, correction=False) == pytest.approx((0.5 - 1.0) * np.log(0.5 / 1.0))
    assert np.isnan(check.information_value(np.array([3.0]), np.array([0.0])))
    block = np.array([[1.0, 2, 5], [2, 4, 5], [3, 7, 5], [np.nan, 1, 5], [4, 8, np.nan]])
    corr, complete = check.complete_case_correlation(block)
    assert complete == 3 and corr[0, 1] == pytest.approx(np.corrcoef([1, 2, 3], [2, 4, 7])[0, 1])
    assert np.isnan(corr[0, 2]) and np.isnan(corr[2, 2])  # constant over the complete rows
    assert check.pairwise_correlation(block)[0, 1] == pytest.approx(np.corrcoef([1, 2, 3, 4], [2, 4, 7, 8])[0, 1])
    assert check.stored(pd.Series([0.1, None])).tolist()[0] == float(np.float32(0.1))


# -------------------------------------------------- the four new readers ----
def _row(name, parent, start, end, **counts):
    return {"name": name, "parent": parent, "start_s": start, "end_s": end, "thread": "t", "counts": counts}


IV, IG, CM, VC = ("association_evaluator/" + n for n in ("IV_calculation", "IG_calculation", "correlation_matrix", "variable_clustering"))
RECORDED = [  # a pass as the program records it: the read 0-1 s, the four nodes side by side 1.0-4.0 s
    _row("run", None, 0.0, 4.2), _row("ingest", "run", 0.0, 1.0), _row("dag", "run", 1.0, 4.1),
    _row(CM, "dag", 1.0, 1.2), _row("assoc/corr", CM, 1.0, 1.1, lanes=128, rows=393_216, complete_rows=9_500),
    _row("assoc/write", CM, 1.1, 1.2, rows=105),
    _row(IV, "dag", 1.05, 1.6), _row("assoc/bin", IV, 1.05, 1.3, columns=104, lanes=128, rows=393_216),
    _row("assoc/group_counts", IV, 1.3, 1.5, columns=120, cells=393_216 * 144, block_rows=2 * 393_216,
         count_lanes=2 * (16 * 128 + 256 * 16), fetches=2, host_rows=0),
    _row("assoc/write", IV, 1.5, 1.6, rows=120),
    _row(IG, "dag", 1.06, 1.65), _row("assoc/wait", IG, 1.06, 1.5), _row("assoc/write", IG, 1.6, 1.65, rows=120),
    _row(VC, "dag", 1.1, 4.0), _row("assoc/prep", VC, 1.1, 2.0, columns=120, sample_rows=100_257, kept=119),
    _row("assoc/corr", VC, 2.0, 2.1, lanes=128, rows=131_072, complete_rows=100_257),
    _row("assoc/varclus", VC, 2.1, 3.9, columns=119, sample_rows=100_257, clusters=46), _row("assoc/write", VC, 3.9, 4.0, rows=119),
]


def _pass(rows, wall=4.2):
    return {"wall_s": wall, "manifest": {"phases": rows}}


def test_span_and_counter_readers_on_a_recorded_manifest():
    seconds = load_module("layer_metrics", "association_s")
    host_rows = load_module("layer_metrics", "assoc_host_rows").read
    run = {"passes": [_pass(RECORDED)]}
    assert seconds.read(run) == pytest.approx(3.0) and host_rows(run) == 0  # the union 1.0-4.0, not the nodes' 4.24 summed
    assert seconds.covered([_row("a", "dag", 0.0, 1.0), _row("b", "dag", 2.0, 2.5), _row("c", "dag", 2.2, 3.0)]) == pytest.approx(2.0)
    fetched = [dict(r, counts=dict(r["counts"], host_rows=2 * 3 * 393_216, fetches=8)) if r["name"] == "assoc/group_counts" else r
               for r in RECORDED]
    assert host_rows({"passes": [_pass(fetched)]}) == 6 * 393_216
    # the parent: the four nodes are there, none has a stage row
    before = [r for r in RECORDED if not r["name"].startswith("assoc/")]
    assert seconds.read({"passes": [_pass(before)]}) == pytest.approx(3.0) and host_rows({"passes": [_pass(before)]}) is None
    # a count outside the block's nodes is not the block's
    stray = before + [_row("assoc/group_counts", "transformers/x", 0.2, 0.3, host_rows=7, fetches=1)]
    assert host_rows({"passes": [_pass(stray)]}) is None
    stats = [_row("run", None, 0.0, 1.0), _row("dag", "run", 0.5, 0.8), _row("stats_generator/measures_of_counts", "dag", 0.5, 0.8)]
    for rows in (stats, [], [_row("run", None, 0.0, 1.0)]):
        assert seconds.read({"passes": [_pass(rows)]}) is None and host_rows({"passes": [_pass(rows)]}) is None
    assert seconds.read({"passes": []}) is None and host_rows({"passes": []}) is None
    # the comparison reads the matrix's count, and no other
    assert check.complete_rows_stated({"phases": RECORDED}) == 9_500 and check.complete_rows_stated({"phases": before}) is None


def test_device_readers_on_a_hand_built_event_list(monkeypatch):
    """Chip 0: the cut-offs' sort 1.0-1.2 s, the bins 1.2-1.25, the group counts' ``while`` 1.3-1.5 with a
    fusion 1.32-1.4 inside it and a copy without a name in the same program 1.5-1.52, two correlations,
    and the inspection's calendar program, which is another reader's.  Chip 1: the sort alone."""
    device_s = load_module("layer_metrics", "assoc_device_s")
    cut, app, grp, cor = device_s.SCOPES
    devices = {
        "/device:TPU:0": [(1.0, 1.2, cut), (1.2, 1.25, app), (1.3, 1.5, grp), (1.32, 1.4, grp), (1.5, 1.52, grp),
                          (0.5, 0.51, cor), (2.0, 2.03, cor), (5.0, 5.9, ""), (6.0, 6.5, "ts/calendar_counts")],
        "/device:TPU:1": [(1.0, 1.2, cut)],
    }
    mod = device_s._reduction()
    assert mod.SCOPES == device_s.SCOPES and load_module("layer_metrics", "ts_device_s").SCOPES[0] == "ts/calendar_counts"
    assert mod.scope_seconds(devices) == {cut: pytest.approx(0.2), app: pytest.approx(0.025), grp: pytest.approx(0.11),
                                          cor: pytest.approx(0.02)}
    assert mod._scope("jit(_group_counts_program)/jit(main)/assoc/group_counts/jit(_block_label_counts_p)/vmap()/while/body/dot_general:") == grp
    assert mod._scope("jit(_masked_quantiles)/assoc/cutoffs/sort:") == cut and mod._scope("jit(f)/noassoc/corr_x/add:") is None
    assert device_s.read({"trace_dir": ""}) is None and device_s.read({}) is None
    assert device_s.read({"assoc_scope_seconds": {cut: 0.2, grp: 0.11}}) == pytest.approx(0.31)
    hbm = load_module("layer_metrics", "assoc_group_hbm_pct")
    by_hand = 5 * 393_216 * 144 + 5 * 2 * 393_216 + 4 * 2 * (16 * 128 + 256 * 16)
    assert hbm.group_count_bytes(393_216 * 144, 2 * 393_216, 2 * (16 * 128 + 256 * 16)) == by_hand == hbm.stage_bytes(RECORDED)
    assert hbm.stage_bytes([r for r in RECORDED if r["name"] != "assoc/group_counts"]) == 0
    assert hbm.share_pct(819e9 * 0.01, 2.0, 819e9) == pytest.approx(0.5)
    # no trace, no scope, no counts, or a device the peaks do not know (the CPU): nothing, and no error
    assert hbm.read({"trace_dir": "", "traced": _pass(RECORDED)}) is None
    assert hbm.read({"assoc_scope_seconds": {cut: 0.2}, "traced": _pass(RECORDED)}) is None
    assert hbm.read({"assoc_scope_seconds": {grp: 0.11}, "traced": _pass([])}) is None
    assert hbm.read({"assoc_scope_seconds": {grp: 0.11}, "traced": _pass(RECORDED)}) is None
    import jax

    class V5e:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a: [V5e()])
    monkeypatch.setattr(jax, "device_count", lambda *a: 1)
    share = hbm.read({"assoc_scope_seconds": {grp: 0.11}, "traced": _pass(RECORDED)})
    assert share == pytest.approx(100.0 * by_hand / (0.11 * 819e9)) and share < 100


def test_the_scope_reader_finds_nothing_in_a_trace_from_before_the_scopes():
    device_s = load_module("layer_metrics", "assoc_device_s")
    mod = device_s._reduction()
    for name in ("tiny_v5e.xplane.pb", "ts_tiny_v5e.xplane.pb"):  # PR 24's and PR 39's recordings: no assoc/* scope
        events = mod.device_events(os.path.join(ROOT, "tests", "benchmark", "recorded", name))
        assert list(events) == ["/device:TPU:0"] and mod.scope_seconds(events) == {}


def test_readers_on_the_live_run(run):
    rows = run["passes"][-1]["manifest"]["phases"]
    nodes = [r for r in rows if r["parent"] == "dag"]
    one = dict(run, passes=run["passes"][-1:])
    span = load_module("layer_metrics", "association_s").read(one)
    assert max(r["end_s"] - r["start_s"] for r in nodes) <= span + 1e-9 <= sum(r["end_s"] - r["start_s"] for r in nodes) + 2e-9
    assert load_module("layer_metrics", "assoc_host_rows").read(one) == 0
    hbm = load_module("layer_metrics", "assoc_group_hbm_pct")
    computed = sum(r["name"] == "assoc/group_counts" for r in rows)  # once on one chip; on the suite's mesh IV and IG each
    assert computed in (1, 2)
    assert hbm.stage_bytes(rows) == computed * hbm.group_count_bytes(PADDED * 144, 2 * PADDED, 2 * (16 * 128 + 256 * 16))
