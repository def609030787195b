"""CPU rehearsal of the cell ``lending_club.vintage_drift`` at 2,000 rows in
the newest vintage (8,961 in the nine tables of a pass): the pipeline driver is
``correct`` against the plain reference, the bfloat16 control, each of the
three named faults and each moved answer are not; the configuration states its
source, every assumption and what is cut; the generator keeps the source's 151
columns in order, name and type, the class of every string's cardinality and
the null structure by vintage, and is a function of ``(rows, seed)``; every
per-layer metric that ``run._in_cell`` admits to the cell is in the traced
line; and the five readers the cell brings on what such a pass left and on
hand-built rows.  One file, one process, no child."""

import json
import os
import sys

import numpy as np
import pandas as pd
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
CELL = "lending_club.vintage_drift"
READERS = ("drift_s", "drift_read_s", "drift_host_values", "drift_device_s", "drift_hist_hbm_pct")
NODES = ("drift_detector/drift_statistics", "drift_detector/stability_index")
SEED = 2**31 + 53

club = load_module("datasets", "lending_club")
check = load_module("checks", "vintage_drift")


def _json(*rel):
    with open(os.path.join(ROOT, *rel)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    return _json("BENCHMARK.json")


@pytest.fixture(scope="module")
def config():
    return _json("benchmark", "configs", "lending_club.json")


@pytest.fixture(scope="module")
def traffic():
    return _json("benchmark", "traffic", "vintage_drift.json")


@pytest.fixture(scope="module")
def run(config, traffic, tmp_path_factory):
    """One run of the driver as run.py would start it, at 2,000 rows in the 2018 vintage, on the CPU."""
    held = club.vintage_rows(ROWS)
    return pipeline.run({
        "workload": CELL, "config": dict(config, rows=ROWS, baseline_rows=held[2015]), "traffic": traffic,
        "traffic_yaml": os.path.join(ROOT, "benchmark", "traffic", "vintage_drift.yaml"),
        "work_dir": str(tmp_path_factory.mktemp("lending_club")), "seed": SEED, "seconds": 0.0,
        "trace": False, "platform": "cpu", "t_start": bench_run.T_START, "say": lambda msg: None,
    })


@pytest.fixture(scope="module")
def vintages(tmp_path_factory):
    """The generator's seven vintages where the newest holds 20,000 rows."""
    dest = str(tmp_path_factory.mktemp("club") / "d")
    club.generate(dest, SEED + 1, ["parquet", "source", "stability_index"], rows=20_000)
    return dest, {y: pd.read_parquet(os.path.join(dest, "stability_index", str(i))) for i, y in enumerate(club.YEARS)}


def _frames(data_dir):
    with open(os.path.join(ROOT, "benchmark", "traffic", "vintage_drift.yaml")) as f:
        return Frames(pipeline._rebase(yaml.safe_load(f), "DATASET/", data_dir + "/"))


# ------------------------------------------------------- the data files ----
def test_the_configuration_states_its_source_what_is_cut_and_every_assumption(bench, config, traffic):
    entry = next(c for c in bench["configs"] if c["name"] == "lending_club")  # by name, not by position
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["file"] == "benchmark/configs/lending_club.json" and entry["reduced"] == config["reduced"]
    assert entry["reduced"] in ([], ["rows"])
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    for what in ("Lending Club", "wordsforthewise/lending-club", "accepted_2007_to_2018Q4.csv", "LCDataDictionary.xlsx",
                 "2,260,701 x 151", "drift_detector"):
        assert what in entry["source"], what
    assert cell == {"name": CELL, "config": "lending_club", "traffic": "vintage_drift", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "nine tables" in cell["why"]
    assert config["chips"] == 1 and config["driver"] == "pipeline" and config["columns"] == 151
    pub = config["published"]
    assert pub["rows"] == 2_260_701 == pub["loans"] + pub["footer_lines"] and pub["columns"] == 151
    assert pub["layout"].split(", ") == club.COLUMNS and pub["strings"].split(", ") == club.STRINGS
    assert {int(y): n for y, n in pub["loans_by_year_of_issue_d"].items()} == club.PUBLISHED_ROWS
    held = {int(y): n for y, n in config["vintages_held"].items()}
    assert held == club.vintage_rows(config["rows"]) and config["baseline_rows"] == held[2015]
    j = 1 if config["reduced"] else 0  # every vintage alike at ceil(n / 2^j)
    assert held == {y: -(-club.PUBLISHED_ROWS[y] // 2**j) for y in club.YEARS}
    for y in club.YEARS:  # the published counts stand beside the held ones
        assert f"{club.PUBLISHED_ROWS[y]:,}" in config["reduced_why"] and f"{held[y]:,}" in config["reduced_why"], y
    told = " ".join(config["assumed"])
    for what in ("parquet", "every distribution", "GRADE_MIX", "EMP_TITLE_LAW", "issue_d", "float32", "random streams",
                 "2007-2011", "footer", "512,000", "behind a letter", "month columns", "some()"):
        assert what in told, what
    g = config["guarantees"]
    assert set(g["tolerances"]) == {"psi", "hd", "jsd", "ks", "si_mean", "si_stddev", "si_kurtosis", "cv",
                                    "stability_index"}
    for name in list(g["tolerances"]) + ["bfloat16"] + list(check.FAULTS):
        assert name in g["tolerances_why"], name
    assert traffic["compare"]["vintage_drift"]["drop_cols"] == ["id", "url", "desc"]
    assert traffic["dataset_parts"] == ["parquet", "source", "stability_index"]


def test_the_generator_keeps_the_151_columns_their_order_and_their_types(vintages):
    dest, by_year = vintages
    schema = pq.read_table(os.path.join(dest, "parquet")).schema
    assert schema.names == club.COLUMNS and len(club.COLUMNS) == 151
    strings = [f.name for f in schema if str(f.type) in ("string", "large_string")]
    assert strings == [c for c in club.COLUMNS if c in club.STRINGS] and len(strings) == 38
    assert all(str(schema.field(c).type) == "double" for c in club.NUMERIC) and len(club.NUMERIC) == 113
    assert "member_id" in club.NUMERIC and "id" in club.STRINGS
    for df in by_year.values():
        assert list(df.columns) == club.COLUMNS
        assert df["id"].str.fullmatch(r"L\d+").all() and df["id"].is_unique  # digits behind a letter: it stays a string
        assert df["member_id"].isna().all() and (df["policy_code"] == 1.0).all()
        num = df[club.NUMERIC].to_numpy(float)
        assert np.array_equal(num, num.astype(np.float32).astype(np.float64), equal_nan=True)  # what an f32 holds


def test_the_vintages_keep_the_sources_proportions_and_the_parts_hold_their_vintage(vintages):
    dest, by_year = vintages
    held = club.vintage_rows(20_000)
    assert {y: len(df) for y, df in by_year.items()} == held
    for y in club.YEARS:
        assert abs(held[y] / held[2018] - club.PUBLISHED_ROWS[y] / club.PUBLISHED_ROWS[2018]) < 1e-4
        assert set(by_year[y]["issue_d"].str[-4:]) == {str(y)}
    assert pd.read_parquet(os.path.join(dest, "parquet")).equals(by_year[2018])
    assert pd.read_parquet(os.path.join(dest, "source")).equals(by_year[2015])
    assert not set(by_year[2015]["issue_d"]) & set(by_year[2018]["issue_d"])  # no month in common: the largest PSI
    assert club.vintage_rows(495_242) == {y: club.PUBLISHED_ROWS[y] for y in club.YEARS}
    assert sum(club.vintage_rows(495_242).values()) == 2_218_133 and sum(club.vintage_rows(247_621).values()) == 1_109_069


CLOSED = {"term": 2, "grade": 7, "sub_grade": 35, "emp_length": 11, "home_ownership": 6, "verification_status": 3,
          "purpose": 14, "addr_state": 51, "initial_list_status": 2, "application_type": 2, "pymnt_plan": 2,
          "hardship_flag": 2, "debt_settlement_flag": 2}


def test_every_string_keeps_the_class_of_its_cardinality(vintages):
    _, by_year = vintages
    new, old = by_year[2018], by_year[2015]
    for c, n in CLOSED.items():  # a closed vocabulary shows every value in every vintage
        assert new[c].nunique() == n and old[c].nunique() == n, c
    assert new["id"].nunique() == len(new) == new["url"].nunique()
    assert new["issue_d"].nunique() == 12 and new["loan_status"].nunique() == 7
    assert new["verification_status_joint"].nunique() == 3 and new["disbursement_method"].nunique() == 2
    assert old["disbursement_method"].nunique() == 1 and new["hardship_type"].nunique() == 1
    assert 5 <= new["hardship_reason"].nunique() <= 9 and new["hardship_loan_status"].nunique() <= 5
    assert 700 < new["zip_code"].nunique() <= 956 and new["zip_code"].str.fullmatch(r"\d{3}xx").all()
    assert 400 < new["earliest_cr_line"].nunique() <= 792 and 100 < new["sec_app_earliest_cr_line"].nunique() <= 660
    for c in ("last_pymnt_d", "last_credit_pull_d", "next_pymnt_d", "hardship_start_date", "settlement_date"):
        assert new[c].nunique() <= 140, c  # the months of the vintage's own life
    # free text by a Zipf law over one universe: a third and more of the rows are distinct values, the frequent ones
    # are shared by two vintages and most of each one's values are absent from the other
    a, b = set(new["emp_title"].dropna()), set(old["emp_title"].dropna())
    assert 0.3 * len(new) < len(a) < 0.6 * len(new) and 0.05 < len(a & b) / len(a) < 0.5
    top = new["emp_title"].value_counts(normalize=True)
    assert list(top.index[:3]) == ["Teacher", "Manager", "Owner"] and 0.01 < top.iloc[0] < 0.025
    assert 0.05 < new["emp_title"].isna().mean() < 0.1
    assert by_year[2012]["title"].nunique() > 20 * new["title"].nunique() / 10 and new["title"].nunique() >= 14
    assert by_year[2012]["desc"].notna().mean() > 0.3 and new["desc"].isna().all()
    assert by_year[2012]["desc"].dropna().is_unique


def test_the_null_structure_follows_the_vintage(vintages):
    _, by_year = vintages
    for y, df in by_year.items():
        filled = df.notna().mean()
        bureau = filled[club.BUREAU_2015_12]
        assert (bureau == 0).all() if y <= 2014 else (bureau.drop(["mths_since_rcnt_il", "il_util"]) == 1).all() \
            if y >= 2016 else ((bureau > 0.01) & (bureau < 0.1)).all(), y
        early = filled[club.BUREAU_2012_07].drop(["mths_since_recent_bc_dlq", "mths_since_recent_revol_delinq"])
        assert ((early > 0.4) & (early < 0.8)).all() if y == 2012 else (early > 0.85).all(), y
        joint, second = filled[club.JOINT], filled[club.SECOND_APPLICANT]
        assert (joint == 0).all() if y <= 2014 else (joint > 0).all() and (joint < 0.16).all(), y
        assert (second == 0).all() if y <= 2016 else (second > 0).all() and (second < 0.16).all(), y
        assert (filled[club.HARDSHIP] < 0.02).all() and (filled[club.SETTLEMENT] < 0.03).all(), y
        assert filled["next_pymnt_d"] == (0 if y <= 2013 else filled["next_pymnt_d"]) and filled["member_id"] == 0
    assert 0.0005 < by_year[2015][club.JOINT[0]].notna().mean() < 0.002
    assert 0.12 < by_year[2018][club.JOINT[0]].notna().mean() < 0.16
    whole = pd.concat(by_year.values())
    for c, rate in (("mths_since_last_record", 0.84), ("mths_since_recent_bc_dlq", 0.77),
                    ("mths_since_last_major_derog", 0.74), ("mths_since_last_delinq", 0.51)):
        assert abs(whole[c].isna().mean() - rate) < 0.03, c


def test_the_same_seed_gives_the_same_bytes_and_another_seed_other_values(tmp_path):
    digests = []
    for name, seed in (("a", 5), ("b", 5), ("c", 6)):
        club.generate(str(tmp_path / name), seed, ["parquet", "source"], rows=1_500)
        digests.append({rel: open(tmp_path / name / rel / "part-00000.parquet", "rb").read()
                        for rel in ("parquet", "source")})
    assert digests[0] == digests[1] and digests[0]["parquet"] != digests[2]["parquet"]
    assert sorted(os.listdir(tmp_path / "a")) == ["parquet", "source"]
    with pytest.raises(ValueError, match="unknown dataset parts"):
        club.generate(str(tmp_path / "x"), 5, ["dictionaries"], rows=100)


# ------------------------------------------------------------- the cell ----
def test_the_cell_is_correct_on_the_cpu_and_every_check_is_beside_its_limit(run):
    assert run["correct"] and run["failed"] == 0 and run["attempted"] == 2
    rows = {r["name"]: r for r in run["checks"]}
    assert set(rows) == {"drift_attributes", "psi", "hd", "jsd", "ks", "drift_flagged", "si_attributes", "si_undefined",
                         "si_mean", "si_stddev", "si_kurtosis", "stability_attributes", "cv_undefined", "mean_stddev",
                         "mean_cv", "stddev_cv", "kurtosis_cv", "stability_scores", "stability_index",
                         "stability_flagged", "rows_stated", "files_with_other_bytes"}
    assert all(r["ok"] for r in rows.values())
    assert "8 of 8 reads" in rows["rows_stated"]["detail"]
    assert {"pass_s", "setup_s", "rows_per_s", "fresh_pass_s"} <= set(run["metrics"])


def test_a_pass_leaves_both_tables_the_history_the_model_and_the_stage_rows(run, traffic):
    last = run["passes"][-1]
    held = club.vintage_rows(ROWS)
    for rel in traffic["tables"].values():
        assert os.path.exists(os.path.join(last["out_dir"], rel)), rel
    drift = check.read(last["out_dir"], traffic, traffic["compare"]["vintage_drift"])
    empty_in_2015 = {"member_id"} | {c for c in club.SECOND_APPLICANT if c != "sec_app_earliest_cr_line"}
    assert set(drift["distances"].index) == set(club.COLUMNS) - {"id", "url", "desc"} - empty_in_2015
    assert len(drift["distances"]) == 135 and 20 < drift["flagged"].sum() < 100  # flagged and not, both
    assert drift["distances"].loc["issue_d", "PSI"] > 10 and drift["flagged"]["sec_app_earliest_cr_line"] == 1
    assert list(drift["si"].index) == club.NUMERIC and len(drift["moments"]) == 7 * 113
    assert drift["moments"].loc["3:open_acc_6m"].isna().all() and drift["moments"].loc["5:open_acc_6m"].notna().all()
    assert drift["si"].loc["member_id"].isna().all() and drift["si_flagged"]["member_id"] == 1
    model = os.path.join(last["out_dir"], "intermediate_data", "drift_statistics", "frequency_counts")
    assert len(os.listdir(model)) == 135
    rows = last["manifest"]["phases"]
    assert sorted(r["name"] for r in rows if r["parent"] == "dag") == sorted(NODES)
    reads = [r for r in rows if r["name"] in ("drift/read", "stability/read")]
    assert [r["parent"] for r in reads].count(NODES[0]) == 1 and [r["parent"] for r in reads].count(NODES[1]) == 7
    assert sorted(r["counts"]["rows"] for r in reads) == sorted([held[2015]] + list(held.values()))
    assert all(r["counts"]["columns"] == 151 and r["counts"]["bytes"] > 0 for r in reads)
    for r in reads:  # the same children an ingest has
        kids = {k["name"] for k in rows if k["parent"] == r["name"] and r["start_s"] <= k["start_s"] <= r["end_s"]}
        assert "io:read_dataset" in kids, kids
    names = {r["name"] for r in rows}
    assert {"drift/fit", "drift/union", "drift/lut", "drift/sides", "drift/model", "drift/frame", "stability/moments",
            "stability/frame", "ingest/decode", "ingest/assemble", "ingest/h2d"} <= names
    by_name = {r["name"]: r["counts"] for r in rows}
    assert by_name["drift/union"]["values"] > 0 and by_name["drift/lut"]["values"] > by_name["drift/union"]["values"]
    assert by_name["drift/model"]["values"] > 100 * 10 and by_name["drift/sides"]["cols"] in (135, 148)  # 148 on one chip: the empty ones leave after the fetch


@pytest.mark.parametrize("fault", ["bfloat16"] + list(check.FAULTS))
def test_the_control_and_each_named_fault_are_not_correct(config, traffic, tmp_path, fault):
    club.generate(str(tmp_path / "d"), SEED + 7, ["parquet", "source", "stability_index"], rows=6_000)
    frames = _frames(str(tmp_path / "d"))
    tol, args = config["guarantees"]["tolerances"], traffic["compare"]["vintage_drift"]
    ref = check.reference(frames, args)
    assert all(r["ok"] for r in check.compare(ref, ref, tol, args))
    wrong = check.control(ref, frames, args) if fault == "bfloat16" else check.reference(frames, args, fault=fault)
    rows = {r["name"]: r for r in check.compare(wrong, ref, tol, args)}
    failed = {k for k, r in rows.items() if not r["ok"]}
    if fault == "bfloat16":  # values cross the cut-offs, and a mean of rounded values is off in the third digit
        assert {"psi", "hd", "jsd", "ks", "si_mean", "si_stddev", "si_kurtosis"} <= failed
        assert rows["psi"]["value"] > 10 and rows["si_mean"]["value"] > 10
    elif fault == "valid_denominator":  # every column with nulls moves, the half-empty ones by tenths
        assert {"psi", "hd", "jsd", "ks", "drift_flagged"} <= failed and rows["psi"]["value"] > 1_000
    elif fault == "left_closed":  # the whole numbers that lie on a cut-off change their bin
        assert {"psi", "hd", "jsd", "ks"} <= failed and rows["psi"]["value"] > 100
    else:  # the target's own values are left out of the sums
        assert {"psi", "hd", "jsd"} <= failed and rows["psi"]["value"] > 1_000
    assert rows["rows_stated"]["ok"] and rows["si_attributes"]["ok"]
    if fault != "bfloat16":
        assert not failed & {"si_mean", "si_stddev", "si_kurtosis", "mean_cv", "stability_scores"}


def test_an_answer_that_moves_is_not_correct(run, config, traffic, tmp_path):
    import shutil

    last, args, tol = run["passes"][-1]["out_dir"], traffic["compare"]["vintage_drift"], config["guarantees"]["tolerances"]
    frames = _frames(os.path.join(os.path.dirname(last), "dataset"))
    ref = check.reference(frames, args)

    def failing(table, edit):
        work = str(tmp_path / "moved")
        shutil.rmtree(work, ignore_errors=True)
        shutil.copytree(last, work)
        path = os.path.join(work, traffic["tables"][table])
        t = pd.read_parquet(path) if path.endswith(".parquet") else pd.read_csv(path)
        t = edit(t)
        t.to_parquet(path) if path.endswith(".parquet") else t.to_csv(path, index=False)
        return [r["name"] for r in check.compare(check.read(work, traffic, args), ref, tol, args) if not r["ok"]]

    def moved(column, by, where=0):
        def edit(t):
            t.loc[t.index[where], column] += by
            return t
        return edit

    assert failing("drift_statistics", lambda t: t) == []
    assert failing("drift_statistics", moved("PSI", 3e-4)) == ["psi"]
    assert failing("drift_statistics", moved("KS", -3e-4, 5)) == ["ks"]
    assert failing("drift_statistics", lambda t: t.assign(flagged=1 - t["flagged"])) == ["drift_flagged"]
    assert failing("drift_statistics", lambda t: t.iloc[1:]) == ["drift_attributes", "psi", "hd", "jsd", "ks", "drift_flagged"]
    assert failing("stabilityIndex_metrics", lambda t: t.assign(mean=t["mean"] * np.where(t.index == 3, 1.001, 1.0))) == ["si_mean"]
    assert failing("stabilityIndex_metrics", lambda t: t[t["attribute"] != "dti"])[:2] == ["si_attributes", "si_undefined"]
    assert failing("stability_index", moved("mean_cv", 0.01, 2)) == ["mean_cv"]
    assert failing("stability_index", lambda t: t.assign(flagged=1 - t["flagged"])) == ["stability_flagged"]
    assert failing("stability_index", moved("stddev_si", 1.0, 2)) == ["stability_scores"]


def test_the_reference_on_tables_small_enough_to_do_by_hand():
    src = pd.Series([0.0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, np.nan])  # cut-offs 1, 2, ... 9: 0 and 1 share the first bin
    tgt = pd.Series([1.0, 1, 2, 10, 11, np.nan, np.nan, np.nan])
    p, q = check.frequencies(src, tgt, 10)
    assert list(p * 12) == [2, 1, 1, 1, 1, 1, 1, 1, 1, 1] and list(q * 8) == [2, 1, 0, 0, 0, 0, 0, 0, 0, 2]
    p, q = check.frequencies(src, tgt, 10, fault="left_closed")
    assert list(p * 12) == [1, 1, 1, 1, 1, 1, 1, 1, 1, 2] and list(q * 8) == [0, 2, 1, 0, 0, 0, 0, 0, 0, 2]
    p, q = check.frequencies(src, tgt, 10, fault="valid_denominator")
    assert p.sum() == pytest.approx(1.0) and q.sum() == pytest.approx(1.0)
    assert check.frequencies(pd.Series([np.nan, np.nan]), tgt, 10) is None
    a, b = pd.Series(["x", "y", "y", None]), pd.Series(["y", "z", None, None, None])
    p, q = check.frequencies(a, b, 10)
    assert list(p) == [0.25, 0.5, 0.0] and list(q) == [0.0, 0.2, 0.2]  # x, y, z over all rows
    p, q = check.frequencies(a, b, 10, fault="source_keys_only")
    assert list(p) == [0.25, 0.5] and list(q) == [0.0, 0.2]
    d = check.distances(np.array([0.5, 0.5, 0.0]), np.array([0.5, 0.0, 0.5]))
    assert d["PSI"] == pytest.approx(2 * (0.5 - 1e-4) * np.log(0.5 / 1e-4)) and d["KS"] == pytest.approx(0.5 - 1e-4)
    assert d["HD"] == pytest.approx(np.sqrt((np.sqrt(0.5) - 0.01) ** 2))
    assert [check.score(v) for v in (0.0, 0.0299, 0.03, -0.15, 0.49, 0.5, 7.0)] == [4, 4, 3, 2, 1, 0, 0]
    assert np.isnan(check.score(np.nan))
    mom = pd.DataFrame({"mean": [10.0, 11.0, np.nan], "stddev": [1.0, 1.0, np.nan], "kurtosis": [3.0, np.nan, np.nan]},
                       index=["1:a", "2:a", "3:a"])
    si, flagged = check.stability(mom, {"mean": 0.5, "stddev": 0.3, "kurtosis": 0.2}, 2)
    assert si.loc["a", "mean_cv"] == pytest.approx(np.sqrt(0.5) / 10.5) and si.loc["a", "mean_si"] == 3
    assert si.loc["a", "stddev_si"] == 4 and np.isnan(si.loc["a", "kurtosis_cv"]) and flagged["a"] == 1


# ------------------------------------------------- the per-layer metrics ----
def test_every_admitted_per_layer_metric_is_in_the_traced_line(run, bench):
    """PR 41 was refused for one name that its traced line lacked.  Off the chip there is no trace,
    so the metrics read from one are left aside; every other admitted metric has to be in the line."""
    traced = bench_run.report(bench, CELL, dict(run, trace_dir="", traced=run["passes"][-1]), True)["metrics"]
    reporting = {m["name"] for m in bench["end_to_end"] if bench_run._in_cell(m, CELL, set())}
    admitted = {m["name"]: m for m in bench["per_layer"] if bench_run._in_cell(m, CELL, reporting)}
    assert set(READERS) <= set(admitted)
    for name in READERS:  # by name, never by position or by count
        m = admitted[name]
        assert m["moves"] == "pass_s" and m["workloads"] == ["income_32k.full", CELL], name
    from_trace = {n for n, m in admitted.items() if m["source"] == "device_trace"}
    assert {"drift_device_s", "drift_hist_hbm_pct"} <= from_trace
    host_side = set(admitted) - from_trace - {"peak_hbm_gb"}  # the CPU backend keeps no peak
    assert host_side <= set(traced), sorted(host_side - set(traced))
    assert not set(traced) - set(admitted)
    assert traced["window_compiles"]["value"] == 0
    assert 0 < traced["drift_read_s"]["value"] < traced["drift_s"]["value"] <= traced["dag_s"]["value"] + 1e-6
    rows = run["passes"][-1]["manifest"]["phases"]
    values = sum(r["counts"]["values"] for r in rows if r["name"] in ("drift/union", "drift/lut", "drift/model"))
    assert traced["drift_host_values"]["value"] == values > 0


def test_the_readers_on_hand_built_rows_and_on_a_program_without_them():
    def row(name, parent, start, end, **counts):
        return {"name": name, "parent": parent, "start_s": start, "end_s": end, "thread": "t", "counts": counts}

    rows = [row("dag", "run", 1.0, 9.0), row(NODES[0], "dag", 1.0, 6.0), row(NODES[1], "dag", 2.0, 8.0),
            row("drift/read", NODES[0], 1.0, 3.0, rows=5), row("stability/read", NODES[1], 2.0, 4.0, rows=5),
            row("stability/read", NODES[1], 5.0, 5.5, rows=5), row("stability/read", "elsewhere", 8.0, 9.0),
            row("drift/union", NODES[0], 3.0, 3.1, values=7), row("drift/lut", NODES[0], 3.1, 3.2, values=11),
            row("drift/model", NODES[0], 5.0, 5.5, values=13, cols=3),
            row("drift/sides", NODES[0], 3.2, 5.0, rows=8, cols=3, cells=1000, cutoffs=18, hist_lanes=40)]
    run = {"passes": [{"wall_s": 1.0, "manifest": {"phases": rows}}], "traced": {"manifest": {"phases": rows}}}
    read = {name: load_module("layer_metrics", name).read for name in READERS}
    assert read["drift_s"](run) == pytest.approx(7.0)  # the union of 1-6 and 2-8
    assert read["drift_read_s"](run) == pytest.approx(3.5)  # 1-4 and 5-5.5; the row outside the nodes is none of them
    assert read["drift_host_values"](run) == 31
    hbm = load_module("layer_metrics", "drift_hist_hbm_pct")
    assert hbm.stage_bytes(rows) == 5 * 1000 + 4 * 18 + 4 * 40 == hbm.side_bytes(1000, 18, 40)
    assert hbm.share_pct(819e9 * 0.5, 1.0, 819e9) == pytest.approx(50.0) and hbm.share_pct(8.0, 1.0, 1.0, chips=4) == 200.0
    assert read["drift_device_s"](run) is None and read["drift_hist_hbm_pct"](run) is None  # no trace
    # a program from before the rows (the parent): every reader finds nothing and none raises
    old = [r for r in rows if r["name"] in ("dag",)] + [row("drift/sides", NODES[0], 3.2, 5.0, rows=8, cols=3)]
    bare = {"passes": [{"wall_s": 1.0, "manifest": {"phases": old}}], "traced": {"manifest": {"phases": old}},
            "trace_dir": ""}
    assert [read[name](bare) for name in READERS] == [None] * 5
    assert [read[name]({"passes": [], "traced": None}) for name in READERS] == [None] * 5
    dev = load_module("layer_metrics", "drift_device_s")
    events = {"/device:TPU:0": [(0.0, 1.0, "drift/side_histograms"), (1.0, 1.5, "stability/moments"), (2.0, 3.0, ""),
                                (3.0, 3.25, "drift/fit_cutoffs")]}
    assert dev._reduction().scope_seconds(events) == {"drift/fit_cutoffs": 0.25, "drift/side_histograms": 1.0,
                                                      "stability/moments": 0.5}
