"""CPU rehearsal of the cell ``expedia_hotel.ts_inspect`` at 20,000 rows: the
pipeline driver is ``correct`` against float64 pandas (``checks/ts_inspect_years.py``),
the bfloat16 control and an answer of the wide daily grain moved beyond its
tolerance are not; the configuration states its source, its cut, every
assumption and its guarantees; the generator keeps the source's 24 columns in
order and type, its null shares and spans, and is a function of ``(rows,
seed)``; the traced line carries every metric the driver admits to the cell;
and the three readers the cell brings (``ts_wide_s``, ``ts_wide_device_s``,
``ts_wide_hbm_pct``) on what such a pass left, on hand-built rows and events
and on a trace recorded before the scope existed.  One file, one process, no
child."""

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

ROWS = 20_000
PADDED = 24_576
CELL = "expedia_hotel.ts_inspect"
MIX = "expedia_ts_inspect"
READERS = ("ts_wide_s", "ts_wide_device_s", "ts_wide_hbm_pct")
CELLS = ["income_32k.full", CELL]
WIDE_SCOPE = "ts/segment_aggregate/wide"

expedia = load_module("datasets", "expedia_hotel")
check = load_module("checks", "ts_inspect_years")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs", "expedia_hotel.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traffic():
    with open(os.path.join(ROOT, "benchmark", "traffic", MIX + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def run(config, traffic, tmp_path_factory):
    """One run of the driver as run.py would start it, at 20,000 rows on the CPU."""
    return pipeline.run({
        "workload": CELL, "config": dict(config, rows=ROWS), "traffic": traffic,
        "traffic_yaml": os.path.join(ROOT, "benchmark", "traffic", MIX + ".yaml"),
        "work_dir": str(tmp_path_factory.mktemp("expedia_hotel")), "seed": 2**31 + 49, "seconds": 0.0,
        "trace": False, "platform": "cpu", "t_start": bench_run.T_START, "say": lambda msg: None,
    })


def _frames(data_dir):
    with open(os.path.join(ROOT, "benchmark", "traffic", MIX + ".yaml")) as f:
        return Frames(pipeline._rebase(yaml.safe_load(f), "DATASET/", data_dir + "/"))


# ------------------------------------------------------- the data files ----
def test_the_configuration_states_its_source_its_cut_its_assumptions_and_its_guarantees(bench, config, traffic):
    entry = next(c for c in bench["configs"] if c["name"] == "expedia_hotel")  # by name, not by position
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["file"] == "benchmark/configs/expedia_hotel.json" and entry["reduced"] == ["rows"] == config["reduced"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    for what in ("Expedia Hotel Recommendations", "train.csv", "37,670,293", "x 24"):
        assert what in entry["source"], what
    assert cell == {"name": CELL, "config": "expedia_hotel", "traffic": MIX, "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert config["chips"] == 1 and config["driver"] == "pipeline" and config["baseline_rows"] == 0
    assert config["published"]["rows"] == expedia.SOURCE_ROWS == 37_670_293 and config["columns"] == len(expedia.SCHEMA) == 24
    # the cut: ceil(published / 2^j) for a j of 2-4, stated with the readings that set it
    j = [-(-expedia.SOURCE_ROWS // 2**j) for j in (2, 3, 4)].index(config["rows"]) + 2
    assert f"1/{2**j} of the rows" in entry["source"]
    assert "published 37,670,293" in config["reduced_why"]["rows"] and f"{config['rows']:,}" in config["reduced_why"]["rows"]
    for tried in ("j = 2", f"j = {j}", "pass_s"):
        assert tried in config["reduced_why"]["rows"], tried
    assert f"{config['rows']:,}" in config["deployment"] and "scheduled" in config["deployment"]
    told = " ".join(config["assumed"])
    for what in ("parquet", "500,000", "date32", "no order of time", "WEEKDAY_WEIGHT", "TREND", "LEAD_MAX_DAYS",
                 "one row of 800", "malformed", "skew", "IDS", "CODES", "random streams"):
        assert what in told, what
    assert "memory" in config["published"]["written_from"] and "destinations.csv" in config["published"]["written_from"]
    g = config["guarantees"]
    with open(os.path.join(ROOT, "benchmark", "configs", "nyc_taxi.json")) as f:
        nyc = json.load(f)["guarantees"]
    assert g["tolerances"] == nyc["tolerances"]  # the same limits, none restated
    for k in ("all_rows", "durable", "precision", "repeatable"):
        assert g[k] == nyc[k], k  # word for word
    for name in g["tolerances"]:
        assert name in g["tolerances_why"], name
    for what in ("bfloat16", "scatter", "user_id"):  # by how much a lower precision and the parent's sums miss them
        assert what in g["tolerances_why"], what
    # the mix: ts_inspect.yaml with nothing changed but the table
    mixes = []
    for name in (MIX, "ts_inspect"):
        with open(os.path.join(ROOT, "benchmark", "traffic", name + ".yaml")) as f:
            mixes.append(yaml.safe_load(f))
    assert mixes[0] == mixes[1]
    args = traffic["compare"]["ts_inspect_years"]
    assert args == {"timestamps": expedia.TIMESTAMPS, "numeric": expedia.NUMERIC, "categorical": [],
                    "max_days": 36000, "period": 7} and len(expedia.NUMERIC) == 21
    assert len(traffic["tables"]) == 2 + 3 * 9 and not [t for t in traffic["tables"] if t.startswith("ts_cat_daily")]
    assert traffic["artifacts"] == ["report_stats/ts_cols_stats.csv"]


def test_benchmark_json_names_the_three_readers(bench):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert by_name["ts_wide_s"] == {"name": "ts_wide_s", "unit": "s", "better": "lower", "source": "program_span",
                                    "layer": "blocks", "moves": "pass_s", "workloads": CELLS}
    assert by_name["ts_wide_device_s"] == {"name": "ts_wide_device_s", "unit": "s", "better": "lower",
                                           "source": "device_trace", "layer": "kernels", "moves": "pass_s", "workloads": CELLS}
    assert by_name["ts_wide_hbm_pct"] == {"name": "ts_wide_hbm_pct", "unit": "%", "better": "higher",
                                          "source": "device_trace", "layer": "kernels", "moves": "pass_s", "workloads": CELLS}
    for name in READERS:
        assert callable(load_module("layer_metrics", name).read)
    # the accepted ts_* metrics keep their lists: a `benchmark` issue may give them the new cell
    for name in ("ts_inspect_s", "ts_host_rows", "ts_device_s", "ts_agg_hbm_pct"):
        assert by_name[name]["workloads"] == ["income_32k.full", "nyc_taxi.ts_inspect"]


@pytest.mark.parametrize("rows,per_part,parts", [(300, None, [300]), (2000, 700, [700, 700, 600])])
def test_generator_writes_the_24_columns_in_order_and_type(tmp_path, monkeypatch, rows, per_part, parts):
    if per_part:
        monkeypatch.setattr(expedia, "ROWS_PER_PART", per_part)
    expedia.generate(str(tmp_path / "d"), 2**31 + 5, ["parquet"], rows=rows)
    files = sorted(os.listdir(tmp_path / "d" / "parquet"))
    assert [pq.read_metadata(tmp_path / "d" / "parquet" / f).num_rows for f in files] == parts
    t = pq.read_table(tmp_path / "d" / "parquet")
    assert t.schema.names == [
        "date_time", "site_name", "posa_continent", "user_location_country", "user_location_region",
        "user_location_city", "orig_destination_distance", "user_id", "is_mobile", "is_package", "channel", "srch_ci",
        "srch_co", "srch_adults_cnt", "srch_children_cnt", "srch_rm_cnt", "srch_destination_id",
        "srch_destination_type_id", "is_booking", "cnt", "hotel_continent", "hotel_country", "hotel_market", "hotel_cluster"]
    kinds = {"date_time": pa.timestamp("ms"), "srch_ci": pa.date32(), "srch_co": pa.date32(),
             "orig_destination_distance": pa.float64()}  # parquet has no unit of a second
    for f in t.schema:
        assert f.type == kinds.get(f.name, pa.int64()), f.name
        if f.name not in ("orig_destination_distance", "srch_ci", "srch_co"):
            assert t[f.name].null_count == 0, f.name
    with pytest.raises(ValueError):
        expedia.generate(str(tmp_path / "e"), 1, ["source"], rows=10)
    with open(expedia.__file__) as f:
        assert "anovos" not in f.read().replace("benchmark/configs", "")


def test_generator_is_a_function_of_rows_and_seed(tmp_path):
    frames = []
    for name, seed in (("a", 2**31 + 9), ("b", 2**31 + 9), ("c", 2**31 + 10)):
        expedia.generate(str(tmp_path / name), seed, ["parquet"], rows=1500)
        frames.append(pd.read_parquet(tmp_path / name / "parquet"))
    assert frames[0].equals(frames[1]) and not frames[0].equals(frames[2])
    digests = [pipeline.check.digest(str(tmp_path / name), {}) for name in ("a", "b")]
    assert digests[0] == digests[1] and len(digests[0]) == 1  # the same bytes for the same seed


@pytest.mark.parametrize("rows,seed", [(120_000, 11), (120_000, 2**31 + 7)])
def test_generator_keeps_the_spans_the_null_shares_and_the_ids(tmp_path, rows, seed):
    expedia.generate(str(tmp_path / "d"), seed, ["parquet"], rows=rows)
    df = pd.read_parquet(tmp_path / "d" / "parquet")
    when = df["date_time"]
    assert when.min() >= pd.Timestamp("2013-01-07") and when.max() < pd.Timestamp("2015-01-01")
    assert when.dt.floor("D").nunique() == 724 and (when.dt.microsecond == 0).all()
    ci, co = pd.to_datetime(df["srch_ci"]), pd.to_datetime(df["srch_co"])
    assert (ci.isna() == co.isna()).all() and 1 / 1600 < ci.isna().mean() < 1 / 400
    stay = ci.notna()
    assert (ci[stay] >= when[stay].dt.floor("D")).all() and (co[stay] > ci[stay]).all()
    assert ((co - ci)[stay].dt.days <= 28).all() and ((ci - when.dt.floor("D"))[stay].dt.days <= 500).all()
    # well past the last event: the daily class of the stays is 2,048, that of the events 1,024
    assert 1024 < (ci.max() - ci.min()).days < 2048 and 1024 < (co.max() - co.min()).days < 2048
    assert ci.max() > pd.Timestamp("2016-01-01") and ci.dt.floor("D").value_counts().max() > 2 * rows / 1200
    assert 0.34 < df["orig_destination_distance"].isna().mean() < 0.38
    d = df["orig_destination_distance"].dropna()
    assert (d > 0).all() and ((d * 1e4).round() / 1e4 == d).all()
    assert 0.07 < df["is_booking"].mean() < 0.09 and (df.loc[df["is_booking"] == 1, "cnt"] == 1).all()
    assert df["cnt"].min() == 1 and df["cnt"].max() <= 269 and set(df["is_mobile"]) == set(df["is_package"]) == {0, 1}
    for name, (size, _, first) in expedia.IDS.items():
        assert first <= df[name].min() and df[name].max() < first + size, name
    assert df["user_id"].max() > 2**20 and df["user_id"].nunique() > rows // 3  # ids whose sums pass 2^24 in a day
    assert df["hotel_cluster"].nunique() == 100 and df["site_name"].min() >= 2
    assert df["user_location_country"].value_counts(normalize=True).iloc[0] > 0.2  # a skew, not a uniform draw
    # a weekly season, a trend and a summer: Saturdays are the emptiest days, 2014 is fuller than 2013
    per_dow = when.dt.dayofweek.value_counts()
    assert per_dow.idxmin() == 5 and (when.dt.year == 2014).sum() > 1.2 * (when.dt.year == 2013).sum()
    assert (when.dt.month == 7).sum() > 1.3 * (when.dt.month == 1).sum()


# ------------------------------------------------------------ the cell ----
def test_the_cell_is_correct_on_the_cpu_and_reports_its_metrics(run, bench):
    assert run["correct"] and run["failed"] == 0 and run["attempted"] == 2
    assert [r["name"] for r in run["checks"] if not r["ok"]] == []
    assert [r["name"] for r in run["checks"]][-3:] == ["null_rows", "date_hours", "files_with_other_bytes"]
    assert len(run["checks"]) == 19
    out = bench_run.report(bench, CELL, run, traced=False)
    assert set(out["metrics"]) == {"pass_s", "rows_per_s", "setup_s"} and out["correct"]


def test_the_traced_line_carries_every_metric_the_driver_admits_to_the_cell(run, bench):
    """PR 41 was refused for one name that its traced line lacked.  Off the chip there is no trace,
    so the metrics read from one are left aside; every other admitted metric has to be in the line."""
    traced = bench_run.report(bench, CELL, dict(run, traced=run["passes"][-1], trace_dir=""), True)["metrics"]
    reporting = {m["name"] for m in bench["end_to_end"] if bench_run._in_cell(m, CELL, set())}
    admitted = [m for m in bench["per_layer"] if bench_run._in_cell(m, CELL, reporting)]
    names = {m["name"] for m in admitted}
    unlisted = [m["name"] for m in bench["per_layer"] if "workloads" not in m]
    assert len(unlisted) == 18 and unlisted[0] == "fresh_programs" and unlisted[-1] == "critical_unnamed_s"
    assert names == set(unlisted) | set(READERS)  # no accepted list was given the new cell
    from_trace = {m["name"] for m in admitted if m["source"] == "device_trace"}
    assert from_trace == {"device_busy_s", "device_idle_share", "idle_unnamed_share", "ts_wide_device_s", "ts_wide_hbm_pct"}
    # peak_hbm_gb is the device's own count of its memory, which the CPU backend does not keep
    host_side = names - from_trace - {"peak_hbm_gb"}
    assert host_side <= set(traced), sorted(host_side - set(traced))
    assert not set(traced) - names  # and nothing the driver did not ask for
    for name in host_side:  # no reader of a span or a counter returns None for want of one
        assert load_module("layer_metrics", name).read(dict(run, trace={})) is not None, name
    assert traced["window_compiles"]["value"] == 0 and traced["ingest_convert_s"]["value"] > 0  # the two date32 columns
    assert 0 < traced["ts_wide_s"]["value"] < traced["dag_s"]["value"]
    assert load_module("layer_metrics", "ingest_encode_s").read(run) is None  # no string column: not this cell's


def test_a_pass_leaves_the_30_csvs_and_the_stage_rows(run, traffic):
    last = run["passes"][-1]
    files = sorted(f for f in os.listdir(os.path.join(last["out_dir"], "report_stats")) if f.endswith(".csv"))
    assert len(files) == 30 and "ts_cols_stats.csv" in files and not [f for f in files if f.startswith("ts_cat_daily")]
    assert sorted(os.path.basename(t) for t in traffic["tables"].values()) == [f for f in files if f != "ts_cols_stats.csv"]
    found = pd.read_csv(os.path.join(last["out_dir"], "report_stats", "ts_cols_stats.csv"))
    assert len(found) == 0 or not set(found.iloc[:, 0]) & set(expedia.NUMERIC)  # the twenty integers are left alone
    rows = last["manifest"]["phases"]
    node = [r for r in rows if r["name"] == "timeseries_analyzer/inspection"]
    assert len(node) == 1 and node[0]["parent"] == "dag"
    stages = [r for r in rows if r["name"].startswith("ts/") and r["start_s"] >= node[0]["start_s"]]
    names = [r["name"] for r in stages]
    assert names.count("ts/eligibility") == 3 and names.count("ts/viz/num") == 3 and names.count("ts/viz/cat") == 0
    num = [r["counts"] for r in stages if r["name"] == "ts/viz/num"]
    for c, wide in zip(num, (1024, 2048, 2048)):  # the events' 724 days, the stays' 1,220
        assert c["rows"] == PADDED and c["cols"] == 21 and c["host_rows"] == 0 and c["fetches"] == 1
        assert c["segments"] == wide + 8 + 8 and c["wide_segments"] == wide and c["wide_cells"] == PADDED * 21
        assert (c["median_selects"], c["median_sorts"], c["select_passes"]) == (42, 21, 9)
    stats = pd.read_csv(os.path.join(last["out_dir"], "report_stats", "ts_stats.csv")).set_index("attribute")
    assert stats["eligible"].tolist() == [1, 1, 1] and stats.loc["date_time", "null_pct"] == 0
    assert 0 < stats.loc["srch_ci", "null_pct"] == stats.loc["srch_co", "null_pct"] < 0.003
    assert stats.loc["date_time", "distinct_days"] == 724 and stats.loc["srch_ci", "span_days"] > 1024


def _moved(out_dir, work, edit):
    """A copy of a pass's output with one file edited."""
    shutil.rmtree(work, ignore_errors=True)
    shutil.copytree(out_dir, work)
    edit(os.path.join(work, "report_stats"))
    return work


def _edit(name, change):
    def edit(stats):
        path = os.path.join(stats, name)
        t = pd.read_csv(path)
        change(t)
        t.to_csv(path, index=False)

    return edit


def test_an_answer_of_the_wide_daily_grain_moved_beyond_its_tolerance_fails(run, traffic, config, tmp_path):
    last = run["passes"][-1]["out_dir"]
    with open(os.path.join(os.path.dirname(last), "pipeline.yaml")) as f:
        frames = Frames(yaml.safe_load(f))
    tol, args = config["guarantees"]["tolerances"], traffic["compare"]["ts_inspect_years"]
    ref = check.reference(frames, args)
    assert all(r["ok"] for r in check.compare(check.read(last, traffic, args), ref, tol, args))

    def failing(edit):
        return [r["name"] for r in check.compare(check.read(_moved(last, str(tmp_path / "m"), edit), traffic, args),
                                                 ref, tol, args) if not r["ok"]]

    def user_mean(t):  # an id's mean of 6 x 10^5 off in the fifth digit: 3e-5 relative, what a bfloat16 sum would do a thousandfold
        i = t.index[t["attribute"] == "user_id"][100]
        t.loc[i, "mean"] = round(t.loc[i, "mean"] * (1 + 3e-5), 4)

    assert failing(_edit("ts_num_daily_srch_ci.csv", user_mean)) == ["bucket_mean"]

    def a_median_and_a_maximum(t):
        i = t.index[t["attribute"] == "hotel_cluster"][300]  # the 21st numeric column: the parent's cap left it out
        t.loc[i, "median"] += 0.5
        t.loc[i + 1, "max"] -= 1

    assert failing(_edit("ts_num_daily_date_time.csv", a_median_and_a_maximum)) == ["bucket_max", "bucket_median"]
    assert failing(_edit("ts_daily_srch_co.csv", lambda t: t.__setitem__("count", t["count"] + (t.index == 700)))) == [
        "daily_counts"]
    # the two answers the years' check adds: a null that is not counted, a date that has an hour
    assert failing(_edit("ts_landscape.csv", lambda t: t.__setitem__("records", t["records"] + (t["attribute"] == "srch_ci")))
                   ) == ["ts_landscape", "null_rows"]

    def an_hour(t):
        t.loc[0, "count"] -= 1
        t.loc[1] = [7.0, 1]

    assert failing(_edit("ts_hourly_srch_co.csv", an_hour)) == ["hourly_counts", "date_hours"]


@pytest.mark.parametrize("seed", [5, 2**31 + 7])
def test_the_control_in_bfloat16_fails_by_a_wide_margin(config, traffic, tmp_path, seed):
    expedia.generate(str(tmp_path / "d"), seed, ["parquet"], rows=ROWS)
    frames = _frames(str(tmp_path / "d"))
    tol, args = config["guarantees"]["tolerances"], traffic["compare"]["ts_inspect_years"]
    ref = check.reference(frames, args)
    assert all(r["ok"] for r in check.compare(ref, ref, tol, args))
    rows = {r["name"]: r for r in check.compare(check.control(ref, frames, args), ref, tol, args)}
    assert not rows["bucket_mean"]["ok"] and rows["bucket_mean"]["value"] > 50  # an id of 6 x 10^5 held to 8 bits
    assert not rows["bucket_median"]["ok"] and rows["bucket_median"]["value"] > 100
    for name in ("daily_counts", "bucket_count", "ts_landscape", "null_rows", "date_hours"):
        assert rows[name]["ok"], name


def test_the_years_check_on_a_table_small_enough_to_do_by_hand(tmp_path):
    """A date column with an empty cell beside a timestamp, and no categorical column."""
    import datetime

    os.makedirs(tmp_path / "p")
    t = pd.to_datetime(["2013-01-07 05:59:59", "2013-01-07 06:00:00", "2013-03-01 23:00:00", "2014-06-01 10:00:00"])
    d = [datetime.date(2013, 1, 9), None, datetime.date(2013, 1, 9), datetime.date(2015, 2, 1)]
    pa_table = pa.table({"t": pa.array(t.astype("datetime64[s]")), "d": pa.array(d, type=pa.date32()),
                         "v": pa.array([1.0, 3.0, None, 7.0])})
    pq.write_table(pa_table, tmp_path / "p" / "part-0.parquet")
    frames = Frames({"input_dataset": {"read_dataset": {"file_path": str(tmp_path / "p"), "file_type": "parquet"}}})
    args = {"timestamps": ["t", "d"], "numeric": ["v"], "categorical": [], "max_days": 36000, "period": 7}
    ref = check.reference(frames, args)
    assert ref["null_rows"] == {"t": 0, "d": 1} and ref["date_hours"] == {"d": ([0], ["late_hours"])} and ref["table_rows"] == 4
    assert ref["daily"]["d|2013-01-09"] == 2 and ref["hourly"]["d|0"] == 3 and ref["cat_daily"] == {}
    assert ref["stats"]["d|null_pct"] == 0.25 and ref["stats"]["d|span_days"] == 753
    assert ref["bucket_count"]["d|daily|2013-01-09|v"] == 1 and ref["bucket_mean"]["t|hourly|late_hours|v"] == 1.0
    assert frames.main["d"].dtype == object  # the reference typed a copy, not the frame other checks read


# ------------------------------------------------- the three new readers ----
def _row(name, parent, start, end, **counts):
    return {"name": name, "parent": parent, "start_s": start, "end_s": end, "thread": "t", "counts": counts}


RECORDED = [  # a pass as the program records it: a wide column, a narrow one (a month of days), a wide one
    _row("run", None, 0.0, 9.4), _row("ingest", "run", 0.0, 5.0), _row("dag", "run", 5.1, 9.3),
    _row("timeseries_analyzer/inspection", "dag", 5.2, 9.2),
    _row("ts/viz/num", "ts/viz", 5.4, 6.6, rows=6_291_456, cols=21, fetches=1, host_rows=0, segments=1040,
         wide_segments=1024, wide_cells=6_291_456 * 21),
    _row("ts/viz/num", "ts/viz", 6.7, 7.0, rows=6_291_456, cols=21, fetches=1, host_rows=0, segments=48,
         wide_segments=0, wide_cells=0),
    _row("ts/viz/num", "ts/viz", 7.1, 8.9, rows=6_291_456, cols=21, fetches=1, host_rows=0, segments=2064,
         wide_segments=2048, wide_cells=6_291_456 * 21),
]


def _pass(rows, wall=9.4):
    return {"wall_s": wall, "manifest": {"phases": rows}}


def test_the_span_reader_on_a_recorded_manifest():
    wide_s = load_module("layer_metrics", "ts_wide_s")
    assert wide_s.read({"passes": [_pass(RECORDED)]}) == pytest.approx(1.2 + 1.8)
    assert [r["counts"]["wide_segments"] for r in wide_s.wide_rows(RECORDED)] == [1024, 2048]
    # the parent: the stage rows are there, none carries the count; a pass with narrow classes only; no pass
    before = [dict(r, counts={k: v for k, v in r["counts"].items() if not k.startswith("wide_")}) for r in RECORDED]
    narrow = [r for r in RECORDED if r["counts"].get("wide_segments") != 1024 and r["counts"].get("wide_segments") != 2048]
    for rows in (before, narrow, [], [_row("run", None, 0.0, 1.0)]):
        assert wide_s.read({"passes": [_pass(rows)]}) is None
    assert wide_s.read({"passes": []}) is None


def test_the_device_readers_on_a_hand_built_event_list(monkeypatch):
    """Chip 0: the calendar's sort 1.0-1.2 s; a narrow grain's fusion 2.0-2.4 under the aggregate's scope; the wide
    moments' ``while`` 3.0-3.5 with a convolution 3.1-3.4 inside it; the wide sort 3.5-5.0."""
    device_s = load_module("layer_metrics", "ts_device_s")
    wide_device_s = load_module("layer_metrics", "ts_wide_device_s")
    assert wide_device_s.SCOPE == WIDE_SCOPE and WIDE_SCOPE.startswith(device_s.SCOPES[1] + "/")
    op = "jit(_ts_num_viz_program)/jit(_segment_aggregate_jit)/ts/segment_aggregate/"
    device_s.SCOPES = (WIDE_SCOPE,)  # what the reader tells its own copy of the module
    assert device_s._scope(op + "wide/moments/while/body/closed_call/dot_general:") == WIDE_SCOPE
    assert device_s._scope(op + "wide/medians/sort:") == WIDE_SCOPE
    assert device_s._scope(op + "while/body/dot_general:") is None and device_s._scope(op + "wider/sort:") is None
    events = {"/device:TPU:0": [(1.0, 1.2, ""), (2.0, 2.4, ""), (3.0, 3.5, WIDE_SCOPE), (3.1, 3.4, WIDE_SCOPE),
                                (3.5, 5.0, WIDE_SCOPE)]}
    assert device_s.scope_seconds(events) == {WIDE_SCOPE: pytest.approx(2.0)}
    # the accepted reader's own scope still claims the wide operations: ts_device_s keeps counting them
    assert load_module("layer_metrics", "ts_device_s")._scope(op + "wide/medians/sort:") == "ts/segment_aggregate"
    assert wide_device_s.read({"trace_dir": ""}) is None and wide_device_s.read({}) is None
    assert wide_device_s.read({"ts_wide_scope_seconds": {WIDE_SCOPE: 2.0}}) == 2.0
    hbm = load_module("layer_metrics", "ts_wide_hbm_pct")
    by_hand = (6_291_456 * 21 * 5 + 6_291_456 * 5) * 2 + 6 * 4 * 21 * (1024 + 2048)
    assert hbm.wide_bytes(6_291_456 * 21, 21, 1024) + hbm.wide_bytes(6_291_456 * 21, 21, 2048) == by_hand == hbm.stage_bytes(RECORDED)
    assert hbm.wide_bytes(2 * 4096 * 3, 3, 512) == 2 * (4096 * 3 * 5 + 4096 * 5) + 6 * 4 * 3 * 512  # two wide grains in a call
    # no trace, no scope, no counts, or a device the peaks do not know (the CPU): nothing, and no error
    assert hbm.read({"trace_dir": "", "traced": _pass(RECORDED)}) is None
    assert hbm.read({"ts_wide_scope_seconds": {}, "traced": _pass(RECORDED)}) is None
    assert hbm.read({"ts_wide_scope_seconds": {WIDE_SCOPE: 2.0}, "traced": _pass([])}) is None
    assert hbm.read({"ts_wide_scope_seconds": {WIDE_SCOPE: 2.0}, "traced": _pass(RECORDED)}) is None
    import jax

    class V5e:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a: [V5e()])
    monkeypatch.setattr(jax, "device_count", lambda *a: 1)
    assert hbm.read({"ts_wide_scope_seconds": {WIDE_SCOPE: 2.0}, "traced": _pass(RECORDED)}) == pytest.approx(
        100.0 * by_hand / (2.0 * 819e9))


def test_the_device_reader_finds_nothing_in_a_trace_from_before_the_scope(tmp_path):
    """PR 39's recording (one call of each of the two programs, class 32): the aggregate's scope is there, no wide one."""
    where = tmp_path / "plugins" / "profile" / "recorded"
    os.makedirs(where)
    shutil.copy(os.path.join(ROOT, "tests", "benchmark", "recorded", "ts_tiny_v5e.xplane.pb"), where)
    run = {"trace_dir": str(tmp_path)}
    assert set(load_module("layer_metrics", "ts_device_s").by_scope(dict(run))) == {"ts/calendar_counts", "ts/segment_aggregate"}
    assert load_module("layer_metrics", "ts_wide_device_s").by_scope(run) == {}
    assert load_module("layer_metrics", "ts_wide_device_s").read(run) is None
    assert load_module("layer_metrics", "ts_wide_hbm_pct").read(dict(run, traced=_pass(RECORDED))) is None


def test_readers_on_the_live_run(run):
    rows = run["passes"][-1]["manifest"]["phases"]
    num = [r for r in rows if r["name"] == "ts/viz/num"]
    one = dict(run, passes=run["passes"][-1:])
    assert load_module("layer_metrics", "ts_wide_s").read(one) == pytest.approx(sum(r["end_s"] - r["start_s"] for r in num))
    hbm = load_module("layer_metrics", "ts_wide_hbm_pct")
    assert hbm.stage_bytes(rows) == sum(hbm.wide_bytes(PADDED * 21, 21, n) for n in (1024, 2048, 2048))
