"""CPU rehearsal of the cell ``nyc_taxi.ts_inspect`` at 2,000 rows: the
pipeline driver is ``correct`` against float64 pandas, the bfloat16 control
and a day's count off by one are not; the configuration states its source,
its cut and every assumption; the generator keeps the source's 19 columns in
order and type and is a function of ``(rows, seed)``; and the four readers
the cell brings (``ts_inspect_s``, ``ts_host_rows``, ``ts_device_s``,
``ts_agg_hbm_pct``) on what such a pass left, on hand-built rows, on a
hand-built event list and on a trace recorded on the chip.  One file, one
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

ROWS = 2000
PADDED = 2048
CELL = "nyc_taxi.ts_inspect"
READERS = ("ts_inspect_s", "ts_host_rows", "ts_device_s", "ts_agg_hbm_pct")
RECORDED_TRACE = os.path.join(ROOT, "tests", "benchmark", "recorded", "ts_tiny_v5e.xplane.pb")

taxi = load_module("datasets", "nyc_taxi")
check = load_module("checks", "ts_inspect")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs", "nyc_taxi.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traffic():
    with open(os.path.join(ROOT, "benchmark", "traffic", "ts_inspect.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def run(config, traffic, tmp_path_factory):
    """One run of the driver as run.py would start it, at 2,000 rows on the CPU."""
    return pipeline.run({
        "workload": CELL, "config": dict(config, rows=ROWS), "traffic": traffic,
        "traffic_yaml": os.path.join(ROOT, "benchmark", "traffic", "ts_inspect.yaml"),
        "work_dir": str(tmp_path_factory.mktemp("nyc_taxi")), "seed": 2**31 + 39, "seconds": 0.0,
        "trace": False, "platform": "cpu", "t_start": bench_run.T_START, "say": lambda msg: None,
    })


def _frames(data_dir):
    with open(os.path.join(ROOT, "benchmark", "traffic", "ts_inspect.yaml")) as f:
        return Frames(pipeline._rebase(yaml.safe_load(f), "DATASET/", data_dir + "/"))


# ------------------------------------------------------- the data files ----
def test_the_configuration_states_its_source_its_cut_and_every_assumption(bench, config, traffic):
    entry = next(c for c in bench["configs"] if c["name"] == "nyc_taxi")  # by name, not by position
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["file"] == "benchmark/configs/nyc_taxi.json" and entry["reduced"] == ["rows"] == config["reduced"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert "yellow_tripdata_2015-01" in entry["source"] and "12,748,986" in entry["source"]
    assert cell == {"name": CELL, "config": "nyc_taxi", "traffic": "ts_inspect", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert config["chips"] == 1 and config["driver"] == "pipeline" and config["baseline_rows"] == 0
    assert config["published"]["rows"] == taxi.SOURCE_ROWS == 12_748_986 and config["columns"] == len(taxi.SCHEMA) == 19
    # the cut: ceil(published / 2^j) for a j of 0-3, stated with the readings that set it
    assert config["rows"] in [-(-taxi.SOURCE_ROWS // 2**j) for j in range(4)]
    assert "published 12,748,986" in config["reduced_why"]["rows"] and f"{config['rows']:,}" in config["reduced_why"]["rows"]
    assert f"{config['rows']:,}" in config["deployment"] and "monthly" in config["deployment"]
    told = " ".join(config["assumed"])
    for what in ("parquet", "500,000", "WEEKDAY_WEIGHT", "durations", "1 February", "mixture", "float32", "0, 0",
                 "distance", "total_amount", "above 180", "null", "random streams"):
        assert what in told, what
    g = config["guarantees"]
    assert set(g["tolerances"]) == {"bucket_mean", "bucket_median", "decompose", "kpss"}
    for name in g["tolerances"]:
        assert name in g["tolerances_why"], name
    for word in ("no sampling", "disk", "f32", "same bytes"):
        assert any(word in g[k] for k in ("all_rows", "durable", "precision", "repeatable")), word
    # the mix: the upstream scenario's own arguments, and nothing but the read and the inspection
    with open(os.path.join(ROOT, "benchmark", "traffic", "ts_inspect.yaml")) as f:
        mix = yaml.safe_load(f)
    assert set(mix) == {"input_dataset", "timeseries_analyzer", "report_preprocessing"}
    assert mix["timeseries_analyzer"] == {"auto_detection": True, "tz_offset": "local", "inspection": True,
                                          "analysis_level": "daily", "max_days": 36000}
    with open(os.path.join(ROOT, "config", "configs_time_series.yaml")) as f:
        theirs = yaml.safe_load(f)["timeseries_analyzer"]
    assert {k: v for k, v in theirs.items() if k != "id_col"} == mix["timeseries_analyzer"]
    assert len(traffic["tables"]) == 22 and traffic["compare"]["ts_inspect"]["numeric"] == taxi.NUMERIC
    assert traffic["compare"]["ts_inspect"]["timestamps"] == taxi.TIMESTAMPS


def test_benchmark_json_names_the_four_readers(bench):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    cells = ["income_32k.full", CELL]
    assert by_name["ts_inspect_s"] == {"name": "ts_inspect_s", "unit": "s", "better": "lower", "source": "program_span",
                                       "layer": "blocks", "moves": "pass_s", "workloads": cells}
    assert by_name["ts_host_rows"] == {"name": "ts_host_rows", "unit": "count", "better": "lower",
                                       "source": "program_counter", "layer": "blocks", "moves": "pass_s", "workloads": cells}
    assert by_name["ts_device_s"] == {"name": "ts_device_s", "unit": "s", "better": "lower", "source": "device_trace",
                                      "layer": "kernels", "moves": "pass_s", "workloads": cells}
    assert by_name["ts_agg_hbm_pct"] == {"name": "ts_agg_hbm_pct", "unit": "%", "better": "higher", "source": "device_trace",
                                         "layer": "kernels", "moves": "pass_s", "workloads": cells}
    for name in READERS:
        assert callable(load_module("layer_metrics", name).read)


@pytest.mark.parametrize("rows,per_part,parts", [(300, None, [300]), (2000, 700, [700, 700, 600])])
def test_generator_writes_the_19_columns_in_order_and_type(tmp_path, monkeypatch, rows, per_part, parts):
    if per_part:
        monkeypatch.setattr(taxi, "ROWS_PER_PART", per_part)
    taxi.generate(str(tmp_path / "d"), 2**31 + 5, ["parquet"], rows=rows)
    files = sorted(os.listdir(tmp_path / "d" / "parquet"))
    assert [pq.read_metadata(tmp_path / "d" / "parquet" / f).num_rows for f in files] == parts
    t = pq.read_table(tmp_path / "d" / "parquet")
    assert t.schema.names == ["VendorID", "tpep_pickup_datetime", "tpep_dropoff_datetime", "passenger_count",
                              "trip_distance", "pickup_longitude", "pickup_latitude", "RateCodeID", "store_and_fwd_flag",
                              "dropoff_longitude", "dropoff_latitude", "payment_type", "fare_amount", "extra", "mta_tax",
                              "tip_amount", "tolls_amount", "improvement_surcharge", "total_amount"]
    for f in t.schema:
        want = taxi.SCHEMA.field(f.name).type
        # parquet has no unit of a second: a timestamp[s] comes back in milliseconds
        assert f.type == (pa.timestamp("ms") if pa.types.is_timestamp(want) else want), f.name
        assert t[f.name].null_count == 0
    with pytest.raises(ValueError):
        taxi.generate(str(tmp_path / "e"), 1, ["source"], rows=10)
    with open(taxi.__file__) as f:
        assert "anovos" not in f.read().replace("benchmark/configs", "")


def test_generator_is_a_function_of_rows_and_seed(tmp_path):
    frames = []
    for name, seed in (("a", 2**31 + 9), ("b", 2**31 + 9), ("c", 2**31 + 10)):
        taxi.generate(str(tmp_path / name), seed, ["parquet"], rows=1500)
        frames.append(pd.read_parquet(tmp_path / name / "parquet"))
    assert frames[0].equals(frames[1]) and not frames[0].equals(frames[2])


@pytest.mark.parametrize("rows,seed", [(60_000, 11), (60_000, 2**31 + 7)])
def test_generator_keeps_the_month_the_codes_and_the_cents(tmp_path, rows, seed):
    taxi.generate(str(tmp_path / "d"), seed, ["parquet"], rows=rows)
    df = pd.read_parquet(tmp_path / "d" / "parquet")
    pick, drop = df["tpep_pickup_datetime"], df["tpep_dropoff_datetime"]
    assert pick.min() >= pd.Timestamp("2015-01-01") and pick.max() < pd.Timestamp("2015-02-01")
    assert pick.dt.day.nunique() == 31 and (pick.dt.microsecond == 0).all()
    assert (drop > pick).all() and drop.max() >= pd.Timestamp("2015-02-01") and drop.max() < pd.Timestamp("2015-02-02")
    assert drop.dt.floor("D").nunique() == 32
    assert set(df["VendorID"]) == {1, 2} and set(df["passenger_count"]) <= set(range(10))
    assert set(df["RateCodeID"]) <= {1, 2, 3, 4, 5, 6, 99} and set(df["payment_type"]) <= {1, 2, 3, 4, 5}
    assert set(df["store_and_fwd_flag"]) == {"Y", "N"}
    for c in taxi.COORDINATES:  # a float32's value, as the public file's
        assert (df[c].astype(np.float32).astype(np.float64) == df[c]).all()
    no_fix = (df["pickup_longitude"] == 0) & (df["pickup_latitude"] == 0)
    assert 0.012 < no_fix.mean() < 0.025 and ((df["pickup_longitude"] == 0) == (df["pickup_latitude"] == 0)).all()
    cents = (df[taxi.AMOUNTS + ["total_amount"]] * 100).round()
    assert ((df[taxi.AMOUNTS + ["total_amount"]] * 100 - cents).abs() < 1e-6).all().all()
    assert (cents["total_amount"] == cents[taxi.AMOUNTS].sum(axis=1)).all()
    assert (df["fare_amount"] > 180).any() and (df["fare_amount"] > 0).all()
    weekend = pick.dt.dayofweek >= 5  # another shape of day: the night is busier than the morning
    assert (pick[weekend].dt.hour < 4).mean() > 2 * (pick[~weekend].dt.hour < 4).mean()


# ------------------------------------------------------------ the cell ----
def test_the_cell_is_correct_on_the_cpu_and_reports_its_metrics(run, bench):
    assert run["correct"] and run["failed"] == 0 and run["attempted"] == 2
    assert [r["name"] for r in run["checks"] if not r["ok"]] == []
    assert len(run["checks"]) == 17 and run["checks"][-1]["name"] == "files_with_other_bytes"
    out = bench_run.report(bench, CELL, run, traced=False)
    assert set(out["metrics"]) == {"pass_s", "rows_per_s", "setup_s"} and out["correct"]
    traced = bench_run.report(bench, CELL, dict(run, traced=run["passes"][-1]), traced=True)
    # no trace on the CPU: the two span readers report, the two device readers have nothing to read
    assert {"ts_inspect_s", "ts_host_rows"} <= set(traced["metrics"])
    assert not {"ts_device_s", "ts_agg_hbm_pct"} & set(traced["metrics"])
    assert traced["metrics"]["ts_host_rows"]["value"] == 0


def test_a_pass_leaves_the_22_csvs_and_the_stage_rows(run, traffic):
    last = run["passes"][-1]
    files = sorted(f for f in os.listdir(os.path.join(last["out_dir"], "report_stats")) if f.endswith(".csv"))
    assert len(files) == 23 and "ts_cols_stats.csv" in files  # the inspection's 22 and auto-detection's one
    rows = last["manifest"]["phases"]
    node = [r for r in rows if r["name"] == "timeseries_analyzer/inspection"]
    assert len(node) == 1 and node[0]["parent"] == "dag"
    stages = [r for r in rows if r["name"].startswith("ts/") and r["start_s"] >= node[0]["start_s"]]
    names = [r["name"] for r in stages]
    assert names.count("ts/eligibility") == 2 and names.count("ts/viz/num") == 2 and names.count("ts/viz/cat") == 2
    assert "ts/feats" not in names and names.count("ts/landscape") == 1
    for r in stages:
        if r["name"] in ("ts/eligibility", "ts/viz/num", "ts/viz/cat"):
            assert r["counts"]["host_rows"] == 0 and r["counts"]["fetches"] >= 1
        if r["name"] == "ts/viz/num":
            assert r["counts"]["rows"] == PADDED and r["counts"]["cols"] == 16 and r["counts"]["segments"] == 32 + 8 + 8


def _moved(out_dir, work, edit):
    """A copy of a pass's output with one file edited."""
    shutil.rmtree(work, ignore_errors=True)
    shutil.copytree(out_dir, work)
    edit(os.path.join(work, "report_stats"))
    return work


def test_one_days_count_off_by_one_fails_an_exact_row(run, traffic, config, tmp_path):
    last = run["passes"][-1]["out_dir"]
    with open(os.path.join(os.path.dirname(last), "pipeline.yaml")) as f:
        frames = Frames(yaml.safe_load(f))
    tol, args = config["guarantees"]["tolerances"], traffic["compare"]["ts_inspect"]
    ref = check.reference(frames, args)
    assert all(r["ok"] for r in check.compare(check.read(last, traffic, args), ref, tol, args))

    def one_more(stats):
        path = os.path.join(stats, "ts_daily_tpep_dropoff_datetime.csv")
        t = pd.read_csv(path)
        t.loc[12, "count"] += 1
        t.to_csv(path, index=False)

    rows = check.compare(check.read(_moved(last, str(tmp_path / "a"), one_more), traffic, args), ref, tol, args)
    assert [r["name"] for r in rows if not r["ok"]] == ["daily_counts"]

    def a_minimum(stats):
        path = os.path.join(stats, "ts_num_weekly_tpep_pickup_datetime.csv")
        t = pd.read_csv(path)
        t.loc[3, "min"] -= 0.0003
        t.loc[5, "median"] += 0.01
        t.to_csv(path, index=False)

    rows = check.compare(check.read(_moved(last, str(tmp_path / "b"), a_minimum), traffic, args), ref, tol, args)
    assert [r["name"] for r in rows if not r["ok"]] == ["bucket_min", "bucket_median"]

    def a_day_lost(stats):
        path = os.path.join(stats, "ts_decompose_tpep_pickup_datetime.csv")
        pd.read_csv(path).iloc[:-1].to_csv(path, index=False)

    rows = check.compare(check.read(_moved(last, str(tmp_path / "c"), a_day_lost), traffic, args), ref, tol, args)
    assert [r["name"] for r in rows if not r["ok"]] == ["decompose_rows", "decompose"]


@pytest.mark.parametrize("seed", [5, 2**31 + 7])
def test_the_control_in_bfloat16_fails_by_a_wide_margin(config, traffic, tmp_path, seed):
    taxi.generate(str(tmp_path / "d"), seed, ["parquet"], rows=ROWS)
    frames = _frames(str(tmp_path / "d"))
    tol, args = config["guarantees"]["tolerances"], traffic["compare"]["ts_inspect"]
    ref = check.reference(frames, args)
    assert all(r["ok"] for r in check.compare(ref, ref, tol, args))
    rows = {r["name"]: r for r in check.compare(check.control(ref, frames, args), ref, tol, args)}
    assert not rows["bucket_mean"]["ok"] and rows["bucket_mean"]["value"] > 10  # a mean averages roundings away
    assert not rows["bucket_median"]["ok"] and rows["bucket_median"]["value"] > 100
    assert rows["decompose"]["ok"]  # a day of 2,000 / 31 trips is a whole number under 256: bfloat16 holds it
    assert rows["daily_counts"]["ok"] and rows["bucket_count"]["ok"] and rows["ts_landscape"]["ok"]


def test_the_reference_on_a_table_small_enough_to_do_by_hand(tmp_path):
    os.makedirs(tmp_path / "p")
    t = pd.to_datetime(["2015-01-03 05:59:59", "2015-01-03 06:00:00", "2015-01-04 23:00:00", None, "2015-01-05 10:00:00"])
    pd.DataFrame({"t": t.astype("datetime64[s]"), "v": [1.0, 3.0, 5.0, 7.0, None], "c": ["a", "b", "a", "a", "a"]}
                 ).to_parquet(tmp_path / "p" / "part-0.parquet", index=False)
    frames = Frames({"input_dataset": {"read_dataset": {"file_path": str(tmp_path / "p"), "file_type": "parquet"}}})
    ref = check.reference(frames, {"timestamps": ["t"], "numeric": ["v"], "categorical": ["c"], "max_days": 10, "period": 7})
    assert ref["daily"] == {"t|2015-01-03": 2, "t|2015-01-04": 1, "t|2015-01-05": 1}
    assert ref["hourly"] == {"t|5": 1, "t|6": 1, "t|23": 1, "t|10": 1}
    assert ref["weekday"] == {"t|5": 2, "t|6": 1, "t|0": 1}  # a Saturday, a Sunday, a Monday
    assert ref["daypart"] == {"t|late_hours": 1, "t|early_hours": 1, "t|night_hours": 1, "t|work_hours": 1}
    assert ref["stats"] == {"t|eligible": 1, "t|span_days": 2, "t|distinct_days": 3, "t|null_pct": 0.2,
                            "t|min_ts": "2015-01-03 05:59:59", "t|max_ts": "2015-01-05 10:00:00"}
    assert ref["landscape"]["t|top_daypart"] == "early_hours"  # four at one each: the first label in sort order
    assert ref["landscape"]["t|weekend_pct"] == 0.75 and ref["landscape"]["t|avg_records_per_day"] == 1.33
    assert ref["bucket_count"]["t|daily|2015-01-03|v"] == 2 and "t|daily|2015-01-05|v" not in ref["bucket_count"]
    assert ref["bucket_mean"]["t|weekly|Sat|v"] == 2.0 and ref["bucket_max"]["t|hourly|night_hours|v"] == 5.0
    assert ref["cat_daily"] == {"t|c|2015-01-03|a": 1, "t|c|2015-01-03|b": 1, "t|c|2015-01-04|a": 1, "t|c|2015-01-05|a": 1}
    assert ref["decompose_rows"] == {} and len(ref["kpss"]) == 0  # three days: no decomposition, no statistic
    y = np.array([5.0, 9, 4, 6, 8, 3, 7] * 3)
    trend, seasonal, resid = check.decompose(y, 7)
    assert np.isnan(trend[:3]).all() and np.isnan(trend[-3:]).all() and np.allclose(trend[3:-3], 6.0)
    assert np.allclose(seasonal[:7], y[:7] - 6.0) and np.allclose(resid[3:-3], 0.0)
    assert check.decompose(y[:13], 7) is None and check.kpss(np.ones(12)) is None
    assert check.kpss(np.arange(40.0)) > 0.463 > 0.347 > check.kpss(np.tile([1.0, -1.0], 20))  # a trend, a see-saw


# -------------------------------------------------- the four new readers ----
def _row(name, parent, start, end, **counts):
    return {"name": name, "parent": parent, "start_s": start, "end_s": end, "thread": "t", "counts": counts}


RECORDED = [  # a pass as the program records it: the read 0-2 s, detection, the inspection 2.2-6.2 s
    _row("run", None, 0.0, 6.4), _row("ingest", "run", 0.0, 2.0), _row("dag", "run", 2.1, 6.3),
    _row("timeseries_analyzer/auto_detection", "dag", 2.1, 2.2), _row("ts/write", "timeseries_analyzer/auto_detection", 2.15, 2.2),
    _row("timeseries_analyzer/inspection", "dag", 2.2, 6.2),
    _row("ts/eligibility", "timeseries_analyzer/inspection", 2.2, 2.3, rows=3_000_000, fetches=1, host_rows=0),
    _row("ts/viz", "timeseries_analyzer/inspection", 2.3, 4.1),
    _row("ts/viz/num", "ts/viz", 2.4, 4.0, rows=4_194_304, cols=16, fetches=1, host_rows=0, segments=48),
    _row("ts/viz/cat", "ts/viz", 4.0, 4.1, rows=4_194_304, cols=1, fetches=2, host_rows=0),
    _row("ts/eligibility", "timeseries_analyzer/inspection", 4.1, 4.2, rows=3_000_000, fetches=1, host_rows=0),
    _row("ts/viz", "timeseries_analyzer/inspection", 4.2, 6.0),
    _row("ts/viz/num", "ts/viz", 4.3, 5.9, rows=4_194_304, cols=16, fetches=1, host_rows=0, segments=48),
    _row("ts/landscape", "timeseries_analyzer/inspection", 6.0, 6.1, cols=2),
    _row("ts/write", "timeseries_analyzer/inspection", 6.1, 6.2, files=1),
]


def _pass(rows, wall=6.4):
    return {"wall_s": wall, "manifest": {"phases": rows}}


def test_span_and_counter_readers_on_a_recorded_manifest():
    inspect_s = load_module("layer_metrics", "ts_inspect_s").read
    host_rows = load_module("layer_metrics", "ts_host_rows").read
    run = {"passes": [_pass(RECORDED)]}
    assert inspect_s(run) == pytest.approx(4.0) and host_rows(run) == 0
    fetched = [dict(r, counts=dict(r["counts"], host_rows=2 * 4_194_304)) if r["name"] == "ts/eligibility" else r
               for r in RECORDED]
    assert host_rows({"passes": [_pass(fetched)]}) == 4 * 4_194_304
    # the parent: the node and its stages are there, no stage carries the count
    before = [dict(r, counts={k: v for k, v in r["counts"].items() if k not in ("host_rows", "segments")}) for r in RECORDED]
    assert inspect_s({"passes": [_pass(before)]}) == pytest.approx(4.0) and host_rows({"passes": [_pass(before)]}) is None
    # a stats pass, an empty manifest, no pass
    stats = [_row("run", None, 0.0, 1.0), _row("dag", "run", 0.5, 0.8), _row("stats_generator/measures_of_counts", "dag", 0.5, 0.8)]
    for rows in (stats, [], [_row("run", None, 0.0, 1.0)]):
        assert inspect_s({"passes": [_pass(rows)]}) is None and host_rows({"passes": [_pass(rows)]}) is None
    assert inspect_s({"passes": []}) is None and host_rows({"passes": []}) is None


def test_device_readers_on_a_hand_built_event_list(monkeypatch):
    """Chip 0: the calendar program's sort 1.0-1.2 s; the aggregate's ``while`` 2.0-3.0 s with a fusion 2.1-2.5
    inside it, its sort 3.0-4.5, a copy without a name in the same program 4.5-4.6 (given to the scope by
    ``device_events``); another program's fusion 5.0-5.9, under no scope.  Chip 1: the calendar's sort alone."""
    device_s = load_module("layer_metrics", "ts_device_s")
    cal, agg = "ts/calendar_counts", "ts/segment_aggregate"
    devices = {
        "/device:TPU:0": [(1.0, 1.2, cal), (2.0, 3.0, agg), (2.1, 2.5, agg), (3.0, 4.5, agg), (4.5, 4.6, agg), (5.0, 5.9, "")],
        "/device:TPU:1": [(1.0, 1.2, cal)],
    }
    assert device_s.scope_seconds(devices) == {cal: pytest.approx(0.2), agg: pytest.approx(2.6 / 2)}
    assert device_s.scope_seconds({"/device:TPU:0": devices["/device:TPU:0"][5:]}) == {} == device_s.scope_seconds({})
    assert device_s.read({"trace_dir": ""}) is None and device_s.read({}) is None
    assert device_s.read({"ts_scope_seconds": {cal: 0.2, agg: 1.3}}) == pytest.approx(1.5)
    assert device_s._scope("jit(_ts_num_viz_program)/jit(main)/ts/segment_aggregate/while/body/dot_general:") == agg
    assert device_s._scope("jit(calendar_counts)/ts/calendar_counts/sort:") == cal
    assert device_s._scope("jit(f)/jit(main)/nots/segment_aggregate_x/add:") is None
    hbm = load_module("layer_metrics", "ts_agg_hbm_pct")
    by_hand = 2 * (4_194_304 * 16 * 5 + 4_194_304 * 5 + 6 * 4 * 16 * 48)
    assert hbm.aggregate_bytes(4_194_304, 16, 48) * 2 == by_hand == hbm.stage_bytes(RECORDED)
    assert hbm.stage_bytes([r for r in RECORDED if r["name"] != "ts/viz/num"]) == 0
    assert hbm.share_pct(819e9 * 0.01, 2.0, 819e9) == pytest.approx(0.5)
    # no trace, no scope, no counts, or a device the peaks do not know (the CPU): nothing, and no error
    assert hbm.read({"trace_dir": "", "traced": _pass(RECORDED)}) is None
    assert hbm.read({"ts_scope_seconds": {cal: 0.2}, "traced": _pass(RECORDED)}) is None
    assert hbm.read({"ts_scope_seconds": {agg: 1.3}, "traced": _pass([])}) is None
    assert hbm.read({"ts_scope_seconds": {agg: 1.3}, "traced": _pass(RECORDED)}) is None
    import jax

    class V5e:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a: [V5e()])
    monkeypatch.setattr(jax, "device_count", lambda *a: 1)
    assert hbm.read({"ts_scope_seconds": {agg: 1.3}, "traced": _pass(RECORDED)}) == pytest.approx(
        100.0 * by_hand / (1.3 * 819e9))


def test_device_reader_on_the_traces_recorded_on_the_chip():
    device_s = load_module("layer_metrics", "ts_device_s")
    # PR 24's recording: no operation names a scope, and the events are ProfileData's
    old = device_s.device_events(os.path.join(ROOT, "tests", "benchmark", "recorded", "tiny_v5e.xplane.pb"))
    assert list(old) == ["/device:TPU:0"] and len(old["/device:TPU:0"]) == 15
    assert {e[2] for e in old["/device:TPU:0"]} == {""} and device_s.scope_seconds(old) == {}
    assert old["/device:TPU:0"][0][0] == pytest.approx(0.048703777, abs=1e-8)
    # this PR's: one call of each of the two programs at 65,536 rows
    with open(RECORDED_TRACE + ".json") as f:
        told = json.load(f)
    got = device_s.scope_seconds(device_s.device_events(RECORDED_TRACE))
    assert set(got) == set(device_s.SCOPES)
    for scope, seconds in told["scope_seconds"].items():
        assert got[scope] == pytest.approx(seconds, rel=1e-9)
    assert sum(got.values()) <= told["busy_s"] * (1 + 1e-9)


def test_readers_on_the_live_run(run):
    rows = run["passes"][-1]["manifest"]["phases"]
    node = next(r for r in rows if r["name"] == "timeseries_analyzer/inspection")
    one = dict(run, passes=run["passes"][-1:])
    assert load_module("layer_metrics", "ts_inspect_s").read(one) == pytest.approx(node["end_s"] - node["start_s"])
    assert load_module("layer_metrics", "ts_host_rows").read(one) == 0
    hbm = load_module("layer_metrics", "ts_agg_hbm_pct")
    assert hbm.stage_bytes(rows) == 2 * hbm.aggregate_bytes(PADDED, 16, 48)
