"""CPU rehearsal of the benchmark (BENCHMARK.json, benchmark/): the contract
of the data files, the pipeline driver's fresh pass, window and check at
2,000 rows on the virtual mesh with ``platform="cpu"`` (run.py itself has no
CPU run: it must refuse), the control in bfloat16 and a broken timed path
coming out as not correct, and the trace reduction on a hand-built event
list.  One file, one process, no child."""

import copy
import json
import os
import re
import statistics
import sys

import pandas as pd
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.drivers import pipeline  # noqa: E402
from benchmark.harness import check, trace_reduce  # noqa: E402
from benchmark.harness.names import load_module  # noqa: E402

ROWS = 2000
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _cell(bench, workload, work_dir, seconds, seed=11):
    """What run.py hands the driver, at 2,000 rows on the CPU."""
    entry = next(w for w in bench["workloads"] if w["name"] == workload)
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    config["rows"], config["baseline_rows"] = ROWS, ROWS // 4
    with open(os.path.join(ROOT, "benchmark", "traffic", entry["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return {
        "workload": workload, "config": config, "traffic": traffic,
        "traffic_yaml": os.path.join(ROOT, "benchmark", "traffic", entry["traffic"] + ".yaml"),
        "work_dir": str(work_dir), "seed": seed, "seconds": seconds, "trace": False,
        "platform": "cpu", "t_start": bench_run.T_START, "say": lambda msg: None,
    }


@pytest.fixture(scope="module")
def runs(bench, tmp_path_factory):
    """One run of the driver per traffic mix the benchmark has a cell for."""
    out = {}
    for w in bench["workloads"]:
        if w["traffic"] not in out:
            seconds = 1.0 if w["traffic"] == "stats" else 0.0
            out[w["traffic"]] = (w["name"], pipeline.run(
                _cell(bench, w["name"], tmp_path_factory.mktemp(w["traffic"]), seconds)))
    return out


# ------------------------------------------------------------ contract ----
def test_benchmark_json_keeps_to_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    for entry, keys in ((bench["configs"], {"name", "source", "file", "reduced", "why"}),
                        (bench["workloads"], {"name", "config", "traffic", "chips", "why"})):
        for e in entry:
            assert set(e) == keys, e
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}, m
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}, m
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in bench[k]]
    for n in names + [w["traffic"] for w in bench["workloads"]]:
        assert NAME.match(n), n
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in bench[k]}) == len(bench[k])
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher"), m
    for x in bench["configs"] + bench["workloads"]:
        for key in ("why", "source"):
            assert 1 <= len(x.get(key, "x")) <= 200 and "\n" not in x.get(key, "")
    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(1, len(bench["workloads"]) // 2)
    assert len(json.dumps(bench)) < 64 * 1024


def test_every_name_in_benchmark_json_has_its_files(bench):
    under = tuple(p.rstrip("/") + "/" for p in bench["paths"])
    configs = {c["name"]: c for c in bench["configs"]}
    assert len({c["file"] for c in bench["configs"]}) == len(configs)
    for c in bench["configs"]:
        assert c["file"].startswith(under)
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert os.path.exists(os.path.join(ROOT, "benchmark", "drivers", cfg["driver"] + ".py"))
        assert os.path.exists(os.path.join(ROOT, "benchmark", "datasets", cfg["dataset"]["module"] + ".py"))
        assert cfg["reduced"] == c["reduced"] and cfg["guarantees"]["tolerances"]
    used = set()
    for w in bench["workloads"]:
        used.add(w["config"])
        assert w["chips"] == json.load(open(os.path.join(ROOT, configs[w["config"]]["file"])))["chips"]
        for ext in (".json", ".yaml"):
            assert os.path.exists(os.path.join(ROOT, "benchmark", "traffic", w["traffic"] + ext))
    assert used == set(configs)  # each configuration is used by some cell
    assert len({(w["config"], w["traffic"]) for w in bench["workloads"]}) == len(bench["workloads"])
    for m in bench["per_layer"]:
        assert callable(load_module("layer_metrics", m["name"]).read)
    for rel in bench["command"][1:]:
        assert rel.startswith(under) and os.path.exists(os.path.join(ROOT, rel))


def test_every_layer_metric_moves_a_metric_its_cells_report(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    reports = {w: {n for n, m in e2e.items() if bench_run._in_cell(m, w, set())} for w in cells}
    layers = {}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != m["name"], m
        for w in m.get("workloads", []):  # a listed cell has to report what the metric moves
            assert w in cells and m["moves"] in reports[w], (m["name"], w)
        assert any(bench_run._in_cell(m, w, reports[w]) for w in cells), m  # read somewhere
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())  # one spelling per layer
    for w in cells:  # every cell reports set-up, another end-to-end metric and a per-layer one
        assert "setup_s" in reports[w] and len(reports[w]) >= 2
        assert any(bench_run._in_cell(m, w, reports[w]) for m in bench["per_layer"])


def test_traffic_yaml_is_the_shipped_config_but_for_drift(bench):
    import yaml

    with open(os.path.join(ROOT, "benchmark", "traffic", "full.yaml")) as f:
        full = yaml.safe_load(f)
    with open(os.path.join(ROOT, "config", "configs_full.yaml")) as f:
        shipped = pipeline._rebase(yaml.safe_load(f), "data/income_dataset/", "DATASET/")
    drift = shipped["drift_detector"]["drift_statistics"]
    drift["source_dataset"]["read_dataset"]["file_path"] = "DATASET/source"
    drift["configs"]["use_sampling"] = False
    assert full == shipped
    with open(os.path.join(ROOT, "benchmark", "traffic", "stats.yaml")) as f:
        assert yaml.safe_load(f) == {k: full[k] for k in ("input_dataset", "stats_generator", "write_stats")}


def test_run_py_refuses_a_platform_other_than_tpu(bench, capsys, monkeypatch):
    import jax  # noqa: F401  before main() names its cache directory: this process must not write CPU entries there

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)  # main() sets it: restore
    for w in bench["workloads"]:
        assert bench_run.main(["--workload", w["name"], "--seed", "3", "--seconds", "1"]) != 0
    assert bench_run.main(["--workload", "no_such_cell", "--seed", "3", "--seconds", "1"]) != 0
    out = capsys.readouterr()
    assert out.out == ""  # no result line, nothing done
    assert "no CPU run" in out.err


def test_generator_is_the_programs_generator_and_takes_a_large_seed(tmp_path):
    """The copy draws what synthetic.py draws; only the category lists differ:
    here they are the public dataset's, every category of it."""
    from anovos_tpu.data_ingest import synthetic

    income = load_module("datasets", "income")
    longer = ["workclass", "education", "marital-status", "occupation", "native-country"]
    for args in ((500, 7), (300, 7, 0.15)):
        ours, theirs = income.synthesize(*args), synthetic.synthesize(*args)
        pd.testing.assert_frame_equal(ours.drop(columns=longer), theirs.drop(columns=longer))
        for c in longer:  # the same draws: nulls in the same rows
            assert ours[c].isna().equals(theirs[c].isna())
    assert [len(x) for x in (income._WORKCLASS, income._EDUCATION, income._MARITAL, income._OCCUPATION,
                             income._RELATIONSHIP, income._RACE, income._COUNTRY)] == [8, 16, 7, 14, 6, 5, 41]
    wide = income.synthesize(20000, 3)
    assert wide["native-country"].nunique() == 41 and wide["education"].nunique() == 16
    big = 2**31 + 12345
    assert not income.synthesize(200, big).equals(income.synthesize(200, big + 1))
    assert income.synthesize(200, big).equals(income.synthesize(200, big))
    assert income.synthesize(10, 1).shape == (10, 24)
    income.generate(str(tmp_path / "d"), big, ["parquet"], rows=100)
    assert sorted(os.listdir(tmp_path / "d")) == ["parquet"]
    with pytest.raises(ValueError):
        income.generate(str(tmp_path / "d"), 1, ["avro"], rows=10)


# ---------------------------------------------------- driver, on the CPU ----
@pytest.mark.parametrize("mix", ["full", "stats"])
def test_driver_runs_fresh_pass_window_and_check(runs, bench, mix):
    if mix not in runs:
        pytest.skip(f"no cell with the traffic mix {mix}")
    workload, run = runs[mix]
    assert run["correct"], run["checks"]
    assert run["failed"] == 0 and run["attempted"] == 1 + len(run["passes"]) >= 2
    assert {r["name"] for r in run["checks"]} >= {"rows", "count", "mean", "stddev", "min", "max",
                                                  "median", "distinct", "files_with_other_bytes"}
    if mix == "full":  # an answer of every block of the pipeline
        assert {r["name"] for r in run["checks"]} >= {
            "duplicates", "null_rows", "invalid", "unique", "mode_rows", "upper_outliers", "missing",
            "correlation", "iv", "ig", "psi", "si_mean", "si_stddev", "si_kurtosis", "sqrt", "bins",
            "event_rate", "final_rows", "ts_daily", "geo_counts", "geo_mean"}
        assert len(run["passes"][-1]["digest"]) > 100  # every file a pass leaves is held to its bytes
    assert not any(rel.endswith("run_manifest.json") for rel in run["passes"][-1]["digest"])
    m = run["metrics"]
    assert m["fresh_pass_s"] > m["pass_s"] > 0 and m["setup_s"] > m["fresh_pass_s"]
    assert m["rows_per_s"] == pytest.approx(ROWS * len(run["passes"]) / sum(
        p["end"] - p["start"] for p in run["passes"]), rel=0.5)
    # the result line for an untraced run: the cell's end-to-end metrics, all of them
    line = bench_run.report(bench, workload, run, False)
    assert set(line["metrics"]) == {m["name"] for m in bench["end_to_end"]
                                    if workload in m.get("workloads", [workload])}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["correct"] is True and line["attempted"] == run["attempted"]


@pytest.mark.parametrize("mix", ["full", "stats"])
def test_layer_metric_readers_read_the_manifests(runs, bench, mix):
    if mix not in runs:
        pytest.skip(f"no cell with the traffic mix {mix}")
    workload, run = runs[mix]
    run = dict(run, trace_dir="")  # no trace was taken: the device readers return nothing
    line = bench_run.report(bench, workload, run, True)
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert {"window_compiles", "outside_dag_s", "dag_s", "overlap_x", "slowest_block_s"} <= set(got)
    assert not {"device_busy_s", "device_idle_share"} & set(got) and "breakdown" not in line
    assert got["fresh_programs"] > 0
    walls = [p["wall_s"] for p in run["passes"]]
    assert 0 < got["slowest_block_s"] <= got["dag_s"] < max(walls)
    assert got["outside_dag_s"] > 0 and got["overlap_x"] >= 1.0
    assert got["outside_dag_s"] + got["dag_s"] == pytest.approx(statistics.median(walls), rel=0.3)
    assert "pass_p95_s" not in got  # fewer than twenty passes: nothing to read


def test_pass_p95_reads_a_window_of_twenty_passes_or_more():
    read = load_module("layer_metrics", "pass_p95_s").read
    walls = [1.0] * 19 + [3.0] + [1.0] * 20
    assert read({"passes": [{"wall_s": w} for w in walls]}) == pytest.approx(1.0)
    assert read({"passes": [{"wall_s": w} for w in [1.0] * 17 + [3.0] * 3]}) == pytest.approx(3.0)
    assert read({"passes": [{"wall_s": 1.0}] * 19}) is None


def test_clean_run_catches_degradation_and_missing_artifacts(runs, tmp_path):
    _, run = runs.get("full") or runs["stats"]
    last = run["passes"][-1]
    with open(os.path.join(ROOT, "benchmark", "traffic", "full.json")) as f:
        traffic = json.load(f)
    assert any("backend" in b for b in check.clean_run(last["manifest"], last["out_dir"], traffic, "tpu"))
    broken = copy.deepcopy(last["manifest"])
    broken["resilience"]["degraded_sections"] = {"drift_detector": "boom"}
    broken["resilience"]["retries"] = 1
    broken["resilience"]["failovers"] = 2
    bad = check.clean_run(broken, str(tmp_path), traffic, "cpu")
    assert any("degraded" in b for b in bad) and any("retries" in b for b in bad)
    assert any("failovers" in b for b in bad)
    assert any("ml_anovos_report.html" in b for b in bad)
    assert any("final_dataset" in b for b in bad)


# ------------------------------- correct has to be able to come out false ----
def _break_table(out_dir, traffic, column, factor):
    rel = traffic["tables"]["measures_of_centralTendency"]
    df = check.table(out_dir, rel)
    df.loc[df["attribute"] == column, "mean"] *= factor
    df.to_parquet(os.path.join(out_dir, rel), index=False)


@pytest.mark.parametrize("broken_call,name", [(-1, "mean"), (1, "files_with_other_bytes")])
def test_a_broken_timed_path_comes_out_not_correct(bench, tmp_path, monkeypatch, broken_call, name):
    """The rest of a run without the look for a chip, the timed path broken
    underneath: an answer altered where a pass leaves it.  Altered beyond its
    tolerance in the last pass of the window, the comparison with the
    reference fails; altered within it in the first (fresh) pass, only the
    bytes differ, and that alone makes the run not correct."""
    from anovos_tpu import workflow

    workload = next(w["name"] for w in bench["workloads"] if w["traffic"] == "stats")
    cell = _cell(bench, workload, tmp_path, seconds=0.0)
    real, calls = workflow.run, []

    def broken_run(config_path, run_type="local", *a, **k):
        real(config_path, run_type, *a, **k)
        calls.append(os.getcwd())
        if broken_call == -1 and len(calls) == 2:  # the last pass of a one-pass window
            _break_table(os.getcwd(), cell["traffic"], "age", 1.001)
        if len(calls) == broken_call:
            _break_table(os.getcwd(), cell["traffic"], "fnlwgt", 1 + 1e-6)

    monkeypatch.setattr(workflow, "run", broken_run)
    run = pipeline.run(cell)
    assert len(calls) == 2 and run["failed"] == 0
    failing = [r["name"] for r in run["checks"] if not r["ok"]]
    assert run["correct"] is False and name in failing
    if broken_call == 1:
        assert failing == ["files_with_other_bytes"]
    line = bench_run.report(bench, workload, run, False)
    assert line["correct"] is False


def test_a_pass_that_raises_is_a_failed_pass(bench, tmp_path, monkeypatch):
    from anovos_tpu import workflow

    workload = next(w["name"] for w in bench["workloads"] if w["traffic"] == "stats")

    def raising_run(*a, **k):
        raise RuntimeError("no such device")

    monkeypatch.setattr(workflow, "run", raising_run)
    run = pipeline.run(_cell(bench, workload, tmp_path, seconds=0.0))
    assert run["correct"] is False and run["failed"] == 1 and run["attempted"] == 1


def _frames(tmp_path, seed, parts, mix):
    import yaml

    from benchmark.harness.frames import Frames

    data_dir = str(tmp_path / "d")
    load_module("datasets", "income").generate(data_dir, seed, parts, rows=ROWS)
    with open(os.path.join(ROOT, "benchmark", "traffic", mix + ".yaml")) as f:
        return Frames(pipeline._rebase(yaml.safe_load(f), "DATASET/", data_dir + "/"))


@pytest.mark.parametrize("seed", [5, 2**31 + 7, 99])
def test_the_control_in_bfloat16_fails_and_the_reference_passes(bench, tmp_path, seed):
    """The control at a size a test can hold: the float64 reference's summary
    computed from the table in bfloat16 must miss a tolerance; the reference
    compared with itself must not."""
    frames = _frames(tmp_path, seed, ["parquet"], "stats")
    with open(os.path.join(ROOT, "benchmark", "configs", "income_32k.json")) as f:
        tol = json.load(f)["guarantees"]["tolerances"]
    with open(os.path.join(ROOT, "benchmark", "traffic", "stats.json")) as f:
        args = json.load(f)["compare"]["summary"]
    summary = load_module("checks", "summary")
    ref = summary.reference(frames, args)
    assert ref["rows"] == ROWS
    assert all(r["ok"] for r in summary.compare(ref, ref, tol, args))
    rows = {r["name"]: r for r in summary.compare(summary.control(ref, frames, args), ref, tol, args)}
    assert not rows["mean"]["ok"] and rows["mean"]["value"] > 3
    assert not rows["min"]["ok"] or not rows["max"]["ok"]
    assert rows["rows"]["ok"] and rows["count"]["ok"]  # counts are no matter of precision
    # a lost column fails too, it is not skipped
    lost = dict(ref, summary=ref["summary"].drop(index="age"))
    assert not all(r["ok"] for r in summary.compare(lost, ref, tol, args))


def _nudge(x):
    """An answer moved by more than any tolerance: a number by 1 % and 0.01,
    a count by one, a label by a character, a table or dict in each of its entries."""
    if isinstance(x, dict):
        return {k: _nudge(v) for k, v in x.items()}
    if isinstance(x, (pd.Series, pd.DataFrame)):
        num = x.apply(pd.to_numeric, errors="coerce") if isinstance(x, pd.DataFrame) else pd.to_numeric(x, errors="coerce")
        return (num * 1.01 + 0.01).where(num.notna(), x)
    return x + "?" if isinstance(x, str) else x + 1


@pytest.mark.parametrize("name", ["summary", "distinct", "duplicates", "quality", "association", "psi",
                                  "stability", "transform", "timeseries", "geospatial"])
def test_each_comparison_passes_on_what_a_pass_left_and_fails_when_it_is_moved(runs, name):
    """Every comparison file of the ``full`` mix, on the last pass of the CPU
    run: ok as the pass left it, and every one of its rows not ok once each
    answer is moved."""
    import yaml

    from benchmark.harness.frames import Frames

    if "full" not in runs:
        pytest.skip("no cell with the traffic mix full")
    _, run = runs["full"]
    last = run["passes"][-1]["out_dir"]
    with open(os.path.join(ROOT, "benchmark", "traffic", "full.json")) as f:
        traffic = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "configs", "income_32k.json")) as f:
        tol = json.load(f)["guarantees"]["tolerances"]
    with open(os.path.join(os.path.dirname(last), "pipeline.yaml")) as f:
        frames = Frames(yaml.safe_load(f))
    mod, args = load_module("checks", name), traffic["compare"][name]
    ans, ref = mod.read(last, traffic, args), mod.reference(frames, args)
    assert all(r["ok"] for r in mod.compare(ans, ref, tol, args))
    moved = mod.compare(_nudge(ans), ref, tol, args)
    assert moved and not any(r["ok"] for r in moved), [r["name"] for r in moved if r["ok"]]


# ------------------------------------------------------ trace reduction ----
def test_trace_reduction_on_a_hand_built_event_list():
    """Two chips.  Chip 0: a ``while`` from 1.0 to 3.0 whose body runs
    ``fusion.1`` 1.2-1.7 and ``sort.2`` 1.5-2.5 (they overlap by 0.2 s), then
    ``copy.3`` 6.0-6.5.  Chip 1: ``fusion.1`` 1.0-2.0.  Session 0-10 s.
    By hand: chip 0 busy = (3.0-1.0) + 0.5 = 2.5, chip 1 busy = 1.0, mean 1.75;
    idle share 1 - 1.75/10.  Self times on chip 0: while = 2.0 - (1.7-1.2) -
    (2.5-1.7) = 0.7 (the overlap goes to the later-started sort), fusion.1 =
    0.3, sort.2 = 1.0, copy.3 = 0.5; fusion.1 over both chips 1.3.  Gaps of
    chip 0: 0-1 (before the first node: outside_dag), 3-6 (middle 4.5: inside
    node_b 4-5 and node_a 2-8, the shorter wins), 6.5-10 (middle 8.25: after
    node_a, inside node_c 8.2-8.4)."""
    trace = {
        "devices": {
            "/device:TPU:0": [(1.0, 3.0, "while"), (1.2, 1.7, "fusion.1"), (1.5, 2.5, "sort.2"),
                              (6.0, 6.5, "copy.3")],
            "/device:TPU:1": [(1.0, 2.0, "fusion.1")],
        },
        "host": [(2.0, 8.0, "node_a"), (4.0, 5.0, "node_b"), (8.2, 8.4, "node_c")],
        "window": (0.0, 10.0),
    }
    r = trace_reduce.reduce(trace)
    assert r["per_chip_busy_s"] == pytest.approx([2.5, 1.0])
    assert r["busy_s"] == pytest.approx(1.75) and r["window_s"] == 10.0
    assert r["idle_share"] == pytest.approx(0.825)
    ops = dict(r["device_ops"])
    assert ops == pytest.approx({"fusion.1": 1.3, "sort.2": 1.0, "while": 0.7, "copy.3": 0.5})
    assert [n for n, _ in r["device_ops"]] == ["fusion.1", "sort.2", "while", "copy.3"]
    assert r["idle_gaps"] == [["node_c", pytest.approx(3.5)], ["node_b", pytest.approx(3.0)],
                              ["outside_dag", pytest.approx(1.0)]]
    # between two nodes, inside the DAG's span, under no node: unattributed
    trace["host"] = [(0.6, 0.9, "node_a"), (9.0, 9.5, "node_c")]
    assert [n for n, _ in trace_reduce.reduce(trace)["idle_gaps"]] == [
        "unattributed", "unattributed", "outside_dag"]
    # without a session window: first event to last
    trace["window"] = None
    assert trace_reduce.reduce(trace)["window_s"] == pytest.approx(9.5 - 0.6)
    assert trace_reduce.reduce({"devices": {}, "host": [], "window": None}) == {}
    assert trace_reduce.union([(3, 4), (1, 2), (1.5, 3.5)]) == [[1, 4]]


def test_trace_loader_reads_a_recorded_v5e_trace():
    """A trace recorded on the chip; the file beside it says how, and what it
    holds as read by hand."""
    path = os.path.join(os.path.dirname(__file__), "recorded", "tiny_v5e.xplane.pb")
    with open(path + ".json") as f:
        by_hand = json.load(f)
    trace = trace_reduce.load(path, by_hand["host_names"])
    assert list(trace["devices"]) == ["/device:TPU:0"]
    ops = trace["devices"]["/device:TPU:0"]
    assert len(ops) == by_hand["device_events"]
    assert {n.split("/")[0] for _, _, n in ops} == set(by_hand["modules_ns"])
    assert "jit_sort/sort.4" in {n for _, _, n in ops}
    assert [h[2] for h in sorted(trace["host"])] == by_hand["host_names"]
    r = trace_reduce.reduce(trace)
    assert r["busy_s"] == pytest.approx(by_hand["busy_s"], rel=1e-3)
    modules_s = sum(d for _, d in by_hand["modules_ns"].values()) * 1e-9
    assert 0.99 * modules_s < r["busy_s"] <= modules_s
    assert r["window_s"] == pytest.approx(by_hand["window_s"], rel=1e-9)
    assert r["device_ops"][0][0] == "jit_sort/sort.4"
    assert sum(s for _, s in trace_reduce.self_times(ops).items()) == pytest.approx(r["busy_s"])
    for got, want in zip(r["idle_gaps"], by_hand["idle_gaps"]):
        assert got[0] == want[0] and got[1] == pytest.approx(want[1], abs=1e-6)
    # no annotation asked for: every gap lies outside the DAG
    bare = trace_reduce.reduce(trace_reduce.load(path))
    assert {n for n, _ in bare["idle_gaps"]} == {"outside_dag"} and bare["busy_s"] == r["busy_s"]
