"""CPU rehearsal of the four-chip cell ``income_1m_x4.stats``: the pipeline
driver at 2,000 rows on a 4-device mesh cut from the suite's 8 virtual
devices is ``correct`` against float64 pandas and the bfloat16 control is
not; a pass there computes one describe and copies no table from chip to
chip; and the three per-layer readers the cell brings (``collective_s``,
``chip_busy_max_s``, ``replica_d2d_gb``) on what such a pass left, on
hand-built rows and on a hand-built four-plane event list.  One file, one
process, no child."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.drivers import pipeline  # noqa: E402
from benchmark.harness import trace_reduce  # noqa: E402
from benchmark.harness.names import load_module  # noqa: E402

ROWS = 2000
CELL = "income_1m_x4.stats"


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config(bench):
    entry = next(c for c in bench["configs"] if c["name"] == "income_1m_x4")
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traffic():
    with open(os.path.join(ROOT, "benchmark", "traffic", "stats.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def run4(bench, config, traffic, tmp_path_factory):
    """One run of the driver as run.py would start it, at 2,000 rows, with the
    runtime on the first four of the suite's eight devices."""
    import jax

    from anovos_tpu.shared.runtime import init_runtime

    cell = {
        "workload": CELL, "config": dict(config, rows=ROWS, baseline_rows=ROWS // 4), "traffic": traffic,
        "traffic_yaml": os.path.join(ROOT, "benchmark", "traffic", "stats.yaml"),
        "work_dir": str(tmp_path_factory.mktemp("income_1m_x4")), "seed": 2**31 + 27, "seconds": 1.0,
        "trace": False, "platform": "cpu", "t_start": bench_run.T_START, "say": lambda msg: None,
    }
    init_runtime(devices=jax.devices()[:4])
    try:
        return pipeline.run(cell)
    finally:
        init_runtime()  # the suite's 8-device mesh again


# ------------------------------------------------------- the data files ----
def test_the_configuration_is_income_400k_on_four_chips(bench, config):
    entry = next(c for c in bench["configs"] if c["name"] == "income_1m_x4")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("income_1m_x4", "stats", 4)
    assert config["chips"] == 4 and config["rows"] == 1_000_000 and config["baseline_rows"] == 250_000
    assert config["columns"] == 24 and config["driver"] == "pipeline" and config["dataset"] == {"module": "income"}
    assert config["source"] == entry["source"] and "spark-submit.sh" in entry["source"]
    assert config["reduced"] == entry["reduced"] == ["num-executors"] and config["num-executors"] == 4
    with open(os.path.join(ROOT, "benchmark", "configs", "income_400k.json")) as f:
        assert config["guarantees"] == json.load(f)["guarantees"]  # word for word
    for name in ("collective_s", "chip_busy_max_s", "replica_d2d_gb"):
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL] and metric["moves"] == "pass_s"
    assert "fresh_pass_s" not in {m["name"] for m in bench["end_to_end"]
                                  if bench_run._in_cell(m, CELL, set())}


# ----------------------------------------- the driver on a 4-device mesh ----
def test_the_driver_on_four_devices_is_correct(run4, bench):
    assert run4["correct"], run4["checks"]
    assert run4["failed"] == 0 and run4["attempted"] == 1 + len(run4["passes"]) >= 2
    checks = {r["name"]: r for r in run4["checks"]}
    assert set(checks) >= {"rows", "count", "mean", "stddev", "min", "max", "median", "distinct",
                           "files_with_other_bytes"}
    assert checks["files_with_other_bytes"]["value"] == 0  # every pass of the process the same bytes
    line = bench_run.report(bench, CELL, run4, False)
    assert set(line["metrics"]) == {"pass_s", "rows_per_s", "setup_s"} and line["correct"] is True


def test_a_pass_on_four_devices_computes_one_describe_and_copies_no_table(run4):
    for p in [run4["fresh"]] + run4["passes"]:
        sched = p["manifest"]["scheduler"]
        assert sched["n_devices"] == 4
        assert {n["lane"] for n in sched["nodes"].values()} == {"mesh"}
        nodes = [r for r in p["manifest"]["phases"] if r["parent"] == "dag"]
        assert len(nodes) == 7 and sum(r["counts"].get("describe_computed", 0) for r in nodes) == 1
        assert sum("describe_computed" in r["counts"] for r in nodes) == 6  # global_summary needs none
        assert not [r for r in p["manifest"]["phases"] if r["name"] == "place/d2d"]
        h2d = [r for r in p["manifest"]["phases"] if r["name"] == "ingest/h2d"]
        assert h2d and all(r["counts"]["shards"] % 4 == 0 and r["counts"]["shards"] > 0 for r in h2d)


@pytest.mark.parametrize("seed", [27, 2**31 + 2027, 4001])
def test_the_control_in_bfloat16_is_not_correct_under_the_configurations_tolerances(config, traffic, tmp_path, seed):
    import yaml

    from benchmark.harness.frames import Frames

    data_dir = str(tmp_path / "d")
    load_module("datasets", config["dataset"]["module"]).generate(data_dir, seed, ["parquet"], rows=ROWS)
    with open(os.path.join(ROOT, "benchmark", "traffic", "stats.yaml")) as f:
        frames = Frames(pipeline._rebase(yaml.safe_load(f), "DATASET/", data_dir + "/"))
    summary, args = load_module("checks", "summary"), traffic["compare"]["summary"]
    tol = config["guarantees"]["tolerances"]
    ref = summary.reference(frames, args)
    assert all(r["ok"] for r in summary.compare(ref, ref, tol, args))
    rows = {r["name"]: r for r in summary.compare(summary.control(ref, frames, args), ref, tol, args)}
    assert not rows["mean"]["ok"] and rows["mean"]["value"] > 3
    assert rows["rows"]["ok"] and rows["count"]["ok"]


# ------------------------------------------------------------ the readers ----
def _read(name, run):
    return load_module("layer_metrics", name).read(run)


def test_the_readers_on_what_the_four_device_run_left(run4, bench):
    run = dict(run4, trace_dir="")  # no trace was taken
    assert _read("replica_d2d_gb", run) == 0.0  # the span's mechanism is there and no copy was made
    assert _read("collective_s", run) is None and _read("chip_busy_max_s", dict(run, trace={})) is None
    line = bench_run.report(bench, CELL, run, True)
    assert line["metrics"]["replica_d2d_gb"] == {"value": 0.0, "unit": "GB"}
    assert {"dag_s", "ingest_s", "after_dag_s", "ingest_h2d_gb", "window_compiles"} <= set(line["metrics"])
    assert not {"collective_s", "chip_busy_max_s", "device_busy_s", "pass_p95_s"} & set(line["metrics"])


def test_replica_d2d_gb_on_hand_built_rows():
    def row(name, parent, **counts):
        return {"name": name, "parent": parent, "start_s": 0.0, "end_s": 1.0, "thread": "t", "counts": counts}

    def run(rows):
        return {"passes": [{"wall_s": 1.0, "manifest": {"phases": rows}}]}

    tree = [row("run", None), row("dag", "run"), row("stats_generator/a", "dag", describe_computed=1),
            row("place/d2d", "stats_generator/a", bytes=150_000_000, chips=1),
            row("stats_generator/b", "dag"), row("place/d2d", "stats_generator/b", bytes=50_000_000, chips=1),
            row("ingest/h2d", "ingest", bytes=7)]  # another span's bytes are not a copy between chips
    assert _read("replica_d2d_gb", run(tree)) == pytest.approx(0.2)
    assert _read("replica_d2d_gb", run([r for r in tree if r["name"] != "place/d2d"])) == 0.0
    # a program from before the span: no scheduler node among its phases, and no manifest at all
    assert _read("replica_d2d_gb", run([row("run", None), row("dag", "run")])) is None
    assert _read("replica_d2d_gb", run([])) is None and _read("replica_d2d_gb", {"passes": []}) is None


def test_collective_s_and_chip_busy_max_s_on_a_hand_built_four_plane_event_list():
    """Four chips, one program.  Every chip: ``fusion.1`` 0.0-1.0, then
    ``all-to-all.2`` from 1.0 until the slowest chip has arrived (chip 3 is
    0.4 s late: its fusion runs 0.0-1.4), all leaving at 1.6; a ``while.5``
    2.0-3.0 whose body holds ``all-reduce-start.6`` 2.1-2.2 and
    ``all-reduce-done.6`` 2.5-2.7; chip 0 alone then sorts 3.0-4.0.  By hand:
    collective self time on chips 0-2 = 0.6 + 0.1 + 0.2 = 0.9, on chip 3 = 0.2 +
    0.3 = 0.5, mean 0.8; the ``while`` keeps its own 0.7 and is no collective.
    Busy: chip 0 = 1.6 + 1.0 + 1.0 = 3.6, the others 2.6: mean 2.85, max 3.6."""
    def chip(late=0.0, sort=False):
        events = [(0.0, 1.0 + late, "jit_f/fusion.1"), (1.0 + late, 1.6, "jit_f/all-to-all.2"),
                  (2.0, 3.0, "jit_f/while.5"), (2.1, 2.2, "jit_f/all-reduce-start.6"),
                  (2.5, 2.7, "jit_f/all-reduce-done.6")]
        return events + ([(3.0, 4.0, "jit_f/sort.7")] if sort else [])

    devices = {"/device:TPU:0": chip(sort=True), "/device:TPU:1": chip(), "/device:TPU:2": chip(),
               "/device:TPU:3": chip(late=0.4)}
    collective = load_module("layer_metrics", "collective_s")
    assert collective.collective_seconds(devices) == pytest.approx(0.8)
    assert collective.collective_seconds({"/device:TPU:0": [(0.0, 1.0, "jit_f/fusion.1")]}) == 0.0
    assert collective.collective_seconds({}) is None
    reduced = trace_reduce.reduce({"devices": devices, "host": [], "window": (0.0, 5.0)})
    assert reduced["busy_s"] == pytest.approx(2.85)
    assert _read("chip_busy_max_s", {"trace": reduced}) == pytest.approx(3.6)
    assert _read("chip_busy_max_s", {"trace": {}}) is None and _read("chip_busy_max_s", {}) is None
    assert _read("collective_s", {"trace_dir": ""}) is None
