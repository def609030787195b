"""CPU rehearsal of the cell ``epsilon_2k.ae_latent``.  The configuration's
entry and file agree; the generator keeps the source's shape at its full
2,001 columns (seeded, no null, no string column, rows of unit length); the
pipeline driver runs the mix and is ``correct`` against the plain reference;
the control (every product's operands in an 8-bit float), a fit with an epoch
fewer, BatchNorm's running statistics used in training and Adam without its
bias correction are each not correct; the five readers the cell brings read
what such a pass left, hand-built rows and a hand-built event list; and the
test PR 41 lacked: every per-layer metric that ``run._in_cell`` admits to the
cell is in the traced line.

The driver's run is at **64 features x 16,384 rows**, not the source's 2,000
x 2,000: at the full width one pass of the driver took 95 s on the sandbox's
CPU (59 s fresh, 35 s of it compiling), over the minute the issue allows, and
at 2,000 rows ten epochs are 60 steps, after which BatchNorm's running
statistics (momentum 0.99) are still 0.55 their initial values and no
validation loss falls under 1.  510 steps at width 64 take 2 s.  One file,
one process, no child."""

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

CELL = "epsilon_2k.ae_latent"
FEATURES, LATENT, ROWS, PADDED = 64, 32, 16_384, 16_384
READERS = ("ae_fit_s", "ae_apply_s", "ae_fit_device_s", "ae_fit_mfu_pct", "ae_fit_hbm_pct")
ACCEPTED_CELLS = ["income_32k.full", "income_400k.stats", "income_32k.stats", "income_1m_x4.stats",
                  "tpch_lineitem.stats", "criteo_display.encode", "nyc_taxi.ts_inspect"]

epsilon = load_module("datasets", "epsilon")
check = load_module("checks", "ae_latent")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs", "epsilon_2k.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traffic():
    with open(os.path.join(ROOT, "benchmark", "traffic", "ae_latent.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def run(config, traffic, tmp_path_factory):
    """One run of the driver as run.py would start it, at the stated width on the CPU."""
    small = dict(config, rows=ROWS, dataset=dict(config["dataset"], features=FEATURES))
    return pipeline.run({
        "workload": CELL, "config": small, "traffic": traffic,
        "traffic_yaml": os.path.join(ROOT, "benchmark", "traffic", "ae_latent.yaml"),
        "work_dir": str(tmp_path_factory.mktemp("epsilon_2k")), "seed": 2**31 + 42, "seconds": 0.0,
        "trace": False, "platform": "cpu", "t_start": bench_run.T_START, "say": lambda msg: None,
    })


@pytest.fixture(scope="module")
def compared(run, traffic):
    """What the last pass left and the reference's answers, as the driver compared them."""
    last = run["passes"][-1]["out_dir"]
    with open(os.path.join(os.path.dirname(last), "pipeline.yaml")) as f:
        frames = Frames(yaml.safe_load(f))
    args = traffic["compare"]["ae_latent"]
    return {"ans": check.read(last, traffic, args), "ref": check.reference(frames, args), "args": args}


# ------------------------------------------------------- the data files ----
def test_the_configuration_states_the_source_its_cuts_and_the_model(bench, config, traffic):
    entry = next(c for c in bench["configs"] if c["name"] == "epsilon_2k")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("epsilon_2k", "ae_latent", 1)
    assert [w["name"] for w in bench["workloads"] if w["config"] == "epsilon_2k"] == [CELL]  # no second cell
    assert config["source"] == entry["source"] and len(entry["source"]) <= 200 and len(cell["why"]) <= 200
    assert "PASCAL LSLC 2008 epsilon (LIBSVM epsilon_normalized): 400,000 x 2,000 + label" in entry["source"]
    assert "autoencoder_latentFeatures" in entry["source"]
    assert config["published"]["rows"] == epsilon.SOURCE_ROWS == 400_000
    assert config["published"]["features"] == epsilon.FEATURES == 2000 and config["columns"] == 2001
    assert config["rows"] in [400_000 // 2**j for j in range(5)]
    assert config["reduced"] == entry["reduced"] == ["rows", "epochs"]
    assert "400,000" in config["reduced_why"]["rows"] and "100" in config["reduced_why"]["epochs"]
    assert config["baseline_rows"] == 0 and config["chips"] == 1 and config["driver"] == "pipeline"
    assert config["dataset"] == {"module": "epsilon"} and len(config["assumed"]) >= 4
    # the model as it is run: no width cut, the steps a pass stated
    with open(os.path.join(ROOT, "benchmark", "traffic", "ae_latent.yaml")) as f:
        mix = yaml.safe_load(f)
    assert set(mix) == {"input_dataset", "transformers", "write_main"} and set(mix["input_dataset"]) == {"read_dataset"}
    ae = mix["transformers"]["numerical_latentFeatures"]["autoencoder_latentFeatures"]
    assert ae == {"list_of_cols": "all", "drop_cols": ["label"], "reduction_params": 0.5, "epochs": 10,
                  "batch_size": 256, "model_path": "model", "output_mode": "replace"}
    model = config["model"]
    counts = check.fit_arithmetic(config["rows"], 2000, 1000, ae["epochs"], ae["batch_size"])
    assert model["layers"] == "2000-4000-2000-1000-2000-4000-2000" and model["weights"] == 36_000_000
    assert (model["epochs"], model["batch_size"], model["latent"]) == (10, 256, 1000)
    assert (model["steps_per_pass"], model["fit_rows"], model["validation_rows"], model["trainable_parameters"]) == (
        counts["steps"], counts["fit_rows"], counts["val_rows"], counts["params"])
    assert model["steps_per_epoch"] * model["epochs"] == model["steps_per_pass"]
    assert model["flops_per_step"] == 6 * 256 * 36_000_000
    g = config["guarantees"]
    assert set(g["tolerances"]) == {"latent", "history"} and set(g["tolerances"]["latent"]) == {"scale_share"}
    assert all(k in g for k in ("all_rows", "durable", "repeatable", "precision", "initial_weights", "batch_order",
                                "tolerances_why"))
    assert "bf16" in g["precision"] and "PRNGKey(0)" in g["batch_order"] and "He-normal" in g["initial_weights"]
    assert traffic["dataset_parts"] == ["parquet"] and traffic["not_repeatable"] == ["output/obs/*"]
    assert "model/autoencoders_latentFeatures/model.npz" in traffic["artifacts"]
    assert traffic["tables"]["history"] == "model/autoencoders_latentFeatures/history.csv"
    e2e = {m["name"] for m in bench["end_to_end"] if bench_run._in_cell(m, CELL, set())}
    assert e2e == {"pass_s", "rows_per_s", "setup_s"}


def test_benchmark_json_appends_the_cell_its_readers_and_ingest_encode_s_list(bench):
    # by name and by place after what was there: a later PR appends its own
    names = [w["name"] for w in bench["workloads"]]
    assert names[:8] == ACCEPTED_CELLS + [CELL] and [c["name"] for c in bench["configs"]][6] == "epsilon_2k"
    assert sum(w["chips"] == 4 for w in bench["workloads"][:8]) == 1
    metrics = [m["name"] for m in bench["per_layer"]]
    at = metrics.index("ts_agg_hbm_pct")
    assert tuple(metrics[at + 1:at + 6]) == READERS
    ours = bench["per_layer"][at + 1:at + 6]
    layers = dict(zip(READERS, ("blocks", "blocks", "kernels", "kernels", "kernels")))
    sources = dict(zip(READERS, ("program_span", "program_span", "device_trace", "device_trace", "device_trace")))
    for m in ours:
        assert m["workloads"] == [CELL] and m["moves"] == "pass_s"
        assert (m["layer"], m["source"]) == (layers[m["name"]], sources[m["name"]])
        assert os.path.exists(os.path.join(ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
    assert [m["unit"] for m in ours] == ["s", "s", "s", "%", "%"]
    # the epsilon table has no string column, so no pass has an ingest/encode span: the metric is
    # held to the seven cells it had, as PR 34 held the describe's three to theirs
    encode = next(m for m in bench["per_layer"] if m["name"] == "ingest_encode_s")
    assert encode == {"name": "ingest_encode_s", "unit": "s", "better": "lower", "source": "program_span",
                      "layer": "ingest", "moves": "pass_s", "workloads": ACCEPTED_CELLS}
    # no metric that was there names the new cell, and the other eighteen without a list stay without one
    assert [m["name"] for m in bench["per_layer"][:at + 6] if CELL in m.get("workloads", ())] == list(READERS)
    assert sum("workloads" not in m for m in bench["per_layer"][:at + 1]) == 18


# ------------------------------------------------------- the generator ----
def test_generator_writes_the_sources_shape_at_its_full_width(tmp_path, monkeypatch):
    monkeypatch.setattr(epsilon, "ROWS_PER_PART", 120)
    epsilon.generate(str(tmp_path / "d"), 2**31 + 5, ["parquet"], rows=300, source_rows=7)
    assert sorted(os.listdir(tmp_path / "d")) == ["parquet"]
    files = sorted(os.listdir(tmp_path / "d" / "parquet"))
    assert [pq.read_metadata(str(tmp_path / "d" / "parquet" / f)).num_rows for f in files] == [120, 120, 60]
    table = pq.read_table(str(tmp_path / "d" / "parquet"))
    assert table.num_rows == 300 and table.num_columns == 2001 and table.schema.equals(epsilon.schema())
    assert table.column_names == ["label"] + [f"f{i}" for i in range(1, 2001)]
    assert str(table.schema.field("label").type) == "int32"
    assert {str(f.type) for f in table.schema if f.name != "label"} == {"float"}  # 32 bits, and no string column
    assert all(c.null_count == 0 for c in table.columns)
    df = table.to_pandas()
    assert set(df["label"].unique()) == {-1, 1} and 0.35 < (df["label"] == 1).mean() < 0.65
    x = df.drop(columns=["label"]).to_numpy(np.float64)
    assert np.abs(np.linalg.norm(x, axis=1) - 1).max() < 1e-6  # every row of unit length
    assert np.abs(x.mean(axis=0)).max() < 0.2 / np.sqrt(2000)  # every feature was standardised before
    assert epsilon.ROWS_PER_PART == 120 and -(-400_000 // 25_000) == 16
    with pytest.raises(ValueError):
        epsilon.generate(str(tmp_path / "d"), 1, ["source"], rows=10)


def test_generator_is_a_function_of_rows_and_seed_and_gives_something_to_learn(tmp_path):
    big = 2**31 + 12345
    a, b, c = (epsilon.synthesize(400, s, 200) for s in (big, big, big + 1))
    assert all(np.array_equal(a[k], b[k]) for k in a) and any(not np.array_equal(a[k], c[k]) for k in a)
    assert a["features"].dtype == np.float32 and a["features"].shape == (400, 200) and a["label"].dtype == np.int32
    epsilon.generate(str(tmp_path / "a"), big, ["parquet"], rows=500, features=40)
    epsilon.generate(str(tmp_path / "b"), big, ["parquet"], rows=500, features=40)
    assert pq.read_table(str(tmp_path / "a" / "parquet")).equals(pq.read_table(str(tmp_path / "b" / "parquet")))
    # a low-rank signal under the noise: an eighth of the directions carry most of the variance
    x = epsilon.synthesize(4000, 7, 400)["features"].astype(np.float64)
    spectrum = np.linalg.svd((x - x.mean(0)) / x.std(0), compute_uv=False) ** 2
    assert 0.6 < spectrum[:50].sum() / spectrum.sum() < 0.95


# ---------------------------------------------------- driver, on the CPU ----
def test_the_cell_is_correct_on_the_cpu_and_reports_its_metrics(run, bench):
    assert run["correct"], [r for r in run["checks"] if not r["ok"]]
    assert run["failed"] == 0 and run["attempted"] == 2
    assert [r["name"] for r in run["checks"]] == [
        "rows", "column_names", "label_rows_changed", "fit_counts", "model_shapes", "latent", "history_loss",
        "history_val_loss", "val_loss_last", "files_with_other_bytes"]
    line = bench_run.report(bench, CELL, run, False)
    assert set(line["metrics"]) == {"pass_s", "rows_per_s", "setup_s"} and line["correct"] is True
    by = {r["name"]: r for r in run["checks"]}
    assert by["latent"]["value"] < 0.01 and by["history_loss"]["value"] < 0.01  # f32 on the CPU: far inside
    assert by["val_loss_last"]["value"] < 0.5  # the model learnt: 510 steps


def test_the_traced_line_carries_every_metric_the_driver_admits_to_the_cell(run, bench):
    """PR 41 was refused for one name that its traced line lacked.  Off the chip there is no trace,
    so the metrics read from one are left aside; every other admitted metric has to be in the line."""
    traced = bench_run.report(bench, CELL, dict(run, trace_dir=""), True)["metrics"]
    reporting = {m["name"] for m in bench["end_to_end"] if bench_run._in_cell(m, CELL, set())}
    admitted = [m for m in bench["per_layer"] if bench_run._in_cell(m, CELL, reporting)]
    assert len(admitted) >= 18 + 5 and "ingest_encode_s" not in {m["name"] for m in admitted}
    from_trace = {m["name"] for m in admitted if m["source"] == "device_trace"}
    assert from_trace >= {"device_busy_s", "device_idle_share", "idle_unnamed_share", "ae_fit_device_s",
                          "ae_fit_mfu_pct", "ae_fit_hbm_pct"}
    # peak_hbm_gb is the device's own count of its memory, which the CPU backend does not keep
    host_side = {m["name"] for m in admitted} - from_trace - {"peak_hbm_gb"}
    assert host_side <= set(traced), sorted(host_side - set(traced))
    assert not set(traced) - {m["name"] for m in admitted}  # and nothing the driver did not ask for
    for m in admitted:  # no reader of a span or a counter returns None for want of one
        if m["name"] in host_side:
            assert load_module("layer_metrics", m["name"]).read(dict(run, trace={})) is not None, m["name"]
    assert load_module("layer_metrics", "ingest_encode_s").read(run) is None  # the reason for its list
    assert traced["window_compiles"]["value"] == 0 and traced["ingest_convert_s"]["value"] == 0.0
    assert 0 < traced["ae_fit_s"]["value"] < traced["dag_s"]["value"] and traced["ae_apply_s"]["value"] > 0
    assert traced["slowest_block_s"]["value"] >= traced["ae_fit_s"]["value"]


def test_a_pass_leaves_the_stage_rows_and_the_files(run, traffic):
    rows = run["passes"][-1]["manifest"]["phases"]
    assert [r["name"] for r in rows if r["parent"] == "dag"] == ["transformers/autoencoder_latentFeatures"]
    kids = [r for r in rows if r["parent"] == "transformers/autoencoder_latentFeatures"]
    assert [r["name"] for r in kids] == ["ae/prep", "ae/fit", "ae/apply", "ae/save"]
    by = {r["name"]: r["counts"] for r in kids}
    assert by["ae/prep"] == {"rows": PADDED, "cols": FEATURES}
    assert by["ae/apply"] == {"rows": PADDED, "cols": FEATURES, "latent": LATENT}
    assert {k: by["ae/fit"][k] for k in check.FIT_COUNTS} == check.fit_arithmetic(ROWS, FEATURES, LATENT, 10, 256)
    assert by["ae/fit"]["steps"] == 510 and by["ae/fit"]["bf16"] == 0  # f32 off the TPU
    assert not [r for r in rows if r["name"] in ("transform/fit", "transform/apply", "ingest/encode")]
    last = run["passes"][-1]["out_dir"]
    assert sorted(os.listdir(os.path.join(last, "model", "autoencoders_latentFeatures"))) == ["history.csv", "model.npz"]
    assert len(run["passes"][-1]["digest"]) == 4  # the part file, _SUCCESS, model.npz, history.csv: same bytes every pass
    out = pd.read_parquet(os.path.join(last, "output", "final_dataset"))
    assert list(out.columns) == ["label"] + [f"latent_{i}" for i in range(LATENT)] and len(out) == ROWS
    # a float column of the table is written as a double, as every cell's final dataset is
    assert str(out["label"].dtype) == "int32" and {str(t) for t in out.dtypes[1:]} == {"float64"}
    assert not out.isna().any().any()


# ------------------------------- correct has to be able to come out false ----
def ref_scale(ans, ref):
    return check.forward64(ans["weights"], ref["block"]).std(axis=0)


def test_the_comparison_passes_on_what_a_pass_left_and_each_moved_answer_fails_alone(compared, config):
    ans, ref, args = compared["ans"], compared["ref"], compared["args"]
    tol = config["guarantees"]["tolerances"]
    assert all(r["ok"] for r in check.compare(ans, ref, tol, args))
    shifted = ans["latent"].copy()
    shifted[5, 3] += 2 * tol["latent"]["scale_share"] * ref_scale(ans, ref)[3]  # one entry of 524,288
    moved = {
        "rows": dict(ans, rows=ans["rows"] + 1),
        "column_names": dict(ans, names={**ans["names"], 1: "latent_zero"}),
        "label_rows_changed": dict(ans, label=-ans["label"]),
        "fit_counts": dict(ans, fit={**ans["fit"], "steps": ans["fit"]["steps"] - 51}),
        "model_shapes": dict(ans, shapes={**ans["shapes"], "bottleneck.w": f"{FEATURES}x{LATENT + 1}"}),
        "latent": dict(ans, latent=shifted),
        "history_loss": dict(ans, history=ans["history"].assign(loss=ans["history"]["loss"] * (1 + 2 * tol["history"]["rtol"]))),
        "history_val_loss": dict(ans, history=ans["history"].assign(
            val_loss=ans["history"]["val_loss"] * (1 - 2 * tol["history"]["rtol"]))),
    }
    for name, other in moved.items():
        assert [r["name"] for r in check.compare(other, ref, tol, args) if not r["ok"]] == [name], name
    # a model that did not learn: the last validation loss not under the first, or not under 1
    flat = ans["history"].assign(val_loss=ans["history"]["val_loss"].iloc[0])
    assert "val_loss_last" in [r["name"] for r in check.compare(dict(ans, history=flat), ref, tol, args) if not r["ok"]]
    # rows in another order: the label and the latents see it
    back = dict(ans, label=ans["label"][::-1].reset_index(drop=True), latent=ans["latent"][::-1])
    assert [r["name"] for r in check.compare(back, ref, tol, args) if not r["ok"]] == ["label_rows_changed", "latent"]


def test_the_control_in_an_eight_bit_float_is_not_correct_and_bfloat16_is(compared, config):
    import ml_dtypes

    ans, ref, args = compared["ans"], compared["ref"], compared["args"]
    tol = config["guarantees"]["tolerances"]
    rows = {r["name"]: r for r in check.compare(check.control(ans, ref), ref, tol, args)}
    assert not rows["latent"]["ok"] and rows["latent"]["value"] >= 3.0  # three times the limit, and more
    assert all(r["ok"] for name, r in rows.items() if name != "latent")
    # the precision the configuration states, in the reference's place: inside half the limit
    stated = dict(ans, latent=check.forward64(ans["weights"], ref["block"], operands=ml_dtypes.bfloat16))
    rows = {r["name"]: r for r in check.compare(stated, ref, tol, args)}
    assert rows["latent"]["ok"] and 0 < rows["latent"]["value"] < 0.5


def test_an_epoch_fewer_and_two_faults_of_the_training_are_not_correct(compared, config):
    """The reference's own fit with a fault, in the program's place: each leaves the band."""
    ans, ref, args = compared["ans"], compared["ref"], compared["args"]
    tol = config["guarantees"]["tolerances"]
    fewer, _, _ = check.train(ref["block"], FEATURES, LATENT, epochs=9, batch=256)
    rows = {r["name"]: r for r in check.compare(dict(ans, history=fewer), ref, tol, args)}
    assert not rows["history_loss"]["ok"] and not rows["history_val_loss"]["ok"] and "9 epochs" in rows["history_loss"]["detail"]
    assert rows["latent"]["ok"] and rows["fit_counts"]["ok"]
    for fault in ({"running_in_training": True}, {"bias_correction": False}):
        faulty, _, _ = check.train(ref["block"], FEATURES, LATENT, epochs=10, batch=256, **fault)
        rows = {r["name"]: r for r in check.compare(dict(ans, history=faulty), ref, tol, args)}
        assert not rows["history_loss"]["ok"] and rows["history_loss"]["value"] > 2, fault
    # and a sound second run of the reference is the first one: nothing in it is left to chance
    again, _, _ = check.train(ref["block"], FEATURES, LATENT, epochs=10, batch=256)
    pd.testing.assert_frame_equal(again, ref["history"])


def test_the_reference_standardises_like_the_program_and_counts_like_the_model(tmp_path):
    df = pd.DataFrame({"a": [1.0, 2.0, np.nan, 4.0, 7.0], "b": [2.0, 2.0, 2.0, 2.0, 2.0]}, dtype=np.float32)
    z = check.standardised(df)
    present = np.array([1.0, 2.0, 4.0, 7.0])
    filled = np.array([1.0, 2.0, 3.0, 4.0, 7.0])  # the median of the four present: 3
    assert np.allclose(z[:, 0], (filled - present.mean()) / present.std(ddof=1), rtol=1e-15)
    assert np.array_equal(z[:, 1], np.zeros(5))  # a constant feature: divided by 1
    assert check.layer_dims(2000, 1000) == [(2000, 4000), (4000, 2000), (2000, 1000), (1000, 2000), (2000, 4000),
                                            (4000, 2000)]
    assert check.fit_arithmetic(50_000, 2000, 1000, 10, 256) == {
        "steps": 1560, "epochs": 10, "batch": 256, "fit_rows": 40_000, "val_rows": 10_000, "params": 36_039_000}
    assert check.fit_arithmetic(600_000, 2000, 1000, 100, 256)["fit_rows"] == 400_000  # sample_size caps the fit
    mfu = load_module("layer_metrics", "ae_fit_mfu_pct")
    assert mfu.weights(2000, 1000) == 36_000_000 and mfu.trainable(2000, 1000) == 36_039_000
    assert mfu.trainable(FEATURES, LATENT) == check.fit_arithmetic(ROWS, FEATURES, LATENT, 10, 256)["params"]


# --------------------------------------------------- the five new readers ----
def _row(name, parent, start, end, **counts):
    return {"name": name, "parent": parent, "start_s": start, "end_s": end, "thread": "t", "counts": counts}


NODE = "transformers/autoencoder_latentFeatures"
RECORDED = [  # a pass as the program records it at the cell's size: ingest 0-3 s, the node 3.1-8.0 s, the write after
    _row("run", None, 0.0, 9.0), _row("ingest", "run", 0.0, 3.0), _row("dag", "run", 3.1, 8.0),
    _row(NODE, "dag", 3.1, 8.0),
    _row("ae/prep", NODE, 3.1, 3.9, rows=65_536, cols=2000),
    _row("ae/fit", NODE, 3.9, 7.4, steps=1560, epochs=10, batch=256, fit_rows=40_000, val_rows=10_000,
         params=36_039_000, flops_per_step=55_296_000_000, bf16=1),
    _row("ae/apply", NODE, 7.4, 7.7, rows=65_536, cols=2000, latent=1000),
    _row("ae/save", NODE, 7.7, 8.0),
    _row("write_main", "run", 8.1, 8.9),
]


def _pass(rows, wall=9.0):
    return {"wall_s": wall, "manifest": {"phases": rows}}


def test_span_readers_on_a_recorded_manifest():
    fit_s = load_module("layer_metrics", "ae_fit_s").read
    apply_s = load_module("layer_metrics", "ae_apply_s").read
    run = {"passes": [_pass(RECORDED)]}
    assert fit_s(run) == pytest.approx(3.5) and apply_s(run) == pytest.approx(0.8 + 0.3 + 0.3)
    # a pass that loaded its model: no fit and nothing saved
    scored = [r for r in RECORDED if r["name"] not in ("ae/fit", "ae/save")]
    assert fit_s({"passes": [_pass(scored)]}) is None and apply_s({"passes": [_pass(scored)]}) == pytest.approx(1.1)
    # a program from before the rows (the parent), a pass of another mix, no pass at all
    before = [r for r in RECORDED if not r["name"].startswith("ae/")]
    for rows in (before, [_row("run", None, 0.0, 1.0)], []):
        assert fit_s({"passes": [_pass(rows)]}) is None and apply_s({"passes": [_pass(rows)]}) is None
    assert fit_s({"passes": []}) is None and apply_s({"passes": []}) is None


def test_device_readers_on_a_hand_built_event_list(monkeypatch):
    """Chip 0 spends 2.0 s under ``ae/train_step`` in two operations and 0.1 s under ``ae/encode``;
    1.0 s more belongs to no scope.  By hand, at the cell's shape: 1,560 steps x 55.296 GFLOP over
    2.0 s x 197 TFLOP/s = 21.89 %; 1,560 x (24 x 36,039,000 + 4 x 256 x 2000) bytes over 2.0 s x
    819 GB/s = 82.57 %."""
    device_s = load_module("layer_metrics", "ae_fit_device_s")
    mfu = load_module("layer_metrics", "ae_fit_mfu_pct")
    hbm = load_module("layer_metrics", "ae_fit_hbm_pct")
    reader = load_module("layer_metrics", "ts_device_s")
    reader.SCOPES = device_s.SCOPES
    events = {"/device:TPU:0": [(1.0, 2.5, "ae/train_step"), (3.0, 3.5, "ae/train_step"), (4.0, 4.1, "ae/encode"),
                                (5.0, 6.0, "")]}
    seconds = reader.scope_seconds(events)
    assert seconds == {"ae/train_step": pytest.approx(2.0), "ae/encode": pytest.approx(0.1)}
    assert load_module("layer_metrics", "ts_device_s").SCOPES == ("ts/calendar_counts", "ts/segment_aggregate")
    # no trace: nothing, and no error; so on a program without the scope
    for read in (device_s.read, mfu.read, hbm.read):
        assert read({"trace_dir": "", "traced": _pass(RECORDED)}) is None
        assert read({"ae_scope_seconds": {}, "traced": _pass(RECORDED)}) is None
    run = {"ae_scope_seconds": seconds, "traced": _pass(RECORDED)}
    assert device_s.read(run) == pytest.approx(2.0)
    assert mfu.read(run) is None and hbm.read(run) is None  # a device the peaks do not know (the CPU)
    assert mfu.flops_per_step(2000, 1000, 256) == 55_296_000_000 and hbm.step_bytes(2000, 1000, 256) == 866_984_000
    assert mfu.fit_shape(RECORDED) == {"n": 2000, "k": 1000, "steps": 1560, "batch": 256,
                                       "flops_per_step": 55_296_000_000}
    assert mfu.fit_shape([r for r in RECORDED if r["name"] != "ae/prep"]) is None
    import jax

    class V5e:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a: [V5e()])
    assert mfu.read(run) == pytest.approx(100 * 1560 * 55.296e9 / (2.0 * 197e12)) == pytest.approx(21.894, abs=1e-3)
    assert hbm.read(run) == pytest.approx(100 * 1560 * 866_984_000 / (2.0 * 819e9)) == pytest.approx(82.570, abs=1e-3)
    # the program's own count of its operations is only checked: where it disagrees the share is not reported
    wrong = [dict(r, counts=dict(r["counts"], flops_per_step=1)) if r["name"] == "ae/fit" else r for r in RECORDED]
    assert mfu.read({"ae_scope_seconds": seconds, "traced": _pass(wrong)}) is None
    # a pass without the rows (the parent): nothing
    assert mfu.read({"ae_scope_seconds": seconds, "traced": _pass([])}) is None
    assert hbm.read({"ae_scope_seconds": seconds, "traced": _pass([])}) is None


def test_readers_on_the_live_run(run):
    rows = run["passes"][-1]["manifest"]["phases"]
    one = dict(run, passes=run["passes"][-1:])
    fit = next(r for r in rows if r["name"] == "ae/fit")
    assert load_module("layer_metrics", "ae_fit_s").read(one) == pytest.approx(fit["end_s"] - fit["start_s"])
    shape = load_module("layer_metrics", "ae_fit_mfu_pct").fit_shape(rows)
    assert shape == {"n": FEATURES, "k": LATENT, "steps": 510, "batch": 256,
                     "flops_per_step": 6 * 256 * load_module("layer_metrics", "ae_fit_mfu_pct").weights(FEATURES, LATENT)}
