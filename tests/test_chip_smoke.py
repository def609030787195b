"""CPU rehearsal of chip_smoke.py: its data, pipeline, check and placement
functions at 2,000 rows on the 8-virtual-device mesh (the script itself
has no CPU run — main() must refuse)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402

ROWS = 2000


@pytest.fixture(scope="module")
def work(tmp_path_factory):
    return str(tmp_path_factory.mktemp("chip_smoke"))


@pytest.fixture(scope="module")
def data_dir(work):
    d = os.path.join(work, "income_dataset")
    assert chip_smoke.generate_data(ROWS, chip_smoke.SEED, d) > 0
    return d


@pytest.fixture(scope="module")
def run(work, data_dir):
    import jax

    # a cold run whatever this worker ran before: xdist hands files to workers in an order that
    # shifts with every new test file, and programs another file compiled at 2,000 rows leave none to count
    jax.clear_caches()
    cfg = chip_smoke.write_config(data_dir, os.path.join(work, "configs_full.yaml"))
    return chip_smoke.run_pipeline(cfg, os.path.join(work, "run_cold"))


def test_main_refuses_a_platform_other_than_tpu(capsys):
    assert chip_smoke.main([]) != 0
    assert chip_smoke.main(["--rows", "10"]) != 0
    out = capsys.readouterr()
    assert out.out == ""  # no result line, nothing done
    assert "no CPU run" in out.err


def test_config_copy_reads_the_generated_dataset(work, data_dir):
    import yaml

    path = chip_smoke.write_config(data_dir, os.path.join(work, "cfg_copy.yaml"))
    text = open(path).read()
    assert "data/income_dataset" not in text.replace(data_dir, "")
    cfg = yaml.safe_load(text)
    assert cfg["input_dataset"]["read_dataset"]["file_path"] == os.path.join(data_dir, "parquet")
    assert cfg["geospatial_controller"]["geospatial_analyzer"]["max_analysis_records"] == 100000


def test_placement_over_the_virtual_mesh(data_dir, runtime):
    assert runtime.n_devices == 8
    assert chip_smoke.check_placement(data_dir) == []


def test_pipeline_run_is_clean_on_cpu(run):
    assert run["manifest"]["compile_census"]["compiles_total"] > 0
    assert chip_smoke.check_run(run, platform="cpu") == []
    # the same run is NOT a pass for the chip
    assert any("backend" in b for b in chip_smoke.check_run(run, platform="tpu"))


def test_answers_match_pandas(run, data_dir):
    ref = chip_smoke.reference(data_dir)
    assert ref["rows"] == ROWS and ref["duplicate_rows"] == ROWS // 1000
    assert chip_smoke.check_answers(run, ref) == []


def test_check_answers_catches_a_wrong_statistic(run, data_dir):
    ref = chip_smoke.reference(data_dir)
    ref["summary"].loc["age", "mean"] *= 1.001
    ref["psi"]["race"] += 0.001
    ref["duplicate_rows"] += 1
    bad = chip_smoke.check_answers(run, ref)
    assert len(bad) == 3 and any("age mean" in b for b in bad)


def test_check_run_catches_degradation_and_missing_artifacts(run, tmp_path):
    import copy

    broken = copy.deepcopy(run)
    broken["manifest"]["resilience"]["degraded_sections"] = {"drift_detector": "boom"}
    broken["manifest"]["resilience"]["retries"] = 1
    broken["out_dir"] = str(tmp_path)
    bad = chip_smoke.check_run(broken, platform="cpu")
    assert any("degraded" in b for b in bad) and any("retries" in b for b in bad)
    assert any("ml_anovos_report.html" in b for b in bad)
