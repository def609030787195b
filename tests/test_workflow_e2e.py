"""End-to-end config-driven run (reference test strategy: the full-demo
workflow on the income dataset, SURVEY.md §4)."""

import json

import pandas as pd
import pytest
import yaml

from anovos_tpu import workflow
from anovos_tpu.data_ingest.synthetic import DEFAULT_DIR

INCOME_PARQUET = str(DEFAULT_DIR / "parquet")

CFG = {
    "input_dataset": {
        "read_dataset": {
            "file_path": INCOME_PARQUET,
            "file_type": "parquet",
        },
        "delete_column": ["logfnl", "empty", "dt_1", "dt_2"],
        "rename_column": {
            "list_of_cols": ["marital-status", "education-num"],
            "list_of_newcols": ["marital_status", "education_num"],
        },
    },
    "anovos_basic_report": {"basic_report": False},
    "stats_generator": {
        "metric": ["global_summary", "measures_of_counts", "measures_of_centralTendency"],
        "metric_args": {"list_of_cols": "all", "drop_cols": ["ifa"]},
    },
    "quality_checker": {
        "duplicate_detection": {"list_of_cols": "all", "drop_cols": ["ifa"], "treatment": True},
        "nullColumns_detection": {
            "list_of_cols": "all",
            "drop_cols": ["ifa", "income"],
            "treatment": True,
            "treatment_method": "MMM",
            "treatment_configs": {"method_type": "median"},
        },
    },
    "association_evaluator": {
        "IV_calculation": {
            "list_of_cols": "all",
            "drop_cols": "ifa",
            "label_col": "income",
            "event_label": ">50K",
        }
    },
    "drift_detector": {
        "drift_statistics": {
            "configs": {
                "list_of_cols": "all",
                "drop_cols": ["ifa", "income"],
                "method_type": "PSI",
                "threshold": 0.1,
                "sample_size": 20000,
            },
            "source_dataset": {
                "read_dataset": {
                    "file_path": INCOME_PARQUET,
                    "file_type": "parquet",
                },
                "delete_column": ["logfnl", "empty", "dt_1", "dt_2"],
                "rename_column": {
                    "list_of_cols": ["marital-status", "education-num"],
                    "list_of_newcols": ["marital_status", "education_num"],
                },
            },
        }
    },
    "report_preprocessing": {
        "master_path": "report_stats",
        "charts_to_objects": {
            "list_of_cols": "all",
            "drop_cols": "ifa",
            "label_col": "income",
            "event_label": ">50K",
            "bin_size": 10,
        },
    },
    "report_generation": {
        "master_path": "report_stats",
        "id_col": "ifa",
        "label_col": "income",
        "final_report_path": "report_stats",
    },
    "write_main": {"file_path": "output", "file_type": "parquet", "file_configs": {"mode": "overwrite"}},
}


@pytest.mark.slow
@pytest.mark.parametrize("executor", ["concurrent", "sequential"])
def test_workflow_end_to_end(tmp_path, monkeypatch, executor):
    """Once per executor mode: the concurrent DAG scheduler and the
    sequential fallback must both satisfy the full output contract.  The
    per-node watchdog turns a scheduler deadlock into a fast failure naming
    the stuck block instead of eating the suite budget."""
    monkeypatch.setenv("ANOVOS_TPU_EXECUTOR", executor)
    monkeypatch.setenv("ANOVOS_TPU_NODE_TIMEOUT", "600")
    monkeypatch.chdir(tmp_path)
    cfg_path = tmp_path / "cfg.yaml"
    # sort_keys=False: block execution follows YAML author order, exactly like
    # the reference's insertion-ordered dict iteration
    cfg_path.write_text(yaml.safe_dump(CFG, sort_keys=False))
    workflow.run(str(cfg_path), "local")

    rs = tmp_path / "report_stats"
    # stats contract
    gs = pd.read_csv(rs / "global_summary.csv")
    assert str(dict(zip(gs["metric"], gs["value"]))["columns_count"]) == "19"
    ct = pd.read_csv(rs / "measures_of_centralTendency.csv").set_index("attribute")
    assert abs(float(ct.loc["age", "mean"]) - 38.5065) < 0.01
    iv = pd.read_csv(rs / "IV_calculation.csv")
    assert "iv" in iv.columns and len(iv) > 5
    drift = pd.read_csv(rs / "drift_statistics.csv")
    assert (drift["PSI"] < 0.05).all()  # same dataset → no drift
    # chart contract
    with open(rs / "freqDist_age") as f:
        fig = json.load(f)
    assert fig["data"][0]["type"] == "bar"
    # report + final dataset
    assert (rs / "ml_anovos_report.html").exists()
    assert (tmp_path / "output" / "final_dataset" / "_SUCCESS").exists()
    # obs subsystem: the run manifest lands under the master path and names
    # every executed node with a completed span
    manifest_path = rs / "obs" / "run_manifest.json"
    assert manifest_path.exists()
    with open(manifest_path) as f:
        manifest = json.load(f)
    # collective-aware lanes (ISSUE 8): the executor no longer degrades
    # on the 8-virtual-device mesh — the manifest records the mode asked
    assert manifest["executor"]["mode"] == executor
    nodes = manifest["scheduler"]["nodes"]
    expected_nodes = {
        "stats_generator/global_summary",
        "stats_generator/measures_of_counts",
        "stats_generator/measures_of_centralTendency",
        "quality_checker/duplicate_detection",
        "quality_checker/nullColumns_detection",
        "association_evaluator/IV_calculation",
        "drift_detector/drift_statistics",
        "report_preprocessing/charts_to_objects",
        "report_generation",
    }
    assert expected_nodes <= set(nodes), sorted(expected_nodes - set(nodes))
    for name, node in nodes.items():
        assert node["state"] == "done", (name, node)
        assert node["dur_s"] is not None, name
    assert manifest["block_seconds"]
    assert manifest["metrics"]["rows_ingested_total"]["series"]


def test_ts_geo_failures_do_not_kill_pipeline(tmp_path, monkeypatch):
    """Reference resilience semantics: ts/geo auto-detection is best-effort
    (ts_auto_detection.py:707 swallows) — a crash there must not abort the
    run or the downstream stats."""
    import anovos_tpu.workflow as wf

    def boom(*a, **k):
        raise RuntimeError("synthetic ts failure")

    monkeypatch.setattr(wf, "ts_preprocess", boom)
    monkeypatch.setattr(
        "anovos_tpu.data_analyzer.geospatial_analyzer.geospatial_autodetection", boom
    )
    cfg = {
        "input_dataset": {
            "read_dataset": {
                "file_path": INCOME_PARQUET,
                "file_type": "parquet",
            },
            "delete_column": ["logfnl", "empty", "dt_2"],
        },
        "timeseries_analyzer": {"auto_detection": True, "id_col": "ifa"},
        "geospatial_controller": {
            "geospatial_analyzer": {"auto_detection_analyzer": True, "id_col": "ifa"}
        },
        "stats_generator": {
            "metric": ["global_summary"],
            "metric_args": {"list_of_cols": "all", "drop_cols": ["ifa"]},
        },
        "report_preprocessing": {"master_path": str(tmp_path)},
    }
    monkeypatch.chdir(tmp_path)
    wf.main(cfg, "local")
    assert (tmp_path / "global_summary.csv").exists()


def test_reread_skips_disk_but_escape_hatch_reads_back(tmp_path, monkeypatch):
    """save(reread=True) writes the checkpoint artifact and returns the
    in-memory Table (no Spark lineage to cut); ANOVOS_REREAD_FROM_DISK=1
    restores the literal read-back for writer/reader parity debugging."""
    import numpy as np
    import pandas as pd

    from anovos_tpu import workflow
    from anovos_tpu.shared import Table

    t = Table.from_pandas(pd.DataFrame({"x": [1.5, 2.5], "c": ["a", "b"]}))
    wc = {"file_path": str(tmp_path), "file_type": "csv",
          "file_configs": {"mode": "overwrite", "header": True}}
    monkeypatch.delenv("ANOVOS_REREAD_FROM_DISK", raising=False)
    out = workflow.save(t, wc, "ckpt", reread=True)
    assert out is t  # identity: no read-back
    assert (tmp_path / "ckpt" / "_SUCCESS").exists()  # artifact still written
    monkeypatch.setenv("ANOVOS_REREAD_FROM_DISK", "1")
    out2 = workflow.save(t, wc, "ckpt", reread=True)
    assert out2 is not t  # literal read-back
    np.testing.assert_allclose(
        np.asarray(out2.columns["x"].data)[:2], [1.5, 2.5])
