"""XLA compile census (obs.compile_census + tools/compile_census.py).

Covers the listener/mark/census contract, the CLI renderer + CI gate, and
the tier-1 manifest-driven program budget: a small config-driven workflow
run must stay under a distinct-program ceiling so a per-call ``jax.jit``
or a lost shape bucket fails loudly instead of silently re-inflating the
cold-run compile tail (the regression class PERF.md's round-4 census
caught by hand)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest
import yaml

from anovos_tpu.obs import compile_census


def test_listener_counts_fresh_compiles():
    compile_census.install()
    mark = compile_census.mark()

    # a shape this suite has never compiled: prime-sized lanes
    @jax.jit
    def _census_probe(x):
        return (x * 2.0 + 1.0).sum(axis=0)

    _census_probe(jnp.ones((13, 7), jnp.float32)).block_until_ready()
    c1 = compile_census.census(since=mark)
    assert c1["compiles_total"] >= 1
    assert c1["distinct_programs"] >= 1
    assert any("_census_probe" in r["program"] for r in c1["programs"])
    assert c1["compile_seconds_total"] > 0

    # identical signature replays the cache: no new compile events
    mark2 = compile_census.mark()
    _census_probe(jnp.ones((13, 7), jnp.float32)).block_until_ready()
    assert compile_census.census(since=mark2)["compiles_total"] == 0

    # a new shape compiles a new program under the SAME kernel name
    _census_probe(jnp.ones((13, 11), jnp.float32)).block_until_ready()
    c3 = compile_census.census(since=mark2)
    assert c3["compiles_total"] >= 1
    probe = [r for r in compile_census.census(since=mark)["programs"]
             if "_census_probe" in r["program"]]
    assert probe and probe[0]["count"] == 2  # two shape variants, one kernel


def test_census_metrics_registered():
    from anovos_tpu.obs import get_metrics

    compile_census.install()
    mark = compile_census.mark()

    @jax.jit
    def _census_probe2(x):
        return x - 3.0

    _census_probe2(jnp.ones((17, 3))).block_until_ready()
    if compile_census.census(since=mark)["compiles_total"]:
        reg = get_metrics()
        assert reg.counter("xla_compiles_total").value() >= 1
        assert reg.counter("xla_compile_seconds_total").value() > 0


# ---------------------------------------------------------------------------
# CLI renderer + gate
# ---------------------------------------------------------------------------
def _manifest_with_census(tmp_path, census):
    path = tmp_path / "run_manifest.json"
    path.write_text(json.dumps({"manifest_version": 1, "compile_census": census}))
    return str(path)


_CENSUS = {
    "compiles_total": 42,
    "distinct_programs": 30,
    "distinct_kernels": 12,
    "compile_seconds_total": 3.21,
    "programs": [
        {"program": "jit(_masked_quantiles)", "count": 5, "seconds": 1.5},
        {"program": "jit(describe_cat)", "count": 3, "seconds": 0.9},
    ],
}


def test_cli_renders_and_passes_within_budget(tmp_path, capsys):
    from tools.compile_census import main

    rc = main([_manifest_with_census(tmp_path, _CENSUS), "--assert-max-programs", "30"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "distinct_programs=30" in out
    assert "jit(_masked_quantiles)" in out


def test_cli_fails_over_budget(tmp_path, capsys):
    from tools.compile_census import main

    rc = main([_manifest_with_census(tmp_path, _CENSUS),
               "--assert-max-programs", "29"])
    assert rc == 2
    assert "distinct_programs 30 > budget 29" in capsys.readouterr().err
    rc = main([_manifest_with_census(tmp_path, _CENSUS),
               "--assert-max-compiles", "41"])
    assert rc == 2


def test_cli_rejects_censusless_manifest(tmp_path):
    from tools.compile_census import main

    path = tmp_path / "m.json"
    path.write_text(json.dumps({"manifest_version": 1}))
    with pytest.raises(SystemExit):
        main([str(path)])


# ---------------------------------------------------------------------------
# tier-1 manifest-driven gate: a real (small) workflow run stays under the
# distinct-program budget
# ---------------------------------------------------------------------------

# Ceiling for the small gate config below, measured at 19 distinct programs
# with column+row bucketing AND whole-block fusion in place (fresh process;
# in-suite runs reuse the session's jit cache and land lower).  A per-call
# jit in any touched op adds one program per invocation and blows through
# this fast.  35, not 45: the blocks' glue runs as fused programs (the
# `_*_program` functions of data_analyzer/, data_transformer/ and
# data_report/), so no single-primitive chain pads the budget.
GATE_MAX_PROGRAMS = 35
# total-compile ceiling (compiles ≈ programs on a fresh process; in-suite
# reruns land near zero) — the second axis the census CLI gates: a warm-path
# re-trace that compiles the SAME program repeatedly inflates compiles
# without adding distinct programs
GATE_MAX_COMPILES = 40


def _small_frame(n=400, seed=5):
    g = np.random.default_rng(seed)
    return pd.DataFrame({
        **{f"num{i}": g.normal(i, 1 + i / 5, n) for i in range(9)},
        "cat_a": g.choice(list("abcd"), n),
        "cat_b": g.choice(list("xyz"), n),
        "label": g.choice(["0", "1"], n),
    })


def test_workflow_manifest_census_gate(tmp_path, monkeypatch):
    """Run a small config-driven workflow, then hold its manifest census to
    the program budget through the actual CLI entry point."""
    from anovos_tpu import workflow
    from tools.compile_census import load_census, main

    data_dir = tmp_path / "data"
    data_dir.mkdir()
    _small_frame().to_parquet(data_dir / "part-00000.parquet", index=False)
    cfg = {
        "input_dataset": {
            "read_dataset": {"file_path": str(data_dir), "file_type": "parquet"},
        },
        "anovos_basic_report": {"basic_report": False},
        "stats_generator": {
            "metric": ["global_summary", "measures_of_counts",
                       "measures_of_centralTendency", "measures_of_dispersion"],
            "metric_args": {"list_of_cols": "all", "drop_cols": []},
        },
        "quality_checker": {
            "outlier_detection": {"list_of_cols": "all", "drop_cols": ["label"],
                                  "detection_configs": {"pctile_lower": 0.05,
                                                        "pctile_upper": 0.95}},
        },
        "drift_detector": {
            "drift_statistics": {
                "configs": {"list_of_cols": "all", "drop_cols": ["label"],
                            "method_type": "PSI", "threshold": 0.1},
                "source_dataset": {
                    "read_dataset": {"file_path": str(data_dir), "file_type": "parquet"},
                },
            }
        },
        "write_main": {"file_path": "output", "file_type": "parquet",
                       "file_configs": {"mode": "overwrite"}},
    }
    monkeypatch.setenv("ANOVOS_TPU_EXECUTOR", "sequential")
    monkeypatch.chdir(tmp_path)
    (tmp_path / "cfg.yaml").write_text(yaml.safe_dump(cfg, sort_keys=False))
    workflow.run(str(tmp_path / "cfg.yaml"), "local")

    manifest_path = workflow.LAST_MANIFEST_PATH
    assert os.path.exists(manifest_path)
    census = load_census(manifest_path)
    # census presence + schema (counts may be near zero when the suite's
    # jit cache already holds these programs — the budget is an upper gate)
    for key in ("compiles_total", "distinct_programs", "distinct_kernels",
                "compile_seconds_total", "programs"):
        assert key in census, key
    rc = main([manifest_path, "--assert-max-programs", str(GATE_MAX_PROGRAMS),
               "--assert-max-compiles", str(GATE_MAX_COMPILES)])
    assert rc == 0, (
        f"census over budget: distinct_programs {census['distinct_programs']} "
        f"(max {GATE_MAX_PROGRAMS}), compiles_total {census['compiles_total']} "
        f"(max {GATE_MAX_COMPILES})")
