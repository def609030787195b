"""XLA compile census (obs.compile_census + tools/compile_census.py).

Covers the listener/mark/census contract, the CLI renderer + CI gate, and
the tier-1 manifest-driven program budget: a small config-driven workflow
run must stay under a distinct-program ceiling so a per-call ``jax.jit``
or a lost shape bucket fails loudly instead of silently re-inflating the
cold-run compile tail (the regression class PERF.md's round-4 census
caught by hand)."""

import json
import os
import subprocess
import sys

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


OLD_KEYS = ("compiles_total", "distinct_programs", "distinct_kernels", "compile_seconds_total", "programs")
NEW_KEYS = ("cache_requests", "cache_hits", "cache_writes", "built_programs", "trace_seconds_total",
            "lower_seconds_total", "load_seconds_total", "build_seconds_total", "self_seconds_total")


def test_a_fresh_program_is_traced_lowered_and_built_once():
    """The suite runs with the persistent cache off: a program the process has
    not seen is one trace, one lowering and one build, in its row and in the sums."""
    compile_census.install()
    arg = jnp.ones((19, 5), jnp.float32)
    arg.block_until_ready()  # what makes the argument compiles before the mark
    mark = compile_census.mark()

    @jax.jit
    def _census_stage_probe(x):
        return jnp.tanh(x).sum(axis=1) * 3.0  # the jnp calls inside are traced inside it: no rows of their own

    _census_stage_probe(arg).block_until_ready()
    c = compile_census.census(since=mark, top=0)
    assert set(OLD_KEYS + NEW_KEYS) == set(c)
    (row,) = [r for r in c["programs"] if "_census_stage_probe" in r["program"]]
    assert set(row) == {"program", "count", "seconds", "nodes", "trace_s", "lower_s", "load_s", "build_s", "hits"}
    assert row["program"] == "jit(_census_stage_probe)" and row["count"] == 1 and row["hits"] == 0
    assert row["trace_s"] > 0 and row["lower_s"] > 0 and row["build_s"] > 0 and row["load_s"] == 0
    assert row["build_s"] == row["seconds"] and row["nodes"] == []
    assert [r["program"] for r in c["programs"]] == ["jit(_census_stage_probe)"]
    assert (c["compiles_total"], c["built_programs"], c["cache_hits"]) == (1, 1, 0)
    assert (c["cache_requests"], c["cache_writes"]) == (0, 0)  # nobody asked a cache that is off
    assert c["trace_seconds_total"] == row["trace_s"] and c["lower_seconds_total"] == row["lower_s"]
    assert c["build_seconds_total"] == row["build_s"] == c["compile_seconds_total"]
    assert c["load_seconds_total"] == 0 and 0 < c["self_seconds_total"] < 0.5
    # the same program again: nothing is heard
    mark = compile_census.mark()
    _census_stage_probe(arg).block_until_ready()
    again = compile_census.census(since=mark)
    assert again["compiles_total"] == 0 and again["programs"] == [] and again["trace_seconds_total"] == 0


def test_the_node_is_the_tracers_open_node_span_whatever_devprof_says(monkeypatch):
    from anovos_tpu.obs import get_tracer

    monkeypatch.setenv("ANOVOS_TPU_DEVPROF", "0")
    compile_census.install()
    arg = jnp.ones((23, 3), jnp.float32)
    arg.block_until_ready()
    mark = compile_census.mark()

    @jax.jit
    def _census_node_probe(x):
        return x * 5.0

    with get_tracer().span("a_census_node", cat="node"):
        _census_node_probe(arg).block_until_ready()
    (row,) = compile_census.census(since=mark)["programs"]
    assert row["program"] == "jit(_census_node_probe)" and row["nodes"] == ["a_census_node"]


_CACHE_SCRIPT = """
import json, sys
import jax, jax.numpy as jnp
from anovos_tpu.obs import compile_census
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
compile_census.install()
@jax.jit
def _census_cache_probe(x):
    return (x * 2.0 + 1.0).sum(axis=0)
_census_cache_probe(jnp.ones((13, 7), jnp.float32)).block_until_ready()
print("CENSUS " + json.dumps(compile_census.census(top=0)))
"""


@pytest.fixture(scope="module")
def two_processes_one_cache(tmp_path_factory):
    """The same program in two fresh processes on one cache directory."""
    cache = str(tmp_path_factory.mktemp("census_cache"))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": root}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = []
    for _ in range(2):
        done = subprocess.run([sys.executable, "-c", _CACHE_SCRIPT, cache], env=env, text=True,
                              capture_output=True, timeout=300)
        assert done.returncode == 0, done.stderr[-2000:]
        (line,) = [ln for ln in done.stdout.splitlines() if ln.startswith("CENSUS ")]
        out.append(json.loads(line[len("CENSUS "):]))
    return out


def test_the_first_process_builds_and_writes(two_processes_one_cache):
    first, _ = two_processes_one_cache
    assert first["cache_hits"] == 0 and first["load_seconds_total"] == 0
    assert first["built_programs"] == first["compiles_total"] >= 1
    assert first["cache_requests"] == first["compiles_total"] and first["cache_writes"] >= 1
    (row,) = [r for r in first["programs"] if r["program"] == "jit(_census_cache_probe)"]
    assert row["hits"] == 0 and row["build_s"] > 0 and row["load_s"] == 0


def test_the_second_process_loads_what_the_first_wrote(two_processes_one_cache):
    first, second = two_processes_one_cache
    assert second["cache_hits"] >= 1 and second["built_programs"] == 0 and second["cache_writes"] == 0
    assert second["cache_hits"] == second["compiles_total"] == second["cache_requests"] == first["compiles_total"]
    assert second["load_seconds_total"] > 0 and second["build_seconds_total"] == 0
    assert second["load_seconds_total"] <= second["compile_seconds_total"]  # the event holds the key's hash too
    (row,) = [r for r in second["programs"] if r["program"] == "jit(_census_cache_probe)"]
    assert row["hits"] == row["count"] == 1 and row["load_s"] > 0 and row["build_s"] == 0
    assert row["trace_s"] > 0 and row["lower_s"] > 0  # a load spares neither
    assert second["distinct_programs"] == first["distinct_programs"]


@pytest.mark.parametrize("call", [
    lambda: compile_census._listener("/jax/no/such/event", 1.0),
    lambda: compile_census._listener(compile_census.COMPILE_EVENT, "not a number", fun_name=object()),
    lambda: compile_census._listener(compile_census.COMPILE_EVENT, None),
    lambda: compile_census._listener("/jax/core/compile/jaxpr_trace_duration", float("nan"), other=1),
    lambda: compile_census._listener(None, None, None),
    lambda: compile_census._event_listener("/jax/no/such/event", key="value"),
    lambda: compile_census._event_listener(None),
    lambda: compile_census._scalar_listener("/jax/no/such/event", "x", fun_name=3),
], ids=["unknown-duration", "garbage-seconds", "none-seconds", "nan-seconds", "all-none",
        "unknown-event", "none-event", "unknown-scalar"])
def test_a_listener_handed_garbage_does_not_raise(call):
    mark = compile_census.mark()
    call()
    assert compile_census.census(since=mark)["compiles_total"] in (0, 1)
    mark = compile_census.mark()

    @jax.jit
    def _census_after_garbage(x):
        return x + 7.0

    _census_after_garbage(jnp.ones((29, 2))).block_until_ready()  # and the census still hears the next program
    assert any(r["program"] == "jit(_census_after_garbage)" and r["count"] == 1
               for r in compile_census.census(since=mark, top=0)["programs"])


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


def test_cli_prints_the_stages_where_the_census_has_them(tmp_path, capsys):
    from tools.compile_census import main

    staged = {**_CENSUS, "cache_requests": 40, "cache_hits": 39, "cache_writes": 1, "built_programs": 3,
              "trace_seconds_total": 0.5, "lower_seconds_total": 0.7, "load_seconds_total": 1.9,
              "build_seconds_total": 1.2, "self_seconds_total": 0.01,
              "programs": [{"program": "jit(_masked_quantiles)", "count": 5, "seconds": 1.5, "trace_s": 0.1,
                            "lower_s": 0.2, "load_s": 0.25, "build_s": 1.2, "hits": 4, "nodes": ["a/node"]}]}
    assert main([_manifest_with_census(tmp_path, staged)]) == 0
    out = capsys.readouterr().out
    assert "cache_hits=39" in out and "built_programs=3" in out and "self_s=0.01" in out
    head, row = out.splitlines()[-2:]
    assert head.split() == ["seconds", "count", "trace_s", "lower_s", "load_s", "build_s", "hits", "program"]
    assert row.split()[:7] == ["1.500", "5", "0.100", "0.200", "0.250", "1.200", "4"] and "[a/node]" in row


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
