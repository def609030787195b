"""bench.py's process contract (a parent that starts its measured children
one after another and fails when one fails or runs off the chip) and the
steady-state device-resident PSI metric."""

import importlib.util
import json
import os
import sys

import numpy as np
import pandas as pd
import pytest

def _load_script(name):
    """Import a repo-root script as a module."""
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(os.path.dirname(__file__), "..", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


bench = _load_script("bench")


def test_main_fails_when_the_measured_child_fails(monkeypatch, capsys):
    """No probe, no adopted older result, no CPU re-run: a dead measured
    child is a non-zero exit and no JSON line."""
    monkeypatch.setattr(bench, "compute_baseline", lambda: {"t_ref": 1.0, "ref": {}})
    calls = []

    def dead_child(mode, timeout_s):
        calls.append(mode)
        return None, "measured run failed: boom"

    monkeypatch.setattr(bench, "_run_child", dead_child)
    assert bench.main() == 1
    assert calls == ["--measure"]  # one attempt, nothing after it
    out = capsys.readouterr()
    assert out.out == "" and "boom" in out.err


def test_main_exits_nonzero_on_a_platform_other_than_tpu(monkeypatch, capsys):
    """The children run with the environment untouched, one after another
    from the parent; a result from any platform but tpu is printed with its
    platform and fails the run."""
    monkeypatch.setattr(bench, "compute_baseline", lambda: {"t_ref": 1.0, "ref": {}})
    monkeypatch.setattr(bench, "SECONDARY_LEGS",
                        (("SERVE", lambda: {"e2e_serve_qps": 10.0}),))
    import tools.perf_ledger as pl

    monkeypatch.setattr(pl, "record_and_check", lambda r: {"ledger_ok": True})
    modes = []

    def child(mode, timeout_s):
        modes.append(mode)
        if mode == "--measure":
            return {"metric": "psi_drift_rows_per_sec", "value": 1.0, "backend": platform}, None
        return {"e2e_warm_s": 1.0, "e2e_backend": platform}, None

    monkeypatch.setattr(bench, "_run_child", child)
    platform = "cpu"
    assert bench.main() == 1
    out = capsys.readouterr()
    rec = json.loads(out.out.strip().splitlines()[-1])
    assert rec["backend"] == "cpu" and rec["e2e_serve_qps"] == 10.0
    assert "not tpu" in out.err
    assert modes == ["--measure", "--measure-e2e"]
    platform = "tpu"
    assert bench.main() == 0


def test_secondary_legs_leave_the_platform_alone(monkeypatch):
    """Every subprocess leg inherits JAX_PLATFORMS as it is — none pins a
    child to the CPU."""
    seen = []

    class Done:
        returncode, stdout, stderr = 1, "", "no"

    def fake_run(cmd, **kw):
        seen.append(kw.get("env", os.environ).get("JAX_PLATFORMS"))
        return Done()

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    for leg in (bench.e2e_serving, bench.e2e_oocore, bench.e2e_continuum,
                bench.e2e_chaos_recovery, bench.e2e_corrupt_ingest):
        assert any(k.endswith("_error") for k in leg())
    assert seen and set(seen) == {None}


def test_e2e_rows_derived_from_config():
    # configs_full reads the income parquet: the derived count must match
    # the dataset, not a hardwired constant
    assert bench._e2e_rows() == 32561


def test_steady_state_args_shapes():
    """drift_device_args must hand drift_side_full the same column layout
    statistics uses: one lane per column, padded masks, a (k, nbins-1)
    cutoff matrix, and a LUT covering every categorical vocab."""
    from anovos_tpu.shared import Table
    from anovos_tpu.drift_stability.drift_detector import drift_device_args
    from anovos_tpu.ops.drift_kernels import drift_side_full

    rng = np.random.default_rng(0)
    df = pd.DataFrame({
        "x": rng.normal(size=300), "y": rng.gamma(2.0, size=300),
        "c": rng.choice(["a", "b", "c"], 300),
    })
    src = Table.from_pandas(df.iloc[:150].reset_index(drop=True))
    tgt = Table.from_pandas(df.iloc[150:].reset_index(drop=True))
    args_t, args_s = drift_device_args(tgt, src, bin_size=10)
    assert len(args_t[0]) == 2 and len(args_t[3]) == 1
    assert args_t[2].shape == (2, 9)
    num_h, cat_h = map(np.asarray, drift_side_full(*args_t))
    assert num_h.shape == (2, 10) and cat_h.shape[0] == 1
    # histogram mass equals the (unpadded) row count per side
    assert num_h.sum(axis=1).tolist() == [150.0, 150.0]
    assert cat_h.sum() == 150.0


def test_cache_gate_flags_zero_hits():
    """The bench record must fail LOUDLY when the fully-cached re-run hits
    nothing (a silently-broken cache otherwise just reads as a slower
    warm wall)."""
    import bench

    ok = bench._cache_fields("cached", {"hits": 14, "misses": 0,
                                        "restore_s": 0.1}, 0.5)
    assert ok["e2e_cache_hits"] == 14 and "e2e_cache_error" not in ok

    broken = bench._cache_fields("cached", {"hits": 0, "misses": 14}, 5.0)
    assert "e2e_cache_error" in broken and broken["e2e_cache_hits"] == 0

    inc = bench._cache_fields("incremental", {"hits": 13, "misses": 1}, 1.0)
    assert inc == {"e2e_incremental_wall_s": 1.0, "e2e_incremental_misses": 1}
    # populate pass contributes no fields
    assert bench._cache_fields("populate", {"misses": 14}, 3.6) == {}


def test_hot_block_budget_gate():
    """Round-9 hot-block gate: the committed budgets trip loudly when the
    fused blocks exceed them, pass when under, and tolerate an absent
    block (a renamed block must not crash the headline — the per-block
    regression test owns name drift)."""
    import bench

    ok = bench.hot_block_budget_check(
        {"geospatial_controller": 0.7, "timeseries_analyzer": 0.55})
    assert ok["e2e_hot_block_budget_ok"] is True
    assert ok["e2e_hot_blocks"]["geospatial_controller"]["budget_s"] == 0.8
    assert "e2e_hot_block_over" not in ok

    bad = bench.hot_block_budget_check(
        {"geospatial_controller": 1.4, "timeseries_analyzer": 0.55})
    assert bad["e2e_hot_block_budget_ok"] is False
    assert "geospatial_controller" in bad["e2e_hot_block_over"]

    missing = bench.hot_block_budget_check({"timeseries_analyzer": 0.5})
    assert missing["e2e_hot_block_budget_ok"] is True
    assert missing["e2e_hot_blocks"]["geospatial_controller"]["warm_s"] is None
