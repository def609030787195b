#!/usr/bin/env python3
"""Chip smoke test: ``configs_full.yaml`` end to end on the TPU, checked
against pandas.

    python chip_smoke.py                  # one chip, 4,000,000 rows, seed 7
    python chip_smoke.py --rows 32561     # a small first call
    python chip_smoke.py --chips 4        # the four-chip phase (run by hand)

One process, no CPU run: without a TPU it exits non-zero before any work.
Default (one chip): generate the seeded income dataset, run
``workflow.run`` on a copy of ``config/configs_full.yaml`` twice in this
process (cold, then warm), require a clean run (no degraded section, no
failover, no retry, backend ``tpu``, every artifact present) and compare the
reported statistics with a float64 pandas computation on the same frame.
``--chips 4``: data, a placement check of the row-sharded table, ONE cold
run of the whole pipeline on the four-chip mesh and the same checks — nothing
else.  What four chips do warm, pass after pass, is the benchmark's to say:
the cell ``income_1m_x4.stats`` (``chiprun --chips 4 -- python3
benchmark/run.py --workload income_1m_x4.stats ...``, PERF.md).

The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
the lines before it are for reading.  The phases are functions so that
tests/test_chip_smoke.py can rehearse them on the CPU at 2,000 rows.
"""

from __future__ import annotations

import argparse
import glob
import json
import logging
import os
import shutil
import sys
import time

import numpy as np
import pandas as pd

ROWS = 4_000_000  # the benchmark size (README "Benchmark")
SEED = 7
HERE = os.path.dirname(os.path.abspath(__file__))
WORK_DIR = os.path.join(HERE, "data", "chip_smoke")  # git-ignored

NUM_COLS = ["age", "fnlwgt", "education_num", "capital-gain", "capital-loss",
            "hours-per-week", "label", "latitude", "longitude"]
# drift columns no quality treatment of configs_full alters (no nulls, no
# outliers, not an id): what the pipeline bins is what pandas bins
PSI_COLS = ["education", "race", "sex", "relationship", "education_num"]
STATS_CSVS = ["global_summary", "measures_of_counts", "measures_of_centralTendency",
              "measures_of_cardinality", "measures_of_percentiles",
              "measures_of_dispersion", "measures_of_shape"]
# per statistic, the tolerance of the parity tests (tests/test_golden.py,
# tests/test_stats_generator.py, tests/test_drift_stability.py)
TOL = {"mean": dict(rtol=1e-4), "stddev": dict(rtol=1e-3), "median": dict(rtol=1e-3),
       "min": dict(rtol=1e-5), "max": dict(rtol=1e-5), "PSI": dict(atol=2e-4)}


def say(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------------ data ----
def generate_data(rows: int, seed: int, dest: str) -> float:
    """The seeded dataset under ``dest``; returns the seconds it took."""
    from anovos_tpu.data_ingest.synthetic import generate

    t0 = time.perf_counter()
    generate(rows, seed, dest)
    return time.perf_counter() - t0


def write_config(data_dir: str, path: str) -> str:
    """A copy of config/configs_full.yaml reading ``data_dir``.  Two drift
    settings differ from the shipped file, so that PSI is a checkable
    number: the drift source is the generated drifted baseline (the shipped
    config compares the table with itself) and drift runs on all rows (the
    default 100,000-row random sample cannot be held to a parity tolerance)."""
    import yaml

    from anovos_tpu.data_ingest.synthetic import rebase_config

    with open(os.path.join(HERE, "config", "configs_full.yaml")) as f:
        cfg = rebase_config(yaml.safe_load(f), "data/income_dataset", data_dir)
    drift = cfg["drift_detector"]["drift_statistics"]
    drift["source_dataset"]["read_dataset"]["file_path"] = os.path.join(data_dir, "source")
    drift["configs"]["use_sampling"] = False
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        yaml.safe_dump(cfg, f, sort_keys=False)
    return path


# -------------------------------------------------------------- pipeline ----
def run_pipeline(config_path: str, out_dir: str) -> dict:
    """One ``workflow.run`` with a fresh ``out_dir`` as working directory;
    the wall ends when the call returns, after the last artifact is written."""
    from anovos_tpu import workflow

    cwd = os.getcwd()
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    os.chdir(out_dir)
    try:
        t0 = time.perf_counter()
        workflow.run(config_path, "local")
        wall = time.perf_counter() - t0
    finally:
        os.chdir(cwd)
    with open(os.path.join(out_dir, "report_stats", "obs", "run_manifest.json")) as f:
        manifest = json.load(f)
    return {"wall_s": wall, "manifest": manifest, "out_dir": out_dir}


def describe_run(label: str, run: dict) -> None:
    import jax

    m = run["manifest"]
    cc = m.get("compile_census") or {}
    say(f"[{label}] wall {run['wall_s']:.2f} s; programs compiled "
        f"{cc.get('compiles_total')} ({cc.get('distinct_programs')} distinct), "
        f"compile {cc.get('compile_seconds_total')} s; backend {m.get('backend')}")
    stats = jax.devices()[0].memory_stats() or {}
    say(f"[{label}] device 0 peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    slow = sorted(m.get("block_seconds", {}).items(), key=lambda kv: -kv[1])[:10]
    say(f"[{label}] ten slowest blocks (s): " + "; ".join(f"{k} {v:.2f}" for k, v in slow))


def check_run(run: dict, platform: str = "tpu") -> list:
    """Step 4d: a clean run on ``platform`` with every artifact written."""
    from anovos_tpu.resilience import failover

    m, out = run["manifest"], run["out_dir"]
    res = m.get("resilience") or {}
    bad = []
    if m.get("backend") != platform:
        bad.append(f"manifest backend {m.get('backend')!r}, expected {platform!r}")
    if res.get("degraded_sections") or res.get("degraded"):
        bad.append(f"degraded sections: {res.get('degraded_sections') or res.get('degraded')}")
    if failover.failover_count() or res.get("failovers"):
        bad.append(f"backend failovers: {failover.failover_count()}")
    for key in ("retries", "timeout_retries", "failover_retries", "timeout_escalations"):
        if res.get(key):
            bad.append(f"resilience.{key} = {res[key]}")
    expected = [os.path.join("report_stats", f"{n}.csv") for n in STATS_CSVS]
    expected += [os.path.join("report_stats", n) for n in
                 ("drift_statistics.csv", "duplicate_detection.csv", "nullRows_detection.csv",
                  "ml_anovos_report.html")]
    for rel in expected:
        if not os.path.exists(os.path.join(out, rel)):
            bad.append(f"missing artifact {rel}")
    if not glob.glob(os.path.join(out, "output", "final_dataset", "*.parquet")):
        bad.append("missing artifact output/final_dataset/*.parquet")
    return bad


# ------------------------------------------------------ pandas reference ----
def _read_parts(path: str) -> pd.DataFrame:
    files = sorted(glob.glob(os.path.join(path, "*.parquet")))
    df = pd.concat([pd.read_parquet(f) for f in files], ignore_index=True)
    # the config's ETL: delete, rename (recast float→float is a no-op)
    return df.drop(columns=["logfnl", "empty", "dt_2"]).rename(
        columns={"marital-status": "marital_status", "education-num": "education_num"})


def _psi(src: pd.Series, tgt: pd.Series, bins: int = 10) -> float:
    """PSI as the reference defines it: equal-range bins from the SOURCE's
    min/max (right-closed), frequencies over the full row count, nulls
    dropped, an empty bin counted as 1e-4, natural log."""
    if pd.api.types.is_numeric_dtype(src):
        s, t = src.to_numpy(float), tgt.to_numpy(float)
        lo, hi = np.nanmin(s), np.nanmax(s)
        cuts = lo + (hi - lo) * np.arange(1, bins) / bins
        keys = np.arange(bins)
        p = np.bincount(np.searchsorted(cuts, s[~np.isnan(s)], side="left"), minlength=bins)
        q = np.bincount(np.searchsorted(cuts, t[~np.isnan(t)], side="left"), minlength=bins)
    else:
        keys = sorted(set(src.dropna().unique()) | set(tgt.dropna().unique()))
        p = src.value_counts().reindex(keys).fillna(0).to_numpy()
        q = tgt.value_counts().reindex(keys).fillna(0).to_numpy()
    assert len(keys) == len(p) == len(q)
    p, q = p / len(src), q / len(tgt)
    p, q = np.where(p == 0, 1e-4, p), np.where(q == 0, 1e-4, q)
    return float(((p - q) * np.log(p / q)).sum())


def reference(data_dir: str) -> dict:
    """Plain float64 pandas on the generated frame: numeric summary, the
    duplicate and null-row counts, PSI of ``PSI_COLS``."""
    df = _read_parts(os.path.join(data_dir, "parquet"))
    num = df[NUM_COLS].astype("float64")
    summary = pd.DataFrame({
        "count": num.count(), "mean": num.mean(), "stddev": num.std(ddof=1),
        "min": num.min(), "max": num.max(), "median": num.median()})
    dup = df.drop(columns=["ifa"]).duplicated()
    kept = df[~dup]  # duplicate_detection treats (drops) before nullRows runs
    null_rows = kept.isna().sum(axis=1).value_counts().to_dict()
    src = _read_parts(os.path.join(data_dir, "source"))
    psi = {c: _psi(src[c], kept[c]) for c in PSI_COLS}
    return {"rows": len(df), "summary": summary, "duplicate_rows": int(dup.sum()),
            "null_rows": {int(k): int(v) for k, v in null_rows.items()}, "psi": psi}


def check_answers(run: dict, ref: dict) -> list:
    """Step 4e: the run's CSVs against :func:`reference`."""
    rs = os.path.join(run["out_dir"], "report_stats")

    def csv(name):
        return pd.read_csv(os.path.join(rs, name + ".csv"))

    def metric(name, key):  # the metric,value CSVs
        df = csv(name)
        return int(float(dict(zip(df["metric"], df["value"]))[key]))

    ours = (csv("measures_of_counts").merge(csv("measures_of_centralTendency"), on="attribute")
            .merge(csv("measures_of_dispersion"), on="attribute")
            .merge(csv("measures_of_percentiles"), on="attribute").set_index("attribute"))
    bad = []
    worst = {}
    for col in NUM_COLS:
        exp = ref["summary"].loc[col]
        if int(ours.loc[col, "fill_count"]) != int(exp["count"]):
            bad.append(f"{col} count {ours.loc[col, 'fill_count']} != {exp['count']}")
        for stat in ("mean", "stddev", "min", "max", "median"):
            got, want = float(ours.loc[col, stat]), float(exp[stat])
            # the CSVs round to 4 decimals
            ok = np.isclose(got, want, atol=5.1e-5, **TOL[stat])
            rel = abs(got - want) / max(abs(want), 1e-12)
            worst[stat] = max(worst.get(stat, (0.0, col)), (rel, col))
            if not ok:
                bad.append(f"{col} {stat}: {got} vs pandas {want} (rel {rel:.2e})")
    say("[check] worst relative gap vs pandas float64: "
        + ", ".join(f"{k} {v:.2e} ({c})" for k, (v, c) in worst.items()))
    for name, key, want in (("global_summary", "rows_count", ref["rows"]),
                            ("duplicate_detection", "duplicate_rows", ref["duplicate_rows"])):
        if metric(name, key) != want:
            bad.append(f"{key} {metric(name, key)} != {want}")
    nr = csv("nullRows_detection")
    got_nr = {int(k): int(v) for k, v in zip(nr["null_cols_count"], nr["row_count"])}
    if got_nr != ref["null_rows"]:
        bad.append(f"null-row counts {got_nr} != {ref['null_rows']}")
    drift = csv("drift_statistics").set_index("attribute")
    for col, want in ref["psi"].items():
        got = float(drift.loc[col, "PSI"])
        if not np.isclose(got, want, rtol=0, **TOL["PSI"]):
            bad.append(f"PSI {col}: {got} vs pandas {want:.6f}")
    say(f"[check] rows {ref['rows']}, duplicate rows {ref['duplicate_rows']}, "
        f"null-row histogram {got_nr}; PSI ours/pandas: "
        + ", ".join(f"{c} {float(drift.loc[c, 'PSI'])}/{v:.4f}" for c, v in ref["psi"].items()))
    return bad


# ------------------------------------------------------------- placement ----
def check_placement(data_dir: str) -> list:
    """After ingest every numeric column and the stacked block are
    row-sharded over every device in equal shards, and (where the backend
    reports it) the devices' bytes in use agree within 10 %."""
    import jax

    from anovos_tpu.data_ingest import read_dataset
    from anovos_tpu.shared.runtime import get_runtime

    n_dev = get_runtime().n_devices
    t = read_dataset(os.path.join(data_dir, "parquet"), "parquet")
    num_cols = [c for c in t.col_names if t[c].kind == "num"]
    X, M = t.numeric_block(num_cols)
    jax.block_until_ready((X, M))
    bad = []
    arrays = {"numeric_block": X, "numeric_mask": M}
    arrays.update({c: t[c].data for c in t.col_names})
    for name, a in arrays.items():
        shapes = {s.data.shape for s in a.addressable_shards}
        if len(a.sharding.device_set) != n_dev or len(a.addressable_shards) != n_dev \
                or len(shapes) != 1:
            bad.append(f"{name}: on {len(a.sharding.device_set)} of {n_dev} devices, "
                       f"shard shapes {sorted(shapes)}")
    used = [(d.memory_stats() or {}).get("bytes_in_use") for d in jax.devices()]
    say(f"[placement] {len(arrays)} arrays over {n_dev} device(s), block {X.shape} "
        f"shard {X.addressable_shards[0].data.shape}; bytes_in_use per device {used}")
    if all(u is not None for u in used) and max(used) > 1.1 * min(used):
        bad.append(f"bytes_in_use differ by more than 10 %: {used}")
    return bad


# ------------------------------------------------------------------ main ----
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rows", type=int, default=ROWS)
    ap.add_argument("--seed", type=int, default=SEED)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: the four-chip phase only (placement + one cold run)")
    args = ap.parse_args(argv)

    import jax

    # the workflow's per-block timing lines, on stderr
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(name)s %(message)s")
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) != args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s), JAX reports {len(devs)} x "
              f"{devs[0].platform} — there is no CPU run of this script", file=sys.stderr)
        return 2
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    say(f"[device] {device}")

    from anovos_tpu.shared import native

    say(f"[native] libanovos_native built: {native.get_native() is not None} "
        "(avro decode only; off this path)")
    data_dir = os.path.join(WORK_DIR, "income_dataset")
    gen_s = generate_data(args.rows, args.seed, data_dir)
    say(f"[data] {args.rows} rows x 24 columns, seed {args.seed}: generated in {gen_s:.1f} s")
    t0 = time.perf_counter()
    ref = reference(data_dir)
    say(f"[reference] pandas float64 in {time.perf_counter() - t0:.1f} s")

    bad = []
    if args.chips == 4:
        bad += check_placement(data_dir)
    labels = ["cold"] if args.chips == 4 else ["cold", "warm"]
    config_path = write_config(data_dir, os.path.join(WORK_DIR, "configs_full.yaml"))
    for label in labels:
        run = run_pipeline(config_path, os.path.join(WORK_DIR, f"run_{label}"))
        describe_run(label, run)
        bad += [f"{label}: {b}" for b in check_run(run) + check_answers(run, ref)]
    for b in bad:
        say(f"FAIL {b}")
    if bad:
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
