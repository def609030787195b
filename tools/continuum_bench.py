"""Continuum bench: the 30-day simulated feed.

Builds a month of daily partitions — schema drift mid-month (day 15
grows a column), one corrupt day (day 20's parquet is garbage bytes), a
distribution shift (day 25's mean jumps) — and measures the continuum
service against a from-scratch batch run over the union:

* **incremental leg** — partitions land one day at a time, one
  ``watcher.step`` per arrival; per-day fold wall recorded from the step
  summary (decode + fold only — the O(new rows) claim);
* **batch leg** — all 30 days present, ONE step from empty state (the
  same sufficient-stats code path, so byte parity is the associativity /
  order-insensitivity of the contract, not a lucky duplicate
  implementation).

Emitted fields (``--json``):

* ``e2e_continuum_fold_s`` — median per-day incremental fold wall;
* ``e2e_continuum_vs_batch_ratio`` — that median over the batch-leg
  wall (≪ 1 is the point of the subsystem: a day's fold must not cost a
  month's recompute);
* ``e2e_continuum_alerts`` — drift alerts emitted across the feed (the
  shift day must fire);
* ``continuum_day2_fold_s`` / ``continuum_day30_fold_s`` /
  ``continuum_day30_vs_day2`` — history-independence: day 30's fold
  within 2× day 2's (acceptance gate);
* ``continuum_parity`` — artifact-tree byte parity between the legs
  (obs/ excluded), ``continuum_quarantined`` — the corrupt day, on both.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import pathlib
import shutil
import statistics
import sys
import tempfile
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

SHIFT_DAY = 25
CORRUPT_DAY = 20
SCHEMA_DRIFT_DAY = 15


def build_feed_30d(root: str, days: int = 30, rows_per_day: int = 2000,
                   seed: int = 13) -> str:
    """The canonical 30-day feed under ``root``: one parquet per day with
    the three planted events.  Idempotent (skips when present)."""
    import numpy as np
    import pandas as pd

    if os.path.isdir(root) and os.listdir(root):
        return root
    os.makedirs(root, exist_ok=True)
    rng = np.random.default_rng(seed)
    for i in range(1, days + 1):
        shift = 6.0 if i >= SHIFT_DAY else 0.0
        df = pd.DataFrame({
            "amount": rng.normal(100.0 + shift, 12.0, rows_per_day),
            "score": rng.exponential(3.0, rows_per_day),
            "segment": rng.choice(["retail", "corp", "gov"], rows_per_day,
                                  p=[0.6, 0.3, 0.1]),
        })
        if i >= SCHEMA_DRIFT_DAY:  # schema drift mid-month: a new column
            df["late_feature"] = rng.normal(0.0, 1.0, rows_per_day)
        path = os.path.join(root, f"day-{i:02d}.parquet")
        df.to_parquet(path, index=False)
        if i == CORRUPT_DAY:  # one corrupt day: not parquet at all
            with open(path, "wb") as f:
                f.write(b"\x00CORRUPTED-DAY\x00" * 256)
    return root


def feed_config(workdir: str, tag: str, feed_dir: str) -> "object":
    from anovos_tpu.continuum.watcher import ContinuumConfig

    return ContinuumConfig.from_dict({
        "dataset_path": feed_dir,
        "state_dir": os.path.join(workdir, tag, "state"),
        "output_path": os.path.join(workdir, tag, "out"),
        "drift": {"baseline": "day-01*", "threshold": 0.2},
    }, base_dir=workdir)


def artifact_tree_hash(root: str) -> str:
    """sha256 over (relpath, bytes); obs/ is run-varying telemetry and
    excluded (the tests/test_cache.py golden-tree rule)."""
    h = hashlib.sha256()
    rootp = pathlib.Path(root)
    for p in sorted(rootp.rglob("*")):
        if p.is_file() and "obs" not in p.parts:
            h.update(str(p.relative_to(rootp)).encode())
            h.update(p.read_bytes())
    return h.hexdigest()


def run(days: int = 30, rows_per_day: int = 2000,
        workdir: str = None) -> dict:
    from anovos_tpu.continuum.watcher import step
    from anovos_tpu.data_ingest import guard
    from anovos_tpu.shared.runtime import init_runtime

    init_runtime()
    workdir = workdir or tempfile.mkdtemp(prefix="anovos_continuum_bench_")
    src = build_feed_30d(os.path.join(workdir, "alldays"), days=days,
                         rows_per_day=rows_per_day)
    day_files = sorted(os.listdir(src))

    # ---- incremental leg: one arrival per day -----------------------------
    inc_cfg = feed_config(workdir, "inc", os.path.join(workdir, "inc", "feed"))
    os.makedirs(inc_cfg.dataset_path, exist_ok=True)
    guard.reset()
    fold_walls = []
    alerts = 0
    shift_alert_day = None
    t_inc = time.monotonic()
    for i, fn in enumerate(day_files, start=1):
        shutil.copy2(os.path.join(src, fn), os.path.join(inc_cfg.dataset_path, fn))
        s = step(inc_cfg)
        fold_walls.append(s["fold_wall_s"])
        alerts += s["alerts"]
        if s["alerts"] and i >= SHIFT_DAY and shift_alert_day is None:
            shift_alert_day = i
    inc_wall = round(time.monotonic() - t_inc, 3)
    inc_quar = sorted(
        k for k, e in __import__("json").loads(
            open(os.path.join(inc_cfg.state_dir, "state_manifest.json")).read()
        )["parts"].items() if e.get("quarantined"))

    # ---- batch leg: the union, one step from empty state ------------------
    bat_cfg = feed_config(workdir, "bat", src)
    guard.reset()
    t_bat = time.monotonic()
    sb = step(bat_cfg)
    batch_wall = round(time.monotonic() - t_bat, 3)
    bat_quar = sb["quarantined"]

    parity = artifact_tree_hash(inc_cfg.output_path) == artifact_tree_hash(
        bat_cfg.output_path)
    med_fold = round(statistics.median(fold_walls), 4)
    day2 = fold_walls[1] if len(fold_walls) > 1 else fold_walls[0]
    day_last = fold_walls[-1]
    return {
        "e2e_continuum_fold_s": med_fold,
        "e2e_continuum_vs_batch_ratio": round(med_fold / max(batch_wall, 1e-9), 4),
        "e2e_continuum_alerts": alerts,
        "continuum_days": days,
        "continuum_rows_per_day": rows_per_day,
        "continuum_incremental_wall_s": inc_wall,
        "continuum_batch_wall_s": batch_wall,
        "continuum_day2_fold_s": round(day2, 4),
        "continuum_day30_fold_s": round(day_last, 4),
        "continuum_day30_vs_day2": round(day_last / max(day2, 1e-9), 3),
        "continuum_parity": parity,
        "continuum_quarantined": inc_quar,
        "continuum_batch_quarantined": sorted(bat_quar),
        "continuum_shift_alert_day": shift_alert_day,
        "workdir": workdir,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="30-day continuum feed bench: incremental fold vs "
                    "from-scratch batch")
    ap.add_argument("--days", type=int, default=30)
    ap.add_argument("--rows", type=int, default=2000, help="rows per day")
    ap.add_argument("--workdir")
    ap.add_argument("--json", action="store_true")
    ns = ap.parse_args(argv)
    result = run(days=ns.days, rows_per_day=ns.rows, workdir=ns.workdir)
    ok = (result["continuum_parity"]
          and result["e2e_continuum_alerts"] >= 1
          and len(result["continuum_quarantined"]) == 1
          and result["continuum_quarantined"] == result["continuum_batch_quarantined"])
    result["ok"] = ok
    sys.stdout.write(json.dumps(result, sort_keys=True, default=str) + "\n")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
