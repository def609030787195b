"""The pipeline driver: one client in a closed loop calls ``workflow.run`` on
the cell's pipeline YAML, each pass into a fresh output directory.

Set-up makes the data from the seed and runs the fresh pass (the first of
the process, with the compile cache as the checkout holds it); the window
then starts passes until ``seconds`` have gone and finishes the one in
flight; nothing but the passes runs inside it.  Once the window has closed,
every pass's manifest and artifacts are read, the plain reference runs and
``correct`` is decided: the last pass of the window against the reference
(the comparisons the traffic mix names, ``benchmark/checks/``), and every
other pass of the run, the fresh one included, byte for byte against the
last in every file it left.  The loop and the clean-run check are copies of
``chip_smoke.py``'s as PR 22 left them.
"""

from __future__ import annotations

import json
import os
import shutil
import statistics
import time

import yaml

from benchmark.harness import check
from benchmark.harness.frames import Frames
from benchmark.harness.names import load_module


def _rebase(node, old: str, new: str):
    """Copy of a loaded YAML with every string that starts with ``old`` re-rooted at ``new``."""
    if isinstance(node, dict):
        return {k: _rebase(v, old, new) for k, v in node.items()}
    if isinstance(node, list):
        return [_rebase(v, old, new) for v in node]
    if isinstance(node, str) and node.startswith(old):
        return new + node[len(old):]
    return node


def write_pipeline_config(traffic_yaml: str, data_dir: str, path: str) -> dict:
    """The traffic mix's YAML with its data paths re-rooted, written to ``path``."""
    with open(traffic_yaml) as f:
        cfg = _rebase(yaml.safe_load(f), "DATASET/", data_dir.rstrip("/") + "/")
    with open(path, "w") as f:
        yaml.safe_dump(cfg, f, sort_keys=False)
    return cfg


def one_pass(config_path: str, out_dir: str, profile_dir: str = "") -> dict:
    """One ``workflow.run`` with a fresh ``out_dir`` as working directory.  The
    wall ends when the call returns, after the last artifact is written."""
    from anovos_tpu import workflow

    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    cwd = os.getcwd()
    os.chdir(out_dir)
    if profile_dir:
        os.environ["ANOVOS_PROFILE"] = profile_dir  # the program's own switch
    p = {"out_dir": out_dir, "traced": bool(profile_dir), "manifest": {}, "bad": []}
    try:
        p["start"] = time.perf_counter()
        try:
            workflow.run(config_path, "local")
        except Exception as e:  # a pass that raises is a failed pass, not a lost run
            p["bad"].append(f"raised {type(e).__name__}: {e}")
        p["end"] = time.perf_counter()
    finally:
        os.chdir(cwd)
        if profile_dir:
            del os.environ["ANOVOS_PROFILE"]
    p["wall_s"] = p["end"] - p["start"]
    return p


def inspect(p: dict, traffic: dict, platform: str) -> dict:
    """Read what a pass left: its manifest, the clean-run check, the digest
    of its files.  After the window, so that the window holds only passes."""
    if not p["bad"]:
        try:
            with open(os.path.join(p["out_dir"], traffic["manifest"])) as f:
                p["manifest"] = json.load(f)
            p["bad"] = check.clean_run(p["manifest"], p["out_dir"], traffic, platform)
            p["digest"] = check.digest(p["out_dir"], traffic)
        except OSError as e:
            p["bad"].append(f"unreadable artifact: {e}")
    return p


def run(cell: dict) -> dict:
    """Drive one run of a cell.  ``cell``: ``config`` and ``traffic`` (the parsed
    JSON files), ``traffic_yaml``, ``work_dir``, ``seed``, ``seconds``, ``trace``,
    ``platform``, ``t_start`` (``perf_counter`` at process start), ``say``."""
    from anovos_tpu import workflow  # noqa: F401  the program, before any work: a checkout without it fails here

    config, traffic, say = cell["config"], cell["traffic"], cell["say"]
    work, platform = cell["work_dir"], cell["platform"]
    dataset = load_module("datasets", config["dataset"]["module"])
    rows = config["rows"]
    args = {k: v for k, v in config["dataset"].items() if k != "module"}

    # ---- set-up: data from the seed, the fresh pass ----
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    data_dir = os.path.join(work, "dataset")
    t0 = time.perf_counter()
    dataset.generate(data_dir, cell["seed"], traffic["dataset_parts"], rows=rows,
                     source_rows=config["baseline_rows"], **args)
    say(f"[data] {rows} rows, seed {cell['seed']}, parts {traffic['dataset_parts']}: "
        f"{time.perf_counter() - t0:.2f} s")
    config_path = os.path.join(work, "pipeline.yaml")
    pipeline_cfg = write_pipeline_config(cell["traffic_yaml"], data_dir, config_path)
    fresh = one_pass(config_path, os.path.join(work, "pass_fresh"))
    say(f"[fresh] wall {fresh['wall_s']:.3f} s")

    # ---- the window: passes and nothing else ----
    passes = [fresh]
    trace_dir = os.path.join(work, "trace") if cell["trace"] else ""
    w_start = time.perf_counter()
    while not passes[-1]["bad"] and (len(passes) == 1 or time.perf_counter() - w_start < cell["seconds"]):
        passes.append(one_pass(config_path, os.path.join(work, f"pass_{len(passes):04d}"),
                               profile_dir=trace_dir if len(passes) == 1 else ""))
    w_end = time.perf_counter()

    import jax

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices())
    for p in passes:
        inspect(p, traffic, platform)
    window = passes[1:]
    timed = [p for p in window if not p["traced"] and not p["bad"]]
    cc = fresh["manifest"].get("compile_census") or {}
    say(f"[fresh] programs {cc.get('compiles_total')} ({cc.get('distinct_programs')} distinct), "
        f"compile {cc.get('compile_seconds_total')} s summed")
    say(f"[window] {len(window)} passes in {w_end - w_start:.3f} s; walls "
        + " ".join(f"{p['wall_s']:.3f}" for p in window[:60]))

    # ---- correct: after the window, the reference and the comparison ----
    failed = [p for p in passes if p["bad"]]
    for p in failed:
        say(f"FAIL pass {os.path.basename(p['out_dir'])}: " + "; ".join(p["bad"]))
    rows_out = []
    if not failed:
        t0 = time.perf_counter()
        last = passes[-1]
        rows_out = check.compare_all(last["out_dir"], traffic, Frames(pipeline_cfg),
                                     config["guarantees"]["tolerances"])
        differ = sorted({f"{os.path.basename(p['out_dir'])}/{rel}" for p in passes[:-1]
                         for rel in set(p["digest"]) | set(last["digest"])
                         if p["digest"].get(rel) != last["digest"].get(rel)})
        rows_out.append({"name": "files_with_other_bytes", "value": len(differ), "limit": 0,
                         "ok": not differ, "detail": ", ".join(differ[:5])})
        say(f"[reference] float64 pandas and its comparisons in {time.perf_counter() - t0:.2f} s "
            f"(after the window, not in setup_s); {len(last['digest'])} files a pass held to the same bytes")
        for r in rows_out:
            say(f"[check] {r['name']}: {r['value']:.6g} (limit {r['limit']:g}) "
                f"{'ok' if r['ok'] else 'FAIL'}  {r['detail']}")
    correct = bool(rows_out) and all(r["ok"] for r in rows_out) and bool(timed)
    for p in passes[:-1]:  # the last pass stays, for whoever reads a failure
        shutil.rmtree(p["out_dir"], ignore_errors=True)

    metrics = {"fresh_pass_s": fresh["wall_s"], "setup_s": w_start - cell["t_start"]}
    if timed:
        metrics["pass_s"] = statistics.median(p["wall_s"] for p in timed)
        metrics["rows_per_s"] = rows * len(window) / (w_end - w_start)
    return {
        "correct": correct, "attempted": len(passes), "failed": len(failed),
        "metrics": metrics, "memory_peak_bytes": int(peak),
        # for the per-layer readers
        "rows": rows, "fresh": fresh, "passes": timed,
        "traced": next((p for p in window if p["traced"]), None), "trace_dir": trace_dir,
        "checks": rows_out,
    }
