#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/run.py --workload W --seed N --seconds S --trace 0|1

run from the root of a checkout on a machine that holds the chips the cell
asks for.  It knows no cell, configuration, traffic mix, driver or metric by
name: the entry for ``W`` in ``BENCHMARK.json`` names a configuration (its
file names its driver, ``benchmark/drivers/<driver>.py``), a traffic mix
(``benchmark/traffic/<mix>.json`` and what its driver reads beside it) and
the metrics, each per-layer metric with a reader
``benchmark/layer_metrics/<name>.py``.  The last line of stdout is the
result; the lines before it are for reading.  See benchmark/README.md.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # process start, as near as a script can take it

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if ROOT not in sys.path:  # started as a script: the checkout is not on the path yet
    sys.path.insert(0, ROOT)

from benchmark.harness import trace_reduce  # noqa: E402
from benchmark.harness.manifest import slowest_blocks  # noqa: E402
from benchmark.harness.names import load_module  # noqa: E402


def say(msg: str) -> None:
    print(msg, flush=True)


def _in_cell(metric: dict, workload: str, reporting: set) -> bool:
    """A metric with a ``workloads`` key is for those cells; one without is for
    every cell (a per-layer one: every cell that reports what it ``moves``)."""
    if "workloads" in metric:
        return workload in metric["workloads"]
    return "moves" not in metric or metric["moves"] in reporting


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"run.py: no workload {args.workload!r} in BENCHMARK.json", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    config_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, config_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    with open(os.path.join(BENCH, "harness", "peaks.json")) as f:
        peaks = json.load(f)["devices"]

    # the compile cache at one fixed place inside this checkout, whatever the
    # machine's environment says: the program takes the directory it is given.
    # A directory of the benchmark's own: entries that other entry points left
    # in .jax_cache itself under another eviction setting break every write
    # (PERF.md, PR 24)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache", "benchmark")
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) != cell["chips"]:
        print(f"run.py: {args.workload} needs {cell['chips']} TPU chip(s), JAX reports "
              f"{len(devs)} x {devs[0].platform}; there is no CPU run of the benchmark",
              file=sys.stderr)
        return 2
    kind = devs[0].device_kind
    if kind not in peaks:
        print(f"run.py: device kind {kind!r} is not in benchmark/harness/peaks.json", file=sys.stderr)
        return 2
    say(f"[device] tpu, {kind}, {len(devs)} chip(s); {args.workload} seed {args.seed} "
        f"seconds {args.seconds} trace {args.trace}")

    driver = load_module("drivers", config["driver"])
    run = driver.run({
        "workload": args.workload, "config": config, "traffic": traffic,
        "traffic_yaml": os.path.join(BENCH, "traffic", cell["traffic"] + ".yaml"),
        "work_dir": os.path.join(ROOT, "data", "benchmark", args.workload),
        "seed": args.seed, "seconds": args.seconds, "trace": bool(args.trace),
        "platform": "tpu", "t_start": T_START, "say": say,
    })
    result = report(bench, args.workload, run, bool(args.trace))
    result["device"] = {"platform": "tpu", "kind": kind, "count": len(devs),
                        "memory_peak_bytes": run["memory_peak_bytes"], **result["device"]}
    print(json.dumps(result), flush=True)
    return 0


def report(bench: dict, workload: str, run: dict, traced: bool) -> dict:
    """The result line but for the device's identity: with ``traced`` the
    cell's per-layer metrics, each from its reader, else its end-to-end
    metrics as the driver measured them."""
    e2e = [m for m in bench["end_to_end"] if _in_cell(m, workload, set())]
    out = {"correct": run["correct"], "attempted": run["attempted"], "failed": run["failed"],
           "metrics": {}, "device": {}}
    if not traced:
        for m in e2e:
            if m["name"] in run["metrics"]:
                out["metrics"][m["name"]] = {"value": run["metrics"][m["name"]], "unit": m["unit"]}
        return out
    path = trace_reduce.find_xplane(run["trace_dir"])
    nodes = ((run["traced"] or {}).get("manifest", {}).get("scheduler") or {}).get("nodes") or {}
    t0 = time.perf_counter()
    run["trace"] = trace_reduce.reduce(trace_reduce.load(path, nodes)) if path else {}
    say(f"[trace] {path}: reduced in {time.perf_counter() - t0:.2f} s")
    reporting = {m["name"] for m in e2e}
    for m in bench["per_layer"]:
        if not _in_cell(m, workload, reporting):
            continue
        value = load_module("layer_metrics", m["name"]).read(run)
        if value is not None:
            out["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    if run["trace"]:
        out["device"] = {"busy_s": run["trace"]["busy_s"], "window_s": run["trace"]["window_s"]}
        out["breakdown"] = {"device_ops": run["trace"]["device_ops"],
                            "idle_gaps": run["trace"]["idle_gaps"],
                            "blocks": slowest_blocks(run.get("passes") or [])}
    return out


if __name__ == "__main__":
    sys.exit(main())
