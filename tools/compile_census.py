"""Render / gate the XLA compile census of a run manifest.

Reads the ``compile_census`` section ``workflow.main`` embeds in
``obs/run_manifest.json`` (obs.compile_census: every program that reached
the backend, attributed per program, with what its trace, its lowering and
its load from the persistent cache or its build took) and prints the top-N
programs — the cold-run tail the column/row shape bucketing exists to keep
short, and on a warm start the programs the cache did not hold.

CI gate: ``--assert-max-programs N`` (and ``--assert-max-compiles N``)
exits non-zero when the run compiled more distinct program signatures
(resp. total compiles) than the budget — a per-call ``jax.jit``, a
missing shape bucket, or a new per-column eager loop re-inflates the cold
wall loudly instead of silently (the regression class PERF.md's round-4
census caught by hand: a per-call closure jit recompiling 10 programs per
ts_analyzer call).

Usage::

    python -m tools.compile_census <run_manifest.json> [--top N]
        [--assert-max-programs N] [--assert-max-compiles N]
"""

from __future__ import annotations

import argparse
import json
import sys


def load_census(manifest_path: str) -> dict:
    with open(manifest_path) as f:
        manifest = json.load(f)
    census = manifest.get("compile_census")
    if not census:
        raise SystemExit(
            f"{manifest_path}: no compile_census section — manifest predates "
            "the census (re-run the workflow) or the run recorded no compiles"
        )
    return census


_STAGE_COLUMNS = ("trace_s", "lower_s", "load_s", "build_s")


def format_census(census: dict, top: int = 15) -> str:
    lines = [
        "compiles_total={compiles_total}  distinct_programs={distinct_programs}  "
        "distinct_kernels={distinct_kernels}  compile_wall_s={compile_seconds_total}".format(**census),
    ]
    # the stages of a program's way to the device and the cache's answers
    # (absent on older manifests): a slow start is builds where loads were
    # expected, or loads that are slow
    staged = "built_programs" in census
    if staged:
        lines.append(
            "cache_requests={cache_requests}  cache_hits={cache_hits}  cache_writes={cache_writes}  "
            "built_programs={built_programs}  trace_s={trace_seconds_total}  lower_s={lower_seconds_total}  "
            "load_s={load_seconds_total}  build_s={build_seconds_total}  self_s={self_seconds_total}"
            .format(**census))
    stage_head = "".join(f"  {c:>8}" for c in _STAGE_COLUMNS) + f"  {'hits':>5}" if staged else ""
    lines.append(f"{'seconds':>9}  {'count':>5}{stage_head}  program")
    for row in census.get("programs", [])[: top or None]:
        # node attribution (census events are stamped with the scheduler node
        # open on the dispatching thread at compile time — fused-block programs
        # then name the node that owns them; absent on older manifests)
        nodes = row.get("nodes") or []
        node_s = ""
        if nodes:
            shown = ", ".join(nodes[:3]) + (f", +{len(nodes) - 3}" if len(nodes) > 3 else "")
            node_s = f"  [{shown}]"
        stages = ("".join(f"  {row.get(c, 0.0):8.3f}" for c in _STAGE_COLUMNS) + f"  {row.get('hits', 0):5d}"
                  if staged else "")
        lines.append(f"{row['seconds']:9.3f}  {row['count']:5d}{stages}  {row['program']}{node_s}")
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("manifest", help="path to obs/run_manifest.json")
    p.add_argument("--top", type=int, default=15, help="programs to print (0 = all)")
    p.add_argument("--assert-max-programs", type=int, default=None,
                   help="fail if distinct_programs exceeds this budget")
    p.add_argument("--assert-max-compiles", type=int, default=None,
                   help="fail if compiles_total exceeds this budget")
    args = p.parse_args(argv)
    census = load_census(args.manifest)
    print(format_census(census, args.top))
    rc = 0
    if args.assert_max_programs is not None and census["distinct_programs"] > args.assert_max_programs:
        print(
            f"FAIL: distinct_programs {census['distinct_programs']} > budget "
            f"{args.assert_max_programs} — a shape-variant or per-call-jit "
            "regression re-inflated the cold compile tail",
            file=sys.stderr,
        )
        rc = 2
    if args.assert_max_compiles is not None and census["compiles_total"] > args.assert_max_compiles:
        print(
            f"FAIL: compiles_total {census['compiles_total']} > budget "
            f"{args.assert_max_compiles}",
            file=sys.stderr,
        )
        rc = 2
    return rc


if __name__ == "__main__":
    sys.exit(main())
