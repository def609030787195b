"""Perf doctor CLI: differential run observability from the command line.

Front-end for :mod:`anovos_tpu.obs.diffing` — takes two runs and prints
the ranked diagnosis (which knob / program set / cache input / node phase
moved), so nobody hand-diffs ``run_manifest.json`` files again.

Modes::

    # two manifests (files, run dirs, or obs dirs — resolved either way)
    python -m tools.perf_doctor --baseline runs/r08 --candidate runs/r09
    python -m tools.perf_doctor old_manifest.json new_manifest.json

    # machine-readable (canonical JSON — byte-stable for a given pair)
    python -m tools.perf_doctor --json ...

Exit codes: 0 diagnosis produced, 1 refused / failed (cross-backend-class
pairs are refused loudly — a different machine is not a regression),
2 usage error.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import List, Optional

from anovos_tpu.obs.diffing import (
    DiffRefused,
    canonical,
    diff_manifests,
    find_manifest,
    render_text,
    validate_diagnosis,
)


def _load_manifest(path: str) -> dict:
    with open(find_manifest(path)) as f:
        return json.load(f)


def _print_diagnosis(diag: dict, as_json: bool, top: int) -> None:
    if as_json:
        print(canonical(diag))
        return
    b, c = diag["baseline"], diag["candidate"]
    print(f"perf_doctor: {diag['kind']} diff — {b['label']} -> {c['label']} "
          f"(backend class {diag['backend_class']})")
    if diag.get("wall_delta_s") is not None:
        print(f"  wall: {b.get('wall_s')}s -> {c.get('wall_s')}s "
              f"({diag['wall_delta_s']:+.3f}s)")
    lines = render_text(diag, top=top)
    if not lines:
        print("  no attributable movement (runs are equivalent within noise)")
    for line in lines:
        print("  " + line)
    n_extra = len(diag.get("attributions") or []) - len(lines)
    if n_extra > 0:
        print(f"  ... {n_extra} more attribution(s) (--top 0 for all, "
              "--json for the full diagnosis)")


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="perf_doctor",
        description="structural run-diff: manifest/census/trace diffing "
                    "with automated regression attribution")
    ap.add_argument("manifests", nargs="*",
                    help="two manifest files / run dirs (positional form)")
    ap.add_argument("--baseline", help="baseline manifest file or run dir")
    ap.add_argument("--candidate", help="candidate manifest file or run dir")
    ap.add_argument("--json", action="store_true",
                    help="canonical JSON diagnosis on stdout")
    ap.add_argument("--top", type=int, default=3,
                    help="attribution lines to print (0 = all; default 3)")
    ns = ap.parse_args(argv)

    try:
        paths = list(ns.manifests)
        if ns.baseline:
            paths.insert(0, ns.baseline)
        if ns.candidate:
            paths.append(ns.candidate)
        if len(paths) != 2:
            ap.error("need exactly two runs: two positional paths, or "
                     "--baseline + --candidate")
        base_man = _load_manifest(paths[0])
        cand_man = _load_manifest(paths[1])
        t0 = time.perf_counter()
        diag = diff_manifests(base_man, cand_man,
                              baseline_label=paths[0],
                              candidate_label=paths[1])
    except DiffRefused as e:
        print(f"perf_doctor: REFUSED — {e}", file=sys.stderr)
        return 1
    except (OSError, ValueError) as e:
        print(f"perf_doctor: failed — {e}", file=sys.stderr)
        return 1
    errs = validate_diagnosis(diag)
    if errs:  # the engine's own output contract, enforced on every run
        print("perf_doctor: internal schema violation:\n  " + "\n  ".join(errs),
              file=sys.stderr)
        return 1
    _print_diagnosis(diag, ns.json, ns.top)
    if not ns.json:
        print(f"perf_doctor: diagnosed in {time.perf_counter() - t0:.3f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
