"""Perf doctor CLI: differential run observability from the command line.

Front-end for :mod:`anovos_tpu.obs.diffing` — takes two runs and prints
the ranked diagnosis (which knob / program set / cache input / node phase
moved), so nobody hand-diffs ``run_manifest.json`` files again.

Modes::

    # two manifests (files, run dirs, or obs dirs — resolved either way)
    python -m tools.perf_doctor --baseline runs/r08 --candidate runs/r09
    python -m tools.perf_doctor old_manifest.json new_manifest.json

    # two perf-ledger entries, selected by source name / round / index
    python -m tools.perf_doctor --entry-baseline BENCH_r04.json \
                                --entry-candidate BENCH_r05.json

    # CI self-check (tier-1): diff the committed BENCH_r04 -> r05 ledger
    # entries twice, assert a schema-valid, byte-identical diagnosis
    python -m tools.perf_doctor --self-check

    # machine-readable (canonical JSON — byte-stable for a given pair)
    python -m tools.perf_doctor --json ...

Exit codes: 0 diagnosis produced (or self-check passed), 1 refused /
failed (cross-backend-class pairs are refused loudly — a different
machine is not a regression), 2 usage error.
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
    diff_ledger_entries,
    diff_manifests,
    find_manifest,
    render_text,
    validate_diagnosis,
)

SELF_CHECK_BASELINE = "BENCH_r04.json"
SELF_CHECK_CANDIDATE = "BENCH_r05.json"


def _load_manifest(path: str) -> dict:
    with open(find_manifest(path)) as f:
        return json.load(f)


def _select_entry(entries: List[dict], sel: str) -> dict:
    """Ledger entry by source name, round number, content id, or index."""
    for e in entries:
        if e.get("source") == sel or e.get("id") == sel:
            return e
    if sel.lstrip("-").isdigit():
        n = int(sel)
        rounds = [e for e in entries if e.get("round") == n]
        if rounds:
            return rounds[-1]
        try:
            return entries[n]
        except IndexError:
            pass
    raise SystemExit(
        f"perf_doctor: no ledger entry matches {sel!r} (sources: "
        + ", ".join(sorted({str(e.get('source')) for e in entries})) + ")")


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


def self_check() -> int:
    """Tier-1 gate: the committed r04 -> r05 trajectory hop must produce a
    deterministic (byte-identical across a double run), schema-valid,
    non-empty diagnosis from the committed ledger — proving the doctor
    machinery end to end with zero jax and zero workflow runs."""
    from tools.perf_ledger import DEFAULT_LEDGER, load

    entries = load(DEFAULT_LEDGER)
    if not entries:
        print(f"perf_doctor: self-check FAILED — committed ledger at "
              f"{DEFAULT_LEDGER} is empty/missing", file=sys.stderr)
        return 1
    try:
        base = _select_entry(entries, SELF_CHECK_BASELINE)
        cand = _select_entry(entries, SELF_CHECK_CANDIDATE)
    except SystemExit as e:
        print(f"perf_doctor: self-check FAILED — {e}", file=sys.stderr)
        return 1
    try:
        d1 = diff_ledger_entries(base, cand)
        d2 = diff_ledger_entries(base, cand)
    except DiffRefused as e:
        print(f"perf_doctor: self-check FAILED — refused: {e}", file=sys.stderr)
        return 1
    b1, b2 = canonical(d1), canonical(d2)
    if b1 != b2:
        print("perf_doctor: self-check FAILED — double run was not "
              "byte-identical (non-deterministic diagnosis)", file=sys.stderr)
        return 1
    errs = validate_diagnosis(d1)
    if errs:
        print("perf_doctor: self-check FAILED — schema violations:\n  "
              + "\n  ".join(errs), file=sys.stderr)
        return 1
    if not d1.get("attributions"):
        print("perf_doctor: self-check FAILED — r04 -> r05 produced an "
              "empty diagnosis (fields moved between those rounds; the "
              "attribution engine is silently broken)", file=sys.stderr)
        return 1
    print(f"perf_doctor: self-check ok — {SELF_CHECK_BASELINE} -> "
          f"{SELF_CHECK_CANDIDATE}: {len(d1['attributions'])} attribution(s), "
          f"deterministic ({len(b1)} canonical bytes), schema-valid")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="perf_doctor",
        description="structural run-diff: manifest/census/trace diffing "
                    "with automated regression attribution")
    ap.add_argument("manifests", nargs="*",
                    help="two manifest files / run dirs (positional form)")
    ap.add_argument("--baseline", help="baseline manifest file or run dir")
    ap.add_argument("--candidate", help="candidate manifest file or run dir")
    ap.add_argument("--ledger", help="perf ledger file for --entry-* mode "
                                     "(default: the committed BENCH_LEDGER.jsonl)")
    ap.add_argument("--entry-baseline", help="ledger entry: source/round/id/index")
    ap.add_argument("--entry-candidate", help="ledger entry: source/round/id/index")
    ap.add_argument("--self-check", action="store_true",
                    help="CI gate: deterministic schema-valid diagnosis of the "
                         "committed r04 -> r05 ledger hop")
    ap.add_argument("--json", action="store_true",
                    help="canonical JSON diagnosis on stdout")
    ap.add_argument("--top", type=int, default=3,
                    help="attribution lines to print (0 = all; default 3)")
    ns = ap.parse_args(argv)

    if ns.self_check:
        return self_check()

    try:
        if ns.entry_baseline or ns.entry_candidate:
            if not (ns.entry_baseline and ns.entry_candidate):
                ap.error("--entry-baseline and --entry-candidate go together")
            from tools.perf_ledger import load, ledger_path

            entries = load(ns.ledger or ledger_path())
            base = _select_entry(entries, ns.entry_baseline)
            cand = _select_entry(entries, ns.entry_candidate)
            t0 = time.perf_counter()
            diag = diff_ledger_entries(base, cand)
        else:
            paths = list(ns.manifests)
            if ns.baseline:
                paths.insert(0, ns.baseline)
            if ns.candidate:
                paths.append(ns.candidate)
            if len(paths) != 2:
                ap.error("need exactly two runs: two positional paths, or "
                         "--baseline + --candidate, or --entry-* (ledger mode)")
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
