"""Perf ledger: an append-only trajectory of bench results + a regression gate.

Five ``BENCH_r*.json`` round snapshots exist in the repo root and the bench
trajectory surfaced to tooling was literally ``[]`` — every perf regression
so far has been caught by a human reading JSON diffs.  This tool folds the
committed round files plus every new ``bench.py`` run into ONE append-only
trajectory file (``BENCH_LEDGER.jsonl``, one JSON entry per line, dedup'd by
content id) and answers the only question that matters mechanically:

    is the latest run WORSE than its own recent history, beyond noise?

The gate (``--check``) compares, per tracked field, the candidate against
the **median of the last 3 prior entries** that carry the field on the same
backend class (cpu-fallback numbers are never judged against accelerator
numbers, and vice versa), with a per-field relative noise band: wall-clock
fields get wide bands (containers differ), compile counts get tight ones
(they are deterministic functions of the code).  Improvements never fail;
missing baselines are skipped, not failed — the gate only ever compares
like with like.

Wire-up:

* ``bench.py`` calls :func:`record_and_check` after assembling its JSON
  line: the run is appended to the ledger and the verdict rides the bench
  record as ``ledger_ok`` / ``ledger_regressions`` — a hard field of every
  round snapshot from now on.
* tier-1 runs the gate advisorily over the committed rounds
  (``tests/test_perf_ledger.py``): the mechanism must work and the REAL
  trajectory must pass; a seeded synthetic regression must be flagged.
* The HTML report renders a trend-sparkline tab from the ledger when
  ``ANOVOS_PERF_LEDGER`` points at one (report_generation.py).

CLI::

    python -m tools.perf_ledger                 # ingest rounds + print trend
    python -m tools.perf_ledger --check         # + regression gate (exit 1)
    python -m tools.perf_ledger --check --candidate run.json
    python -m tools.perf_ledger --json          # machine-readable
"""

from __future__ import annotations

import argparse
import glob
import hashlib
import json
import os
import sys
import time
from typing import Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LEDGER_ENV = "ANOVOS_PERF_LEDGER"
# the tool's own file — PERF_LEDGER.jsonl at the root is the PR driver's record
DEFAULT_LEDGER = os.path.join(REPO, "BENCH_LEDGER.jsonl")
LEDGER_VERSION = 1

# field -> (direction, relative noise band).  Direction is which way is
# BETTER; a candidate is a regression when it is worse than the baseline
# median by more than the band.  Walls get wide bands (different
# containers/hosts between rounds); compile counts are deterministic
# functions of the code and get tight ones.
TRACKED_FIELDS: Dict[str, Tuple[str, float]] = {
    "value": ("higher", 0.35),                        # PSI rows/s headline
    "psi_steady_rows_per_sec": ("higher", 0.35),
    "psi_steady_gbps": ("higher", 0.35),
    "e2e_cold_s": ("lower", 0.50),
    "e2e_warm_s": ("lower", 0.40),
    "e2e_warm_rows_per_sec_per_chip": ("higher", 0.40),
    "e2e_cold_compiles": ("lower", 0.15),
    "e2e_distinct_programs": ("lower", 0.15),
    "e2e_cold_compile_wall_s": ("lower", 0.50),
    "e2e_cached_wall_s": ("lower", 0.60),
    "e2e_incremental_wall_s": ("lower", 0.60),
    "e2e_chaos_overhead_s": ("lower", 0.80),
    "e2e_device_time_s": ("lower", 0.60),
    "e2e_dispatch_s": ("lower", 0.60),
    # multi-device concurrent executor (the MULTICHIP dryrun's executor
    # pass): measured node overlap on the mesh must not collapse back to
    # sequential-in-disguise, and the concurrent wall must hold its line
    "e2e_multidev_overlap": ("higher", 0.40),
    "e2e_multidev_wall_s": ("lower", 0.60),
    "e2e_multidev_seq_wall_s": ("lower", 0.60),
    # online serving (round 11): sustained QPS + request-latency tail from
    # the concurrent-client smoke load, and the bounded cold start the
    # persistent XLA cache buys.  Generous ±60% bands: the shared CI box
    # timeshares the 4 client threads with whatever else runs there.
    "e2e_serve_qps": ("higher", 0.60),
    "e2e_serve_p50_ms": ("lower", 0.60),
    "e2e_serve_p99_ms": ("lower", 0.60),
    "e2e_serve_cold_start_s": ("lower", 0.60),
    # out-of-core streaming (round 12): the prefetched whole-table pass
    # must hold its throughput, its window-bounded RSS ceiling, and its
    # decode/compute overlap.  ±60% walls (shared box), ±50% on the RSS
    # ceiling (allocator noise), ±40% on overlap share.
    "e2e_oocore_wall_s": ("lower", 0.60),
    "e2e_oocore_rows_per_s": ("higher", 0.60),
    "e2e_oocore_peak_rss_mb": ("lower", 0.50),
    "e2e_stream_overlap_pct": ("higher", 0.40),
    # continuum feed (round 13): per-day incremental fold wall and its
    # ratio to a from-scratch batch run (tiny walls on a shared box →
    # wide ±60% bands); the alert count is a correctness level — dropping
    # to zero from the expected shift-day alerts is a regression, so it
    # rides "higher" with the same generous band.
    "e2e_continuum_fold_s": ("lower", 0.60),
    "e2e_continuum_vs_batch_ratio": ("lower", 0.60),
    "e2e_continuum_alerts": ("higher", 0.60),
    # telemetry plane (round 14): the A/B overhead percentage hovers near
    # zero and is noise-dominated on a shared box, so its band is very
    # wide (the <1% acceptance bar is enforced by bench itself, loudly);
    # the scrape tail rides the usual shared-box latency band.
    "e2e_telemetry_overhead_pct": ("lower", 3.00),
    "e2e_scrape_p99_ms": ("lower", 0.60),
    # static analysis (graftcheck engine v2): the warm incremental re-scan
    # wall — the cost every tier-1 run pays once the cache is populated.
    # A very wide band (interpreter start + AST parse on a timeshared
    # box), but a blown cache shows up as a multiple, not a percentage.
    "e2e_graftcheck_incr_s": ("lower", 1.00),
}
BASELINE_WINDOW = 3


def ledger_path() -> str:
    return os.environ.get(LEDGER_ENV) or DEFAULT_LEDGER


def _backend_class(backend: Optional[str]) -> str:
    """'cpu' | 'accel' | 'unknown' — trajectories only compare within a
    class (a CPU-fallback round vs a TPU round is not a regression, it is
    a different machine)."""
    b = str(backend or "").lower()
    if not b or b == "none":
        return "unknown"
    if b.startswith("cpu"):
        return "cpu"
    return "accel"


# per-node phase keys lifted from bench's e2e_node_summary into ledger
# entries — the doctor's material for naming WHICH node regressed and its
# dominant phase when a gate failure attaches a diagnosis
_NODE_SUMMARY_KEYS = ("wall_s", "device_time_s", "dispatch_s",
                      "transfer_s", "host_s")


def _node_summary(parsed: dict) -> Optional[dict]:
    raw = parsed.get("e2e_node_summary")
    if not isinstance(raw, dict):
        return None
    out = {}
    for name, rec in sorted(raw.items()):
        if not isinstance(rec, dict):
            continue
        keep = {k: round(float(rec[k]), 6) for k in _NODE_SUMMARY_KEYS
                if isinstance(rec.get(k), (int, float))
                and not isinstance(rec.get(k), bool)}
        if keep:
            out[str(name)] = keep
    return out or None


def _entry_from_bench(parsed: dict, source: str, round_n: Optional[int]) -> dict:
    fields = {
        k: parsed[k] for k in TRACKED_FIELDS
        if isinstance(parsed.get(k), (int, float))
        and not isinstance(parsed.get(k), bool)
    }
    backend = parsed.get("backend")
    entry = {
        "ledger_version": LEDGER_VERSION,
        "source": source,
        "round": round_n,
        "backend": backend,
        "backend_class": _backend_class(
            parsed.get("e2e_backend") or backend),
        "attested": bool(parsed.get("attested", False)),
        "fields": fields,
    }
    nodes = _node_summary(parsed)
    if nodes:
        entry["nodes"] = nodes
    # content id stays a function of (source, round, backend, fields) ONLY:
    # the committed entries' ids must not move when the node summary or a
    # diagnosis is attached alongside
    entry["id"] = hashlib.sha256(
        json.dumps({k: entry[k] for k in ("source", "round", "backend", "fields")},
                   sort_keys=True, separators=(",", ":")).encode()
    ).hexdigest()[:16]
    return entry


def parse_round_file(path: str) -> Optional[dict]:
    """One committed ``BENCH_rNN.json`` driver snapshot → ledger entry.
    Rounds whose run died (``parsed: null``) carry no numbers and are
    skipped."""
    try:
        with open(path) as f:
            blob = json.load(f)
    except (OSError, ValueError):
        return None
    parsed = blob.get("parsed")
    if not isinstance(parsed, dict):
        return None
    return _entry_from_bench(parsed, os.path.basename(path), blob.get("n"))


def load(path: Optional[str] = None) -> List[dict]:
    """All parseable ledger entries, file order (= append order)."""
    path = path or ledger_path()
    out: List[dict] = []
    if not os.path.exists(path):
        return out
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except ValueError:
                continue  # a torn tail from a killed append
    return out


def append_entries(entries: List[dict], path: Optional[str] = None) -> int:
    """Append entries not already present (by content id); returns the
    number actually appended.  Append-only by design — history is the
    entire point of the file."""
    path = path or ledger_path()
    have = {e.get("id") for e in load(path)}
    fresh = [e for e in entries if e.get("id") not in have]
    if not fresh:
        return 0
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "a") as f:
        for e in fresh:
            f.write(json.dumps(e, sort_keys=True, separators=(",", ":")) + "\n")
    return len(fresh)


def ingest_rounds(pattern: Optional[str] = None,
                  path: Optional[str] = None) -> int:
    """Fold every committed round snapshot into the ledger (idempotent)."""
    pattern = pattern or os.path.join(REPO, "BENCH_r*.json")
    entries = []
    for p in sorted(glob.glob(pattern)):
        e = parse_round_file(p)
        if e is not None:
            entries.append(e)
    return append_entries(entries, path)


def _median(vals: List[float]) -> float:
    s = sorted(vals)
    n = len(s)
    return s[n // 2] if n % 2 else (s[n // 2 - 1] + s[n // 2]) / 2.0


def check(entries: List[dict], candidate: dict,
          window: int = BASELINE_WINDOW) -> List[dict]:
    """Regressions of ``candidate`` against its trajectory.

    Per tracked field present in the candidate: baseline = median of the
    last ``window`` PRIOR entries (same backend class, field present,
    candidate's own id excluded).  Worse-than-baseline beyond the field's
    noise band → one regression record.  No baseline → skipped."""
    cls = candidate.get("backend_class", "unknown")
    cand_id = candidate.get("id")
    # entries the gate itself flagged are EXCLUDED from baseline history:
    # otherwise a sustained regression is flagged for ~2 runs and then
    # becomes its own baseline — the gate must keep comparing against the
    # last-known-good trajectory until a clean run re-establishes it
    prior = [e for e in entries
             if e.get("id") != cand_id and e.get("backend_class") == cls
             and not e.get("regressions")]
    out: List[dict] = []
    for field, value in sorted((candidate.get("fields") or {}).items()):
        spec = TRACKED_FIELDS.get(field)
        if spec is None:
            continue
        direction, band = spec
        history = [e["fields"][field] for e in prior
                   if isinstance(e.get("fields", {}).get(field), (int, float))]
        if not history:
            continue
        baseline = _median(history[-window:])
        if baseline == 0:
            continue
        if direction == "lower":
            bad = value > baseline * (1.0 + band)
            ratio = value / baseline
        else:
            bad = value < baseline * (1.0 - band)
            ratio = baseline / value if value else float("inf")
        if bad:
            out.append({
                "field": field,
                "value": round(float(value), 4),
                "baseline": round(float(baseline), 4),
                "band": band,
                "direction": direction,
                "worse_by": round((ratio - 1.0) * 100, 1),  # percent
                "n_baseline": len(history[-window:]),
            })
    return out


def attach_diagnosis(entries: List[dict], cand: dict,
                     regressions: List[dict]) -> List[str]:
    """Perf-doctor hookup: a gate-flagged candidate gets a ``diagnosis``
    object (anovos_tpu.obs.diffing ledger diff against the last clean
    same-class entry) attached in place, and the top-3 attribution lines
    are returned for bench to print instead of a bare field name.

    Best-effort by contract: a broken doctor must never break the gate —
    failures land as ``diagnosis_error`` on the entry, and [] returns."""
    if not regressions:
        return []
    try:
        from anovos_tpu.obs.diffing import diff_ledger_entries, render_text

        cls = cand.get("backend_class", "unknown")
        cand_fields = set(cand.get("fields") or {})
        prior = [e for e in entries
                 if e.get("id") != cand.get("id")
                 and e.get("backend_class") == cls
                 and not e.get("regressions")
                 and cand_fields & set(e.get("fields") or {})]
        if not prior:
            return []
        diag = diff_ledger_entries(prior[-1], cand,
                                   flagged=[r["field"] for r in regressions])
        cand["diagnosis"] = diag
        return render_text(diag, top=3)
    except Exception as e:
        cand["diagnosis_error"] = str(e)[-200:]
        return []


def record_and_check(bench_result: dict,
                     path: Optional[str] = None) -> dict:
    """bench.py's hook: ingest committed rounds, append this run, gate it.

    Returns the fields bench merges into its JSON line.  Never raises —
    bench's output contract survives a broken ledger.  A flagged run's
    ledger entry carries a full perf-doctor ``diagnosis`` and the return
    carries the top-3 attribution lines (``ledger_attribution``)."""
    path = path or ledger_path()
    try:
        ingest_rounds(path=path)
        entries = load(path)
        cand = _entry_from_bench(dict(bench_result), "live", None)
        cand["t_unix"] = round(time.time(), 3)
        regressions = check(entries, cand)
        cand["regressions"] = [r["field"] for r in regressions]
        attribution = attach_diagnosis(entries, cand, regressions)
        append_entries([cand], path)
        return {
            "ledger_ok": not regressions,
            "ledger_regressions": [
                f"{r['field']}: {r['value']} vs baseline {r['baseline']} "
                f"({r['worse_by']}% worse, band {int(r['band'] * 100)}%)"
                for r in regressions
            ],
            "ledger_attribution": attribution,
            "ledger_entries": len(entries) + 1,
            "ledger_path": path,
        }
    except Exception as e:
        return {"ledger_ok": False, "ledger_error": str(e)[-200:]}


_SPARK_BLOCKS = "▁▂▃▄▅▆▇█"
# explicit gap marker for an entry that does not carry the field: every
# trend string has one glyph PER LEDGER ENTRY, so sparklines stay aligned
# against run ids (silently skipping an entry shifted everything after it
# left — the HTML ledger tab was misattributing values to rounds)
GAP_MARK = "·"


def field_trends(entries: List[dict]) -> List[dict]:
    """Per-tracked-field trajectory rows (the ONE source for the CLI trend
    text and the HTML report's ledger tab): ``{field, trend (unicode
    sparkline, one glyph per ledger entry with ``·`` marking entries that
    lack the field), latest, min, max, n, gaps, better, noise_band}``,
    fields with fewer than two data points omitted."""
    rows: List[dict] = []
    for field in sorted({f for e in entries for f in (e.get("fields") or {})}):
        spec = TRACKED_FIELDS.get(field)
        if spec is None:
            continue
        pts: List[Optional[float]] = []
        for e in entries:
            v = (e.get("fields") or {}).get(field)
            pts.append(float(v) if isinstance(v, (int, float))
                       and not isinstance(v, bool) else None)
        vals = [v for v in pts if v is not None]
        if len(vals) < 2:
            continue
        lo, hi = min(vals), max(vals)
        span = (hi - lo) or 1.0
        spark = "".join(
            GAP_MARK if v is None
            else _SPARK_BLOCKS[int((v - lo) / span * (len(_SPARK_BLOCKS) - 1))]
            for v in pts)
        direction, band = spec
        rows.append({"field": field, "trend": spark, "latest": vals[-1],
                     "min": lo, "max": hi, "n": len(vals),
                     "gaps": len(pts) - len(vals),
                     "better": direction, "noise_band": f"{int(band * 100)}%"})
    return rows


def _trend_text(entries: List[dict]) -> str:
    """Per-field unicode sparkline over the trajectory."""
    return "\n".join(
        f"{r['field']:38s} {r['trend']}  latest={r['latest']:g} "
        f"(min {r['min']:g}, max {r['max']:g}, n={r['n']})"
        for r in field_trends(entries))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="append-only bench trajectory + regression gate")
    ap.add_argument("--ledger", help=f"ledger file (default ${LEDGER_ENV} "
                                     f"or {os.path.relpath(DEFAULT_LEDGER, REPO)})")
    ap.add_argument("--rounds-glob", help="committed round snapshots to ingest "
                                          "(default BENCH_r*.json in the repo root)")
    ap.add_argument("--candidate", help="bench JSON (file or '-' for stdin) to "
                                        "gate; default: the ledger's last entry")
    ap.add_argument("--check", action="store_true",
                    help="run the regression gate (exit 1 on regression)")
    ap.add_argument("--window", type=int, default=BASELINE_WINDOW,
                    help="baseline window (median of the last N prior entries)")
    ap.add_argument("--json", action="store_true", help="machine-readable output")
    ns = ap.parse_args(argv)

    path = ns.ledger or ledger_path()
    added = ingest_rounds(ns.rounds_glob, path)
    entries = load(path)
    result = {"ledger": path, "entries": len(entries), "ingested": added}

    candidate = None
    if ns.candidate:
        raw = sys.stdin.read() if ns.candidate == "-" else open(ns.candidate).read()
        parsed = json.loads(raw)
        if isinstance(parsed, dict) and "parsed" in parsed:  # a driver snapshot
            parsed = parsed.get("parsed") or {}
        candidate = _entry_from_bench(parsed, ns.candidate, None)
        # mark the entry with its own gate verdict BEFORE appending — like
        # record_and_check does — so a regressing candidate is excluded
        # from future baselines instead of normalizing the regression away
        cand_regressions = check(entries + [candidate], candidate,
                                 window=ns.window)
        candidate["regressions"] = [r["field"] for r in cand_regressions]
        # a flagged candidate carries its perf-doctor diagnosis in the
        # ledger itself (same contract as the bench hook)
        attach_diagnosis(entries, candidate, cand_regressions)
        append_entries([candidate], path)
        entries = load(path)
        result["entries"] = len(entries)
    elif entries:
        candidate = entries[-1]

    rc = 0
    if ns.check:
        if candidate is None:
            result["check"] = "no entries to gate"
            rc = 2
        else:
            regressions = check(entries, candidate, window=ns.window)
            result["candidate"] = candidate.get("source")
            result["regressions"] = regressions
            result["ok"] = not regressions
            rc = 1 if regressions else 0
            if regressions and "diagnosis" not in candidate:
                attach_diagnosis(entries, candidate, regressions)
            if candidate.get("diagnosis") is not None:
                try:
                    from anovos_tpu.obs.diffing import render_text

                    result["attribution"] = render_text(
                        candidate["diagnosis"], top=3)
                except Exception:
                    pass  # the gate verdict stands without the doctor
    if ns.json:
        print(json.dumps(result, sort_keys=True))
    else:
        print(f"perf_ledger: {len(entries)} entr(ies) at {path} "
              f"(+{added} ingested)")
        trend = _trend_text(entries)
        if trend:
            print(trend)
        if ns.check:
            if rc == 0 and candidate is not None:
                print(f"perf_ledger: OK — {candidate.get('source')} holds the "
                      f"trajectory (window={ns.window})")
            for r in result.get("regressions", []):
                print(f"perf_ledger: REGRESSION {r['field']}: {r['value']} vs "
                      f"baseline {r['baseline']} ({r['worse_by']}% worse, "
                      f"band {int(r['band'] * 100)}%)", file=sys.stderr)
            for line in result.get("attribution") or []:
                print(f"perf_ledger: diagnosis {line}", file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
