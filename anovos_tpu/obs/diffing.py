"""Perf doctor: structural run-diffing with automated regression attribution.

Every observability plane records WHAT happened — per-node devprof splits,
the compile census, cache hit sets, the env fingerprint, trace spans — and
nothing of it explains a DELTA between two runs.  This module is the diff
engine: it takes the manifests of two runs and emits one machine-readable
**diagnosis** — a ranked attribution list naming which knob / program set /
cache input / node phase actually moved.

Consumers:

* ``tools/perf_doctor.py`` — the CLI (``--baseline``/``--candidate`` run
  dirs or manifest files);
* ``obs.flight.build_snapshot`` — the live ``/statusz`` document carries
  :func:`live_node_summary` (this run's nodes vs the last completed run
  at the same output path: "what is slow *right now* vs last clean run");
* the HTML report's "Run Diff" tab (``data_report.report_generation``).

Diagnosis JSON schema (version 1)
---------------------------------

The schema below is the contract ``validate_diagnosis`` enforces (see
also the event-catalogue cross-reference in
``anovos_tpu/cache/journal.py``)::

    {
      "diagnosis_version": 1,
      "kind": "manifest",
      "backend_class": "cpu" | "accel" | "unknown",
      "baseline":  {"label", "config_hash"?, "backend"?, "wall_s"?,
                    "generated_unix"?},
      "candidate": {same shape},
      "wall_delta_s": float | null,          # scheduler wall movement
      "executor_change": [base, cand] | null,
      "nodes": {name: {                      # union of both node sets
          "status": "common" | "added" | "removed",
          "wall_s": [base|null, cand|null], "wall_delta_s": float|null,
          "phases": {device_time_s,dispatch_s,transfer_s,host_s: delta}|null,
          "dominant_phase": str|null,        # largest |phase delta|
          "queue_wait_delta_s": float|null,  # reported, NEVER scored —
                                             # queue wait is executor
                                             # scheduling, not node cost
          "cached": [bool|null, bool|null],
          "degraded": [bool, bool]}} | null,
      "programs": {                          # compile-census set diff
          "baseline_distinct": int, "candidate_distinct": int,
          "new": [names], "retired": [names],
          "count_changed": {name: [base_count, cand_count]},
          "compile_wall_delta_s": float,
          "nodes_touched": [node names]} | null,
      "cache": {"re_executed": [names],      # cached in base, ran in cand
                "newly_cached": [names],
                "moved_inputs": [str]} | null,   # which fingerprint input
                                                 # moved: config slice /
                                                 # env knob / code /
                                                 # dataset signature
      "env": {"changed_knobs": {knob: [base|null, cand|null]},
              "code_version": [base, cand] | null,
              "dataset_changed": bool | null} | null,
      "attributions": [{                     # ranked, rank 1..N
          "rank": int, "kind": str, "subject": str,
          "severity": "structural" | "timing" | "info",
          "score": float,                    # ranking key within severity
          "delta_s": float | null,
          "detail": str}],
    }

Attribution ``kind`` values: ``degraded`` / ``node_added`` /
``node_removed`` (structural), ``programs`` / ``phase`` / ``cache`` /
``node`` (timing), ``env`` / ``executor`` (info).  Ranking is
``(severity, -score, kind, subject)`` with structural first — a newly
degraded section outranks any timing movement, and env-knob changes are
listed but never outrank measured seconds.

Determinism contract: the diagnosis is a pure function of its two inputs
— no timestamps, no environment reads — and :func:`canonical` dumps it
with sorted keys and fixed separators, so diffing the same pair twice is
byte-identical.

Cross-backend-class pairs are REFUSED loudly (:class:`DiffRefused`): a
CPU run diffed against an accelerator run is a different machine, not a
regression.
"""

from __future__ import annotations

import json
from typing import Dict, Iterable, List, Optional, Tuple

__all__ = [
    "DIAGNOSIS_VERSION",
    "DiffRefused",
    "backend_class",
    "canonical",
    "diff_manifests",
    "find_manifest",
    "live_node_summary",
    "render_text",
    "validate_diagnosis",
]

DIAGNOSIS_VERSION = 1

# phase keys of one devprof node entry, in attribution order
PHASE_KEYS = ("device_time_s", "dispatch_s", "transfer_s", "host_s")

# seconds below which a phase/node movement is measurement noise, not a
# diagnosis line (the nodes map still records the raw delta)
_MIN_S = 0.001

_SEVERITY_RANK = {"structural": 0, "timing": 1, "info": 2}


class DiffRefused(ValueError):
    """Raised when two runs are not comparable (cross-backend-class)."""


def backend_class(backend) -> str:
    """'cpu' | 'accel' | 'unknown'."""
    b = str(backend or "").lower()
    if not b or b == "none":
        return "unknown"
    if b.startswith("cpu"):
        return "cpu"
    return "accel"


def canonical(diagnosis: dict) -> str:
    """Deterministic serialization (sorted keys, fixed separators)."""
    return json.dumps(diagnosis, sort_keys=True, separators=(",", ":"))


def _r(x, nd: int = 6):
    return None if x is None else round(float(x), nd)


def _refuse_cross_class(base_cls: str, cand_cls: str) -> str:
    if base_cls != "unknown" and cand_cls != "unknown" and base_cls != cand_cls:
        raise DiffRefused(
            f"refusing to diff across backend classes: baseline is "
            f"{base_cls!r}, candidate is {cand_cls!r} — a different machine "
            "is not a regression (run the doctor on same-class pairs)")
    return cand_cls if cand_cls != "unknown" else base_cls


def _truncate(names: Iterable[str], n: int = 3) -> str:
    names = list(names)
    head = ", ".join(names[:n])
    return head + (f", +{len(names) - n} more" if len(names) > n else "")


def _rank(attributions: List[dict]) -> List[dict]:
    """Sort by (severity, -score, kind, subject) and stamp 1-based ranks."""
    out = sorted(
        attributions,
        key=lambda a: (_SEVERITY_RANK.get(a["severity"], 3), -a["score"],
                       a["kind"], a["subject"]))
    for i, a in enumerate(out):
        a["rank"] = i + 1
    return out


# -- manifest diff --------------------------------------------------------

def _man_meta(man: dict, label: str) -> dict:
    sched = man.get("scheduler") or {}
    return {
        "label": label,
        "config_hash": man.get("config_hash"),
        "backend": man.get("backend"),
        "wall_s": _r(sched.get("wall_s"), 4),
        "generated_unix": man.get("generated_unix"),
    }


def _node_wall(name: str, devprof: dict, sched_nodes: dict) -> Optional[float]:
    d = devprof.get(name)
    if isinstance(d, dict) and isinstance(d.get("wall_s"), (int, float)):
        return float(d["wall_s"])
    nd = sched_nodes.get(name) or {}
    return float(nd["dur_s"]) if isinstance(nd.get("dur_s"), (int, float)) else None


def _degraded_nodes(man: dict) -> Dict[str, str]:
    """{node: reason} — scheduler flags unioned with the resilience
    section's degraded_sections reasons."""
    out: Dict[str, str] = {}
    res = man.get("resilience") or {}
    sections = res.get("degraded_sections") or {}
    if isinstance(sections, dict):
        out.update({str(k): str(v) for k, v in sections.items()})
    sched = (man.get("scheduler") or {})
    for name in (sched.get("resilience") or {}).get("degraded", []) or []:
        out.setdefault(str(name), "degraded (retries exhausted)")
    for name, nd in (sched.get("nodes") or {}).items():
        if isinstance(nd, dict) and nd.get("degraded"):
            out.setdefault(str(name), "degraded (retries exhausted)")
    return out


def diff_manifests(baseline: dict, candidate: dict,
                   baseline_label: str = "baseline",
                   candidate_label: str = "candidate") -> dict:
    """Structural diff of two ``run_manifest.json`` documents.

    Raises :class:`DiffRefused` on cross-backend-class pairs.  Output
    follows the module-docstring schema (``kind="manifest"``)."""
    cls = _refuse_cross_class(backend_class(baseline.get("backend")),
                              backend_class(candidate.get("backend")))
    b_sched = baseline.get("scheduler") or {}
    c_sched = candidate.get("scheduler") or {}
    b_nodes = b_sched.get("nodes") or {}
    c_nodes = c_sched.get("nodes") or {}
    b_dev = baseline.get("devprof") or {}
    c_dev = candidate.get("devprof") or {}
    attributions: List[dict] = []

    # --- per-node diff + phase decomposition ---------------------------
    nodes_out: Dict[str, dict] = {}
    phase_totals = {k: 0.0 for k in PHASE_KEYS}
    phase_movers: Dict[str, List[Tuple[float, str]]] = {k: [] for k in PHASE_KEYS}  # (signed delta, node)
    for name in sorted(set(b_nodes) | set(c_nodes) | set(b_dev) | set(c_dev)):
        in_b = name in b_nodes or name in b_dev
        in_c = name in c_nodes or name in c_dev
        bw = _node_wall(name, b_dev, b_nodes) if in_b else None
        cw = _node_wall(name, c_dev, c_nodes) if in_c else None
        status = "common" if (in_b and in_c) else ("added" if in_c else "removed")
        phases = None
        dominant = None
        if status == "common":
            bd, cd = b_dev.get(name), c_dev.get(name)
            if isinstance(bd, dict) and isinstance(cd, dict):
                phases = {}
                for k in PHASE_KEYS:
                    d = float(cd.get(k) or 0.0) - float(bd.get(k) or 0.0)
                    phases[k] = _r(d)
                    phase_totals[k] += d
                    if abs(d) >= _MIN_S:
                        phase_movers[k].append((d, name))
                if any(abs(v) > 0 for v in phases.values()):
                    dominant = max(PHASE_KEYS, key=lambda k: (abs(phases[k]), k))
        bq = (b_nodes.get(name) or {}).get("queue_wait_s")
        cq = (c_nodes.get(name) or {}).get("queue_wait_s")
        nodes_out[name] = {
            "status": status,
            "wall_s": [_r(bw), _r(cw)],
            "wall_delta_s": _r(cw - bw) if (bw is not None and cw is not None) else None,
            "phases": phases,
            "dominant_phase": dominant,
            # queue wait is EXECUTOR scheduling (a concurrent run waits
            # where a sequential one cannot) — recorded for the reader,
            # never booked as a regression attribution
            "queue_wait_delta_s": (_r(cq - bq)
                                   if isinstance(bq, (int, float))
                                   and isinstance(cq, (int, float)) else None),
            "cached": [(b_nodes.get(name) or {}).get("cached"),
                       (c_nodes.get(name) or {}).get("cached")],
            "degraded": [bool((b_nodes.get(name) or {}).get("degraded")),
                         bool((c_nodes.get(name) or {}).get("degraded"))],
        }
        if status != "common":
            wall = cw if status == "added" else bw
            attributions.append({
                "kind": f"node_{status}", "subject": name,
                "severity": "structural", "score": _r(abs(wall or 0.0)) or 0.0,
                "delta_s": _r(cw) if status == "added" else _r(-(bw or 0.0)),
                "detail": (f"node {name!r} only in the "
                           f"{'candidate' if status == 'added' else 'baseline'} "
                           f"run (wall {wall if wall is not None else '?'}s) — "
                           "the registration set changed"),
            })

    # --- newly degraded sections (structural, outrank everything) ------
    b_deg, c_deg = _degraded_nodes(baseline), _degraded_nodes(candidate)
    for name in sorted(set(c_deg) - set(b_deg)):
        base_wall = _node_wall(name, b_dev, b_nodes)
        attributions.append({
            "kind": "degraded", "subject": name, "severity": "structural",
            "score": _r(base_wall or 0.0) or 0.0, "delta_s": None,
            "detail": (f"node {name!r} DEGRADED in the candidate run "
                       f"({c_deg[name]}) but clean in the baseline — its "
                       "statistics are missing, not slower"),
        })

    # --- phase aggregate attributions ----------------------------------
    for k in PHASE_KEYS:
        total = phase_totals[k]
        if abs(total) < _MIN_S:
            continue
        movers = sorted(phase_movers[k], key=lambda t: (-abs(t[0]), t[1]))[:3]
        mover_txt = ", ".join(f"{n} ({d:+.3f}s)" for d, n in movers) \
            or "no single node dominates"
        attributions.append({
            "kind": "phase", "subject": k, "severity": "timing",
            "score": _r(abs(total)) or 0.0, "delta_s": _r(total),
            "detail": (f"{k} moved {total:+.3f}s across the common node set; "
                       f"top movers: {mover_txt}"),
        })

    # --- compile-census program-set diff -------------------------------
    programs = None
    b_cen, c_cen = baseline.get("compile_census"), candidate.get("compile_census")
    if isinstance(b_cen, dict) and isinstance(c_cen, dict):
        b_prog = {p.get("program"): p for p in (b_cen.get("programs") or [])
                  if isinstance(p, dict)}
        c_prog = {p.get("program"): p for p in (c_cen.get("programs") or [])
                  if isinstance(p, dict)}
        new = sorted(set(c_prog) - set(b_prog))
        retired = sorted(set(b_prog) - set(c_prog))
        count_changed = {
            n: [int(b_prog[n].get("count") or 0), int(c_prog[n].get("count") or 0)]
            for n in sorted(set(b_prog) & set(c_prog))
            if int(b_prog[n].get("count") or 0) != int(c_prog[n].get("count") or 0)
        }
        wall_delta = (float(c_cen.get("compile_seconds_total") or 0.0)
                      - float(b_cen.get("compile_seconds_total") or 0.0))
        touched = sorted({nd for n in new + retired
                          for nd in (c_prog.get(n) or b_prog.get(n) or {}).get("nodes", [])})
        programs = {
            "baseline_distinct": int(b_cen.get("distinct_programs") or 0),
            "candidate_distinct": int(c_cen.get("distinct_programs") or 0),
            "new": new, "retired": retired, "count_changed": count_changed,
            "compile_wall_delta_s": _r(wall_delta),
            "nodes_touched": touched,
        }
        n_changes = len(new) + len(retired) + len(count_changed)
        if n_changes:
            attributions.append({
                "kind": "programs", "subject": "program_set",
                "severity": "timing",
                # compile wall is the measurable cost; a warm/warm pair
                # with equal walls still surfaces on the count fallback
                "score": _r(max(abs(wall_delta), 0.01 * n_changes)) or 0.0,
                "delta_s": _r(wall_delta),
                "detail": (f"program set moved: +{len(new)} new, "
                           f"-{len(retired)} retired, {len(count_changed)} "
                           f"shape-count changed (distinct "
                           f"{programs['baseline_distinct']} -> "
                           f"{programs['candidate_distinct']}, compile wall "
                           f"{wall_delta:+.3f}s)"
                           + (f"; new: {_truncate(new)}" if new else "")
                           + (f"; retired: {_truncate(retired)}" if retired else "")
                           + (f"; nodes touched: {_truncate(touched)}"
                              if touched else "")),
            })

    # --- env / fingerprint-input diff ----------------------------------
    env = None
    b_env, c_env = baseline.get("env"), candidate.get("env")
    if isinstance(b_env, dict) or isinstance(c_env, dict):
        b_env, c_env = b_env or {}, c_env or {}
        bk, ck = b_env.get("knobs") or {}, c_env.get("knobs") or {}
        changed = {k: [bk.get(k), ck.get(k)]
                   for k in sorted(set(bk) | set(ck)) if bk.get(k) != ck.get(k)}
        code = None
        if (b_env.get("code_version") and c_env.get("code_version")
                and b_env["code_version"] != c_env["code_version"]):
            code = [b_env["code_version"], c_env["code_version"]]
        ds = None
        if b_env.get("dataset_fingerprint") and c_env.get("dataset_fingerprint"):
            ds = b_env["dataset_fingerprint"] != c_env["dataset_fingerprint"]
        env = {"changed_knobs": changed, "code_version": code,
               "dataset_changed": ds}
        for knob, (bv, cv) in changed.items():
            b_txt = "unset" if bv is None else repr(bv)
            c_txt = "unset" if cv is None else repr(cv)
            attributions.append({
                "kind": "env", "subject": knob, "severity": "info",
                "score": 0.0, "delta_s": None,
                "detail": f"env knob {knob} moved: {b_txt} -> {c_txt}",
            })

    # --- cache hit-set diff --------------------------------------------
    cache = None
    b_cache, c_cache = baseline.get("cache"), candidate.get("cache")
    any_cached = any(bool((nd or {}).get("cached"))
                     for nd in list(b_nodes.values()) + list(c_nodes.values()))
    if b_cache or c_cache or any_cached:
        re_exec = sorted(
            n for n in set(b_nodes) & set(c_nodes)
            if (b_nodes[n] or {}).get("cached") and not (c_nodes[n] or {}).get("cached"))
        newly = sorted(
            n for n in set(b_nodes) & set(c_nodes)
            if not (b_nodes[n] or {}).get("cached") and (c_nodes[n] or {}).get("cached"))
        moved: List[str] = []
        if baseline.get("config_hash") != candidate.get("config_hash"):
            moved.append("config slice (config_hash moved)")
        if env:
            moved.extend(f"env knob {k}" for k in (env["changed_knobs"] or {}))
            if env.get("code_version"):
                moved.append("code ({} -> {})".format(*env["code_version"]))
            if env.get("dataset_changed"):
                moved.append("dataset signature")
        if re_exec and not moved:
            moved.append("upstream node output or cache-store state")
        cache = {"re_executed": re_exec, "newly_cached": newly,
                 "moved_inputs": moved}
        if re_exec:
            cost = sum(_node_wall(n, c_dev, c_nodes) or 0.0 for n in re_exec)
            attributions.append({
                "kind": "cache", "subject": "re_executed",
                "severity": "timing", "score": _r(cost) or 0.0,
                "delta_s": _r(cost),
                "detail": (f"{len(re_exec)} node cone(s) re-executed that the "
                           f"baseline restored from cache ({_truncate(re_exec)}; "
                           f"{cost:.3f}s of candidate wall); moved fingerprint "
                           f"input(s): {', '.join(moved)}"),
            })

    # --- executor-mode change (informational) --------------------------
    b_mode = (baseline.get("executor") or {}).get("mode") or b_sched.get("mode")
    c_mode = (candidate.get("executor") or {}).get("mode") or c_sched.get("mode")
    executor_change = None
    if b_mode != c_mode:
        executor_change = [b_mode, c_mode]
        attributions.append({
            "kind": "executor", "subject": "mode", "severity": "info",
            "score": 0.0, "delta_s": None,
            "detail": (f"executor mode moved {b_mode!r} -> {c_mode!r}: "
                       "queue-wait movement is scheduling, not node cost, "
                       "and is deliberately not booked as a regression"),
        })

    bw, cw = _man_meta(baseline, baseline_label), _man_meta(candidate, candidate_label)
    wall_delta = (None if bw["wall_s"] is None or cw["wall_s"] is None
                  else _r(cw["wall_s"] - bw["wall_s"], 4))
    return {
        "diagnosis_version": DIAGNOSIS_VERSION,
        "kind": "manifest",
        "backend_class": cls,
        "baseline": bw,
        "candidate": cw,
        "wall_delta_s": wall_delta,
        "executor_change": executor_change,
        "nodes": nodes_out or None,
        "programs": programs,
        "cache": cache,
        "env": env,
        "attributions": _rank(attributions),
    }


# -- live doctor summary (flight recorder / /statusz) ---------------------

def live_node_summary(baseline_manifest: Optional[dict],
                      finished: Dict[str, dict],
                      active: Optional[Dict[str, dict]] = None) -> Optional[dict]:
    """Compare THIS run's per-node attribution against the last completed
    run's manifest at the same output path.

    ``finished`` is ``obs.devprof.results()``; ``active`` the in-flight
    frame snapshots.  Returns a compact summary (``None`` when no
    baseline devprof exists) that ``obs.flight.build_snapshot`` embeds
    under ``doctor`` — so ``/statusz`` answers "what is slow right now
    vs the last clean run" without a postmortem.  Never raises on odd
    shapes; the caller guards the rest."""
    base_dev = (baseline_manifest or {}).get("devprof") or {}
    if not base_dev:
        return None
    nodes: Dict[str, dict] = {}
    slow: List[str] = []
    for name, cur in sorted((finished or {}).items()):
        if not isinstance(cur, dict):
            continue
        bw = (base_dev.get(name) or {}).get("wall_s")
        cw = cur.get("wall_s")
        delta = (_r(cw - bw)
                 if isinstance(bw, (int, float)) and isinstance(cw, (int, float))
                 else None)
        dominant = None
        vals = {k: float(cur.get(k) or 0.0) for k in PHASE_KEYS}
        if any(v > 0 for v in vals.values()):
            dominant = max(PHASE_KEYS, key=lambda k: (vals[k], k))
        slower = (delta is not None
                  and delta > max(0.05, 0.25 * float(bw)))
        nodes[name] = {"wall_s": _r(cw), "baseline_wall_s": _r(bw),
                       "wall_delta_s": delta, "dominant_phase": dominant,
                       "in_flight": False, "slower": bool(slower)}
        if slower:
            slow.append(name)
    for name, fr in sorted((active or {}).items()):
        if not isinstance(fr, dict) or name in nodes:
            continue
        bw = (base_dev.get(name) or {}).get("wall_s")
        el = fr.get("elapsed_s")
        overdue = (isinstance(bw, (int, float)) and isinstance(el, (int, float))
                   and el > max(0.05, 2.0 * float(bw)))
        nodes[name] = {"wall_s": _r(el), "baseline_wall_s": _r(bw),
                       "wall_delta_s": None, "dominant_phase": None,
                       "in_flight": True, "slower": bool(overdue)}
        if overdue:
            slow.append(name)
    if not nodes:
        return None
    return {
        "baseline_generated_unix": (baseline_manifest or {}).get("generated_unix"),
        "baseline_config_hash": (baseline_manifest or {}).get("config_hash"),
        "nodes": nodes,
        "slow": sorted(slow),
    }


# -- rendering / validation ----------------------------------------------

def render_text(diagnosis: dict, top: int = 3) -> List[str]:
    """Human-facing attribution lines, most severe first."""
    out = []
    for a in (diagnosis.get("attributions") or [])[: top or None]:
        out.append(f"#{a['rank']} [{a['kind']}:{a['subject']}] {a['detail']}")
    return out


def find_manifest(path: str) -> str:
    """Resolve a manifest file from a path the CLI was handed: the file
    itself, a run dir containing ``obs/run_manifest.json``, or the obs
    dir containing ``run_manifest.json``."""
    import os

    if os.path.isfile(path):
        return path
    for cand in (os.path.join(path, "obs", "run_manifest.json"),
                 os.path.join(path, "run_manifest.json")):
        if os.path.isfile(cand):
            return cand
    raise FileNotFoundError(
        f"no run_manifest.json under {path!r} (expected the file, a run dir "
        "with obs/run_manifest.json, or the obs dir itself)")


_TOP_KEYS = ("diagnosis_version", "kind", "backend_class", "baseline",
             "candidate", "wall_delta_s", "executor_change", "nodes",
             "programs", "cache", "env", "attributions")
_ATTR_KEYS = ("rank", "kind", "subject", "severity", "score", "delta_s", "detail")


def validate_diagnosis(diagnosis: dict) -> List[str]:
    """Schema check (module-docstring contract); returns error strings,
    empty when valid."""
    errs: List[str] = []
    if not isinstance(diagnosis, dict):
        return ["diagnosis is not a dict"]
    for k in _TOP_KEYS:
        if k not in diagnosis:
            errs.append(f"missing top-level key {k!r}")
    if diagnosis.get("diagnosis_version") != DIAGNOSIS_VERSION:
        errs.append(f"diagnosis_version != {DIAGNOSIS_VERSION}")
    if diagnosis.get("kind") != "manifest":
        errs.append(f"kind must be manifest, got {diagnosis.get('kind')!r}")
    if diagnosis.get("backend_class") not in ("cpu", "accel", "unknown"):
        errs.append(f"bad backend_class {diagnosis.get('backend_class')!r}")
    for side in ("baseline", "candidate"):
        s = diagnosis.get(side)
        if not isinstance(s, dict) or "label" not in s:
            errs.append(f"{side} must be a dict with a label")
    attrs = diagnosis.get("attributions")
    if not isinstance(attrs, list):
        errs.append("attributions must be a list")
        attrs = []
    for i, a in enumerate(attrs):
        if not isinstance(a, dict):
            errs.append(f"attribution {i} is not a dict")
            continue
        for k in _ATTR_KEYS:
            if k not in a:
                errs.append(f"attribution {i} missing {k!r}")
        if a.get("rank") != i + 1:
            errs.append(f"attribution {i} rank {a.get('rank')} != {i + 1}")
        if a.get("severity") not in _SEVERITY_RANK:
            errs.append(f"attribution {i} bad severity {a.get('severity')!r}")
        if not isinstance(a.get("score"), (int, float)) or a.get("score") < 0:
            errs.append(f"attribution {i} score must be a non-negative number")
        if not isinstance(a.get("detail"), str) or not a.get("detail"):
            errs.append(f"attribution {i} detail must be a non-empty string")
    for i in range(1, len(attrs)):
        a, b = attrs[i - 1], attrs[i]
        ka = (_SEVERITY_RANK.get(a.get("severity"), 3), -float(a.get("score") or 0))
        kb = (_SEVERITY_RANK.get(b.get("severity"), 3), -float(b.get("score") or 0))
        if ka > kb:
            errs.append(f"attributions {i - 1}/{i} out of severity/score order")
    return errs
