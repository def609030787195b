"""Perf ledger (tools/perf_ledger): the append-only bench trajectory and
its regression gate, wired into tier-1 advisorily:

* ingesting the COMMITTED round snapshots works and is idempotent;
* the REAL trajectory passes the gate (acceptance: improvements and
  flat fields are never regressions);
* a seeded synthetic regression IS flagged;
* backend classes never cross-compare;
* the bench hook (``record_and_check``) appends + gates without raising.
"""

import glob
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools import perf_ledger  # noqa: E402

ROUNDS = sorted(glob.glob(os.path.join(REPO, "BENCH_r*.json")))


def _fresh(tmp_path):
    return str(tmp_path / "ledger.jsonl")


def test_rounds_exist_and_parse():
    assert len(ROUNDS) >= 4
    entries = [perf_ledger.parse_round_file(p) for p in ROUNDS]
    parsed = [e for e in entries if e is not None]
    assert len(parsed) == len(ROUNDS)
    for e in parsed:
        assert e["fields"], e
        assert e["id"]


def test_ingest_idempotent(tmp_path):
    path = _fresh(tmp_path)
    n1 = perf_ledger.ingest_rounds(path=path)
    assert n1 == len(ROUNDS)
    assert perf_ledger.ingest_rounds(path=path) == 0  # dedup by content id
    assert len(perf_ledger.load(path)) == n1


def test_real_trajectory_passes_the_gate(tmp_path):
    """Acceptance: BENCH_r02–r05 hold their own trajectory — the walls
    only improved and the PSI headline is flat within noise."""
    path = _fresh(tmp_path)
    perf_ledger.ingest_rounds(path=path)
    entries = perf_ledger.load(path)
    regressions = perf_ledger.check(entries, entries[-1])
    assert regressions == [], regressions


def test_synthetic_regression_is_flagged(tmp_path):
    path = _fresh(tmp_path)
    perf_ledger.ingest_rounds(path=path)
    entries = perf_ledger.load(path)
    bad = perf_ledger._entry_from_bench(
        {"value": 1_200_000.0, "e2e_warm_s": 21.0, "e2e_backend": "cpu",
         "backend": "cpu-fallback (synthetic)"},
        "synthetic", None)
    regressions = perf_ledger.check(entries + [bad], bad)
    fields = {r["field"] for r in regressions}
    assert "e2e_warm_s" in fields      # 21.0 vs median(25.0, 8.1, 6.1)=8.1
    assert "value" in fields           # 1.2M vs ~3.78M median
    for r in regressions:
        assert r["worse_by"] > 0


def test_improvement_never_flags(tmp_path):
    path = _fresh(tmp_path)
    perf_ledger.ingest_rounds(path=path)
    entries = perf_ledger.load(path)
    good = perf_ledger._entry_from_bench(
        {"value": 9_000_000.0, "e2e_warm_s": 2.0, "e2e_backend": "cpu",
         "backend": "cpu-fallback (synthetic)"},
        "synthetic-good", None)
    assert perf_ledger.check(entries + [good], good) == []


def test_backend_classes_never_cross_compare(tmp_path):
    """A first TPU round must not be judged against the CPU-fallback
    history (different machine, different numbers)."""
    path = _fresh(tmp_path)
    perf_ledger.ingest_rounds(path=path)
    entries = perf_ledger.load(path)
    tpu = perf_ledger._entry_from_bench(
        # on-chip e2e warm could legitimately be WORSE than the CPU number
        # at first (dispatch overhead) — no baseline, no verdict
        {"value": 100.0, "e2e_warm_s": 500.0, "e2e_backend": "tpu",
         "backend": "tpu"},
        "tpu-run", None)
    assert tpu["backend_class"] == "accel"
    assert perf_ledger.check(entries + [tpu], tpu) == []


def test_record_and_check_appends_and_verdicts(tmp_path):
    path = _fresh(tmp_path)
    out = perf_ledger.record_and_check(
        {"value": 3_700_000.0, "e2e_warm_s": 6.0, "e2e_backend": "cpu",
         "backend": "cpu-fallback (t)"},
        path=path)
    assert out["ledger_ok"] is True
    assert out["ledger_regressions"] == []
    entries = perf_ledger.load(path)
    assert entries[-1]["source"] == "live"
    assert "t_unix" in entries[-1]
    # a regressing run verdicts False and records WHICH fields
    out2 = perf_ledger.record_and_check(
        {"value": 500_000.0, "e2e_warm_s": 60.0, "e2e_backend": "cpu",
         "backend": "cpu-fallback (t)"},
        path=path)
    assert out2["ledger_ok"] is False
    assert any("e2e_warm_s" in r for r in out2["ledger_regressions"])
    # the flagged entry carries its regressions in the ledger itself
    assert perf_ledger.load(path)[-1]["regressions"]


def test_sustained_regression_never_becomes_its_own_baseline(tmp_path):
    """Regression: gate-flagged entries are excluded from baseline
    history — a sustained regression must stay flagged run after run, not
    get absorbed into the median after two appends."""
    path = _fresh(tmp_path)
    perf_ledger.ingest_rounds(path=path)
    bad = {"value": 3_700_000.0, "e2e_warm_s": 21.0, "e2e_backend": "cpu",
           "backend": "cpu-fallback (t)"}
    verdicts = [perf_ledger.record_and_check(dict(bad), path=path)["ledger_ok"]
                for _ in range(4)]
    assert verdicts == [False, False, False, False], verdicts
    # ...and a recovery back to the good trajectory goes green again
    good = {"value": 3_700_000.0, "e2e_warm_s": 6.0, "e2e_backend": "cpu",
            "backend": "cpu-fallback (t)"}
    assert perf_ledger.record_and_check(good, path=path)["ledger_ok"] is True


def test_record_and_check_never_raises(tmp_path, monkeypatch):
    monkeypatch.setattr(perf_ledger, "ingest_rounds",
                        lambda **k: (_ for _ in ()).throw(OSError("disk")))
    out = perf_ledger.record_and_check({"value": 1.0}, path=_fresh(tmp_path))
    assert out["ledger_ok"] is False
    assert "ledger_error" in out


def test_no_baseline_fields_are_skipped(tmp_path):
    """New fields (first round that carries e2e_device_time_s) have no
    history — skipped, not failed."""
    path = _fresh(tmp_path)
    perf_ledger.ingest_rounds(path=path)
    entries = perf_ledger.load(path)
    novel = perf_ledger._entry_from_bench(
        {"e2e_device_time_s": 123.0, "e2e_backend": "cpu",
         "backend": "cpu-fallback (x)"}, "novel", None)
    assert perf_ledger.check(entries + [novel], novel) == []


def test_cli_check_real_trajectory(tmp_path):
    ledger = _fresh(tmp_path)
    p = subprocess.run(
        [sys.executable, "-m", "tools.perf_ledger", "--check", "--json",
         "--ledger", ledger],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert p.returncode == 0, p.stdout + p.stderr
    rec = json.loads(p.stdout.strip().splitlines()[-1])
    assert rec["ok"] is True
    assert rec["entries"] == len(ROUNDS)


def test_cli_check_flags_candidate_regression(tmp_path):
    ledger = _fresh(tmp_path)
    cand = tmp_path / "bad.json"
    cand.write_text(json.dumps(
        {"value": 1_000_000.0, "e2e_warm_s": 30.0, "e2e_backend": "cpu",
         "backend": "cpu-fallback (x)"}))
    p = subprocess.run(
        [sys.executable, "-m", "tools.perf_ledger", "--check", "--json",
         "--ledger", ledger, "--candidate", str(cand)],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert p.returncode == 1, p.stdout + p.stderr
    rec = json.loads(p.stdout.strip().splitlines()[-1])
    assert rec["ok"] is False
    assert {r["field"] for r in rec["regressions"]} >= {"e2e_warm_s", "value"}


def test_field_trends_emits_gap_markers_aligned_to_entries():
    """Satellite (round 15): an entry missing a tracked field used to be
    silently skipped, shifting the sparkline left and misaligning the
    HTML ledger tab against run ids — now every trend string carries one
    glyph per ledger entry with an explicit gap marker."""
    def entry(n, fields):
        return perf_ledger._entry_from_bench(
            {**fields, "e2e_backend": "cpu", "backend": "cpu"}, f"e{n}", n)

    entries = [
        entry(1, {"e2e_warm_s": 8.0, "value": 100.0}),
        entry(2, {"value": 110.0}),                      # e2e_warm_s gap
        entry(3, {"e2e_warm_s": 6.0, "value": 120.0}),
    ]
    rows = {r["field"]: r for r in perf_ledger.field_trends(entries)}
    warm = rows["e2e_warm_s"]
    assert len(warm["trend"]) == len(entries)            # aligned to run ids
    assert warm["trend"][1] == perf_ledger.GAP_MARK      # the gap is EXPLICIT
    assert warm["trend"][0] != perf_ledger.GAP_MARK
    assert warm["trend"][2] != perf_ledger.GAP_MARK
    assert warm["n"] == 2 and warm["gaps"] == 1
    val = rows["value"]
    assert perf_ledger.GAP_MARK not in val["trend"]
    assert val["n"] == 3 and val["gaps"] == 0
    assert len(val["trend"]) == len(entries)


def test_flagged_entry_carries_doctor_diagnosis(tmp_path):
    """Tentpole wiring (round 15): a gate failure attaches a non-empty
    perf-doctor ``diagnosis`` to the flagged ledger entry, naming the
    regressed node and its dominant phase, and the bench hook returns the
    top attribution lines for printing."""
    path = _fresh(tmp_path)
    good = {"value": 3_700_000.0, "e2e_warm_s": 6.0, "e2e_backend": "cpu",
            "backend": "cpu-fallback (t)",
            "e2e_node_summary": {
                "drift_statistics/all": {"wall_s": 1.0, "dispatch_s": 0.8,
                                         "host_s": 0.2}}}
    assert perf_ledger.record_and_check(good, path=path)["ledger_ok"] is True
    bad = {"value": 3_700_000.0, "e2e_warm_s": 60.0, "e2e_backend": "cpu",
           "backend": "cpu-fallback (t)",
           "e2e_node_summary": {
               "drift_statistics/all": {"wall_s": 3.0, "dispatch_s": 2.6,
                                        "host_s": 0.4}}}
    out = perf_ledger.record_and_check(bad, path=path)
    assert out["ledger_ok"] is False
    assert out["ledger_attribution"], out  # top-3 lines, not a bare field
    flagged = perf_ledger.load(path)[-1]
    diag = flagged.get("diagnosis")
    assert diag and diag["attributions"], flagged
    from anovos_tpu.obs.diffing import validate_diagnosis

    assert validate_diagnosis(diag) == []
    # the flagged FIELD leads (structural), and the regressed NODE is
    # named with its dominant phase
    assert diag["attributions"][0]["subject"] == "e2e_warm_s"
    node_attrs = [a for a in diag["attributions"] if a["kind"] == "node"]
    assert any("drift_statistics/all" in a["detail"]
               and "dispatch" in a["detail"] for a in node_attrs), node_attrs
    # a clean follow-up run attaches nothing
    out3 = perf_ledger.record_and_check(dict(good), path=path)
    assert out3["ledger_attribution"] == []


def test_node_summary_rides_entries_but_not_content_id():
    """The per-node summary must not move the committed entries' content
    ids (ingest dedup keys on them)."""
    base = {"value": 1.0, "e2e_backend": "cpu", "backend": "cpu"}
    with_nodes = {**base,
                  "e2e_node_summary": {"n1": {"wall_s": 1.0, "host_s": 1.0}}}
    e1 = perf_ledger._entry_from_bench(base, "s", 1)
    e2 = perf_ledger._entry_from_bench(with_nodes, "s", 1)
    assert e1["id"] == e2["id"]
    assert "nodes" not in e1 and e2["nodes"]["n1"]["wall_s"] == 1.0


def test_committed_ledger_matches_rounds():
    """The repo-root BENCH_LEDGER.jsonl is the ingested committed rounds —
    regenerating from BENCH_r*.json must be a no-op (append-only identity;
    live bench entries may follow, which is fine)."""
    path = perf_ledger.DEFAULT_LEDGER
    assert os.path.exists(path), "committed ledger missing"
    have = {e["id"] for e in perf_ledger.load(path)}
    for p in ROUNDS:
        e = perf_ledger.parse_round_file(p)
        if e is not None:
            assert e["id"] in have, f"{p} not ingested into the committed ledger"
