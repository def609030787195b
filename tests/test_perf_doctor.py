"""Perf doctor (anovos_tpu.obs.diffing + tools/perf_doctor): differential
run observability.

Covers the ISSUE-15 acceptance surface:

* manifest-diff edge cases — node present in one run only, degraded-vs-
  clean pairs (structural, ranks first), sequential-vs-concurrent pairs
  (queue-wait movement must NOT book as a regression attribution), and
  cross-backend-class pairs refused loudly;
* the compile-census program-set diff with node attribution and the
  cache hit-set diff naming the moved fingerprint input;
* determinism (byte-identical double diff) + schema validity;
* the flight recorder's live doctor summary ("slow vs the last clean
  run" on /statusz);
* a program-set change that comes with a dispatch_s drop and a flipped
  knob: the first two lead the top-3 attributions, the knob is the
  informational tail, deterministically.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from anovos_tpu.obs import diffing  # noqa: E402


# -- synthetic manifest helpers -------------------------------------------

def _node(dur=1.0, queue=0.0, cached=False, degraded=False, state="done"):
    return {"start_s": 0.0, "end_s": dur, "dur_s": dur, "queue_wait_s": queue,
            "thread": "w0", "lane": "mesh", "devices": [], "state": state,
            "cached": cached, "attempts": 1, "escalated": False,
            "degraded": degraded, "deps": []}


def _dev(wall=1.0, device=0.0, dispatch=0.2, transfer=0.05, host=None,
         h2d=1000, d2h=500):
    if host is None:
        host = max(wall - device - dispatch - transfer, 0.0)
    return {"wall_s": wall, "device_time_s": device, "dispatch_s": dispatch,
            "transfer_s": transfer, "host_s": round(host, 6),
            "h2d_bytes": h2d, "d2h_bytes": d2h, "dispatches": 3,
            "transfers": 2, "last_op": "op", "clamped": False}


def _man(nodes, devprof=None, census=None, backend="cpu", config_hash="c1",
         mode="sequential", wall=10.0, cache=None, resilience=None, env=None):
    return {
        "manifest_version": 1,
        "config_hash": config_hash,
        "run_type": "local",
        "executor": {"mode": mode, "workers": 1},
        "critical_path": sorted(nodes),
        "scheduler": {"mode": mode, "workers": 1, "wall_s": wall,
                      "nodes": nodes},
        "block_seconds": {},
        "metrics": {},
        "compile_census": census,
        "cache": cache,
        "resilience": resilience,
        "devprof": devprof,
        "env": env,
        "trace_path": None,
        "backend": backend,
        "generated_unix": 1000.0,
    }


def _kinds(diag, top=None):
    attrs = diag["attributions"][: top or None]
    return [(a["kind"], a["subject"]) for a in attrs]


# -- manifest-diff edge cases ---------------------------------------------

def test_phase_decomposition_and_dominant_phase():
    base = _man({"a": _node(1.0), "b": _node(2.0)},
                devprof={"a": _dev(1.0, dispatch=0.2),
                         "b": _dev(2.0, dispatch=0.5)})
    cand = _man({"a": _node(1.0), "b": _node(3.0)},
                devprof={"a": _dev(1.0, dispatch=0.2),
                         "b": _dev(3.0, dispatch=1.4)})
    d = diffing.diff_manifests(base, cand)
    assert diffing.validate_diagnosis(d) == []
    nb = d["nodes"]["b"]
    assert nb["wall_delta_s"] == pytest.approx(1.0)
    assert nb["dominant_phase"] == "dispatch_s"
    disp = [a for a in d["attributions"]
            if a["kind"] == "phase" and a["subject"] == "dispatch_s"]
    assert disp and disp[0]["delta_s"] == pytest.approx(0.9)
    assert "b (+0.900s)" in disp[0]["detail"]
    assert d["wall_delta_s"] is None or isinstance(d["wall_delta_s"], float)


def test_node_present_in_one_run_only():
    base = _man({"a": _node(1.0), "gone": _node(2.5)},
                devprof={"a": _dev(1.0), "gone": _dev(2.5)})
    cand = _man({"a": _node(1.0), "fresh": _node(0.5)},
                devprof={"a": _dev(1.0), "fresh": _dev(0.5)})
    d = diffing.diff_manifests(base, cand)
    assert d["nodes"]["gone"]["status"] == "removed"
    assert d["nodes"]["fresh"]["status"] == "added"
    kinds = _kinds(d)
    assert ("node_removed", "gone") in kinds
    assert ("node_added", "fresh") in kinds
    # structural: registration-set changes outrank timing movement
    removed = next(a for a in d["attributions"] if a["kind"] == "node_removed")
    assert removed["severity"] == "structural"


def test_degraded_vs_clean_pair_ranks_first():
    base = _man({"a": _node(1.0), "q": _node(4.0)},
                devprof={"a": _dev(1.0), "q": _dev(4.0)})
    cand = _man({"a": _node(1.2), "q": _node(0.1, degraded=True,
                                             state="degraded")},
                devprof={"a": _dev(1.2), "q": _dev(0.1)},
                resilience={"degraded_sections": {"q": "retries exhausted"}})
    d = diffing.diff_manifests(base, cand)
    top = d["attributions"][0]
    assert top["kind"] == "degraded" and top["subject"] == "q"
    assert top["severity"] == "structural"
    assert "missing, not slower" in top["detail"]
    # the degraded node's wall COLLAPSE is not misread as an improvement
    # headline: the structural line leads regardless of timing scores
    assert d["nodes"]["q"]["degraded"] == [False, True]


def test_sequential_vs_concurrent_queue_wait_never_books_as_regression():
    """A concurrent run queues nodes behind the worker pool — queue-wait
    movement is scheduling, not node cost, and must produce ZERO timing
    attributions when body walls are unchanged."""
    base = _man({"a": _node(1.0, queue=0.0), "b": _node(2.0, queue=0.0)},
                devprof={"a": _dev(1.0), "b": _dev(2.0)},
                mode="sequential")
    cand = _man({"a": _node(1.0, queue=1.7), "b": _node(2.0, queue=2.4)},
                devprof={"a": _dev(1.0), "b": _dev(2.0)},
                mode="concurrent", wall=8.0)
    d = diffing.diff_manifests(base, cand)
    assert d["executor_change"] == ["sequential", "concurrent"]
    assert d["nodes"]["b"]["queue_wait_delta_s"] == pytest.approx(2.4)
    timing = [a for a in d["attributions"] if a["severity"] == "timing"]
    assert timing == [], timing
    kinds = {a["kind"] for a in d["attributions"]}
    assert kinds <= {"executor"}


def test_cross_backend_class_pair_refused_loudly():
    base = _man({"a": _node(1.0)}, backend="cpu")
    cand = _man({"a": _node(1.0)}, backend="tpu")
    with pytest.raises(diffing.DiffRefused, match="backend classes"):
        diffing.diff_manifests(base, cand)


def test_program_set_diff_names_nodes_and_wall():
    base = _man({"a": _node(1.0)}, devprof={"a": _dev(1.0)}, census={
        "compiles_total": 10, "distinct_programs": 8, "distinct_kernels": 8,
        "compile_seconds_total": 5.0,
        "programs": [
            {"program": "jit(eager_one)", "count": 3, "seconds": 2.0,
             "nodes": ["a"]},
            {"program": "jit(shared)", "count": 1, "seconds": 1.0,
             "nodes": ["a"]},
        ]})
    cand = _man({"a": _node(1.0)}, devprof={"a": _dev(1.0)}, census={
        "compiles_total": 4, "distinct_programs": 3, "distinct_kernels": 3,
        "compile_seconds_total": 2.0,
        "programs": [
            {"program": "jit(_fused_block)", "count": 2, "seconds": 1.5,
             "nodes": ["a"]},
            {"program": "jit(shared)", "count": 2, "seconds": 1.2,
             "nodes": ["a"]},
        ]})
    d = diffing.diff_manifests(base, cand)
    p = d["programs"]
    assert p["new"] == ["jit(_fused_block)"]
    assert p["retired"] == ["jit(eager_one)"]
    assert p["count_changed"] == {"jit(shared)": [1, 2]}
    assert p["compile_wall_delta_s"] == pytest.approx(-3.0)
    assert p["nodes_touched"] == ["a"]
    prog = next(a for a in d["attributions"] if a["kind"] == "programs")
    assert "jit(_fused_block)" in prog["detail"]
    assert "nodes touched: a" in prog["detail"]


def test_program_set_change_and_dispatch_drop_lead_the_top3():
    """Glue chains folded into one program: the program-set change is
    named, the dispatch_s drop is a NEGATIVE phase attribution beside it in
    the top three, and the knob that differs is the informational tail."""
    env_b = {"code_version": "1.0", "knobs": {"ANOVOS_SHAPE_BUCKETS": "0"},
             "env_fingerprint": "e1", "dataset_fingerprint": "d1"}
    env_c = {"code_version": "1.0", "knobs": {"ANOVOS_SHAPE_BUCKETS": "1"},
             "env_fingerprint": "e2", "dataset_fingerprint": "d1"}
    base = _man(
        {"a": _node(2.0), "b": _node(1.0)},
        devprof={"a": _dev(2.0, dispatch=1.2), "b": _dev(1.0, dispatch=0.4)},
        census={"programs_distinct": 3, "programs": [
            {"program": "jit(eager_and)", "count": 4, "seconds": 0.5, "nodes": ["a"]},
            {"program": "jit(eager_cast)", "count": 4, "seconds": 0.5, "nodes": ["a", "b"]},
            {"program": "jit(shared)", "count": 1, "seconds": 1.0, "nodes": ["a"]},
        ]}, env=env_b)
    cand = _man(
        {"a": _node(1.1), "b": _node(0.8)},
        devprof={"a": _dev(1.1, dispatch=0.3), "b": _dev(0.8, dispatch=0.2)},
        census={"programs_distinct": 2, "programs": [
            {"program": "jit(_glue_program)", "count": 1, "seconds": 0.6, "nodes": ["a", "b"]},
            {"program": "jit(shared)", "count": 1, "seconds": 1.0, "nodes": ["a"]},
        ]}, env=env_c)
    d = diffing.diff_manifests(base, cand)
    assert diffing.validate_diagnosis(d) == []
    assert diffing.canonical(d) == diffing.canonical(diffing.diff_manifests(base, cand))
    top3 = d["attributions"][:3]
    assert ("programs", "program_set") in _kinds(d, 3), d["attributions"][:6]
    prog = next(a for a in top3 if a["kind"] == "programs")
    assert prog["detail"].startswith("program set moved"), prog
    assert d["programs"]["new"] == ["jit(_glue_program)"]
    assert d["programs"]["retired"] == ["jit(eager_and)", "jit(eager_cast)"]
    disp = next((a for a in top3
                 if a["kind"] == "phase" and a["subject"] == "dispatch_s"), None)
    assert disp is not None, d["attributions"][:6]
    assert disp["delta_s"] == pytest.approx(-1.1)
    env_attrs = [a for a in d["attributions"] if a["kind"] == "env"]
    assert [a["subject"] for a in env_attrs] == ["ANOVOS_SHAPE_BUCKETS"]
    assert env_attrs[0]["severity"] == "info"


def test_cache_hit_set_diff_names_moved_fingerprint_input():
    env_b = {"code_version": "1.0", "knobs": {"ANOVOS_SHAPE_BUCKETS": "1"},
             "env_fingerprint": "e1", "dataset_fingerprint": "d1"}
    env_c = {"code_version": "1.0", "knobs": {},
             "env_fingerprint": "e2", "dataset_fingerprint": "d1"}
    base = _man({"a": _node(1.0, cached=True), "b": _node(2.0, cached=True)},
                devprof={}, cache={"enabled": True, "hits": 2, "misses": 0},
                env=env_b)
    cand = _man({"a": _node(1.0, cached=False), "b": _node(2.0, cached=True)},
                devprof={}, cache={"enabled": True, "hits": 1, "misses": 1},
                env=env_c)
    d = diffing.diff_manifests(base, cand)
    assert d["cache"]["re_executed"] == ["a"]
    assert any("ANOVOS_SHAPE_BUCKETS" in m for m in d["cache"]["moved_inputs"])
    cache_attr = next(a for a in d["attributions"] if a["kind"] == "cache")
    assert "re-executed" in cache_attr["detail"]
    assert "ANOVOS_SHAPE_BUCKETS" in cache_attr["detail"]
    env_attr = next(a for a in d["attributions"] if a["kind"] == "env")
    assert env_attr["subject"] == "ANOVOS_SHAPE_BUCKETS"
    assert env_attr["severity"] == "info"


def test_diff_is_deterministic_and_schema_valid():
    base = _man({"a": _node(1.0), "b": _node(2.0)},
                devprof={"a": _dev(1.0), "b": _dev(2.0)})
    cand = _man({"a": _node(1.5), "c": _node(0.5)},
                devprof={"a": _dev(1.5, dispatch=0.7), "c": _dev(0.5)})
    d1 = diffing.diff_manifests(base, cand)
    d2 = diffing.diff_manifests(base, cand)
    assert diffing.canonical(d1) == diffing.canonical(d2)
    assert diffing.validate_diagnosis(d1) == []
    # the validator actually bites
    broken = json.loads(diffing.canonical(d1))
    broken["attributions"][0]["rank"] = 99
    assert diffing.validate_diagnosis(broken)


# -- flight recorder / live doctor summary --------------------------------

def test_live_node_summary_flags_slow_and_inflight_nodes():
    baseline = _man({"a": _node(1.0), "b": _node(0.4)},
                    devprof={"a": _dev(1.0), "b": _dev(0.4)})
    finished = {"a": _dev(2.0, dispatch=1.5)}     # 2x the baseline: slow
    active = {"b": {"elapsed_s": 5.0, "dispatch_s": 0.1}}  # way overdue
    s = diffing.live_node_summary(baseline, finished, active)
    assert s["slow"] == ["a", "b"]
    assert s["nodes"]["a"]["wall_delta_s"] == pytest.approx(1.0)
    assert s["nodes"]["a"]["dominant_phase"] == "dispatch_s"
    assert s["nodes"]["b"]["in_flight"] is True
    # no baseline devprof -> no summary (never a crash)
    assert diffing.live_node_summary({}, finished) is None
    assert diffing.live_node_summary(None, finished) is None


def test_flight_snapshot_carries_doctor_summary(tmp_path, monkeypatch):
    """build_snapshot embeds the doctor's per-node comparison against the
    PREVIOUS completed run's manifest at the same obs dir, so /statusz
    answers "what is slow right now vs the last clean run"."""
    from anovos_tpu.obs import devprof, flight, write_manifest

    obs_dir = tmp_path / "obs"
    obs_dir.mkdir()
    baseline = _man({"a": _node(1.0)}, devprof={"a": _dev(1.0)})
    write_manifest(baseline, str(obs_dir / "run_manifest.json"))
    monkeypatch.setattr(devprof, "results",
                        lambda: {"a": _dev(3.0, dispatch=2.5)})
    monkeypatch.setattr(devprof, "active_frames", lambda: {})
    flight.configure(str(obs_dir))
    try:
        doc = flight.build_snapshot("test", node="a")
        doctor = doc["doctor"]
        assert doctor is not None
        assert doctor["slow"] == ["a"]
        assert doctor["nodes"]["a"]["baseline_wall_s"] == pytest.approx(1.0)
        assert doctor["baseline_config_hash"] == "c1"
    finally:
        flight.reset()
    # disarmed + no prior manifest -> doctor is None, snapshot still works
    doc2 = flight.build_snapshot("test2")
    assert doc2["doctor"] is None


# -- CLI ------------------------------------------------------------------

def test_cli_manifest_mode_and_run_dir_resolution(tmp_path):
    from anovos_tpu.obs import write_manifest

    run_b = tmp_path / "run_b"
    (run_b / "obs").mkdir(parents=True)
    write_manifest(_man({"a": _node(1.0)}, devprof={"a": _dev(1.0)}),
                   str(run_b / "obs" / "run_manifest.json"))
    cand_file = tmp_path / "cand_manifest.json"
    write_manifest(_man({"a": _node(2.0)},
                        devprof={"a": _dev(2.0, dispatch=1.0)}),
                   str(cand_file))
    p = subprocess.run(
        [sys.executable, "-m", "tools.perf_doctor", "--json",
         "--baseline", str(run_b), "--candidate", str(cand_file)],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stdout + p.stderr
    diag = json.loads(p.stdout.strip().splitlines()[-1])
    assert diag["kind"] == "manifest"
    assert diffing.validate_diagnosis(diag) == []
    assert any(a["kind"] == "phase" for a in diag["attributions"])


def test_cli_refuses_cross_backend_pair(tmp_path):
    from anovos_tpu.obs import write_manifest

    b = tmp_path / "b.json"
    c = tmp_path / "c.json"
    write_manifest(_man({"a": _node(1.0)}, backend="cpu"), str(b))
    write_manifest(_man({"a": _node(1.0)}, backend="tpu"), str(c))
    p = subprocess.run(
        [sys.executable, "-m", "tools.perf_doctor", str(b), str(c)],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert p.returncode == 1
    assert "REFUSED" in p.stderr


# -- HTML report "Run Diff" tab -------------------------------------------

def test_run_diff_tab_env_gated_and_renders_ranked_table(tmp_path, monkeypatch):
    from anovos_tpu.data_report.report_generation import run_diff_gen
    from anovos_tpu.obs import write_manifest

    master = tmp_path / "master"
    (master / "obs").mkdir(parents=True)
    write_manifest(_man({"a": _node(2.0)},
                        devprof={"a": _dev(2.0, dispatch=1.2)}),
                   str(master / "obs" / "run_manifest.json"))
    base_dir = tmp_path / "baseline_run"
    (base_dir / "obs").mkdir(parents=True)
    write_manifest(_man({"a": _node(1.0)}, devprof={"a": _dev(1.0)}),
                   str(base_dir / "obs" / "run_manifest.json"))
    # env-gated: unset -> no tab, report bytes independent of checkout state
    monkeypatch.delenv("ANOVOS_RUN_DIFF_BASELINE", raising=False)
    assert run_diff_gen(str(master)) == ""
    monkeypatch.setenv("ANOVOS_RUN_DIFF_BASELINE", str(base_dir))
    html = run_diff_gen(str(master))
    assert "Run Diff" in html and "ranked attributions" in html
    assert "dispatch_s" in html
    # a refused cross-class pair renders LOUDLY instead of a thinner tab
    write_manifest(_man({"a": _node(1.0)}, backend="tpu"),
                   str(base_dir / "obs" / "run_manifest.json"))
    assert "Diff REFUSED" in run_diff_gen(str(master))
    # a fully-disjoint node set (every wall_delta_s None) still renders —
    # the |delta| sort must tolerate an all-None column (review fix)
    write_manifest(_man({"renamed": _node(1.0)},
                        devprof={"renamed": _dev(1.0)}),
                   str(base_dir / "obs" / "run_manifest.json"))
    html3 = run_diff_gen(str(master))
    assert "per-node movement" in html3 and "renamed" in html3
