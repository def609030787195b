"""anovos_tpu.resilience — tier-1 acceptance (ISSUE 6).

* chaos spec parsing is exact and injections are seeded/deterministic;
* per-node retry absorbs a transient failure (a flaky node no longer
  costs the run), discarding the failed attempt's partial artifacts but
  never append-mode files;
* timeout escalation interrupts and re-executes instead of fatal
  ``NodeTimeout``; a truly stuck retry+degrade node is abandoned and
  DEGRADED, not fatal;
* a simulated mid-run backend wedge triggers exactly one failover with a
  WAL record, and the node re-executes to completion;
* the chaos e2e: a run with one injected exception + one hang + one
  wedge completes with artifacts byte-identical to the clean golden
  tree (obs/ excluded) and manifest retry/failover counters > 0
  (``tools/chaos_run.py`` is the same gate as a CLI);
* the aborted-run ``writer.close()`` failure no longer masks the
  original node exception (regression).
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from anovos_tpu.parallel.scheduler import DagScheduler, NodeTimeout  # noqa: E402
from anovos_tpu.resilience import chaos, failover  # noqa: E402
from anovos_tpu.resilience import policy as rpolicy  # noqa: E402
from anovos_tpu.resilience.policy import ErrorPolicy, backoff_delay, parse_policy  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_resilience_state():
    chaos.reset()
    failover.reset()
    rpolicy.reset_degraded()
    yield
    chaos.reset()
    failover.reset()
    rpolicy.reset_degraded()


# ------------------------------------------------------------- chaos ----
def test_chaos_spec_parsing_and_options():
    p = chaos.ChaosPlan(
        "seed=42;exc@node:a;hang@node:q/*:secs=3.5:n=2;wedge@node:d:p=0.5")
    assert p.seed == 42
    kinds = {(d.kind, d.pattern) for d in p.directives}
    assert kinds == {("exc", "node:a"), ("hang", "node:q/*"), ("wedge", "node:d")}
    hang = next(d for d in p.directives if d.kind == "hang")
    assert hang.secs == 3.5 and hang.n == 2


def test_chaos_spec_rejects_garbage():
    with pytest.raises(ValueError, match="no '@site'"):
        chaos.ChaosPlan("exc")
    with pytest.raises(ValueError, match="unknown chaos kind"):
        chaos.ChaosPlan("explode@node:a")


def test_chaos_claim_counts_and_glob():
    p = chaos.ChaosPlan("exc@node:stats/*")
    assert p.claim("node:other") == []
    assert len(p.claim("node:stats/x")) == 1   # fires once
    assert p.claim("node:stats/x") == []       # n=1 exhausted
    assert p.injection_count() == 1
    assert p.summary()["fired"] == {"exc@node:stats/*": 1}


def test_chaos_probabilistic_is_seeded_deterministic():
    def fire_pattern(seed):
        p = chaos.ChaosPlan(f"seed={seed};exc@node:x:p=0.5:n=100")
        return [bool(p.claim("node:x")) for _ in range(20)]

    assert fire_pattern(7) == fire_pattern(7)  # reproducible
    assert fire_pattern(7) != fire_pattern(8)  # seed actually used


def test_chaos_hang_interruptible_and_inert_without_plan():
    chaos.chaos_point("node:anything")  # no plan: inert
    chaos.install("hang@node:h:secs=60")
    ev = threading.Event()
    ev.set()
    t0 = time.monotonic()
    with pytest.raises(chaos.ChaosHang):
        chaos.chaos_point("node:h", interrupt=ev)
    assert time.monotonic() - t0 < 5


# ------------------------------------------------------------ policy ----
def test_parse_policy_variants():
    assert parse_policy("raise").mode == "raise"
    assert parse_policy("continue").mode == "continue"
    p = parse_policy("retry:3")
    assert (p.mode, p.retries, p.on_exhausted) == ("retry", 3, "raise")
    p = parse_policy("retry:2:degrade")
    assert (p.retries, p.on_exhausted) == (2, "degrade")
    p2 = ErrorPolicy(mode="retry", retries=1, timeout_factor=2.0)
    assert parse_policy(p2) is p2
    for bad in ("explode", "retry:x", "retry:1:maybe"):
        with pytest.raises(ValueError):
            parse_policy(bad)


def test_backoff_is_deterministic_capped_and_jittered():
    pol = parse_policy("retry:5")
    a = [backoff_delay("n", i, pol) for i in range(1, 6)]
    b = [backoff_delay("n", i, pol) for i in range(1, 6)]
    assert a == b                                   # no shared RNG state
    assert all(d <= pol.backoff_cap_s for d in a)   # capped
    assert backoff_delay("n", 1, pol) != backoff_delay("m", 1, pol)  # decorrelated


def test_degraded_registry_roundtrip():
    rpolicy.record_degraded("nodeA", "ValueError: boom")
    assert rpolicy.degraded_sections() == {"nodeA": "ValueError: boom"}
    rpolicy.reset_degraded()
    assert rpolicy.degraded_sections() == {}


# ------------------------------------------------- scheduler: retry ----
def test_retry_absorbs_transient_failure_and_books_attempts():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("transient")

    s = DagScheduler()
    s.add("flaky", flaky, on_error="retry:3")
    summary = s.run(mode="sequential")
    assert calls["n"] == 3
    assert summary["nodes"]["flaky"]["attempts"] == 3
    assert summary["nodes"]["flaky"]["state"] == "done"
    assert summary["resilience"]["retries"] == 2


def test_retry_exhaustion_raises_original_error():
    def always():
        raise ValueError("permanent")

    s = DagScheduler()
    s.add("bad", always, on_error="retry:2")
    with pytest.raises(ValueError, match="permanent"):
        s.run(mode="sequential")
    assert s._by_name["bad"].attempts == 3


@pytest.mark.parametrize("mode", ["sequential", "concurrent"])
def test_degrade_keeps_run_alive_and_unblocks_dependents(mode):
    ran = []

    def always():
        raise ValueError("permanent")

    s = DagScheduler()
    s.add("anal", always, writes=("stats:x",), on_error="retry:1:degrade")
    s.add("report", lambda: ran.append("report"), reads=("stats:x",))
    summary = s.run(mode=mode, node_timeout=30)
    assert ran == ["report"]  # the dependent still ran
    assert summary["nodes"]["anal"]["state"] == "degraded"
    assert summary["resilience"]["degraded"] == ["anal"]
    assert rpolicy.degraded_sections().keys() == {"anal"}


def test_retry_discards_partial_artifacts_but_keeps_appends(tmp_path):
    """Between attempts the capture recorder's created files are removed;
    append-mode files (pre-existing content) survive."""
    from anovos_tpu.cache import CacheStore, NodeCachePolicy, capture

    store = CacheStore(str(tmp_path / "store"))
    partial = tmp_path / "partial.csv"
    appended = tmp_path / "metrics.csv"
    appended.write_text("history\n")
    calls = {"n": 0}

    def body():
        calls["n"] += 1
        if calls["n"] == 1:
            with open(partial, "w") as f:  # builtins.open: the hooked path
                f.write("half-written")
            with open(appended, "a") as f:
                f.write("attempt1\n")
            raise RuntimeError("mid-write failure")
        # the discard pass must have removed the partial, kept the append
        assert not partial.exists()
        assert appended.read_text().startswith("history\n")
        with open(partial, "w") as f:
            f.write("complete")

    s = DagScheduler(cache_store=store)
    s.add("writer_node", body, on_error="retry:1",
          cache=NodeCachePolicy(key_material="km"))
    capture.install_open_hook()  # as workflow.main does when the cache is on
    try:
        s.run(mode="sequential")
    finally:
        capture.uninstall_open_hook()
    assert calls["n"] == 2
    assert partial.read_text() == "complete"
    assert "history\n" in appended.read_text()


def test_node_retry_and_failover_events_land_in_journal(tmp_path):
    from anovos_tpu.cache import RunJournal, read_journal

    journal = RunJournal(str(tmp_path / "j.jsonl"))
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("transient")

    chaos.install("wedge@node:wedgy")
    s = DagScheduler(journal=journal)
    s.add("flaky", flaky, on_error="retry:1")
    s.add("wedgy", lambda: None, on_error="retry:0")
    s.run(mode="sequential")
    events = [r["event"] for r in read_journal(journal.path)]
    assert "node_retry" in events
    assert "backend_failover" in events
    retry = next(r for r in read_journal(journal.path) if r["event"] == "node_retry"
                 and r["node"] == "flaky")
    assert retry["kind"] == "retry" and retry["attempt"] == 1


# --------------------------------------- scheduler: timeout paths ----
def test_hang_escalates_interrupts_and_reexecutes():
    chaos.install("hang@node:hangy:secs=600")
    ran = []
    s = DagScheduler()
    s.add("hangy", lambda: ran.append(1), on_error="retry:0")
    t0 = time.monotonic()
    summary = s.run(mode="concurrent", node_timeout=0.5)
    assert time.monotonic() - t0 < 30
    assert ran == [1]
    assert summary["nodes"]["hangy"]["escalated"] is True
    assert summary["resilience"]["timeout_escalations"] == 1
    assert summary["resilience"]["timeout_retries"] == 1


def test_truly_stuck_degrade_node_is_abandoned_not_fatal():
    hung = threading.Event()
    ran = []
    s = DagScheduler()
    s.add("stuck", lambda: hung.wait(30), writes=("x",),
          on_error="retry:0:degrade")
    s.add("down", lambda: ran.append(1), reads=("x",))
    t0 = time.monotonic()
    summary = s.run(mode="concurrent", node_timeout=0.3)
    assert time.monotonic() - t0 < 20
    assert ran == [1]  # dependent ran after the abandon
    assert summary["nodes"]["stuck"]["state"] == "degraded"
    assert "stuck" in rpolicy.degraded_sections()
    hung.set()


def test_truly_stuck_raise_node_still_raises_nodetimeout():
    hung = threading.Event()
    s = DagScheduler()
    s.add("stuck_block", lambda: hung.wait(30))
    with pytest.raises(NodeTimeout, match="stuck_block"):
        s.run(mode="concurrent", node_timeout=0.3)
    hung.set()


# --------------------------------------------- failover / health ----
def test_probe_in_process_healthy_on_cpu():
    from anovos_tpu.resilience.failover import probe_in_process

    assert probe_in_process(60.0) is True


def test_backend_healthy_false_under_simulated_wedge():
    chaos.set_wedged()
    assert failover.backend_healthy() is False
    chaos.clear_wedge()


def test_wedge_flips_once_and_clears():
    chaos.install("wedge@node:w")
    ran = []
    s = DagScheduler()
    # retry:0 — no policy budget; the post-failover re-execution is the
    # budget-free grant retry-mode nodes get
    s.add("w", lambda: ran.append(1), on_error="retry:0")
    summary = s.run(mode="sequential")
    assert ran == [1]
    assert summary["resilience"]["failovers"] == 1
    assert not chaos.backend_wedged()
    # one flip per run: a second maybe_failover is a no-op
    assert failover.maybe_failover(RuntimeError("XlaRuntimeError: x")) is False


def test_raise_mode_node_opts_out_of_all_reexecution():
    """A node registered on_error='raise' (e.g. the stability node, whose
    cross-run metric appends a re-execution could double-book) gets NO
    re-execution of any kind: the failover still flips the backend for the
    REST of the run, but this node's error propagates."""
    chaos.install("wedge@node:w")
    calls = {"n": 0}

    def body():
        calls["n"] += 1

    s = DagScheduler()
    s.add("w", body, on_error="raise")
    with pytest.raises(chaos.BackendWedge):
        s.run(mode="sequential")
    assert calls["n"] == 0  # the chaos wedge fired pre-body; no re-execution
    assert s._by_name["w"].attempts == 1
    assert failover.failover_count() == 1  # the run-level flip still happened


def test_ordinary_errors_never_pay_a_probe(monkeypatch):
    probed = []
    monkeypatch.setattr(failover, "backend_healthy",
                        lambda *a, **k: probed.append(1) or True)
    assert failover.maybe_failover(ValueError("plain config error")) is False
    assert probed == []  # not backend-shaped: no probe
    assert failover.maybe_failover(RuntimeError("XlaRuntimeError: dead")) is False
    assert probed == [1]  # backend-shaped: probed (healthy -> no flip)


# --------------------------------------------------- workflow level ----
def _mini_run(tmp_path, monkeypatch, chaos_spec="", **env):
    """One small workflow.main run in a tmp dir; returns the manifest."""
    import copy

    from anovos_tpu import workflow
    from anovos_tpu.obs import load_manifest
    from tools.chaos_run import synthetic_config

    cfg = synthetic_config(str(tmp_path))
    rundir = tmp_path / "run"
    rundir.mkdir(exist_ok=True)
    monkeypatch.delenv("ANOVOS_TPU_CACHE", raising=False)
    monkeypatch.setenv("ANOVOS_TPU_EXECUTOR", "sequential")
    if chaos_spec:
        monkeypatch.setenv("ANOVOS_TPU_CHAOS", chaos_spec)
    else:
        monkeypatch.delenv("ANOVOS_TPU_CHAOS", raising=False)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    monkeypatch.chdir(rundir)
    workflow.main(copy.deepcopy(cfg), "local")
    return load_manifest(workflow.LAST_MANIFEST_PATH)


def test_manifest_resilience_section_clean_run(tmp_path, monkeypatch):
    man = _mini_run(tmp_path, monkeypatch)
    res = man["resilience"]
    assert res["retries"] == 0
    assert res["failovers"] == 0
    assert res["degraded_sections"] == {}
    assert res["chaos"] is None
    # stable_view strips the fault-history fields
    from anovos_tpu.obs import stable_view

    sv = stable_view(man)
    assert "resilience" not in sv
    assert all("attempts" not in n for n in sv["scheduler"]["nodes"].values())


def test_degraded_section_reaches_manifest_and_report(tmp_path, monkeypatch):
    """A fan-out analytics node that exhausts retries degrades: the run
    completes, the manifest names the section, the report renders the
    placeholder tab."""
    man = _mini_run(
        tmp_path, monkeypatch,
        # n=99: the injection outlives every retry -> exhaustion -> degrade
        chaos_spec="exc@node:stats_generator/measures_of_counts:n=99",
        ANOVOS_TPU_RETRIES="1")
    res = man["resilience"]
    assert "stats_generator/measures_of_counts" in res["degraded_sections"]
    assert res["degraded"] == ["stats_generator/measures_of_counts"]
    # the report (not part of the synthetic config) would render the
    # placeholder banner from the same registry the manifest read
    from anovos_tpu.resilience import degraded_sections

    assert "stats_generator/measures_of_counts" in degraded_sections()


def test_writer_close_failure_does_not_mask_node_error(tmp_path, monkeypatch):
    """Regression (ISSUE 6 satellite): an aborted run whose async writer
    ALSO fails on close() must re-raise the ORIGINAL node exception, with
    the close failure chained onto its __context__, not masking it."""
    import copy

    from anovos_tpu import workflow
    from anovos_tpu.shared.artifact_store import AsyncArtifactWriter
    from tools.chaos_run import synthetic_config

    cfg = synthetic_config(str(tmp_path))
    cfg["stats_generator"]["metric"] = ["global_summary", "no_such_metric"]
    rundir = tmp_path / "run2"
    rundir.mkdir()
    monkeypatch.delenv("ANOVOS_TPU_CACHE", raising=False)
    monkeypatch.setenv("ANOVOS_TPU_EXECUTOR", "sequential")
    monkeypatch.setenv("ANOVOS_TPU_RETRIES", "0")
    monkeypatch.setenv("ANOVOS_TPU_DEGRADE", "0")
    monkeypatch.delenv("ANOVOS_TPU_CHAOS", raising=False)
    monkeypatch.chdir(rundir)

    orig_close = AsyncArtifactWriter.close

    def bad_close(self):
        orig_close(self)
        raise RuntimeError("close boom")

    monkeypatch.setattr(AsyncArtifactWriter, "close", bad_close)
    with pytest.raises(AttributeError) as ei:
        workflow.main(copy.deepcopy(cfg), "local")
    # the original AttributeError (bad metric) propagated; the close
    # failure rides its context chain instead of masking it
    chain, seen = [], ei.value
    while seen is not None and len(chain) < 10:  # bounded: a cycle is a bug
        chain.append(seen)
        seen = seen.__context__
    assert len(chain) < 10, "context chain does not terminate (cycle)"
    assert any(isinstance(c, RuntimeError) and "close boom" in str(c)
               for c in chain[1:]), [repr(c) for c in chain]


# ------------------------------------------------------- chaos e2e ----
def _chaos_cli(scenario, workdir, timeout=560):
    """Run tools/chaos_run.py in a FRESH single-device process.

    A fresh process gives the single-device production shape (concurrent
    DAG, watchdog armed) without inheriting the pytest process's 8-virtual-
    device XLA_FLAGS; the multi-device variant of the gate — lanes,
    rendezvous-lane release, the ``hang-collective`` scenario — runs with
    ``--devices 8`` in tests/test_multidev_executor.py."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    for k in ("ANOVOS_TPU_CHAOS", "ANOVOS_TPU_CACHE", "ANOVOS_TPU_EXECUTOR",
              "XLA_FLAGS"):
        env.pop(k, None)
    p = subprocess.run(
        [sys.executable, "-m", "tools.chaos_run", "--scenario", scenario,
         "--workdir", str(workdir), "--json"],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )
    assert p.returncode == 0, p.stdout + p.stderr
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_chaos_e2e_exception_hang_wedge_byte_identical(tmp_path):
    """THE acceptance gate: a seeded run injecting one exception, one
    hang and one simulated wedge completes with artifacts byte-identical
    to the clean golden tree (obs/ excluded) and manifest retry/failover
    counters > 0 — and doubles as the tier-1 wiring of the
    tools/chaos_run.py CLI scenario gate."""
    result = _chaos_cli("full", tmp_path)
    assert result["ok"], result
    assert result["parity"] is True
    assert result["injections"] == 3
    res = result["resilience"]
    assert res["retries"] >= 3  # exc retry + hang timeout-retry + wedge failover-retry
    assert res["timeout_escalations"] >= 1
    assert res["failovers"] == 1
    assert res["degraded"] == []
