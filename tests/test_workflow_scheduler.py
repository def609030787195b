"""Dependency-aware workflow executor: scheduler unit tests + the
sequential-vs-concurrent golden comparison on an income-demo config.

The contract under test (anovos_tpu/parallel/scheduler.py):
  * derived edges (read-after-write, write-after-write, write-after-read)
    give a topological order identical to the YAML walk in sequential mode;
  * fan-out analyzers pinned to a df version never observe a later spine
    mutation;
  * a read-only node registered ``on_error="continue"`` logs and the run
    completes; a spine (``on_error="raise"``) failure aborts with the
    ORIGINAL exception and skips dependents;
  * the per-node hang watchdog raises ``NodeTimeout`` naming the stuck
    block instead of deadlocking the suite;
  * both executors produce byte-identical artifacts on the demo pipeline.
"""

import hashlib
import os
import threading
import time

import pytest

from anovos_tpu.parallel.scheduler import DagScheduler, NodeTimeout, default_workers
from anovos_tpu.shared.artifact_store import AsyncArtifactWriter


def _order_recorder():
    order, lock = [], threading.Lock()

    def rec(name):
        def f():
            with lock:
                order.append(name)
        return f
    return order, rec


# ---------------------------------------------------------------------------
# graph construction / ordering
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["sequential", "concurrent"])
def test_topological_correctness_raw_waw_war(mode):
    """Readers run after their writer (RAW), a re-writer runs after both the
    previous writer (WAW) and its readers (WAR)."""
    order, rec = _order_recorder()
    s = DagScheduler()
    s.add("w1", rec("w1"), writes=("r",))
    s.add("read1", rec("read1"), reads=("r",))
    s.add("read2", rec("read2"), reads=("r",))
    s.add("w2", rec("w2"), writes=("r",))      # WAW w1, WAR read1/read2
    s.add("read3", rec("read3"), reads=("r",))  # RAW w2
    summary = s.run(mode=mode)
    pos = {n: i for i, n in enumerate(order)}
    assert pos["w1"] < min(pos["read1"], pos["read2"], pos["w2"])
    assert max(pos["read1"], pos["read2"]) < pos["w2"] < pos["read3"]
    assert summary["mode"] == mode
    assert all(n["state"] == "done" for n in summary["nodes"].values())


def test_sequential_runs_registration_order():
    order, rec = _order_recorder()
    s = DagScheduler()
    for name in ("a", "b", "c", "d"):
        s.add(name, rec(name))  # fully independent
    s.run(mode="sequential")
    assert order == ["a", "b", "c", "d"]


def test_duplicate_node_name_rejected():
    s = DagScheduler()
    s.add("n", lambda: None)
    with pytest.raises(ValueError, match="duplicate"):
        s.add("n", lambda: None)


def test_unwritten_resource_is_external_input():
    """Reading a resource nobody writes must not block or error (the
    sequential runner would likewise just read whatever pre-exists)."""
    order, rec = _order_recorder()
    s = DagScheduler()
    s.add("r", rec("r"), reads=("never_written",))
    s.run(mode="concurrent", node_timeout=30)
    assert order == ["r"]


def test_independent_nodes_actually_overlap():
    """Two nodes that each wait on the OTHER's started-event only finish if
    they genuinely run concurrently."""
    ev_a, ev_b = threading.Event(), threading.Event()

    def a():
        ev_a.set()
        assert ev_b.wait(10), "b never started concurrently with a"

    def b():
        ev_b.set()
        assert ev_a.wait(10), "a never started concurrently with b"

    s = DagScheduler()
    s.add("a", a)
    s.add("b", b)
    summary = s.run(mode="concurrent", max_workers=2, node_timeout=30)
    assert summary["nodes"]["a"]["state"] == "done"
    assert summary["nodes"]["b"]["state"] == "done"


def test_spine_vs_fanout_ordering():
    """A fan-out node pinned to version 1 sees version 1 even when the spine
    has already advanced to version 2 (the workflow's df-versioning)."""
    versions = {0: "v0"}
    fanout_saw = {}
    spine2_done = threading.Event()

    def spine1():
        versions[1] = versions[0] + "+s1"

    def spine2():
        versions[2] = versions[1] + "+s2"
        spine2_done.set()

    def fan():
        spine2_done.wait(10)  # let the spine advance first if it can
        fanout_saw["df"] = versions[1]

    s = DagScheduler()
    s.add("spine1", spine1, reads=("df:0",), writes=("df:1",))
    s.add("fan", fan, reads=("df:1",))
    s.add("spine2", spine2, reads=("df:1",), writes=("df:2",))
    s.run(mode="concurrent", max_workers=3, node_timeout=30)
    assert fanout_saw["df"] == "v0+s1"
    assert versions[2] == "v0+s1+s2"


# ---------------------------------------------------------------------------
# failure semantics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["sequential", "concurrent"])
def test_continue_node_failure_does_not_kill_run(mode):
    order, rec = _order_recorder()

    def boom():
        raise RuntimeError("best-effort analyzer crashed")

    s = DagScheduler()
    s.add("geo", boom, on_error="continue")
    s.add("stats", rec("stats"))
    s.add("after_geo", rec("after_geo"), reads=("x",))
    summary = s.run(mode=mode, node_timeout=30)
    assert order.count("stats") == 1 and order.count("after_geo") == 1
    assert summary["nodes"]["geo"]["state"] == "failed-continued"


@pytest.mark.parametrize("mode", ["sequential", "concurrent"])
def test_spine_failure_aborts_with_original_exception(mode):
    order, rec = _order_recorder()

    class SpineError(RuntimeError):
        pass

    def boom():
        raise SpineError("spine block failed")

    s = DagScheduler()
    s.add("ok", rec("ok"), writes=("df:1",))
    s.add("bad", boom, reads=("df:1",), writes=("df:2",))
    s.add("down", rec("down"), reads=("df:2",))
    with pytest.raises(SpineError, match="spine block failed"):
        s.run(mode=mode, node_timeout=30)
    assert "down" not in order  # dependent never ran


def test_spine_failure_skips_pending_nodes_concurrent():
    ran, rec = _order_recorder()

    s = DagScheduler()
    s.add("bad", lambda: (_ for _ in ()).throw(ValueError("dead")), writes=("df:1",))
    s.add("dep", rec("dep"), reads=("df:1",))
    with pytest.raises(ValueError):
        s.run(mode="concurrent", node_timeout=30)
    assert ran == []
    assert all(n.state in ("failed", "skipped") for n in s._nodes)


def test_watchdog_names_stuck_node():
    hung = threading.Event()

    def stuck():
        hung.wait(20)  # far beyond the timeout

    s = DagScheduler()
    s.add("stuck_block", stuck)
    t0 = time.monotonic()
    with pytest.raises(NodeTimeout, match="stuck_block"):
        s.run(mode="concurrent", node_timeout=0.3)
    assert time.monotonic() - t0 < 10
    hung.set()  # unblock the daemon worker


# ---------------------------------------------------------------------------
# async artifact writer
# ---------------------------------------------------------------------------

def test_async_writer_keyed_wait_and_drain_reraise(tmp_path):
    w = AsyncArtifactWriter(workers=2)
    w.submit("ok", (tmp_path / "a.txt").write_text, "hello")

    def boom():
        raise IOError("disk full")

    w.submit("bad", boom)
    w.wait(["ok"])  # keyed wait: unaffected by the failing key
    assert (tmp_path / "a.txt").read_text() == "hello"
    with pytest.raises(IOError, match="disk full"):
        w.wait(["bad"])
    with pytest.raises(IOError, match="disk full"):
        w.drain()
    w._pending.clear()  # drop the failed ticket so close() can succeed
    w.close()


def test_async_writer_sync_mode_inline(tmp_path):
    w = AsyncArtifactWriter(sync=True)
    w.submit("k", (tmp_path / "s.txt").write_text, "now")
    assert (tmp_path / "s.txt").read_text() == "now"  # no drain needed
    w.drain()
    w.close()


def test_default_workers_env_override(monkeypatch):
    monkeypatch.setenv("ANOVOS_TPU_EXECUTOR_WORKERS", "5")
    assert default_workers() == 5
    monkeypatch.delenv("ANOVOS_TPU_EXECUTOR_WORKERS")
    assert default_workers() >= 2


# ---------------------------------------------------------------------------
# workflow-level satellites
# ---------------------------------------------------------------------------

def test_save_none_write_config_is_identity_even_with_reread():
    """No write config → the data returns untouched before any path logic,
    including under reread=True (the checkpoint call sites pass reread=True
    on every intermediate step)."""
    from anovos_tpu import workflow

    sentinel = object()
    assert workflow.save(sentinel, None, "anything", reread=True) is sentinel
    assert workflow.save(sentinel, {}, "anything", reread=True) is sentinel


def test_main_and_run_have_no_mutable_default_auth():
    import inspect

    from anovos_tpu import workflow

    assert inspect.signature(workflow.main).parameters["auth_key_val"].default is None
    assert inspect.signature(workflow.run).parameters["auth_key_val"].default is None
    assert workflow._auth_key(None) == "NA"
    assert workflow._auth_key({}) == "NA"
    assert workflow._auth_key({"a": "k1", "b": "k2"}) == "k2"


def test_block_times_thread_safe_accumulation():
    """Block walls accumulate in the obs MetricsRegistry; ``block_times()``
    reads them."""
    from anovos_tpu import workflow
    from anovos_tpu.obs import get_metrics

    get_metrics().reset()
    start = time.monotonic()
    threads = [
        threading.Thread(target=workflow._log_block_time, args=("label", start))
        for _ in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    bt = workflow.block_times()
    assert len(bt) == 1  # all 8 accumulated onto one label
    assert bt["label"] >= 0.0


# ---------------------------------------------------------------------------
# golden comparison: sequential vs concurrent artifacts, income-demo config
# ---------------------------------------------------------------------------

def _synthesize_income(n=6000):
    from anovos_tpu.data_ingest.synthetic import synthesize

    return synthesize(n)


def _demo_cfg(pq: str) -> dict:
    src = {
        "read_dataset": {"file_path": pq, "file_type": "parquet"},
        "delete_column": ["logfnl", "empty", "dt_1", "dt_2"],
        "rename_column": {
            "list_of_cols": ["marital-status", "education-num"],
            "list_of_newcols": ["marital_status", "education_num"],
        },
    }
    return {
        "input_dataset": dict(src),
        "anovos_basic_report": {"basic_report": False},
        "stats_generator": {
            "metric": ["global_summary", "measures_of_counts", "measures_of_cardinality",
                       "measures_of_centralTendency"],
            "metric_args": {"list_of_cols": "all", "drop_cols": ["ifa"]},
        },
        "quality_checker": {
            "duplicate_detection": {"list_of_cols": "all", "drop_cols": ["ifa"], "treatment": True},
            "nullColumns_detection": {
                "list_of_cols": "all", "drop_cols": ["ifa", "income"], "treatment": True,
                "treatment_method": "MMM", "treatment_configs": {"method_type": "median"},
            },
        },
        "association_evaluator": {
            "IV_calculation": {"list_of_cols": "all", "drop_cols": "ifa",
                               "label_col": "income", "event_label": ">50K"},
        },
        "drift_detector": {
            "drift_statistics": {
                "configs": {"list_of_cols": "all", "drop_cols": ["ifa", "income"],
                            "method_type": "PSI", "threshold": 0.1},
                "source_dataset": dict(src),
            },
        },
        "report_preprocessing": {
            "master_path": "report_stats",
            "charts_to_objects": {"list_of_cols": "all", "drop_cols": "ifa",
                                  "label_col": "income", "event_label": ">50K",
                                  "bin_size": 10, "drift_detector": True},
        },
        "report_generation": {"master_path": "report_stats", "id_col": "ifa",
                              "label_col": "income", "final_report_path": "report_stats"},
        "write_intermediate": {"file_path": "intermediate_data", "file_type": "csv",
                               "file_configs": {"mode": "overwrite", "header": True}},
        "write_main": {"file_path": "output", "file_type": "parquet",
                       "file_configs": {"mode": "overwrite"}},
    }


def _tree_hashes(root: str) -> dict:
    out = {}
    for dirpath, dirs, files in os.walk(root):
        # the obs/ subtree (run manifest, trace) intentionally records the
        # executor mode and wall-clock timings — it is the run's telemetry,
        # not a pipeline artifact, so it is exempt from byte-parity
        dirs[:] = [d for d in dirs if d != "obs"]
        for f in files:
            p = os.path.join(dirpath, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha1(fh.read()).hexdigest()
    return out


_RUNNER = """
import json, logging, os, sys, warnings
import jax
jax.config.update("jax_platforms", "cpu")
logging.disable(logging.INFO)
warnings.filterwarnings("ignore")
from anovos_tpu import workflow
with open(sys.argv[1]) as f:
    cfg = json.load(f)
os.chdir(sys.argv[2])
workflow.main(cfg, "local")
s = workflow.LAST_RUN_SUMMARY
with open(sys.argv[3], "w") as f:
    json.dump({"mode": s.get("mode"), "critical_path": s.get("critical_path", []),
               "serial_s": s.get("serial_s"), "wall_s": s.get("wall_s")}, f)
"""


def test_executor_modes_produce_identical_artifacts(tmp_path):
    """The income-demo pipeline once per executor mode: every artifact —
    stats CSVs, chart JSONs, intermediate checkpoints, drift model, final
    parquet, the HTML report — must be byte-identical.

    Each mode runs in a SUBPROCESS on a single-device CPU runtime — the
    single-device shape keeps this gate about scheduler ordering alone
    (no lanes, no placement re-lays); the multi-device parity + overlap
    gate lives in tests/test_multidev_executor.py.  The subprocess
    watchdog (ANOVOS_TPU_NODE_TIMEOUT) plus the hard timeout turn a
    scheduler deadlock into a fast, named failure instead of eating the
    tier-1 budget."""
    import json
    import subprocess
    import sys

    pq = tmp_path / "parquet"
    pq.mkdir()
    _synthesize_income().to_parquet(pq / "part-0.parquet")
    cfg_path = tmp_path / "cfg.json"
    cfg_path.write_text(json.dumps(_demo_cfg(str(pq))))
    runner = tmp_path / "runner.py"
    runner.write_text(_RUNNER)

    outs, summaries = {}, {}
    for mode in ("sequential", "concurrent"):
        d = tmp_path / mode
        d.mkdir()
        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "",  # single device: no collective rendezvous
            "ANOVOS_TPU_EXECUTOR": mode,
            "ANOVOS_TPU_NODE_TIMEOUT": "300",
            "PYTHONPATH": repo_root + os.pathsep + os.environ.get("PYTHONPATH", ""),
        }
        summary_path = tmp_path / f"summary_{mode}.json"
        r = subprocess.run(
            [sys.executable, str(runner), str(cfg_path), str(d), str(summary_path)],
            capture_output=True, text=True, timeout=600, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        assert r.returncode == 0, f"{mode} run failed:\n{r.stderr[-3000:]}"
        outs[mode] = _tree_hashes(str(d))
        summaries[mode] = json.loads(summary_path.read_text())

    assert outs["sequential"], "sequential run produced no artifacts"
    assert set(outs["sequential"]) == set(outs["concurrent"]), (
        "artifact sets differ between executors: "
        f"only-seq={sorted(set(outs['sequential']) - set(outs['concurrent']))[:5]} "
        f"only-conc={sorted(set(outs['concurrent']) - set(outs['sequential']))[:5]}"
    )
    mismatched = [k for k, h in outs["sequential"].items() if outs["concurrent"][k] != h]
    assert not mismatched, f"artifacts differ between executors: {mismatched[:10]}"

    # observability contract: both summaries carry the critical path fields,
    # and the concurrent subprocess really ran concurrent (single device)
    for mode, s in summaries.items():
        assert s["mode"] == mode
        assert s["critical_path"], f"{mode} summary missing critical path"
        # report waits on the analyzers it reads: it is on the tail of
        # the dependency chain in both modes
        assert s["critical_path"][-1] == "report_generation"

    # obs run manifest: each mode wrote one, recording its own executor
    # mode and the SAME executed node set (the manifest is telemetry and is
    # exempt from byte-parity, but its structure must agree)
    manifests = {}
    for mode in ("sequential", "concurrent"):
        mp = tmp_path / mode / "report_stats" / "obs" / "run_manifest.json"
        assert mp.exists(), f"{mode} run wrote no run_manifest.json"
        manifests[mode] = json.loads(mp.read_text())
        assert manifests[mode]["executor"]["mode"] == mode
    assert (set(manifests["sequential"]["scheduler"]["nodes"])
            == set(manifests["concurrent"]["scheduler"]["nodes"]))
    assert manifests["sequential"]["config_hash"] == manifests["concurrent"]["config_hash"]
