"""anovos_tpu.cache — content-addressed incremental recompute.

Tier-1 acceptance contract (ISSUE 5):

* a fully-cached re-run executes ZERO analytic nodes (every scheduler
  node restores) and produces an artifact tree BYTE-IDENTICAL to an
  uncached run (golden tree-hash, ``obs/`` telemetry excluded);
* editing one config block re-executes only that block's downstream
  cone;
* a run killed mid-flight resumes from the journal/store frontier and
  completes with the same golden tree-hash;
* ``tools/cache_gc.py --max-bytes`` evicts LRU and exits 0/1 correctly.

The pipeline runs use a small synthetic dataset (the income parquet is
not present in every container) — the cache mechanics are dataset-
agnostic.
"""

import copy
import hashlib
import json
import os
import pathlib
import threading

import numpy as np
import pandas as pd
import pytest

from anovos_tpu.cache import (
    CacheStore,
    NodeCachePolicy,
    RunJournal,
    canonical,
    capture,
    committed_fingerprints,
    dataset_fingerprint,
    digest,
    env_fingerprint,
    node_fingerprint,
    read_journal,
)


# ------------------------------------------------------------ fixtures ----
@pytest.fixture(scope="module")
def mini_data(tmp_path_factory):
    """A small synthetic table written ONCE (dataset fingerprints are
    stat-based, so the file must not be rewritten between runs)."""
    d = tmp_path_factory.mktemp("mini_data")
    rng = np.random.default_rng(7)
    pd.DataFrame({
        "age": rng.normal(40, 9, 1500).round(1),
        "fnlwgt": rng.normal(2e5, 4e4, 1500).round(0),
        "workclass": rng.choice(["private", "gov", "self"], 1500),
        "income": rng.choice(["<=50K", ">50K"], 1500),
    }).to_parquet(os.path.join(str(d), "part-0.parquet"), index=False)
    return str(d)


def mini_config(data_dir: str) -> dict:
    return {
        "input_dataset": {"read_dataset": {"file_path": data_dir,
                                           "file_type": "parquet"}},
        "stats_generator": {
            "metric": ["global_summary", "measures_of_counts",
                       "measures_of_cardinality"],
            "metric_args": {"list_of_cols": "all", "drop_cols": []},
        },
        "quality_checker": {
            "duplicate_detection": {"list_of_cols": "all", "drop_cols": [],
                                    "treatment": True},
            "IDness_detection": {"list_of_cols": "all", "drop_cols": [],
                                 "treatment": True, "treatment_threshold": 0.9},
        },
        "drift_detector": {"drift_statistics": {
            "configs": {"list_of_cols": "all", "drop_cols": [],
                        "method_type": "PSI", "threshold": 0.1},
            "source_dataset": {"read_dataset": {"file_path": data_dir,
                                                "file_type": "parquet"}},
        }},
        "report_preprocessing": {"master_path": "report_stats"},
        "write_main": {"file_path": "output", "file_type": "parquet",
                       "file_configs": {"mode": "overwrite"}},
    }


def tree_hash(root) -> str:
    """sha256 over (relpath, bytes) of every artifact file; obs/ telemetry
    (manifest, journal, trace — run-varying by design) is excluded."""
    h = hashlib.sha256()
    root = pathlib.Path(root)
    for p in sorted(root.rglob("*")):
        if p.is_file() and "obs" not in p.parts:
            h.update(str(p.relative_to(root)).encode())
            h.update(p.read_bytes())
    return h.hexdigest()


def run_main(cfg, workdir, monkeypatch, cache_dir=None, resume=False):
    from anovos_tpu import workflow
    from anovos_tpu.obs import load_manifest

    if cache_dir is None:
        monkeypatch.delenv("ANOVOS_TPU_CACHE", raising=False)
    else:
        monkeypatch.setenv("ANOVOS_TPU_CACHE", str(cache_dir))
    monkeypatch.setenv("ANOVOS_TPU_EXECUTOR", "sequential")
    monkeypatch.chdir(workdir)
    workflow.main(copy.deepcopy(cfg), "local", resume=resume)
    return load_manifest(workflow.LAST_MANIFEST_PATH)


# ------------------------------------------------------- fingerprints ----
def test_canonical_drops_none_recursively():
    assert canonical({"a": 1, "b": None}) == canonical({"a": 1})
    assert canonical({"a": {"x": None, "y": [1, None]}}) == \
        canonical({"a": {"y": [1, None]}})  # None dropped in dicts only
    assert canonical({"b": 2, "a": 1}) == canonical({"a": 1, "b": 2})


def test_digest_has_unambiguous_part_boundaries():
    assert digest("ab", "c") != digest("a", "bc")
    assert digest("x") == digest("x")


def test_dataset_fingerprint_tracks_file_state(tmp_path):
    d = tmp_path / "ds"
    d.mkdir()
    (d / "a.csv").write_text("x,y\n1,2\n")
    spec = {"read_dataset": {"file_path": str(d), "file_type": "csv"}}
    fp1 = dataset_fingerprint(spec)
    assert fp1 == dataset_fingerprint(spec)  # stable while untouched
    (d / "a.csv").write_text("x,y\n1,3\n")
    assert dataset_fingerprint(spec) != fp1  # size/mtime change invalidates
    assert dataset_fingerprint(None) == dataset_fingerprint({})


def test_env_fingerprint_sensitive_to_audited_knobs(monkeypatch):
    base = env_fingerprint()
    monkeypatch.setenv("ANOVOS_SHAPE_BUCKETS", "0")
    assert env_fingerprint() != base
    monkeypatch.delenv("ANOVOS_SHAPE_BUCKETS")
    # a NON-audited (pure perf) knob must NOT invalidate
    monkeypatch.setenv("ANOVOS_TPU_EXECUTOR_WORKERS", "7")
    assert env_fingerprint() == base


def test_node_fingerprint_folds_slice_writes_and_deps():
    a = node_fingerprint("base", "n", {"k": 1}, ("w",), ("dep1",))
    assert a == node_fingerprint("base", "n", {"k": 1}, ("w",), ("dep1",))
    assert a != node_fingerprint("base", "n", {"k": 2}, ("w",), ("dep1",))
    assert a != node_fingerprint("base", "n", {"k": 1}, ("w2",), ("dep1",))
    assert a != node_fingerprint("base", "n", {"k": 1}, ("w",), ("dep2",))
    assert a != node_fingerprint("base2", "n", {"k": 1}, ("w",), ("dep1",))


# ------------------------------------------------------ compile cache ----
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compile_cache_env_var_means_no_directory_set_in_code(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: JAX has read it; init_runtime must
    not touch jax_compilation_cache_dir at all."""
    import jax

    from anovos_tpu.shared.runtime import init_runtime

    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", "/sentinel/not/ours")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    try:
        init_runtime()
        assert jax.config.jax_compilation_cache_dir == "/sentinel/not/ours"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(monkeypatch, tmp_path):
    import jax

    from anovos_tpu.shared import runtime

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)  # not resolved from cwd
    before = jax.config.jax_compilation_cache_dir
    try:
        runtime.init_runtime()
        assert jax.config.jax_compilation_cache_dir == os.path.join(_CHECKOUT, ".jax_cache")
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.02
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert runtime.DEFAULT_COMPILE_CACHE_DIR == os.path.join(_CHECKOUT, ".jax_cache")


_CACHE_PROBE = """
import json, sys
import jax, jax.numpy as jnp
hits = []
jax.monitoring.register_event_listener(
    lambda name, **kw: hits.append(name) if name.endswith("/cache_hits") else None)
sys.path.insert(0, sys.argv[1])
from anovos_tpu.shared.runtime import init_runtime
init_runtime()
f = jax.jit(lambda a: jnp.cumsum(jnp.sort(a * float(sys.argv[2]), axis=0), axis=0).sum())
f(jnp.ones((4096, 8))).block_until_ready()
print(json.dumps({"dir": jax.config.jax_compilation_cache_dir, "hits": len(hits)}))
"""


def _probe(cwd, salt, env_dir=None, extra_env=None):
    import json
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    env.update(extra_env or {})
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE, _CHECKOUT, salt], cwd=cwd,
                         env=env, capture_output=True, text=True, timeout=300, check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_compile_cache_same_directory_from_two_cwds(tmp_path):
    """Unset: two processes started in different working directories share
    <checkout>/.jax_cache, and the second compiles nothing the first cached."""
    import random

    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    salt = repr(random.random())  # a constant no earlier run has compiled
    first = _probe(str(tmp_path / "a"), salt)
    second = _probe(str(tmp_path / "b"), salt)
    assert first["dir"] == second["dir"] == os.path.join(_CHECKOUT, ".jax_cache")
    # helper programs (jnp.ones) may already be cached; the salted one is new
    assert second["hits"] > first["hits"]
    assert not list((tmp_path / "a").iterdir()) and not list((tmp_path / "b").iterdir())


def test_compile_cache_placed_by_env_var_gets_the_entries(tmp_path):
    placed = tmp_path / "placed"
    out = _probe(str(tmp_path), "3.5", env_dir=str(placed))
    assert out["dir"] == str(placed) and out["hits"] == 0
    assert any(placed.iterdir())
    assert _probe(str(tmp_path), "3.5", env_dir=str(placed))["hits"] >= 1


@pytest.mark.parametrize("stamped", [False, True])
def test_size_limited_compile_cache_takes_an_entry_without_a_stamp(tmp_path, stamped):
    """With a size limit JAX reads an ``-atime`` file beside every entry before
    each write.  An entry a process without the limit left has none: without
    init_runtime's stamp every write fails and the second process finds nothing."""
    placed = tmp_path / "placed"
    placed.mkdir()
    (placed / "jit_left_by_a_process_without_the_limit-0f0f-cache").write_bytes(b"x" * 64)
    if stamped:
        (placed / "jit_left_by_a_process_without_the_limit-0f0f-atime").write_bytes(b"\x01" * 8)
    limit = {"JAX_COMPILATION_CACHE_MAX_SIZE": str(1 << 28)}
    assert _probe(str(tmp_path), "4.25", env_dir=str(placed), extra_env=limit)["hits"] == 0
    assert _probe(str(tmp_path), "4.25", env_dir=str(placed), extra_env=limit)["hits"] >= 1
    stamp = (placed / "jit_left_by_a_process_without_the_limit-0f0f-atime").read_bytes()
    assert len(stamp) == 8 and (stamp == b"\x01" * 8) == stamped  # one that is there is left alone


# -------------------------------------------------------------- store ----
def test_store_commit_lookup_restore_roundtrip(tmp_path):
    store = CacheStore(str(tmp_path / "store"))
    base = tmp_path / "base1"
    (base / "sub").mkdir(parents=True)
    (base / "a.csv").write_bytes(b"alpha")
    (base / "sub" / "b.json").write_bytes(b'{"x":1}')
    man = store.commit("f" * 64, "node/x",
                       [str(base / "a.csv"), str(base / "sub" / "b.json")],
                       base_dir=str(base))
    assert man["node"] == "node/x" and len(man["files"]) == 2
    assert all(e["portable"] for e in man["files"])

    got = store.lookup("f" * 64)
    assert got is not None and got["files"] == man["files"]
    assert store.lookup("0" * 64) is None

    dest = tmp_path / "base2"
    dest.mkdir()
    n = store.restore(got, base_dir=str(dest))
    assert n == 2
    assert (dest / "a.csv").read_bytes() == b"alpha"
    assert (dest / "sub" / "b.json").read_bytes() == b'{"x":1}'


def test_store_lookup_misses_on_evicted_objects(tmp_path):
    """A manifest whose object was swept is a MISS, never a broken restore."""
    store = CacheStore(str(tmp_path / "store"))
    f = tmp_path / "x.txt"
    f.write_bytes(b"content")
    man = store.commit("a" * 64, "n", [str(f)], base_dir=str(tmp_path))
    os.remove(store._obj_path(man["files"][0]["sha256"]))
    assert store.lookup("a" * 64) is None


def test_store_gc_lru_eviction_and_exit_accounting(tmp_path):
    store = CacheStore(str(tmp_path / "store"))
    base = tmp_path / "b"
    base.mkdir()
    fps = []
    for i in range(3):
        f = base / f"f{i}.bin"
        f.write_bytes(bytes([i]) * 4096)
        fp = f"{i}" * 64
        store.commit(fp, f"n{i}", [str(f)], base_dir=str(base))
        fps.append(fp)
        # stagger the LRU clock deterministically
        os.utime(store._manifest_path(fp), (1000 + i, 1000 + i))
    total = store.total_bytes()
    assert total > 8192
    stats = store.gc(total - 4096)  # must evict at least the oldest
    assert stats["fits"] and not stats["dry_run"]
    assert fps[0] in stats["evicted_nodes"]
    assert store.lookup(fps[0]) is None
    assert store.lookup(fps[2]) is not None  # most recent survives
    # dry run never deletes
    stats2 = store.gc(0, dry_run=True)
    assert stats2["dry_run"] and store.lookup(fps[2]) is not None


def test_store_payload_dir_roundtrip(tmp_path):
    store = CacheStore(str(tmp_path / "store"))

    def write_payload(d):
        with open(os.path.join(d, "blob.bin"), "wb") as f:
            f.write(b"payload")

    man = store.commit("b" * 64, "n", [], payload_write=write_payload)
    assert man["payload"]
    got = store.lookup("b" * 64)
    assert got is not None
    with open(os.path.join(store.payload_dir("b" * 64), "blob.bin"), "rb") as f:
        assert f.read() == b"payload"


# ------------------------------------------------------------ journal ----
def test_journal_roundtrip_and_committed_frontier(tmp_path):
    path = str(tmp_path / "obs" / "run_journal.jsonl")
    j = RunJournal(path)
    j.append("run_begin", run_id="r1")
    j.append("node_begin", node="a", fp="fa")
    j.append("node_commit", node="a", fp="fa")
    j.append("node_restored", node="b", fp="fb")
    j.append("node_failed", node="c", fp="fc")
    with open(path, "a") as f:
        f.write('{"torn": ')  # simulated kill mid-line
    records = read_journal(path)
    assert [r["event"] for r in records][:2] == ["run_begin", "node_begin"]
    assert committed_fingerprints(records) == ["fa", "fb"]  # failed c absent


def test_journal_rides_async_writer(tmp_path):
    from anovos_tpu.shared.artifact_store import AsyncArtifactWriter

    writer = AsyncArtifactWriter(workers=2)
    j = RunJournal(str(tmp_path / "j.jsonl"), writer)
    for i in range(20):
        j.append("node_commit", node=f"n{i}", fp=f"f{i}")
    writer.close()  # drain barrier
    records = read_journal(str(tmp_path / "j.jsonl"))
    assert len(records) == 20  # no interleaved/torn lines
    assert {r["node"] for r in records} == {f"n{i}" for i in range(20)}


# ------------------------------------------------------------ capture ----
def test_open_hook_records_write_opens_on_recording_thread(tmp_path):
    capture.install_open_hook()
    try:
        rec = capture.Recorder()
        with capture.recording(rec):
            with open(tmp_path / "w.txt", "w") as f:
                f.write("x")
            with open(tmp_path / "w.txt") as f:  # read mode: not recorded
                f.read()
        with open(tmp_path / "outside.txt", "w") as f:  # no recorder active
            f.write("y")
        assert rec.paths == {str(tmp_path / "w.txt")}
        # a second thread without a recorder records nothing
        def other():
            with open(tmp_path / "thread.txt", "w") as f:
                f.write("z")
        t = threading.Thread(target=other)
        t.start(); t.join()
        assert str(tmp_path / "thread.txt") not in rec.paths
    finally:
        capture.uninstall_open_hook()
    import builtins
    assert builtins.open.__name__ == "open"  # hook fully removed


def test_open_hook_survives_foreign_repatch(tmp_path):
    """Another tool wrapping builtins.open ON TOP of the hook (coverage,
    pyfakefs) captures _hooked_open as its downstream; uninstalling must
    keep that delegation chain alive, not null its target."""
    import builtins

    capture.install_open_hook()
    hooked = builtins.open
    foreign_calls = []

    def foreign_wrapper(*a, **k):
        foreign_calls.append(a)
        return hooked(*a, **k)

    builtins.open = foreign_wrapper
    try:
        capture.uninstall_open_hook()  # cannot remove: foreign wrapper on top
        with open(tmp_path / "still_works.txt", "w") as f:  # must NOT raise
            f.write("x")
        assert foreign_calls  # the chain routed through the foreign wrapper
        # a re-install against the live foreign chain must not cycle either
        capture.install_open_hook()
        with open(tmp_path / "still_works2.txt", "w") as f:
            f.write("y")
        capture.uninstall_open_hook()
    finally:
        builtins.open = capture._ORIG_OPEN  # the true original
    assert builtins.open.__name__ == "open"


def test_async_writer_propagates_recorder_to_writer_threads(tmp_path):
    from anovos_tpu.shared.artifact_store import AsyncArtifactWriter

    capture.install_open_hook()
    try:
        writer = AsyncArtifactWriter(workers=2)
        rec = capture.Recorder()

        def write_it(p):
            with open(p, "w") as f:
                f.write("queued")

        with capture.recording(rec):
            writer.submit("stats:x", write_it, str(tmp_path / "q.csv"))
        writer.close()
        assert rec.keys == {"stats:x"}          # commit barrier knows the key
        assert str(tmp_path / "q.csv") in rec.paths  # write attributed
    finally:
        capture.uninstall_open_hook()


# -------------------------------------------------- scheduler-level ----
def test_scheduler_hit_restores_and_skips_body(tmp_path, monkeypatch):
    from anovos_tpu.parallel.scheduler import DagScheduler

    store = CacheStore(str(tmp_path / "store"))
    capture.install_open_hook()
    try:
        runs = []

        def build(workdir):
            monkeypatch.chdir(workdir)
            s = DagScheduler("t", cache_store=store)

            def a():
                runs.append("a")
                with open("a.txt", "w") as f:
                    f.write("A")

            def b():
                runs.append("b")
                with open("b.txt", "w") as f:
                    f.write("B")

            s.add("a", a, writes=("r:a",),
                  cache=NodeCachePolicy(key_material=digest("base", "a")))
            s.add("b", b, reads=("r:a",),
                  cache=NodeCachePolicy(key_material=digest("base", "b")))
            s.add("plain", lambda: runs.append("plain"))  # no policy: always runs
            return s

        d1 = tmp_path / "w1"; d1.mkdir()
        sm1 = build(d1).run(mode="sequential")
        assert sm1["cache"] == {"enabled": True, "hits": 0, "misses": 2,
                                "restore_s": 0.0, "uncacheable": 1}
        d2 = tmp_path / "w2"; d2.mkdir()
        runs.clear()
        sm2 = build(d2).run(mode="sequential")
        assert runs == ["plain"]  # both cacheable nodes skipped
        assert sm2["cache"]["hits"] == 2 and sm2["cache"]["misses"] == 0
        assert (d2 / "a.txt").read_text() == "A"
        assert (d2 / "b.txt").read_text() == "B"
        assert sm2["nodes"]["a"]["cached"] and sm2["nodes"]["b"]["cached"]
        assert sm2["nodes"]["a"]["state"] == "done"
    finally:
        capture.uninstall_open_hook()


def test_scheduler_dep_fingerprint_invalidation(tmp_path, monkeypatch):
    """Changing an upstream node's key re-executes the downstream reader
    even though the reader's own key material is unchanged (RAW folding)."""
    from anovos_tpu.parallel.scheduler import DagScheduler

    store = CacheStore(str(tmp_path / "store"))
    capture.install_open_hook()
    try:
        runs = []

        def build(workdir, a_key):
            monkeypatch.chdir(workdir)
            s = DagScheduler("t", cache_store=store)
            s.add("a", lambda: runs.append("a"), writes=("r:a",),
                  cache=NodeCachePolicy(key_material=a_key))
            s.add("b", lambda: runs.append("b"), reads=("r:a",),
                  cache=NodeCachePolicy(key_material=digest("b")))
            return s

        d1 = tmp_path / "w1"; d1.mkdir()
        build(d1, digest("a-v1")).run(mode="sequential")
        runs.clear()
        d2 = tmp_path / "w2"; d2.mkdir()
        build(d2, digest("a-v2")).run(mode="sequential")
        assert runs == ["a", "b"]  # b invalidated transitively
    finally:
        capture.uninstall_open_hook()


# ------------------------------------------------ workflow end-to-end ----
def test_fully_cached_rerun_byte_identical_and_incremental_cone(
        mini_data, tmp_path, monkeypatch):
    cfg = mini_config(mini_data)
    cache_dir = tmp_path / "store"

    # golden: an UNCACHED run
    d0 = tmp_path / "uncached"; d0.mkdir()
    run_main(cfg, d0, monkeypatch, cache_dir=None)
    golden = tree_hash(d0)

    # populate
    d1 = tmp_path / "populate"; d1.mkdir()
    m1 = run_main(cfg, d1, monkeypatch, cache_dir=cache_dir)
    assert m1["cache"]["hits"] == 0 and m1["cache"]["misses"] == 6
    assert tree_hash(d1) == golden  # capture changes nothing

    # fully-cached re-run: ZERO analytic nodes execute.  The per-run gc
    # knob accepts the suffixed form the CLI documents (a generous cap:
    # nothing evicted, run must not warn/fail)
    monkeypatch.setenv("ANOVOS_TPU_CACHE_MAX_BYTES", "1G")
    d2 = tmp_path / "cached"; d2.mkdir()
    m2 = run_main(cfg, d2, monkeypatch, cache_dir=cache_dir)
    monkeypatch.delenv("ANOVOS_TPU_CACHE_MAX_BYTES")
    assert m2["cache"]["misses"] == 0
    assert m2["cache"]["hits"] == 6
    assert all(n["cached"] for n in m2["scheduler"]["nodes"].values())
    assert tree_hash(d2) == golden  # restored tree is byte-identical
    # stable_view contract under caching: two same-cache-state re-runs of
    # one config compare equal (PR-2's stability contract, now with the
    # cache section / cached flags / cache_ families stripped), and the
    # write-volume counters — whose VALUES shift when nodes restore
    # instead of execute — are reduced to series names only
    from anovos_tpu.obs import stable_view
    d2b = tmp_path / "cached2"; d2b.mkdir()
    m2b = run_main(cfg, d2b, monkeypatch, cache_dir=cache_dir)
    assert stable_view(m2) == stable_view(m2b)
    sv = stable_view(m2)
    assert sv["metrics"]["rows_ingested_total"]["series"]  # values kept
    for name in ("bytes_written_total", "artifact_writes_total"):
        if name in sv["metrics"]:
            assert isinstance(sv["metrics"][name]["series"], list)  # names only
    # cache observability: metrics + journal + manifest all record the hits
    assert m2["metrics"]["cache_hits_total"]["series"]
    journal = read_journal(str(d2 / "report_stats" / "obs" / "run_journal.jsonl"))
    assert sum(1 for r in journal if r["event"] == "node_restored") == 6
    assert journal[0]["event"] == "run_begin" and journal[-1]["event"] == "run_end"

    # incremental: edit ONE block -> only its downstream cone re-executes
    cfg_inc = copy.deepcopy(cfg)
    cfg_inc["quality_checker"]["IDness_detection"]["treatment_threshold"] = 0.8
    d3 = tmp_path / "incr"; d3.mkdir()
    m3 = run_main(cfg_inc, d3, monkeypatch, cache_dir=cache_dir)
    state = {k: v["cached"] for k, v in m3["scheduler"]["nodes"].items()}
    # stats fan-outs read df:0 — untouched by the quality edit: still hits
    assert state["stats_generator/global_summary"]
    assert state["stats_generator/measures_of_counts"]
    assert state["stats_generator/measures_of_cardinality"]
    # the edited block and everything downstream of its df versions re-ran
    assert not state["quality_checker/duplicate_detection"]
    assert not state["quality_checker/IDness_detection"]
    assert not state["drift_detector/drift_statistics"]
    # and the incremental artifacts equal a from-scratch run of cfg_inc
    d4 = tmp_path / "incr_scratch"; d4.mkdir()
    run_main(cfg_inc, d4, monkeypatch, cache_dir=None)
    assert tree_hash(d3) == tree_hash(d4)


def test_killed_run_resumes_to_same_golden_tree(mini_data, tmp_path, monkeypatch):
    """Fault injection: the drift node dies mid-run (after stats + quality
    committed); --resume completes the run with the pre-crash frontier
    restored and the final tree byte-identical to a clean run."""
    import anovos_tpu.drift_stability.drift_detector as dd

    cfg = mini_config(mini_data)
    cache_dir = tmp_path / "store"

    d0 = tmp_path / "golden"; d0.mkdir()
    run_main(cfg, d0, monkeypatch, cache_dir=None)
    golden = tree_hash(d0)

    d1 = tmp_path / "crashed"; d1.mkdir()
    orig = dd.statistics
    monkeypatch.setattr(dd, "statistics",
                        lambda *a, **k: (_ for _ in ()).throw(
                            KeyboardInterrupt("simulated kill")))
    with pytest.raises(KeyboardInterrupt):
        run_main(cfg, d1, monkeypatch, cache_dir=cache_dir)
    monkeypatch.setattr(dd, "statistics", orig)

    # the write-ahead journal recorded the committed frontier
    journal_path = d1 / "report_stats" / "obs" / "run_journal.jsonl"
    frontier = committed_fingerprints(read_journal(str(journal_path)))
    assert len(frontier) == 5  # stats x3 + quality x2 landed before the kill
    failed = [r for r in read_journal(str(journal_path))
              if r["event"] == "node_failed"]
    assert failed and failed[0]["node"] == "drift_detector/drift_statistics"

    # resume IN THE SAME output dir: frontier restores, drift executes
    m2 = run_main(cfg, d1, monkeypatch, cache_dir=cache_dir, resume=True)
    assert m2["cache"]["resumed_from"] == 5
    assert m2["cache"]["hits"] == 5 and m2["cache"]["misses"] == 1
    state = {k: v["cached"] for k, v in m2["scheduler"]["nodes"].items()}
    assert not state["drift_detector/drift_statistics"]
    assert tree_hash(d1) == golden


# --------------------------------------------------------- gc CLI ----
def test_cache_gc_cli_exit_codes_and_eviction(tmp_path, capsys):
    import tools.cache_gc as gc_cli

    root = tmp_path / "store"
    store = CacheStore(str(root))
    base = tmp_path / "b"; base.mkdir()
    for i in range(2):
        f = base / f"f{i}.bin"
        f.write_bytes(bytes([i]) * 8192)
        store.commit(f"{i}" * 64, f"n{i}", [str(f)], base_dir=str(base))
        os.utime(store._manifest_path(f"{i}" * 64), (1000 + i, 1000 + i))

    # generous cap: nothing evicted, exit 0
    assert gc_cli.main(["--root", str(root), "--max-bytes", "1G"]) == 0
    # lookup() TOUCHES the LRU clock: n0 is now the most recently used,
    # so the tight sweep below must evict n1 instead
    assert store.lookup("0" * 64) is not None

    # tight cap: LRU eviction brings it under, exit 0
    assert gc_cli.main(["--root", str(root), "--max-bytes", "9000", "--json"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "1" * 64 in out["evicted_nodes"]
    assert store.lookup("1" * 64) is None and store.lookup("0" * 64) is not None

    # missing root: exit 1
    assert gc_cli.main(["--root", str(tmp_path / "nope"), "--max-bytes", "1"]) == 1
    # suffix parsing
    assert gc_cli.parse_bytes("500M") == 500 * (1 << 20)
    assert gc_cli.parse_bytes("2k") == 2048


def test_uses_preexisting_gates_cacheability():
    from anovos_tpu.workflow import _uses_preexisting

    assert _uses_preexisting({"pre_existing_model": True})
    assert _uses_preexisting({"a": {"configs": {"pre_existing_source": True}}})
    assert _uses_preexisting({"l": [{"pre_existing_model": 1}]})
    assert not _uses_preexisting({"pre_existing_model": False})
    assert not _uses_preexisting({"threshold": 0.1, "nested": {"x": [1, 2]}})
