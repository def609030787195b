"""The pass's phase tree (``obs.tracing``: ``run_pass``, ``phase``) and its
two sinks: the run manifest's ``phases`` and ``clock``, read from a
2,000-row ``stats`` pass, and the profiler annotations of phase and node
spans, read from a real profiler session's ``.xplane.pb`` on the CPU."""

import gc
import glob
import json
import os
import subprocess
import sys
import threading
import time

import jax
import pytest
import yaml

from anovos_tpu import obs, workflow
from anovos_tpu.data_ingest import synthetic
from anovos_tpu.obs import devprof, tracing
from anovos_tpu.parallel.scheduler import DagScheduler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:  # the benchmark's generator, driver and readers, for the ``full`` rehearsal
    sys.path.insert(0, ROOT)

ROWS = 2000
TOP = ["config", "reset", "ingest", "register", "dag", "artifact:drain", "manifest", "close",
       "write_main", "release"]
UNDER_INGEST = ["io:read_dataset", "ingest/decode", "ingest/assemble", "ingest/encode",
                "ingest/h2d", "ingest/delete_column", "ingest/rename_column",
                "ingest/recast_column"]


def _h2d_bytes_so_far() -> float:
    return sum(v for _, v in obs.get_metrics().counter("transfer_h2d_bytes_total").items())


@pytest.fixture(scope="module")
def work(tmp_path_factory):
    return tmp_path_factory.mktemp("phase_spans")


@pytest.fixture(scope="module")
def config_path(work):
    """``input_dataset`` with three column edits + ``stats_generator`` +
    ``write_stats`` on the seeded income schema at 2,000 rows."""
    data = synthetic.generate(ROWS, 7, dest=work / "income_dataset")
    cfg = {
        "input_dataset": {
            "read_dataset": {"file_path": os.path.join(data, "parquet"), "file_type": "parquet"},
            "delete_column": ["logfnl", "empty", "dt_2"],
            "rename_column": {"list_of_cols": ["marital-status", "education-num"],
                              "list_of_newcols": ["marital_status", "education_num"]},
            "recast_column": {"list_of_cols": ["age", "education_num"],
                              "list_of_dtypes": ["float", "float"]},
        },
        "stats_generator": {
            "metric": ["global_summary", "measures_of_counts", "measures_of_centralTendency"],
            "metric_args": {"list_of_cols": "all", "drop_cols": ["ifa"]},
        },
        "write_stats": {"file_path": str(work / "stats"), "file_type": "parquet",
                        "file_configs": {"mode": "overwrite"}},
    }
    path = work / "pipeline.yaml"
    path.write_text(yaml.safe_dump(cfg, sort_keys=False))
    return str(path)


@pytest.fixture(scope="module")
def stats_pass(work, config_path):
    """One ``workflow.run``: its manifest, its spans, and the host-to-device
    bytes booked at the moment the scheduler starts."""
    seen = {}
    real_run = DagScheduler.run

    def run_and_note(self, *a, **k):
        seen["h2d_before_dag"] = _h2d_bytes_so_far()
        return real_run(self, *a, **k)

    cwd = os.getcwd()
    os.chdir(work)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(DagScheduler, "run", run_and_note)
        try:
            workflow.run(config_path, "local")
        finally:
            os.chdir(cwd)
    seen["manifest"] = obs.load_manifest(workflow.LAST_MANIFEST_PATH)
    seen["spans"] = obs.get_tracer().snapshot()
    return seen


def _rows(stats_pass, name, parent=None):
    return [r for r in stats_pass["manifest"]["phases"]
            if r["name"] == name and (parent is None or r["parent"] == parent)]


def _one(stats_pass, name, parent="run"):
    (row,) = _rows(stats_pass, name, parent)
    return row


def test_manifest_holds_every_phase_of_the_pass(stats_pass):
    man = stats_pass["manifest"]
    assert man["manifest_version"] == obs.MANIFEST_VERSION == 2
    names = {r["name"] for r in man["phases"]}
    assert names >= {"run", *TOP, *UNDER_INGEST}
    assert "input_dataset/ETL" not in {sp.name for sp in stats_pass["spans"]}
    for r in man["phases"]:
        assert set(r) == {"name", "parent", "start_s", "end_s", "thread", "counts", "usage"}
        assert 0.0 <= r["start_s"] <= r["end_s"]
        if r["name"].startswith("compile/"):
            continue  # a program's stage: on the thread of whichever row waited for it (the fresh process, below)
        # the scheduler's nodes are rows too, each on its worker's thread under ``dag``,
        # and so is what a node opens: the describe under the node that computes it
        in_node = r["parent"] == "dag" or r["parent"] in man["scheduler"]["nodes"] or r["parent"] == "describe"
        assert (r["thread"] == "MainThread") != in_node, r
    nodes = {r["name"]: r for r in man["phases"] if r["parent"] == "dag"}
    assert set(nodes) == set(man["scheduler"]["nodes"])
    # one describe a pass: of the two nodes that need it one computes it and one reads the memo
    # (global_summary needs none), on the suite's 8-device mesh as on one device
    assert sorted(r["counts"].get("describe_computed", -1) for r in nodes.values()) == [-1, 0, 1]
    assert [r["name"] for r in man["phases"] if r["parent"] == "run" and r["name"] in TOP] == TOP
    assert len(man["clock"]["run_id"]) == 12
    assert {sp.run_id for sp in stats_pass["spans"]} == {man["clock"]["run_id"]}


def test_every_parent_resolves_and_children_lie_inside_their_parents(stats_pass):
    rows = stats_pass["manifest"]["phases"]
    roots = [r for r in rows if r["parent"] is None]
    assert [r["name"] for r in roots] == ["run"] and roots[0]["start_s"] == 0.0
    for r in rows:
        if r["parent"] is None:
            continue
        holders = [p for p in rows if p["name"] == r["parent"] and p is not r
                   and p["start_s"] <= r["start_s"] and r["end_s"] <= p["end_s"]]
        assert holders, f"{r['name']} at {r['start_s']} lies in no {r['parent']!r}"
    assert {r["parent"] for r in _rows(stats_pass, "ingest/decode")} == {"io:read_dataset"}
    assert _one(stats_pass, "io:read_dataset", "ingest")
    assert {r["parent"] for r in _rows(stats_pass, "artifact:drain")} == {"run", "close"}


def test_children_of_run_tile_it(stats_pass):
    """No stretch of the pass longer than 10 ms outside every child of the
    root, and the named parts add up to the root within 2 %."""
    (run,) = [r for r in stats_pass["manifest"]["phases"] if r["parent"] is None]
    kids = sorted((r for r in stats_pass["manifest"]["phases"] if r["parent"] == "run"),
                  key=lambda r: r["start_s"])
    edges = [run["start_s"]] + [t for k in kids for t in (k["start_s"], k["end_s"])] + [run["end_s"]]
    gaps = [b - a for a, b in zip(edges[0::2], edges[1::2])]
    assert all(-1e-6 <= g < 0.010 for g in gaps), gaps
    dag = _one(stats_pass, "dag")
    parts = sum(_one(stats_pass, n)["end_s"] - _one(stats_pass, n)["start_s"]
                for n in ("config", "ingest", "register", "dag")) + run["end_s"] - dag["end_s"]
    assert parts == pytest.approx(run["end_s"] - run["start_s"], rel=0.02)


def test_ingest_spans_carry_their_counts(stats_pass, work):
    ingest = _one(stats_pass, "ingest")
    inside = [r for r in stats_pass["manifest"]["phases"]
              if ingest["start_s"] <= r["start_s"] and r["end_s"] <= ingest["end_s"]]
    decode = [r for r in inside if r["name"] == "ingest/decode"]
    files = glob.glob(str(work / "income_dataset" / "parquet" / "*.parquet"))
    assert len(decode) == len(files) > 1
    assert sum(r["counts"]["rows"] for r in decode) == ROWS
    assert sum(r["counts"]["bytes"] for r in decode) == sum(os.path.getsize(f) for f in files)
    encode = [r for r in inside if r["name"] == "ingest/encode"]
    strings = [c for c, t in synthetic.load_income(ROWS, 7, work / "income_dataset").dtypes.items()
               if str(t) in ("object", "str", "string")]
    assert len(encode) == len(strings) >= 10
    assert all(r["counts"]["rows"] == ROWS and r["counts"]["hashed"] == 1
               and r["counts"]["native_sort"] == 1 for r in encode)
    assert all(r["parent"] == "io:read_dataset" for r in encode)
    assert max(r["counts"]["distinct"] for r in encode) == ROWS  # the id column
    # every byte handed to device_put before the scheduler starts is on an ingest/h2d span
    h2d = [r for r in inside if r["name"] == "ingest/h2d"]
    assert sum(r["counts"]["bytes"] for r in h2d) == stats_pass["h2d_before_dag"] > 0
    assert all(0.0 < r["counts"]["enqueue_s"] <= r["end_s"] - r["start_s"] + 1e-6 for r in h2d)


def test_one_encode_span_a_string_column_and_the_looped_one_says_so(tmp_path):
    """A parquet table read inside a pass: one ``ingest/encode`` row per
    string column inside ``ingest``, each with ``rows``, ``distinct``,
    ``hashed`` and ``native_sort`` (and ``hash_s``, ``sort_s`` where Arrow
    did both); a binary column decodes to ``bytes`` objects, which the hash
    cannot take (``str(b"x")`` is ``"b'x'"``), and reports both 0."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from anovos_tpu.data_ingest.data_ingest import read_dataset

    n = 60
    os.makedirs(tmp_path / "t")
    for part in range(2):
        pq.write_table(pa.table({
            "word": pa.array([["x", "y", None][i % 3] for i in range(n)], pa.string()),
            "key": pa.array([f"k{part}_{i}" for i in range(n)], pa.string()),
            "raw": pa.array([[b"x", b"y", None, b"z"][i % 4] for i in range(n)], pa.binary()),
            "value": pa.array([float(i) for i in range(n)], pa.float64()),
        }), tmp_path / "t" / f"part-{part:05d}.parquet")
    tr = obs.get_tracer()
    with tr.run_pass():
        with tr.phase("ingest"):
            tbl = read_dataset(str(tmp_path / "t"), "parquet")
    rows = tr.phases()
    (ingest,) = [r for r in rows if r["name"] == "ingest"]
    # by column, not by start: the string columns of a long frame are encoded side by side
    # (no two of these have as many distinct values: word 2, key 2n, raw 3)
    encode = sorted((r for r in rows if r["name"] == "ingest/encode"),
                    key=lambda r: [2, 2 * n, 3].index(r["counts"]["distinct"]))
    timings = [{k: r["counts"].pop(k) for k in ("hash_s", "sort_s") if k in r["counts"]} for r in encode]
    assert [r["counts"] for r in encode] == [
        {"rows": 2 * n, "distinct": 2, "hashed": 1, "native_sort": 1},
        {"rows": 2 * n, "distinct": 2 * n, "hashed": 1, "native_sort": 1},
        {"rows": 2 * n, "distinct": 3, "hashed": 0, "native_sort": 0},
    ]
    # where Arrow hashed the rows and ordered the vocab, where the span's time went; the loop is one
    assert [sorted(t) for t in timings] == [["hash_s", "sort_s"], ["hash_s", "sort_s"], []]
    assert all(0.0 <= t["hash_s"] and 0.0 <= t["sort_s"]
               and t["hash_s"] + t["sort_s"] <= r["end_s"] - r["start_s"] + 1e-6
               for t, r in zip(timings, encode) if t)
    assert all(r["parent"] == "io:read_dataset" and ingest["start_s"] <= r["start_s"]
               and r["end_s"] <= ingest["end_s"] for r in encode)
    assert list(tbl["raw"].vocab) == ["b'x'", "b'y'", "b'z'"] and tbl["value"].kind == "num"
    (h2d,) = [r for r in rows if r["name"] == "ingest/h2d"]  # a short table: one row, after the encodes
    assert h2d["parent"] == "io:read_dataset" and max(r["end_s"] for r in encode) <= h2d["start_s"]
    assert h2d["counts"]["bytes"] == sum(a.nbytes for c in tbl.columns.values() for a in c.device_arrays())


def test_clock_places_the_scheduler_nodes_among_the_phases(stats_pass):
    man = stats_pass["manifest"]
    origin, dag = man["clock"]["scheduler_origin_s"], _one(stats_pass, "dag")
    nodes = man["scheduler"]["nodes"]
    assert len(nodes) >= 3 and min(n["start_s"] for n in nodes.values()) == 0.0
    for n in nodes.values():  # the summary rounds to 1e-4
        assert dag["start_s"] - 2e-4 <= origin + n["start_s"] <= origin + n["end_s"] <= dag["end_s"] + 2e-4
    node_spans = [sp for sp in stats_pass["spans"] if sp.cat == "node"]
    assert {sp.name for sp in node_spans} == set(nodes)
    assert {sp.args["parent"] for sp in node_spans} == {"dag"}


def test_stable_view_drops_phases_and_clock(stats_pass):
    view = obs.stable_view(stats_pass["manifest"])
    assert "phases" not in view and "clock" not in view
    assert view["manifest_version"] == 2 and "scheduler" in view


def test_only_the_first_pass_of_a_process_tells_what_came_before_it(stats_pass, config_path, work):
    """The suite's worker may have run passes before this module's: a pass
    says which of its process it was, and the first alone has rows."""
    process = stats_pass["manifest"]["process"]
    assert set(process) == ({"pass_index", "rows"} if process["pass_index"] == 0 else {"pass_index"})
    assert "process" not in obs.stable_view(stats_pass["manifest"])
    cwd = os.getcwd()
    os.chdir(work)
    try:
        workflow.run(config_path, "local")
    finally:
        os.chdir(cwd)
    later = obs.load_manifest(workflow.LAST_MANIFEST_PATH)["process"]
    assert set(later) == {"pass_index"} and later["pass_index"] > process["pass_index"]


def test_main_called_directly_is_a_pass_of_its_own(stats_pass, config_path, work):
    with open(config_path) as f:
        cfg = yaml.safe_load(f)
    cwd = os.getcwd()
    os.chdir(work)
    try:
        workflow.main(cfg, "local")
    finally:
        os.chdir(cwd)
    man = obs.load_manifest(workflow.LAST_MANIFEST_PATH)
    top = [r["name"] for r in man["phases"] if r["parent"] == "run"]
    assert top == [n for n in TOP if n != "config"]
    assert man["clock"]["run_id"] != stats_pass["manifest"]["clock"]["run_id"]
    assert not obs.get_tracer().in_pass()


def test_a_finished_pass_keeps_no_device_array(stats_pass, config_path, work):
    """The registrar and its nodes' closures hold each other; the pass lets
    go of its last table itself, so its device memory does not wait for the
    cyclic collector (which the next pass's ingest no longer wakes: it
    makes no Python object per row)."""
    cwd = os.getcwd()
    os.chdir(work)
    gc.collect()
    gc.disable()
    try:
        before = {id(a) for a in jax.live_arrays()}
        workflow.run(config_path, "local")
        left = [a for a in jax.live_arrays() if id(a) not in before and a.size >= ROWS]
    finally:
        gc.enable()
        os.chdir(cwd)
    assert not left, [(a.shape, a.dtype) for a in left]


# -------------------------------------------------------- a fresh process ----
_TWO_PASSES = """
import os, shutil, sys
from anovos_tpu import workflow
os.chdir(sys.argv[2])
for i in range(2):
    workflow.run(sys.argv[1], "local")
    shutil.copy(workflow.LAST_MANIFEST_PATH, os.path.join(sys.argv[2], f"manifest_{i}.json"))
"""
COMPILE_STAGES = {"compile/trace", "compile/lower", "compile/load", "compile/build"}


@pytest.fixture(scope="module")
def fresh_process(config_path, work):
    """Two passes of the 2,000-row ``stats`` pipeline in a process of their
    own, with a compile cache of its own and nothing in it: their manifests."""
    out = work / "fresh_process"
    os.makedirs(out)
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "JAX_COMPILATION_CACHE_DIR": str(out / "jax_cache")}
    done = subprocess.run([sys.executable, "-c", _TWO_PASSES, config_path, str(out)], env=env,
                          text=True, capture_output=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    return [obs.load_manifest(str(out / f"manifest_{i}.json")) for i in range(2)]


def _holder(rows, r):
    """The row of the tree that ``r`` names as its parent: of that name, around it in time."""
    found = [p for p in rows if p["name"] == r["parent"] and p is not r
             and p["start_s"] <= r["start_s"] and r["end_s"] <= p["end_s"]]
    return found[0] if found else None


def test_a_first_pass_names_the_stages_of_its_programs_under_the_rows_that_waited(fresh_process):
    first = fresh_process[0]
    rows = first["phases"]
    stages = [r for r in rows if r["name"] in COMPILE_STAGES]
    assert {r["name"] for r in stages} == {"compile/trace", "compile/lower", "compile/build"}  # an empty cache
    assert not [r for r in rows if r["name"].startswith("compile/") and r["name"] not in COMPILE_STAGES]
    for r in stages:
        assert set(r) == {"name", "parent", "start_s", "end_s", "thread", "counts", "usage"}
        assert r["counts"] == {} and r["usage"] == {}  # which program: the census; a finished span used nothing
        holder = _holder(rows, r)
        assert holder is not None, f"{r['name']} at {r['start_s']} lies in no {r['parent']!r}"
        assert holder["thread"] == r["thread"] and not holder["name"].startswith("compile/")
    census = first["compile_census"]
    assert len([r for r in stages if r["name"] == "compile/build"]) == census["built_programs"] \
        == census["compiles_total"] > 5
    assert census["cache_hits"] == 0 and census["cache_requests"] == census["compiles_total"]
    for stage in ("trace", "lower", "build"):  # the rows carry the seconds the census sums
        seconds = sum(r["end_s"] - r["start_s"] for r in stages if r["name"] == "compile/" + stage)
        assert seconds == pytest.approx(census[f"{stage}_seconds_total"], abs=0.01 + 1e-5 * len(stages))
    # most of them under the node that computed the describe, and the node's name is in the census's table
    nodes = {n for p in census["programs"] for n in p["nodes"]}
    assert nodes and nodes <= set(first["scheduler"]["nodes"])
    assert any(r["parent"].startswith("describe/") for r in stages)


def test_a_first_pass_holds_the_runtimes_start(fresh_process):
    (init,) = [r for r in fresh_process[0]["phases"] if r["name"] == "runtime/init"]
    assert init["counts"] == {"devices": 8, "cache_entries": 0}  # no size limit: nothing is listed
    assert init["thread"] == "MainThread" and _holder(fresh_process[0]["phases"], init) is not None
    assert not [r for r in fresh_process[1]["phases"] if r["name"] == "runtime/init"]


def test_a_first_pass_tells_what_the_process_did_before_it(fresh_process):
    process = fresh_process[0]["process"]
    assert set(process) == {"pass_index", "rows"} and process["pass_index"] == 0
    rows = process["rows"]
    assert [r["name"] for r in rows] == ["process/interpreter", "process/import", "process/caller"]
    assert all(set(r) == {"name", "start_s", "end_s"} for r in rows)
    assert all(a["end_s"] == b["start_s"] for a, b in zip(rows, rows[1:])) and rows[-1]["end_s"] == 0.0
    interpreter, imported, caller = (r["end_s"] - r["start_s"] for r in rows)
    assert imported > 0.5  # jax, pandas and the package
    assert 0.0 <= caller < imported and 0.0 <= interpreter < 5.0  # a script of four lines; clock ticks of 10 ms


def test_the_second_pass_of_the_process_is_no_first_pass(fresh_process):
    second = fresh_process[1]
    assert second["process"] == {"pass_index": 1}
    assert second["clock"]["run_id"] != fresh_process[0]["clock"]["run_id"]
    rows, census = second["phases"], second["compile_census"]
    # on the CPU the ``stats`` race of PERF.md section 7 item 14 may compile; whatever
    # does lies inside a node that the census names too
    nodes = {n for p in census["programs"] for n in p["nodes"]}
    for r in (r for r in rows if r["name"] in COMPILE_STAGES):
        assert any(p["name"] in nodes and p["thread"] == r["thread"]
                   and p["start_s"] <= r["start_s"] and r["end_s"] <= p["end_s"] for p in rows), r
    assert len([r for r in rows if r["name"] in ("compile/load", "compile/build")]) == census["compiles_total"]
    assert census["compiles_total"] < fresh_process[0]["compile_census"]["compiles_total"]


# ---------------------------------------------------------------- tracer ----
def test_a_phase_is_a_phase_only_under_a_phase():
    tr = obs.Tracer(buffer=100)
    with tr.phase("io:read_dataset", cat="io"):  # outside any pass
        pass
    with tr.run_pass() as root:
        assert tr.in_pass() and root.name == "run" and tr.current() is root
        with tr.phase("ingest") as sp:
            sp.add(rows=2)
            sp.add(rows=3)
            with tr.phase("ingest/decode", cat="io"):
                pass
        with tr.span("a_node", cat="node") as node:  # a scheduler node of the pass: a row
            with tr.phase("place/d2d", cat="place", bytes=8):  # and so is a phase inside it
                assert tr.enclosing("node") is node and tr.enclosing("op") is None
            with tr.span("ops.table_describe", cat="op"):  # obs.timed around a library call: no row,
                with tr.phase("describe", cat="op"):  # and a phase inside it is one, under the node
                    with tr.phase("describe/numeric", cat="op", rows=4):
                        pass
        seen = []
        worker = threading.Thread(target=lambda: seen.append(tr.in_pass()))  # another thread's stack
        worker.start()
        worker.join()
        assert seen == [False]
    with tr.span("a_node", cat="node"):  # no pass open: an ordinary span, and what it holds too
        with tr.phase("ingest/encode", cat="io"):
            pass
    cats = {sp.name: sp.cat for sp in tr.snapshot()}
    assert cats == {"ingest/decode": "phase", "ingest": "phase", "ingest/encode": "io", "place/d2d": "phase",
                    "a_node": "node", "run": "phase", "ops.table_describe": "op", "describe": "phase",
                    "describe/numeric": "phase"}  # run_pass cleared what came before
    rows = tr.phases()
    assert [(r["name"], r["parent"]) for r in rows] == [
        ("run", None), ("ingest", "run"), ("ingest/decode", "ingest"), ("a_node", "run"),
        ("place/d2d", "a_node"), ("describe", "a_node"), ("describe/numeric", "describe")]
    assert rows[4]["counts"] == {"bytes": 8}
    assert rows[1]["counts"] == {"rows": 5}
    assert not tr.in_pass() and tr.current() is None
    assert tr.seconds_at(time.monotonic()) == pytest.approx(rows[0]["end_s"], abs=0.05)
    assert obs.Tracer(buffer=10).phases() == [] and obs.Tracer(buffer=10).seconds_at(0.0) is None


def test_phases_survive_a_drained_ring():
    """Rotation drains the ring mid-pass; the manifest's rows do not go with it."""
    tr = obs.Tracer(buffer=100)
    with tr.run_pass():
        with tr.phase("ingest"):
            pass
        assert [sp.name for sp in tr.drain()] == ["ingest"]
    assert [r["name"] for r in tr.phases()] == ["run", "ingest"]


def test_a_finished_span_is_filed_as_a_phase_would_have_been():
    """What a listener learns after the fact: a row under the innermost row
    open on the thread, starting no earlier than it; an ordinary span anywhere else."""
    tr = obs.Tracer(buffer=100)
    tr.finished("compile/build", 0.002, cat="compile")  # outside any pass
    assert [(sp.name, sp.cat, sp.args) for sp in tr.snapshot()] == [("compile/build", "compile", {})]
    with tr.run_pass():
        with tr.span("a_node", cat="node"):
            time.sleep(0.003)
            with tr.span("ops.table_describe", cat="op"):  # no row: passed over
                tr.finished("compile/trace", 0.001, cat="compile")
            tr.finished("compile/build", 3600.0, cat="compile")  # longer than its row has been open
        seen = []
        worker = threading.Thread(target=lambda: (tr.finished("compile/load", 0.001, cat="compile"),
                                                  seen.extend(tr.snapshot())))
        worker.start()  # a thread with no row open: not of the tree
        worker.join()
    rows = {r["name"]: r for r in tr.phases()}
    assert sorted(rows) == ["a_node", "compile/build", "compile/trace", "run"]
    node, trace, build = rows["a_node"], rows["compile/trace"], rows["compile/build"]
    assert trace["parent"] == build["parent"] == "a_node" and trace["thread"] == node["thread"]
    assert trace["end_s"] - trace["start_s"] == pytest.approx(0.001, abs=2e-6)
    assert node["start_s"] == build["start_s"] <= trace["start_s"] and build["end_s"] <= node["end_s"]
    assert trace["counts"] == build["counts"] == trace["usage"] == build["usage"] == {}
    assert [(sp.cat, sp.args) for sp in seen if sp.name == "compile/load"] == [("compile", {})]
    cats = {sp.name: sp.cat for sp in tr.snapshot()}
    assert cats["compile/trace"] == cats["compile/build"] == "phase" and cats["compile/load"] == "compile"


def test_a_perf_counter_reading_lands_on_the_passes_clock():
    tr = obs.Tracer(buffer=10)
    before = time.perf_counter()
    with tr.run_pass():
        inside = time.perf_counter()
    (root,) = tr.phases()
    assert tr.seconds_at(before, perf_counter=True) <= 0.0 <= tr.seconds_at(inside, perf_counter=True) <= root["end_s"]
    assert tr.seconds_at(time.perf_counter(), perf_counter=True) == pytest.approx(
        tr.seconds_at(time.monotonic()), abs=0.01)


def test_transfer_outside_a_node_is_booked_on_the_open_h2d_span(monkeypatch):
    monkeypatch.delenv("ANOVOS_TPU_DEVPROF", raising=False)
    tr = obs.get_tracer()
    with tr.span("ingest/h2d", cat="io") as sp:
        devprof.record_transfer("h2d", 4096, 0.25, "test")
        devprof.record_transfer("h2d", 1024, 0.5, "test")
    assert sp.attrs["bytes"] == 5120 and sp.attrs["enqueue_s"] == pytest.approx(0.75)
    with tr.span("ingest/encode", cat="io") as other:  # not the transfer's span
        devprof.record_transfer("h2d", 4096, 0.25, "test")
    assert "bytes" not in other.attrs
    with devprof.node_bracket("a_node", drain=False) as frame, tr.span("ingest/h2d", cat="io") as sp:
        devprof.record_transfer("h2d", 4096, 0.25, "test")  # inside a node: the node's frame
    assert frame.h2d_bytes == 4096 and "bytes" not in sp.attrs


# ----------------------------------------------------------- annotations ----
class _Note:
    opened, closed = [], []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _Note.opened.append(self.name)

    def __exit__(self, *exc):
        _Note.closed.append(self.name)


@pytest.fixture
def notes():
    _Note.opened, _Note.closed = [], []
    yield _Note
    tracing.annotate_with(None)


def _two_nodes():
    s = DagScheduler(name="notes")
    s.add("producer", lambda: None, writes=("r",))
    s.add("consumer", lambda: None, reads=("r",))
    return s


@pytest.mark.parametrize("devprof_switch", ["1", "0"])
def test_phase_and_node_spans_are_annotated_while_a_session_is_on(monkeypatch, notes, devprof_switch):
    """The annotation is the tracer's: ANOVOS_TPU_DEVPROF=0 does not take the
    node names out of a trace."""
    monkeypatch.setenv("ANOVOS_TPU_DEVPROF", devprof_switch)
    tr = obs.get_tracer()
    tracing.annotate_with(notes)
    with tr.run_pass():
        with tr.phase("dag"):
            _two_nodes().run(mode="concurrent", max_workers=2, node_timeout=30)
        with tr.span("an_op", cat="op"), tr.span("a_write", cat="artifact"):
            pass
    assert sorted(notes.opened) == ["consumer", "dag", "producer", "run"]
    assert sorted(notes.closed) == sorted(notes.opened)


def test_nothing_is_annotated_with_the_session_off(notes):
    tr = obs.get_tracer()
    with tr.run_pass(), tr.phase("dag"):
        _two_nodes().run(mode="sequential")
    assert notes.opened == [] and tracing._ANNOTATION is None


def test_anovos_profile_runs_without_the_python_tracer_and_names_the_pass(
        stats_pass, config_path, work, monkeypatch):
    """A real profiler session on the CPU, devprof off: the node and phase
    names are events of the trace's host plane, placed as the manifest says."""
    import jax
    from jax.profiler import ProfileData

    profile_dir = str(work / "profile")
    monkeypatch.setenv("ANOVOS_PROFILE", profile_dir)
    monkeypatch.setenv("ANOVOS_TPU_DEVPROF", "0")
    options = []
    real_start = jax.profiler.start_trace

    def start_and_note(log_dir, *a, **k):
        options.append(k["profiler_options"])
        return real_start(log_dir, *a, **k)

    monkeypatch.setattr(jax.profiler, "start_trace", start_and_note)
    monkeypatch.chdir(work)
    workflow.run(config_path, "local")
    assert [o.python_tracer_level for o in options] == [0]
    assert options[0].host_tracer_level == jax.profiler.ProfileOptions().host_tracer_level
    assert tracing._ANNOTATION is None

    man = obs.load_manifest(workflow.LAST_MANIFEST_PATH)
    top = [r["name"] for r in man["phases"] if r["parent"] == "run"]
    assert top == ["config", "profiler:start"] + TOP[1:] + ["profiler:export"]
    (path,) = glob.glob(os.path.join(profile_dir, "plugins", "profile", "*", "*.xplane.pb"))
    wanted = set(man["scheduler"]["nodes"]) | {r["name"] for r in man["phases"]}
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        events.setdefault(e.name, []).append((e.start_ns * 1e-9, e.duration_ns * 1e-9))
    # all but what is open across the session's edges
    assert set(events) == wanted - {"run", "config", "profiler:start", "profiler:export"}
    # one clock: the trace's spans are the manifest's, shifted by the session's start
    shift = events["ingest"][0][0] - next(r for r in man["phases"] if r["name"] == "ingest")["start_s"]
    for name in ("reset", "register", "dag", "manifest", "close"):
        row = next(r for r in man["phases"] if r["name"] == name and r["parent"] == "run")
        (start, dur), = events[name]
        assert start - shift == pytest.approx(row["start_s"], abs=2e-3)
        assert dur == pytest.approx(row["end_s"] - row["start_s"], abs=2e-3)


# ------------------------------------------------- what a row consumed ----
USAGE = {"cpu_s", "proc_cpu_s", "minflt", "majflt", "nivcsw"}


def _spin(seconds):
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def test_every_row_carries_cpu_s_and_only_the_top_rows_the_process_counts(stats_pass):
    rows = stats_pass["manifest"]["phases"]
    for r in rows:
        top = r["parent"] in (None, "run")
        if r["name"].startswith("compile/"):  # a finished span: it was told its seconds, and measured nothing
            assert r["usage"] == {}, r
            continue
        assert set(r["usage"]) == (USAGE if top else {"cpu_s"}), r
        assert not USAGE & set(r["counts"]), r  # kept apart from the counts of the work
        # a thread's own clock: never more than the row's wall (and a tick of either clock)
        assert 0.0 <= r["usage"]["cpu_s"] <= r["end_s"] - r["start_s"] + 0.005, r
        if top:
            u = r["usage"]
            assert u["proc_cpu_s"] >= 0.0 and min(u["minflt"], u["majflt"], u["nivcsw"]) >= 0
            assert all(isinstance(u[k], int) for k in ("minflt", "majflt", "nivcsw"))
    tops = {r["name"] for r in rows if r["parent"] == "run"}
    assert tops == set(TOP)
    # the process's seconds hold the main thread's: ingest decodes and encodes on it
    ingest = _one(stats_pass, "ingest")["usage"]
    assert ingest["proc_cpu_s"] >= ingest["cpu_s"] - 0.005 and ingest["cpu_s"] > 0
    # the dag phase's thread only waits for its workers; the process works meanwhile
    dag = _one(stats_pass, "dag")
    assert dag["usage"]["cpu_s"] < 0.5 * (dag["end_s"] - dag["start_s"])
    assert dag["usage"]["proc_cpu_s"] > dag["usage"]["cpu_s"]
    run = next(r for r in rows if r["parent"] is None)
    assert run["usage"]["proc_cpu_s"] >= ingest["proc_cpu_s"] + dag["usage"]["proc_cpu_s"] - 0.005


def test_a_row_that_spins_reads_its_wall_and_one_that_sleeps_reads_nothing():
    tr = obs.Tracer(buffer=100)
    with tr.run_pass():
        with tr.phase("spin"):
            _spin(0.2)
        with tr.phase("sleep"):
            time.sleep(0.2)
        with tr.span("a_node", cat="node"):
            with tr.phase("stage/spin"):
                _spin(0.1)
    rows = {r["name"]: r for r in tr.phases()}
    wall = {n: r["end_s"] - r["start_s"] for n, r in rows.items()}
    assert rows["spin"]["usage"]["cpu_s"] >= 0.3 * wall["spin"] >= 0.06  # loose: the sandbox's cores are shared
    assert rows["sleep"]["usage"]["cpu_s"] <= 0.05 and wall["sleep"] >= 0.2
    assert rows["stage/spin"]["usage"]["cpu_s"] >= 0.3 * wall["stage/spin"]
    assert rows["a_node"]["usage"]["cpu_s"] >= rows["stage/spin"]["usage"]["cpu_s"]
    # the process's clock on the direct children of the root, and on no row below them
    assert rows["spin"]["usage"]["proc_cpu_s"] >= 0.3 * wall["spin"]
    assert rows["sleep"]["usage"]["proc_cpu_s"] <= 0.1
    assert set(rows["run"]["usage"]) == set(rows["spin"]["usage"]) == USAGE
    assert set(rows["a_node"]["usage"]) == set(rows["stage/spin"]["usage"]) == {"cpu_s"}
    assert rows["run"]["usage"]["cpu_s"] >= rows["spin"]["usage"]["cpu_s"] + rows["stage/spin"]["usage"]["cpu_s"]


def test_a_unit_handed_to_a_pool_thread_records_that_threads_cpu():
    """``Tracer.under``: the row lies under the submitting thread's row and
    its ``cpu_s`` is the pool thread's, while the submitter only waits."""
    tr = obs.Tracer(buffer=100)
    with tr.run_pass():
        with tr.phase("ingest"):
            spans = tr.open_spans()

            def unit():
                with tr.under(spans), tr.phase("ingest/decode", cat="io"):
                    _spin(0.15)

            worker = threading.Thread(target=unit, name="anovos-host_9")
            worker.start()
            worker.join()
    rows = {r["name"]: r for r in tr.phases()}
    unit_row, ingest = rows["ingest/decode"], rows["ingest"]
    assert unit_row["parent"] == "ingest" and unit_row["thread"] == "anovos-host_9"
    assert unit_row["usage"]["cpu_s"] >= 0.3 * (unit_row["end_s"] - unit_row["start_s"]) >= 0.045
    assert ingest["usage"]["cpu_s"] <= 0.05  # the caller's thread slept in join()
    assert ingest["usage"]["proc_cpu_s"] >= unit_row["usage"]["cpu_s"] - 0.02  # the process did the work
    assert set(unit_row["usage"]) == {"cpu_s"}


def test_spans_outside_the_tree_carry_none_of_it():
    """An op span, a writer thread's span, a span outside any pass and a
    phase outside any pass: as they were."""
    tr = obs.Tracer(buffer=100)
    with tr.span("before", cat="node"), tr.phase("ingest/encode", cat="io"):
        pass
    kept = list(tr.snapshot())
    with tr.run_pass():
        with tr.span("ops.something", cat="op"):
            pass
        def write():
            with tr.span("write:stats", cat="artifact"), tr.phase("write/parquet", cat="io"):
                pass

        writer = threading.Thread(target=write)
        writer.start()
        writer.join()
    spans = {sp.name: sp for sp in kept + tr.snapshot()}
    for name in ("before", "ingest/encode", "ops.something", "write:stats", "write/parquet"):
        assert not USAGE & set(spans[name].args), name
    assert USAGE <= set(spans["run"].args)
    assert [r["name"] for r in tr.phases()] == ["run"]


def test_a_node_that_finds_the_describe_in_flight_waits_under_describe_wait(monkeypatch):
    """Three scheduler nodes ask for the same table's describe at once: one
    computes it, and each of the others shows its wait for the table's lock
    as a row ``describe/wait`` under itself."""
    import numpy as np

    from anovos_tpu.ops import describe
    from anovos_tpu.shared.table import Table

    tbl = Table.from_numpy({"x": np.arange(64, dtype=np.float32), "y": np.ones(64, np.float32)})
    real = describe._table_describe
    started = threading.Event()

    def slow(*a, **k):
        started.set()
        time.sleep(0.3)  # long enough for the others to reach the lock
        return real(*a, **k)

    monkeypatch.setattr(describe, "_table_describe", slow)
    tr = obs.get_tracer()

    def first():
        describe.table_describe(tbl, ["x", "y"], [])

    def later():
        started.wait(10)
        describe.table_describe(tbl, ["x", "y"], [])

    s = DagScheduler(name="describe_wait")
    s.add("computes", first)
    s.add("waits_a", later)
    s.add("waits_b", later)
    with tr.run_pass(), tr.phase("dag"):
        s.run(mode="concurrent", max_workers=3, node_timeout=60)
    rows = tr.phases()
    nodes = {r["name"]: r for r in rows if r["parent"] == "dag"}
    assert {n: r["counts"]["describe_computed"] for n, r in nodes.items()} == {
        "computes": 1, "waits_a": 0, "waits_b": 0}
    waits = [r for r in rows if r["name"] == "describe/wait"]
    assert sorted(r["parent"] for r in waits) == ["waits_a", "waits_b"]
    for r in waits:
        node = nodes[r["parent"]]
        assert node["start_s"] <= r["start_s"] <= r["end_s"] <= node["end_s"] and r["thread"] == node["thread"]
        assert r["end_s"] - r["start_s"] >= 0.1 and r["usage"]["cpu_s"] <= 0.05  # a wait: wall and no work
    (computed,) = [r for r in rows if r["name"] == "describe"]
    assert computed["parent"] == "computes"
    # uncontended, no row: a fourth call reads the memo without a wait
    with tr.run_pass(), tr.span("alone", cat="node"):
        describe.table_describe(tbl, ["x", "y"], [])
    assert [r["name"] for r in tr.phases()] == ["run", "alone"]


def test_the_readers_of_one_table_version_wait_for_their_turn_under_lane_wait(stats_pass):
    """``stats_generator``'s nodes share the rendezvous lane and run one at
    a time under the version's lock (``_PipelineRun.fanout(share_lane=True)``):
    a node that found the turn taken shows the wait as a ``lane/wait`` row
    (it may still be the one that computes the describe: ``global_summary``
    takes a turn and needs none)."""
    rows = stats_pass["manifest"]["phases"]
    nodes = {r["name"]: r for r in rows if r["parent"] == "dag"}
    waits = [r for r in rows if r["name"] == "lane/wait"]
    assert {r["parent"] for r in waits} <= set(nodes)
    assert len({r["parent"] for r in waits}) == len(waits)  # at most one a node
    for r in waits:
        node = nodes[r["parent"]]
        assert node["start_s"] <= r["start_s"] <= r["end_s"] <= node["end_s"] and r["thread"] == node["thread"]
        assert r["usage"]["cpu_s"] <= 0.5 * (r["end_s"] - r["start_s"]) + 0.005  # a wait, not work
    assert not [r for r in rows if r["name"] == "describe/wait"]  # the turn is taken first


def test_ten_thousand_empty_rows_cost_microseconds_each():
    tr = obs.Tracer(buffer=100)
    n = 10_000
    with tr.run_pass():
        with tr.span("a_node", cat="node"):
            t0 = time.perf_counter()
            for _ in range(n):
                with tr.phase("stage"):
                    pass
            each = (time.perf_counter() - t0) / n
    assert each < 50e-6, f"{each * 1e6:.1f} us a row"
    assert len(tr.phases()) == n + 2


# ------------------------------ stage rows inside the long host blocks ----
# the names PERF.md section 3 gives, by the node they lie under (children first,
# then what lies under ``ts/viz`` and ``geo/cluster``)
STAGES = {
    "timeseries_analyzer/inspection": (
        {"ts/eligibility", "ts/viz", "ts/landscape", "ts/write"},
        {"ts/viz": {"ts/viz/counts", "ts/viz/num", "ts/viz/frame", "ts/viz/cat", "ts/viz/decompose",
                    "ts/viz/write"}}),
    "geospatial_controller": (
        {"geo/detect", "geo/points", "geo/stats", "geo/charts", "geo/write", "geo/cluster"},
        {"geo/cluster": {"geo/cluster/kmeans", "geo/cluster/dbscan", "geo/cluster/silhouette"}}),
    "quality_checker/invalidEntries_detection": (
        {"invalid/unique", "invalid/scan", "invalid/mask", "invalid/frame", "invalid/treat"}, {}),
    "report_generation": ({"report/read", "report/tab", "report/render", "report/write"}, {}),
}
# the other nodes of the pass's critical path, by the same rule
MORE_STAGES = {
    "timeseries_analyzer/auto_detection": {"ts/detect", "ts/write"},
    "quality_checker/duplicate_detection": {"duplicate/signature", "duplicate/verify"},
    "quality_checker/nullRows_detection": {"nullrows/count", "nullrows/frame"},
    "quality_checker/IDness_detection": {"idness/stats"},
    "quality_checker/biasedness_detection": {"biasedness/stats"},
    "quality_checker/outlier_detection": {"outlier/bounds", "outlier/flags", "outlier/treat"},
    "quality_checker/nullColumns_detection": {"nullcols/stats", "nullcols/treat"},
    "drift_detector/drift_statistics": {"drift/read", "drift/fit", "drift/union", "drift/lut", "drift/sides", "drift/model",
                                        "drift/frame"},
    "drift_detector/stability_index": {"stability/read", "stability/moments", "stability/frame"},
    "report_preprocessing/charts_to_objects": {"charts/read", "charts/num", "charts/cat", "charts/write"},
}
COLUMNS, TABS = 24, 11  # of the income table; of the report


@pytest.fixture(scope="module")
def full_pass(tmp_path_factory):
    """One pass of the benchmark's ``full`` mix at 2,000 rows: its manifest."""
    from benchmark.datasets import income
    from benchmark.drivers import pipeline

    work = tmp_path_factory.mktemp("full_stages")
    with open(os.path.join(ROOT, "benchmark", "traffic", "full.json")) as f:
        traffic = json.load(f)
    income.generate(str(work / "dataset"), 36, traffic["dataset_parts"], rows=ROWS, source_rows=ROWS // 4)
    config_path = str(work / "pipeline.yaml")
    pipeline.write_pipeline_config(os.path.join(ROOT, "benchmark", "traffic", "full.yaml"),
                                   str(work / "dataset"), config_path)
    p = pipeline.inspect(pipeline.one_pass(config_path, str(work / "pass")), traffic, "cpu")
    assert not p["bad"], p["bad"]
    return p["manifest"]


def _kids(rows, parent, node):
    """The rows named ``parent`` as their parent that lie inside ``node``."""
    return [r for r in rows if r["parent"] == parent
            and node["start_s"] - 1e-6 <= r["start_s"] and r["end_s"] <= node["end_s"] + 1e-6]


def _covered(node, kids):
    """The share of ``node`` under the union of ``kids``, as ``critical_unnamed_s`` counts it."""
    from benchmark.harness.names import load_module

    left = load_module("layer_metrics", "critical_unnamed_s").uncovered(node, kids)
    return 1.0 - left / max(node["end_s"] - node["start_s"], 1e-9)


@pytest.mark.parametrize("node_name", sorted(STAGES))
def test_the_four_long_host_blocks_have_their_stage_rows(full_pass, node_name):
    rows = full_pass["phases"]
    (node,) = [r for r in rows if r["name"] == node_name and r["parent"] == "dag"]
    children, below = STAGES[node_name]
    mine = [r for r in rows if r["parent"] == node_name]
    # a node that found another's writes still queued waits for them first, under artifact:wait
    assert {r["name"] for r in mine} - {"artifact:wait"} == children
    for r in mine:  # inside the node's interval, on the node's thread, with what a row carries
        assert node["start_s"] <= r["start_s"] <= r["end_s"] <= node["end_s"], r
        assert r["thread"] == node["thread"] and set(r["usage"]) == {"cpu_s"}, r
    assert _covered(node, mine) >= 0.8  # what the node did lies under a named stage
    for parent, names in below.items():
        for holder in (r for r in mine if r["name"] == parent):
            inner = _kids(rows, parent, holder)
            assert {r["name"] for r in inner} <= names and inner, parent
            assert _covered(holder, inner) >= 0.8
    # a span a column or a tab at the most: never one a row or a distinct value
    family = next(iter(children)).split("/")[0]
    names = [r["name"] for r in rows if r["name"].split("/")[0] == family]
    assert max(names.count(n) for n in set(names)) <= COLUMNS + TABS


def test_stage_rows_carry_their_counts(full_pass):
    rows = full_pass["phases"]
    by = {}
    for r in rows:
        by.setdefault(r["name"], []).append(r)
    uniq = by["invalid/unique"]
    assert all(r["counts"]["rows"] == 2048 and r["counts"]["fetches"] == 2 and 0 < r["counts"]["distinct"] <= ROWS
               for r in uniq)
    assert len(by["invalid/scan"]) == len(by["invalid/mask"]) >= len(uniq) > 0
    tabs = by["report/tab"]
    assert [r["counts"]["tab"] for r in tabs if "bytes" in r["counts"]] == list(
        range(sum("bytes" in r["counts"] for r in tabs)))
    assert by["report/render"][0]["counts"]["tabs"] == sum("bytes" in r["counts"] for r in tabs) >= 8
    assert by["report/write"][0]["counts"]["bytes"] >= by["report/render"][0]["counts"]["bytes"] > 0
    writes = by["ts/viz/write"] + by["ts/write"] + by["geo/write"]
    assert all(r["counts"]["files"] >= 1 and r["counts"]["bytes"] > 0 for r in writes)
    # the inspection fetches aggregates only: one calendar a column, one fused aggregate, two category tables
    assert "ts/feats" not in by and all(r["counts"]["fetches"] == 1 for r in by["ts/eligibility"] + by["ts/viz/num"])
    assert all(r["counts"]["host_rows"] == 0 for r in by["ts/eligibility"] + by["ts/viz/num"] + by["ts/viz/cat"])
    assert all(r["counts"]["combos"] > 0 for r in by["geo/cluster/silhouette"])


def test_the_critical_path_is_named_by_stage(full_pass):
    """Every node of the pass's critical path lies under stage rows for the
    most part, and the reader of what is left agrees with the tree."""
    from benchmark.harness.names import load_module

    rows, sched = full_pass["phases"], full_pass["scheduler"]
    nodes = {r["name"]: r for r in rows if r["parent"] == "dag"}
    assert sched["critical_path"] and set(sched["critical_path"]) <= set(nodes)
    known = {**{n: c for n, (c, _) in STAGES.items()}, **MORE_STAGES}
    for name in sched["critical_path"]:
        mine = {r["name"] for r in rows if r["parent"] == name}
        if name in known:
            assert mine - {"io:read_dataset", "artifact:wait", "lane/wait"} - {
                n for n in mine if n.startswith(("ingest/", "transform/", "describe", "place/", "compile/"))} <= known[name]
            assert mine, name
            # loose (the smallest nodes are milliseconds long): a node whose stages went missing reads 0
            assert _covered(nodes[name], [r for r in rows if r["parent"] == name]) >= 0.3, name
    run = {"passes": [{"wall_s": 1.0, "manifest": full_pass}]}
    unnamed = load_module("layer_metrics", "critical_unnamed_s").read(run)
    by_hand = sum((1.0 - _covered(nodes[n], [r for r in rows if r["parent"] == n]))
                  * (nodes[n]["end_s"] - nodes[n]["start_s"]) for n in sched["critical_path"])
    assert unnamed == pytest.approx(by_hand, abs=1e-6) and 0.0 <= unnamed <= sched["critical_path_s"] + 0.01
    assert load_module("layer_metrics", "dag_cpu_s").read(run) > 0
    assert load_module("layer_metrics", "ingest_cpu_s").read(run) > 0
    # a few hundred rows a pass, not thousands (and three more for every program this one was the first to run)
    assert len([r for r in rows if not r["name"].startswith("compile/")]) <= 600


# ---------------------------------------------- the write of a device table ----
def _write_in_a_pass(tbl, path):
    from anovos_tpu.data_ingest.data_ingest import write_dataset

    tr = obs.get_tracer()
    with tr.run_pass():
        with tr.phase("dag"):
            with tr.span("a_node", cat="node"):
                tbl.to_pandas()  # a node's fetch: no row of its own
        with tr.phase("write_main"):
            write_dataset(tbl, path, "parquet", {"mode": "overwrite"})
    return tr.phases()


@pytest.mark.parametrize("rows,side_by_side", [(131_072, True), (32_561, False)])
def test_a_written_tables_columns_are_rows_under_write_d2h_and_a_nodes_fetch_opens_none(
        tmp_path, monkeypatch, rows, side_by_side):
    import numpy as np

    from anovos_tpu.shared import host_pool
    from anovos_tpu.shared.table import Table

    made = host_pool.HostPool(4)
    monkeypatch.setattr(host_pool, "_POOL", made)
    big = np.arange(rows, dtype="int64") + (1 << 40)
    tbl = Table.from_numpy({"a": np.arange(rows, dtype="float32"), "b": np.arange(rows, dtype="int32"),
                            "wide": big, "c": np.linspace(0.0, 1.0, rows).astype("float32"),
                            "d": np.ones(rows, dtype="float32"), "e": np.zeros(rows, dtype="int32")})
    padded = tbl.padded_rows

    def written():
        return [sum(v for _, v in obs.get_metrics().counter(c).items())
                for c in ("rows_written_total", "bytes_written_total")]

    before = written()
    phases = _write_in_a_pass(tbl, str(tmp_path / "out"))
    made._executor.shutdown(wait=True)
    (part,) = glob.glob(str(tmp_path / "out" / "part-*.parquet"))
    assert [a - b for a, b in zip(written(), before)] == [rows, os.path.getsize(part)]
    write = [r for r in phases if r["parent"] == "write_main"]
    assert [r["name"] for r in write] == ["write/d2h", "write/parquet"]
    d2h = write[0]
    assert d2h["counts"] == {"arrays": 14, "bytes": padded * (5 * 5 + 13)}
    columns = [r for r in phases if r["name"] == "write/column"]
    assert len(columns) == 6 and all(r["parent"] == "write/d2h" for r in columns)
    assert all(set(r["counts"]) == {"arrays", "bytes", "wait_s"} and "cpu_s" in r["usage"] for r in columns)
    assert sorted(r["counts"]["arrays"] for r in columns) == [2, 2, 2, 2, 2, 4]
    assert sum(r["counts"]["bytes"] for r in columns) == d2h["counts"]["bytes"]
    assert sum(r["counts"]["arrays"] for r in columns) == d2h["counts"]["arrays"]
    for r in columns:
        assert d2h["start_s"] <= r["start_s"] <= r["end_s"] <= d2h["end_s"]
        assert 0.0 <= r["counts"]["wait_s"] <= r["end_s"] - r["start_s"] + 1e-6
    threads = {r["thread"] for r in columns}
    if side_by_side:
        assert len(threads) <= 4 and threads != {d2h["thread"]}  # the pool's threads took units
    else:
        assert threads == {d2h["thread"]}
    # the node fetched the same table and its row has no child
    assert [r["name"] for r in phases if r["parent"] == "a_node"] == []
    assert len([r for r in phases if r["name"].startswith("write/")]) == 2 + 6
