"""The pass's phase tree (``obs.tracing``: ``run_pass``, ``phase``) and its
two sinks: the run manifest's ``phases`` and ``clock``, read from a
2,000-row ``stats`` pass, and the profiler annotations of phase and node
spans, read from a real profiler session's ``.xplane.pb`` on the CPU."""

import gc
import glob
import os
import threading
import time

import jax
import pytest
import yaml

from anovos_tpu import obs, workflow
from anovos_tpu.data_ingest import synthetic
from anovos_tpu.obs import devprof, tracing
from anovos_tpu.parallel.scheduler import DagScheduler

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
        assert set(r) == {"name", "parent", "start_s", "end_s", "thread", "counts"}
        assert 0.0 <= r["start_s"] <= r["end_s"]
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
    assert len([r for r in rows if r["name"] == "ingest/h2d"]) == 4


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
