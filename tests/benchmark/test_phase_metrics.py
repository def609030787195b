"""The per-layer readers of the program's phase spans (manifest ``phases``):
each on a hand-built manifest, nothing on a manifest without ``phases`` (a
program from before the spans), ``idle_unnamed_share`` on a hand-built event
list and on the trace recorded on the chip, and all six from what a
2,000-row ``stats`` run of the driver leaves on the CPU."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.drivers import pipeline  # noqa: E402
from benchmark.harness import trace_reduce  # noqa: E402
from benchmark.harness.names import load_module  # noqa: E402

NEW = ["ingest_s", "ingest_decode_s", "ingest_encode_s", "ingest_h2d_gb", "after_dag_s",
       "idle_unnamed_share"]


def _row(name, parent, start, end, **counts):
    return {"name": name, "parent": parent, "start_s": start, "end_s": end,
            "thread": "MainThread", "counts": counts}


def _manifest(scale=1.0):
    """A pass of 10 s (times ``scale``): config 0-0.1, ingest 0.1-6 (two
    decodes 0.2-1.2 and 1.2-2.0, assemble 2.0-2.5, encodes 2.6-4.6 and
    4.8-5.0, three h2d spans of 1e9, 5e8 and 5e8 bytes, a column edit
    5.5-6.0), register 6-6.2, dag 6.2-8.2, drain 8.2-9.0, manifest 9.0-9.1,
    close 9.1-9.5 with a second drain inside, write_main 9.5-10.  A node, on
    a worker thread, encoded a table of its own 7.0-7.5: not ingest's."""
    s = scale
    rows = [
        _row("run", None, 0.0, 10 * s),
        _row("config", "run", 0.0, 0.1 * s),
        _row("ingest", "run", 0.1 * s, 6 * s),
        _row("io:read_dataset", "ingest", 0.1 * s, 5.4 * s),
        _row("ingest/decode", "io:read_dataset", 0.2 * s, 1.2 * s, bytes=100, rows=5),
        _row("ingest/decode", "io:read_dataset", 1.2 * s, 2.0 * s, bytes=80, rows=4),
        _row("ingest/assemble", "io:read_dataset", 2.0 * s, 2.5 * s),
        _row("ingest/encode", "io:read_dataset", 2.6 * s, 4.6 * s, rows=9, distinct=9),
        _row("ingest/h2d", "io:read_dataset", 4.6 * s, 4.7 * s, bytes=10 ** 9, enqueue_s=0.01),
        _row("ingest/encode", "io:read_dataset", 4.8 * s, 5.0 * s, rows=9, distinct=2),
        _row("ingest/h2d", "io:read_dataset", 5.0 * s, 5.1 * s, bytes=5 * 10 ** 8, enqueue_s=0.01),
        _row("ingest/h2d", "io:read_dataset", 5.2 * s, 5.3 * s, bytes=5 * 10 ** 8, enqueue_s=0.01),
        _row("ingest/recast_column", "ingest", 5.5 * s, 6.0 * s),
        _row("register", "run", 6.0 * s, 6.2 * s),
        _row("dag", "run", 6.2 * s, 8.2 * s),
        _row("ingest/encode", "a_node", 7.0 * s, 7.5 * s, rows=3, distinct=3),
        _row("artifact:drain", "run", 8.2 * s, 9.0 * s, pending=7),
        _row("manifest", "run", 9.0 * s, 9.1 * s),
        _row("close", "run", 9.1 * s, 9.5 * s),
        _row("artifact:drain", "close", 9.1 * s, 9.2 * s, pending=0),
        _row("write_main", "run", 9.5 * s, 10 * s),
    ]
    return {"phases": rows, "clock": {"run_id": "x", "scheduler_origin_s": 6.25 * s},
            "scheduler": {"nodes": {"a_node": {"start_s": 0.0, "end_s": 1.9 * s}}}}


def _run(scales=(1.0, 3.0, 2.0), with_phases=True):
    passes = []
    for i, s in enumerate(scales):
        man = _manifest(s)
        if not with_phases:
            del man["phases"], man["clock"]
        passes.append({"wall_s": 10 * s + 0.01, "manifest": man, "traced": False})
    return {"passes": passes, "traced": None, "trace_dir": ""}


def _read(name, run):
    return load_module("layer_metrics", name).read(run)


def test_each_reader_on_a_hand_built_manifest():
    run = _run()  # walls 10, 30, 20: the median pass is the one of scale 2
    assert _read("ingest_s", run) == pytest.approx(2 * 5.9)  # the median of 5.9, 17.7, 11.8
    assert _read("ingest_decode_s", run) == pytest.approx(2 * (1.0 + 0.8 + 0.5))
    assert _read("ingest_encode_s", run) == pytest.approx(2 * (2.0 + 0.2))  # not the node's 0.5
    assert _read("ingest_h2d_gb", run) == pytest.approx(2.0)  # bytes do not scale
    assert _read("after_dag_s", run) == pytest.approx(2 * 1.8)
    assert _read("idle_unnamed_share", run) is None  # no trace was taken
    # what the issue asks of the parts: they add up to what lies outside the scheduler
    p = run["passes"][2]
    rows = p["manifest"]["phases"]
    top = {r["name"]: r["end_s"] - r["start_s"] for r in rows if r["parent"] == "run"}
    dag_span = p["manifest"]["scheduler"]["nodes"]["a_node"]["end_s"]
    assert top["config"] + top["ingest"] + top["register"] + _read("after_dag_s", run) == pytest.approx(
        p["wall_s"] - dag_span, rel=0.02)


@pytest.mark.parametrize("name", NEW)
def test_a_manifest_without_phases_gives_nothing(name):
    """The parent commit's manifests: the reader returns None and does not raise."""
    run = _run(with_phases=False)
    run["traced"] = dict(run["passes"][0], traced=True)
    run["trace_dir"] = os.path.join(os.path.dirname(__file__), "no_such_dir")
    assert _read(name, run) is None
    assert _read(name, {"passes": [], "traced": None, "trace_dir": ""}) is None


def test_a_pass_without_ingest_gives_nothing_for_the_ingest_readers():
    run = _run(scales=(1.0,))
    man = run["passes"][0]["manifest"]
    man["phases"] = [r for r in man["phases"] if not r["name"].startswith(("ingest", "io:"))]
    assert [_read(n, run) for n in NEW[:4]] == [None] * 4
    assert _read("after_dag_s", run) == pytest.approx(1.8)


def test_idle_unnamed_share_on_a_hand_built_event_list():
    """One chip, busy 2-3 and 6-7, session 0-12, the pass 1-11.  Spans:
    ``run`` 1-11 and ``dag`` 5-9 name nothing; ``ingest`` 1-4.5, ``node_a``
    5.5-8, ``close`` 9.5-10.  Idle in the pass: 10 - 2 = 8 s.  Under a span:
    1-2 and 3-4.5 (ingest), 5.5-6 and 7-8 (node_a), 9.5-10 (close) = 4.5 s.
    Under none: 4.5-5.5 (covered only by run, then by run and dag), 8-9.5
    (dag, then run alone), 10-11 (run alone) = 3.5 s of 8."""
    share = load_module("layer_metrics", "idle_unnamed_share").unnamed_share
    trace = {
        "devices": {"/device:TPU:0": [(2.0, 3.0, "a"), (6.0, 7.0, "b")],
                    "/device:TPU:1": [(0.0, 12.0, "never read: the first chip's gaps are named")]},
        "host": [(1.0, 11.0, "run"), (5.0, 9.0, "dag"), (1.0, 4.5, "ingest"),
                 (5.5, 8.0, "node_a"), (9.5, 10.0, "close")],
        "window": (0.0, 12.0),
    }
    assert share(trace, (1.0, 11.0)) == pytest.approx(100 * 3.5 / 8)
    assert share(trace) == pytest.approx(100 * 5.5 / 10)  # the session: 0-1 and 11-12 unnamed too
    # every gap under a span: nothing unnamed
    trace["host"] = [(1.0, 11.0, "run"), (1.0, 11.0, "ingest")]
    assert share(trace, (1.0, 11.0)) == pytest.approx(0.0)
    # covered by run and dag alone, or by nothing: all of it unnamed
    trace["host"] = [(1.0, 11.0, "run"), (5.0, 9.0, "dag")]
    assert share(trace, (1.0, 11.0)) == pytest.approx(100.0)
    trace["host"] = []
    assert share(trace) == pytest.approx(100.0)
    # no session window in the trace: first event to last
    trace.update(host=[(2.5, 6.5, "ingest")], window=None)
    assert share(trace) == pytest.approx(0.0)
    assert share({"devices": {}, "host": [], "window": None}) is None


def test_the_traced_pass_is_placed_on_the_traces_clock():
    """``run`` 0-10 s of the pass, the profiler starting until 0.5 and
    exporting from 9.0; the trace has ``ingest`` (0.6 s of the pass) at 0.1 s
    of its session, so the session began at 0.5 s of the pass."""
    window = load_module("layer_metrics", "idle_unnamed_share").pass_window
    rows = [_row("run", None, 0.0, 10.0), _row("config", "run", 0.0, 0.2),
            _row("profiler:start", "run", 0.2, 0.5), _row("ingest", "run", 0.6, 4.0),
            _row("ingest/decode", "ingest", 0.7, 1.0), _row("dag", "run", 4.0, 8.5),
            _row("profiler:export", "run", 9.0, 10.0)]
    trace = {"host": [(0.2, 0.5, "ingest/decode"), (0.1, 3.5, "ingest"), (3.5, 8.0, "dag")],
             "window": (0.0, 8.7)}
    assert window(rows, trace) == pytest.approx((0.0, 8.5))
    assert window(rows[:-1], trace) == pytest.approx((0.0, 8.7))  # no export phase: run's end, cut to the session
    assert window([r for r in rows if not r["name"].startswith("profiler")],
                  dict(trace, window=None)) == pytest.approx((-0.5, 9.5))
    assert window(rows, {"host": [(0.2, 0.5, "ingest/decode")], "window": (0.0, 8.7)}) is None


def test_idle_unnamed_share_on_the_recorded_v5e_trace(tmp_path):
    """The trace recorded on the chip (tiny_v5e.xplane.pb.json says how): node_a
    49.807599-50.565019 ms, node_b 61.125278-2790.219853 ms, and inside them
    the chip busy for 59.626 us (jit_sort, 2788.181-2788.241 ms; jit_f ran
    before node_a on the device's clock).  From node_a's start to node_b's
    end, 2740.412254 ms, the one stretch under no span is the 10.560259 ms
    between the two."""
    recorded = os.path.join(os.path.dirname(__file__), "recorded")
    path = os.path.join(recorded, "tiny_v5e.xplane.pb")
    share = load_module("layer_metrics", "idle_unnamed_share")
    both = (0.049807599, 2.790219853)
    by_hand = 100 * 0.010560259 / (2.740412254 - 0.000059626)
    trace = trace_reduce.load(path, ["node_a", "node_b"])
    assert share.unnamed_share(trace, both) == pytest.approx(by_hand, rel=1e-6)
    assert share.unnamed_share(trace_reduce.load(path), both) == pytest.approx(100.0)
    # through read(): names and the pass's place in the session come from the
    # traced pass's manifest: a pass of 3 s in which node_b began at 0.06 s
    trace_dir = tmp_path / "trace"
    session = trace_dir / "plugins" / "profile" / "2026_09_27"
    session.mkdir(parents=True)
    os.symlink(path, session / "vm.xplane.pb")
    traced = {"manifest": {"phases": [_row("run", None, 0.0, 3.0), _row("node_b", "run", 0.06, 2.79)],
                           "scheduler": {"nodes": {"node_a": {}}}}}
    run = {"passes": [], "traced": traced, "trace_dir": str(trace_dir)}
    placed = (0.061125278 - 0.06, 0.061125278 - 0.06 + 3.0)
    assert share.pass_window(traced["manifest"]["phases"], trace) == pytest.approx(placed)
    assert _read("idle_unnamed_share", run) == pytest.approx(share.unnamed_share(trace, placed))
    assert 9.0 < _read("idle_unnamed_share", run) < 9.01  # 270.14 ms of 2999.93 idle under no span
    del traced["manifest"]["scheduler"]["nodes"]["node_a"]  # node_a under no span now
    assert _read("idle_unnamed_share", run) == pytest.approx(
        share.unnamed_share(trace_reduce.load(path, ["node_b"]), placed))


def test_benchmark_json_names_the_new_readers():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    tail = bench["per_layer"][-len(NEW):]
    assert [m["name"] for m in tail] == NEW  # appended, in the issue's order
    assert {m["moves"] for m in tail} == {"pass_s"} and not any("workloads" in m for m in tail)
    assert [m["layer"] for m in tail] == ["ingest"] * 4 + ["artifact writes", "device"]
    assert [m["source"] for m in tail] == ["program_span"] * 3 + ["program_counter", "program_span",
                                                                  "device_trace"]


def test_the_readers_read_what_a_stats_run_leaves(tmp_path):
    """The driver at 2,000 rows on the CPU: five of the six print (the sixth
    needs a device plane), and the phases outside ``dag`` are what
    ``outside_dag_s`` has by subtraction but for the manifest's own write."""
    sys.path.insert(0, os.path.dirname(__file__))
    from test_benchmark_harness import ROWS, _cell

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    run = pipeline.run(_cell(bench, "income_32k.stats", tmp_path, 0.5))
    assert run["correct"], run["checks"]
    line = bench_run.report(bench, "income_32k.stats", dict(run, trace_dir=""), True)
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW[:5]) <= set(got) and "idle_unnamed_share" not in got
    assert 0 < got["ingest_decode_s"] + got["ingest_encode_s"] < got["ingest_s"]
    assert got["ingest_h2d_gb"] * 1e9 > ROWS * 24 * 4  # 24 columns of four bytes and a mask each
    for p in run["passes"]:
        rows = p["manifest"]["phases"]
        (root,) = [r for r in rows if r["parent"] is None]
        (dag,) = [r for r in rows if r["name"] == "dag"]
        nodes = p["manifest"]["scheduler"]["nodes"]
        outside = p["wall_s"] - max(n["end_s"] for n in nodes.values())
        named = root["end_s"] - (dag["end_s"] - dag["start_s"])
        assert 0 <= p["wall_s"] - root["end_s"] < 0.05  # the manifest's write, on no span
        assert named == pytest.approx(outside, abs=0.06)
