"""The three readers of what a row of the phase tree consumed (``usage`` on
the manifest's ``phases``, PR 36): ``ingest_cpu_s``, ``dag_cpu_s`` and
``critical_unnamed_s``, each on hand-built manifests (a value where the usage
is recorded, nothing where it is not: a program from before it), and their
entries in ``BENCHMARK.json`` found by name."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.harness.names import load_module  # noqa: E402

NEW = {"ingest_cpu_s": "ingest", "dag_cpu_s": "blocks", "critical_unnamed_s": "blocks"}


def _row(name, parent, start, end, thread="MainThread", usage=None, **counts):
    row = {"name": name, "parent": parent, "start_s": start, "end_s": end, "thread": thread,
           "counts": counts}
    if usage is not None:
        row["usage"] = usage
    return row


def _top(proc_cpu_s, cpu_s):
    return {"cpu_s": cpu_s, "proc_cpu_s": proc_cpu_s, "minflt": 10, "majflt": 0, "nivcsw": 1}


def _manifest(scale=1.0, usage=True):
    """A pass of 10 s (times ``scale``): ingest 0-2 on 3 cores, dag 2-9 on 1.5,
    and a critical path of two nodes, ``a`` 2-5 and ``b`` 5-9.  ``a`` has two
    stage rows that overlap (2.5-3.5 and 3.0-4.0: 1.5 s named of 3) and a
    grandchild that is no child of the node; ``b`` has one stage row that
    sticks out of it at both ends of the pass's clock (4.5-6.0: 1 s named of
    4 once clipped) and a row of another node's under the same stage name.
    ``c`` runs beside them, off the path, with nothing inside it."""
    s, u = scale, (lambda *a: _top(*a)) if usage else (lambda *a: None)
    cpu = (lambda x: {"cpu_s": x}) if usage else (lambda x: None)
    rows = [
        _row("run", None, 0.0, 10 * s, usage=u(17.0 * s, 1.0 * s)),
        _row("ingest", "run", 0.0, 2 * s, usage=u(6.0 * s, 1.5 * s)),
        _row("dag", "run", 2 * s, 9 * s, usage=u(10.5 * s, 0.01)),
        _row("a", "dag", 2 * s, 5 * s, "w0", usage=cpu(1.0 * s), queue_wait_s=0.0),
        _row("x/one", "a", 2.5 * s, 3.5 * s, "w0", usage=cpu(0.9 * s), rows=5),
        _row("x/two", "a", 3.0 * s, 4.0 * s, "pool_1", usage=cpu(0.1 * s)),
        _row("x/two/inner", "x/two", 3.2 * s, 3.4 * s, "pool_1", usage=cpu(0.1 * s)),
        _row("b", "dag", 5 * s, 9 * s, "w1", usage=cpu(3.0 * s), queue_wait_s=0.0),
        _row("y/one", "b", 4.5 * s, 6.0 * s, "w1", usage=cpu(0.5 * s)),
        _row("c", "dag", 2 * s, 8 * s, "w2", usage=cpu(0.2 * s), queue_wait_s=0.0),
        _row("y/one", "c", 8.5 * s, 8.9 * s, "w2", usage=cpu(0.1 * s)),
        _row("release", "run", 9 * s, 10 * s, usage=u(0.5 * s, 0.5 * s)),
    ]
    return {
        "phases": rows,
        "scheduler": {"critical_path": ["a", "b"], "critical_path_s": 7 * s,
                      "nodes": {n: {"start_s": 0.0, "end_s": 1.0, "dur_s": d * s}
                                for n, d in (("a", 3.0), ("b", 4.0), ("c", 6.0))}},
    }


def _run(scales=(1.0, 3.0, 2.0), **kw):
    return {"passes": [{"wall_s": 10 * s, "manifest": _manifest(s, **kw), "traced": False}
                       for s in scales], "traced": None, "trace_dir": ""}


def _read(name, run):
    return load_module("layer_metrics", name).read(run)


def test_the_readers_read_the_median_pass():
    run = _run()  # the median pass is the one of scale 2
    assert _read("ingest_cpu_s", run) == pytest.approx(12.0)
    assert _read("dag_cpu_s", run) == pytest.approx(21.0)
    # a: 3 s of which 1.5 under the union of two overlapping rows; b: 4 s of which 1 under a row
    # that starts before it; c is off the path and the other node's y/one is not b's
    assert _read("critical_unnamed_s", run) == pytest.approx(2 * (1.5 + 3.0))


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_program_without_the_usage_gives_nothing(name):
    assert _read(name, _run(usage=False)) is None
    assert _read(name, {"passes": [], "traced": None, "trace_dir": ""}) is None
    bare = _run()
    for p in bare["passes"]:
        del p["manifest"]["phases"]  # a program from before the phase tree
    assert _read(name, bare) is None


def test_a_path_node_without_a_row_counts_whole_and_an_empty_path_reads_zero():
    run = _run(scales=(1.0,))
    man = run["passes"][0]["manifest"]
    man["phases"] = [r for r in man["phases"] if r["name"] != "b" and r["parent"] != "b"]
    assert _read("critical_unnamed_s", run) == pytest.approx(1.5 + 4.0)  # b restored from the cache: its dur_s
    man["scheduler"]["critical_path"] = []
    assert _read("critical_unnamed_s", run) == 0.0


def test_uncovered_clips_children_to_their_node():
    reader = load_module("layer_metrics", "critical_unnamed_s")
    node = _row("n", "dag", 10.0, 20.0)
    assert reader.uncovered(node, []) == pytest.approx(10.0)
    assert reader.uncovered(node, [_row("k", "n", 5.0, 25.0)]) == pytest.approx(0.0)
    kids = [_row("k", "n", 12.0, 14.0), _row("k", "n", 13.0, 13.5), _row("k", "n", 19.0, 30.0),
            _row("k", "n", 1.0, 2.0)]
    assert reader.uncovered(node, kids) == pytest.approx(10.0 - 2.0 - 1.0)


def test_benchmark_json_names_the_three_readers():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, layer in NEW.items():
        assert by_name[name] == {"name": name, "unit": "s", "better": "lower", "source": "program_span",
                                 "layer": layer, "moves": "pass_s"}
        assert os.path.exists(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py"))
    # no ``workloads`` list: every cell reports ``pass_s``, so every cell's traced line carries them
    reporting = {m["name"] for m in bench["end_to_end"]}
    for cell in bench["workloads"]:
        assert all(bench_run._in_cell(by_name[name], cell["name"], reporting) for name in NEW), cell["name"]
