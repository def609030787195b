"""The nine readers of set-up (PR 51): what the process did before its first
pass (``process`` of the fresh pass's manifest), the start of the runtime
(``runtime/init``), the stages of every program's way to the device
(``compile/*`` rows of ``phases``) and the census's ``built_programs``.  Each
on a hand-built manifest, nothing where the manifest is from before them,
the union of overlapping stage rows, and their entries in ``BENCHMARK.json``
found by name."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.harness import setup  # noqa: E402
from benchmark.harness.names import load_module  # noqa: E402


def _row(name, parent, start, end, thread="MainThread", **counts):
    return {"name": name, "parent": parent, "start_s": start, "end_s": end, "thread": thread,
            "counts": counts, "usage": {}}


def _manifest():
    """A fresh pass of 12 s after 9 s of process: the interpreter 1 s, the
    imports 5, the caller 3.  The runtime starts inside the read; two nodes
    compile side by side, ``a`` on ``w0`` (trace 3.0-3.5, lower 3.5-4.0, load
    4.0-5.0) and ``b`` on ``w1`` (trace 3.25-4.25, lower 4.25-4.5, build
    4.5-7.0, and a second program's trace 8.0-8.5 and load 8.5-8.75)."""
    return {
        "process": {"pass_index": 0, "rows": [
            {"name": "process/interpreter", "start_s": -9.0, "end_s": -8.0},
            {"name": "process/import", "start_s": -8.0, "end_s": -3.0},
            {"name": "process/caller", "start_s": -3.0, "end_s": 0.0}]},
        "phases": [
            _row("run", None, 0.0, 12.0),
            _row("ingest", "run", 0.0, 2.0),
            _row("io:read_dataset", "ingest", 0.0, 2.0),
            _row("runtime/init", "io:read_dataset", 0.5, 0.75, devices=1, cache_entries=110),
            _row("dag", "run", 2.0, 11.0),
            _row("a", "dag", 3.0, 6.0, "w0"),
            _row("compile/trace", "a", 3.0, 3.5, "w0"),
            _row("compile/lower", "a", 3.5, 4.0, "w0"),
            _row("compile/load", "a", 4.0, 5.0, "w0"),
            _row("b", "dag", 3.0, 9.0, "w1"),
            _row("compile/trace", "b", 3.25, 4.25, "w1"),
            _row("compile/lower", "b", 4.25, 4.5, "w1"),
            _row("compile/build", "b", 4.5, 7.0, "w1"),
            _row("compile/trace", "b", 8.0, 8.5, "w1"),
            _row("compile/load", "b", 8.5, 8.75, "w1"),
        ],
        "compile_census": {"compiles_total": 3, "distinct_programs": 3, "cache_hits": 2, "built_programs": 1},
    }


def _run(manifest):
    return {"fresh": {"wall_s": 12.0, "manifest": manifest}, "passes": [], "traced": None, "trace_dir": ""}


def _read(name, run):
    return load_module("layer_metrics", name).read(run)


EXPECTED = {
    "setup_import_s": 5.0,
    "setup_before_run_s": 3.0,
    "fresh_runtime_s": 0.25,
    "fresh_trace_s": 0.5 + 1.0 + 0.5,
    "fresh_lower_s": 0.5 + 0.25,
    "fresh_cache_load_s": 1.0 + 0.25,
    "fresh_build_s": 2.5,
    # 3.0-7.0 (the two threads' stages run into one another) and 8.0-8.75
    "fresh_compile_wall_s": 4.0 + 0.75,
    "fresh_built_programs": 1,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_each_reader_on_a_hand_built_fresh_pass(name):
    value = _read(name, _run(_manifest()))
    assert value == pytest.approx(EXPECTED[name]) and type(value) is type(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_manifest_from_before_the_spans_gives_nothing(name):
    old = _manifest()
    del old["process"]
    old["phases"] = [r for r in old["phases"] if not r["name"].startswith(("compile/", "runtime/"))]
    old["compile_census"] = {"compiles_total": 3, "distinct_programs": 3}
    assert _read(name, _run(old)) is None
    assert _read(name, _run({})) is None  # a pass that raised: no manifest at all


def test_a_warm_checkout_that_built_nothing_reads_zero_and_not_nothing():
    warm = _manifest()
    warm["phases"] = [r for r in warm["phases"] if r["name"] != "compile/build"]
    warm["compile_census"]["built_programs"] = 0
    assert _read("fresh_build_s", _run(warm)) == 0.0
    assert _read("fresh_built_programs", _run(warm)) == 0
    assert _read("fresh_cache_load_s", _run(warm)) == pytest.approx(1.25)


def test_a_rehearsal_in_a_long_lived_process_reads_zero_for_what_came_before_it():
    """The tests drive the driver in a process that has run passes before: the
    fresh pass is then not the process's first and the runtime is up."""
    later = _manifest()
    later["process"] = {"pass_index": 3}  # no rows: nothing came before this pass on its account
    later["phases"] = [r for r in later["phases"] if r["name"] != "runtime/init"]
    for name in ("setup_import_s", "setup_before_run_s", "fresh_runtime_s"):
        value = _read(name, _run(later))
        assert value == 0.0 and type(value) is float, name
    assert _read("fresh_trace_s", _run(later)) == pytest.approx(2.0)
    first = _manifest()
    first["process"]["rows"] = first["process"]["rows"][1:2]  # /proc unreadable and a caller's row lost
    assert _read("setup_import_s", _run(first)) == 5.0 and _read("setup_before_run_s", _run(first)) is None


def test_the_wall_is_the_union_of_the_rows_of_all_threads():
    rows = [_row("compile/trace", "a", 1.0, 3.0, "w0"), _row("compile/build", "b", 2.0, 6.0, "w1"),
            _row("compile/lower", "a", 3.0, 3.5, "w0"), _row("compile/load", "c", 8.0, 9.0, "w2"),
            _row("compile/trace", "c", 8.25, 8.5, "w2")]  # a compile inside a trace, on one thread
    assert setup.union_seconds(rows) == pytest.approx(5.0 + 1.0)
    assert setup.union_seconds([]) == 0.0 and setup.union_seconds(rows[:1]) == pytest.approx(2.0)
    man = _manifest()
    man["phases"] = [_row("run", None, 0.0, 12.0)] + rows
    wall, sums = _read("fresh_compile_wall_s", _run(man)), sum(
        _read(n, _run(man)) for n in ("fresh_trace_s", "fresh_lower_s", "fresh_cache_load_s", "fresh_build_s"))
    assert wall == pytest.approx(6.0) and sums == pytest.approx(7.75) and wall <= sums and wall <= 12.0


def test_benchmark_json_names_the_nine_readers_last():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in EXPECTED:
        counted = name == "fresh_built_programs"
        assert by_name[name] == {"name": name, "unit": "count" if counted else "s", "better": "lower",
                                 "source": "program_counter" if counted else "program_span",
                                 "layer": "entry and runtime", "moves": "setup_s"}
        assert os.path.exists(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py"))
    assert {m["name"] for m in bench["per_layer"][-9:]} == set(EXPECTED)
    # no ``workloads`` list: every cell reports ``setup_s``, so every cell's traced line carries them
    reporting = {m["name"] for m in bench["end_to_end"]}
    for cell in bench["workloads"]:
        assert all(bench_run._in_cell(by_name[name], cell["name"], reporting) for name in EXPECTED), cell["name"]
