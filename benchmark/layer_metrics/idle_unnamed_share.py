"""Of the seconds the first chip was idle in the traced pass, the share, in
percent, under no span of the program: no scheduler node and no phase other
than ``run`` (the whole pass) and ``dag`` (the scheduler, whose nodes are
spans of their own).  The spans are the ``TraceAnnotation`` events of the
trace's host plane, named as the traced pass's manifest names its nodes and
phases; a manifest without ``phases`` gives nothing.

The traced pass is the root span ``run`` but for the profiler's own start
and export (the phases ``profiler:start`` and ``profiler:export``, which lie
across the session's edges).  The manifest has it in seconds of the pass;
the first child of ``run`` that the trace has too places it on the trace's
clock."""

from benchmark.harness import phases, trace_reduce

WHOLE = ("run", "dag")


def unnamed_share(trace: dict, window=None):
    """``trace`` as ``trace_reduce.load`` gives it, its ``host`` events being
    the program's spans; over ``window``, else the trace's session, else from
    its first event to its last."""
    devices, host = trace["devices"], trace["host"]
    if not devices:
        return None
    busy = trace_reduce.union(devices[sorted(devices)[0]])
    every = busy + [h[:2] for h in host]
    w0, w1 = window or trace.get("window") or (min(iv[0] for iv in every), max(iv[1] for iv in every))
    named = trace_reduce.union(busy + [h[:2] for h in host if h[2] not in WHOLE])

    def within(intervals):
        return sum(min(e, w1) - max(s, w0) for s, e in intervals if e > w0 and s < w1)

    idle = (w1 - w0) - within(busy)
    return 100.0 * ((w1 - w0) - within(named)) / idle if idle > 0 else None


def pass_window(rows: list, trace: dict):
    """``(start_s, end_s)`` of the traced pass on the trace's clock, within
    its session; None where the trace has no child of ``run`` to place it by."""
    root = phases.one(rows, "run", parent=None)
    for row in (r for r in rows if r["parent"] == "run"):
        found = [h for h in trace["host"] if h[2] == row["name"]]
        if root and len(found) == 1:
            shift = found[0][0] - row["start_s"]
            start, stop = phases.one(rows, "profiler:start"), phases.one(rows, "profiler:export")
            w0 = shift + (start["end_s"] if start else root["start_s"])
            w1 = shift + (stop["start_s"] if stop else root["end_s"])
            s0, s1 = trace.get("window") or (w0, w1)
            return max(w0, s0), min(w1, s1)
    return None


def read(run):
    manifest = (run.get("traced") or {}).get("manifest") or {}
    rows = phases.rows(run.get("traced"))
    path = trace_reduce.find_xplane(run["trace_dir"]) if run.get("trace_dir") else None
    if not rows or not path:
        return None
    names = set((manifest.get("scheduler") or {}).get("nodes") or {}) | {r["name"] for r in rows}
    trace = trace_reduce.load(path, names)
    return unnamed_share(trace, pass_window(rows, trace))
