"""Seconds a chip spent, in the traced pass, under the scope
``ts/segment_aggregate/wide``: the per-bucket aggregates of a wide segment
class (``ts_wide_s`` says which), whatever takes them; its two parts are
named ``wide/moments`` and ``wide/medians`` for a reader of the trace.  The
time is part of ``ts_device_s``'s ``ts/segment_aggregate``.

Read as ``ts_device_s`` reads its scopes (an operation belongs to a scope by
the ``tf_op`` of its event metadata; self time, mean over the chips), with
this file's own copy of that module told the one scope to look for.  Nothing
without a trace, or where no operation names the scope (a program from
before it, or a pass without a wide class)."""

from benchmark.harness import trace_reduce
from benchmark.harness.names import load_module

SCOPE = "ts/segment_aggregate/wide"


def by_scope(run) -> dict:
    """The traced pass's seconds under ``SCOPE``, read once a run."""
    if "ts_wide_scope_seconds" not in run:
        reader = load_module("layer_metrics", "ts_device_s")  # a fresh module object: the scopes set here are its alone
        reader.SCOPES = (SCOPE,)
        path = trace_reduce.find_xplane(run["trace_dir"]) if run.get("trace_dir") else None
        run["ts_wide_scope_seconds"] = reader.scope_seconds(reader.device_events(path)) if path else {}
    return run["ts_wide_scope_seconds"]


def read(run):
    return by_scope(run).get(SCOPE)
