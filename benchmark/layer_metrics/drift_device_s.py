"""Seconds a chip spent under the drift block's named scopes in the traced
pass: ``drift/fit_cutoffs`` (the source's smallest and largest value a
column, or its quantiles), ``drift/side_histograms`` (a side's binned and
categorical counts) and ``stability/moments`` (a period's moments), whatever
the jitted functions around them are called.  An operation belongs to a scope
as ``ts_device_s`` decides it, and the seconds are self time, mean over the
chips of the trace: this reader is ``ts_device_s``'s reduction (its own copy
of that module, as ``assoc_device_s`` takes one) over these three names.
Nothing without a trace, or where no operation names a scope (a program from
before them)."""

from benchmark.harness import trace_reduce
from benchmark.harness.names import load_module

SCOPES = ("drift/fit_cutoffs", "drift/side_histograms", "stability/moments")


def _reduction():
    mod = load_module("layer_metrics", "ts_device_s")  # a module object of our own: load_module executes the file anew
    mod.SCOPES = SCOPES
    return mod


def by_scope(run) -> dict:
    """The traced pass's seconds per scope, read once a run."""
    if "drift_scope_seconds" not in run:
        path = trace_reduce.find_xplane(run["trace_dir"]) if run.get("trace_dir") else None
        mod = _reduction()
        run["drift_scope_seconds"] = mod.scope_seconds(mod.device_events(path)) if path else {}
    return run["drift_scope_seconds"]


def read(run):
    found = by_scope(run)
    return sum(found.values()) if found else None
