"""Seconds a chip spent under the association block's named scopes in the
traced pass: ``assoc/cutoffs`` (the sort that gives the equal-frequency
cut-offs), ``assoc/bin_apply`` (a bin for every cell), ``assoc/group_counts``
(labelled rows and events per group) and ``assoc/corr`` (the complete-case
correlation), whatever the jitted functions around them are called.  An
operation belongs to a scope as ``ts_device_s`` decides it (the ``tf_op`` of
its event metadata names the scope; an operation without one belongs to the
scope its program's operations name, if they name exactly one), and the
seconds are self time, mean over the chips of the trace: this reader is
``ts_device_s``'s reduction (its own copy of that module, so that the other's
scopes stay what they are) over these four names.  Nothing without a trace,
or where no operation names a scope (a program from before them)."""

from benchmark.harness import trace_reduce
from benchmark.harness.names import load_module

SCOPES = ("assoc/cutoffs", "assoc/bin_apply", "assoc/group_counts", "assoc/corr")


def _reduction():
    mod = load_module("layer_metrics", "ts_device_s")  # a module object of our own: load_module executes the file anew
    mod.SCOPES = SCOPES
    return mod


def by_scope(run) -> dict:
    """The traced pass's seconds per scope, read once a run."""
    if "assoc_scope_seconds" not in run:
        path = trace_reduce.find_xplane(run["trace_dir"]) if run.get("trace_dir") else None
        mod = _reduction()
        run["assoc_scope_seconds"] = mod.scope_seconds(mod.device_events(path)) if path else {}
    return run["assoc_scope_seconds"]


def read(run):
    found = by_scope(run)
    return sum(found.values()) if found else None
