"""Seconds a chip spent under the named scope ``ae/train_step`` (forward,
backward and Adam of the autoencoder's step) in the traced pass.  The trace
is read as ``ts_device_s`` reads it (an operation belongs to the scope that
its ``tf_op`` names; self time, mean over the chips), by a copy of that
module of this reader's own, told these scopes.  Nothing without a trace,
or where no operation names the scope."""

from benchmark.harness import trace_reduce
from benchmark.harness.names import load_module

SCOPES = ("ae/train_step", "ae/encode")


def by_scope(run) -> dict:
    """The traced pass's seconds per scope of ``SCOPES``, read once a run."""
    if "ae_scope_seconds" not in run:
        path = trace_reduce.find_xplane(run["trace_dir"]) if run.get("trace_dir") else None
        reader = load_module("layer_metrics", "ts_device_s")  # a fresh module object: SCOPES is set on no one else's
        reader.SCOPES = SCOPES
        run["ae_scope_seconds"] = reader.scope_seconds(reader.device_events(path)) if path else {}
    return run["ae_scope_seconds"]


def read(run):
    return by_scope(run).get("ae/train_step")
