"""Seconds a chip spent in the describe's own programs in the traced pass:
the self time of the ``XLA Ops`` events that lie in a program named
``jit__describe_*`` or ``jit_describe_cat*`` (``ops/describe.py``'s jitted
kernels; the stacking of their inputs is another program and not counted),
mean over the chips of the trace, read from the trace again as
``collective_s`` reads its own.  Nothing without a trace, or where no such
program ran."""

from benchmark.harness import trace_reduce

PROGRAMS = ("jit__describe_", "jit_describe_cat")


def describe_seconds(devices: dict):
    """``devices`` as ``trace_reduce.load`` gives them: per chip a list of
    ``(start_s, end_s, "<program>/<operation>")``."""
    per_chip = [sum(sec for name, sec in trace_reduce.self_times(events).items()
                    if name.startswith(PROGRAMS))
                for events in devices.values()]
    return sum(per_chip) / len(per_chip) if per_chip and any(per_chip) else None


def read(run):
    if "describe_device_s" not in run:  # describe_hbm_pct reads it too: one load of the trace
        path = trace_reduce.find_xplane(run["trace_dir"]) if run.get("trace_dir") else None
        run["describe_device_s"] = describe_seconds(trace_reduce.load(path)["devices"]) if path else None
    return run["describe_device_s"]
