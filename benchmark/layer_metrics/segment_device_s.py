"""Seconds a chip spent in the group counts and LUT gathers of
``ops/segment.py`` in the traced pass: the self time of the ``XLA Ops`` events
that lie in the programs ``jit__code_counts_p`` (rows per category),
``jit__code_label_counts_p`` (events per category) and ``jit__lut_gather`` (a
per-category value back onto the rows), mean over the chips of the trace,
read from the trace again as ``describe_device_s`` reads its own.  Nothing
without a trace, or where no such program ran."""

from benchmark.harness import trace_reduce

PROGRAMS = ("jit__code_counts_p/", "jit__code_label_counts_p/", "jit__lut_gather/")


def segment_seconds(devices: dict):
    """``devices`` as ``trace_reduce.load`` gives them: per chip a list of
    ``(start_s, end_s, "<program>/<operation>")``."""
    per_chip = [sum(sec for name, sec in trace_reduce.self_times(events).items()
                    if name.startswith(PROGRAMS))
                for events in devices.values()]
    return sum(per_chip) / len(per_chip) if per_chip and any(per_chip) else None


def read(run):
    if "segment_device_s" not in run:  # segment_hbm_pct reads it too: one load of the trace
        path = trace_reduce.find_xplane(run["trace_dir"]) if run.get("trace_dir") else None
        run["segment_device_s"] = segment_seconds(trace_reduce.load(path)["devices"]) if path else None
    return run["segment_device_s"]
