"""Seconds a chip spent in collective operations in the traced pass: the self
time of the all-to-all, all-reduce, all-gather, reduce-scatter and
collective-permute events of ``XLA Ops`` (their ``-start`` and ``-done``
halves included), mean over the chips of the trace.  A collective's event
lasts until the slowest chip has arrived, so the time holds the wait for the
others.  0.0 where the trace has device operations and none is a
collective; nothing without a trace."""

from benchmark.harness import trace_reduce

COLLECTIVES = ("all-to-all", "all-reduce", "all-gather", "reduce-scatter", "collective-permute")


def collective_seconds(devices: dict):
    """``devices`` as ``trace_reduce.load`` gives them: per chip a list of
    ``(start_s, end_s, "<program>/<operation>")``."""
    per_chip = [sum(sec for name, sec in trace_reduce.self_times(events).items()
                    if name.split("/")[-1].startswith(COLLECTIVES))
                for events in devices.values()]
    return sum(per_chip) / len(per_chip) if per_chip else None


def read(run):
    path = trace_reduce.find_xplane(run["trace_dir"]) if run.get("trace_dir") else None
    return collective_seconds(trace_reduce.load(path)["devices"]) if path else None
