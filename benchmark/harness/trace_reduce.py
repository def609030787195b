"""From a profiler trace (``.xplane.pb``) to device busy time, the idle
share, the device operations that took most time and the longest idle gaps
named by what the host was doing.

Two steps, so that the arithmetic can be checked on a hand-built event
list: :func:`load` reads the file into plain lists of ``(start_s, end_s,
name)``; :func:`reduce` is pure arithmetic on such lists.

What a v5e trace looks like (looked at by hand, PR 24): one plane per chip
named ``/device:TPU:<n>``, on it the line ``XLA Ops`` with one event per
executed HLO operation, named by its whole HLO text (a ``while`` spans its
body's events on the same line, which is why busy time is a union and not a
sum), and the line ``XLA Modules`` with one event per executed program,
named ``jit_f(<id>)``; DMA waits sit apart on ``Async XLA Ops`` and are not
counted busy.  Host threads are lines of the plane ``/host:CPU``, where a
``jax.profiler.TraceAnnotation`` is an event under its own name on the line
of the thread that opened it, among that thread's runtime and python events.
The plane ``Task Environment`` holds the session's start and stop in ns of
the wall clock; event times count from the start.
"""

from __future__ import annotations

import glob
import os
from typing import Iterable, Optional

DEVICE_PLANE_PREFIX = "/device:TPU:"
DEVICE_OPS_LINE = "XLA Ops"
DEVICE_MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SESSION_PLANE = "Task Environment"


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def load(path: str, host_names: Iterable[str] = ()) -> dict:
    """``{"devices": {plane: [(start_s, end_s, name), ...]}, "host": [...],
    "window": (0.0, session_s) or None}``.  A device operation is named
    ``<program>/<operation>`` (``jit_sort/sort.4``): the program is the event
    of ``XLA Modules`` it starts in, the operation what its HLO text assigns
    to.  Of the host's events only those named in ``host_names`` are kept (the
    node annotations); seconds count from the start of the profiler session."""
    from jax.profiler import ProfileData

    def events(line):
        return sorted((e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
                      for e in line.events)

    keep = set(host_names)
    out = {"devices": {}, "host": [], "window": None}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            lines = {line.name: events(line) for line in plane.lines
                     if line.name in (DEVICE_OPS_LINE, DEVICE_MODULES_LINE)}
            modules, ops, m = lines.get(DEVICE_MODULES_LINE, []), [], 0
            for s, e, text in lines.get(DEVICE_OPS_LINE, []):
                while m + 1 < len(modules) and modules[m + 1][0] <= s:
                    m += 1
                program = modules[m][2].split("(")[0] if modules and modules[m][0] <= s else "?"
                ops.append((s, e, f"{program}/{text.split(' = ')[0].lstrip('%')}"))
            out["devices"][plane.name] = ops
        elif plane.name == SESSION_PLANE:
            stats = dict(plane.stats)
            if "profile_start_time" in stats and "profile_stop_time" in stats:
                out["window"] = (0.0, (stats["profile_stop_time"] - stats["profile_start_time"]) * 1e-9)
        elif plane.name == HOST_PLANE and keep:
            for line in plane.lines:
                out["host"] += [
                    (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
                    for e in line.events if e.name in keep]
    return out


def union(intervals: Iterable[tuple]) -> list:
    """Disjoint ``[start, end]`` pairs covering the same points, sorted."""
    out = []
    for s, e in sorted((iv[0], iv[1]) for iv in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(events: list) -> dict:
    """Seconds per operation name, each instant of the line counted once, for
    the innermost event that covers it (a ``while`` is charged what its body's
    operations leave over)."""
    total: dict = {}
    stack: list = []  # (end, name) of the events open at the cursor
    cursor = 0.0

    def charge(upto):
        nonlocal cursor
        if stack and upto > cursor:
            total[stack[-1][1]] = total.get(stack[-1][1], 0.0) + upto - cursor
        cursor = max(cursor, upto)

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            charge(stack[-1][0])
            stack.pop()
        charge(s)
        cursor = max(cursor, s)
        stack.append((e, name))
    while stack:
        charge(stack[-1][0])
        stack.pop()
    return total


def reduce(trace: dict, top: int = 10) -> dict:
    """``busy_s`` (union of device-operation intervals, averaged over the chips
    in the trace), ``window_s``, ``idle_share`` (1 - busy/window),
    ``device_ops`` (the ``top`` operations by self time, summed over chips)
    and ``idle_gaps`` (the ``top`` longest gaps of the first chip, each named
    by the shortest host event that covers its middle; ``outside_dag`` before
    the first and after the last host event; ``unattributed`` between them).

    The window is the profiler session (``trace["window"]``) or, where the
    trace does not say, the span from its first to its last event."""
    devices = trace["devices"]
    if not devices:
        return {}
    every = [ev for evs in devices.values() for ev in evs] + list(trace["host"])
    w0, w1 = trace.get("window") or (min(ev[0] for ev in every), max(ev[1] for ev in every))
    window_s = w1 - w0
    busy, ops, first_busy = [], {}, None
    for plane in sorted(devices):
        clipped = [(max(s, w0), min(e, w1), n) for s, e, n in devices[plane] if e > w0 and s < w1]
        u = union(clipped)
        if first_busy is None:
            first_busy = u
        busy.append(sum(e - s for s, e in u))
        for name, sec in self_times(clipped).items():
            ops[name] = ops.get(name, 0.0) + sec
    busy_s = sum(busy) / len(busy)

    host = sorted(trace["host"])
    dag = (min(h[0] for h in host), max(h[1] for h in host)) if host else None
    edges = [w0] + [t for iv in first_busy for t in iv] + [w1]
    longest = sorted(((e - s, s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s),
                     reverse=True)[:top]
    gaps = []
    for length, s, e in longest:
        mid = (s + e) / 2
        cover = [h for h in host if h[0] <= mid <= h[1]]
        if cover:
            name = min(cover, key=lambda h: h[1] - h[0])[2]
        elif dag and dag[0] <= mid <= dag[1]:
            name = "unattributed"
        else:
            name = "outside_dag"
        gaps.append([name, length])
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "per_chip_busy_s": busy,
        "device_ops": [[n, s] for n, s in sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": gaps,
    }
