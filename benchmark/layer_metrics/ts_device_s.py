"""Seconds a chip spent under the time-series inspection's two named scopes
in the traced pass: ``ts/calendar_counts`` (the calendar program of a
timestamp column) and ``ts/segment_aggregate`` (the per-bucket aggregates of
the numeric columns), whatever the jitted functions around them are called.

A device operation belongs to a scope if the ``tf_op`` of its event metadata
(the HLO's ``op_name``: ``jit(f)/.../ts/segment_aggregate/...``) names it; an
operation without any ``tf_op`` (a copy the compiler put in) belongs to the
scope that the operations of its program name, if they name exactly one.
Self time as ``trace_reduce.self_times`` counts it (a ``while`` is charged
what its body leaves over), mean over the chips of the trace.

``jax.profiler.ProfileData`` does not show an event's metadata, so the file
is read here as protobuf wire format (``XSpace`` > ``XPlane`` > ``XLine`` >
``XEvent``; tsl/profiler/protobuf/xplane.proto), the few fields this needs.
Nothing without a trace, or where no operation names a scope (a program from
before them)."""

from benchmark.harness import trace_reduce

SCOPES = ("ts/calendar_counts", "ts/segment_aggregate")


def _varint(buf, i):
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, the bytes of
    a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        else:
            size = {1: 8, 5: 4}[wire]
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _scope(op_name: str):
    return next((s for s in SCOPES if f"/{s}/" in f"/{op_name.rstrip(':')}/"), None)


def device_events(path: str) -> dict:
    """``{plane: [(start_s, end_s, scope or ""), ...]}`` of the ``XLA Ops``
    line of every device plane: ``trace_reduce``'s event lists, named by scope."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for no, plane in _fields(space):
        if no != 1:
            continue
        name, lines, events_meta, stat_names = "", [], {}, {}
        for no, v in _fields(plane):
            if no == 2:
                name = _text(v)
            elif no == 3:
                lines.append(v)
            elif no in (4, 5):
                entry = dict(_fields(v))
                if no == 4:
                    events_meta[entry[1]] = entry[2]
                else:
                    stat_names[entry[1]] = _text(dict(_fields(entry[2])).get(2, b""))
        if not name.startswith(trace_reduce.DEVICE_PLANE_PREFIX):
            continue
        about = {}  # metadata id -> (op_name or None, program id)
        for mid, meta in events_meta.items():
            op_name, program = None, 0
            for no, v in _fields(meta):
                if no != 5:
                    continue
                stat = dict(_fields(v))
                key = stat_names.get(stat.get(1))
                if key == "tf_op":
                    op_name = _text(stat[5]) if 5 in stat else stat_names.get(stat.get(7), "")
                elif key == "program_id":
                    program = stat.get(3, stat.get(4, 0))
            about[mid] = (op_name, program)
        named = {}  # program id -> the scopes its operations name
        for op_name, program in about.values():
            if op_name is not None and _scope(op_name):
                named.setdefault(program, set()).add(_scope(op_name))
        events = []
        for line in lines:
            line_name, t0, raw = "", 0.0, []
            for no, v in _fields(line):
                if no == 2:
                    line_name = _text(v)
                elif no == 3:
                    t0 = v * 1e-9
                elif no == 4:
                    raw.append(v)
            if line_name != trace_reduce.DEVICE_OPS_LINE:
                continue
            for v in raw:
                e = {k: x for k, x in _fields(v) if k in (1, 2, 3)}
                op_name, program = about.get(e.get(1), (None, 0))
                scope = _scope(op_name) if op_name is not None else (
                    next(iter(named[program])) if len(named.get(program, ())) == 1 else None)
                start = t0 + e.get(2, 0) * 1e-12
                events.append((start, start + e.get(3, 0) * 1e-12, scope or ""))
        out[name] = events
    return out


def scope_seconds(devices: dict) -> dict:
    """Seconds per scope, mean over the chips; empty where no operation names one."""
    total = {}
    for events in devices.values():
        times = trace_reduce.self_times(events)
        for scope in SCOPES:
            if times.get(scope):
                total[scope] = total.get(scope, 0.0) + times[scope] / len(devices)
    return total


def by_scope(run) -> dict:
    """The traced pass's seconds per scope, read once a run."""
    if "ts_scope_seconds" not in run:
        path = trace_reduce.find_xplane(run["trace_dir"]) if run.get("trace_dir") else None
        run["ts_scope_seconds"] = scope_seconds(device_events(path)) if path else {}
    return run["ts_scope_seconds"]


def read(run):
    found = by_scope(run)
    return sum(found.values()) if found else None
