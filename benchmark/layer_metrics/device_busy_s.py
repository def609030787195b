"""Union of the device-operation intervals of the traced pass, averaged
over the chips (benchmark/harness/trace_reduce.py)."""


def read(run):
    return (run.get("trace") or {}).get("busy_s")
