"""Busy seconds of the busiest chip in the traced pass (the largest of the
reduced trace's ``per_chip_busy_s``); ``device_busy_s`` is their mean, so the
two apart show imbalance between the chips."""


def read(run):
    per_chip = (run.get("trace") or {}).get("per_chip_busy_s")
    return max(per_chip) if per_chip else None
