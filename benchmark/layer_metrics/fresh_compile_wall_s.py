"""Seconds of the fresh pass during which some thread was in a stage of a
program's way to the device: the union over time of all its ``compile/*``
rows, the wall that the four stage sums share out."""

from benchmark.harness import setup


def read(run):
    found = setup.stage_rows(run)
    return None if found is None else float(setup.union_seconds(found))
