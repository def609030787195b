"""Distinct values the drift block walks one at a time in Python in the
window's median pass: the ``values`` counts of the stage rows ``drift/union``
(a ``str`` and a set entry a value of either side's vocabulary),
``drift/lut`` (a dict entry a value of the union and a look-up a local value,
once a side) and ``drift/model`` (a CSV line a value of the union), summed.
What a vectorised union, remap and model writer would bring to 0; a cell's
host seconds in these rows follow it.  Nothing where no row carries the count
(a program from before it)."""

from benchmark.harness import phases
from benchmark.harness.manifest import median_pass

ROWS = ("drift/union", "drift/lut", "drift/model")


def read(run):
    counted = [r["counts"]["values"] for r in phases.rows(median_pass(run["passes"]))
               if r["name"] in ROWS and "values" in r["counts"]]
    return sum(counted) if counted else None
