"""Seconds of the ``ingest/encode`` spans (the dictionary-encoding of one
string column each) inside ``ingest``, in the window's median pass."""

from benchmark.harness import phases
from benchmark.harness.manifest import median_pass


def read(run):
    rows = phases.rows(median_pass(run["passes"]))
    found = phases.inside(rows, phases.one(rows, "ingest"), "ingest/encode")
    return phases.seconds(found) if found else None
