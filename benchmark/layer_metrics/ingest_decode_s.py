"""Seconds of the ``ingest/decode`` spans (one per part file) and the
``ingest/assemble`` span (reconcile, concatenate, schema inference,
sanitize) inside ``ingest``, in the window's median pass."""

from benchmark.harness import phases
from benchmark.harness.manifest import median_pass


def read(run):
    rows = phases.rows(median_pass(run["passes"]))
    found = phases.inside(rows, phases.one(rows, "ingest"), "ingest/decode", "ingest/assemble")
    return phases.seconds(found) if found else None
