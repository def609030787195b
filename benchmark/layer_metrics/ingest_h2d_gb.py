"""Bytes handed to ``device_put`` inside ``ingest`` (the ``bytes`` count of
its ``ingest/h2d`` spans), in the window's median pass, in GB (1e9 bytes)."""

from benchmark.harness import phases
from benchmark.harness.manifest import median_pass


def read(run):
    rows = phases.rows(median_pass(run["passes"]))
    found = phases.inside(rows, phases.one(rows, "ingest"), "ingest/h2d")
    return sum(r["counts"].get("bytes", 0) for r in found) / 1e9 if found else None
