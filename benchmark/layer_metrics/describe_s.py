"""Seconds of the ``describe`` spans of the window's median pass: the fused
describe of a table (``ops/describe.py::table_describe``), opened under the
scheduler node that computes it and nowhere else (a memo hit opens none), so
one span a table and pass; their sum where a pass describes several tables.
Nothing where the manifest's ``phases`` hold no such span (a program from
before it)."""

from benchmark.harness import phases
from benchmark.harness.manifest import median_pass


def read(run):
    found = [r for r in phases.rows(median_pass(run["passes"])) if r["name"] == "describe"]
    return phases.seconds(found) if found else None
