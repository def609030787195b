"""Seconds of wall that the drift block's own reads cover in the window's
median pass: the union of the ``drift/read`` row (the source, inside the node
``drift_detector/drift_statistics``) and the ``stability/read`` rows (a period
each, inside ``drift_detector/stability_index``); eight tables in
``lending_club.vintage_drift``, five in ``income_32k.full``.  Each holds an
``io:read_dataset`` with the children an ``ingest`` has and carries ``rows``,
``columns`` and ``bytes`` (the part files').  The two nodes run side by side,
so a sum would count a second twice.  Nothing where no such row lies inside a
drift node (a program from before them)."""

from benchmark.harness import phases
from benchmark.harness.manifest import median_pass
from benchmark.harness.names import load_module
from benchmark.harness.setup import union_seconds

ROWS = ("drift/read", "stability/read")


def reads(rows: list) -> list:
    nodes = {n["name"] for n in load_module("layer_metrics", "drift_s").nodes(rows)}
    return [r for r in rows if r["name"] in ROWS and r["parent"] in nodes]


def read(run):
    found = reads(phases.rows(median_pass(run["passes"])))
    return union_seconds(found) if found else None
