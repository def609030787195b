"""Bytes copied from chip to chip to re-place a table for a scheduler node
(the ``bytes`` count of the ``place/d2d`` spans, ``Table.with_runtime``), in
the window's median pass, in GB (1e9 bytes).  0.0 where the pass made no such
copy; nothing where the manifest's ``phases`` hold no scheduler node (a
program from before the span: its copies went unrecorded, not unmade)."""

from benchmark.harness import phases
from benchmark.harness.manifest import median_pass


def read(run):
    rows = phases.rows(median_pass(run["passes"]))
    if not any(r["parent"] == "dag" for r in rows):
        return None
    return sum(r["counts"].get("bytes", 0) for r in rows if r["name"] == "place/d2d") / 1e9
