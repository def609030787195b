"""Seconds of wall that the drift block covers in the window's median pass:
the union of the spans of the scheduler's nodes named ``drift_detector/<node>``
(rows of the manifest's ``phases`` under ``dag``: ``drift_statistics`` and
``stability_index``; they run side by side, so their sum would count a second
twice).  Each holds the reads of its own tables (``drift/read``,
``stability/read``) and its stage rows (``drift/fit``, ``drift/union``,
``drift/lut``, ``drift/sides``, ``drift/model``, ``drift/frame``;
``stability/moments``, ``stability/frame``).  Nothing where a pass runs no
such node or the manifest's ``phases`` hold no scheduler node."""

from benchmark.harness import phases
from benchmark.harness.manifest import median_pass
from benchmark.harness.setup import union_seconds

NODES = "drift_detector/"


def nodes(rows: list) -> list:
    return [r for r in rows if r["parent"] == "dag" and r["name"].startswith(NODES)]


def read(run):
    found = nodes(phases.rows(median_pass(run["passes"])))
    return union_seconds(found) if found else None
