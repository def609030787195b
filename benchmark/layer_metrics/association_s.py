"""Seconds of wall that the association block covers in the window's median
pass: the union of the spans of the scheduler's nodes named
``association_evaluator/<measure>`` (rows of the manifest's ``phases`` under
``dag``: ``correlation_matrix``, ``IV_calculation``, ``IG_calculation``,
``variable_clustering``; they run side by side, so their sum would count a
second twice).  Each holds its ``assoc/*`` stage rows (``assoc/bin``,
``assoc/group_counts``, ``assoc/corr``, ``assoc/prep``, ``assoc/varclus``,
``assoc/write``, and ``assoc/wait`` where a measure waits for another's
counts).  Nothing where a pass runs no such node or the manifest's ``phases``
hold no scheduler node."""

from benchmark.harness import phases
from benchmark.harness.manifest import median_pass

NODES = "association_evaluator/"


def nodes(rows: list) -> list:
    return [r for r in rows if r["parent"] == "dag" and r["name"].startswith(NODES)]


def covered(spans: list) -> float:
    """The length of the union of ``(start_s, end_s)`` intervals."""
    total, end = 0.0, float("-inf")
    for start, stop in sorted((r["start_s"], r["end_s"]) for r in spans):
        total += max(0.0, stop - max(start, end))
        end = max(end, stop)
    return total


def read(run):
    found = nodes(phases.rows(median_pass(run["passes"])))
    return covered(found) if found else None
