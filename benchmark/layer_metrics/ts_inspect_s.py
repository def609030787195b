"""Seconds of the time-series inspection in the window's median pass: the
span of the scheduler's node ``timeseries_analyzer/inspection`` (a row of the
manifest's ``phases`` under ``dag``), which holds a ``ts/eligibility`` and a
``ts/viz`` stage per timestamp column, ``ts/landscape`` and ``ts/write``.
Nothing where a pass runs no inspection or the manifest's ``phases`` hold no
scheduler node."""

from benchmark.harness import phases
from benchmark.harness.manifest import median_pass

NODE = "timeseries_analyzer/inspection"


def read(run):
    node = phases.one(phases.rows(median_pass(run["passes"])), NODE, parent="dag")
    return phases.seconds([node]) if node else None
