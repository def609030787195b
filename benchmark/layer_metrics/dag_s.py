"""Median over the window's passes of the scheduler's span (manifest
``scheduler.nodes``)."""

import statistics

from benchmark.harness.manifest import dag_span


def read(run):
    spans = [s for s in map(dag_span, run["passes"]) if s is not None]
    return statistics.median(spans) if spans else None
