"""Median over the window's passes of the pass wall minus the scheduler's
span: ingest, column edits and the final writes, by subtraction (the
program has no span for them yet)."""

import statistics

from benchmark.harness.manifest import dag_span


def read(run):
    out = [p["wall_s"] - dag_span(p) for p in run["passes"] if dag_span(p) is not None]
    return statistics.median(out) if out else None
