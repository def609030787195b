"""Median over the window's untraced passes of the ``ingest`` phase span:
the read of the input dataset into a device table and the column edits
chained to it (manifest ``phases``)."""

import statistics

from benchmark.harness import phases


def read(run):
    spans = [phases.one(phases.rows(p), "ingest") for p in run["passes"]]
    out = [s["end_s"] - s["start_s"] for s in spans if s is not None]
    return statistics.median(out) if out else None
