"""Median over the window's passes of the sum of ``block_seconds`` over the
scheduler's span: how many blocks ran side by side on average."""

import statistics

from benchmark.harness.manifest import dag_span


def read(run):
    out = []
    for p in run["passes"]:
        blocks = p["manifest"].get("block_seconds") or {}
        if blocks and dag_span(p):
            out.append(sum(blocks.values()) / dag_span(p))
    return statistics.median(out) if out else None
