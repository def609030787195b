"""Rows of table-length arrays the time-series inspection brought to the host
in the window's median pass: the ``host_rows`` counts of the ``ts/*`` stage
rows inside the node ``timeseries_analyzer/inspection``, summed (every stage
that fetches carries ``fetches`` and ``host_rows``).  0 where the inspection
fetches aggregates only; a multiple of the table's padded length where a stage
fetches a column.  Nothing where no stage carries the count (a program from
before it) or no inspection ran."""

from benchmark.harness import phases
from benchmark.harness.manifest import median_pass
from benchmark.harness.names import load_module


def read(run):
    rows = phases.rows(median_pass(run["passes"]))
    node = phases.one(rows, load_module("layer_metrics", "ts_inspect_s").NODE, parent="dag")
    counted = [r["counts"]["host_rows"] for r in rows if r["name"].startswith("ts/") and "host_rows" in r["counts"]
               and node and node["start_s"] <= r["start_s"] and r["end_s"] <= node["end_s"]]
    return sum(counted) if counted else None
