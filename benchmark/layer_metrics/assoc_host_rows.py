"""Rows of table-length arrays the association block brought to the host in
the window's median pass: the ``host_rows`` counts of the ``assoc/*`` stage
rows inside the nodes ``association_evaluator/<measure>``, summed (a stage
that fetches carries ``fetches`` and ``host_rows``, counted as
``ts_host_rows`` counts them: every fetched array as long as the table).  0
where the block fetches counts and matrices only; twice the table's padded
length for every numeric column that is grouped by its values on the host.
Nothing where no stage carries the count (a program from before it) or no
association node ran."""

from benchmark.harness import phases
from benchmark.harness.manifest import median_pass
from benchmark.harness.names import load_module


def read(run):
    rows = phases.rows(median_pass(run["passes"]))
    nodes = load_module("layer_metrics", "association_s").nodes(rows)
    counted = [r["counts"]["host_rows"] for r in rows
               if r["name"].startswith("assoc/") and "host_rows" in r["counts"]
               and any(n["start_s"] <= r["start_s"] and r["end_s"] <= n["end_s"] for n in nodes)]
    return sum(counted) if counted else None
