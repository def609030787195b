"""Seconds of the ``write_main`` phase of the window's median pass: the final
dataset, a device ``Table`` fetched array by array (``write/d2h``) and written
as part files (``write/parquet``, ``write/csv``).  Every pass opens the phase;
nothing where it wrote no table (no ``write/*`` span under it: a mix without
``write_main``, or a program from before those spans)."""

from benchmark.harness import phases
from benchmark.harness.manifest import median_pass


def read(run):
    rows = phases.rows(median_pass(run["passes"]))
    wrote = [r for r in rows if r["parent"] == "write_main" and r["name"].startswith("write/")]
    span = phases.one(rows, "write_main")
    return span["end_s"] - span["start_s"] if span and wrote else None
