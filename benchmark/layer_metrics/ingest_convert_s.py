"""Seconds of the ``ingest/convert`` spans inside ``ingest`` in the window's
median pass: one per column whose Arrow type is converted on the way in (a
decimal to float64, a date to seconds), in Arrow and numpy.  A program that
converts says so on ``ingest/assemble`` (count ``arrow_typed``: how many
columns), so a pass that had nothing to convert reads 0.0; nothing where the
span does not carry the count (a program from before the conversion)."""

from benchmark.harness import phases
from benchmark.harness.manifest import median_pass


def read(run):
    rows = phases.rows(median_pass(run["passes"]))
    ingest = phases.one(rows, "ingest")
    if not any("arrow_typed" in r["counts"] for r in phases.inside(rows, ingest, "ingest/assemble")):
        return None
    return float(phases.seconds(phases.inside(rows, ingest, "ingest/convert")))
