"""Seconds, in the window's median pass, of the time-series inspection's
numeric stages that aggregate a WIDE segment class (a daily grain over more
than two months: a class above ``data_transformer/datetime.py``'s
``_DENSE_SEGMENTS_MAX``): the ``ts/viz/num`` stage rows whose count
``wide_segments`` is above 0, summed (one a time column: the row holds the
call's three grains, the wide daily one beside the two narrow ones, its
dispatch, its one fetch and the frames built from it).  Nothing where no such
row carries the count (a program from before it) or no pass ran a wide grain
(a month of days is class 32)."""

from benchmark.harness import phases
from benchmark.harness.manifest import median_pass

ROW = "ts/viz/num"


def wide_rows(rows: list) -> list:
    """``rows``: a manifest's ``phases``."""
    return [r for r in rows if r["name"] == ROW and r["counts"].get("wide_segments", 0) > 0]


def read(run):
    found = wide_rows(phases.rows(median_pass(run["passes"])))
    return phases.seconds(found) if found else None
