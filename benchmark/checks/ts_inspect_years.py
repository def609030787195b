"""``ts_inspect.py`` for a table whose time columns run over years, have empty
cells, arrive as dates, and stand beside no categorical column: the same
answers against the same float64 pandas reference (that file's ``_answers``,
``compare`` and ``control``, as they stand), plus two exact ones:

``null_rows``: the rows of the table without a value in a time column, the
table's length less the records the landscape counts (``ts_stats.csv`` holds
the share to 4 decimals only); the reference counts the empty cells.
``date_hours``: a column that the files hold as a date has every record in
hour 0 and in ``late_hours``: ``ts_hourly_<col>.csv`` and
``ts_daypart_<col>.csv`` are one row each.

What the old file cannot take and this one can: ``categorical`` empty, so that
no ``ts_cat_daily_<col>.csv`` is written or named among the mix's tables; a
parquet ``date32`` column, which pandas reads as ``datetime.date`` objects.
args: ``timestamps``, ``numeric``, ``categorical``, ``max_days``, ``period``.
Tables: ts_stats, ts_landscape and, per time column, ts_daily, ts_hourly,
ts_weekly, ts_daypart, ts_num_daily, ts_num_hourly, ts_num_weekly,
ts_decompose, ts_stationarity, and ts_cat_daily where ``categorical`` names a
column."""

import pandas as pd

from benchmark.harness.check import exact
from benchmark.harness.names import load_module

base = load_module("checks", "ts_inspect")  # this module's own copy of that file
TOLERANCED = base.TOLERANCED

_csv = base._csv
_NO_CATEGORIES = pd.DataFrame(columns=["date", "attribute", "category", "count"])
# the old reader opens a category table a time column: one that the mix does not name reads as empty
base._csv = lambda out_dir, traffic, name: (
    _NO_CATEGORIES if name.startswith("ts_cat_daily_") and name not in traffic["tables"] else _csv(out_dir, traffic, name))


def read(out_dir, traffic, args):
    ans = base.read(out_dir, traffic, args)
    ans["hours_seen"] = {
        col: (sorted(int(h) for h in _csv(out_dir, traffic, f"ts_hourly_{col}")["hour"]),
              sorted(_csv(out_dir, traffic, f"ts_daypart_{col}")["daypart"]))
        for col in args["timestamps"]}
    return ans


class _Typed:
    """``frames`` with the date columns of ``main`` as timestamps at midnight."""

    def __init__(self, frames, timestamps):
        self.main = frames.main.copy(deep=False)
        self.dates = [c for c in timestamps if self.main[c].dtype == object]
        for c in self.dates:
            self.main[c] = pd.to_datetime(self.main[c]).astype("datetime64[s]")


def _more(ans, typed, args):
    ans["table_rows"] = len(typed.main)
    ans["null_rows"] = {c: int(typed.main[c].isna().sum()) for c in args["timestamps"]}
    ans["date_hours"] = {c: ([0], ["late_hours"]) for c in typed.dates}
    return ans


def reference(frames, args):
    typed = _Typed(frames, args["timestamps"])
    return _more(base.reference(typed, args), typed, args)


def control(ref, frames, args):
    typed = _Typed(frames, args["timestamps"])
    return _more(base.control(ref, typed, args), typed, args)


def compare(ans, ref, tolerances, args):
    rows = base.compare(ans, ref, tolerances, args)
    if "hours_seen" in ans:  # what a pass left (not the reference or its control, which carry the two answers)
        ans = dict(ans, date_hours={c: ans["hours_seen"][c] for c in ref["date_hours"]},
                   null_rows={c: ref["table_rows"] - ans["landscape"].get(f"{c}|records", 0) for c in args["timestamps"]})
    return rows + [exact("null_rows", ans["null_rows"], ref["null_rows"]),
                   exact("date_hours", ans["date_hours"], ref["date_hours"])]
