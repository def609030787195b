"""timeseries_analyzer: records per day of the ``column`` it detects, exact.
Table: ts_daily."""

from benchmark.harness.check import exact, table


def read(out_dir, traffic, args):
    t = table(out_dir, traffic["tables"]["ts_daily"])
    return {str(d): int(n) for d, n in zip(t.iloc[:, 0], t["count"])}


def reference(frames, args):
    return {str(d)[:10]: int(n) for d, n in frames.main[args["column"]].value_counts().items()}


def compare(ans, ref, tolerances, args):
    return [exact("ts_daily", ans, ref)]
