"""Descriptive statistics of the numeric columns against float64 pandas:
exact row count and per-column counts; mean, stddev, min, max and median
each within the configuration's tolerance of that name.
args: ``columns``.  Tables: global_summary, measures_of_counts,
measures_of_centralTendency, measures_of_dispersion, measures_of_percentiles."""

import numpy as np
import pandas as pd

from benchmark.harness.check import exact, table, toleranced

STATS = ("mean", "stddev", "min", "max", "median")


def read(out_dir, traffic, args):
    t = traffic["tables"]
    gs = table(out_dir, t["global_summary"])
    ours = (table(out_dir, t["measures_of_counts"])
            .merge(table(out_dir, t["measures_of_centralTendency"]), on="attribute")
            .merge(table(out_dir, t["measures_of_dispersion"]), on="attribute")
            .merge(table(out_dir, t["measures_of_percentiles"]), on="attribute")
            .set_index("attribute").rename(columns={"fill_count": "count"}))
    return {"rows": int(float(dict(zip(gs["metric"], gs["value"]))["rows_count"])),
            "summary": ours[["count", *STATS]].astype("float64")}


def _summary(num: pd.DataFrame) -> pd.DataFrame:
    return pd.DataFrame({"count": num.count(), "mean": num.mean(), "stddev": num.std(ddof=1),
                         "min": num.min(), "max": num.max(), "median": num.median()})


def reference(frames, args):
    return {"rows": len(frames.main),
            "summary": _summary(frames.main[args["columns"]].astype("float64"))}


def control(ref, frames, args):
    """The control: the reference computed from the table held in bfloat16,
    each answer rounded to bfloat16 (the mildest form of "computed in
    bfloat16": sums still accumulate in float64), in the program's place."""
    import ml_dtypes

    def bf16(x):
        return np.asarray(x, dtype=np.float64).astype(ml_dtypes.bfloat16).astype(np.float64)

    low = _summary(pd.DataFrame({c: bf16(frames.main[c].to_numpy()) for c in args["columns"]}))
    for stat in STATS:
        low[stat] = bf16(low[stat].to_numpy())
    return {**ref, "summary": low}


def compare(ans, ref, tolerances, args):
    cols = ref["summary"].index
    rows = [exact("rows", ans["rows"], ref["rows"]),
            exact("count", {c: int(v) for c, v in ans["summary"]["count"].reindex(cols).fillna(-1).items()},
                  {c: int(v) for c, v in ref["summary"]["count"].items()})]
    return rows + [toleranced(s, ans["summary"][s], ref["summary"][s], tolerances[s]) for s in STATS]
