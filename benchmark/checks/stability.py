"""stability_index's inputs: mean, sample stddev and (Pearson, population)
kurtosis of every numeric column of each period slice, as the pipeline
appends them to its metric history.  Table: stabilityIndex_metrics."""

import pandas as pd

from benchmark.harness.check import table, toleranced

STATS = ("mean", "stddev", "kurtosis")


def read(out_dir, traffic, args):
    t = table(out_dir, traffic["tables"]["stabilityIndex_metrics"])
    t.index = [f"{i}:{a}" for i, a in zip(t["idx"], t["attribute"])]
    return t


def reference(frames, args):
    out = {}
    for i, df in enumerate(frames.periods, start=1):
        for c in df.select_dtypes("number").columns:
            x = df[c].dropna().astype("float64")
            d = x - x.mean()
            out[f"{i}:{c}"] = {"mean": x.mean(), "stddev": x.std(ddof=1),
                               "kurtosis": (d ** 4).mean() / (d ** 2).mean() ** 2}
    return pd.DataFrame(out).T


def compare(ans, ref, tolerances, args):
    return [toleranced("si_" + s, ans[s], ref[s], tolerances["si_" + s]) for s in STATS]
