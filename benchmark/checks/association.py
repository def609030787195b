"""association_evaluator on the columns no quality treatment alters but by
its null imputation (median): Pearson correlation of each pair of
``correlation`` columns; information value (natural log) and information
gain (bits) of each ``iv_ig`` column, unbinned, against the YAML's label
and event (with the upstream's continuity correction where a category has
no event, or nothing else).  All on the table after the repeats are dropped.
Tables: correlation_matrix, IV_calculation, IG_calculation."""

import itertools

import numpy as np
import pandas as pd

from benchmark.harness.check import table, toleranced


def read(out_dir, traffic, args):
    cm = table(out_dir, traffic["tables"]["correlation_matrix"]).set_index("attribute")
    return {"correlation": pd.Series({f"{a}~{b}": cm.loc[a, b] if a in cm.index and b in cm else np.nan
                                      for a, b in itertools.combinations(args["correlation"], 2)}),
            "iv": table(out_dir, traffic["tables"]["IV_calculation"]).set_index("attribute")["iv"],
            "ig": table(out_dir, traffic["tables"]["IG_calculation"]).set_index("attribute")["ig"]}


def _entropy_bits(p: pd.Series) -> pd.Series:
    return -(p * np.log2(p.where(p > 0, 1.0)) + (1 - p) * np.log2((1 - p).where(p < 1, 1.0)))


def reference(frames, args):
    kept = frames.kept
    num = kept[args["correlation"]].astype("float64")
    num = num.fillna(num.median())
    iv_cfg = frames.pipeline["association_evaluator"]["IV_calculation"]
    event = kept[iv_cfg["label_col"]] == iv_cfg["event_label"]
    iv, ig = {}, {}
    for c in args["iv_ig"]:
        g = event.groupby(kept[c]).agg(["sum", "count"])
        ev, non = g["sum"].astype("float64"), (g["count"] - g["sum"]).astype("float64")
        pe, pn = ev / ev.sum(), non / non.sum()
        # the upstream's continuity correction: where a category has no event, or
        # nothing else, its weight of evidence is taken with half a row added to both
        woe = np.log((pn / pe).where((ev > 0) & (non > 0),
                                     ((non + 0.5) / non.sum()) / ((ev + 0.5) / ev.sum())))
        iv[c] = float(((pn - pe) * woe).sum())
        share = g["count"] / g["count"].sum()
        ig[c] = float(_entropy_bits(pd.Series([event.mean()]))[0]
                      - (share * _entropy_bits(g["sum"] / g["count"])).sum())
    return {"correlation": pd.Series({f"{a}~{b}": num[a].corr(num[b])
                                      for a, b in itertools.combinations(args["correlation"], 2)}),
            "iv": pd.Series(iv), "ig": pd.Series(ig)}


def compare(ans, ref, tolerances, args):
    return [toleranced(k, ans[k], ref[k], tolerances[k]) for k in ("correlation", "iv", "ig")]
