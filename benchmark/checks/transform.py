"""The transformers, read where each leaves its table, on columns whose
values reach it unaltered but for the median imputation; rows are matched
by ``id``.  ``sqrt``: feature_transformation, every row; ``bins``:
attribute_binning's ten equal-frequency bins (right-closed cuts at the
deciles) of those square roots, exact; ``event_rate``: cat_to_num_supervised,
each category's share of events after outlier_categories has lumped what
lies beyond its coverage of the most frequent ones; the final dataset holds
the rows the quality checks keep, each with its label.
Tables: feature_transformation, attribute_binning, cat_to_num_supervised, final_dataset."""

import numpy as np
import pandas as pd

from benchmark.harness.check import exact, table, toleranced


def _stack(df: pd.DataFrame, cols) -> pd.Series:
    s = df[list(cols)].stack(future_stack=True)
    s.index = [f"{c}@{i}" for i, c in s.index]
    return s


def read(out_dir, traffic, args):
    t = {k: table(out_dir, traffic["tables"][k]).set_index(args["id"]) for k in
         ("feature_transformation", "attribute_binning", "cat_to_num_supervised", "final_dataset")}
    label = args["label"]
    return {"sqrt": _stack(t["feature_transformation"], args["sqrt"]),
            "bins": _stack(t["attribute_binning"], args["sqrt"]).to_dict(),
            "event_rate": _stack(t["cat_to_num_supervised"], args["event_rate"]),
            "final_rows": t["final_dataset"][label].to_dict()}


def reference(frames, args):
    pipe = frames.pipeline
    kept = frames.kept.set_index(args["id"])
    num = kept[args["sqrt"]].astype("float64")
    root = np.sqrt(num.fillna(num.median()))
    bins = pd.DataFrame({c: np.searchsorted([root[c].quantile(i / 10) for i in range(1, 10)],
                                            root[c].to_numpy(), side="left") + 1 for c in root},
                        index=root.index)
    sup = pipe["transformers"]["categorical_encoding"]["cat_to_num_supervised"]
    coverage = pipe["transformers"]["categorical_outliers"]["outlier_categories"]["coverage"]
    event = (kept[sup["label_col"]] == sup["event_label"]).astype("float64")
    rates = {}
    for c in args["event_rate"]:
        share = kept[c].value_counts(normalize=True)
        top = share.index[:int((share.cumsum() < coverage).sum()) + 1]
        lumped = kept[c].where(kept[c].isin(top), "\0rest")
        rates[c] = lumped.map(event.groupby(lumped).mean())
    return {"sqrt": _stack(root, args["sqrt"]), "bins": _stack(bins, args["sqrt"]).to_dict(),
            "event_rate": _stack(pd.DataFrame(rates), args["event_rate"]),
            "final_rows": kept[args["label"]].to_dict()}


def compare(ans, ref, tolerances, args):
    return [toleranced("sqrt", ans["sqrt"], ref["sqrt"], tolerances["sqrt"]),
            exact("bins", ans["bins"], ref["bins"]),
            toleranced("event_rate", ans["event_rate"], ref["event_rate"], tolerances["event_rate"]),
            exact("final_rows", ans["final_rows"], ref["final_rows"])]
