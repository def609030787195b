"""One exact count per remaining quality check, on the columns that no
earlier treatment of the pipeline alters (each list is an argument):
``invalid``: entries whose whole part is one digit three or more times
(3333, -111.2), the one kind of invalid entry the data holds; ``unique``:
distinct values of IDness_detection; ``mode_rows``: rows holding the mode in
biasedness_detection (taken before the repeats are dropped, as the pipeline
takes it from its statistics); ``upper_outliers``: values above two of the
three upper bounds (percentile, mean + k stddev, Q3 + k IQR) the YAML
sets; ``missing``: nulls per column of nullColumns_detection.
Tables: invalidEntries_detection, IDness_detection, biasedness_detection,
outlier_detection, nullColumns_detection."""

import numpy as np

from benchmark.harness.check import exact, table

KINDS = {"invalid": ("invalidEntries_detection", "invalid_count"),
         "unique": ("IDness_detection", "unique_values"),
         "mode_rows": ("biasedness_detection", "mode_rows"),
         "upper_outliers": ("outlier_detection", "upper_outliers"),
         "missing": ("nullColumns_detection", "missing_count")}


def read(out_dir, traffic, args):
    out = {}
    for kind, (name, col) in KINDS.items():
        t = table(out_dir, traffic["tables"][name]).set_index("attribute")[col]
        out.update({f"{kind}:{c}": int(t[c]) if c in t.index else None for c in args[kind]})
    return out


def _one_digit(v) -> bool:
    whole = str(int(abs(v))) if v == v else ""
    return len(whole) >= 3 and len(set(whole)) == 1


def reference(frames, args):
    kept = frames.kept
    out = {f"invalid:{c}": int(kept[c].map(_one_digit).sum()) for c in args["invalid"]}
    out.update({f"unique:{c}": int(kept[c].nunique()) for c in args["unique"]})
    out.update({f"mode_rows:{c}": int(frames.main[c].value_counts().iloc[0]) for c in args["mode_rows"]})
    cfg = frames.pipeline["quality_checker"]["outlier_detection"]["detection_configs"]
    for c in args["upper_outliers"]:
        x = kept[c].dropna()
        q1, q3 = x.quantile(0.25), x.quantile(0.75)
        uppers = sorted([x.quantile(cfg["pctile_upper"]), x.mean() + cfg["stdev_upper"] * x.std(),
                         q3 + cfg["IQR_upper"] * (q3 - q1)], reverse=True)
        out[f"upper_outliers:{c}"] = int((x > uppers[cfg["min_validation"] - 1]).sum())
    out.update({f"missing:{c}": int(kept[c].isna().sum()) for c in args["missing"]})
    return out


def compare(ans, ref, tolerances, args):
    return [exact(kind, {k: v for k, v in ans.items() if k.startswith(kind + ":")},
                  {k: v for k, v in ref.items() if k.startswith(kind + ":")}) for kind in KINDS]
