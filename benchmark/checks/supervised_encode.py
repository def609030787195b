"""The final dataset of the ``supervised_encode`` mix against float64 numpy
and pandas on the same files, rows matched by position (the final dataset
keeps the input's row order), every row of every column:

``zscore``: each integer column, its nulls filled with the lower median
(``quantile(0.5, interpolation="lower")``: a value of the column), then
(x - mean) / stddev (sample, n - 1), toleranced; ``event_rate``: each
categorical column, its nulls filled with the mode (a tie goes to the first
value in code-point order, as the program's ``mode_from_counts`` documents),
each row's value its category's share of clicks, toleranced (the program
holds the share to 4 decimals, the reference does not round); exact: the row
count, the column names in order, each column's kind (the label an integer,
every other column a float), that no null is left, the label row by row, and
from the saved imputation model the fill value of every integer column with
nulls and the mode of every categorical with nulls.
args: ``label``, ``integers``, ``categoricals``.  Tables: final_dataset
(every part file of it), imputation_model."""

import glob
import os

import numpy as np
import pandas as pd

from benchmark.harness.check import exact, toleranced


def _kind(dtype) -> str:
    for name, test in (("integer", pd.api.types.is_integer_dtype), ("float", pd.api.types.is_float_dtype)):
        if test(dtype):
            return name
    return str(dtype)


def read(out_dir, traffic, args):
    parts = sorted(glob.glob(os.path.join(out_dir, traffic["tables"]["final_dataset"])))
    df = pd.concat([pd.read_parquet(f) for f in parts], ignore_index=True)
    model = pd.read_parquet(glob.glob(os.path.join(out_dir, traffic["tables"]["imputation_model"]))[0])
    fitted = {kind: dict(zip(rows["attribute"], rows["fill_value"])) for kind, rows in model.groupby("kind")}
    return {"rows": len(df),
            "names": dict(enumerate(df.columns)),
            "kinds": {c: _kind(df[c].dtype) for c in df.columns},
            "nulls": {c: int(n) for c, n in df.isna().sum().items()},
            "label": df[args["label"]],
            "zscore": df.reindex(columns=args["integers"]).astype("float64"),
            "event_rate": df.reindex(columns=args["categoricals"]).astype("float64"),
            "fill_values": {c: float(v) for c, v in fitted.get("num", {}).items()},
            "modes": {c: str(v) for c, v in fitted.get("cat", {}).items()}}


def _fill_values(num: pd.DataFrame) -> dict:
    return {c: float(num[c].quantile(0.5, interpolation="lower")) for c in num if num[c].isna().any()}


def _zscore(num: pd.DataFrame, fills: dict) -> pd.DataFrame:
    filled = num.fillna(fills)
    return (filled - filled.mean()) / filled.std(ddof=1)


def _event_rates(main: pd.DataFrame, cats, event: np.ndarray):
    """Per categorical column the mode (where it has nulls) and, per row, the
    share of events among the rows of its category after the fill."""
    modes, rates = {}, {}
    for c in cats:
        codes, values = pd.factorize(main[c])  # a null: code -1
        count = np.bincount(codes[codes >= 0], minlength=len(values))
        if (codes < 0).any():
            modes[c] = str(min(values[count == count.max()]))
            codes = np.where(codes < 0, np.flatnonzero(values == modes[c])[0], codes)
            count = np.bincount(codes, minlength=len(values))
        rates[c] = (np.bincount(codes, weights=event, minlength=len(values)) / count)[codes]
    return modes, pd.DataFrame(rates)


def reference(frames, args):
    main = frames.main
    sup = frames.pipeline["transformers"]["categorical_encoding"]["cat_to_num_supervised"]
    num = main[args["integers"]].astype("float64")
    fills = _fill_values(num)
    event = (main[sup["label_col"]] == sup["event_label"]).to_numpy("float64")
    modes, rates = _event_rates(main, args["categoricals"], event)
    return {"rows": len(main),
            "names": dict(enumerate(main.columns)),
            "kinds": {c: "integer" if c == args["label"] else "float" for c in main.columns},
            "nulls": {c: 0 for c in main.columns},
            "label": main[args["label"]],
            "zscore": _zscore(num, fills), "event_rate": rates,
            "fill_values": fills, "modes": modes}


def control(ref, frames, args):
    """The control: the reference computed from the integers held in
    bfloat16, each answer rounded to bfloat16 (sums still accumulate in
    float64), in the program's place."""
    import ml_dtypes

    def bf16(x):
        return np.asarray(x, dtype=np.float64).astype(ml_dtypes.bfloat16).astype(np.float64)

    low = frames.main[args["integers"]].astype("float64").apply(bf16)
    fills = _fill_values(low)
    return {**ref, "fill_values": fills, "zscore": _zscore(low, fills).apply(bf16),
            "event_rate": ref["event_rate"].apply(bf16)}


def _worst(name: str, got: pd.DataFrame, want: pd.DataFrame, tol: dict) -> dict:
    """``toleranced`` over every column of a frame, every row: the worst column's row."""
    rows = []
    for c in want.columns:
        have = got[c] if c in got else pd.Series(np.nan, index=want.index)
        r = toleranced(name, have, want[c], tol)
        rows.append(dict(r, detail=f"{c} row {r['detail'][len('worst '):]}"))
    return max(rows, key=lambda r: r["value"])


def compare(ans, ref, tolerances, args):
    same_rows = len(ans["label"]) == len(ref["label"])
    changed = int((ans["label"].to_numpy() != ref["label"].to_numpy()).sum()) if same_rows else len(ref["label"])
    return [exact("rows", ans["rows"], ref["rows"]),
            exact("column_names", ans["names"], ref["names"]),
            exact("column_kinds", ans["kinds"], ref["kinds"]),
            exact("nulls_left", ans["nulls"], ref["nulls"]),
            exact("label_rows_changed", changed, 0),
            exact("fill_values", ans["fill_values"], ref["fill_values"]),
            exact("modes", ans["modes"], ref["modes"]),
            _worst("zscore", ans["zscore"], ref["zscore"], tolerances["zscore"]),
            _worst("event_rate", ans["event_rate"], ref["event_rate"], tolerances["event_rate"])]
