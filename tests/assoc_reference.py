"""The plain reference of the association block for the tier-1 tests: a copy of
the reference half of ``benchmark/checks/association_binned.py`` (the
benchmark keeps its own, so that neither side of a comparison can move the
other).  numpy and pandas float64; it imports nothing of ``anovos_tpu``.

Every numeric value is taken as the float32 the table stores; equal-frequency
cut-offs are the order statistics ``v[(j * (n - 1)) // B]`` of the values
present; a value's bin is 1 + the number of cut-offs below it (value <=
cut-off stays under it); the rows where an attribute is null are a group of
their own; a row whose label is null is in no group; IV with the natural log
and the upstream's half-row correction; IG in bits against the event rate over
all rows; the correlation over the rows complete in all its columns.
``FAULTS`` names four wrong answers that the tests show to differ."""

import itertools

import numpy as np
import pandas as pd

FAULTS = ("nulls_dropped", "strict_cutoff", "no_correction", "pairwise_correlation")


def stored(x) -> np.ndarray:
    """A numeric column as the table stores it: float32 values, NaN for null."""
    return pd.to_numeric(pd.Series(x), errors="coerce").to_numpy(np.float64, na_value=np.nan) \
        .astype(np.float32).astype(np.float64)


def cutoffs(x: np.ndarray, bin_size: int) -> np.ndarray:
    """The ``bin_size - 1`` equal-frequency cut-offs of the values present."""
    v = np.sort(x[~np.isnan(x)])
    if not len(v):
        return np.full(bin_size - 1, np.nan)
    return v[(np.arange(1, bin_size) * (len(v) - 1)) // bin_size]


def bins(x: np.ndarray, bin_size: int, strict: bool = False) -> np.ndarray:
    """1 + the number of cut-offs below a value; 0 for a null.  ``strict``
    is the fault: a value equal to a cut-off counted as above it."""
    b = 1 + np.searchsorted(cutoffs(x, bin_size), x, side="right" if strict else "left")
    return np.where(np.isnan(x), 0, b)


def group_counts(groups: np.ndarray, event: np.ndarray, labelled: np.ndarray, drop=None):
    """(non-events, events) of every group that has a labelled row."""
    keep = labelled if drop is None else labelled & (groups != drop)
    g, e = groups[keep], event[keep]
    _, codes = np.unique(g, return_inverse=True)
    tot = np.bincount(codes).astype(np.float64)
    ev = np.bincount(codes, weights=e).astype(np.float64)
    return tot - ev, ev


def information_value(non: np.ndarray, ev: np.ndarray, correction: bool = True) -> float:
    n_all, e_all = non.sum(), ev.sum()
    if n_all == 0 or e_all == 0:
        return float("nan")
    pn, pe = non / n_all, ev / e_all
    both = (non > 0) & (ev > 0)
    with np.errstate(divide="ignore", invalid="ignore"):
        woe = np.log(pn / pe)
        if correction:
            woe = np.where(both, woe, np.log(((non + 0.5) / n_all) / ((ev + 0.5) / e_all)))
            return float(np.sum((pn - pe) * woe))
    return float(np.sum(((pn - pe) * woe)[both]))  # the fault: such a group left out


def _entropy_bits(p):
    p = np.asarray(p, np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        h = -(p * np.log2(p) + (1 - p) * np.log2(1 - p))
    return np.where((p > 0) & (p < 1), h, 0.0)


def information_gain(non: np.ndarray, ev: np.ndarray, table_rate: float) -> float:
    tot = non + ev
    return float(_entropy_bits(table_rate) - np.sum(tot / tot.sum() * _entropy_bits(ev / tot)))


def complete_case_correlation(block: np.ndarray):
    """(matrix, complete rows) of a (rows, k) float64 block with NaN for null."""
    rows = block[~np.isnan(block).any(axis=1)]
    centred = rows - rows.mean(axis=0) if len(rows) else rows
    cov = centred.T @ centred
    sd = np.sqrt(np.diag(cov))
    with np.errstate(divide="ignore", invalid="ignore"):
        corr = cov / np.outer(sd, sd)
    corr[(sd == 0)[:, None] | (sd == 0)[None, :]] = np.nan
    return corr, len(rows)


def pairwise_correlation(block: np.ndarray) -> np.ndarray:
    """The fault: every pair over the rows complete in those two columns."""
    return pd.DataFrame(block).corr().to_numpy()


def answers(df: pd.DataFrame, args: dict, label_col: str, event_label, bin_size: int, fault=None,
            numeric=stored) -> dict:
    """The reference's answers on a frame; ``numeric`` turns a numeric column
    into float64 values with NaN for null (the control's holds them in bfloat16)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no fault {fault!r}")
    label = df[label_col]
    labelled = label.notna().to_numpy()
    event = (label == event_label).to_numpy().astype(np.float64)
    rate = event[labelled].sum() / max(len(df), 1)
    drop = 0 if fault == "nulls_dropped" else None
    iv, ig = {}, {}
    for c in args["numeric"] + args["categorical"]:
        if c in args["numeric"]:
            groups = bins(numeric(df[c]), bin_size, strict=fault == "strict_cutoff")
        else:
            groups = 1 + pd.factorize(df[c], sort=True)[0]  # a null is -1: group 0
        non, ev = group_counts(groups, event, labelled, drop)
        iv[c] = information_value(non, ev, correction=fault != "no_correction")
        ig[c] = information_gain(non, ev, rate)
    block = np.column_stack([numeric(df[c]) for c in args["correlation"]])
    if fault == "pairwise_correlation":
        corr, complete = pairwise_correlation(block), complete_case_correlation(block)[1]
    else:
        corr, complete = complete_case_correlation(block)
    index = {c: i for i, c in enumerate(args["correlation"])}
    pairs = {f"{a}~{b}": corr[index[a], index[b]] for a, b in itertools.combinations(args["correlation"], 2)}
    outside_mode = {c: int(df[c].notna().sum() - df[c].value_counts().iloc[0]) if df[c].notna().any() else 0
                    for c in args["numeric"] + args["categorical"]}
    return {"correlation": pd.Series(pairs, dtype="float64"), "iv": pd.Series(iv), "ig": pd.Series(ig),
            "complete_rows": int(complete),
            "sure": sorted(c for c, k in outside_mode.items() if k >= args["sure_rows"]),
            "constant": sorted(c for c in outside_mode if df[c].nunique(dropna=True) < 2)}
