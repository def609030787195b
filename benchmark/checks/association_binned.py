"""What a pass of the ``association`` mix left against a plain reference of
the upstream's ``association_evaluator`` section (Anovos v1.1.0
``data_analyzer/association_evaluator.py``: ``correlation_matrix``,
``IV_calculation``, ``IG_calculation``, ``variable_clustering``) on the same
parquet files: numpy and pandas float64, nothing of ``anovos_tpu``.

The reference takes every numeric value as the float32 the table stores
(``float64(float32(x))``), then:

bins        of a numeric attribute, ``bin_size`` B by equal frequency: the
            cut-offs are the order statistics ``v[(j * (n - 1)) // B]``, j =
            1 .. B - 1, of the n values present, sorted (the lower one: an
            element of the column, as Spark's ``approxQuantile`` returns;
            exact where Spark's is approximate, which is the one departure
            from the upstream and the program's too); a value's bin is 1 + the
            number of cut-offs below it (value <= cut-off stays under it).
            Cut-offs may be equal (a 0 / 1 flag, a sentinel on 18 % of the
            rows): the bins between them are then empty and are no group.
groups      of an attribute: its bins or its categories, and the rows where it
            is null as one more group (Spark's ``groupBy`` keeps them).  A row
            whose label is null is in no group.
IV          ``sum((n% - e%) * ln(n% / e%))`` over the groups, n% and e% a
            group's share of the non-events and of the events; a group
            without an event, or with nothing else, takes the upstream's
            half-row correction ``ln(((n + 0.5) / N) / ((e + 0.5) / E))``.
            Undefined (NaN) where the table has no event or nothing else.
IG          in bits: the entropy of the table's event rate (events over all
            rows, as the upstream counts them) less the groups' entropies
            weighted by their share of the labelled rows; a group of one
            class adds nothing.
correlation Pearson, over the rows complete in ALL the ``correlation``
            columns (``VectorAssembler(handleInvalid="skip")``), every pair;
            undefined where either column is constant over those rows.

Compared (tolerances in the configuration, ``guarantees``): every ``iv``,
every ``ig``, every defined correlation, each by its worst entry; exactly: the
pairs whose correlation is undefined, the count of complete rows (the program
states it on its ``assoc/corr`` stage row of the manifest), and that
``variable_clustering`` lists an attribute once, none it was not given, every
attribute with ``sure_rows`` or more rows outside its most frequent value (a
100,000-row sample of the upstream's cannot lose those) and none that is
constant.  Its clusters are held to the same bytes in every pass and to
nothing else: the upstream's VarClus has no plain reference yet.

``reference(frames, args, fault=...)`` computes a named wrong answer instead
(``FAULTS``), and ``control`` the reference from the table held in bfloat16:
the tests and PERF.md show each of them not ``correct``.

args: ``correlation`` (the columns of the matrix), ``numeric`` and
``categorical`` (the attributes of IV and IG), ``sure_rows``.  Tables:
correlation_matrix, IV_calculation, IG_calculation, variable_clustering."""

import itertools
import json
import os

import numpy as np
import pandas as pd

from benchmark.harness.check import exact, table, toleranced

FAULTS = ("nulls_dropped", "strict_cutoff", "no_correction", "pairwise_correlation")
CORR_NODE, CORR_ROW = "association_evaluator/correlation_matrix", "assoc/corr"


# ---------------------------------------------------------------- what a pass left ----
def complete_rows_stated(manifest: dict):
    """The ``complete_rows`` count of the ``assoc/corr`` row inside the node
    ``association_evaluator/correlation_matrix``; None where no row states it."""
    rows = manifest.get("phases") or []
    node = next((r for r in rows if r["name"] == CORR_NODE), None)
    found = [r["counts"]["complete_rows"] for r in rows
             if node and r["name"] == CORR_ROW and "complete_rows" in r["counts"]
             and node["start_s"] <= r["start_s"] and r["end_s"] <= node["end_s"]]
    return found[0] if len(found) == 1 else None


def read(out_dir, traffic, args):
    t = traffic["tables"]
    cm = table(out_dir, t["correlation_matrix"]).set_index("attribute")
    pairs = {f"{a}~{b}": cm.loc[a, b] if a in cm.index and b in cm else np.nan
             for a, b in itertools.combinations(args["correlation"], 2)}
    vc = table(out_dir, t["variable_clustering"])
    try:
        with open(os.path.join(out_dir, traffic["manifest"])) as f:
            stated = complete_rows_stated(json.load(f))
    except OSError:
        stated = None
    return {"correlation": pd.Series(pairs, dtype="float64"),
            "iv": table(out_dir, t["IV_calculation"]).set_index("attribute")["iv"],
            "ig": table(out_dir, t["IG_calculation"]).set_index("attribute")["ig"],
            "complete_rows": -1 if stated is None else int(stated),
            "clustered": [str(a) for a in vc["Attribute"]]}


# --------------------------------------------------------------- the plain reference ----
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


def _settings(frames):
    iv = frames.pipeline["association_evaluator"]["IV_calculation"]
    enc = iv.get("encoding_configs") or {}
    if enc.get("bin_method", "equal_frequency") != "equal_frequency" or enc.get("monotonicity_check", 0):
        raise ValueError("the reference bins by equal frequency without the monotonicity search")
    return iv["label_col"], iv["event_label"], int(enc.get("bin_size", 10))


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


def reference(frames, args, fault=None):
    return answers(frames.main, args, *_settings(frames), fault=fault)


def control(ref, frames, args):
    """The control: the reference from the table held in bfloat16 (every
    numeric value rounded to bfloat16 before it is binned or correlated; the
    arithmetic stays float64, the mildest form of "computed in bfloat16")."""
    import ml_dtypes

    def bf16(x):
        return stored(x).astype(ml_dtypes.bfloat16).astype(np.float64)

    low = answers(frames.main, args, *_settings(frames), numeric=bf16)
    return {**ref, "iv": low["iv"], "ig": low["ig"], "correlation": low["correlation"]}


# ------------------------------------------------------------------- the comparison ----
def compare(ans, ref, tolerances, args):
    want = ref["correlation"]
    got = ans["correlation"].reindex(want.index)
    undefined = {k: True for k in want.index[want.isna()]}
    rows = [exact("complete_rows", ans["complete_rows"], ref["complete_rows"]),
            exact("correlation_undefined", {k: True for k in got.index[got.isna()]}, undefined)]
    defined = want.index[want.notna()]
    if len(defined):
        rows.append(toleranced("correlation", got[defined], want[defined], tolerances["correlation"]))
    rows += [_with_undefined(k, ans[k], ref[k], tolerances[k]) for k in ("iv", "ig")]
    listed = ans["clustered"] if "clustered" in ans else ref["sure"]  # the reference against itself lists what it must
    given = set(args["numeric"] + args["categorical"])
    wrong = ({f"twice {a}" for a in listed if listed.count(a) > 1} | {f"not given {a}" for a in set(listed) - given}
             | {f"missing {a}" for a in set(ref["sure"]) - set(listed)}
             | {f"constant {a}" for a in set(ref["constant"]) & set(listed)})
    rows.append({"name": "varclus_attributes", "value": len(wrong), "limit": 0, "ok": not wrong,
                 "detail": ", ".join(sorted(wrong)[:5]) or f"{len(listed)} attributes clustered"})
    return rows


def _with_undefined(name: str, got: pd.Series, want: pd.Series, tol: dict) -> dict:
    """``toleranced`` where an entry undefined on both sides agrees."""
    got = got.reindex(want.index).astype("float64")
    both = got.isna() & want.isna()
    return toleranced(name, got.where(~both, 0.0), want.where(~both, 0.0), tol)
