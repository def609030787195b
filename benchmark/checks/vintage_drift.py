"""What a pass of the ``vintage_drift`` mix left against a plain reference of
the upstream's ``drift_detector`` section (Anovos v1.1.0
``drift_stability/drift_detector.py::statistics`` and
``stability.py::stability_index_computation``) on the same parquet files:
numpy and pandas float64, nothing of ``anovos_tpu``.

The reference takes every numeric value as the float32 the table stores
(``float64(float32(x))``), then, for every column of the mix (all of the
target's but ``drop_cols``):

frequencies  of a numeric attribute: ``bins`` equal-range bins from the
             SOURCE's smallest and largest value, cut-offs ``lo + j * ((hi - lo)
             / bins)`` in float64 as the upstream computes them (``min + j *
             bin_width``), a value equal to a cut-off in the bin
             under it (right-closed); of a string attribute: its values, the
             union of both sides' in code-point order.  Counts over the
             side's FULL row count, so the nulls are in no bin and the
             frequencies of a column with nulls add up to less than 1.  A
             frequency of 0 counts as 1e-4 (on either side).
distances    PSI ``sum((p - q) ln(p / q))``, HD ``sqrt(sum((sqrt p - sqrt
             q)^2) / 2)``, JSD ``(sum(p ln(p / m)) + sum(q ln(q / m))) / 2``
             with ``m = (p + q) / 2``, KS ``max |cumsum p - cumsum q|`` in the
             order above; natural logarithm; p the source's, q the target's.
flagged      1 where any of the four passes ``threshold``.
leaves       a numeric attribute without a value in the source has no
             cut-offs and leaves the answer (the upstream's binning drops it
             with a warning); a string attribute without one stays: every p
             is the 1e-4 of an empty bin.
moments      of every numeric column of every period: ``checks/stability.py``'s
             (mean, sample stddev, Pearson population kurtosis), undefined
             (NaN) where a period has no value, one value (stddev) or no
             spread (kurtosis).
stability    per column over the periods where the moment is defined: the
             sample stddev of the means, and the CV (stddev over mean) of the
             means, the stddevs and the kurtoses, undefined where fewer than
             two periods define it or the mean of them is 0; a score from
             |CV|: < 0.03 is 4, < 0.1 is 3, < 0.2 is 2, < 0.5 is 1, else 0;
             the index ``sum(weight * score)``, undefined where a score is;
             flagged 1 where the index is undefined or under ``si_threshold``.

Compared (tolerances in the configuration, ``guarantees``): each distance,
each moment, each CV by its worst entry (``mean_stddev`` as a share of the
level of the column's period means, 1 at least); exactly: the attributes of both
tables, which moments and CVs are undefined, the row counts the program states
(``rows`` of its ``drift/read`` and ``stability/read`` stage rows), and the
flags, the scores and the index wherever the reference's number is further
from every threshold than the tolerance of that number.

``reference(frames, args, fault=...)`` computes a named wrong answer instead
(``FAULTS``), and ``control`` the reference from tables held in bfloat16: the
tests and PERF.md show each of them not ``correct``.

args: ``drop_cols``, ``bins``, ``threshold``, ``si_threshold``,
``weightages``.  Tables: drift_statistics, stability_index,
stabilityIndex_metrics."""

import json
import os

import numpy as np
import pandas as pd

from benchmark.harness.check import exact, table, toleranced
from benchmark.harness.names import load_module

DISTANCES = ("PSI", "HD", "JSD", "KS")
MOMENTS = ("mean", "stddev", "kurtosis")
CVS = ("mean_stddev", "mean_cv", "stddev_cv", "kurtosis_cv")
SCORES = ("mean_si", "stddev_si", "kurtosis_si")
CV_THRESHOLDS = (0.03, 0.1, 0.2, 0.5)
EMPTY_BIN = 1e-4
FAULTS = ("valid_denominator", "left_closed", "source_keys_only")
READ_ROWS = {"drift/read": "drift_detector/drift_statistics", "stability/read": "drift_detector/stability_index"}


def stored(x) -> np.ndarray:
    """A numeric column as the table stores it: float32, held in float64."""
    return np.asarray(x, np.float64).astype(np.float32).astype(np.float64)


# ------------------------------------------------------------------- what the pass left ----
def read(out_dir, traffic, args):
    t = traffic["tables"]
    drift = table(out_dir, t["drift_statistics"]).set_index("attribute")
    si = table(out_dir, t["stability_index"]).set_index("attribute")
    out = {"distances": drift[list(DISTANCES)].astype("float64"), "flagged": drift["flagged"].astype(int),
           "moments": load_module("checks", "stability").read(out_dir, traffic, args)[list(MOMENTS)].astype("float64"),
           "si": si[list(CVS + SCORES) + ["stability_index"]].astype("float64"),
           "si_flagged": si["flagged"].astype(int), "rows": {}}
    with open(os.path.join(out_dir, traffic["manifest"])) as f:
        phases = json.load(f).get("phases") or []
    for name, parent in READ_ROWS.items():  # in the order the node read them
        found = sorted((r for r in phases if r["name"] == name and r["parent"] == parent), key=lambda r: r["start_s"])
        out["rows"].update({f"{name}:{i}": r["counts"].get("rows") for i, r in enumerate(found)})
    return out


# ------------------------------------------------------------------- the reference ----
def frequencies(src: pd.Series, tgt: pd.Series, bins: int, numeric=stored, fault=None):
    """``(p, q)`` of one attribute in the order KS adds them up, or None
    where a numeric attribute has no value in the source."""
    if pd.api.types.is_numeric_dtype(src):
        s, t = numeric(src.to_numpy(float)), numeric(tgt.to_numpy(float))
        s_live, t_live = s[~np.isnan(s)], t[~np.isnan(t)]
        if not len(s_live):
            return None
        lo, hi = s_live.min(), s_live.max()
        cuts = lo + np.arange(1, bins) * ((hi - lo) / bins)  # the upstream's order: min + j * bin_width
        side = "right" if fault == "left_closed" else "left"  # the fault: a value on a cut-off goes to the bin above
        p = np.bincount(np.searchsorted(cuts, s_live, side=side), minlength=bins).astype(float)
        q = np.bincount(np.searchsorted(cuts, t_live, side=side), minlength=bins).astype(float)
        n_src, n_tgt = len(s_live), len(t_live)
    else:
        ps, qs = src.dropna().astype(str).value_counts(), tgt.dropna().astype(str).value_counts()
        keys = sorted(set(ps.index) if fault == "source_keys_only" else set(ps.index) | set(qs.index))
        p = ps.reindex(keys).fillna(0).to_numpy(float)
        q = qs.reindex(keys).fillna(0).to_numpy(float)
        n_src, n_tgt = int(ps.sum()), int(qs.sum())
    if fault != "valid_denominator":  # the fault: over the rows that have a value
        n_src, n_tgt = len(src), len(tgt)
    return p / max(n_src, 1), q / max(n_tgt, 1)


def distances(p: np.ndarray, q: np.ndarray) -> dict:
    p, q = np.where(p == 0, EMPTY_BIN, p), np.where(q == 0, EMPTY_BIN, q)
    m = (p + q) / 2
    return {"PSI": float(((p - q) * np.log(p / q)).sum()),
            "HD": float(np.sqrt(((np.sqrt(p) - np.sqrt(q)) ** 2).sum() / 2)),
            "JSD": float(((p * np.log(p / m)).sum() + (q * np.log(q / m)).sum()) / 2),
            "KS": float(np.abs(np.cumsum(p) - np.cumsum(q)).max()) if len(p) else 0.0}


def moments(periods: list, numeric=stored) -> pd.DataFrame:
    """``checks/stability.py``'s moments, one row ``"<period>:<column>"``."""
    out = {}
    for i, df in enumerate(periods, start=1):
        for c in df.select_dtypes("number").columns:
            x = numeric(df[c].to_numpy(float))
            x = x[~np.isnan(x)]
            d = x - x.mean() if len(x) else x
            with np.errstate(divide="ignore", invalid="ignore"):
                out[f"{i}:{c}"] = {"mean": x.mean() if len(x) else np.nan,
                                   "stddev": x.std(ddof=1) if len(x) > 1 else np.nan,
                                   "kurtosis": (d ** 4).mean() / (d ** 2).mean() ** 2 if len(x) else np.nan}
    return pd.DataFrame(out).T[list(MOMENTS)]


def score(cv: float) -> float:
    if np.isnan(cv):
        return np.nan
    return float(4 - np.searchsorted(CV_THRESHOLDS, abs(cv), side="right"))


def stability(mom: pd.DataFrame, weightages: dict, si_threshold: float) -> tuple:
    """The stability table from a frame of moments (``moments``'s layout)."""
    period = np.array([int(k.split(":", 1)[0]) for k in mom.index])
    column = np.array([k.split(":", 1)[1] for k in mom.index])
    rows, flags = {}, {}
    for c in dict.fromkeys(column):
        sub = mom[column == c].iloc[np.argsort(period[column == c])]
        r = {}
        for stat in MOMENTS:
            v = sub[stat].to_numpy(float)
            v = v[~np.isnan(v)]
            spread = v.std(ddof=1) if len(v) > 1 else np.nan
            r[stat + "_cv"] = spread / v.mean() if len(v) > 1 and v.mean() != 0 else np.nan
            if stat == "mean":
                r["mean_stddev"] = spread
        for stat in MOMENTS:
            r[stat + "_si"] = score(r[stat + "_cv"])
        r["stability_index"] = sum(weightages[s] * r[s + "_si"] for s in MOMENTS)  # NaN where a score is
        rows[c] = r
        flags[c] = int(np.isnan(r["stability_index"]) or r["stability_index"] < si_threshold)
    return pd.DataFrame(rows).T[list(CVS + SCORES) + ["stability_index"]].astype("float64"), pd.Series(flags)


def answers(source: pd.DataFrame, target: pd.DataFrame, periods: list, args: dict, fault=None, numeric=stored) -> dict:
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no fault {fault!r}")
    found = {}
    for c in target.columns:
        if c in args["drop_cols"]:
            continue
        pq = frequencies(source[c], target[c], args["bins"], numeric=numeric, fault=fault)
        if pq is not None:
            found[c] = distances(*pq)
    dist = pd.DataFrame(found).T[list(DISTANCES)]
    mom = moments(periods, numeric=numeric)
    si, si_flagged = stability(mom, args["weightages"], args["si_threshold"])
    return {"distances": dist, "flagged": (dist > args["threshold"]).any(axis=1).astype(int), "moments": mom,
            "si": si, "si_flagged": si_flagged,
            "rows": {"drift/read:0": len(source), **{f"stability/read:{i}": len(df) for i, df in enumerate(periods)}}}


def reference(frames, args, fault=None):
    return answers(frames.source, frames.main, frames.periods, args, fault=fault)


def control(ref, frames, args):
    """The control: the reference from tables held in bfloat16 (every numeric
    value rounded to bfloat16 before it is binned or summed; the arithmetic
    stays float64, the mildest form of "computed in bfloat16").  The row
    counts stay the reference's."""
    import ml_dtypes

    def bf16(x):
        return stored(x).astype(ml_dtypes.bfloat16).astype(np.float64)

    return {**answers(frames.source, frames.main, frames.periods, args, numeric=bf16), "rows": ref["rows"]}


# ------------------------------------------------------------------- the comparison ----
def _both_undefined(got: pd.Series, want: pd.Series) -> tuple:
    """``(got, want)`` with 0 on both sides wherever both are undefined, so
    that ``toleranced`` takes it as agreement."""
    got = got.reindex(want.index).astype("float64")
    both = got.isna() & want.isna()
    return got.where(~both, 0.0), want.where(~both, 0.0)


def _undefined(name: str, got: pd.DataFrame, want: pd.DataFrame) -> dict:
    """The entries undefined on one side and not on the other, exactly."""
    got = got.reindex(want.index)
    return exact(name, {f"{i}.{c}": True for c in want.columns for i in got.index[got[c].isna()]},
                 {f"{i}.{c}": True for c in want.columns for i in want.index[want[c].isna()]})


def _room(tol: dict, value: np.ndarray) -> np.ndarray:
    return tol.get("atol", 0.0) + tol.get("rtol", 0.0) * np.abs(value)


def compare(ans, ref, tolerances, args):
    want = ref["distances"]
    rows = [exact("drift_attributes", {a: True for a in ans["distances"].index}, {a: True for a in want.index})]
    got = ans["distances"].reindex(want.index)
    rows += [toleranced(d.lower(), got[d], want[d], tolerances[d.lower()]) for d in DISTANCES]
    # a flag is held where no distance of the reference is within its tolerance of the threshold
    w = want.to_numpy(float)
    near = np.abs(w - args["threshold"]) <= np.stack([_room(tolerances[d.lower()], want[d].to_numpy(float))
                                                     for d in DISTANCES], axis=1)
    sure = want.index[~near.any(axis=1)]
    rows.append(exact("drift_flagged", ans["flagged"].reindex(sure).fillna(-1).astype(int).to_dict(),
                      ref["flagged"].reindex(sure).astype(int).to_dict()))
    rows[-1]["detail"] = rows[-1]["detail"] or f"{len(sure)} of {len(want)} attributes sure, {int(ref['flagged'].sum())} flagged"

    stab = load_module("checks", "stability")
    mom_got, mom_want = ans["moments"], ref["moments"]
    rows += [exact("si_attributes", {k: True for k in mom_got.index}, {k: True for k in mom_want.index}),
             _undefined("si_undefined", mom_got, mom_want)]
    pairs = {s: _both_undefined(mom_got[s], mom_want[s]) for s in MOMENTS}
    rows += stab.compare(pd.DataFrame({s: pairs[s][0] for s in MOMENTS}),
                         pd.DataFrame({s: pairs[s][1] for s in MOMENTS}), tolerances, args)

    si_got, si_want = ans["si"].reindex(ref["si"].index), ref["si"]
    rows += [exact("stability_attributes", {a: True for a in ans["si"].index}, {a: True for a in si_want.index}),
             _undefined("cv_undefined", si_got[list(CVS)], si_want[list(CVS)])]
    # mean_stddev carries the column's units: it is held as a share of the level of the period means (1 at least), as
    # the CVs are (two means of 30,000 that are each sound to 1e-7 leave their spread of 1.8 unsound in the third digit)
    level = ref["moments"]["mean"].groupby([k.split(":", 1)[1] for k in ref["moments"].index]).mean()
    scale = np.maximum(level.abs().reindex(si_want.index).fillna(0.0), 1.0)
    rows.append(toleranced("mean_stddev", *_both_undefined(si_got["mean_stddev"] / scale, si_want["mean_stddev"] / scale),
                           tolerances["cv"]))
    rows += [toleranced(c, *_both_undefined(si_got[c], si_want[c]), tolerances["cv"]) for c in CVS[1:]]
    # a score is held where the reference's CV is further from every threshold than its tolerance
    cv = si_want[[s.replace("_si", "_cv") for s in SCORES]].to_numpy(float)
    room = _room(tolerances["cv"], cv)
    edge = (np.abs(np.abs(cv)[:, :, None] - np.array(CV_THRESHOLDS)) <= room[:, :, None]).any(axis=2)
    scores_got, scores_want = {}, {}
    for j, s in enumerate(SCORES):
        for a in si_want.index[~edge[:, j]]:
            scores_got[f"{a}.{s}"], scores_want[f"{a}.{s}"] = _key(si_got.at[a, s]), _key(si_want.at[a, s])
    rows.append(exact("stability_scores", scores_got, scores_want))
    whole = si_want.index[~edge.any(axis=1)]
    rows.append(toleranced("stability_index", *_both_undefined(si_got.loc[whole, "stability_index"],
                                                               si_want.loc[whole, "stability_index"]),
                           tolerances["stability_index"]))
    rows.append(exact("stability_flagged", ans["si_flagged"].reindex(whole).fillna(-1).astype(int).to_dict(),
                      ref["si_flagged"].reindex(whole).astype(int).to_dict()))
    rows[-1]["detail"] = rows[-1]["detail"] or f"{len(whole)} of {len(si_want)} attributes sure, {int(ref['si_flagged'].sum())} flagged"
    stated = {k: v for k, v in ans["rows"].items() if v is not None}
    rows.append(exact("rows_stated", stated, {k: ref["rows"].get(k) for k in stated}))
    rows[-1]["detail"] = rows[-1]["detail"] or f"{len(stated)} of {len(ref['rows'])} reads state their rows"
    return rows


def _key(x) -> str:
    return "undefined" if x is None or np.isnan(x) else f"{float(x):g}"
