"""Golden-fixture generator — an INDEPENDENT pure pandas/numpy encoding of
the reference's metric semantics (anovos/anovos), with no imports from
anovos_tpu.  Run once, commit the CSVs; tests/test_golden.py then diffs the
framework's output against these files, so a cross-implementation
disagreement about what a metric MEANS shows up as a diff against a
committed artifact rather than passing both self-derived sides.

Semantics encoded here (reference file:line):
- stats_generator: fill/missing/nonzero counts, mean/median/mode (mode for
  EVERY column incl. floats — stats_generator.py:360-421), unique/IDness,
  stddev(ddof=1)/cov/IQR/range, percentile grid, population skew / excess
  kurtosis (Spark's skewness/kurtosis aggregates).
- drift_detector.statistics: equal-range 10-bin from SOURCE min/max
  (transformers.py attribute_binning:87-), per-category frequency with
  denominator = full row count, full-outer join, missing/zero -> 1e-4
  (drift_detector.py:262-270), PSI natural log, HD sqrt(sum/2), JSD natural
  log (no /ln2), KS max |cumsum p - cumsum q| ordered by category; nulls
  form a group whose F.count(col)==0 -> p=q=1e-4 (i.e. dropped);
  flagged = any metric > 0.1 (drift_detector.py:352-355).
- IV (association_evaluator.py:253-425): equal-frequency 10-bin (quantile
  cutoffs), nulls are their own bin, WOE=ln(nonevent_pct/event_pct) with a
  +0.5-count fallback when either pct is zero, IV=sum((non-event - event)*WOE).
- IG (association_evaluator.py:427-590): same binning, log2 entropies,
  pure (0/1) segments contribute nothing (Spark log2(0)=null -> sum skips).

Usage:  python tests/golden/generate_golden.py  (writes CSVs next to itself)

Spark-oracle mode (self-closing — VERDICT r4 #4):
    python tests/golden/generate_golden.py --from-spark [--write] [--diff]
runs the ACTUAL reference implementation under pyspark on the same inputs
and diffs (or regenerates) the oracle-mapped fixtures — see
spark_oracle.py.  Exits 3 when no JVM/pyspark is available (CI skips).
"""

import glob
import os

import numpy as np
import pandas as pd

HERE = os.path.dirname(os.path.abspath(__file__))
# the seeded 32,561-row set: `python -m anovos_tpu.data_ingest.synthetic`
DATA = os.path.join(os.path.dirname(os.path.dirname(HERE)),
                    "data", "income_dataset", "parquet", "*.parquet")

NUM_COLS = [
    "age", "fnlwgt", "logfnl", "education-num", "capital-gain",
    "capital-loss", "hours-per-week", "latitude", "longitude",
]
CAT_COLS = [
    "workclass", "education", "marital-status", "occupation",
    "relationship", "race", "sex", "native-country", "income",
]
LABEL_COL, EVENT = "income", ">50K"
BIN_SIZE = 10
DRIFT_THRESHOLD = 0.1


def load() -> pd.DataFrame:
    files = sorted(glob.glob(DATA))
    df = pd.concat([pd.read_parquet(f) for f in files], ignore_index=True)
    return df[NUM_COLS + CAT_COLS]


def r4(x):
    return None if x is None or (isinstance(x, float) and np.isnan(x)) else round(float(x), 4)


# --------------------------------------------------------------- stats ----
def golden_counts(df):
    n = len(df)
    rows = []
    for c in NUM_COLS + CAT_COLS:
        fill = int(df[c].notna().sum())
        row = {
            "attribute": c,
            "fill_count": fill,
            "fill_pct": r4(fill / n),
            "missing_count": n - fill,
            "missing_pct": r4((n - fill) / n),
        }
        if c in NUM_COLS:
            nz = int((df[c].fillna(0) != 0).sum())
            row["nonzero_count"] = nz
            row["nonzero_pct"] = r4(nz / n)
        else:
            row["nonzero_count"] = None
            row["nonzero_pct"] = None
        rows.append(row)
    return pd.DataFrame(rows)


def golden_central(df):
    rows = []
    for c in NUM_COLS + CAT_COLS:
        s = df[c].dropna()
        vc = s.value_counts()
        if vc.empty:
            mode, mode_rows = None, None
        else:
            # tiebreak: smallest value among max-count ties (the reference's
            # groupBy/orderBy/limit(1) tiebreak is engine-nondeterministic, so
            # the golden contract pins a deterministic convention)
            top = vc[vc == vc.iloc[0]]
            mode, mode_rows = min(top.index), int(vc.iloc[0])
        # reference renders mode through a string-typed schema
        if mode is not None and c in NUM_COLS:
            mode = str(float(mode))
        rows.append({
            "attribute": c,
            "mean": r4(s.mean()) if c in NUM_COLS else None,
            "median": r4(np.percentile(s.to_numpy(float), 50)) if c in NUM_COLS else None,
            "mode": mode,
            "mode_rows": mode_rows,
            "mode_pct": r4(mode_rows / len(s)) if mode_rows else None,
        })
    return pd.DataFrame(rows)


def golden_cardinality(df):
    rows = []
    for c in NUM_COLS + CAT_COLS:
        s = df[c].dropna()
        u = int(s.nunique())
        rows.append({"attribute": c, "unique_values": u, "IDness": r4(u / len(s))})
    return pd.DataFrame(rows)


def golden_dispersion(df):
    rows = []
    for c in NUM_COLS:
        s = df[c].dropna().to_numpy(float)
        sd, mu = np.std(s, ddof=1), np.mean(s)
        q75, q25 = np.percentile(s, 75), np.percentile(s, 25)
        rows.append({
            "attribute": c,
            "stddev": r4(sd),
            "variance": r4(sd * sd),
            "cov": r4(sd / mu) if mu != 0 else None,
            "IQR": r4(q75 - q25),
            "range": r4(s.max() - s.min()),
        })
    return pd.DataFrame(rows)


def golden_percentiles(df):
    grid = [0, 1, 5, 10, 25, 50, 75, 90, 95, 99, 100]
    names = ["min", "1%", "5%", "10%", "25%", "50%", "75%", "90%", "95%", "99%", "max"]
    rows = []
    for c in NUM_COLS:
        s = df[c].dropna().to_numpy(float)
        vals = np.percentile(s, grid)
        rows.append({"attribute": c, **{nm: r4(v) for nm, v in zip(names, vals)}})
    return pd.DataFrame(rows)


def golden_shape(df):
    rows = []
    for c in NUM_COLS:
        s = df[c].dropna().to_numpy(float)
        m = s.mean()
        m2 = np.mean((s - m) ** 2)
        m3 = np.mean((s - m) ** 3)
        m4 = np.mean((s - m) ** 4)
        skew = m3 / m2 ** 1.5 if m2 > 0 else None
        kurt = m4 / m2 ** 2 - 3.0 if m2 > 0 else None
        rows.append({"attribute": c, "skewness": r4(skew), "kurtosis": r4(kurt)})
    return pd.DataFrame(rows)


# --------------------------------------------------------------- drift ----
def _equal_range_bins(src_vals, vals):
    lo, hi = np.nanmin(src_vals), np.nanmax(src_vals)
    cuts = [lo + j * (hi - lo) / BIN_SIZE for j in range(1, BIN_SIZE)]
    # reference bucket_label: first cutoff with value <= cutoff -> bin i+1
    return np.searchsorted(cuts, vals, side="left") + 1


def _freqs(keys, n_total):
    """Per-category frequency with the FULL row count as denominator; null
    keys dropped (their F.count(col)==0 in the reference's groupBy)."""
    keys = pd.Series(keys).dropna()
    return (keys.value_counts() / n_total).to_dict()


def golden_drift(src, tgt):
    rows = []
    for c in NUM_COLS + CAT_COLS:
        if c in NUM_COLS:
            sv, tv = src[c].to_numpy(float), tgt[c].to_numpy(float)
            sb = np.where(np.isnan(sv), np.nan, _equal_range_bins(sv, sv))
            tb = np.where(np.isnan(tv), np.nan, _equal_range_bins(sv, tv))
            p, q = _freqs(sb, len(src)), _freqs(tb, len(tgt))
        else:
            p, q = _freqs(src[c], len(src)), _freqs(tgt[c], len(tgt))
        cats = sorted(set(p) | set(q))
        # reference replaces EXACT zeros with 1e-4 (fillna + replace(0, ...));
        # genuinely small nonzero frequencies stay as they are
        pa = np.array([p.get(k, 0.0) or 1e-4 for k in cats])
        qa = np.array([q.get(k, 0.0) or 1e-4 for k in cats])
        psi = float(((pa - qa) * np.log(pa / qa)).sum())
        hd = float(np.sqrt(((np.sqrt(pa) - np.sqrt(qa)) ** 2).sum() / 2))
        m = (pa + qa) / 2
        jsd = float((np.sum(pa * np.log(pa / m)) + np.sum(qa * np.log(qa / m))) / 2)
        ks = float(np.abs(np.cumsum(pa) - np.cumsum(qa)).max())
        vals = {"PSI": r4(psi), "HD": r4(hd), "JSD": r4(jsd), "KS": r4(ks)}
        vals["flagged"] = int(any(v > DRIFT_THRESHOLD for v in vals.values()))
        rows.append({"attribute": c, **vals})
    return pd.DataFrame(rows)


# ------------------------------------------------------------- quality ----
def golden_outlier(df):
    """outlier_detection semantics (quality_checker.py:550-1045): three
    detectors — percentile fences, mean±3σ (sample stddev), 1.5·IQR fences —
    voted with min_validation=2 (2nd-most-extreme candidate on each side);
    columns with p5 == p95 excluded as skewed; counts of values strictly
    outside [lower, upper] on the full data (no sampling at this size)."""
    rows = []
    for c in NUM_COLS:
        s = df[c].dropna().to_numpy(float)
        p5, p95 = np.quantile(s, 0.05, method="lower"), np.quantile(s, 0.95, method="lower")
        if p5 == p95:
            continue  # skewed
        mean, sd = s.mean(), s.std(ddof=1)
        q1, q3 = np.quantile(s, 0.25, method="lower"), np.quantile(s, 0.75, method="lower")
        iqr = q3 - q1
        lows = sorted([p5, mean - 3 * sd, q1 - 1.5 * iqr], reverse=True)
        highs = sorted([p95, mean + 3 * sd, q3 + 1.5 * iqr])
        lo, hi = lows[1], highs[1]  # min_validation=2
        rows.append({
            "attribute": c,
            "lower_outliers": int((s < lo).sum()),
            "upper_outliers": int((s > hi).sum()),
        })
    return pd.DataFrame(rows)


def golden_duplicates(df):
    """duplicate_detection stats (quality_checker.py:49-149).  The income
    data has no duplicate rows, so the fixture re-appends the first 500 —
    the dedup path must actually find them (non-degenerate by construction)."""
    df = pd.concat([df, df.head(500)], ignore_index=True)
    n = len(df)
    uniq = len(df.drop_duplicates())
    return pd.DataFrame(
        [
            ["rows_count", float(n)],
            ["unique_rows_count", float(uniq)],
            ["duplicate_rows", float(n - uniq)],
            ["duplicate_pct", r4((n - uniq) / n)],
        ],
        columns=["metric", "value"],
    )


def golden_nullrows(df):
    """nullRows_detection stats (quality_checker.py:152-283): per-row null
    count distribution with flag = null_count > 0.1 * ncols (threshold 0.1
    so BOTH flag values occur on this data — 18 cols, up to 8 nulls/row)."""
    cnt = df.isna().sum(axis=1).to_numpy()
    flagged = (cnt > 0.1 * df.shape[1]).astype(int)
    g = pd.DataFrame({"null_cols_count": cnt, "flagged": flagged})
    out = g.groupby(["null_cols_count", "flagged"], as_index=False).size().rename(
        columns={"size": "row_count"}
    )
    out["row_pct"] = (out["row_count"] / len(df)).round(4)
    return out[["null_cols_count", "row_count", "row_pct", "flagged"]].sort_values(
        "null_cols_count"
    ).reset_index(drop=True)


# ---------------------------------------------------------- transformers --
def golden_binning(df):
    """attribute_binning semantics (transformers.py:87-291): equal_range
    cutoffs lo + j*(hi-lo)/10; equal_frequency cutoffs at j/10 quantiles;
    label = searchsorted(cutoffs, x, 'left') + 1; per-bin row counts."""
    rows = []
    for c in NUM_COLS:
        v = df[c].to_numpy(float)
        nn = v[~np.isnan(v)]
        for method in ("equal_range", "equal_frequency"):
            if method == "equal_range":
                lo, hi = nn.min(), nn.max()
                cuts = [lo + j * (hi - lo) / BIN_SIZE for j in range(1, BIN_SIZE)]
            else:
                cuts = np.quantile(nn, [j / BIN_SIZE for j in range(1, BIN_SIZE)], method="lower").tolist()
            b = np.searchsorted(cuts, nn, side="left") + 1
            counts = np.bincount(b, minlength=BIN_SIZE + 1)[1:]
            rows.append({
                "attribute": c, "method": method,
                **{f"cut_{j}": r4(cuts[j - 1]) for j in range(1, BIN_SIZE)},
                **{f"bin_{j}": int(counts[j - 1]) for j in range(1, BIN_SIZE + 1)},
            })
    return pd.DataFrame(rows)


def golden_scalers(df):
    """z_standardization (mean, sample stddev — transformers.py:965-1100)
    and IQR_standardization (median, Q3−Q1 — :1102-1232) fit parameters."""
    rows = []
    for c in NUM_COLS:
        s = df[c].dropna().to_numpy(float)
        q25, q50, q75 = np.quantile(s, [0.25, 0.5, 0.75], method="lower")
        rows.append({
            "attribute": c,
            "mean": r4(s.mean()),
            "stddev": r4(s.std(ddof=1)),
            "median": r4(q50),
            "IQR": r4(q75 - q25),
        })
    return pd.DataFrame(rows)


# ------------------------------------------------------- invalid entries ---
_IE_NULL_VOCAB = [
    "", " ", "nan", "null", "na", "inf", "n/a", "not defined", "none",
    "undefined", "blank", "unknown",
]
_IE_SPECIAL = list("&$;:.,*#@_?%!^()-/'")


def _ie_invalid(e) -> bool:
    """Reference quality_checker.py:1504-1568 'auto' rules: lowercased
    trimmed membership in the null/special vocab, the repeated-chars regex,
    and whole-string strictly-consecutive ordinal runs of length >= 3."""
    import re as _re

    e = str(e).lower().strip()
    if e in _IE_NULL_VOCAB + _IE_SPECIAL:
        return True
    if _re.search(r"\b([a-zA-Z0-9])\1\1+\b", e):
        return True
    if len(e) >= 3 and all(ord(e[i]) - ord(e[i - 1]) == 1 for i in range(1, len(e))):
        return True
    return False


def _ie_frame() -> pd.DataFrame:
    """Deterministic synthetic frame covering every 'auto' rule class plus
    clean lookalikes (the test rebuilds the same frame)."""
    return pd.DataFrame({
        "nullish": ["ok", "NA", "  none ", "Unknown", "n/a", "fine", "nano", "infinite"],
        "special": [":", "-", "a-b", "x", "&", "(", "val", "9.5"],
        "repeats": ["aaa", "xaaax", "aab", "1111", "good", "zz", "999", "normal"],
        "ordinal": ["abc", "xyz", "123", "12", "acb", "wxyz", "cba", "hi"],
        "clean": ["alpha", "beta", "gamma", "delta", "x1", "y2", "z3", "w4"],
    })


def golden_invalid_entries():
    df = _ie_frame()
    rows = []
    for c in df.columns:
        bad = sorted({str(v).lower().strip() for v in df[c] if _ie_invalid(v)})
        n_bad = int(sum(_ie_invalid(v) for v in df[c]))
        rows.append({
            "attribute": c,
            "invalid_entries": "|".join(bad),
            "invalid_count": n_bad,
            "invalid_pct": r4(n_bad / len(df)),
        })
    return pd.DataFrame(rows)


# ----------------------------------------------------------- correlation ---
def golden_correlation(df):
    """Pearson correlation over the numeric block (reference
    association_evaluator.py:38-141 — MLlib Correlation.corr), pairwise on
    rows where BOTH columns are non-null is NOT the reference semantics:
    the assembler drops any row with a null in the selected block, so the
    oracle uses complete-case rows only."""
    sub = df[NUM_COLS].dropna()
    corr = sub.corr(method="pearson")
    ordered = sorted(NUM_COLS)  # reference sorts the column axis (:128-133)
    corr = corr.loc[ordered, ordered]
    out = corr.reset_index().rename(columns={"index": "attribute"})
    for c in ordered:
        out[c] = out[c].map(r4)
    return out


# ------------------------------------------------------------ stability ----
def _si_score(cv):
    """CV → SI score map (reference validations.py:97-126):
    [0.03, 0.1, 0.2, 0.5] → 4..0."""
    acv = abs(cv)
    for score, thr in zip((4, 3, 2, 1), (0.03, 0.1, 0.2, 0.5)):
        if acv < thr:
            return score
    return 0


def stability_datasets():
    """The deterministic synthetic 3-dataset history shared by the pandas
    encoding, the framework test, and the Spark oracle (spark_oracle.py)."""
    rng = np.random.default_rng(99)
    return [
        pd.DataFrame({
            "steady": rng.normal(100.0, 5.0, 2000),
            "drifty": rng.normal(100.0 + 40.0 * i, 5.0 + 3.0 * i, 2000),
        })
        for i in range(3)
    ]


def golden_stability(datasets=None):
    """stability_index_computation semantics (reference stability.py:15-334)
    on a DETERMINISTIC synthetic 3-dataset history (seeded; the test rebuilds
    the same datasets): per-dataset mean/stddev/kurtosis(+3), CV of each
    metric across datasets (SAMPLE stddev ddof=1 — Spark's F.stddev), CV→SI
    map, weighted SI with the 50/30/20 default weights.  ``datasets``
    overrides the fixture history (the fuzz sweep feeds random histories)."""
    if datasets is None:
        datasets = stability_datasets()
    rows = []
    for c in datasets[0].columns:
        means, stds, kurts = [], [], []
        for d in datasets:
            v = d[c].to_numpy(float)
            m = v.mean()
            m2 = ((v - m) ** 2).mean()
            m4 = ((v - m) ** 4).mean()
            means.append(m)
            stds.append(v.std(ddof=1))
            kurts.append(m4 / m2**2)  # kurtosis + 3 (reference adds 3)
        cvs = [np.std(x, ddof=1) / abs(np.mean(x)) for x in (means, stds, kurts)]
        sis = [_si_score(cv) for cv in cvs]
        si = 0.5 * sis[0] + 0.3 * sis[1] + 0.2 * sis[2]
        rows.append({
            "attribute": c,
            "mean_cv": r4(cvs[0]), "stddev_cv": r4(cvs[1]), "kurtosis_cv": r4(cvs[2]),
            "mean_si": sis[0], "stddev_si": sis[1], "kurtosis_si": sis[2],
            "stability_index": r4(si),
            "flagged": int(si < 1),
        })
    return pd.DataFrame(rows)


# --------------------------------------------------------------- IV/IG ----
def _equal_freq_keys(df, c):
    """Binned group keys for one attribute; nulls stay null (their own bin)."""
    if c not in NUM_COLS:
        return df[c]
    v = df[c].to_numpy(float)
    nn = v[~np.isnan(v)]
    cuts = np.quantile(nn, [j / BIN_SIZE for j in range(1, BIN_SIZE)])
    b = np.searchsorted(cuts, v, side="left") + 1.0
    return pd.Series(np.where(np.isnan(v), np.nan, b))


def golden_iv(df):
    y = (df[LABEL_COL] == EVENT).to_numpy()
    rows = []
    for c in [x for x in NUM_COLS + CAT_COLS if x != LABEL_COL]:
        keys = _equal_freq_keys(df, c)
        g = pd.DataFrame({"k": keys, "e": y}).groupby("k", dropna=False)
        n1 = g["e"].sum().to_numpy(float)
        n0 = (g["e"].count() - g["e"].sum()).to_numpy(float)
        t1, t0 = n1.sum(), n0.sum()
        ep, np_ = n1 / t1, n0 / t0
        woe = np.where(
            (ep != 0) & (np_ != 0),
            np.log(np.maximum(np_, 1e-300) / np.maximum(ep, 1e-300)),
            np.log(((n0 + 0.5) / t0) / ((n1 + 0.5) / t1)),
        )
        iv = float(((np_ - ep) * woe).sum())
        rows.append({"attribute": c, "iv": r4(iv)})
    return pd.DataFrame(rows)


def golden_ig(df):
    y = (df[LABEL_COL] == EVENT).to_numpy()
    p = y.mean()
    h_total = -(p * np.log2(p) + (1 - p) * np.log2(1 - p))
    rows = []
    for c in [x for x in NUM_COLS + CAT_COLS if x != LABEL_COL]:
        keys = _equal_freq_keys(df, c)
        g = pd.DataFrame({"k": keys, "e": y}).groupby("k", dropna=False)
        cnt = g["e"].count().to_numpy(float)
        ep = g["e"].mean().to_numpy(float)
        seg = cnt / cnt.sum()
        # pure segments: Spark's log2(0) is null -> the whole entropy term is
        # null and dropped from the sum (i.e. contributes 0)
        mask = (ep > 0) & (ep < 1)
        h = -(seg[mask] * (ep[mask] * np.log2(ep[mask]) + (1 - ep[mask]) * np.log2(1 - ep[mask])))
        rows.append({"attribute": c, "ig": r4(h_total - float(h.sum()))})
    return pd.DataFrame(rows)


def main():
    df = load()
    n = len(df)
    src, tgt = df.iloc[: n // 2].reset_index(drop=True), df.iloc[n // 2 :].reset_index(drop=True)
    out = {
        "golden_counts.csv": golden_counts(df),
        "golden_central.csv": golden_central(df),
        "golden_cardinality.csv": golden_cardinality(df),
        "golden_dispersion.csv": golden_dispersion(df),
        "golden_percentiles.csv": golden_percentiles(df),
        "golden_shape.csv": golden_shape(df),
        "golden_drift.csv": golden_drift(src, tgt),
        "golden_outlier.csv": golden_outlier(df),
        "golden_binning.csv": golden_binning(df),
        "golden_scalers.csv": golden_scalers(df),
        "golden_stability.csv": golden_stability(),
        "golden_invalid_entries.csv": golden_invalid_entries(),
        "golden_correlation.csv": golden_correlation(df),
        "golden_duplicates.csv": golden_duplicates(df),
        "golden_nullrows.csv": golden_nullrows(df),
        "golden_iv.csv": golden_iv(df),
        "golden_ig.csv": golden_ig(df),
    }
    for name, odf in out.items():
        odf.to_csv(os.path.join(HERE, name), index=False)
        print(name, len(odf), "rows")


if __name__ == "__main__":
    import sys

    if "--from-spark" in sys.argv:
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "spark_oracle", os.path.join(HERE, "spark_oracle.py")
        )
        oracle = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(oracle)
        sys.exit(oracle.main(sys.argv[1:]))
    main()
