"""Self-closing Spark-oracle leg for the golden fixtures (VERDICT r4 #4).

The committed ``tests/golden/*.csv`` are an independently-written
pandas/numpy ENCODING of the reference's semantics (see
generate_golden.py) — not reference output, because this image has no
JVM.  This module closes that epistemic gap the first time a Java
environment appears: it runs the ACTUAL reference implementation
(anovos/anovos under pyspark, local[*]) on the same golden inputs,
regenerates the oracle-mapped fixtures, and diffs them against the
committed pandas encodings.

Oracle-mapped fixtures (17 = all committed golden CSVs): counts, central,
cardinality, dispersion, percentiles, shape, drift, correlation, iv, ig,
duplicates, nullrows, binning (model-artifact cutoffs + bin counts),
scalers (fit params from the model CSVs), outlier (detection metric
frame), stability (on the shared synthetic 3-dataset history),
invalid_entries (on the shared synthetic frame).

Tolerances: metrics computed with exact arithmetic on both sides diff at
rel 1e-3 (rounding to 4dp is the fixture contract); percentile-family
fields (median, percentile grid, IQR-derived) allow rel 1e-2 because the
reference computes them via Spark's approxQuantile; bin counts and
outlier tail counts allow rel 0.15 — the reference derives them from
approxQuantile cutoffs at 0.01 relative-rank accuracy, so boundary-tied
rows legitimately move between bins (the pandas encoding, which uses
exact order statistics, remains the committed contract).

Usage:
    python tests/golden/generate_golden.py --from-spark [--write] [--diff]
Exit codes: 0 ok, 3 unavailable (no JVM/pyspark/reference — CI skips).
"""

import glob
import os
import shutil
import sys
import tempfile

import numpy as np
import pandas as pd

HERE = os.path.dirname(os.path.abspath(__file__))
REFERENCE_SRC = os.environ.get("ANOVOS_REFERENCE_SRC", "/root/reference/src/main")
# the seeded set the committed goldens were generated from
# (python -m anovos_tpu.data_ingest.synthetic)
DATA = os.environ.get(
    "ANOVOS_GOLDEN_DATA",
    os.path.join(os.path.dirname(os.path.dirname(HERE)), "data", "income_dataset", "parquet"),
)

NUM_COLS = [
    "age", "fnlwgt", "logfnl", "education-num", "capital-gain",
    "capital-loss", "hours-per-week", "latitude", "longitude",
]
CAT_COLS = [
    "workclass", "education", "marital-status", "occupation",
    "relationship", "race", "sex", "native-country", "income",
]
LABEL_COL, EVENT = "income", ">50K"

# fixture -> tolerance class
ORACLE_MAPPED = {
    "golden_counts.csv": "exact",
    "golden_central.csv": "quantile",   # median via approxQuantile
    "golden_cardinality.csv": "exact",
    "golden_dispersion.csv": "quantile",  # IQR via approxQuantile
    "golden_percentiles.csv": "quantile",
    "golden_shape.csv": "exact",
    "golden_drift.csv": "exact",
    "golden_correlation.csv": "exact",
    "golden_iv.csv": "quantile",        # equal-frequency cutoffs
    "golden_ig.csv": "quantile",
    "golden_duplicates.csv": "exact",
    "golden_nullrows.csv": "exact",
    "golden_binning.csv": "sketch",     # approxQuantile cutoffs move ties
    "golden_scalers.csv": "quantile",
    "golden_outlier.csv": "sketch",     # tail counts from approx fences
    "golden_stability.csv": "exact",
    "golden_invalid_entries.csv": "exact",
}
RTOL = {"exact": 1e-3, "quantile": 1e-2, "sketch": 0.15}


def available():
    """(ok, reason): can the reference actually run here?"""
    if shutil.which("java") is None:
        return False, "no JVM (java not on PATH)"
    try:
        import pyspark  # noqa: F401
    except ImportError:
        return False, "pyspark not installed"
    if not os.path.isdir(REFERENCE_SRC):
        return False, f"reference source not found at {REFERENCE_SRC}"
    if not glob.glob(os.path.join(DATA, "*.parquet")):
        return False, f"golden input data not found at {DATA}"
    return True, "ok"


def _spark():
    from pyspark.sql import SparkSession

    return (
        SparkSession.builder.master("local[*]")
        .appName("golden-oracle")
        .config("spark.driver.memory", "4g")
        .config("spark.sql.shuffle.partitions", "8")
        .getOrCreate()
    )


def _round_frame(pdf: pd.DataFrame) -> pd.DataFrame:
    for c in pdf.columns:
        if pd.api.types.is_float_dtype(pdf[c]):
            pdf[c] = pdf[c].round(4)
    return pdf


def _load_pandas_encoder():
    """generate_golden.py loaded as a module (shared synthetic builders)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "generate_golden", os.path.join(HERE, "generate_golden.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def regenerate() -> dict:
    """Run the reference on the golden inputs; return {fixture: DataFrame}."""
    sys.path.insert(0, REFERENCE_SRC)
    from anovos.data_analyzer import association_evaluator as ae
    from anovos.data_analyzer import quality_checker as qc
    from anovos.data_analyzer import stats_generator as sg
    from anovos.data_transformer import transformers as tr
    from anovos.drift_stability import drift_detector as dd
    from anovos.drift_stability import stability as st

    spark = _spark()
    idf = spark.read.parquet(DATA).select(NUM_COLS + CAT_COLS)
    idf.persist()
    n = idf.count()
    out = {}

    out["golden_counts.csv"] = sg.measures_of_counts(spark, idf).toPandas()
    out["golden_central.csv"] = sg.measures_of_centralTendency(spark, idf).toPandas()
    out["golden_cardinality.csv"] = sg.measures_of_cardinality(spark, idf).toPandas()
    out["golden_dispersion.csv"] = sg.measures_of_dispersion(spark, idf).toPandas()
    out["golden_percentiles.csv"] = sg.measures_of_percentiles(spark, idf).toPandas()
    out["golden_shape.csv"] = sg.measures_of_shape(spark, idf).toPandas()

    # drift: same halves as generate_golden.load() — row order of the
    # parquet read is deterministic for a local sorted file list
    pdf = idf.toPandas()
    src = spark.createDataFrame(pdf.iloc[: n // 2])
    tgt = spark.createDataFrame(pdf.iloc[n // 2:])
    with tempfile.TemporaryDirectory() as d:
        drift = dd.statistics(
            spark, tgt, src, method_type="all", use_sampling=False,
            source_path=os.path.join(d, "drift_src"),
        ).toPandas()
    out["golden_drift.csv"] = drift

    out["golden_correlation.csv"] = ae.correlation_matrix(
        spark, idf.select(NUM_COLS)
    ).toPandas()
    out["golden_iv.csv"] = ae.IV_calculation(
        spark, idf, label_col=LABEL_COL, event_label=EVENT
    ).toPandas()
    out["golden_ig.csv"] = ae.IG_calculation(
        spark, idf, label_col=LABEL_COL, event_label=EVENT
    ).toPandas()

    dup_input = idf.union(idf.limit(500))  # fixture appends first 500 rows
    out["golden_duplicates.csv"] = qc.duplicate_detection(
        spark, dup_input, treatment=False
    )[1].toPandas()
    out["golden_nullrows.csv"] = qc.nullRows_detection(
        spark, idf, treatment=False, treatment_threshold=0.1
    )[1].toPandas()

    # ---- model-artifact fixtures ---------------------------------------
    out["golden_outlier.csv"] = qc.outlier_detection(
        spark, idf.select(NUM_COLS), detection_side="both", treatment=False
    )[1].toPandas()

    with tempfile.TemporaryDirectory() as d:
        rows = []
        for method in ("equal_range", "equal_frequency"):
            mp = os.path.join(d, method)
            odf = tr.attribute_binning(
                spark, idf.select(NUM_COLS), list_of_cols=NUM_COLS,
                method_type=method, bin_size=10, model_path=mp,
            )
            model = spark.read.parquet(mp + "/attribute_binning").toPandas()
            cuts = dict(zip(model["attribute"], model["parameters"]))
            for c in NUM_COLS:
                counts = (
                    odf.groupBy(c).count().toPandas()
                    .set_index(c)["count"].to_dict()
                )
                rows.append({
                    "attribute": c, "method": method,
                    **{f"cut_{j}": round(float(cuts[c][j - 1]), 4)
                       for j in range(1, 10)},
                    **{f"bin_{j}": int(counts.get(j, counts.get(float(j), 0)))
                       for j in range(1, 11)},
                })
        out["golden_binning.csv"] = pd.DataFrame(rows)

        # scaler fit parameters from the saved model artifacts (parquet,
        # schema [feature, parameters]: z -> [mean, stddev], IQR -> the
        # [q25, q50, q75] approxQuantile triple)
        zp, qp = os.path.join(d, "z"), os.path.join(d, "iqr")
        tr.z_standardization(spark, idf.select(NUM_COLS), model_path=zp)
        tr.IQR_standardization(spark, idf.select(NUM_COLS), model_path=qp)
        z = spark.read.parquet(zp + "/z_standardization").toPandas()
        q = spark.read.parquet(qp + "/IQR_standardization").toPandas()
        zmap = dict(zip(z["feature"], z["parameters"]))
        qmap = dict(zip(q["feature"], q["parameters"]))
        out["golden_scalers.csv"] = pd.DataFrame([
            {
                "attribute": c,
                "mean": round(float(zmap[c][0]), 4),
                "stddev": round(float(zmap[c][1]), 4),
                "median": round(float(qmap[c][1]), 4),
                "IQR": round(float(qmap[c][2] - qmap[c][0]), 4),
            }
            for c in NUM_COLS
        ])

    gg = _load_pandas_encoder()
    sdfs = [spark.createDataFrame(p) for p in gg.stability_datasets()]
    stab = st.stability_index_computation(spark, sdfs).toPandas()
    if "flagged" not in stab.columns and "stability_index" in stab.columns:
        stab["flagged"] = (stab["stability_index"] < 1).astype(int)
    out["golden_stability.csv"] = stab

    ie = qc.invalidEntries_detection(
        spark, spark.createDataFrame(gg._ie_frame()), treatment=False
    )[1].toPandas()
    if "invalid_entries" in ie.columns:
        # the fixture pins a normalized encoding: entries lowercased/trimmed
        # and sorted inside the pipe-join (the reference emits raw-case
        # values in engine order), and clean columns as an empty cell (the
        # reference joins [] to "") — normalize before diffing
        def _norm_entries(s):
            if pd.isna(s) or str(s) == "":
                return np.nan
            ents = sorted({e.lower().strip() for e in str(s).split("|") if e.strip() or e})
            return "|".join(ents) if ents else np.nan

        ie["invalid_entries"] = ie["invalid_entries"].map(_norm_entries)
    out["golden_invalid_entries.csv"] = ie

    return {k: _round_frame(v) for k, v in out.items()}


def diff(regen: dict) -> list:
    """Compare regenerated oracle output to the committed pandas encodings.

    Returns a list of failure strings (empty = parity)."""
    failures = []
    for name, got in regen.items():
        path = os.path.join(HERE, name)
        want = pd.read_csv(path)
        tol = RTOL[ORACLE_MAPPED[name]]
        # align on the fixture's key columns — composite for fixtures with
        # several rows per attribute (binning: one row per method)
        keys = [c for c in ("attribute", "method", "metric") if c in want.columns]
        if keys and all(k in got.columns for k in keys):
            got = want[keys].merge(got, on=keys, how="left")
        for c in want.columns:
            if c not in got.columns:
                failures.append(f"{name}: column {c!r} missing from oracle output")
                continue
            w, g = want[c], got[c]
            if pd.api.types.is_numeric_dtype(w):
                wv = w.to_numpy(float)
                gv = pd.to_numeric(g, errors="coerce").to_numpy(float)
                both = ~(np.isnan(wv) | np.isnan(gv))
                if (np.isnan(wv) != np.isnan(gv)).any():
                    failures.append(f"{name}.{c}: null-pattern mismatch")
                scale = np.maximum(np.abs(wv[both]), 1e-4)
                bad = np.abs(wv[both] - gv[both]) / scale > tol
                if bad.any():
                    i = int(np.nonzero(bad)[0][0])
                    failures.append(
                        f"{name}.{c}: {int(bad.sum())} values beyond rtol={tol} "
                        f"(first: want {wv[both][i]}, got {gv[both][i]})"
                    )
            else:
                # NaN (empty CSV cell) and "" are the same absent value
                wn = w.fillna("").astype(str)
                gn = g.fillna("").astype(str)
                if not wn.equals(gn):
                    n_bad = int((wn != gn).sum())
                    i = int(np.nonzero((wn != gn).to_numpy())[0][0])
                    failures.append(
                        f"{name}.{c}: {n_bad} string mismatches "
                        f"(first: want {wn.iloc[i]!r}, got {gn.iloc[i]!r})"
                    )
    return failures


def main(argv) -> int:
    ok, reason = available()
    if not ok:
        print(f"spark-oracle unavailable: {reason} (skipping)")
        return 3
    regen = regenerate()
    if "--write" in argv:
        for name, pdf in regen.items():
            pdf.to_csv(os.path.join(HERE, name), index=False)
            print(f"regenerated {name} from the Spark oracle ({len(pdf)} rows)")
    if "--diff" in argv or "--write" not in argv:
        failures = diff(regen)
        print(f"oracle-mapped fixtures: {len(regen)}")
        if failures:
            print("ORACLE DIVERGENCE:")
            for f in failures:
                print(" -", f)
            return 1
        print("oracle parity: all mapped fixtures agree within tolerance")
    return 0
