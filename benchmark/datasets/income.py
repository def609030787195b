"""The income-schema dataset of the benchmark: a seeded generator.

A copy of ``anovos_tpu/data_ingest/synthetic.py`` as PR 22 left it (same
columns, same draws in the same order) but for the category lists, which
here are the public dataset's own; the data is part of the yardstick, so
the benchmark keeps its own.  It writes only the parts a traffic mix names
and imports nothing of the program.  A configuration file names this module
under ``dataset.module``; another schema brings another module here.  The
plain references that read this data are ``benchmark/checks/``.
"""

from __future__ import annotations

import os
import shutil
from typing import Iterable, Optional

import numpy as np
import pandas as pd

ROWS_PER_PART = 500_000
MIN_PARTS = 4
SI_PERIODS = 4

# numeric columns of the stability-index slices (names as the raw table has them)
SI_COLS = ["age", "fnlwgt", "education-num", "capital-gain", "capital-loss",
           "hours-per-week", "latitude", "longitude"]
# the categories of the public UCI Adult / Anovos income data, every one of
# them, most frequent first as there (the Zipf-like weights below follow the
# list's order); synthetic.py has fewer (education 9, occupation 10,
# native-country 8), which is where this copy departs from it
_WORKCLASS = ["Private", "Self-emp-not-inc", "Local-gov", "State-gov", "Self-emp-inc",
              "Federal-gov", "Without-pay", "Never-worked"]
_EDUCATION = ["HS-grad", "Some-college", "Bachelors", "Masters", "Assoc-voc", "11th",
              "Assoc-acdm", "10th", "7th-8th", "Prof-school", "9th", "12th", "Doctorate",
              "5th-6th", "1st-4th", "Preschool"]
_MARITAL = ["Married-civ-spouse", "Never-married", "Divorced", "Separated", "Widowed",
            "Married-spouse-absent", "Married-AF-spouse"]
_OCCUPATION = ["Prof-specialty", "Craft-repair", "Exec-managerial", "Adm-clerical", "Sales",
               "Other-service", "Machine-op-inspct", "Transport-moving", "Handlers-cleaners",
               "Farming-fishing", "Tech-support", "Protective-serv", "Priv-house-serv",
               "Armed-Forces"]
_RELATIONSHIP = ["Husband", "Not-in-family", "Own-child", "Unmarried", "Wife", "Other-relative"]
_RACE = ["White", "Black", "Asian-Pac-Islander", "Amer-Indian-Eskimo", "Other"]
_COUNTRY = ["United-States", "Mexico", "Philippines", "Germany", "Canada", "Puerto-Rico",
            "El-Salvador", "India", "Cuba", "England", "Jamaica", "South", "China", "Italy",
            "Dominican-Republic", "Vietnam", "Guatemala", "Japan", "Poland", "Columbia",
            "Taiwan", "Haiti", "Iran", "Portugal", "Nicaragua", "Peru", "Greece", "France",
            "Ecuador", "Ireland", "Hong", "Cambodia", "Trinadad&Tobago", "Laos", "Thailand",
            "Yugoslavia", "Outlying-US(Guam-USVI-etc)", "Hungary", "Honduras", "Scotland",
            "Holand-Netherlands"]

_DATA_DICTIONARY = [
    ("ifa", "unique record id"), ("age", "age in years"),
    ("workclass", "employer type"), ("fnlwgt", "census sampling weight"),
    ("logfnl", "natural log of fnlwgt"), ("education", "highest education level"),
    ("education-num", "education level, ordinal"), ("marital-status", "marital status"),
    ("occupation", "occupation group"), ("relationship", "household relationship"),
    ("race", "race"), ("sex", "sex"), ("capital-gain", "capital gains"),
    ("capital-loss", "capital losses"), ("hours-per-week", "hours worked per week"),
    ("native-country", "country of origin"), ("income", "income bracket (label)"),
    ("label", "binary label"), ("latitude", "latitude, degrees"),
    ("longitude", "longitude, degrees"), ("geohash", "geohash cell"),
    ("empty", "all-null column"), ("dt_1", "event date"), ("dt_2", "event date + 30 days"),
]

_METRIC_DICTIONARY = [
    ("Descriptive Statistics", "fill_pct", "share of non-null rows"),
    ("Descriptive Statistics", "mean", "arithmetic mean of non-null values"),
    ("Descriptive Statistics", "median", "50th percentile"),
    ("Descriptive Statistics", "stddev", "sample standard deviation"),
    ("Descriptive Statistics", "skewness", "population skewness"),
    ("Descriptive Statistics", "kurtosis", "excess kurtosis"),
    ("Quality Check", "duplicate_rows", "rows repeating an earlier row"),
    ("Quality Check", "null_rows", "rows by count of null columns"),
    ("Quality Check", "outlier", "values beyond percentile/stddev/IQR bounds"),
    ("Attribute Associations", "correlation", "Pearson correlation"),
    ("Attribute Associations", "iv", "information value against the label"),
    ("Attribute Associations", "ig", "information gain against the label"),
    ("Data Drift & Stability", "PSI", "population stability index"),
    ("Data Drift & Stability", "HD", "Hellinger distance"),
    ("Data Drift & Stability", "JSD", "Jensen-Shannon divergence"),
    ("Data Drift & Stability", "KS", "Kolmogorov-Smirnov statistic"),
    ("Data Drift & Stability", "stability_index", "weighted CV score of mean/stddev/kurtosis"),
]


# ------------------------------------------------------------- generator ----
def _probs(k: int, drift: float) -> np.ndarray:
    """Zipf-like category weights; ``drift`` moves mass toward the tail."""
    w = 1.0 / np.arange(1, k + 1) ** (1.2 - drift)
    return w / w.sum()


def _take(cats, codes: np.ndarray, null_mask: Optional[np.ndarray] = None) -> pd.Series:
    """``cats[codes]`` as a string column, built in bulk by arrow (a
    python-object array of a million strings costs seconds per column)."""
    import pyarrow as pa

    idx = pa.array(codes.astype(np.int32), mask=null_mask)
    return pa.DictionaryArray.from_arrays(idx, pa.array(list(cats))).cast(pa.string()).to_pandas()


def _pick(rng, cats, n: int, drift: float, null_frac: float = 0.0) -> pd.Series:
    codes = rng.choice(len(cats), n, p=_probs(len(cats), drift))
    return _take(cats, codes, rng.random(n) < null_frac if null_frac else None)


def synthesize(rows: int, seed: int, drift: float = 0.0) -> pd.DataFrame:
    """The 24-column income-schema frame, a pure function of the arguments:
    11 float and 13 string columns, nulls in ``age`` and three categoricals,
    0.1 % of rows repeating another row in everything but the id ``ifa``.
    ``drift`` shifts numeric locations and category weights."""
    n = int(rows)
    rng = np.random.default_rng([int(seed), int(round(drift * 1000))])
    fnlwgt = np.round(rng.lognormal(12.0, 0.55, n).clip(1.2e4, 1.5e6))
    age = np.round(rng.gamma(6.0, 6.5 + 4 * drift, n) + 17).clip(17, 90)
    age[rng.random(n) < 0.02] = np.nan
    days = rng.integers(0, 3600, n)
    day_str = (pd.Timestamp("2015-01-01")
               + pd.to_timedelta(np.arange(3630), unit="D")).strftime("%Y-%m-%d")
    income_p = 0.24 + 0.3 * drift
    df = pd.DataFrame(
        {
            "ifa": _take([f"id{i:04d}" for i in range(10_000)], np.arange(n) // 1000)
            + _take([f"{i:03d}" for i in range(1000)], np.arange(n) % 1000),
            "age": age,
            "workclass": _pick(rng, _WORKCLASS, n, drift, null_frac=0.05),
            "fnlwgt": fnlwgt,
            "logfnl": np.log(fnlwgt),
            "education": _pick(rng, _EDUCATION, n, drift),
            "education-num": rng.integers(1, 17, n).astype(float),
            "marital-status": _pick(rng, _MARITAL, n, drift),
            "occupation": _pick(rng, _OCCUPATION, n, drift, null_frac=0.05),
            "relationship": _pick(rng, _RELATIONSHIP, n, drift),
            "race": _pick(rng, _RACE, n, drift),
            "sex": _pick(rng, ["Male", "Female"], n, drift),
            "capital-gain": np.where(rng.random(n) < 0.08,
                                     np.round(rng.gamma(2, 5000, n)), 0.0),
            "capital-loss": np.where(rng.random(n) < 0.05,
                                     np.round(rng.gamma(2, 900, n)), 0.0),
            "hours-per-week": np.round(rng.normal(40 + 8 * drift, 12, n)).clip(1, 99),
            "native-country": _pick(rng, _COUNTRY, n, drift, null_frac=0.02),
            "income": _take(["<=50K", ">50K"], rng.random(n) < income_p),
            "label": rng.integers(0, 2, n).astype(float),
            "latitude": rng.uniform(25.0, 48.0, n),
            "longitude": rng.uniform(-122.0, -71.0, n),
            "geohash": _take([f"9q{i:02d}" for i in range(97)], rng.integers(0, 97, n)),
            "empty": np.full(n, np.nan),
            "dt_1": _take(day_str, days),
            "dt_2": _take(day_str, days + 30),
        }
    )
    n_dup = n // 1000
    if n_dup:
        dst = rng.choice(n, n_dup, replace=False)
        src = rng.integers(0, n, n_dup)
        cols = [c for c in df.columns if c != "ifa"]
        df.loc[dst, cols] = df.loc[src, cols].to_numpy()
    return df


def _write_parts(df: pd.DataFrame, out_dir: str, n_parts: int) -> None:
    os.makedirs(out_dir)
    edges = np.linspace(0, len(df), n_parts + 1).astype(int)
    for i in range(n_parts):
        df.iloc[edges[i]:edges[i + 1]].to_parquet(
            os.path.join(out_dir, f"part-{i:05d}.parquet"), index=False)


def generate(dest: str, seed: int, parts: Iterable[str], rows: int,
             source_rows: Optional[int] = None) -> None:
    """Write the named ``parts`` of the dataset under ``dest`` (emptied
    first: every run makes its data anew, so set-up is the same work for
    every seed).

    ``parquet``: the main table as part files; ``source``: the drifted
    baseline (``source_rows``, default ``rows // 4``); ``stability_index``:
    four CSV period slices; ``dictionaries``: the report's two CSVs."""
    parts = set(parts)
    unknown = parts - {"parquet", "source", "stability_index", "dictionaries"}
    if unknown:
        raise ValueError(f"unknown dataset parts {sorted(unknown)}")
    quarter = max(rows // 4, 1)
    shutil.rmtree(dest, ignore_errors=True)
    os.makedirs(dest)
    df = synthesize(rows, seed)
    if "parquet" in parts:
        _write_parts(df, os.path.join(dest, "parquet"), max(MIN_PARTS, -(-rows // ROWS_PER_PART)))
    if "source" in parts:
        _write_parts(synthesize(source_rows or quarter, seed, drift=0.15),
                     os.path.join(dest, "source"), 2)
    if "stability_index" in parts:
        import pyarrow as pa
        import pyarrow.csv as pacsv

        for i in range(SI_PERIODS):
            si_dir = os.path.join(dest, "stability_index", str(i))
            os.makedirs(si_dir)
            pacsv.write_csv(
                pa.Table.from_pandas(synthesize(quarter, seed, drift=0.02 * (i + 1))[SI_COLS],
                                     preserve_index=False),
                os.path.join(si_dir, "part-00000.csv"))
    if "dictionaries" in parts:
        pd.DataFrame(_DATA_DICTIONARY, columns=["column_name", "definition"]).to_csv(
            os.path.join(dest, "data_dictionary.csv"), index=False)
        pd.DataFrame(_METRIC_DICTIONARY,
                     columns=["Section Category", "Metric Name", "Metric Definitions"]).to_csv(
            os.path.join(dest, "metric_dictionary.csv"), index=False)
