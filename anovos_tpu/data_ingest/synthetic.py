"""Seeded generator of the income-schema demo dataset.

The upstream project ships a 32,561-row demo dataset (census income with
id, geo and date columns added) that every income config reads.  Nothing
binary is committed here: this module writes the same schema, as a pure
function of ``(rows, seed)``, under a git-ignored directory of the checkout
(``data/income_dataset/`` by default):

* ``parquet/part-0000N.parquet``  the main table, several part files as a
  Spark job would leave them (24 columns);
* ``source/``                     a drifted baseline (``rows // 4`` rows) for
  drift checks that must not compare a table with itself;
* ``join/part-00000.avro``        the ``ifa``-keyed join side (first
  ``min(rows, 32561)`` ids — the avro codec is the slow writer);
* ``stability_index/{0..3}/``     four CSV period slices (``rows // 4`` rows
  each, numeric columns, slowly drifting);
* ``data_dictionary.csv``, ``metric_dictionary.csv``  the report's wiki tab.

``python -m anovos_tpu.data_ingest.synthetic [--rows N] [--seed S] [--dest D]``
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import shutil
import sys
from typing import Optional

import numpy as np
import pandas as pd

# upstream demo set's size: the default small dataset of tests and examples
DEFAULT_ROWS = 32561
DEFAULT_SEED = 7
CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
DATA_ROOT = CHECKOUT / "data"
DEFAULT_DIR = DATA_ROOT / "income_dataset"
ROWS_PER_PART = 500_000
MIN_PARTS = 4
JOIN_ROWS_MAX = DEFAULT_ROWS
SI_PERIODS = 4

NUM_COLS = ["age", "fnlwgt", "education-num", "capital-gain", "capital-loss",
            "hours-per-week", "latitude", "longitude"]

_WORKCLASS = ["Private", "Self-emp-not-inc", "Self-emp-inc", "Federal-gov",
              "Local-gov", "State-gov", "Without-pay"]
_EDUCATION = ["HS-grad", "Some-college", "Bachelors", "Masters", "Assoc-voc",
              "11th", "Assoc-acdm", "10th", "Doctorate"]
_MARITAL = ["Married-civ-spouse", "Never-married", "Divorced", "Separated", "Widowed"]
_OCCUPATION = ["Prof-specialty", "Craft-repair", "Exec-managerial", "Adm-clerical",
               "Sales", "Other-service", "Machine-op-inspct", "Transport-moving",
               "Farming-fishing", "Tech-support"]
_RELATIONSHIP = ["Husband", "Not-in-family", "Own-child", "Unmarried", "Wife", "Other-relative"]
_RACE = ["White", "Black", "Asian-Pac-Islander", "Amer-Indian-Eskimo", "Other"]
_COUNTRY = ["United-States", "Mexico", "Philippines", "Germany", "Canada",
            "India", "England", "Cuba"]


def _probs(k: int, drift: float) -> np.ndarray:
    """Zipf-like category weights; ``drift`` moves mass toward the tail."""
    w = 1.0 / np.arange(1, k + 1) ** (1.2 - drift)
    return w / w.sum()


def _take(cats, codes: np.ndarray, null_mask: Optional[np.ndarray] = None) -> pd.Series:
    """``cats[codes]`` as a string column, built in bulk by arrow (a
    python-object array of 4 M strings costs seconds per column)."""
    import pyarrow as pa

    idx = pa.array(codes.astype(np.int32), mask=null_mask)
    return pa.DictionaryArray.from_arrays(idx, pa.array(list(cats))).cast(pa.string()).to_pandas()


def _pick(rng, cats, n: int, drift: float, null_frac: float = 0.0) -> pd.Series:
    codes = rng.choice(len(cats), n, p=_probs(len(cats), drift))
    return _take(cats, codes, rng.random(n) < null_frac if null_frac else None)


def synthesize(rows: int = DEFAULT_ROWS, seed: int = DEFAULT_SEED,
               drift: float = 0.0) -> pd.DataFrame:
    """The 24-column income-schema frame, a pure function of the arguments.

    Includes the ``logfnl`` / ``empty`` / ``dt_2`` columns the demo configs
    delete, nulls in ``age`` and three categoricals, and 0.1 % of rows that
    repeat another row in everything but ``ifa``.  ``drift`` shifts numeric
    locations and category weights (0 = the target population)."""
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
    # exact duplicates in everything but the id
    n_dup = n // 1000
    if n_dup:
        dst = rng.choice(n, n_dup, replace=False)
        src = rng.integers(0, n, n_dup)
        cols = [c for c in df.columns if c != "ifa"]
        df.loc[dst, cols] = df.loc[src, cols].to_numpy()
    return df


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


def _write_parts(df: pd.DataFrame, out_dir: pathlib.Path, n_parts: int) -> None:
    out_dir.mkdir(parents=True, exist_ok=True)
    edges = np.linspace(0, len(df), n_parts + 1).astype(int)
    for i in range(n_parts):
        df.iloc[edges[i]:edges[i + 1]].to_parquet(
            out_dir / f"part-{i:05d}.parquet", index=False)


def generate(rows: int = DEFAULT_ROWS, seed: int = DEFAULT_SEED,
             dest: Optional[os.PathLike] = None) -> str:
    """Write the dataset under ``dest`` (default ``data/income_dataset``).

    Idempotent and safe under concurrent callers (test workers): a directory
    already stamped with the same ``(rows, seed)`` is left alone; otherwise
    everything is written beside it and renamed into place."""
    dest = pathlib.Path(dest) if dest is not None else DEFAULT_DIR
    stamp_file = dest / "_GENERATED.json"
    stamp = {"rows": int(rows), "seed": int(seed), "schema": 1}
    if stamp_file.exists() and json.loads(stamp_file.read_text()) == stamp:
        return str(dest)
    tmp = dest.with_name(f".{dest.name}.tmp{os.getpid()}")
    shutil.rmtree(tmp, ignore_errors=True)
    tmp.mkdir(parents=True)

    df = synthesize(rows, seed)
    _write_parts(df, tmp / "parquet", max(MIN_PARTS, -(-rows // ROWS_PER_PART)))
    _write_parts(synthesize(max(rows // 4, 1), seed, drift=0.15), tmp / "source", 2)

    import pyarrow as pa
    import pyarrow.csv as pacsv

    from anovos_tpu.data_ingest.avro_io import write_avro

    (tmp / "join").mkdir()
    write_avro(df.loc[: min(rows, JOIN_ROWS_MAX) - 1, ["ifa", "age", "workclass"]],
               str(tmp / "join" / "part-00000.avro"))
    for i in range(SI_PERIODS):
        si_dir = tmp / "stability_index" / str(i)
        si_dir.mkdir(parents=True)
        # arrow's CSV writer: pandas' takes 5 s per million rows
        pacsv.write_csv(
            pa.Table.from_pandas(synthesize(max(rows // 4, 1), seed, drift=0.02 * (i + 1))[NUM_COLS],
                                 preserve_index=False),
            si_dir / "part-00000.csv")
    pd.DataFrame(_DATA_DICTIONARY, columns=["column_name", "definition"]).to_csv(
        tmp / "data_dictionary.csv", index=False)
    pd.DataFrame(_METRIC_DICTIONARY,
                 columns=["Section Category", "Metric Name", "Metric Definitions"]).to_csv(
        tmp / "metric_dictionary.csv", index=False)
    (tmp / "_GENERATED.json").write_text(json.dumps(stamp))

    old = dest.with_name(f".{dest.name}.old{os.getpid()}")
    try:
        if dest.exists():
            os.rename(dest, old)
        os.rename(tmp, dest)
    except OSError:
        # another worker renamed its identical copy into place first
        shutil.rmtree(tmp, ignore_errors=True)
    shutil.rmtree(old, ignore_errors=True)
    return str(dest)


def load_income(rows: int = DEFAULT_ROWS, seed: int = DEFAULT_SEED,
                dest: Optional[os.PathLike] = None) -> pd.DataFrame:
    """The main table as pandas, generated on first use."""
    import glob

    files = sorted(glob.glob(os.path.join(generate(rows, seed, dest), "parquet", "*.parquet")))
    # files this module wrote a moment ago, read back for tests/examples
    return pd.concat([pd.read_parquet(f) for f in files],  # graftcheck: disable=GC012
                     ignore_index=True)


def rebase_config(node, old: str = "data/", new: Optional[str] = None):
    """Copy of a loaded YAML config with every string that starts with
    ``old`` re-rooted at ``new`` (default: this checkout's ``data/``,
    absolute) — for callers that run a shipped config from another cwd or
    against a dataset generated elsewhere."""
    new = f"{DATA_ROOT}/" if new is None else new
    if isinstance(node, dict):
        return {k: rebase_config(v, old, new) for k, v in node.items()}
    if isinstance(node, list):
        return [rebase_config(v, old, new) for v in node]
    if isinstance(node, str) and node.startswith(old):
        return new + node[len(old):]
    return node


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rows", type=int, default=DEFAULT_ROWS)
    ap.add_argument("--seed", type=int, default=DEFAULT_SEED)
    ap.add_argument("--dest", default=None, help=f"default {DEFAULT_DIR}")
    a = ap.parse_args(argv)
    sys.stdout.write(generate(a.rows, a.seed, a.dest) + "\n")


if __name__ == "__main__":
    main()
