"""The ``epsilon`` table of the PASCAL Large Scale Learning Challenge (2008)
in the form LIBSVM republished it (``epsilon_normalized``): 400,000 training
rows, **2,000 dense real features**, every feature standardised over the
table and every row then scaled to unit length, a label of +1 / -1 in about
equal shares, no nulls.  A seeded, vectorised generator that writes the
table's 2,001 columns as parquet part files of 32-bit floats:

    label        int32    +1 / -1
    f1 .. f2000  float    dense, every row of unit Euclidean length

What is the source's: the width, the label's two values and their balance,
that every feature is dense and real, the two normalisations in their order,
that nothing is null, ``SOURCE_ROWS``.  What is assumed
(``benchmark/configs/epsilon_2k.json`` names each): parquet in place of
LIBSVM text and the rows a part; how the features are drawn.  The challenge
never said how its features came about, so the generator gives an
autoencoder what such a table gives it, something to learn: 250 latent
factors a row, standard normal, reach the 2,000 features through a seeded
mixing, ``LINEAR_SHARE`` of each feature's signal straight and the
rest through a tanh of a second mixing; noise of ``NOISE_SHARE`` of a
feature's variance is added; then the source's own two steps.  The label is
the sign of a seeded linear score of the factors with noise.  Everything is
a function of ``(rows, seed)``; the data are made without the program (see
``require_history_file`` for the one question asked of it) and with no
Python loop over rows.
"""

from __future__ import annotations

import os
import shutil
from typing import Iterable, Optional

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

SOURCE_ROWS = 400_000  # the training file; the 100,000 test rows are not used
FEATURES = 2000
ROWS_PER_PART = 25_000  # 200 MB of floats a part: sixteen parts at the source's size

# assumed: how the features are drawn
FACTORS_PER_FEATURE = 1 / 8  # latent factors a row: 250 at the source's width
HIDDEN_PER_FACTOR = 2  # width of the nonlinear path: 500
LINEAR_SHARE = 0.6  # of a feature's signal variance that comes straight from the factors
NOISE_SHARE = 0.25  # of a feature's variance that no factor explains
LABEL_NOISE = 0.5  # standard deviations of noise on the label's unit-variance score
CHUNK = 50_000  # rows drawn at a time: bounds the float64 working set, not the values


def feature_names(features: int = FEATURES) -> list:
    return [f"f{i}" for i in range(1, features + 1)]  # LIBSVM counts features from 1


def schema(features: int = FEATURES) -> pa.Schema:
    return pa.schema([("label", pa.int32())] + [(name, pa.float32()) for name in feature_names(features)])


def _mixing(seed: int, features: int) -> dict:
    """The fixed part of the law: the matrices every row goes through."""
    rng = np.random.default_rng([seed, 1])
    factors = max(int(features * FACTORS_PER_FEATURE), 2)
    hidden = HIDDEN_PER_FACTOR * factors
    mix = {
        "direct": rng.standard_normal((factors, features)) / np.sqrt(factors),
        "inner": rng.standard_normal((factors, hidden)) * (1.5 / np.sqrt(factors)),
        "outer": rng.standard_normal((hidden, features)) / np.sqrt(hidden),
        "score": rng.standard_normal(factors) / np.sqrt(factors),
    }
    # the nonlinear path brought to unit variance, on a probe of its own: a row's value
    # does not depend on which rows are drawn with it
    probe = np.tanh(rng.standard_normal((4096, factors)) @ mix["inner"]) @ mix["outer"]
    mix["outer"] /= probe.std()
    return mix


def synthesize(rows: int, seed: int, features: int = FEATURES) -> dict:
    """``{"label": int32 (rows,), "features": float32 (rows, features)}``."""
    mix = _mixing(seed, features)
    factors = len(mix["score"])
    rng = np.random.default_rng([seed, 2])
    raw = np.empty((rows, features), np.float64)
    label = np.empty(rows, np.int32)
    for lo in range(0, rows, CHUNK):
        n = min(CHUNK, rows - lo)
        z = rng.standard_normal((n, factors))
        bent = np.tanh(z @ mix["inner"]) @ mix["outer"]
        signal = np.sqrt(LINEAR_SHARE) * (z @ mix["direct"]) + np.sqrt(1 - LINEAR_SHARE) * bent
        raw[lo:lo + n] = (np.sqrt(1 - NOISE_SHARE) * signal
                          + np.sqrt(NOISE_SHARE) * rng.standard_normal((n, features)))
        score = z @ mix["score"] + LABEL_NOISE * rng.standard_normal(n)
        label[lo:lo + n] = np.where(score >= 0, 1, -1)
    # the source's two steps, in its order: every feature standardised, every row to unit length
    raw -= raw.mean(axis=0)
    raw /= raw.std(axis=0)
    raw /= np.linalg.norm(raw, axis=1, keepdims=True)
    return {"label": label, "features": raw.astype(np.float32)}


def arrow_table(cols: dict, lo: int, hi: int) -> pa.Table:
    by_column = np.ascontiguousarray(cols["features"][lo:hi].T)  # one transpose, not a strided copy a column
    arrays = [pa.array(cols["label"][lo:hi], type=pa.int32())]
    arrays += [pa.array(column, type=pa.float32()) for column in by_column]
    return pa.Table.from_arrays(arrays, schema=schema(len(by_column)))


def require_history_file() -> None:
    """Stop at once where the program cannot run this deployment.  The mix
    lists the fit's ``history.csv`` among the files a pass must leave, and the
    reference reads it.  A program from before that file trains and writes
    every pass of the window and fails each for the missing file, which is
    known before any data is made: say it then, with an exit code that is not
    0.  (The one thing this module asks of the program; the data are made
    without it.)"""
    from anovos_tpu.models import autoencoder

    if getattr(autoencoder, "HISTORY_FILE", None) != "history.csv":
        raise SystemExit("epsilon_2k: this checkout's autoencoder keeps no history.csv "
                         "(anovos_tpu.models.autoencoder.HISTORY_FILE): it cannot run the deployment")


def generate(dest: str, seed: int, parts: Iterable[str], rows: int,
             source_rows: Optional[int] = None, features: int = FEATURES) -> None:
    """Write the table under ``dest/parquet`` (``dest`` emptied first) as
    part files of ``ROWS_PER_PART`` rows, the last one the rest, in the order
    of the rows.  ``parquet`` is the one part this dataset has;
    ``source_rows`` is taken and ignored (no baseline); ``features`` is the
    source's 2,000 but in a test that states its width.  The floats are
    written plain: a dictionary of values that never repeat is only cost."""
    unknown = set(parts) - {"parquet"}
    if unknown:
        raise ValueError(f"unknown dataset parts {sorted(unknown)}")
    require_history_file()
    shutil.rmtree(dest, ignore_errors=True)
    os.makedirs(dest)
    if "parquet" not in set(parts):
        return
    out_dir = os.path.join(dest, "parquet")
    os.makedirs(out_dir)
    cols = synthesize(rows, seed, features)
    for i, lo in enumerate(range(0, rows, ROWS_PER_PART)):
        pq.write_table(arrow_table(cols, lo, min(lo + ROWS_PER_PART, rows)),
                       os.path.join(out_dir, f"part-{i:05d}.parquet"), use_dictionary=["label"])
