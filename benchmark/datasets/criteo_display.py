"""The Criteo Display Advertising Challenge click log (Criteo Labs / Kaggle,
2014; ``train.txt`` as its ``readme.txt`` lays it out): a seeded, vectorised
generator that writes the table's 40 columns as parquet part files.

    label     int32   1 = the ad was clicked; no nulls
    I1..I13   int64   "mostly count features"; missing values are nulls
    C1..C26   string  categories "hashed onto 32 bits": 8 lower-case hex
                      characters; missing values are nulls

What is the source's: the columns, their types, which of them have missing
values, the per-column category counts of the whole file (``CATEGORY_COUNTS``,
as facebookresearch/dlrm reads them for its Kaggle run) and a click rate of
about a quarter.  What is assumed (``benchmark/configs/criteo_display.json``
names each): the law a column's categories are drawn from (a power law over
the PUBLISHED count, so the distinct values among ``rows`` are what a prefix
of the file would show: fewer than published), the null rates, the integer
distributions, the label model and the random streams (numpy's, from
``--seed``).  It imports nothing of the program and runs no Python loop over
rows.
"""

from __future__ import annotations

import os
import shutil
from typing import Iterable, Optional

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

ROWS_PER_PART = 500_000  # income.py's
SOURCE_ROWS = 45_840_617
CLICK_RATE = 0.256

INTEGERS = [f"I{i}" for i in range(1, 14)]
CATEGORICALS = [f"C{i}" for i in range(1, 27)]
CATEGORY_COUNTS = [1460, 583, 10131227, 2202608, 305, 24, 12517, 633, 3, 93145, 5683, 8351593, 3194,
                   27, 14992, 5461306, 10, 5652, 2173, 4, 7046547, 18, 15, 286181, 105, 142572]
SCHEMA = pa.schema([("label", pa.int32())] + [(c, pa.int64()) for c in INTEGERS]
                   + [(c, pa.string()) for c in CATEGORICALS])

# assumed, from the public file's profile: the share of missing values a column
INTEGER_NULLS = [0.453, 0.0, 0.215, 0.217, 0.026, 0.224, 0.043, 0.0005, 0.043, 0.453, 0.043, 0.765, 0.217]
CATEGORICAL_NULLS = {"C3": 0.034, "C4": 0.034, "C6": 0.121, "C12": 0.034, "C16": 0.034, "C19": 0.44,
                     "C20": 0.44, "C21": 0.034, "C22": 0.762, "C24": 0.034, "C25": 0.44, "C26": 0.44}
# assumed: a count is floor(exp(N(mu, sigma))) - shift, kept inside [low, high]
# (high: the largest value the public file shows; I5's lies beyond 2^24)
INTEGER_LAWS = [  # mu, sigma, shift, low, high
    (0.4, 1.3, 1, 0, 5_775), (1.2, 1.9, 3, -3, 257_675), (1.6, 1.6, 1, 0, 65_535), (1.6, 1.0, 1, 0, 969),
    (7.5, 2.6, 1, 0, 23_159_456), (3.6, 1.7, 1, 0, 431_037), (1.5, 1.6, 1, 0, 56_311), (2.2, 1.0, 1, 0, 6_047),
    (3.9, 1.4, 1, 0, 29_019), (0.0, 0.6, 1, 0, 11), (0.9, 1.0, 1, 0, 231), (0.0, 1.4, 1, 0, 4_008),
    (1.7, 1.1, 1, 0, 7_393)]
# assumed: P(rank r) ~ (r + ZIPF_OFFSET)^-ZIPF_EXPONENT over a column's published count
ZIPF_EXPONENT = 1.05
ZIPF_OFFSET = 2.0
# assumed: the click is a Bernoulli whose logit moves with the category of these columns
LABEL_COLUMNS = {"C6": 0.8, "C9": 0.5, "C14": 0.6, "C17": 0.7, "C20": 0.4, "C23": 0.6}

_HEX = np.frombuffer(b"0123456789abcdef", dtype=np.uint8)
_HEX_PAIRS = np.stack([_HEX[np.arange(256) >> 4], _HEX[np.arange(256) & 15]], axis=1)  # byte -> 2 characters


def mix32(x: np.ndarray) -> np.ndarray:
    """murmur3's 32-bit finaliser: a bijection of uint32, so two categories
    of a column never share a rendered value."""
    x = x.astype(np.uint32)
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x85EBCA6B)
    x ^= x >> np.uint32(13)
    x *= np.uint32(0xC2B2AE35)
    x ^= x >> np.uint32(16)
    return x


def zipf_ranks(rng: np.random.Generator, n: int, categories: int) -> np.ndarray:
    """``n`` ranks in ``[0, categories)`` by the inverse of the power law's
    continuous distribution function, floored."""
    s, q = ZIPF_EXPONENT, ZIPF_OFFSET
    a, b = q ** (1 - s), (categories + q) ** (1 - s)
    x = (a + rng.random(n) * (b - a)) ** (1 / (1 - s)) - q
    return np.minimum(x.astype(np.int64), categories - 1)


def column_salt(column: int, seed: int) -> np.uint32:
    """A salt of the column and the seed, drawn again until the value of the
    column's most frequent category (rank 0) holds one of ``a b c d f``: a
    reader that infers a schema re-types a string column whose EVERY value
    reads as a number (digits alone, or digits ``e`` digits), which three or
    four hashed values do once in 10^4-10^6 seeds."""
    for attempt in range(64):
        salt = mix32(np.array([(column + 1) * 0x9E3779B1 + seed + attempt * 0x7F4A7C15], dtype=np.uint64))[0]
        if set(f"{int(mix32(np.array([salt]))[0]):08x}") & set("abcdf"):
            break
    return salt


def category_ids(ranks: np.ndarray, column: int, seed: int) -> np.ndarray:
    """A column's ranks as the 32-bit values the file would hold: the rank
    xor the column's salt, mixed."""
    return mix32(ranks.astype(np.uint32) ^ column_salt(column, seed))


def synthesize(rows: int, seed: int) -> dict:
    """The table's columns as numpy arrays: ``label`` int32; per integer
    column its int64 values and ``<name>_null``; per categorical its uint32
    ids and ``<name>_null`` (absent where the column has no missing value)."""
    n, seed = int(rows), int(seed)
    rng = np.random.default_rng([seed, 0xC717E0])
    out = {}
    logit = np.zeros(n)
    for j, (name, count) in enumerate(zip(CATEGORICALS, CATEGORY_COUNTS)):
        ranks = zipf_ranks(rng, n, count)
        out[name] = category_ids(ranks, j, seed)
        if name in CATEGORICAL_NULLS:
            out[name + "_null"] = rng.random(n) < CATEGORICAL_NULLS[name]
        if name in LABEL_COLUMNS:  # a category's effect: its id's low 16 bits, in [-1/2, 1/2)
            logit += LABEL_COLUMNS[name] * ((out[name] & np.uint32(0xFFFF)) / 65536.0 - 0.5)
    logit += np.log(CLICK_RATE / (1 - CLICK_RATE)) - logit.mean()
    out["label"] = (rng.random(n) < 1 / (1 + np.exp(-logit))).astype(np.int32)
    for name, rate, (mu, sigma, shift, low, high) in zip(INTEGERS, INTEGER_NULLS, INTEGER_LAWS):
        values = np.floor(np.exp(rng.normal(mu, sigma, n))).clip(0, 2.0 ** 40).astype(np.int64) - shift
        out[name] = values.clip(low, high)
        if rate:
            out[name + "_null"] = rng.random(n) < rate
    return out


def _bitmap(null: Optional[np.ndarray]):
    return None if null is None else pa.py_buffer(np.packbits(~null, bitorder="little"))


def _hex_strings(ids: np.ndarray, null: Optional[np.ndarray]) -> pa.Array:
    """uint32 ids as an Arrow string array of their 8 lower-case hex characters."""
    n = len(ids)
    data = _HEX_PAIRS[ids.astype(">u4").view(np.uint8)]  # (4n, 2): most significant byte first
    offsets = np.arange(n + 1, dtype=np.int32) * 8
    return pa.Array.from_buffers(pa.string(), n, [_bitmap(null), pa.py_buffer(offsets), pa.py_buffer(data)],
                                 null_count=int(null.sum()) if null is not None else 0)


def arrow_table(cols: dict, lo: int, hi: int) -> pa.Table:
    """Rows ``lo:hi`` of ``synthesize``'s columns as an Arrow table of ``SCHEMA``."""
    def part(name):
        null = cols.get(name + "_null")
        return cols[name][lo:hi], None if null is None else null[lo:hi]

    arrays = [pa.array(cols["label"][lo:hi], type=pa.int32())]
    for name in INTEGERS:
        values, null = part(name)
        arrays.append(pa.array(values, type=pa.int64(), mask=null))
    arrays += [_hex_strings(*part(name)) for name in CATEGORICALS]
    return pa.Table.from_arrays(arrays, schema=SCHEMA)


def generate(dest: str, seed: int, parts: Iterable[str], rows: int,
             source_rows: Optional[int] = None) -> None:
    """Write the table under ``dest/parquet`` (``dest`` emptied first) as
    part files of ``ROWS_PER_PART`` rows, the last one the rest, in the order
    of the rows: 500,000 + 500,000 + 432,519 at the cell's cut.  ``parquet``
    is the one part this dataset has; ``source_rows`` is taken and ignored
    (no baseline)."""
    unknown = set(parts) - {"parquet"}
    if unknown:
        raise ValueError(f"unknown dataset parts {sorted(unknown)}")
    shutil.rmtree(dest, ignore_errors=True)
    os.makedirs(dest)
    if "parquet" not in set(parts):
        return
    out_dir = os.path.join(dest, "parquet")
    os.makedirs(out_dir)
    cols = synthesize(rows, seed)
    for i, lo in enumerate(range(0, rows, ROWS_PER_PART)):
        pq.write_table(arrow_table(cols, lo, min(lo + ROWS_PER_PART, rows)),
                       os.path.join(out_dir, f"part-{i:05d}.parquet"))
