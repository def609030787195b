"""TPC-H ``LINEITEM`` (Standard Specification v3.0.1, clause 1.4.1 for the
layout, clause 4.2.3 for how its rows are populated): a seeded, vectorised
generator that writes the table as parquet part files with the column types
of the specification's ``dss.ddl``.

The rules and distributions are the specification's; the random streams are
numpy's, seeded from ``--seed``, so a file differs from dbgen's value for
value.  The comment is a substring of a text pool made from clause
4.2.2.14's word classes, as dbgen takes one from its pool, with abridged
word lists and a pool of ``TEXT_POOL_BYTES`` (dbgen: 300 MB).  The scale
comes from ``rows`` alone: SF = rows / 6,001,215.  It imports nothing of the
program and runs no Python loop over rows.
"""

from __future__ import annotations

import os
import shutil
from typing import Iterable, Optional

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

ROWS_PER_PART = 500_000  # income.py's
MIN_PARTS = 4
SF1_ROWS = 6_001_215
TEXT_POOL_BYTES = 8 << 20
COMMENT_MIN, COMMENT_MAX = 10, 43

_EPOCH = np.datetime64("1970-01-01", "D")
STARTDATE = int((np.datetime64("1992-01-01", "D") - _EPOCH).astype(int))
ENDDATE = int((np.datetime64("1998-12-31", "D") - _EPOCH).astype(int))
CURRENTDATE = int((np.datetime64("1995-06-17", "D") - _EPOCH).astype(int))
LAST_ORDERDATE = ENDDATE - 151  # 1998-08-02

SHIPINSTRUCT = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
SHIPMODE = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]

DECIMAL = pa.decimal128(15, 2)
SCHEMA = pa.schema([
    ("l_orderkey", pa.int64()), ("l_partkey", pa.int64()), ("l_suppkey", pa.int64()),
    ("l_linenumber", pa.int32()),
    ("l_quantity", DECIMAL), ("l_extendedprice", DECIMAL), ("l_discount", DECIMAL), ("l_tax", DECIMAL),
    ("l_returnflag", pa.string()), ("l_linestatus", pa.string()),
    ("l_shipdate", pa.date32()), ("l_commitdate", pa.date32()), ("l_receiptdate", pa.date32()),
    ("l_shipinstruct", pa.string()), ("l_shipmode", pa.string()), ("l_comment", pa.string()),
])

# clause 4.2.2.14's word classes, abridged
_NOUNS = ["foxes", "ideas", "theodolites", "pinto beans", "instructions", "dependencies", "excuses",
          "platelets", "asymptotes", "courts", "dolphins", "multipliers", "sauternes", "warthogs",
          "frets", "dinos", "attainments", "somas", "Tiresias", "patterns", "forges", "braids",
          "hockey players", "frays", "warhorses", "dugouts", "notornis", "epitaphs", "pearls",
          "tithes", "waters", "orbits", "gifts", "sheaves", "depths", "sentiments", "decoys",
          "realms", "pains", "grouches", "escapades", "packages", "requests", "accounts", "deposits"]
_VERBS = ["sleep", "wake", "are", "cajole", "haggle", "nag", "use", "boost", "affix", "detect",
          "integrate", "maintain", "nod", "was", "lose", "sublate", "solve", "thrash", "promise",
          "engage", "hinder", "print", "x-ray", "breach", "eat", "grow", "impress", "mold",
          "poach", "serve", "run", "dazzle", "snooze", "doze", "unwind", "kindle", "play", "hang",
          "believe", "doubt"]
_ADJECTIVES = ["furious", "sly", "careful", "blithe", "quick", "fluffy", "slow", "quiet", "ruthless",
               "thin", "close", "dogged", "daring", "brave", "stealthy", "permanent", "enticing",
               "idle", "busy", "regular", "final", "ironic", "even", "bold", "silent", "special",
               "pending", "unusual", "express"]
_ADVERBS = ["sometimes", "always", "never", "furiously", "slyly", "carefully", "blithely", "quickly",
            "fluffily", "slowly", "quietly", "ruthlessly", "thinly", "closely", "doggedly", "daringly",
            "bravely", "stealthily", "permanently", "enticingly", "idly", "busily", "regularly",
            "finally", "ironically", "evenly", "boldly", "silently"]
_PREPOSITIONS = ["about", "above", "according to", "across", "after", "against", "along",
                 "alongside of", "among", "around", "at", "atop", "before", "behind", "beneath",
                 "beside", "besides", "between", "beyond", "by", "despite", "during", "except",
                 "for", "from", "in place of", "inside", "instead of", "into", "near", "of", "on",
                 "outside", "over", "past", "since", "through", "throughout", "to", "toward",
                 "under", "until", "up", "upon", "without", "with", "within"]
_AUXILIARIES = ["do", "may", "might", "shall", "will", "would", "can", "could", "should",
                "ought to", "must", "will have to", "shall have to", "could have to",
                "should have to", "must have to", "need to", "try to"]
_TERMINATORS = [".", ";", ":", "?", "!", "--"]
_CLASSES = {"N": _NOUNS, "V": _VERBS, "J": _ADJECTIVES, "D": _ADVERBS, "P": _PREPOSITIONS,
            "X": _AUXILIARIES, "T": _TERMINATORS}
# the grammar's sentences with their phrases expanded, one string of classes
# each (a terminator joins without a space)
_SENTENCES = ["NVT", "JNVT", "JNXVT", "NVPNT", "JNVPJNT", "DJNVT", "NXVDT", "NVPDJNT",
              "JNVDPNT", "NPNVT", "JJNVT", "NXVPJNT"]


def text_pool(rng: np.random.Generator, nbytes: int = TEXT_POOL_BYTES) -> np.ndarray:
    """``nbytes`` of pseudo-text as uint8, sentences of the grammar above
    separated by one space; the loops here run over sentence forms and their
    slots, the sentences themselves are built by numpy."""
    n = nbytes // 24 + 64  # no sentence is shorter than that on average
    form = rng.integers(0, len(_SENTENCES), n)
    sentences = np.empty(n, dtype=object)
    for k, classes in enumerate(_SENTENCES):
        m = int((form == k).sum())
        built = np.full(m, "", dtype=object)
        for j, cls in enumerate(classes):
            words = np.array(_CLASSES[cls], dtype=object)[rng.integers(0, len(_CLASSES[cls]), m)]
            built = built + words if cls == "T" or j == 0 else built + " " + words
        sentences[form == k] = built
    text = " ".join(sentences.tolist()).encode("ascii")
    while len(text) < nbytes:  # never with the lists above; a guard, not a path
        text += b" " + text
    return np.frombuffer(text[:nbytes], dtype=np.uint8)


def _decimal(cents: np.ndarray) -> pa.Array:
    """``cents / 100`` as ``decimal128(15,2)``, exact: the 128-bit
    little-endian integers of the array's buffer are the cents themselves."""
    words = np.zeros((len(cents), 2), dtype=np.int64)
    words[:, 0] = cents  # no negative value in this table: the high word stays 0
    return pa.Array.from_buffers(DECIMAL, len(cents), [None, pa.py_buffer(words)])


def _strings(cats, codes: np.ndarray) -> pa.Array:
    return pa.DictionaryArray.from_arrays(pa.array(codes.astype(np.int32)), pa.array(cats)).cast(pa.string())


def _comments(pool: np.ndarray, start: np.ndarray, length: np.ndarray) -> pa.Array:
    """``pool[start : start + length]`` per row as one Arrow string array,
    built from its two buffers."""
    offsets = np.zeros(len(start) + 1, dtype=np.int32)
    np.cumsum(length, out=offsets[1:])
    src = np.repeat(start - offsets[:-1], length) + np.arange(offsets[-1], dtype=np.int64)
    return pa.Array.from_buffers(pa.string(), len(start),
                                 [None, pa.py_buffer(offsets), pa.py_buffer(pool[src])])


def scale(rows: int) -> dict:
    """The ranges that follow from the table's size: SF = rows / 6,001,215,
    parts SF x 200,000 and suppliers SF x 10,000, each at least 1."""
    sf = rows / SF1_ROWS
    return {"scale_factor": sf, "parts": max(1, round(sf * 200_000)),
            "suppliers": max(1, round(sf * 10_000))}


def synthesize(rows: int, seed: int) -> dict:
    """Every column but the comment, as numpy arrays (money in cents, dates
    in days since 1970-01-01), a pure function of the arguments."""
    n = int(rows)
    rng = np.random.default_rng([int(seed), 0x7C9])
    sc = scale(n)
    n_part, n_supp = sc["parts"], sc["suppliers"]
    # orders 0, 1, ... with 1-7 lines each, cut where the table is full
    lines = rng.integers(1, 8, n)  # n orders always suffice
    ends = np.cumsum(lines)
    last = int(np.searchsorted(ends, n))
    lines = lines[:last + 1]
    lines[-1] -= ends[last] - n
    order = np.repeat(np.arange(last + 1, dtype=np.int64), lines)
    first_line = np.repeat(np.concatenate([[0], np.cumsum(lines)[:-1]]), lines)
    orderdate = rng.integers(STARTDATE, LAST_ORDERDATE + 1, last + 1)[order]

    partkey = rng.integers(1, n_part + 1, n)
    j = rng.integers(0, 4, n)
    suppkey = (partkey + j * (n_supp // 4 + (partkey - 1) // n_supp)) % n_supp + 1
    quantity = rng.integers(1, 51, n)
    retail = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)  # p_retailprice, cents
    shipdate = orderdate + rng.integers(1, 122, n)
    commitdate = orderdate + rng.integers(30, 91, n)
    receiptdate = shipdate + rng.integers(1, 31, n)
    returned = np.where(rng.integers(0, 2, n) == 0, 0, 2)  # R or A, codes into "ANR"
    return {
        "l_orderkey": (order // 8) * 32 + order % 8 + 1,
        "l_partkey": partkey,
        "l_suppkey": suppkey,
        "l_linenumber": (np.arange(n, dtype=np.int64) - first_line + 1).astype(np.int32),
        "l_quantity": quantity * 100,
        "l_extendedprice": quantity * retail,
        "l_discount": rng.integers(0, 11, n),
        "l_tax": rng.integers(0, 9, n),
        "l_returnflag": np.where(receiptdate <= CURRENTDATE, returned, 1),
        "l_linestatus": (shipdate > CURRENTDATE).astype(np.int64),  # codes into "FO"
        "l_shipdate": shipdate.astype(np.int32),
        "l_commitdate": commitdate.astype(np.int32),
        "l_receiptdate": receiptdate.astype(np.int32),
        "l_shipinstruct": rng.integers(0, len(SHIPINSTRUCT), n),
        "l_shipmode": rng.integers(0, len(SHIPMODE), n),
        "comment_start": rng.integers(0, TEXT_POOL_BYTES - COMMENT_MAX, n),
        "comment_length": rng.integers(COMMENT_MIN, COMMENT_MAX + 1, n),
    }


def arrow_table(cols: dict, pool: np.ndarray, lo: int, hi: int) -> pa.Table:
    """Rows ``lo:hi`` of ``synthesize``'s columns as an Arrow table of ``SCHEMA``."""
    c = {k: v[lo:hi] for k, v in cols.items()}
    arrays = {
        **{k: pa.array(c[k], type=pa.int64()) for k in ("l_orderkey", "l_partkey", "l_suppkey")},
        "l_linenumber": pa.array(c["l_linenumber"], type=pa.int32()),
        **{k: _decimal(c[k]) for k in ("l_quantity", "l_extendedprice", "l_discount", "l_tax")},
        "l_returnflag": _strings(["A", "N", "R"], c["l_returnflag"]),
        "l_linestatus": _strings(["F", "O"], c["l_linestatus"]),
        **{k: pa.array(c[k], type=pa.int32()).cast(pa.date32())
           for k in ("l_shipdate", "l_commitdate", "l_receiptdate")},
        "l_shipinstruct": _strings(SHIPINSTRUCT, c["l_shipinstruct"]),
        "l_shipmode": _strings(SHIPMODE, c["l_shipmode"]),
        "l_comment": _comments(pool, c["comment_start"], c["comment_length"]),
    }
    return pa.Table.from_arrays([arrays[f.name] for f in SCHEMA], schema=SCHEMA)


def generate(dest: str, seed: int, parts: Iterable[str], rows: int,
             source_rows: Optional[int] = None) -> None:
    """Write the table under ``dest/parquet`` (``dest`` emptied first) as
    ``max(4, ceil(rows / 500,000))`` part files.  ``parquet`` is the one part
    this dataset has; ``source_rows`` is taken and ignored (no baseline)."""
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
    pool = text_pool(np.random.default_rng([int(seed), 0x7CA]))
    n_parts = max(MIN_PARTS, -(-rows // ROWS_PER_PART))
    edges = np.linspace(0, rows, n_parts + 1).astype(int)
    for i in range(n_parts):
        pq.write_table(arrow_table(cols, pool, int(edges[i]), int(edges[i + 1])),
                       os.path.join(out_dir, f"part-{i:05d}.parquet"))
