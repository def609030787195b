"""Shared helpers mirroring the reference's shared/utils.py surface.

``attributeType_segregation`` / ``get_dtype`` (utils.py:48-76) delegate to
:class:`~anovos_tpu.shared.table.Table` when given a Table and handle pandas
frames directly; ``flatten_dataframe`` / ``transpose_dataframe`` (utils.py:6-45)
are host-side reshapes of stats frames.  Plus the list-handling and path
helpers and ``pairwise_reduce`` (utils.py:113-132).
"""

from __future__ import annotations

import functools
import os
from typing import Callable, Iterable, List, Sequence, Union


def parse_cols(
    list_of_cols: Union[str, Sequence[str]],
    all_cols: Sequence[str],
    drop_cols: Union[str, Sequence[str], None] = None,
) -> List[str]:
    """Resolve the universal ``list_of_cols`` convention: a list, a
    pipe-delimited string (``"c1|c2"``), or ``"all"``; then remove
    ``drop_cols`` (same formats).  Reference: stats_generator.py:69-79."""
    if list_of_cols is None:
        list_of_cols = "all"
    if isinstance(list_of_cols, str):
        if list_of_cols.strip().lower() == "all":
            cols = list(all_cols)
        else:
            cols = [c.strip() for c in list_of_cols.split("|") if c.strip()]
    else:
        cols = list(list_of_cols)
    if drop_cols is None:
        drop_cols = []
    if isinstance(drop_cols, str):
        drop_cols = [c.strip() for c in drop_cols.split("|") if c.strip()]
    dropset = set(drop_cols)
    out, seen = [], set()
    for c in cols:
        if c not in dropset and c not in seen:
            seen.add(c)
            out.append(c)
    return out


def pairwise_reduce(op: Callable, items: Iterable):
    """Tree-reduce (reference utils.py:113-132) — balanced combine order, which
    also matches the numerically-stable pairwise merge of running moments."""
    items = list(items)
    if not items:
        raise ValueError("pairwise_reduce of empty sequence")
    while len(items) > 1:
        nxt = []
        for i in range(0, len(items) - 1, 2):
            nxt.append(op(items[i], items[i + 1]))
        if len(items) % 2:
            nxt.append(items[-1])
        items = nxt
    return items[0]


def ends_with(string: str, end_str: str = "/") -> str:
    """Ensure trailing separator (reference utils.py:93)."""
    return string if string.endswith(end_str) else string + end_str


def write_csv_counted(frame, path: str, span) -> None:
    """``frame.to_csv(path, index=False)``, counted on the open stage span
    ``span`` (``files``, ``rows``, ``bytes`` on disk): how the analyzers
    write the CSVs the report reads."""
    frame.to_csv(path, index=False)
    span.add(files=1, rows=len(frame), bytes=os.path.getsize(path))


def output_to_local(path: str) -> str:
    """dbfs:/ → /dbfs/ rewrite (reference utils.py:135)."""
    if path.startswith("dbfs:"):
        return "/dbfs" + path[len("dbfs:"):]
    return path


def path_ak8s_modify(path: str) -> str:
    """Azure wasbs:// → https:// rewrite (reference utils.py:157)."""
    if path.startswith("wasbs://"):
        rest = path[len("wasbs://"):]
        container, _, tail = rest.partition("@")
        account, _, blob_path = tail.partition("/")
        return f"https://{account}/{container}/{blob_path}"
    return path


def attributeType_segregation(idf):
    """(num_cols, cat_cols, other_cols) for a Table or pandas frame
    (reference utils.py:48-65)."""
    if hasattr(idf, "attribute_type_segregation"):
        return idf.attribute_type_segregation()
    num, cat, other = [], [], []
    for c in idf.columns:
        kind = idf[c].dtype.kind
        (num if kind in "ifu" else cat if kind in "OUSb" else other).append(c)
    return num, cat, other


def get_dtype(idf, col: str) -> str:
    """Declared dtype name of one column (reference utils.py:68-76)."""
    if hasattr(idf, "dtypes") and callable(idf.dtypes):
        return dict(idf.dtypes())[col]
    return str(idf[col].dtype)


def flatten_dataframe(idf, fixed_cols):
    """Melt every column not in ``fixed_cols`` into key/value rows
    (reference utils.py:6-26).  Stats frames are pandas here, so this is a
    host-side reshape; device Tables export via ``to_pandas`` first."""
    import pandas as pd

    pdf = idf.to_pandas() if hasattr(idf, "to_pandas") else idf
    return pd.melt(
        pdf,
        id_vars=list(fixed_cols),
        value_vars=[c for c in pdf.columns if c not in set(fixed_cols)],
        var_name="key",
        value_name="value",
    )


def transpose_dataframe(idf, fixed_col):
    """Values of ``fixed_col`` become the header row (reference utils.py:29-45).

    All-NaN attributes stay as null rows (dropna=False) and rows keep the
    source column order rather than pivot_table's alphabetical sort."""
    pdf = idf.to_pandas() if hasattr(idf, "to_pandas") else idf
    flat = flatten_dataframe(pdf, fixed_cols=[fixed_col])
    key_order = [c for c in pdf.columns if c != fixed_col]
    return (
        flat.pivot_table(index="key", columns=fixed_col, values="value", aggfunc="first", dropna=False)
        .reindex(key_order)
        .reset_index()
        .rename_axis(None, axis=1)
    )
