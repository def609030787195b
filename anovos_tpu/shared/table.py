"""Sharded columnar Table — the Spark-DataFrame replacement.

Design (SURVEY.md §7 "Design center"):

- numeric columns: ``float32``/``int32`` device arrays with an explicit bool
  validity mask (NaN in the source becomes mask=False);
- categorical/string columns: host-side dictionary (``vocab``: np.ndarray of
  strings) + device ``int32`` code arrays — *strings never live on the TPU*;
  null is code ``-1`` with mask=False;
- timestamp columns: ``int32`` epoch-seconds + mask (host-side parse);
- every column has the same padded row count, a multiple of the mesh's data
  axis, so per-shard shapes are static; ``nrows`` is the true row count and
  padding rows carry mask=False;
- layout ``(rows_sharded_over_mesh,)`` per column via NamedSharding; stats
  kernels stack column groups into (rows, ncols) blocks so one batched XLA
  reduction covers all columns at once (replacing the reference's per-column
  Spark job loops, e.g. stats_generator.py:386-401).

The reference's dtype triage (shared/utils.py:48-73: string→cat,
double/int/bigint/float/long/decimal→num) maps onto ``Column.kind``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import sys
import threading
import time
from collections import OrderedDict
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.compute as pc
from jax.sharding import NamedSharding, PartitionSpec as P

from anovos_tpu.shared.host_pool import UnitsRun, get_host_pool, record_units
from anovos_tpu.shared.native import NativeEncodedStrings
from anovos_tpu.shared.runtime import get_runtime

# Spark-style dtype names kept for report parity (global_summary prints them).
NUM_DTYPES = {"int", "bigint", "float", "double", "long", "decimal", "smallint", "tinyint"}
CAT_DTYPES = {"string", "boolean"}


@dataclasses.dataclass
class Column:
    """One column: device data + validity mask (+ host vocab for cat).

    int64 values outside int32 range (id-like columns around 1e9+) keep an
    EXACT device representation as an (hi, lo) int32 pair alongside the f32
    approximation in ``data``: ``hi = v >> 32`` and ``lo`` is the low 32 bits
    bias-shifted by 2^31 so that signed (hi, lo) lexicographic order equals
    int64 numeric order.  Moment kernels keep using the f32 ``data``;
    exactness-critical ops (distinct count, mode, percentiles, joins, dedup)
    consult the pair — TPUs have no native int64, so this is the idiomatic
    split (round-1 verdict: the silent f32 cast corrupted uniqueCount/IDness
    on exactly the id columns that need them).
    """

    kind: str  # "num" | "cat" | "ts"
    data: jax.Array  # f32/i32 (num), i32 codes (cat), i32 epoch-sec (ts)
    mask: jax.Array  # bool, True = valid
    vocab: Optional[np.ndarray] = None  # host strings, cat only
    dtype_name: str = "double"  # spark-style name for reports
    wide_hi: Optional[jax.Array] = None  # int32, v >> 32 of the wide key
    wide_lo: Optional[jax.Array] = None  # int32, (v & 0xffffffff) - 2^31
    # "int": the wide key IS the int64 value.  "float": the key is the
    # order-preserving int64 transform of the float64 bit pattern (see
    # float_order_parts) — attached when a float64 column does not survive
    # the f32 round-trip, so distinct/mode/percentiles stay exact (the same
    # failure class as the round-1 id-column bug, but for dense floats like
    # lat/long whose spacing is below f32 resolution).
    wide_kind: str = "int"

    @property
    def padded_len(self) -> int:
        return self.data.shape[0]

    @property
    def is_wide(self) -> bool:
        return self.wide_hi is not None

    @property
    def is_wide_int(self) -> bool:
        return self.wide_hi is not None and self.wide_kind == "int"

    def astype_float(self, dtype=jnp.float32) -> jax.Array:
        return self.data.astype(dtype)

    def device_arrays(self) -> List[jax.Array]:
        """Every array the column holds on the device: what a fetch copies."""
        return [a for a in (self.data, self.mask, self.wide_hi, self.wide_lo) if a is not None]

    def exact_host(self, nrows: Optional[int] = None) -> np.ndarray:
        """Host values with exactness preserved (wide pair → int64/float64)."""
        n = self.data.shape[0] if nrows is None else nrows
        if self.wide_hi is not None:
            hi, lo = _fetch((self.wide_hi, self.wide_lo), n, "column.exact_host")
            return _wide_exact(hi, lo, self.wide_kind)
        return _fetch((self.data,), n, "column.exact_host")[0]

    def to_host(self, nrows: int) -> "HostColumn":
        """The fetch of :meth:`Table.to_pandas`: the column's first ``nrows``
        entries on the host, the wide pair under ``exact_host``'s bracket."""
        data, mask = _fetch((self.data, self.mask), nrows, "table.to_pandas")
        hi = lo = None
        if self.wide_hi is not None:
            hi, lo = _fetch((self.wide_hi, self.wide_lo), nrows, "column.exact_host")
        return HostColumn(self.kind, data, mask, self.vocab, self.dtype_name,
                          hi, lo, self.wide_kind)


@dataclasses.dataclass
class HostColumn:
    """A :class:`Column`'s arrays on the host, one entry a row and no
    padding: what :func:`_plain_to_host` makes of an input array before the
    device has it, and what :meth:`Column.to_host` fetches back.  Everything
    between the two is a copy, so a frame that is only being written goes
    from the one to :func:`_host_column_to_pandas` directly
    (:func:`host_table_frame`)."""

    kind: str
    data: np.ndarray
    mask: np.ndarray
    vocab: Optional[np.ndarray] = None
    dtype_name: str = "double"
    wide_hi: Optional[np.ndarray] = None
    wide_lo: Optional[np.ndarray] = None
    wide_kind: str = "int"


def _fetch(arrays, n: int, label: str) -> List[np.ndarray]:
    """d2h materialization boundary: the first ``n`` entries of each device
    array.  ``device_get`` blocks until the producing programs retire, so
    the wall includes the device tail a fetch waits on (devprof books it as
    transfer — "what the host was waiting ON", see obs.devprof).  Where the
    array's copy is already in flight (``Table.to_pandas`` starts them ahead)
    the call waits for that copy and the seconds booked are that wait."""
    from anovos_tpu.obs import devprof

    with devprof.transfer_bracket("d2h", sum(a.nbytes for a in arrays), label=label):
        return [np.asarray(jax.device_get(a))[:n] for a in arrays]


def _wide_exact(hi: np.ndarray, lo: np.ndarray, wide_kind: str) -> np.ndarray:
    """The exact int64 / float64 values of a host (hi, lo) pair."""
    key = (hi.astype(np.int64) << 32) + (lo.astype(np.int64) + (1 << 31))
    if wide_kind == "float":
        return float_from_order_key(key)
    return key


def wide_int_parts(v64: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Split int64 → (hi, lo) int32 pair in the sortable encoding."""
    v64 = np.ascontiguousarray(v64, np.int64)
    if sys.byteorder == "little":
        # the two halves as they lie in memory: no shift, mask and cast over
        # int64 temporaries; flipping the low half's top bit subtracts 2^31
        halves = v64.view(np.int32).reshape(v64.shape + (2,))
        return np.ascontiguousarray(halves[..., 1]), halves[..., 0] ^ np.int32(-(1 << 31))
    hi = (v64 >> 32).astype(np.int32)
    lo = ((v64 & 0xFFFFFFFF) - (1 << 31)).astype(np.int32)
    return hi, lo


def float_order_key(v64: np.ndarray) -> np.ndarray:
    """float64 → int64 key whose numeric order equals the float order.

    IEEE-754 trick on the bit pattern read as int64: a non-negative float is
    already in order and stays; a negative one flips every bit but its sign,
    which reverses the order of its magnitudes below zero.  (-0.0 and +0.0
    map to distinct keys — acceptable for distinct-count semantics.)"""
    i = np.ascontiguousarray(v64, np.float64).view(np.int64)
    return i ^ ((i >> 63) & np.int64(0x7FFFFFFFFFFFFFFF))


def float_from_order_key(key: np.ndarray) -> np.ndarray:
    """Inverse of float_order_key."""
    u = key.view(np.uint64) ^ np.uint64(0x8000000000000000)
    flip = np.where(u >> np.uint64(63), np.uint64(0x8000000000000000),
                    np.uint64(0xFFFFFFFFFFFFFFFF))
    return (u ^ flip).view(np.float64)


def float_order_parts(v64: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """float64 → (hi, lo) int32 pair whose signed lexicographic order equals
    the float numeric order (same pair encoding as wide_int_parts)."""
    return wide_int_parts(float_order_key(v64))


def _f32_holds(host: np.ndarray, v64: np.ndarray) -> bool:
    """Whether every float64 of ``v64`` survives the round trip through its
    float32 in ``host``; a piece at a time, so that a column that does not
    (the common case for a measure with decimals) says so on its first piece."""
    step = 1 << 20
    return all(np.array_equal(host[i:i + step].astype(np.float64), v64[i:i + step])
               for i in range(0, len(v64), step))


def _pad_to(arr: np.ndarray, n: int, fill) -> np.ndarray:
    if arr.shape[0] == n:
        return arr
    pad = np.full((n - arr.shape[0],) + arr.shape[1:], fill, dtype=arr.dtype)
    return np.concatenate([arr, pad], axis=0)


def _spark_dtype_name(np_dtype) -> str:
    kind = np.dtype(np_dtype).kind
    if kind in "iu":
        return "bigint" if np.dtype(np_dtype).itemsize > 4 else "int"
    if kind == "f":
        return "double" if np.dtype(np_dtype).itemsize > 4 else "float"
    if kind == "b":
        return "boolean"
    if kind == "M":
        return "timestamp"
    return "string"


class Table:
    """Immutable-ish columnar table; transformation methods return new Tables."""

    def __init__(
        self,
        columns: "OrderedDict[str, Column]",
        nrows: int,
        valid_rows: Optional[jax.Array] = None,
    ):
        self.columns: "OrderedDict[str, Column]" = columns
        self.nrows = int(nrows)
        # multi-host tables carry interleaved per-process padding, so row
        # validity is an explicit device mask instead of arange < nrows
        self.valid_rows = valid_rows

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------
    @staticmethod
    def from_numpy(
        data: Dict[str, np.ndarray],
        nrows: Optional[int] = None,
    ) -> "Table":
        """Build from host column arrays (object arrays → cat; datetime64 →
        ts; numeric → num).  NaN/None become nulls, and so do the masked
        entries of a ``np.ma.MaskedArray`` (integers with nulls: the values
        stay integers and exact, the mask is the column's).

        :func:`_upload_columns` builds the columns: the encode where an array
        still needs one, the conversion to the device dtypes, the padding and
        the hand-over to the device.  For ``_POOLED_COLUMNS_MIN_ROWS`` rows or
        more a column is one unit of the host pool and an array one
        ``device_put``, side by side; under it the table's arrays go over on
        this thread by typed block, a few transfers a dtype
        (:func:`_upload_in_blocks`); the table is the same either way."""
        if not data:
            return Table(OrderedDict(), 0)
        n = nrows if nrows is not None else len(next(iter(data.values())))
        return Table(_upload_columns(data, n), n)

    @staticmethod
    def from_pandas(df) -> "Table":
        """Build from a pandas frame.  A column of a string dtype (pandas 3's
        ``str``, ``string``; Arrow- or python-backed) or of dtype ``category``
        is dictionary-encoded from the Series by :func:`encode_strings` and
        never becomes an object array; an ``object`` column is encoded from
        its objects, by the same function; every other dtype goes as its
        numpy array (:func:`_frame_sources`).  The vocab of a cat column is
        in code-point order, which is ``np.unique``'s over Python ``str``;
        Arrow computes it over the UTF-8 bytes where the distinct values are
        an Arrow string array.  In a frame of ``_POOLED_COLUMNS_MIN_ROWS``
        rows or more a string column's encode and its upload are one unit of
        :func:`_upload_columns`, so the other columns are on the device while
        the longest encode still runs; a shorter frame's strings are encoded
        first and its arrays then go over together."""
        sources = _frame_sources(df)
        if not sources:
            return Table(OrderedDict(), 0)
        return Table(_upload_columns(sources, len(df)), len(df))

    # ------------------------------------------------------------------
    # basic introspection (the reference's utils.attributeType_segregation)
    # ------------------------------------------------------------------
    @property
    def ncols(self) -> int:
        return len(self.columns)

    @property
    def col_names(self) -> List[str]:
        return list(self.columns.keys())

    @property
    def padded_rows(self) -> int:
        if not self.columns:
            return 0
        return next(iter(self.columns.values())).padded_len

    def pad_target(self) -> int:
        """Padded length a NEW column of this table must have.  Always the
        table's existing padded length when it has columns — a fresh
        ``pad_rows(nrows)`` would diverge on multi-host tables (interleaved
        per-process padding) and whenever the bucketing policy changed
        between table creation and column addition."""
        if self.columns:
            return self.padded_rows
        return get_runtime().pad_rows(max(self.nrows, 1))

    def dtypes(self) -> List[Tuple[str, str]]:
        return [(k, c.dtype_name) for k, c in self.columns.items()]

    def attribute_type_segregation(self) -> Tuple[List[str], List[str], List[str]]:
        """num_cols, cat_cols, other_cols (reference shared/utils.py:48-73)."""
        num, cat, other = [], [], []
        for k, c in self.columns.items():
            if c.kind == "num":
                num.append(k)
            elif c.kind == "cat":
                cat.append(k)
            else:
                other.append(k)
        return num, cat, other

    # ------------------------------------------------------------------
    # column ops (reference data_ingest.py:201-367)
    # ------------------------------------------------------------------
    def select(self, names: Sequence[str]) -> "Table":
        missing = [n for n in names if n not in self.columns]
        if missing:
            raise KeyError(f"columns not in table: {missing}")
        # column ops keep the row layout → valid_rows must survive (multi-
        # host tables would otherwise silently revert to arange < nrows)
        return Table(
            OrderedDict((n, self.columns[n]) for n in names), self.nrows, self.valid_rows
        )

    def drop(self, names: Sequence[str]) -> "Table":
        names = set(names)
        return Table(
            OrderedDict((n, c) for n, c in self.columns.items() if n not in names),
            self.nrows,
            self.valid_rows,
        )

    def rename(self, mapping: Dict[str, str]) -> "Table":
        return Table(
            OrderedDict((mapping.get(n, n), c) for n, c in self.columns.items()),
            self.nrows,
            self.valid_rows,
        )

    def with_column(self, name: str, col: Column) -> "Table":
        cols = OrderedDict(self.columns)
        cols[name] = col
        return Table(cols, self.nrows, self.valid_rows)

    def with_columns(self, named) -> "Table":
        """:meth:`with_column` for every ``(name, column)`` of ``named`` in
        order, with one copy of the column dict in place of one a column."""
        cols = OrderedDict(self.columns)
        cols.update(named)
        return Table(cols, self.nrows, self.valid_rows)

    def __getitem__(self, name: str) -> Column:
        return self.columns[name]

    def __contains__(self, name: str) -> bool:
        return name in self.columns

    # ------------------------------------------------------------------
    # device block extraction for batched kernels
    # ------------------------------------------------------------------
    def numeric_block(
        self, names: Sequence[str], dtype=jnp.float32, shard_cols: bool = False,
        pad_cols: bool = True,
    ) -> Tuple[jax.Array, jax.Array]:
        """Stack numeric columns into (padded_rows, k_pad) X and bool mask M,
        row-sharded.  This is the input shape for every batched stats kernel.
        Cast+stack runs as ONE jitted program — per-column eager casts would
        cost one device dispatch each (expensive on remote backends).

        The column axis is padded up to ``Runtime.pad_cols``'s geometric
        size class (the row-axis shape-bucketing contract extended to
        columns): padding lanes carry mask=False (their values alias the
        first column's buffer and are DEAD — readable only under the
        mask), so masked kernels never count them, and per-block column
        subsets of nearby widths reuse one compiled program shape instead
        of each paying a fresh XLA compile (PERF.md cold-compile census).
        CONSUMER CONTRACT: every
        per-column output must be sliced back to the live ``k=len(names)``
        before host materialization, and any row-wise (axis=1) statistic
        must ignore the dead lanes (e.g. complete-case = ``M.sum(axis=1)
        == k``, never ``M.all(axis=1)``).  ``pad_cols=False`` opts out for
        consumers whose semantics depend on the exact feature count (model
        fits: AE latent dim, KNN distance scaling, ridge/ALS solves).

        ``shard_cols=True`` additionally shards the column axis over the
        mesh's model axis — the wide-table analogue of tensor parallelism
        (SURVEY §2.10): per-column stats kernels reduce over rows only, so a
        frame whose (rows × cols) block exceeds one chip's HBM splits across
        the whole mesh with no kernel changes (GSPMD inserts the layout).
        The layout is computed from the PADDED width ``k_pad`` (rounded up
        to a model-axis multiple so per-device lane counts stay static)."""
        rt = get_runtime()
        datas = tuple(self.columns[n].data for n in names)
        masks = tuple(self.columns[n].mask for n in names)
        k_pad = rt.pad_cols(len(names)) if pad_cols else len(names)
        if shard_cols:
            from anovos_tpu.shared.runtime import DATA_AXIS, MODEL_AXIS

            n_model = rt.mesh.shape.get(MODEL_AXIS, 1)
            if k_pad >= n_model > 1:
                k_pad = -(-k_pad // n_model) * n_model
        X, M = _stack_canonical(list(datas), list(masks), dtype, k_pad)
        if shard_cols:
            if rt.mesh is not None and k_pad >= rt.mesh.shape.get(MODEL_AXIS, 1) > 1:
                sh = NamedSharding(rt.mesh, P(DATA_AXIS, MODEL_AXIS))
                X = jax.device_put(X, sh)
                M = jax.device_put(M, sh)
        return X, M

    # ------------------------------------------------------------------
    # placement (multi-device DAG execution — shared/runtime.py PR 8)
    # ------------------------------------------------------------------
    def with_runtime(self, rt) -> "Table":
        """Re-place every column onto ``rt``'s row sharding (same padded
        shapes, different device layout).  Used by the DAG executor to
        hand a ``device``/``submesh``-placed node a copy of the mesh-
        resident df that lives entirely on the node's leased devices, so
        every program the node dispatches is local to its lane.  A table
        already on that layout round-trips through ``device_put`` as a
        cheap no-op; the cross-layout copy is booked as a ``d2d``
        transfer and lies under a ``place/d2d`` span (``bytes`` copied,
        ``chips`` copied to), a row of the pass's phase tree where a
        scheduler node makes the copy."""
        from anovos_tpu.obs import devprof
        from anovos_tpu.obs.tracing import get_tracer

        def put(a):
            spec = P(*((rt.data_axis,) + (None,) * (a.ndim - 1)))
            return jax.device_put(a, NamedSharding(rt.mesh, spec))

        nbytes = sum(
            c.data.nbytes + c.mask.nbytes
            + (c.wide_hi.nbytes + c.wide_lo.nbytes if c.wide_hi is not None else 0)
            for c in self.columns.values()
        ) + (self.valid_rows.nbytes if self.valid_rows is not None else 0)
        with get_tracer().phase("place/d2d", cat="place", bytes=nbytes, chips=rt.mesh.size), \
                devprof.transfer_bracket("d2d", nbytes, label="table.with_runtime"):
            cols: "OrderedDict[str, Column]" = OrderedDict()
            for name, c in self.columns.items():
                cols[name] = Column(
                    c.kind, put(c.data), put(c.mask), vocab=c.vocab,
                    dtype_name=c.dtype_name,
                    wide_hi=put(c.wide_hi) if c.wide_hi is not None else None,
                    wide_lo=put(c.wide_lo) if c.wide_lo is not None else None,
                    wide_kind=c.wide_kind,
                )
            valid = put(self.valid_rows) if self.valid_rows is not None else None
        return Table(cols, self.nrows, valid)

    def to_active_placement(self) -> "Table":
        """Under a scheduler placement scope, the table re-placed onto
        the scope's runtime; outside any scope (or when the table already
        lives on exactly the scope's devices), the table itself."""
        from anovos_tpu.shared.runtime import active_placement_runtime

        rt = active_placement_runtime()
        if rt is None or not self.columns:
            return self
        target = set(rt.mesh.devices.flat)
        try:
            current = set(next(iter(self.columns.values())).data.sharding.device_set)
        except Exception:
            current = None
        if current == target:
            return self
        return self.with_runtime(rt)

    def row_mask(self) -> jax.Array:
        """Validity of the *row* (excludes padding rows).  Multi-host tables
        carry interleaved per-process padding → explicit mask."""
        if self.valid_rows is not None:
            return self.valid_rows
        return jnp.arange(self.padded_rows) < self.nrows

    # ------------------------------------------------------------------
    # row movement (gather/filter) — the shuffle replacement
    # ------------------------------------------------------------------
    def gather_rows(self, idx: np.ndarray, valid: Optional[np.ndarray] = None) -> "Table":
        """New Table whose row r is this table's row ``idx[r]``.

        ``idx`` is a host int array (−1 or ``valid[r]==False`` → null row —
        used for outer joins).  All columns move in ONE jitted program and the
        result is blocked on before returning: a cross-shard gather lowers to
        an all-gather, and two *independent* collective programs in flight at
        once can interleave their rendezvous on hosts with fewer worker
        threads than devices (observed deadlock on the 8-virtual-device CPU
        mesh) — single program + block makes the dispatch race-free.
        """
        rt = get_runtime()
        idx = np.asarray(idx)
        n = len(idx)
        npad = rt.pad_rows(max(n, 1))
        if valid is None:
            valid = idx >= 0
        live = idx[np.asarray(valid, bool)]
        if live.size and (live.min() < 0 or live.max() >= self.nrows):
            raise IndexError(
                f"gather_rows: index out of range [0, {self.nrows}) "
                f"(min={live.min()}, max={live.max()})"
            )
        idx_p = _pad_to(np.where(valid, idx, 0).astype(np.int32), npad, 0)
        val_p = _pad_to(np.asarray(valid, bool), npad, False)
        idx_d = rt.shard_rows(idx_p)
        val_d = rt.shard_rows(val_p)
        names = self.col_names
        datas: List[jax.Array] = []
        for c in names:
            col = self.columns[c]
            datas.append(col.data)
            if col.wide_hi is not None:
                datas.append(col.wide_hi)
                datas.append(col.wide_lo)
        masks = tuple(self.columns[c].mask for c in names)
        gd, gm = _gather_program(tuple(datas), masks, idx_d, val_d)
        jax.block_until_ready((gd, gm))
        cols: "OrderedDict[str, Column]" = OrderedDict()
        j = 0
        for i, name in enumerate(names):
            c = self.columns[name]
            whi = wlo = None
            data = gd[j]
            j += 1
            if c.wide_hi is not None:
                whi, wlo = gd[j], gd[j + 1]
                j += 2
            cols[name] = Column(
                c.kind, data, gm[i], vocab=c.vocab, dtype_name=c.dtype_name,
                wide_hi=whi, wide_lo=wlo, wide_kind=c.wide_kind,
            )
        return Table(cols, n)

    def filter_rows(self, keep: np.ndarray) -> "Table":
        """Compact to rows where host bool ``keep`` is True (stage-boundary
        host compaction — the 'mask-don't-shrink' escape hatch).  ``keep``
        must cover all rows (length nrows or padded_rows)."""
        keep = np.asarray(keep)
        if len(keep) not in (self.nrows, self.padded_rows):
            raise ValueError(
                f"filter_rows: keep has length {len(keep)}, expected "
                f"{self.nrows} (nrows) or {self.padded_rows} (padded_rows)"
            )
        idx = np.nonzero(keep[: self.nrows])[0]
        return self.gather_rows(idx)

    # ------------------------------------------------------------------
    # host materialization
    # ------------------------------------------------------------------
    def to_pandas(self):
        """The table's first ``nrows`` rows as a pandas frame, every column
        fetched whole (padding included) and converted on the host.

        The device→host copies are started ahead of the column being
        converted (``jax.Array.copy_to_host_async``): those of the first
        ``get_host_pool().threads`` columns before any is waited for, then,
        as a column's unit is taken up, those of the columns up to that many
        past it.  So the link has copies queued while the host converts, and
        a table of any length has no more raw buffers in flight than the
        columns being converted and that window.  A column's unit waits for
        its arrays (:meth:`Column.to_host`: the same ``d2h`` records, whose
        seconds are now the wait for a copy in flight) and converts them
        (:func:`_host_column_to_pandas`); the units of a table of
        ``_POOLED_COLUMNS_MIN_ROWS`` rows or more run side by side on the
        host pool, a shorter table's in a loop on this thread.  The frame is
        the same either way.

        Inside ``write_dataset``'s ``write/d2h`` row of a pass's tree each
        unit is a row ``write/column`` under it: ``arrays``, ``bytes``, and
        ``wait_s``, the seconds the unit was blocked on its copies (the rest
        of its wall is conversion).  Anywhere else a unit opens nothing.

        A unit that raises stops the units not yet started; the copies
        already in flight are waited for before the error leaves."""
        from anovos_tpu.obs.tracing import get_tracer

        n = self.nrows
        cols = list(self.columns.values())
        pool = get_host_pool()
        ahead = pool.threads
        tracer = get_tracer()
        row = tracer.tree_row()
        in_write = row is not None and row.name == FETCH_PHASE
        lock = threading.Lock()
        started = 0

        def start_copies(upto: int) -> None:
            nonlocal started
            with lock:
                while started < min(upto, len(cols)):
                    for a in cols[started].device_arrays():
                        a.copy_to_host_async()
                    started += 1

        def unit(i: int):
            start_copies(i + 1 + ahead)
            arrays = cols[i].device_arrays()
            column_row = (tracer.phase("write/column", arrays=len(arrays), bytes=sum(a.nbytes for a in arrays))
                          if in_write else contextlib.nullcontext())
            with column_row as span:
                t0 = time.perf_counter()
                hc = cols[i].to_host(n)
                if span is not None:
                    span.add(wait_s=time.perf_counter() - t0)
                return _host_column_to_pandas(hc)

        start_copies(ahead)
        try:
            out = pool.run(unit, range(len(cols)), side_by_side=n >= _POOLED_COLUMNS_MIN_ROWS).results
        except BaseException:
            for c in cols[:started]:  # a copy never outlives the call that started it
                for a in c.device_arrays():
                    np.asarray(a)
            raise
        return pd.DataFrame(dict(zip(self.columns, out)), columns=list(self.columns))

    def head(self, k: int = 5):
        return self.to_pandas().head(k)

    def __repr__(self) -> str:
        cols = ", ".join(f"{n}:{c.kind}" for n, c in self.columns.items())
        return f"Table[{self.nrows} rows]({cols})"


@functools.partial(jax.jit, static_argnames=("dtype",))
def _stack_cast(datas, masks, dtype):
    X = jnp.stack([d.astype(dtype) for d in datas], axis=1)
    M = jnp.stack(masks, axis=1)
    return X, M


def _extend_dead_lanes(datas, masks, k_pad):
    """Extend column tuples to ``k_pad`` with zero-data / False-mask lanes.

    The extension happens BEFORE the stack program, so the stack is keyed
    on the bucketed arity — two blocks of nearby widths (and the same
    dtype pattern) replay ONE compiled stack instead of one per width.
    ``jnp.zeros_like`` costs a tiny shared fill program per (shape, dtype),
    amortized process-wide."""
    k = len(datas)
    if k_pad <= k:
        return tuple(datas), tuple(masks)
    # dead DATA lanes alias the first column's buffer — zero device work,
    # no fill program (the drift _padded_col_tuples pattern); only the
    # all-False mask needs a real (tiny, shared) fill.  Consumers may read
    # dead-lane VALUES only under the mask, which is False there.
    dead_d = datas[0]
    dead_m = jnp.zeros_like(masks[0])
    return (tuple(datas) + (dead_d,) * (k_pad - k),
            tuple(masks) + (dead_m,) * (k_pad - k))


def _stack_canonical(datas, masks, dtype, k_pad):
    """Bucketed stack: dead-lane tuple extension before the stack program,
    so the stack is keyed on the bucketed arity.  (A dtype-canonical lane
    sort + inverse-perm gather was measured here and reverted: real blocks
    differ in their dtype COUNTS, not their order, so the permutation only
    added gather programs without collapsing stack variants.)"""
    datas, masks = _extend_dead_lanes(list(datas), list(masks), k_pad)
    return _stack_cast(tuple(datas), tuple(masks), dtype)


def stack_padded(datas, masks, dtype=jnp.float32, pad_cols: bool = True):
    """Column-bucketed stack for ad-hoc (rows, k) blocks built from raw
    column arrays (cat codes, wide-int hi/lo pairs, mixed-kind stacks) —
    the same contract as :meth:`Table.numeric_block` for callers that are
    not stacking ``Column.data`` of a single table: padding lanes carry
    mask=False (dead values) and per-column outputs must be sliced back to
    the live ``len(datas)``."""
    k_pad = get_runtime().pad_cols(len(datas)) if pad_cols else len(datas)
    return _stack_canonical(list(datas), list(masks), dtype, k_pad)


@jax.jit
def _stack_bool(masks):
    return jnp.stack(masks, axis=1)


def stack_masks_padded(masks, pad_cols: bool = True) -> jax.Array:
    """Column-bucketed (rows, k_pad) bool stack of validity masks (dead
    lanes False).  Row-wise consumers must count against the LIVE k — e.g.
    nulls-per-row is ``k − M.sum(axis=1)`` and complete-case is
    ``M.sum(axis=1) == k`` — never ``(~M).sum(axis=1)`` / ``M.all(axis=1)``,
    which would count the dead lanes."""
    masks = list(masks)
    k_pad = get_runtime().pad_cols(len(masks)) if pad_cols else len(masks)
    if k_pad > len(masks):
        dead = jnp.zeros_like(masks[0])
        masks = masks + [dead] * (k_pad - len(masks))
    return _stack_bool(tuple(masks))


def counted_fetch(tree, idf: "Table", span=None):
    """``jax.device_get``, counted on the open stage row ``span``: one
    ``fetches`` a call and, as ``host_rows``, the rows of every fetched array
    that is as long as the table.  A block that brings aggregates to the host
    and never a column reads 0 (the time-series inspection of a table padded
    to exactly ``CALENDAR_DAY_LANES`` rows reads its day lanes too: high,
    never low)."""
    out = jax.device_get(tree)
    if span is not None:
        span.add(fetches=1, host_rows=sum(
            a.shape[0] for a in jax.tree_util.tree_leaves(out)
            if np.ndim(a) and a.shape[0] == idf.padded_rows))
    return out


def pad_lane_params(arr: np.ndarray, k_pad: int, fill=0.0) -> np.ndarray:
    """Pad a host per-column parameter array (k, ...) to (k_pad, ...) along
    axis 0 so elementwise kernels broadcast against a column-bucketed block
    without a per-width recompile.  ``fill`` picks a value that keeps the
    dead lanes numerically inert (1.0 for divisors, 0.0 otherwise)."""
    arr = np.asarray(arr)
    if arr.shape[0] >= k_pad:
        return arr
    widths = ((0, k_pad - arr.shape[0]),) + ((0, 0),) * (arr.ndim - 1)
    return np.pad(arr, widths, constant_values=fill)


@jax.jit
def _gather_program(datas, masks, idx, valid):
    gd = tuple(jnp.take(a, idx, axis=0) for a in datas)
    gm = tuple(jnp.take(m, idx, axis=0) & valid for m in masks)
    return gd, gm


def _sorted_vocab_codes(first: np.ndarray, uniques) -> NativeEncodedStrings:
    """Codes into ``uniques`` (−1 null; every entry in use), which may be any
    objects (a categorical's categories) → int32 codes into the sorted vocab
    of their ``str()``.  The order is ``np.unique``'s over an object array of
    Python ``str``, which is code-point order, and two entries that ``str()``
    to one string become one code: the Python work is over the distinct
    values, the rows pay one int32 gather."""
    strs = np.empty(len(uniques), dtype=object)
    strs[:] = [str(u) for u in uniques]
    vocab, inverse = np.unique(strs, return_inverse=True)
    remap = np.append(inverse, -1).astype(np.int32)  # first == −1 reads the −1
    return NativeEncodedStrings(remap[first], vocab)


def _arrow_ordered(first: np.ndarray, dictionary: pa.Array) -> Tuple[np.ndarray, pa.Array]:
    """Codes into ``dictionary`` (−1 null), the distinct values of a
    ``dictionary_encode`` (an Arrow string array: non-null, valid UTF-8 and no
    two equal, so there is nothing to merge) → int32 codes into the same
    values in order, and those, still an Arrow array.  Arrow orders them by
    their UTF-8 bytes, whose order is code-point order (``np.unique``'s over
    Python ``str``)."""
    order = pc.array_sort_indices(dictionary)
    rank = np.empty(len(order) + 1, dtype=np.int32)
    rank[order.to_numpy()] = np.arange(len(order), dtype=np.int32)
    rank[-1] = -1  # first == −1 reads the −1
    return rank[first], dictionary.take(order)


def _arrow_sorted_vocab_codes(first: np.ndarray, dictionary: pa.Array) -> NativeEncodedStrings:
    """:func:`_sorted_vocab_codes` for distinct values that are an Arrow
    string array: Python neither compares them nor makes a ``str`` of one
    more than once, the vocab is built from the dictionary already in order."""
    codes, vocab = _arrow_ordered(first, dictionary)
    return NativeEncodedStrings(codes, vocab.to_numpy(zero_copy_only=False))


# A column of a million rows or more whose values are mostly distinct (free
# text, ids) costs seconds in one hash table and one sort; its rows are
# partitioned by their first bytes and the partitions encoded side by side.
_BUCKETED_ENCODE_MIN_ROWS = 1 << 20
# The column-sized units of a frame or table of this many rows or more run
# side by side on the host pool: the columns of a table being built, each
# encoded where it is a string column, converted, padded and put on the
# device (``_upload_columns``: ``Table.from_numpy`` / ``from_pandas``), the
# string columns of a frame that stays on the host (``_frame_arrays``), the
# columns of a table being fetched and converted (``Table.to_pandas``); a
# shorter one's on the calling thread (the stats tables, a node's small
# frames, a 32,561-row dataset): thirteen columns of 65,536 rows are 3 ms
# each and threads that wake for them gave nothing back in the median, at
# 131,072 rows they halved the wall (PERF.md section 3).  Under it an array
# is at most 0.5 MB and a call that hands one to the device costs more than
# its copy, so a table being built sends its arrays by typed block
# (``_upload_in_blocks``).
_POOLED_COLUMNS_MIN_ROWS = 1 << 17
# The row of a pass's tree under which ``Table.to_pandas`` files a row a
# column: ``write_dataset`` opens it around the fetch of the table it writes.
FETCH_PHASE = "write/d2h"
_BUCKETED_ENCODE_SAMPLE = 1 << 16
_BUCKETED_ENCODE_BUCKETS_A_WORKER = 4
_PREFIX_BYTES = 8


def _mostly_distinct(strings: pa.Array) -> bool:
    """Whether a column looks like free text or ids: more than a quarter of
    its first 65,536 rows are distinct.  A wrong guess costs time, not
    correctness: both encodings give the same codes and the same vocab."""
    head = strings.slice(0, _BUCKETED_ENCODE_SAMPLE)
    return 4 * pc.count_distinct(head).as_py() > len(head)


def _prefix_keys(strings: pa.Array) -> np.ndarray:
    """Per row of a ``large_string`` array the first eight bytes of its UTF-8
    as one big-endian uint64, a shorter (or null) value padded with zero
    bytes: ``s <= t`` bytewise implies ``key(s) <= key(t)``."""
    n = len(strings)
    _, offsets, data = strings.buffers()
    start = np.frombuffer(offsets, dtype=np.int64)[strings.offset:strings.offset + n + 1]
    length = np.diff(start)
    if strings.null_count:
        length = np.where(strings.is_valid().to_numpy(zero_copy_only=False), length, 0)
    data = np.frombuffer(data, dtype=np.uint8) if data is not None else np.zeros(0, np.uint8)
    data = np.concatenate([data, np.zeros(_PREFIX_BYTES, np.uint8)])  # a window at the last byte stays inside
    first = np.lib.stride_tricks.sliding_window_view(data, _PREFIX_BYTES)[start[:-1]]  # (n, 8), one gather
    short = np.flatnonzero(length < _PREFIX_BYTES)  # their windows reach into the next value
    first[short] = np.where(np.arange(_PREFIX_BYTES) < length[short, None], first[short], 0)
    return first.view(">u8").ravel().astype(np.uint64)


def _bucketed_encode(strings: pa.Array) -> Tuple[NativeEncodedStrings, Dict[str, float]]:
    """:func:`_hash_encode`'s Arrow path for a long column of mostly distinct
    values, in parallel and in pieces that fit a cache: the rows are cut into
    buckets by :func:`_prefix_keys` at splitters taken from a sample of the
    keys, so that no value lies in two buckets and every value of a bucket
    sorts before every value of the next; each bucket is hashed and its
    distinct values ordered by Arrow as a unit of the host pool (Arrow's
    kernels release the GIL; the thread that partitioned the rows takes
    buckets too, so a column that is itself a unit of the pool waits for no
    queue); the vocab is the buckets' vocabs one after the other, a
    row's code its bucket's code plus the distinct values of the buckets
    before.  The same codes and vocab as the one hash table and one sort
    give.  ``hash_s``: the seconds to the end of the last bucket; ``sort_s``:
    those joining them and building the vocab of Python ``str``; ``buckets``:
    how many."""
    t0 = time.perf_counter()
    n = len(strings)
    keys = _prefix_keys(strings)
    pool = get_host_pool()
    workers = pool.threads
    sample = np.sort(keys[:: max(1, n // _BUCKETED_ENCODE_SAMPLE)])
    splitters = np.unique(sample[np.linspace(0, len(sample), workers * _BUCKETED_ENCODE_BUCKETS_A_WORKER + 1)
                                 .astype(np.int64)[1:-1]])
    bucket = np.searchsorted(splitters, keys, side="right").astype(np.uint16)  # equal keys, one bucket
    order = np.argsort(bucket, kind="stable")  # rows by bucket, in their own order within one
    edges = np.concatenate([[0], np.cumsum(np.bincount(bucket, minlength=len(splitters) + 1))])

    def encode(edge):
        rows = order[edge[0]:edge[1]]
        enc = strings.take(pa.array(rows)).dictionary_encode()
        first = enc.indices.fill_null(-1).to_numpy(zero_copy_only=False)
        return rows, _arrow_ordered(first, enc.dictionary)

    parts = pool.run(encode, list(zip(edges[:-1], edges[1:]))).results
    t1 = time.perf_counter()
    codes = np.empty(n, dtype=np.int32)
    before = 0
    for rows, (local, vocab) in parts:
        codes[rows] = np.where(local >= 0, local + np.int32(before), np.int32(-1))
        before += len(vocab)
    vocab = pa.concat_arrays([v for _, (_, v) in parts]).to_numpy(zero_copy_only=False)
    return NativeEncodedStrings(codes, vocab), {
        "hashed": 1, "native_sort": 1, "hash_s": t1 - t0, "sort_s": time.perf_counter() - t1,
        "buckets": len(parts)}


def _hash_encode(values) -> Optional[Tuple[NativeEncodedStrings, Dict[str, float]]]:
    """The encoding by one hash pass in C over the rows, with no Python
    object per row, and what the span says of it: ``hashed`` 1,
    ``native_sort`` (1 where Arrow ordered the vocab, 0 where Python did)
    and, where Arrow did both, ``hash_s`` (seconds in ``dictionary_encode``)
    and ``sort_s`` (seconds ordering the distinct values, building the vocab
    and gathering the rows' codes).  None for an input whose hash is another function than its
    ``str()``, or that Arrow cannot hold.  A categorical was hashed when it
    was made: its codes are taken, its unused categories left out, and its
    categories, which may be any objects, are ordered in Python."""
    if isinstance(values.dtype, pd.CategoricalDtype):
        cats = values.cat.categories.to_numpy(dtype=object)
        first = values.cat.codes.to_numpy()
        used = np.flatnonzero(np.bincount(first[first >= 0], minlength=len(cats)))
        lut = np.full(len(cats) + 1, -1)
        lut[used] = np.arange(len(used))
        return _sorted_vocab_codes(lut[first], cats[used]), {"hashed": 1, "native_sort": 0}
    if not isinstance(values.dtype, pd.StringDtype) and (
            pd.api.types.infer_dtype(values, skipna=True) != "string"):
        return None
    try:
        strings = pa.array(values, type=pa.large_string(), from_pandas=True)
    except UnicodeEncodeError:  # a lone surrogate: a str, and not UTF-8
        return None
    if isinstance(strings, pa.ChunkedArray):  # a pd.concat of part files
        strings = strings.combine_chunks()
    if len(strings) >= _BUCKETED_ENCODE_MIN_ROWS and _mostly_distinct(strings):
        return _bucketed_encode(strings)
    t0 = time.perf_counter()
    enc = strings.dictionary_encode()
    t1 = time.perf_counter()
    first = enc.indices.fill_null(-1).to_numpy(zero_copy_only=False)
    encoded = _arrow_sorted_vocab_codes(first, enc.dictionary)
    return encoded, {"hashed": 1, "native_sort": 1, "hash_s": t1 - t0,
                     "sort_s": time.perf_counter() - t1}


def _loop_encode(vals: np.ndarray) -> NativeEncodedStrings:
    """The plain encoding, a ``str()`` per row and a sort of all of them:
    what the hash path must equal, and the path of the inputs it leaves
    (``1``, ``1.0`` and ``True`` are one key to a hash table and three
    strings; ``b"x"`` is ``"b'x'"``)."""
    isnull = pd.isna(vals)
    nn_strs = np.array([str(v) for v in vals[~isnull]], dtype=object)
    vocab, codes = np.unique(nn_strs, return_inverse=True)
    code_arr = np.full(len(vals), -1, dtype=np.int32)
    code_arr[~isnull] = codes.astype(np.int32)
    return NativeEncodedStrings(code_arr, vocab.astype(object))


def encode_strings(values) -> NativeEncodedStrings:
    """Dictionary-encode one string column (a Series or an array) on the
    host: int32 codes (−1 null) into a vocab of Python ``str`` in code-point
    order, which is how ``np.unique`` sorts them.  Nulls are what ``pd.isna``
    says (None, NaN, ``pd.NA``, NaT); ``""`` is a value.  Which input takes
    which path:

    - a Series of a string dtype, and an object or ``U`` array or Series
      whose non-null values are all ``str`` (``infer_dtype`` says "string"):
      hashed by Arrow, and the distinct values, an Arrow string array,
      ordered by Arrow over their UTF-8 bytes;
    - a Series of dtype ``category``: its own codes taken, its categories
      ordered by ``np.unique`` over their ``str()``;
    - anything else (mixed objects, bytes, an all-null or empty object
      array, a lone surrogate): the per-value loop.

    One ``ingest/encode`` span per call (a phase of the pass where ingest
    calls this) with the counts ``rows``, ``distinct``, ``hashed`` and
    ``native_sort`` (1 where Arrow ordered the vocab, 0 where Python did),
    and where Arrow did ``hash_s`` and ``sort_s``: the seconds in its
    ``dictionary_encode`` over the rows, and those ordering the distinct
    values and building the vocab."""
    from anovos_tpu.obs.tracing import get_tracer

    with get_tracer().phase("ingest/encode", cat="io", rows=len(values)) as sp:
        enc, counts = _encode_with_counts(values)
        sp.add(distinct=len(enc.vocab), **counts)
    return enc


def _encode_with_counts(values) -> Tuple[NativeEncodedStrings, Dict[str, float]]:
    """:func:`encode_strings` without its span: the encoding, and the counts
    the span carries."""
    return _hash_encode(values) or (
        _loop_encode(np.asarray(values, dtype=object)), {"hashed": 0, "native_sort": 0})


class _UnencodedStrings(NamedTuple):
    """A frame's string or category column on its way into a table: the
    Series, which :func:`encode_strings` takes as it is."""
    series: "pd.Series"


def _record_unit_times(what: str, stamps: List[Tuple[int, float, float]], side_by_side: bool, **counts) -> None:
    """What :func:`record_units` says of one kind of work inside a call's
    units, where a unit is two kinds (a string column's encode, then its
    upload), from the ``(thread, start, end)`` of each piece of it:
    ``<what>_workers`` (threads that ran the work; 0 for the calling thread
    alone) and ``<what>_wall_s`` (first start to last end), if any ran, and
    the caller's ``counts`` of that work."""
    if stamps:
        threads, starts, ends = zip(*stamps)
        record_units(what, UnitsRun([], len(set(threads)) if side_by_side else 0, max(ends) - min(starts)),
                     **counts)


def _upload_columns(sources: Dict[str, object], n: int) -> "OrderedDict[str, Column]":
    """The device columns of a table of ``n`` rows from what
    :meth:`Table.from_numpy` takes or :func:`_frame_sources` gives.  A string
    column is dictionary-encoded first (:func:`encode_strings`, its
    ``ingest/encode`` span); every column is then converted to the device
    dtypes (:func:`_plain_to_host`), each of its arrays padded to the row
    bucket with the fill it documents, and handed to the device on the row
    sharding.  How the arrays are handed over is read from ``n``:

    - ``_POOLED_COLUMNS_MIN_ROWS`` rows or more: a column is one unit of the
      host pool, its encode and then :func:`_upload_column` (one
      ``ingest/h2d`` span and one ``device_put`` an array), side by side.
      The columns that need an encode are claimed first: they are the long
      units (one of mostly distinct values hands its own buckets to the same
      pool), and every other column is on the device before the longest
      encode has ended.
    - under it an array is so small that the call handing it over costs more
      than its copy (0.29 ms for 0.13 MB on the chip, PERF.md section 6,
      PR 47), and a table may have thousands: on this thread, the encodes,
      then :func:`_upload_in_blocks` (one ``ingest/h2d`` span for the table,
      a few transfers a dtype).

    Either way the columns come back in ``sources``' order, each what one
    ``device_put`` an array makes of it.  A column that raises stops those
    not yet started; the error of the first of them in the units' order is
    raised and no table is made.  On the row of the pass's tree the call runs
    under (``io:read_dataset`` inside a read): ``h2d_workers`` /
    ``h2d_wall_s`` of the uploads and, where a frame's string columns were
    encoded here, ``encode_workers`` / ``encode_wall_s``, as
    :func:`record_units` files them (0 workers: this thread alone), and
    beside them ``h2d_arrays`` (arrays that reached the device) and
    ``h2d_transfers`` (``device_put`` calls that carried them: as many as
    arrays where a column is a unit)."""
    rt = get_runtime()
    npad = rt.pad_rows(max(n, 1))
    sources = {name: src if isinstance(src, (NativeEncodedStrings, _UnencodedStrings))
               else np.asanyarray(src)  # a masked array keeps its mask
               for name, src in sources.items()}
    encodes, uploads = [], []  # (thread, start, end) of every encode and upload; an append is atomic

    def needs_encode(name) -> bool:
        src = sources[name]
        return isinstance(src, _UnencodedStrings) or (
            not isinstance(src, NativeEncodedStrings) and src.dtype.kind in "OUS")

    def encoded(name) -> Union[np.ndarray, NativeEncodedStrings]:
        src = sources[name]
        if isinstance(src, _UnencodedStrings):
            t0 = time.perf_counter()
            src = encode_strings(src.series)
            encodes.append((threading.get_ident(), t0, time.perf_counter()))
        elif needs_encode(name):
            src = encode_strings(src[:n])
        return src

    def unit(name) -> Column:
        src = encoded(name)
        t0 = time.perf_counter()
        col = _upload_column(src, n, npad, rt)
        uploads.append((threading.get_ident(), t0, time.perf_counter()))
        return col

    order = sorted(sources, key=lambda name: not needs_encode(name))  # stable: a kind keeps the table's order
    if n >= _POOLED_COLUMNS_MIN_ROWS:
        ran = get_host_pool().run(unit, order)
        columns, side_by_side = ran.results, ran.workers > 0
        transfers = sum(len(col.device_arrays()) for col in columns)  # a ``device_put`` an array
    else:
        plain = [encoded(name) for name in order]
        t0 = time.perf_counter()
        columns, transfers = _upload_in_blocks(plain, n, npad, rt)
        uploads.append((threading.get_ident(), t0, time.perf_counter()))
        side_by_side = False
    arrays = sum(len(col.device_arrays()) for col in columns)
    _record_unit_times("encode", encodes, side_by_side)
    _record_unit_times("h2d", uploads, side_by_side, arrays=arrays, transfers=transfers)
    made = dict(zip(order, columns))
    return OrderedDict((name, made[name]) for name in sources)


def _host_to_column(arr: np.ndarray, n: int, npad: int, rt) -> Column:
    """Convert one host array to a device Column (pad + shard).

    An object / ``U`` / ``S`` array is a string column: :func:`encode_strings`
    dictionary-encodes it under an ``ingest/encode`` span (hashed when its
    non-null values are all ``str``, the per-value loop otherwise; the vocab
    in code-point order either way, which is ``np.unique``'s: computed by
    Arrow over UTF-8 bytes in the first case, by ``np.unique`` in the other).
    Codes that arrive encoded (avro's decoder) skip that.  Then
    :func:`_upload_column`.  One column on the calling thread: a table's
    columns go through :func:`_upload_columns`, whose units are these two
    steps."""
    if not isinstance(arr, NativeEncodedStrings) and arr.dtype.kind in "OUS":
        arr = encode_strings(arr[:n])
    return _upload_column(arr, n, npad, rt)


def _upload_column(arr: Union[np.ndarray, NativeEncodedStrings], n: int, npad: int, rt) -> Column:
    """One column that needs no dictionary-encoding, from the host to the
    device by itself (a unit of :func:`_upload_columns` from
    ``_POOLED_COLUMNS_MIN_ROWS`` rows up, and :func:`_host_to_column`'s one
    column), under one ``ingest/h2d`` span (a row of the pass's tree where
    ingest calls this; on a pool thread it has the parent it would have had
    on the calling one): the conversion to the device dtypes
    (:func:`_plain_to_host`; ``convert_s``), then array by array the padding
    to ``npad`` rows (``pad_s``) and the ``device_put`` (:func:`_place_column`).
    ``Runtime.shard_rows``'s transfer bracket puts ``bytes``, ``shards`` and
    ``enqueue_s`` on the span for the latter: ``device_put`` is async, so
    those seconds are the time to enqueue, not to move.  The three counts
    are host seconds of this thread and sum to the span's wall."""
    from anovos_tpu.obs.tracing import get_tracer

    with get_tracer().phase("ingest/h2d", cat="io") as sp:
        t0 = time.perf_counter()
        hc = _plain_to_host(arr, n)
        sp.add(convert_s=time.perf_counter() - t0)
        return _place_column(hc, npad, rt, sp)


def _plain_to_host(arr: np.ndarray, n: int) -> HostColumn:
    """The host half of a column that needs no dictionary-encoding (codes
    the native decoder or :func:`encode_strings` made, timestamps, booleans,
    numbers): its first ``n`` entries in the dtypes the device holds, numpy
    throughout."""
    if isinstance(arr, NativeEncodedStrings):
        code_arr = arr.codes[:n]
        return HostColumn("cat", code_arr, code_arr >= 0, vocab=arr.vocab,
                          dtype_name="string")
    if arr.dtype.kind == "M":
        # timestamps → epoch seconds int32
        secs = arr[:n].astype("datetime64[s]", copy=False).view(np.int64)
        isnull = secs == np.iinfo(np.int64).min  # NaT
        if isnull.any():
            secs = np.where(isnull, 0, secs)
        return HostColumn("ts", secs.astype(np.int32), ~isnull, dtype_name="timestamp")
    if arr.dtype.kind == "b":
        return HostColumn("num", arr[:n].astype(np.int32), np.ones(n, bool),
                          dtype_name="boolean")
    # numeric
    dtn = _spark_dtype_name(arr.dtype)
    masked = np.ma.getmaskarray(arr)[:n] if isinstance(arr, np.ma.MaskedArray) else None
    vals = np.ma.getdata(arr)[:n]
    if masked is not None and masked.any():
        # what lies under the mask is anything: zero, as a padding row holds
        vals = np.where(masked, vals.dtype.type(0), vals)
    else:
        masked = None
    if vals.dtype.kind == "f":
        isnull = np.isnan(vals) if masked is None else np.isnan(vals) | masked
        clean = np.where(isnull, 0.0, vals) if isnull.any() else vals
        host = clean.astype(np.float32)
        if vals.dtype.itemsize > 4:
            v64 = clean.astype(np.float64, copy=False)
            if not _f32_holds(host, v64):
                # values don't survive the f32 round-trip: keep the exact
                # order-preserving (hi, lo) pair for distinct/mode/percentiles
                whi, wlo = float_order_parts(v64)
                return HostColumn("num", host, ~isnull, dtype_name=dtn,
                                  wide_hi=whi, wide_lo=wlo, wide_kind="float")
    else:
        isnull = np.zeros(n, dtype=bool) if masked is None else masked
        if vals.dtype.itemsize > 4:
            lo, hi = vals.min(initial=0), vals.max(initial=0)
            if lo >= np.iinfo(np.int32).min and hi <= np.iinfo(np.int32).max:
                host = vals.astype(np.int32)
            else:
                # wide int64: f32 approximation for moment kernels + exact
                # (hi, lo) int32 pair for distinct/mode/percentiles/joins
                whi, wlo = wide_int_parts(vals)
                return HostColumn("num", vals.astype(np.float32), ~isnull,
                                  dtype_name="bigint", wide_hi=whi, wide_lo=wlo)
        else:
            host = vals.astype(np.int32) if vals.dtype.kind in "iu" else vals.astype(np.float32)
    return HostColumn("num", host, ~isnull, dtype_name=dtn)


def _host_arrays(hc: HostColumn) -> List[Tuple[str, np.ndarray, object]]:
    """``(field, array, fill)`` of every array of ``hc`` that the device
    holds, in :meth:`Column.device_arrays`' order; the fill is what a padding
    row carries: mask=False, code −1 in a cat column, the wide pair
    (0, −2^31), 0 elsewhere."""
    arrays = [("data", hc.data, -1 if hc.kind == "cat" else 0), ("mask", hc.mask, False)]
    if hc.wide_hi is not None:
        arrays += [("wide_hi", hc.wide_hi, np.int32(0)), ("wide_lo", hc.wide_lo, np.int32(-(1 << 31)))]
    return arrays


def _device_column(hc: HostColumn, placed: Dict[str, jax.Array]) -> Column:
    """``hc`` with its arrays on the device: ``placed`` by field."""
    return Column(hc.kind, placed["data"], placed["mask"], vocab=hc.vocab, dtype_name=hc.dtype_name,
                  wide_hi=placed.get("wide_hi"), wide_lo=placed.get("wide_lo"), wide_kind=hc.wide_kind)


def _place_column(hc: HostColumn, npad: int, rt, span) -> Column:
    """The device half where a column is a unit: each array of ``hc`` padded
    to ``npad`` rows (:func:`_host_arrays`' fills) and put on ``rt``'s row
    sharding by a ``device_put`` of its own, one array after the other, so
    that an array is on its way while the next is padded.  The padding's
    seconds go on ``span`` as ``pad_s``."""
    def put(a, fill):
        t0 = time.perf_counter()
        padded = _pad_to(a, npad, fill)
        span.add(pad_s=time.perf_counter() - t0)
        return rt.shard_rows(padded)

    return _device_column(hc, {field: put(a, fill) for field, a, fill in _host_arrays(hc)})


# Arrays of one dtype that go to the device as one block and come apart
# there (``_upload_in_blocks``).  The width of the split program, so one
# program a dtype and row bucket whatever the table's width; chosen on the
# chip (PERF.md section 6, PR 47).
_BLOCK_ARRAYS = 128


@functools.lru_cache(maxsize=16)
def _split_rows_program(sharding: NamedSharding):
    """The program that takes a ``(k, rows)`` block apart into its ``k``
    arrays of ``(rows,)``, each on ``sharding``.  ``out_shardings`` is stated:
    left to the compiler the arrays come back with another spec than
    ``Runtime.shard_rows`` gives (``P()`` for ``P('data',)`` on one device),
    and every program they then enter compiles a second time."""
    def _split_rows(block):
        return tuple(block[i] for i in range(block.shape[0]))

    return jax.jit(_split_rows, out_shardings=sharding)


def _upload_in_blocks(srcs: Sequence[Union[np.ndarray, NativeEncodedStrings]], n: int, npad: int,
                      rt) -> Tuple[List[Column], int]:
    """The columns of a short table (no array needs a dictionary-encoding any
    more) from the host to the device on this thread, by typed block and not
    by array, under ONE ``ingest/h2d`` span: column after column the
    conversion to the device dtypes (:func:`_plain_to_host`; ``convert_s``);
    the arrays wait by dtype (f32 data; int32 data, codes, timestamps and wide
    halves; bool masks), and as soon as ``_BLOCK_ARRAYS`` of a dtype wait they
    are copied into the rows of one ``(_BLOCK_ARRAYS, npad)`` host block, which
    is their padding (``pad_s``), the block goes over in one ``device_put``
    with its row axis on ``rt``'s data axis (``enqueue_s``, ``bytes``,
    ``shards`` from ``Runtime``'s transfer bracket) and is taken apart on the
    device by :func:`_split_rows_program` (``split_s``: the seconds to
    dispatch it; the block is let go of there and freed when the split has
    run, so the device never holds a second table).  What is left of a dtype
    at the end, fewer arrays than a block has, is padded array by array and
    goes in one ``device_put`` of the list: no bytes but the arrays' own are
    moved, and no program is compiled for a width.  The counts are this
    thread's seconds and sum to at most the span's wall.  Returns the
    columns and how many ``device_put`` calls carried their arrays."""
    from anovos_tpu.obs.tracing import get_tracer

    made: List[Tuple[HostColumn, Dict[str, jax.Array]]] = []  # a column and its arrays on the device, by field
    waiting: Dict[np.dtype, List[Tuple[Dict[str, jax.Array], str, np.ndarray, object]]] = {}
    transfers = 0

    def hand_over(group, sp) -> None:
        nonlocal transfers
        t0 = time.perf_counter()
        if len(group) == _BLOCK_ARRAYS:
            block = np.empty((len(group), npad), dtype=group[0][2].dtype)
            for i, (_, _, a, fill) in enumerate(group):
                block[i, :len(a)] = a
                block[i, len(a):] = fill
            t1 = time.perf_counter()
            on_device = rt.shard_rows_block(block)
            t2 = time.perf_counter()
            parts = _split_rows_program(rt.row_sharding())(on_device)
            sp.add(split_s=time.perf_counter() - t2)
        else:
            padded = [_pad_to(a, npad, fill) for _, _, a, fill in group]
            t1 = time.perf_counter()
            parts = rt.shard_rows_of_many(padded)
        sp.add(pad_s=t1 - t0)
        transfers += 1
        for (placed, field, _, _), part in zip(group, parts):
            placed[field] = part
        group.clear()

    with get_tracer().phase("ingest/h2d", cat="io") as sp:
        for src in srcs:
            t0 = time.perf_counter()
            hc = _plain_to_host(src, n)
            sp.add(convert_s=time.perf_counter() - t0)
            made.append((hc, {}))
            for field, a, fill in _host_arrays(hc):
                group = waiting.setdefault(a.dtype, [])
                group.append((made[-1][1], field, a, fill))
                if len(group) == _BLOCK_ARRAYS:
                    hand_over(group, sp)
        for group in waiting.values():
            if group:
                hand_over(group, sp)
    return [_device_column(hc, placed) for hc, placed in made], transfers


def _host_column_to_pandas(hc: HostColumn):
    """The host half of :meth:`Table.to_pandas`: one column of the frame
    from its host arrays."""
    data, mask = hc.data, hc.mask
    if hc.kind == "cat":
        vals = np.empty(len(data), dtype=object)
        valid = mask & (data >= 0)
        vals[valid] = hc.vocab[data[valid]]
        vals[~valid] = None
        return vals
    if hc.kind == "ts":
        vals = data.astype("int64") * np.int64(1_000_000_000)
        s = pd.Series(vals.view("datetime64[ns]").copy())
        s[~mask] = pd.NaT
        return s
    if hc.wide_hi is not None:
        vals = _wide_exact(hc.wide_hi, hc.wide_lo, hc.wide_kind)  # exact int64 / float64
        if hc.wide_kind == "float":
            vals = vals.copy()
            vals[~mask] = np.nan
            return vals
        if mask.all():
            return vals
        # nullable after outer joins: pandas Int64 keeps exactness
        return pd.arrays.IntegerArray(vals, ~mask)
    if np.issubdtype(data.dtype, np.integer) and mask.all():
        return data
    vals = data.astype("float64")
    vals[~mask] = np.nan
    return vals


def arrow_typed_kind(dtype) -> Optional[str]:
    """``"decimal"`` or ``"date"`` for a pandas dtype that holds such an Arrow
    type (``pd.ArrowDtype``): the types that pandas would otherwise make one
    Python object a value of (a ``decimal.Decimal``, a ``datetime.date``).
    None for every other dtype."""
    if not isinstance(dtype, pd.ArrowDtype):
        return None
    t = dtype.pyarrow_dtype
    return "decimal" if pa.types.is_decimal(t) else "date" if pa.types.is_date(t) else None


def _decimal_to_float64(arr: pa.ChunkedArray) -> np.ndarray:
    """A decimal column as float64 (null: NaN) without an object per value.
    Up to 18 digits of ``decimal128`` the unscaled integer is the low word of
    the 16 bytes a value has in the array's buffer; it is divided by 10^scale,
    which for the 15 digits float64 holds exactly is ``float(Decimal)`` to
    the bit (Arrow's own cast multiplies by 10^-scale and is an ulp off on
    one value in eight).  Wider decimals take Arrow's cast."""
    t = arr.type
    if not pa.types.is_decimal128(t) or t.precision > 18:
        return pc.cast(arr, pa.float64()).to_numpy()
    out = np.empty(len(arr), dtype=np.float64)
    at = 0
    for chunk in arr.chunks:
        if not len(chunk):  # an empty part file: a chunk with no buffer to read
            continue
        words = np.frombuffer(chunk.buffers()[1], dtype=np.int64)
        vals = out[at:at + len(chunk)]
        np.divide(words[2 * chunk.offset:2 * (chunk.offset + len(chunk)):2],
                  float(10 ** t.scale), out=vals)
        if chunk.null_count:
            vals[~chunk.is_valid().to_numpy(zero_copy_only=False)] = np.nan
        at += len(chunk)
    return out


def arrow_typed_to_numpy(s) -> np.ndarray:
    """A Series of an :func:`arrow_typed_kind` dtype as the numpy array
    :func:`_plain_to_host` takes, in Arrow and numpy with no object a value:
    a decimal as float64 (null: NaN), a date as ``datetime64[s]`` at midnight
    (null: NaT)."""
    arr = s.array.__arrow_array__()
    if arrow_typed_kind(s.dtype) == "decimal":
        return _decimal_to_float64(arr)
    return pc.cast(arr, pa.timestamp("s")).to_numpy()


def _frame_sources(df) -> Dict[str, object]:
    """A pandas frame's columns as :func:`_upload_columns` takes them, in the
    frame's order: a column of a string dtype or of dtype ``category`` as its
    Series, not yet encoded (:class:`_UnencodedStrings`); an ``object`` column
    as objects; every other dtype as its numpy array; an Arrow-typed decimal
    or date column (:func:`arrow_typed_kind`) as float64 or
    ``datetime64[s]``; a column of pandas' nullable integers as a masked
    array of its integers."""
    data = {}
    for name in df.columns:
        s = df[name]
        if isinstance(s.dtype, (pd.StringDtype, pd.CategoricalDtype)):
            data[name] = _UnencodedStrings(s)
        elif arrow_typed_kind(s.dtype):
            data[name] = arrow_typed_to_numpy(s)
        elif isinstance(s.array, pd.arrays.IntegerArray):
            # pandas' nullable integers (a parquet integer column with nulls):
            # values and mask as they are, no float and no object a value
            data[name] = np.ma.MaskedArray(s.array._data, s.array._mask)
        elif s.dtype == object:
            data[name] = s.to_numpy(dtype=object)
        else:
            data[name] = s.to_numpy()
    return data


def _frame_arrays(df, encode) -> Dict[str, Union[np.ndarray, NativeEncodedStrings]]:
    """:func:`_frame_sources` with the string and category columns through
    ``encode``: a frame's columns as :meth:`Table.from_numpy` takes them, for
    a frame that stays on the host (:func:`host_table_frame`).  The string
    columns of a frame of ``_POOLED_COLUMNS_MIN_ROWS`` rows or more are units
    of the host pool (``shared.host_pool``), encoded side by side once this
    thread has taken the other columns' arrays; the dict is in the frame's
    column order either way.  ``encode_workers`` (threads that
    encoded a column; 0 for the loop) and ``encode_wall_s`` (first start to
    last end) go on the row of the pass's tree the call runs under."""
    data = _frame_sources(df)
    strings = [name for name, src in data.items() if isinstance(src, _UnencodedStrings)]
    if strings:
        ran = get_host_pool().run(lambda name: encode(data[name].series), strings,
                                  side_by_side=len(df) >= _POOLED_COLUMNS_MIN_ROWS)
        data.update(zip(strings, ran.results))
        record_units("encode", ran)
    return data


def host_table_frame(df):
    """``Table.from_pandas(df).to_pandas()`` without the device: the two
    host halves of that round trip (:func:`_plain_to_host`,
    :func:`_host_column_to_pandas`) composed, so the frame comes back as a
    ``Table`` would return it (``bool`` and an ``int64`` that fits as
    ``int32``, wide ints and floats that ``float32`` cannot hold exact,
    strings through :func:`encode_strings`' vocabulary with ``None`` for a
    null, timestamps to the second) with no ``device_put``, no ``device_get``,
    no padding to the row bucket and no ``ingest/encode`` / ``ingest/h2d``
    span.  For a frame that is on the host and is only being written."""
    def encode(values):
        return _encode_with_counts(values)[0]

    n = len(df)
    out = {}
    for name, arr in _frame_arrays(df, encode).items():
        if not isinstance(arr, NativeEncodedStrings):
            arr = np.asanyarray(arr)
            if arr.dtype.kind in "OUS":
                arr = encode(arr[:n])
        out[name] = _host_column_to_pandas(_plain_to_host(arr, n))
    return pd.DataFrame(out, columns=list(out))


def make_column_from_device(
    kind: str,
    data: jax.Array,
    mask: jax.Array,
    vocab: Optional[np.ndarray] = None,
    dtype_name: Optional[str] = None,
) -> Column:
    if dtype_name is None:
        dtype_name = {"num": "double", "cat": "string", "ts": "timestamp"}[kind]
        if kind == "num" and data.dtype in (jnp.int32, jnp.int16, jnp.int8):
            dtype_name = "int"
    return Column(kind, data, mask, vocab=vocab, dtype_name=dtype_name)
