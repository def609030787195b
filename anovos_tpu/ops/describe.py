"""Fused whole-table description kernels.

stats_generator's seven public functions each need a slice of the same
underlying statistics.  Computing them per function costs one device
dispatch each — expensive on remote backends and wasteful anywhere.  These
kernels compute EVERYTHING for a column block in ONE program:

- ``describe_numeric``: count/sum/mean/var/std/skew/kurt/min/max/nonzero,
  the full percentile grid, and exact distinct counts — one sort, shared.
- ``describe_wide_int``: the exact order statistics of (hi, lo) int32 pair
  columns — one two-key sort, shared the same way.
- ``describe_cat``: per-column code histograms (padded to the max vocab),
  from which mode, unique, missing, and frequency charts all derive.

``table_describe`` memoizes per (table, column tuple) so a pipeline's stats
block issues two dispatches total instead of ~14.
"""

from __future__ import annotations

import functools
import os
import threading
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from anovos_tpu.shared.runtime import column_parallel, wants_column_parallel
from anovos_tpu.shared.table import Table
from anovos_tpu.obs import get_tracer, timed

# the percentile grid every consumer shares (measures_of_percentiles order)
PCTL_QS = (0.0, 0.01, 0.05, 0.10, 0.25, 0.50, 0.75, 0.90, 0.95, 0.99, 1.0)


@timed("ops.describe_numeric")
def describe_numeric(X: jax.Array, M: jax.Array) -> Dict[str, jax.Array]:
    """One program: moments + percentiles + distinct counts for (rows, k).

    The sort-based statistics run column-parallel on a multi-device mesh
    (see runtime.column_parallel); moments stay on the input's row
    sharding (partial-sum + psum)."""
    return _describe_numeric(X, M, cp=wants_column_parallel(X, M))


@functools.partial(jax.jit, static_argnames=("cp",))
def _describe_numeric(X: jax.Array, M: jax.Array, *, cp: bool = False) -> Dict[str, jax.Array]:
    dt = jnp.float32
    Xf = X.astype(dt)
    # exact integer valid count — a float32 ones-sum plateaus at 2^24 rows
    n_int = M.sum(axis=0, dtype=jnp.int32)
    n = n_int.astype(dt)
    safe_n = jnp.maximum(n, 1.0)
    s1 = jnp.where(M, Xf, 0).sum(axis=0)
    mean = s1 / safe_n
    d = jnp.where(M, Xf - mean, 0)
    d2 = d * d
    m2 = d2.sum(axis=0)
    m3 = (d2 * d).sum(axis=0)
    m4 = (d2 * d2).sum(axis=0)
    var_samp = m2 / jnp.maximum(n - 1.0, 1.0)
    std = jnp.sqrt(var_samp)
    m2p = m2 / safe_n
    skew = jnp.where(m2p > 0, (m3 / safe_n) / jnp.power(jnp.maximum(m2p, 1e-38), 1.5), jnp.nan)
    kurt = jnp.where(m2p > 0, (m4 / safe_n) / jnp.maximum(m2p * m2p, 1e-38) - 3.0, jnp.nan)
    nonzero = (M & (Xf != 0)).sum(axis=0, dtype=jnp.int32).astype(dt)

    # ONE sort feeds percentiles AND distinct counts.  The sort input is
    # re-laid column-parallel first: a sort along the row-sharded axis
    # would emit O(log n) cross-device partition exchanges, while one
    # small all-to-all makes the sort and everything derived from it
    # device-local (runtime.column_parallel).
    big = jnp.asarray(jnp.finfo(dt).max, dt)
    Xs = jnp.sort(column_parallel(jnp.where(M, Xf, big), cp), axis=0)
    rows = X.shape[0]
    pos_idx = jnp.arange(rows, dtype=jnp.int32)[:, None]
    valid_sorted = pos_idx < n_int[None, :]
    trans = jnp.concatenate([jnp.ones((1, X.shape[1]), bool), Xs[1:] != Xs[:-1]], axis=0)
    nunique = (trans & valid_sorted).sum(axis=0, dtype=jnp.int32)

    # integer percentile positions: float64-free exact index arithmetic
    qs = jnp.asarray(PCTL_QS, dt)
    pos = qs[:, None] * jnp.maximum(n[None, :] - 1, 0)
    lo_i = jnp.minimum(jnp.floor(pos).astype(jnp.int32), jnp.maximum(n_int[None, :] - 1, 0))
    pctls = jnp.where(n[None, :] > 0, jnp.take_along_axis(Xs, lo_i, axis=0), jnp.nan)

    # mode from the same sort: longest equal run, via cummax of run-start
    # positions (no scatter/segment ops — cheap to compile, VPU-friendly).
    # runlen peaks at the END of the longest run; argmax takes the first
    # peak → earliest run → smallest value on count ties.
    pos2 = jnp.arange(rows, dtype=jnp.int32)[:, None]
    run_start = jax.lax.cummax(jnp.where(trans, pos2, -1), axis=0)
    runlen = jnp.where(valid_sorted, pos2 - run_start + 1, 0)
    best_idx = jnp.argmax(runlen, axis=0)  # (k,)
    mode_cnt = jnp.take_along_axis(runlen, best_idx[None, :], axis=0)[0]
    mode_val = jnp.take_along_axis(Xs, best_idx[None, :], axis=0)[0]

    empty = n_int == 0
    nanv = jnp.asarray(jnp.nan, dt)
    return {
        "count": n_int,
        "mean": jnp.where(empty, nanv, mean),
        "variance": jnp.where(n > 1, var_samp, nanv),
        "stddev": jnp.where(n > 1, std, nanv),
        "skewness": jnp.where(empty, nanv, skew),
        "kurtosis": jnp.where(empty, nanv, kurt),
        "min": pctls[0],
        "max": pctls[-1],
        "nonzero": nonzero,
        "nunique": nunique,
        "percentiles": pctls,  # (len(PCTL_QS), k), 'lower' interpolation
        "mode_value": jnp.where(empty, nanv, mode_val),
        "mode_count": mode_cnt,
    }


@functools.partial(jax.jit, static_argnames=("chunk",))
def _chunked_chunk_moments(X: jax.Array, M: jax.Array, chunk: int) -> Dict[str, jax.Array]:
    """Per-chunk centered moments for the compensated path: (rows, k) →
    dict of (c, k) f32 arrays, one device dispatch.  Each chunk is centered
    on its OWN mean, so the f32 error of every partial stays bounded by the
    chunk length instead of the full row count; the cross-chunk combination
    happens on host in float64 (Chan et al., ops/streaming._combine).
    The per-chunk body IS streaming's ``_chunk_stats`` vmapped over the
    chunk axis — one copy of the moment math, one merge contract."""
    from anovos_tpu.ops.streaming import _chunk_stats

    rows, k = X.shape
    c = -(-rows // chunk)
    pad = c * chunk - rows
    Xp = jnp.pad(X.astype(jnp.float32), ((0, pad), (0, 0)))
    Mp = jnp.pad(M, ((0, pad), (0, 0)))
    return jax.vmap(_chunk_stats)(Xp.reshape(c, chunk, k), Mp.reshape(c, chunk, k))


_COMPENSATED_CHUNK = 1 << 16


@timed("ops.compensated_moments")
def compensated_moments(X: jax.Array, M: jax.Array, chunk: int = _COMPENSATED_CHUNK) -> Dict[str, np.ndarray]:
    """Chunked-Chan compensated moments (SURVEY §7 hard-part 7): f32 error
    stops growing with the row count because each 2^16-row chunk is centered
    locally on device and the chunk partials merge pairwise on host in
    float64.  Returns float64 host arrays: count/mean/variance/stddev/
    skewness/kurtosis (sample variance, Fisher kurtosis — describe_numeric
    conventions).  Measured tolerance vs a float64 two-pass at 10^7 rows is
    recorded in PERF.md."""
    from anovos_tpu.ops.streaming import _pairwise_merge

    k = X.shape[1]
    if X.shape[0] == 0:  # zero-row block: no chunks to merge
        nank = np.full(k, np.nan)
        return {"count": np.zeros(k, np.int64), "mean": nank.copy(),
                "variance": nank.copy(), "stddev": nank.copy(),
                "skewness": nank.copy(), "kurtosis": nank.copy()}
    parts_dev = {kk: np.asarray(v, np.float64) for kk, v in _chunked_chunk_moments(X, M, chunk).items()}
    c = parts_dev["n"].shape[0]
    agg = _pairwise_merge([{kk: v[i] for kk, v in parts_dev.items()} for i in range(c)])
    n = agg["n"]
    safe_n = np.maximum(n, 1.0)
    m2p = agg["M2"] / safe_n
    with np.errstate(invalid="ignore", divide="ignore"):
        var_samp = np.where(n > 1, agg["M2"] / np.maximum(n - 1.0, 1.0), np.nan)
        skew = np.where(m2p > 0, (agg["M3"] / safe_n) / np.power(np.maximum(m2p, 1e-308), 1.5), np.nan)
        kurt = np.where(m2p > 0, (agg["M4"] / safe_n) / np.maximum(m2p * m2p, 1e-308) - 3.0, np.nan)
    return {
        "count": n.astype(np.int64),
        "mean": np.where(n > 0, agg["mean"], np.nan),
        "variance": var_samp,
        "stddev": np.sqrt(var_samp),
        "skewness": np.where(n > 0, skew, np.nan),
        "kurtosis": np.where(n > 0, kurt, np.nan),
    }


# 'auto' turns the compensated path on once plain-f32 tree reductions have
# demonstrably drifting tails (≥2^24 rows the f32 significand is exhausted
# by the count alone); '1'/'0' force it either way
_COMPENSATED_AUTO_ROWS = 1 << 24


def _compensated_enabled(rows: int) -> bool:
    mode = os.environ.get("ANOVOS_COMPENSATED_MOMENTS", "auto").lower()
    if mode in ("1", "true", "always"):
        return True
    if mode in ("0", "false", "never"):
        return False
    return rows >= _COMPENSATED_AUTO_ROWS


@timed("ops.describe_wide_int")
def describe_wide_int(hi: jax.Array, lo: jax.Array, M: jax.Array) -> Dict[str, jax.Array]:
    """Exact order statistics for wide-int64 columns stored as (hi, lo) int32
    pairs (Table docstring encoding: signed lexicographic pair order == int64
    numeric order).  One program: lexicographic sort of the pair as one
    two-key sort, then distinct count, percentile grid, and mode — all int32
    ops, no f32 precision loss (TPUs have no native int64)."""
    return _describe_wide_int(hi, lo, M, cp=wants_column_parallel(hi, lo, M))


@functools.partial(jax.jit, static_argnames=("cp",))
def _describe_wide_int(hi: jax.Array, lo: jax.Array, M: jax.Array, *, cp: bool = False) -> Dict[str, jax.Array]:
    rows, k = hi.shape
    n_int = M.sum(axis=0, dtype=jnp.int32)
    big = jnp.iinfo(jnp.int32).max
    # column-parallel re-lay before the sort (runtime.column_parallel)
    hi_s = column_parallel(jnp.where(M, hi, big), cp)
    lo_s = column_parallel(jnp.where(M, lo, big), cp)
    # ONE two-key sort carries both halves: no permutation, no row-length gather.
    # Both operands are keys, so rows that compare equal are equal and stability
    # decides nothing; asking for it makes the TPU compiler carry an iota as a
    # third operand (a quarter more device time, twice the compile).
    hi2, lo2 = jax.lax.sort((hi_s, lo_s), dimension=0, num_keys=2, is_stable=False)
    pos = jnp.arange(rows, dtype=jnp.int32)[:, None]
    valid_sorted = pos < n_int[None, :]
    trans = jnp.concatenate(
        [jnp.ones((1, k), bool), (hi2[1:] != hi2[:-1]) | (lo2[1:] != lo2[:-1])], axis=0
    )
    nunique = (trans & valid_sorted).sum(axis=0, dtype=jnp.int32)
    qs = jnp.asarray(PCTL_QS, jnp.float32)
    n = n_int.astype(jnp.float32)
    pos_q = qs[:, None] * jnp.maximum(n[None, :] - 1, 0)
    lo_i = jnp.minimum(jnp.floor(pos_q).astype(jnp.int32), jnp.maximum(n_int[None, :] - 1, 0))
    run_start = jax.lax.cummax(jnp.where(trans, pos, -1), axis=0)
    runlen = jnp.where(valid_sorted, pos - run_start + 1, 0)
    best = jnp.argmax(runlen, axis=0)
    return {
        "count": n_int,
        "nunique": nunique,
        "pctl_hi": jnp.take_along_axis(hi2, lo_i, axis=0),
        "pctl_lo": jnp.take_along_axis(lo2, lo_i, axis=0),
        "mode_hi": jnp.take_along_axis(hi2, best[None, :], axis=0)[0],
        "mode_lo": jnp.take_along_axis(lo2, best[None, :], axis=0)[0],
        "mode_count": jnp.take_along_axis(runlen, best[None, :], axis=0)[0],
    }


def _wide_pair_to_f64(hi: np.ndarray, lo: np.ndarray, kinds=None) -> np.ndarray:
    """Host reconstruction of the exact value as float64.  kinds is a
    per-column list over the LAST axis: "int" pairs are the int64 value
    (exact up to 2^53, i.e. every realistic id); "float" pairs are the
    order-preserving key of a float64 bit pattern (table.float_order_key)."""
    v = (hi.astype(np.int64) << 32) + (lo.astype(np.int64) + (1 << 31))
    out = v.astype(np.float64)
    if kinds is not None:
        from anovos_tpu.shared.table import float_from_order_key

        for j, kind in enumerate(kinds):
            if kind == "float":
                out[..., j] = float_from_order_key(v[..., j])
    return out


@functools.partial(jax.jit, static_argnames=("max_vocab",))
def describe_cat(C: jax.Array, M: jax.Array, max_vocab: int) -> Dict[str, jax.Array]:
    """One program: per-column code histograms for (rows, k_cat) codes.
    counts: (k, max_vocab); count/nunique/mode derive from it."""
    valid = M & (C >= 0)
    lanes = jnp.arange(max_vocab, dtype=C.dtype)
    eq = (C[:, :, None] == lanes) & valid[:, :, None]
    counts = eq.sum(axis=0).astype(jnp.float32)  # (k, maxv)
    return {
        "counts": counts,
        "count": valid.sum(axis=0),
        "nunique": (counts > 0).sum(axis=1),
        "mode_code": jnp.argmax(counts, axis=1),
        "mode_count": counts.max(axis=1),
    }


# above this vocab size the dense lane sweep is wasteful (O(rows·k·vocab));
# high-cardinality columns (ids) go through the sort-based kernel on their
# codes instead — same count/nunique/mode outputs
_CAT_SWEEP_MAX_VOCAB = 1024
# the sort path takes the codes through f32 (describe_numeric's one dtype),
# which holds every integer only below 2^24: a vocabulary of that length or
# more would merge neighbouring codes, silently
_CAT_SORT_MAX_VOCAB = 1 << 24


@timed("ops.table_describe")
def table_describe(idf: Table, num_cols: List[str], cat_cols: List[str]) -> Tuple[dict, dict]:
    """Memoized fused description: (numeric dict of host arrays, cat dict
    with per-column count/nunique/mode_code/mode_count).

    The cache lives on the Table instance — any transformation produces a
    NEW Table, so staleness is impossible by construction.  The table's
    lock makes the memo single-flight: of the scheduler nodes that ask for
    the same table at once, one computes and the others wait for its
    result (without it six of a stats pass's seven nodes each dispatched
    the whole describe), under the span ``describe/wait``.  The scheduler
    node a call runs under counts the outcome on its span:
    ``describe_computed`` 1 for the compute, 0 for a memo hit.
    """
    lock = idf.__dict__.setdefault("_describe_lock", threading.Lock())
    with get_tracer().holding(lock, "describe/wait", cat="op"):
        cache = idf.__dict__.setdefault("_describe_cache", {})
        # the compensated mode is a cache INPUT: toggling the env var mid-process
        # must not serve the other mode's moments.  The threshold compares the
        # LOGICAL row count — shape-bucket padding inflates the device length
        # and must not flip the mode for tables just under the cutoff.
        compensated = bool(num_cols) and _compensated_enabled(idf.nrows)
        key = (tuple(num_cols), tuple(cat_cols), compensated)
        computed = key not in cache
        if computed:
            cache[key] = _table_describe(idf, num_cols, cat_cols, compensated)
    node = get_tracer().enclosing("node")
    if node is not None:
        node.add(describe_computed=int(computed))
    return cache[key]


def _table_describe(idf: Table, num_cols: List[str], cat_cols: List[str],
                    compensated: bool) -> Tuple[dict, dict]:
    """The unmemoized body of :func:`table_describe`, under the span
    ``describe`` with one child a kernel family that ran: ``describe/numeric``,
    ``describe/wide``, ``describe/cat_sweep``, ``describe/cat_sort``, each from
    the stacking of its block to the fetch of its results, with ``rows`` and
    ``cols`` of the stacked block as the program takes it (padding included)
    and on the cat spans ``vocab_max``."""
    too_long = [c for c in cat_cols if len(idf.columns[c].vocab) >= _CAT_SORT_MAX_VOCAB]
    if too_long:  # before any dispatch
        raise ValueError(
            f"describe: the vocabulary of {too_long} has {_CAT_SORT_MAX_VOCAB} values or more; "
            "the sort path holds codes in float32, which is exact only below 2^24")
    with get_tracer().phase("describe", cat="op", num_cols=len(num_cols), cat_cols=len(cat_cols)):
        return _describe_blocks(idf, num_cols, cat_cols, compensated)


def _describe_blocks(idf: Table, num_cols: List[str], cat_cols: List[str],
                     compensated: bool) -> Tuple[dict, dict]:
    phase = get_tracer().phase
    num_out: dict = {}
    if num_cols:
        with phase("describe/numeric", cat="op") as sp:
            num_out = _numeric_stats(idf, num_cols, compensated, sp)
        wide = [c for c in num_cols if idf.columns[c].is_wide]
        if wide:
            with phase("describe/wide", cat="op") as sp:
                num_out = _wide_order_stats(idf, num_cols, wide, num_out, sp)
    cat_out: dict = {}
    if cat_cols:
        k = len(cat_cols)
        cat_out = {
            "count": np.zeros(k, np.int64),
            "nunique": np.zeros(k, np.int64),
            "mode_code": np.zeros(k, np.int64),
            "mode_count": np.zeros(k, np.float64),
        }
        small = [c for c in cat_cols if len(idf.columns[c].vocab) <= _CAT_SWEEP_MAX_VOCAB]
        large = [c for c in cat_cols if c not in set(small)]
        if small:
            with phase("describe/cat_sweep", cat="op") as sp:
                _cat_sweep_stats(idf, cat_cols, small, cat_out, sp)
        if large:
            with phase("describe/cat_sort", cat="op") as sp:
                _cat_sort_stats(idf, cat_cols, large, cat_out, sp)
    return num_out, cat_out


def _numeric_stats(idf: Table, num_cols: List[str], compensated: bool, sp) -> dict:
    X, M = idf.numeric_block(num_cols)
    sp.add(rows=X.shape[0], cols=X.shape[1])
    # numeric_block column-buckets to k_pad dead lanes (mask=False);
    # slice every per-column output back to the live k before the host
    # arrays escape to consumers that zip/stack them against num_cols
    kk_live = len(num_cols)
    num_out = {k: np.asarray(v)[..., :kk_live]
               for k, v in describe_numeric(X, M).items()}
    if compensated:
        comp = compensated_moments(X, M)
        for kk in ("mean", "variance", "stddev", "skewness", "kurtosis"):
            num_out[kk] = comp[kk][..., :kk_live]
    return num_out


def _wide_order_stats(idf: Table, num_cols: List[str], wide: List[str], num_out: dict, sp) -> dict:
    """``num_out`` with the f32-approximate order stats of the ``wide``
    columns overwritten by exact values from the (hi, lo) int32-pair kernel
    (moments stay f32-approx); the lexicographic sort is order-correct for
    BOTH wide kinds.  Stacks are column-bucketed like numeric_block; the
    j-indexed reads below never touch the dead lanes."""
    from anovos_tpu.shared.table import stack_padded

    Hi, Mw = stack_padded([idf.columns[c].wide_hi for c in wide],
                          [idf.columns[c].mask for c in wide], dtype=jnp.int32)
    Lo, _ = stack_padded([idf.columns[c].wide_lo for c in wide],
                         [idf.columns[c].mask for c in wide], dtype=jnp.int32)
    sp.add(rows=Hi.shape[0], cols=Hi.shape[1])
    w = {kk: np.asarray(v) for kk, v in describe_wide_int(Hi, Lo, Mw).items()}
    kinds = [idf.columns[c].wide_kind for c in wide]
    pctl = _wide_pair_to_f64(w["pctl_hi"], w["pctl_lo"], kinds)  # (nq, kw)
    mode = _wide_pair_to_f64(w["mode_hi"], w["mode_lo"], kinds)
    num_out = {kk: v.copy() for kk, v in num_out.items()}
    for kk in ("percentiles", "min", "max", "mode_value"):
        num_out[kk] = num_out[kk].astype(np.float64)
    for j, c in enumerate(wide):
        if w["count"][j] == 0:
            continue  # all-null: keep describe_numeric's NaNs, not the sort sentinel
        i = num_cols.index(c)
        num_out["nunique"][i] = w["nunique"][j]
        num_out["percentiles"][:, i] = pctl[:, j]
        num_out["min"][i] = pctl[0, j]
        num_out["max"][i] = pctl[-1, j]
        num_out["mode_value"][i] = mode[j]
        num_out["mode_count"][i] = w["mode_count"][j]
    return num_out


def _cat_sweep_stats(idf: Table, cat_cols: List[str], small: List[str], cat_out: dict, sp) -> None:
    """The lane sweep over the ``small`` vocabularies, into ``cat_out``."""
    from anovos_tpu.shared.table import stack_padded

    # bucket by vocab size (powers of 4): one 1000-category column must
    # not multiply the lane count of thirty binary columns
    buckets: Dict[int, List[str]] = {}
    for c in small:
        v = max(len(idf.columns[c].vocab), 1)
        b = 4
        while b < v:
            b *= 4
        buckets.setdefault(b, []).append(c)
    # dispatch every bucket's program before fetching any result: the
    # per-bucket kernels overlap on the device stream instead of each
    # waiting for the previous bucket's download (graftcheck GC001)
    bucket_res = []
    for b, cols_b in sorted(buckets.items()):
        # column-bucketed stack (dead lanes code 0 / mask False → zero
        # counts); reads below are j-indexed over the live cols_b
        C, Mc = stack_padded([idf.columns[c].data for c in cols_b],
                             [idf.columns[c].mask for c in cols_b], dtype=jnp.int32)
        sp.add(cols=C.shape[1])
        bucket_res.append((cols_b, describe_cat(C, Mc, b)))
    sp.add(rows=idf.padded_rows, vocab_max=max(buckets))
    for cols_b, res in bucket_res:
        sw = {kk: np.asarray(v) for kk, v in res.items()}
        for j, c in enumerate(cols_b):
            i = cat_cols.index(c)
            cat_out["count"][i] = sw["count"][j]
            cat_out["nunique"][i] = sw["nunique"][j]
            cat_out["mode_code"][i] = sw["mode_code"][j]
            cat_out["mode_count"][i] = sw["mode_count"][j]


def _cat_sort_stats(idf: Table, cat_cols: List[str], large: List[str], cat_out: dict, sp) -> None:
    """The ``large`` vocabularies, into ``cat_out``: codes are just ints, so
    the sort-based numeric kernel yields count/nunique/mode directly, no
    per-vocab lanes (in f32: ``_CAT_SORT_MAX_VOCAB``)."""
    from anovos_tpu.ops.segment import cat_valid_mask
    from anovos_tpu.shared.table import stack_padded

    lg_masks = [cat_valid_mask(idf.columns[c].data, idf.columns[c].mask)
                for c in large]
    C, Mc = stack_padded(
        [idf.columns[c].data for c in large],
        lg_masks,
        dtype=jnp.int32,
    )
    sp.add(rows=C.shape[0], cols=C.shape[1],
           vocab_max=max(len(idf.columns[c].vocab) for c in large))
    lg_dev = describe_numeric(C, Mc)
    # bulk-materialize the four stats once: per-element int()/float()
    # in the loop was one blocking device round-trip per column per
    # stat (graftcheck GC001)
    lg = {kk: np.asarray(lg_dev[kk])
          for kk in ("count", "nunique", "mode_value", "mode_count")}
    for j, c in enumerate(large):
        i = cat_cols.index(c)
        cat_out["count"][i] = int(lg["count"][j])
        cat_out["nunique"][i] = int(lg["nunique"][j])
        mv = float(lg["mode_value"][j])
        cat_out["mode_code"][i] = int(mv) if mv == mv else -1
        cat_out["mode_count"][i] = float(lg["mode_count"][j])
