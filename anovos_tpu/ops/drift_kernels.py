"""Fused drift-histogram kernels.

The entire per-dataset side of drift_detector.statistics — numeric binning
against source cutoffs AND categorical code counting for every column — runs
in ONE jitted program.  This is the dispatch-count discipline that makes the
PSI benchmark fast: the reference launches thousands of Spark jobs
(drift_detector.py:243-344); a naive port launches dozens of eager device
ops; this launches two.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from anovos_tpu.obs import timed


# Above this lane count, compare-and-reduce's O(rows·k·nbins) sweep loses to
# the scatter; below it, the dense sweep is ~3× faster on TPU (scatter-adds
# serialize; elementwise compare + tree-reduce ride the VPU at full tilt).
_CMP_LANES_MAX = 8192


def _dense_budget() -> int:
    """Max rows·k·nbins elements the dense compare-and-reduce may touch.

    The lane cap alone is not enough: with a 3.5k-way categorical (e.g. a
    geohash column) the dense sweep is rows×k×3558 — tens of GB at benchmark
    row counts, an OOM on TPU and minutes on CPU — while the flattened
    segment_sum stays O(rows·k) regardless of lane count.
    """
    env = os.environ.get("ANOVOS_DENSE_HIST_BUDGET")
    if env:
        return int(env)
    return 1 << 30 if jax.default_backend() == "tpu" else 1 << 24


def _flat_counts(idx: jax.Array, valid: jax.Array, nbins: int) -> jax.Array:
    """Per-column counts: idx (rows, k) in [0, nbins), valid (rows, k) →
    (k, nbins).  Small lane counts use compare-and-reduce (TPU-friendly,
    no scatter); large sweeps fall back to one flattened segment_sum."""
    rows, k = idx.shape
    if nbins <= _CMP_LANES_MAX and rows * k * nbins <= _dense_budget():
        lanes = jnp.arange(nbins, dtype=idx.dtype)
        eq = (idx[:, :, None] == lanes) & valid[:, :, None]
        return eq.sum(axis=0).astype(jnp.float32)
    offset = jnp.arange(k, dtype=jnp.int32)[None, :] * nbins
    flat = jnp.where(valid, idx + offset, k * nbins)  # invalid → overflow lane
    counts = jax.ops.segment_sum(
        jnp.ones(flat.size, jnp.float32), flat.reshape(-1), num_segments=k * nbins + 1
    )
    return counts[: k * nbins].reshape(k, nbins)


def compare_digitize(X: jax.Array, interior: jax.Array) -> jax.Array:
    """Bin ids by counting interior cutoffs strictly below each value —
    identical to searchsorted(side='left') (right-closed bins) but a dense
    compare+reduce instead of a per-element binary search, which lowers to
    slow serialized code on TPU (measured ~10× slower)."""
    return (X[:, :, None] > interior[None, :, :]).sum(axis=2).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("nbins",))
def _binned_histograms_xla(X: jax.Array, M: jax.Array, cutoffs: jax.Array, nbins: int) -> jax.Array:
    bins = compare_digitize(X, cutoffs)
    return _flat_counts(bins, M, nbins)


@timed("ops.binned_histograms")
def binned_histograms(X: jax.Array, M: jax.Array, cutoffs: jax.Array, nbins: int) -> jax.Array:
    """Numeric columns → per-column bin frequencies in one program.

    X/M: (rows, k); cutoffs: (k, nbins-1) interior edges.
    Returns (k, nbins) counts (valid entries only).
    ``ANOVOS_USE_PALLAS=1`` swaps in the hand-scheduled Pallas kernel
    (ops/pallas_kernels.py).  The backend choice happens OUTSIDE jit so the
    env var is honored per call, not baked into a compile cache.
    """
    from anovos_tpu.ops.pallas_kernels import binned_histograms_pallas, use_pallas

    if use_pallas():
        return binned_histograms_pallas(X, M, cutoffs, nbins)
    return _binned_histograms_xla(X, M, cutoffs, nbins)


@functools.partial(jax.jit, static_argnames=("nbins",))
def code_histograms(C: jax.Array, M: jax.Array, nbins: int) -> jax.Array:
    """Categorical code columns → per-column counts in one program.

    C: (rows, k) int32 union-vocab codes (−1 null); M: (rows, k).
    Returns (k, nbins) counts.
    """
    return _flat_counts(jnp.maximum(C, 0), M & (C >= 0), nbins)


@timed("ops.drift_side_histograms")
@functools.partial(jax.jit, static_argnames=("nbins", "n_cat_bins"))
def drift_side_histograms(
    X: jax.Array,
    Mx: jax.Array,
    cutoffs: jax.Array,
    C: jax.Array,
    Mc: jax.Array,
    nbins: int,
    n_cat_bins: int,
) -> Tuple[jax.Array, jax.Array]:
    """One dataset side, everything fused: numeric + categorical histograms."""
    return (
        binned_histograms(X, Mx, cutoffs, nbins),
        code_histograms(C, Mc, n_cat_bins),
    )


@timed("ops.drift_side_full")
@functools.partial(jax.jit, static_argnames=("nbins", "n_cat_bins"))
def drift_side_full(
    num_datas: Tuple[jax.Array, ...],
    num_masks: Tuple[jax.Array, ...],
    cutoffs: jax.Array,
    cat_datas: Tuple[jax.Array, ...],
    cat_masks: Tuple[jax.Array, ...],
    lut: jax.Array,
    nbins: int,
    n_cat_bins: int,
) -> Tuple[jax.Array, jax.Array]:
    """ONE program for a whole dataset side, straight from raw column arrays:
    stack+cast numeric, stack+vocab-remap categorical, both histogram
    families.  Exactly one device dispatch per side."""
    with jax.named_scope("drift/side_histograms"):
        return _side_histograms(num_datas, num_masks, cutoffs, cat_datas, cat_masks, lut, nbins, n_cat_bins)


def _side_histograms(num_datas, num_masks, cutoffs, cat_datas, cat_masks, lut, nbins: int, n_cat_bins: int):
    if num_datas:
        X = jnp.stack([d.astype(jnp.float32) for d in num_datas], axis=1)
        Mx = jnp.stack(num_masks, axis=1)
        num_h = binned_histograms(X, Mx, cutoffs, nbins)
    else:
        num_h = jnp.zeros((0, nbins), jnp.float32)
    if cat_datas:
        C = jnp.stack(cat_datas, axis=1)
        Mc = jnp.stack(cat_masks, axis=1)
        # histogram-then-permute: counting over each column's LOCAL codes is
        # a cheap compare-and-reduce, and the union-vocab remap then acts on
        # the tiny (k, maxv) count matrix via the one-hot'd LUT — identical
        # result to remapping every row first, without the (rows, k) device
        # gather that dominated the side program (~3/4 of its wall time)
        local_h = code_histograms(C, Mc, lut.shape[1])
        k = local_h.shape[0]
        # scatter-add on the (k, maxv) count matrix — O(k·maxv) work and no
        # (k, maxv, u) intermediate, which would go quadratic in cardinality
        cat_h = jnp.zeros((k, n_cat_bins), jnp.float32).at[
            jnp.arange(k, dtype=jnp.int32)[:, None], lut
        ].add(local_h)
    else:
        cat_h = jnp.zeros((0, n_cat_bins), jnp.float32)
    return num_h, cat_h


@functools.partial(jax.jit, static_argnames=("nbins", "method"))
def fit_cutoffs(
    num_datas: Tuple[jax.Array, ...],
    num_masks: Tuple[jax.Array, ...],
    nbins: int,
    method: str = "equal_range",
) -> jax.Array:
    """Interior bin cutoffs (k, nbins-1) fitted in one program."""
    with jax.named_scope("drift/fit_cutoffs"):
        return _fit_cutoffs(num_datas, num_masks, nbins, method)


def _fit_cutoffs(num_datas, num_masks, nbins: int, method: str) -> jax.Array:
    X = jnp.stack([d.astype(jnp.float32) for d in num_datas], axis=1)
    M = jnp.stack(num_masks, axis=1)
    if method == "equal_frequency":
        from anovos_tpu.ops.quantiles import masked_quantiles

        qs = jnp.array([j / nbins for j in range(1, nbins)], jnp.float32)
        return masked_quantiles(X, M, qs, interpolation="lower").T
    return _equal_range_cuts(*_bounds(X, M), nbins)


def _bounds(X: jax.Array, M: jax.Array):
    """Smallest value, largest value and count of every column of a masked block."""
    big = jnp.asarray(jnp.finfo(jnp.float32).max, jnp.float32)
    return jnp.where(M, X, big).min(axis=0), jnp.where(M, X, -big).max(axis=0), M.sum(axis=0)


@jax.jit
def fit_bounds(num_datas: Tuple[jax.Array, ...], num_masks: Tuple[jax.Array, ...]):
    """Smallest value, largest value and count of every column, one program:
    what the equal_range cutoffs are made of (:func:`cutoffs_from_bounds`)."""
    with jax.named_scope("drift/fit_cutoffs"):
        return _bounds(jnp.stack([d.astype(jnp.float32) for d in num_datas], axis=1), jnp.stack(num_masks, axis=1))


def _equal_range_cuts(lo: jax.Array, hi: jax.Array, n: jax.Array,
                      nbins: int) -> jax.Array:
    """The equal_range cutoff arithmetic of ``fit_cutoffs`` in f32 on the
    device (the report's charts bin with it; the drift model takes
    :func:`cutoffs_from_bounds`)."""
    width = (hi - lo) / nbins
    cuts = lo[:, None] + jnp.arange(1, nbins, dtype=jnp.float32)[None, :] * width[:, None]
    return jnp.where(n[:, None] > 0, cuts, jnp.nan)


def cutoffs_from_bounds(lo, hi, n, nbins: int) -> np.ndarray:
    """Interior equal_range cutoffs ``(k, nbins-1)`` from per-column bounds,
    in float64 on the host as the upstream computes them (``min + j * ((max -
    min) / bins)``; NaN where ``n`` is 0): ``lo`` / ``hi`` are a side's f32
    smallest and largest value (exact under any merge order), so the
    in-memory fit, the streamed fit and the continuum's land on the same
    model to the bit.  The f32 arithmetic this replaces put a cut-off a unit
    of the last place to either side of a value that lies ON it (a whole
    number, a rate to two decimals), and a bin's worth of rows with it."""
    lo, hi = np.asarray(lo, np.float64), np.asarray(hi, np.float64)
    width = (hi - lo) / nbins
    cuts = lo[:, None] + np.arange(1, nbins, dtype=np.float64)[None, :] * width[:, None]
    return np.where(np.asarray(n)[:, None] > 0, cuts, np.nan)


def device_cutoffs(cuts) -> np.ndarray:
    """float64 cutoffs as the f32 the device compares with: each rounded
    DOWN, so that ``x > cut`` decides for every f32 ``x`` what it decides in
    float64 (no f32 lies between a cut-off and the f32 under it)."""
    cuts = np.asarray(cuts, np.float64)
    near = cuts.astype(np.float32)
    with np.errstate(invalid="ignore"):
        return np.where(near.astype(np.float64) > cuts, np.nextafter(near, np.float32(-np.inf)), near).astype(np.float32)
