"""Pearson correlation / covariance via MXU matmuls.

Replaces ``pyspark.ml.stat.Correlation.corr`` (association_evaluator.py:122)
and MLlib ``RowMatrix.computeCovariance`` (association_eval_varclus.py:83).
Pairwise-complete masked statistics are expressed entirely as X.T @ X-shaped
products so the whole computation lands on the systolic array; row-sharded
inputs psum-merge the partial products.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from anovos_tpu.obs import timed
from anovos_tpu.ops.mxu import bf16_sweep, mm
from anovos_tpu.ops.reductions import masked_mean


@timed("ops.masked_corr")
def masked_corr(X: jax.Array, M: jax.Array) -> jax.Array:
    """Pairwise-complete Pearson correlation matrix.

    X: (rows, k); M: (rows, k) bool.  Returns (k, k).
    For each pair (a,b) all sums run over rows where BOTH are valid — five
    matmuls total, all MXU-shaped.  The matmuls are pre-centered, so they
    qualify for the guarded bf16 sweep (``ANOVOS_TPU_BF16=1``, ops/mxu.py
    — read here, outside jit, per call); default is true-f32.
    """
    return _masked_corr(X, M, bf16=bf16_sweep())


@functools.partial(jax.jit, static_argnames=("bf16",))
def _masked_corr(X: jax.Array, M: jax.Array, bf16: bool = False) -> jax.Array:
    dt = jnp.float32
    Mf = M.astype(dt)
    Xf = X.astype(dt)
    # pre-center each column by its global masked mean: pairwise-complete
    # Pearson r is exactly translation-invariant, and without the shift the
    # n·Sxy − Sx·Sy cancellation loses most f32 bits for large-offset
    # low-spread columns (a year column came back with r off by 0.06).
    # The centering is also what makes the bf16 route SAFE: post-shift
    # magnitudes are spread-scale, so bf16 input rounding is a bounded
    # relative perturbation instead of a cancellation amplifier.
    Xm = jnp.where(M, Xf - masked_mean(Xf, M)[None, :], 0.0)
    X2m = Xm * Xm
    n = mm(Mf.T, Mf, bf16)              # pairwise counts
    Sx = mm(Xm.T, Mf, bf16)             # Sx[a,b] = Σ x_a over both-valid rows
    Sxx = mm(X2m.T, Mf, bf16)
    Sxy = mm(Xm.T, Xm, bf16)
    Sy = Sx.T
    Syy = Sxx.T
    cov_n = n * Sxy - Sx * Sy
    var_a = n * Sxx - Sx * Sx
    var_b = n * Syy - Sy * Sy
    # the roots apart: the product of two sums of squares of 10^6-sized values over 10^4 rows is past f32
    denom = jnp.sqrt(jnp.maximum(var_a, 0.0)) * jnp.sqrt(jnp.maximum(var_b, 0.0))
    corr = jnp.where(denom > 0, cov_n / jnp.maximum(denom, 1e-30), jnp.nan)
    k = X.shape[1]
    return jnp.where(jnp.eye(k, dtype=bool), 1.0, corr)


@timed("ops.masked_cov")
def masked_cov(X: jax.Array, M: jax.Array) -> jax.Array:
    """Pairwise-complete sample covariance matrix (n-1 normalization),
    matching RowMatrix.computeCovariance on complete data.  Pre-centered →
    eligible for the guarded bf16 sweep (ops/mxu.py), like masked_corr."""
    return _masked_cov(X, M, bf16=bf16_sweep())


@functools.partial(jax.jit, static_argnames=("bf16",))
def _masked_cov(X: jax.Array, M: jax.Array, bf16: bool = False) -> jax.Array:
    dt = jnp.float32
    Mf = M.astype(dt)
    Xf = X.astype(dt)
    # same pre-centering as masked_corr: covariance is translation-invariant
    # and the Sxy − SxSy/n cancellation is catastrophic at raw magnitudes
    Xm = jnp.where(M, Xf - masked_mean(Xf, M)[None, :], 0.0)
    n = mm(Mf.T, Mf, bf16)
    Sx = mm(Xm.T, Mf, bf16)
    Sxy = mm(Xm.T, Xm, bf16)
    mean_prod = Sx * Sx.T / jnp.maximum(n, 1.0)
    return jnp.where(n > 1, (Sxy - mean_prod) / jnp.maximum(n - 1.0, 1.0), jnp.nan)


@functools.partial(jax.jit, static_argnames=("bf16",))
def _masked_corr_cc(X: jax.Array, M: jax.Array, k_live: jax.Array,
                    bf16: bool = False) -> jax.Array:
    """Complete-case Pearson correlation over the LIVE lanes of a
    column-bucketed block (``k_live`` of them, a device scalar so that the
    program stays keyed on the bucketed shape): every pair over the rows
    valid in ALL live lanes.  Every pair has the same rows, so the columns
    are centred and brought to unit length once and ONE product, of the
    complete rows gathered first, gives the matrix: no sum of squares of a
    raw magnitude is multiplied by another (two columns of 10^6 over 10^4
    rows overflowed f32 in the pairwise form's denominator).  A column
    constant over the complete rows has no correlation (NaN); the diagonal
    is 1."""
    dt = jnp.float32
    row_ok = (M.sum(axis=1) == k_live)[:, None]
    Mc = M & row_ok
    Xf = X.astype(dt)
    Xc = jnp.where(Mc, Xf - masked_mean(Xf, Mc)[None, :], 0.0)
    big = jnp.asarray(jnp.finfo(dt).max, dt)
    varies = jnp.max(jnp.where(Mc, Xf, -big), axis=0) > jnp.min(jnp.where(Mc, Xf, big), axis=0)
    Z = Xc * jax.lax.rsqrt(jnp.maximum((Xc * Xc).sum(axis=0), jnp.finfo(dt).tiny))[None, :]
    # the complete rows first, the zero rows after them: the MXU adds the row tiles of a contraction into its
    # f32 accumulator rounding down, so 10^4 live rows among 3 x 10^5 zero rows read 1e-5 low in their worst
    # pair at any precision and the same rows side by side 1e-6 (measured on a v5e: PERF.md section 6, PR 46)
    Z = jnp.take(Z, jnp.argsort(~row_ok[:, 0]), axis=0)
    k = Z.shape[1]
    G = mm(Z.T, Z, bf16)
    d = jnp.sqrt(jnp.diagonal(G))  # 1 but for the rounding of the scaling, which this takes out again
    corr = jnp.where(varies[:, None] & varies[None, :], G / jnp.maximum(d[:, None] * d[None, :], 1e-30), jnp.nan)
    return jnp.where(jnp.eye(k, dtype=bool), 1.0, corr)
