"""Sort-based segment machinery: distinct counts, mode, group-by counts.

Replaces Spark's shuffle-based groupBy (stats_generator.py:386-401 mode loop;
:605-612 countDistinct/HLL).  Keys on device are int32 codes (categoricals)
or raw numerics; a device sort turns equal keys into contiguous segments and
transition-counting / bincount does the rest.  Static shapes throughout —
"mask-don't-shrink" (SURVEY.md §7 hard part 2).
"""

from __future__ import annotations

import functools
import os
from typing import Tuple

import jax
import jax.numpy as jnp

from anovos_tpu.shared.runtime import column_parallel, wants_column_parallel
from anovos_tpu.obs import timed


@timed("ops.masked_nunique")
def masked_nunique(X: jax.Array, M: jax.Array) -> jax.Array:
    """Exact distinct count per column (valid entries only).

    X: (rows, k) — any numeric (cat codes included); M: (rows, k) bool.
    Sort each column with invalid → +inf, count value transitions among the
    first n valid slots.  On a multi-device mesh the sort runs
    column-parallel (runtime.column_parallel).
    """
    return _masked_nunique(X, M, cp=wants_column_parallel(X, M))


@functools.partial(jax.jit, static_argnames=("cp",))
def _masked_nunique(X: jax.Array, M: jax.Array, cp: bool = False) -> jax.Array:
    dt = jnp.float32 if X.dtype not in (jnp.float32, jnp.float64) else X.dtype
    big = jnp.asarray(jnp.finfo(dt).max, dt)
    Xs = jnp.sort(column_parallel(jnp.where(M, X.astype(dt), big), cp), axis=0)
    n = M.sum(axis=0)  # (k,)
    rows = X.shape[0]
    pos = jnp.arange(rows)[:, None]
    valid = pos < n[None, :]
    trans = jnp.concatenate(
        [jnp.ones((1, X.shape[1]), bool), Xs[1:] != Xs[:-1]], axis=0
    )
    return (trans & valid).sum(axis=0)


def _bucket_segments(n: int) -> int:
    """Static segment counts round up to 16^k size classes (min 16) up to
    65,536, and to 2^k classes above: every vocab size in a table then
    reuses ONE compiled program per row shape — unbucketed, a 19-column
    describe compiled code_counts 16 times on identical array shapes, a
    fresh XLA compile each.
    Power-of-SIXTEEN (coarser than describe_cat's dense-sweep pow-4
    buckets, which pay O(rows·k·vocab) per lane and must stay fine):
    segment_sum cost is rows-driven and the outputs are (vocab,)-scale
    vectors, so the coarse classes {16, 256, 4096, 65536} trade idle
    output lanes for a near-minimal distinct-program count across a run's
    vocab-size spread (cold-compile census).
    Above 65,536 the next 16^k classes are 1,048,576 and 16,777,216: a
    vocabulary of 65,537 values would pad to sixteen times its size, and
    every count vector fetched and every LUT uploaded is that padded vector
    (4 bytes a lane, up to 64 MB an array, per column per call).  There the
    padded dimension is memory-proportional, which is what
    ``bucket_segments_pow2`` is for: at most twice the vocabulary, and at
    most eight more classes up to 2^24 (hashed ids of 10^5–10^7 values: a
    click log's categoricals)."""
    if n > 65536:
        return bucket_segments_pow2(n)
    b = 16
    while b < max(n, 1):
        b *= 16
    return b


def bucket_segments_pow2(n: int) -> int:
    """2^k size classes (min 8) — for consumers whose PADDED dimension is
    memory-proportional (a (k, maxv) LUT matrix, a (k, nseg) aggregate
    table): waste stays ≤2× where the coarse 4^k/16^k classes could cost
    16× real bytes."""
    return max(8, 1 << (max(n, 1) - 1).bit_length())


def segment_class(n: int) -> int:
    """The static segment count a (k, nseg) aggregate program is compiled
    for: ``bucket_segments_pow2`` unless ``ANOVOS_SHAPE_BUCKETS=0`` asks for
    exact shapes."""
    return bucket_segments_pow2(n) if os.environ.get("ANOVOS_SHAPE_BUCKETS", "1") != "0" else n


@jax.jit
def cat_valid_mask(codes: jax.Array, M: jax.Array) -> jax.Array:
    """mask & (code >= 0) — THE categorical null rule as one shared
    program for every stacking call site (stats mask prep, varclus,
    large-cat describe)."""
    return M & (codes >= 0)


@functools.partial(jax.jit, static_argnames=("vocab_size",))
def _code_counts_p(codes: jax.Array, M: jax.Array, vocab_size: int) -> jax.Array:
    valid = M & (codes >= 0)
    safe = jnp.where(valid, codes, 0)
    return jax.ops.segment_sum(
        valid.astype(jnp.float32), safe, num_segments=vocab_size
    )


@timed("ops.code_counts")
def code_counts(codes: jax.Array, M: jax.Array, vocab_size: int) -> jax.Array:
    """Frequency of each dictionary code for ONE categorical column.

    codes: (rows,) int32 with -1 for null; M: (rows,) bool.
    Returns counts PADDED to the ``_bucket_segments`` size class
    ({16, 256, 4096, …} ≥ vocab_size) — trailing lanes are zero.  Callers
    slice ``[:vocab_size]`` after host materialization: an on-device slice
    here compiled one dynamic_slice program per vocab size, re-creating
    exactly the per-shape compile tail the segment-class bucketing removes
    (PERF.md cold-compile census)."""
    return _code_counts_p(codes, M, _bucket_segments(vocab_size))


@functools.partial(jax.jit, static_argnames=("vocab_size",))
def _code_label_counts_p(
    codes: jax.Array, M: jax.Array, y: jax.Array, vocab_size: int
) -> jax.Array:
    valid = M & (codes >= 0)
    safe = jnp.where(valid, codes, 0)
    return jax.ops.segment_sum(
        jnp.where(valid, y, 0.0).astype(jnp.float32), safe, num_segments=vocab_size
    )


@timed("ops.code_label_counts")
def code_label_counts(
    codes: jax.Array, M: jax.Array, y: jax.Array, vocab_size: int
) -> jax.Array:
    """Per-code sum of a row weight/label (event counts for IV, target
    encoding).  Returns counts PADDED to the ``_bucket_segments`` class
    (trailing lanes zero) — same host-slice contract as
    :func:`code_counts`."""
    return _code_label_counts_p(codes, M, y, _bucket_segments(vocab_size))


@jax.jit
def _lut_gather(lut: jax.Array, codes: jax.Array) -> jax.Array:
    return lut[jnp.clip(codes, 0, lut.shape[0] - 1)]


@timed("ops.vocab_lookup")
def vocab_lookup(lut_host, codes: jax.Array) -> jax.Array:
    """Per-code lookup through a small host-built table.

    The LUT is padded to its ``_bucket_segments`` class so every vocab size shares one
    compiled gather per row shape (eagerly indexing ``jnp.asarray(lut)[codes]``
    per column compiled ~70 distinct gather programs across an e2e run).
    Codes are clipped; callers keep their own null/validity masking."""
    import numpy as np

    lut_host = np.asarray(lut_host)
    p = _bucket_segments(len(lut_host))
    if p > len(lut_host):
        lut_host = np.concatenate([lut_host, np.zeros(p - len(lut_host), lut_host.dtype)])
    return _lut_gather(jnp.asarray(lut_host), codes)


def mode_from_counts(counts: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(argmax code, count) from a (vocab,) count vector; ties → lowest code
    (Spark's groupBy().orderBy(desc).limit(1) is nondeterministic on ties;
    we pin the deterministic choice)."""
    return jnp.argmax(counts), counts.max()


@jax.jit
def row_signature(Xcodes: jax.Array, M: jax.Array) -> jax.Array:
    """64-bit-ish hash per row over all columns (two f32 lanes) for duplicate
    detection (quality_checker.py:49 dedup).  Null hashes as a distinct
    sentinel.  Collision-checked host-side at stage boundary."""
    k = Xcodes.shape[1]
    vals = jnp.where(M, Xcodes, -2).astype(jnp.uint32)
    h1 = jnp.zeros(Xcodes.shape[0], jnp.uint32)
    h2 = jnp.zeros(Xcodes.shape[0], jnp.uint32)
    for j in range(k):  # unrolled — k is static and small
        h1 = (h1 * jnp.uint32(1000003)) ^ (vals[:, j] + jnp.uint32(0x9E3779B9))
        h2 = (h2 * jnp.uint32(69069)) ^ (vals[:, j] * jnp.uint32(2654435761) + jnp.uint32(j + 1))
    return jnp.stack([h1, h2], axis=1)  # (rows, 2) uint32 — x64-free 64-bit key
