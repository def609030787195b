"""Pallas TPU kernels for the hot histogram path — **EXPERIMENTAL**.

``binned_histograms_pallas`` fuses binning + counting for the drift/report
pipeline into a hand-scheduled kernel: the row dimension streams through
VMEM in tiles (grid), each tile does the compare-count binning and the
lane-compare histogram entirely on the VPU, and the (k, nbins) accumulator
lives in the output block across grid steps (initialized on the first step).
Functionally identical to ops/drift_kernels.binned_histograms.

Status (PERF.md "Pallas status"): the kernels are parity-verified in
interpret mode (tests/test_pallas_kernels.py); ``moments_pallas`` and
``binned_histograms_pallas`` compile for v5e at 4 M x 16
(tests/test_chip_compile.py), ``neighbor_counts_pallas`` does not at
pipeline sizes.  None has been timed on the chip, so there is no measured
XLA-vs-Pallas comparison and **no performance claim**.  The XLA versions
are the default; ``ANOVOS_USE_PALLAS=1`` opts in on TPU and raises
elsewhere.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_TILE_ROWS = 2048


def _hist_kernel(x_ref, m_ref, cut_ref, out_ref):
    """One row tile: bin via compare-count, histogram via lane compare,
    accumulate into the shared output block."""
    i = pl.program_id(0)
    x = x_ref[:]  # (TILE, k)
    m = m_ref[:] != 0  # (TILE, k)
    cuts = cut_ref[:]  # (nbins-1, k)
    nbins = out_ref.shape[0]
    # Everything stays 2-D (TILE, k), one static loop step per cutoff / bin:
    # Mosaic refuses the (TILE, k, 1) broadcast of an i1 vector that a 3-D
    # compare-against-lanes needs, and a (TILE, k, nbins) block pads its
    # 10-wide minor axis to 128 lanes.
    # bin id = number of interior cutoffs strictly below the value
    bins = jnp.zeros(x.shape, jnp.int32)
    for j in range(nbins - 1):
        bins = bins + (x > cuts[j][None, :]).astype(jnp.int32)
    tile_counts = jnp.stack(
        [jnp.where(m & (bins == b), 1.0, 0.0).sum(axis=0) for b in range(nbins)]
    )  # (nbins, k)

    @pl.when(i == 0)
    def _init():
        out_ref[:] = tile_counts

    @pl.when(i > 0)
    def _acc():
        out_ref[:] = out_ref[:] + tile_counts


@functools.partial(jax.jit, static_argnames=("nbins", "interpret"))
def binned_histograms_pallas(
    X: jax.Array, M: jax.Array, cutoffs: jax.Array, nbins: int, interpret: bool = False
) -> jax.Array:
    """Fused bin+count histogram: X/M (rows, k), cutoffs (k, nbins-1) →
    (k, nbins) float32 counts.  rows are padded to the tile size with
    mask=False lanes."""
    rows, k = X.shape
    pad = (-rows) % _TILE_ROWS
    if pad:
        X = jnp.concatenate([X, jnp.zeros((pad, k), X.dtype)])
        M = jnp.concatenate([M, jnp.zeros((pad, k), bool)])
    grid = (X.shape[0] // _TILE_ROWS,)
    return pl.pallas_call(
        _hist_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((_TILE_ROWS, k), lambda i: (i, 0)),
            pl.BlockSpec((_TILE_ROWS, k), lambda i: (i, 0)),
            pl.BlockSpec((cutoffs.shape[1], k), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((nbins, k), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((nbins, k), jnp.float32),
        interpret=interpret,
    )(X.astype(jnp.float32), M, cutoffs.astype(jnp.float32).T).T


def _moments_kernel(x_ref, m_ref, out_ref):
    """One row tile → Chan-merge into the running (8, k) accumulator:
    rows of the accumulator are [n, mean, M2, M3, M4, min, max, nonzero].

    A naive raw-power-sum single pass cancels catastrophically in f32 for
    columns with large means; per-tile central moments merged pairwise keep
    the error O(log tiles) — same policy as ops/streaming."""
    i = pl.program_id(0)
    x = x_ref[:].astype(jnp.float32)  # (TILE, k)
    m = m_ref[:] != 0
    big = jnp.float32(3.4e38)
    n_t = m.sum(axis=0).astype(jnp.float32)
    safe = jnp.maximum(n_t, 1.0)
    mean_t = jnp.where(m, x, 0).sum(axis=0) / safe
    d = jnp.where(m, x - mean_t, 0)
    d2 = d * d
    M2_t = d2.sum(axis=0)
    M3_t = (d2 * d).sum(axis=0)
    M4_t = (d2 * d2).sum(axis=0)
    min_t = jnp.where(m, x, big).min(axis=0)
    max_t = jnp.where(m, x, -big).max(axis=0)
    nz_t = (m & (x != 0)).sum(axis=0).astype(jnp.float32)
    tile = jnp.stack([n_t, mean_t, M2_t, M3_t, M4_t, min_t, max_t, nz_t])

    @pl.when(i == 0)
    def _init():
        out_ref[:] = tile

    @pl.when(i > 0)
    def _merge():
        acc = out_ref[:]
        na, nb = acc[0], n_t
        n = na + nb
        s = jnp.maximum(n, 1.0)
        delta = mean_t - acc[1]
        mean = acc[1] + delta * nb / s
        M2 = acc[2] + M2_t + delta**2 * na * nb / s
        M3 = (
            acc[3] + M3_t
            + delta**3 * na * nb * (na - nb) / (s * s)
            + 3 * delta * (na * M2_t - nb * acc[2]) / s
        )
        M4 = (
            acc[4] + M4_t
            + delta**4 * na * nb * (na * na - na * nb + nb * nb) / (s * s * s)
            + 6 * delta**2 * (na * na * M2_t + nb * nb * acc[2]) / (s * s)
            + 4 * delta * (na * M3_t - nb * acc[3]) / s
        )
        out_ref[:] = jnp.stack(
            [n, mean, M2, M3, M4,
             jnp.minimum(acc[5], min_t), jnp.maximum(acc[6], max_t), acc[7] + nz_t]
        )


@functools.partial(jax.jit, static_argnames=("interpret",))
def moments_pallas(X: jax.Array, M: jax.Array, interpret: bool = False) -> jax.Array:
    """Fused single-pass masked moments: X/M (rows, k) → (8, k) float32
    accumulator [n, mean, M2, M3, M4, min, max, nonzero].  Finalize with
    ops/reductions.finalize_moments (s1 = n·mean)."""
    rows, k = X.shape
    pad = (-rows) % _TILE_ROWS
    if pad:
        X = jnp.concatenate([X, jnp.zeros((pad, k), X.dtype)])
        M = jnp.concatenate([M, jnp.zeros((pad, k), bool)])
    grid = (X.shape[0] // _TILE_ROWS,)
    return pl.pallas_call(
        _moments_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((_TILE_ROWS, k), lambda i: (i, 0)),
            pl.BlockSpec((_TILE_ROWS, k), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((8, k), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, k), jnp.float32),
        interpret=interpret,
    )(X.astype(jnp.float32), M)


def _neighbor_count_kernel(xq_ref, xs_ref, eps2_ref, out_ref):
    """One query tile vs the FULL point set: the (TILE, n) squared-distance
    block never leaves VMEM — quadratic expansion on the MXU, compare +
    lane-reduce on the VPU, only the (TILE,) counts are written back.

    Distances stay f32 end-to-end: the MXU's bf16-input default is exactly
    the corruption class PERF.md documents for quadratic expansions, so the
    matmul pins HIGHEST precision like the XLA twin (_neighbor_counts_tile).
    """
    xq = xq_ref[:]  # (TILE, d)
    xs = xs_ref[:]  # (n_pad, d)
    eps2 = eps2_ref[0]
    d2 = (
        (xq * xq).sum(axis=1, keepdims=True)
        - 2.0 * jax.lax.dot_general(
            xq, xs, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
        )
        + (xs * xs).sum(axis=1)[None, :]
    )  # (TILE, n_pad)
    # padding rows of the SOURCE set sit at 1e9 per lane — squared distance
    # ≥ 1e18 ≫ any real eps², so they can never count as neighbors
    out_ref[:] = (d2 <= eps2).sum(axis=1).astype(jnp.int32)


_NC_TILE = 1024


@functools.partial(jax.jit, static_argnames=("interpret",))
def neighbor_counts_pallas(X: jax.Array, eps2: jax.Array, interpret: bool = False) -> jax.Array:
    """Fused DBSCAN neighbor-count pass: X (n, d) centered points →
    (n,) int32 within-eps neighbor counts (incl. self).

    The XLA path (ops/cluster.neighbor_counts) dispatches one tiled
    distance program per 4096-row block and materializes each (tile, n)
    distance matrix in HBM; here the row dimension streams through VMEM in
    tiles (grid) with the distance block kept on-chip — the second of the
    two profiled non-XLA-friendly loops (ROADMAP item 5; the many-bucket
    histogram was the first).  Parity-verified in interpret mode
    (tests/test_pallas_kernels.py) only: Mosaic does not accept it at
    pipeline sizes (the untiled source axis — ``use_pallas`` refuses it;
    PERF.md "Pallas status", ROADMAP C3)."""
    n, d = X.shape
    pad = (-n) % _NC_TILE
    Xq = X.astype(jnp.float32)
    if pad:
        # query padding at 1e9: the padded rows' counts are discarded by the
        # caller's [:n] slice; as SOURCE rows they are masked by distance
        Xq = jnp.concatenate([Xq, jnp.full((pad, d), 1e9, jnp.float32)])
    grid = (Xq.shape[0] // _NC_TILE,)
    out = pl.pallas_call(
        _neighbor_count_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((_NC_TILE, d), lambda i: (i, 0)),
            pl.BlockSpec((Xq.shape[0], d), lambda i: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((_NC_TILE,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((Xq.shape[0],), jnp.int32),
        interpret=interpret,
    )(Xq, Xq, jnp.asarray(eps2, jnp.float32).reshape(1))
    return out[:n]


def use_pallas(kernel: str = "") -> bool:
    """True iff ``ANOVOS_USE_PALLAS=1`` asks for the Pallas kernels.  They
    are Mosaic kernels: asked for on any backend but TPU it raises instead
    of quietly giving way to XLA.  ``kernel="neighbor_counts"`` is refused
    outright (see :func:`neighbor_counts_pallas`)."""
    if os.environ.get("ANOVOS_USE_PALLAS", "0") != "1":
        return False
    if kernel == "neighbor_counts":
        raise RuntimeError(
            "ANOVOS_USE_PALLAS=1: the chip's compiler does not accept "
            "neighbor_counts_pallas at pipeline sizes (the whole point set "
            "and a (1024, n) distance block must fit fast memory: 66 s to "
            "compile at n=8,192 for v5e, no result after 5 min at 32,768; "
            "the geospatial block sends up to 100,000) — unset it for runs "
            "with a geospatial block")
    if jax.default_backend() != "tpu":
        raise RuntimeError(
            "ANOVOS_USE_PALLAS=1 but the backend is "
            f"{jax.default_backend()!r}: compiled pallas_call is TPU-only "
            "(interpret mode exists for the tests, not for pipeline runs)")
    return True
