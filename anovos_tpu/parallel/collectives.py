"""Explicit-collective kernels via shard_map.

The framework's default is GSPMD: kernels are global array programs and XLA
inserts the psum/all-gathers (SURVEY.md §2.10).  This module holds the
manually-scheduled counterpart — shard_map bodies with explicit ``psum``
over the data axis — for the cases where hand placement matters (e.g.
pinning the reduction order, or fusing many per-shard steps before one
collective).  ``masked_moments_shmap`` returns the same key set as the
GSPMD kernel (shared finalizer) and is tested for exact agreement.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from anovos_tpu.ops.reductions import finalize_moments
from anovos_tpu.shared.runtime import DATA_AXIS


@functools.lru_cache(maxsize=8)
def _moments_shmap_fn(mesh: Mesh):
    """Per-mesh cached jitted shard_map program (a fresh closure per call
    would defeat the jit cache and recompile every invocation)."""

    def body(x, m):
        mf = m.astype(jnp.float32)
        # pass 1: one psum for the stacked count/sum partials → global mean
        n, s1 = jax.lax.psum(
            jnp.stack([mf.sum(axis=0), jnp.where(m, x, 0).sum(axis=0)]), DATA_AXIS
        )
        mean = s1 / jnp.maximum(n, 1.0)
        # pass 2: one fused psum for all centered power sums + nonzero
        d = jnp.where(m, x - mean, 0)
        d2 = d * d
        nz = (m & (x != 0)).sum(axis=0).astype(jnp.float32)
        m2, m3, m4, nonzero = jax.lax.psum(
            jnp.stack([d2.sum(axis=0), (d2 * d).sum(axis=0), (d2 * d2).sum(axis=0), nz]),
            DATA_AXIS,
        )
        big = jnp.asarray(jnp.finfo(jnp.float32).max, jnp.float32)
        cmin = jax.lax.pmin(jnp.where(m, x, big).min(axis=0), DATA_AXIS)
        cmax = jax.lax.pmax(jnp.where(m, x, -big).max(axis=0), DATA_AXIS)
        return n, s1, m2, m3, m4, cmin, cmax, nonzero

    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS, None)),
        out_specs=(P(),) * 8,
    )
    return jax.jit(fn)


def masked_moments_shmap(X: jax.Array, M: jax.Array, mesh: Mesh) -> Dict[str, jax.Array]:
    """Two-pass masked moments with explicit psums over the 'data' axis.
    Key-compatible with ops.reductions.masked_moments."""
    n, s1, m2, m3, m4, cmin, cmax, nonzero = _moments_shmap_fn(mesh)(X.astype(jnp.float32), M)
    return finalize_moments(n, s1, m2, m3, m4, cmin, cmax, nonzero)
