"""Guarded bf16 mixed-precision sweep (``ANOVOS_TPU_BF16``, ops/mxu.py).

The sweep routes the pre-centered MXU matmuls (correlation, covariance,
PCA) through bf16 inputs + f32 accumulation; artifacts then change within
the tolerance bands pinned here.  Distance expansions are the PERF.md
corruption class and must stay true-f32 NO MATTER WHAT the knob says —
also pinned here (byte-identical under the knob).
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp


@pytest.fixture
def bf16_env(monkeypatch):
    monkeypatch.setenv("ANOVOS_TPU_BF16", "1")


def _block(rows=4096, k=6, seed=0):
    g = np.random.default_rng(seed)
    # include the documented hard case: a large-offset low-spread column
    # (the raw-magnitude cancellation class) — pre-centering is what makes
    # the bf16 route safe there
    cols = [g.normal(2015.0, 3.0, rows)]
    for i in range(1, k):
        cols.append(g.normal(i * 10.0, 1.0 + i, rows))
    X = jnp.asarray(np.stack(cols, 1), jnp.float32)
    M = jnp.asarray(g.random((rows, k)) > 0.08)
    return X, M


def test_knob_default_off():
    from anovos_tpu.ops.mxu import bf16_sweep

    assert os.environ.get("ANOVOS_TPU_BF16", "0") != "1"
    assert bf16_sweep() is False


def test_knobs_registered_in_fingerprint():
    from anovos_tpu.cache.fingerprint import KNOWN_ENV_KNOBS

    assert "ANOVOS_TPU_BF16" in KNOWN_ENV_KNOBS


def test_corr_bf16_within_band(bf16_env):
    """Pairwise-complete Pearson r under bf16 inputs: |Δr| ≤ 0.02
    everywhere (pre-centered magnitudes are spread-scale, so bf16's 8-bit
    mantissa costs a bounded perturbation, not a cancellation blowup)."""
    from anovos_tpu.ops.correlation import _masked_corr, masked_corr

    X, M = _block()
    ref = np.asarray(_masked_corr(X, M, bf16=False))
    out = np.asarray(masked_corr(X, M))  # env-routed: bf16 on
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out, ref, atol=2e-2)
    # diagonal stays exactly 1 (pinned by the kernel, not the matmul)
    np.testing.assert_array_equal(np.diag(out), np.ones(X.shape[1]))


def test_cov_bf16_within_band(bf16_env):
    from anovos_tpu.ops.correlation import _masked_cov, masked_cov

    X, M = _block(seed=1)
    ref = np.asarray(_masked_cov(X, M, bf16=False))
    out = np.asarray(masked_cov(X, M))
    # relative band on the diagonal (variances), absolute-vs-scale off it
    scale = np.sqrt(np.outer(np.diag(ref), np.diag(ref)))
    np.testing.assert_allclose(out, ref, atol=2e-2 * float(scale.max()))
    np.testing.assert_allclose(np.diag(out), np.diag(ref), rtol=2e-2)


def test_pca_bf16_subspace_band(bf16_env, monkeypatch, tmp_path):
    """PCA under the sweep: same component count, loadings aligned with
    the f32 ones up to sign (|cos| ≥ 0.99 per component on a spectrum with
    well-separated eigenvalues)."""
    import pandas as pd

    from anovos_tpu.data_transformer.latent_features import PCA_latentFeatures
    from anovos_tpu.shared.table import Table

    g = np.random.default_rng(2)
    base = g.normal(size=(3000, 3))
    df = pd.DataFrame({
        "a": 5.0 * base[:, 0],
        "b": 2.0 * base[:, 1] + 0.3 * base[:, 0],
        "c": 1.0 * base[:, 2],
        "d": 0.5 * base[:, 0] + 0.2 * base[:, 2],
    })
    t = Table.from_pandas(df)

    def latents(env_val):
        monkeypatch.setenv("ANOVOS_TPU_BF16", env_val)
        out = PCA_latentFeatures(t, "all", explained_variance_cutoff=0.95,
                                 output_mode="append")
        lat = [c for c in out.col_names if c.startswith("latent_")]
        Z = np.stack([np.asarray(out.columns[c].data)[: out.nrows] for c in lat], 1)
        return Z

    Z32 = latents("0")
    Zbf = latents("1")
    assert Z32.shape == Zbf.shape  # same chosen k
    for i in range(Z32.shape[1]):
        a, b = Z32[:, i], Zbf[:, i]
        cos = abs(float(a @ b) / max(np.linalg.norm(a) * np.linalg.norm(b), 1e-30))
        assert cos >= 0.99, f"component {i} rotated under bf16: |cos|={cos:.4f}"


def test_distance_expansions_unaffected_by_knob(bf16_env):
    """The corruption-class guard: pairwise distances and neighbor counts
    are BYTE-identical with the sweep on — the knob must never reach the
    quadratic expansion kernels."""
    from anovos_tpu.ops.cluster import neighbor_counts, pairwise_d2

    g = np.random.default_rng(3)
    X = np.asarray(g.uniform(-50, 50, (2048, 2)), np.float32)
    d2_on = np.asarray(pairwise_d2(jnp.asarray(X)))
    nc_on = neighbor_counts(X, 0.5)
    os.environ["ANOVOS_TPU_BF16"] = "0"
    try:
        d2_off = np.asarray(pairwise_d2(jnp.asarray(X)))
        nc_off = neighbor_counts(X, 0.5)
    finally:
        os.environ["ANOVOS_TPU_BF16"] = "1"  # fixture restores on teardown
    np.testing.assert_array_equal(d2_on, d2_off)
    np.testing.assert_array_equal(nc_on, nc_off)


def test_mm_helper_routes(bf16_env):
    from anovos_tpu.ops.mxu import mm

    a = jnp.asarray(np.random.default_rng(4).normal(size=(64, 8)), jnp.float32)
    b = a.T
    exact = np.asarray(mm(a, b, False))
    routed = np.asarray(mm(a, b, True))
    assert routed.dtype == np.float32  # f32 accumulation output
    assert not np.array_equal(exact, routed)  # the cast is real
    # bf16 input rounding is ~2^-8 relative per product; near-cancelling
    # off-diagonal sums need an absolute band at the product scale
    np.testing.assert_allclose(routed, exact, rtol=2e-2, atol=1e-1)
