"""Pallas kernel parity (interpret mode — logic verified without TPU)."""

import numpy as np
import pytest

import jax.numpy as jnp


def test_pallas_histogram_parity():
    from anovos_tpu.ops.drift_kernels import binned_histograms
    from anovos_tpu.ops.pallas_kernels import binned_histograms_pallas

    g = np.random.default_rng(0)
    rows, k, nbins = 5000, 6, 10
    X = jnp.asarray(g.normal(50, 20, (rows, k)), jnp.float32)
    M = jnp.asarray(g.random((rows, k)) > 0.1)
    cuts = jnp.asarray(np.sort(g.normal(50, 20, (k, nbins - 1)), axis=1), jnp.float32)
    ref = np.asarray(binned_histograms(X, M, cuts, nbins))
    out = np.asarray(binned_histograms_pallas(X, M, cuts, nbins, interpret=True))
    np.testing.assert_allclose(out, ref)
    assert out.sum() == np.asarray(M).sum()


def test_pallas_neighbor_counts_parity():
    """DBSCAN neighbor-count kernel == the XLA tiled pass, in interpret
    mode, across non-tile-multiple row counts and eps scales (incl. the
    all-isolated and the everything-connected regimes)."""
    from anovos_tpu.ops.cluster import neighbor_counts
    from anovos_tpu.ops.pallas_kernels import neighbor_counts_pallas

    import jax

    g = np.random.default_rng(3)
    centers = g.uniform(-40, 40, size=(4, 2))
    for n, eps in [(3000, 0.4), (1024, 0.05), (1500, 50.0), (257, 0.3)]:
        X = (centers[g.integers(0, 4, n)] + g.normal(0, 0.3, (n, 2))).astype(np.float32)
        Xc = X - X.mean(axis=0, keepdims=True)
        ref = neighbor_counts(X, eps)
        out = np.asarray(neighbor_counts_pallas(
            jnp.asarray(Xc), jnp.asarray(eps * eps, jnp.float32), interpret=True))
        np.testing.assert_array_equal(out, ref)
        assert out.min() >= 1  # every point neighbors itself


def test_moments_pallas_matches_xla_interpret():
    """Single-pass Chan-merge moments kernel == two-pass XLA kernel,
    including a large-mean column that would cancel under raw power sums."""
    import numpy as np
    import jax.numpy as jnp

    from anovos_tpu.ops.pallas_kernels import moments_pallas
    from anovos_tpu.ops.reductions import finalize_moments, masked_moments

    rng = np.random.default_rng(0)
    X = jnp.asarray(
        np.stack([rng.normal(1e5, 3.0, 60000), rng.exponential(5, 60000)], 1).astype(np.float32)
    )
    M = jnp.asarray(rng.random((60000, 2)) > 0.1)
    acc = moments_pallas(X, M, interpret=True)
    got = finalize_moments(acc[0], acc[0] * acc[1], acc[2], acc[3], acc[4], acc[5], acc[6], acc[7])
    exp = masked_moments(X, M)
    for k in ("count", "mean", "stddev", "min", "max", "nonzero"):
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(exp[k]), rtol=5e-3, atol=1e-3)
    for k in ("skewness", "kurtosis"):  # f32 sampling noise scale for shape stats
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(exp[k]), rtol=2e-2, atol=2e-2)


def test_use_pallas_raises_off_tpu_and_refuses_neighbor_kernel(monkeypatch):
    """ANOVOS_USE_PALLAS=1 on a backend other than TPU is an error, not a
    warning and a quiet XLA run; the DBSCAN neighbor kernel, which the
    chip's compiler does not accept at pipeline sizes, is refused by name."""
    from anovos_tpu.ops.pallas_kernels import use_pallas

    monkeypatch.delenv("ANOVOS_USE_PALLAS", raising=False)
    assert use_pallas() is False and use_pallas("neighbor_counts") is False
    monkeypatch.setenv("ANOVOS_USE_PALLAS", "1")
    with pytest.raises(RuntimeError, match="TPU-only"):
        use_pallas()
    with pytest.raises(RuntimeError, match="compiler"):
        use_pallas("neighbor_counts")
