"""Autoencoder + PCA latent feature tests."""

import numpy as np
import pandas as pd
import pytest

from anovos_tpu.data_transformer.latent_features import (
    PCA_latentFeatures,
    autoencoder_latentFeatures,
)
from anovos_tpu.models.autoencoder import AutoEncoder
from anovos_tpu.shared.table import Table
import jax.numpy as jnp


@pytest.fixture(scope="module")
def latent_df():
    """4 observed columns driven by 2 latent factors."""
    g = np.random.default_rng(21)
    n = 2000
    f1, f2 = g.normal(size=n), g.normal(size=n)
    return pd.DataFrame(
        {
            "a": f1 + 0.05 * g.normal(size=n),
            "b": -f1 + 0.05 * g.normal(size=n),
            "c": f2 + 0.05 * g.normal(size=n),
            "d": f2 + f1 + 0.05 * g.normal(size=n),
        }
    )


def test_autoencoder_trains_and_reconstructs(latent_df):
    t = Table.from_pandas(latent_df)
    ae = AutoEncoder(4, 2)
    from anovos_tpu.data_transformer.latent_features import _prep_block

    X, _, _ = _prep_block(t, ["a", "b", "c", "d"], True, True)
    Xr = X[: t.nrows]
    params = ae.fit(Xr, epochs=100, batch_size=256)  # reference default epochs
    mse = float(jnp.mean((ae.reconstruct(params, Xr) - Xr) ** 2))
    assert mse < 0.1  # 2 latent dims explain 4 correlated columns


def test_autoencoder_bf16_parity(latent_df):
    """The bf16-input / f32-accumulate matmul path (the TPU MXU recipe) must
    train to the same quality as pure f32 and reconstruct within bf16's
    representational tolerance (~8 mantissa bits → ~0.4% relative)."""
    t = Table.from_pandas(latent_df)
    from anovos_tpu.data_transformer.latent_features import _prep_block

    X, _, _ = _prep_block(t, ["a", "b", "c", "d"], True, True)
    Xr = X[: t.nrows]
    losses, recons = {}, {}
    for mode in ("f32", "bf16"):
        ae = AutoEncoder(4, 2, compute_dtype=mode)
        params = ae.fit(Xr, epochs=40, batch_size=256)
        recon = ae.reconstruct(params, Xr)
        losses[mode] = float(jnp.mean((recon - Xr) ** 2))
        recons[mode] = recon
    # both converge, and to comparable reconstruction quality
    assert losses["f32"] < 0.2 and losses["bf16"] < 0.2
    assert abs(losses["bf16"] - losses["f32"]) < 0.05
    # master weights stay f32 in both modes
    ae = AutoEncoder(4, 2, compute_dtype="bf16")
    p = ae.init_params()
    assert p["enc1"]["w"].dtype == jnp.float32
    # a single forward at identical params differs only by bf16 rounding
    xh_f32 = AutoEncoder(4, 2, compute_dtype="f32").reconstruct(p, Xr[:256])
    xh_bf16 = ae.reconstruct(p, Xr[:256])
    assert xh_bf16.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(xh_bf16), np.asarray(xh_f32), atol=0.1, rtol=0.05
    )


def test_autoencoder_latentFeatures_transformer(latent_df):
    t = Table.from_pandas(latent_df)
    out = autoencoder_latentFeatures(t, reduction_params=0.5, epochs=20, output_mode="replace")
    df = out.to_pandas()
    assert {"latent_0", "latent_1"} <= set(df.columns)
    assert "a" not in df.columns
    assert not df["latent_0"].isna().any()


def test_autoencoder_model_roundtrip(latent_df, tmp_path):
    t = Table.from_pandas(latent_df)
    mp = str(tmp_path / "ae")
    a = autoencoder_latentFeatures(t, epochs=5, model_path=mp, output_mode="append").to_pandas()
    b = autoencoder_latentFeatures(
        t, pre_existing_model=True, model_path=mp, output_mode="append"
    ).to_pandas()
    np.testing.assert_allclose(a["latent_0"].to_numpy(), b["latent_0"].to_numpy(), atol=1e-5)


def test_pca_latentFeatures(latent_df):
    t = Table.from_pandas(latent_df)
    out = PCA_latentFeatures(t, explained_variance_cutoff=0.95, output_mode="replace")
    df = out.to_pandas()
    latents = [c for c in df.columns if c.startswith("latent_")]
    # 2 factors dominate → ≤3 components reach 95%
    assert 2 <= len(latents) <= 3
    v = df[latents].var()
    assert v.iloc[0] >= v.iloc[-1]  # components ordered by variance


def test_pca_matches_float64_reference(latent_df):
    """PCA_latentFeatures against float64 numpy (centre, covariance over
    n-1, eigh, descending), compared free of each eigenvector's sign: the
    chosen k exactly, the kept spectrum relatively, |latent column|
    absolutely.  Read on this fixture: 1.5e-7 and 9.5e-7 (values up to 6.7,
    kept eigenvalues 3.51 and 1.32, the next 0.0025); held to 1e-3."""
    cutoff = 0.95
    out = PCA_latentFeatures(Table.from_pandas(latent_df), explained_variance_cutoff=cutoff)
    got = out.to_pandas()
    latents = [c for c in got.columns if c.startswith("latent_")]

    X = latent_df.to_numpy().astype(np.float32).astype(np.float64)
    Xc = X - X.mean(axis=0)
    w, v = np.linalg.eigh(Xc.T @ Xc / (len(X) - 1))
    order = np.argsort(w)[::-1]
    w, v = w[order], v[:, order]
    k = int(np.searchsorted(np.cumsum(w) / w.sum(), cutoff) + 1)

    assert latents == [f"latent_{i}" for i in range(k)]
    Z = got[latents].to_numpy().astype(np.float64)
    np.testing.assert_allclose(Z.var(axis=0, ddof=1), w[:k], rtol=1e-3)
    np.testing.assert_allclose(np.abs(Z), np.abs(Xc @ v[:, :k]), atol=1e-3)


def test_pca_model_roundtrip(latent_df, tmp_path):
    t = Table.from_pandas(latent_df)
    mp = str(tmp_path / "pca")
    a = PCA_latentFeatures(t, model_path=mp, output_mode="append").to_pandas()
    b = PCA_latentFeatures(t, pre_existing_model=True, model_path=mp, output_mode="append").to_pandas()
    np.testing.assert_allclose(a["latent_0"].to_numpy(), b["latent_0"].to_numpy(), atol=1e-4)
