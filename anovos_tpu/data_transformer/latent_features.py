"""Latent-feature transformers (reference transformers.py:2524-3168).

``autoencoder_latentFeatures``: the north-star item — the reference trains a
Keras AE on a ≤500k pandas sample and applies it via pandas_udf
(ref :2783-2892); here the AE (models/autoencoder.py) trains as a jitted
optax loop on the device-resident standardized block and the encoder applies
as one forward pass.  ``PCA_latentFeatures``: Spark ML PCA → device SVD with
the same explained-variance-cutoff k selection (ref :3121-3137).
"""

from __future__ import annotations

import functools
import logging

import os
import warnings
from collections import OrderedDict
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd

from anovos_tpu.data_transformer.model_io import load_model_df, save_model_df
from anovos_tpu.models.autoencoder import AutoEncoder
from anovos_tpu.obs import get_tracer
from anovos_tpu.ops.mxu import bf16_sweep, mm
from anovos_tpu.ops.reductions import masked_moments
from anovos_tpu.shared.runtime import get_runtime
from anovos_tpu.shared.table import Column, Table
from anovos_tpu.shared.utils import parse_cols

logger = logging.getLogger(__name__)


def _prep_block(idf: Table, cols: List[str], standardization: bool, imputation: bool):
    """Common preamble (reference :2560-2780): impute missing with median,
    z-standardize.  Returns (X, stats) with X fully dense.

    pad_cols=False: the block width IS the autoencoder's input dimension —
    bucketed dead lanes would change the model architecture (and the
    persisted weights), not just the batch shape."""
    X, M = idf.numeric_block(cols, pad_cols=False)
    mom = masked_moments(X, M)
    if imputation:
        from anovos_tpu.ops.quantiles import masked_median

        fill = masked_median(X, M)
    else:
        fill = mom["mean"]
    Xd, mean, std = _prep_dense(X, M, mom["mean"], mom["stddev"], fill, standardization)
    return Xd, mean, std


@functools.partial(jax.jit, static_argnames=("standardization",))
def _prep_dense(X, M, mean, stddev, fill, standardization):
    """Fused dense-fill + standardize (the eager where/affine chain here
    compiled one program per step per AE width — cold-compile census)."""
    std = jnp.where(stddev > 0, stddev, 1.0)
    Xd = jnp.where(M, X, fill[None, :])
    if standardization:
        Xd = (Xd - mean[None, :]) / std[None, :]
    return Xd, mean, std


def autoencoder_latentFeatures(
    idf: Table,
    list_of_cols="all",
    drop_cols=[],
    reduction_params: float = 0.5,
    sample_size: int = 500000,
    epochs: int = 100,
    batch_size: int = 256,
    pre_existing_model: bool = False,
    model_path: str = "NA",
    standardization: bool = True,
    standardization_configs: dict = {},
    imputation: bool = True,
    imputation_configs: dict = {},
    output_mode: str = "replace",
    print_impact: bool = False,
    **_ignored,
) -> Table:
    """Append/replace with ``latent_<i>`` encoder outputs.

    ``reduction_params`` < 1 → bottleneck = round(r·n_cols); ≥ 1 → exact k
    (reference :2640-2651).  Training runs on device over the full (or
    ``sample_size``-capped) standardized block.
    """
    num_all, _, _ = idf.attribute_type_segregation()
    cols = parse_cols(list_of_cols if list_of_cols != "all" else num_all, idf.col_names, drop_cols)
    cols = [c for c in cols if c in num_all]
    if len(cols) < 2:
        warnings.warn("No Autoencoder Computation - need ≥2 numerical columns")
        return idf
    n = len(cols)
    k = int(round(reduction_params * n)) if reduction_params < 1 else int(reduction_params)
    k = max(1, min(k, n))
    # stage rows of the node; each ends on the completion of what it
    # dispatched, so its seconds are the device's work and not the enqueueing
    tracer = get_tracer()
    with tracer.phase("ae/prep", rows=idf.padded_rows, cols=n):
        X, _, _ = jax.block_until_ready(_prep_block(idf, cols, standardization, imputation))

    if pre_existing_model:
        ae, params = AutoEncoder.load(model_path)
    else:
        n_fit = min(idf.nrows, sample_size)
        split = int(n_fit * 0.8)
        batch = int(min(batch_size, max(split, 1)))
        ae = AutoEncoder(n, k)
        # a multiply-add is two operations, forward and backward are three products a matrix
        with tracer.phase("ae/fit", steps=int(epochs) * max(split // batch, 1), epochs=int(epochs), batch=batch,
                          fit_rows=split, val_rows=n_fit - split, params=ae.n_trainable,
                          flops_per_step=6 * batch * ae.n_weights, bf16=int(ae.compute_dtype is not None)):
            Xfit = X[: idf.nrows][:n_fit]
            # fit() fetches the history when the last step is done: the barrier
            params = ae.fit(
                Xfit[:split],
                epochs=int(epochs),
                batch_size=batch,
                validation_X=Xfit[split:] if split < n_fit else None,
                verbose=print_impact,
            )

    with tracer.phase("ae/apply", rows=idf.padded_rows, cols=n, latent=ae.n_bottleneck):
        Z = jax.block_until_ready(ae.latent_columns(params, X))  # k arrays of padded_rows
        in_range = jnp.arange(idf.padded_rows) < idf.nrows
        odf = idf.with_columns(
            (f"latent_{i}", Column("num", z, in_range, dtype_name="float")) for i, z in enumerate(Z))
        if output_mode == "replace":
            odf = odf.drop(cols)
    if not pre_existing_model and model_path != "NA":
        with tracer.phase("ae/save"):
            ae.save(params, model_path)
    if print_impact:
        logger.info(f"autoencoder latent features: {ae.n_bottleneck} from {n} columns")
    return odf


@jax.jit
def _pca_center(X, nrows):
    """Row-masked centering alone (the pre_existing_model scoring path —
    no spectrum needed)."""
    rowmask = (jnp.arange(X.shape[0]) < nrows)[:, None]
    return jnp.where(rowmask, X - X.mean(axis=0, where=rowmask), 0.0)


@functools.partial(jax.jit, static_argnames=("bf16",))
def _pca_cov_eig(X, nrows, bf16: bool = False):
    """Fused PCA spectrum: row-masked centering + covariance + eigh +
    descending reorder in ONE program.  The covariance matmul is
    pre-centered, so it qualifies for the guarded bf16 sweep (ops/mxu.py);
    eigh itself always runs f32."""
    rowmask = (jnp.arange(X.shape[0]) < nrows)[:, None]
    Xc = jnp.where(rowmask, X - X.mean(axis=0, where=rowmask), 0.0)
    cov = mm(Xc.T, Xc, bf16) / jnp.maximum(nrows - 1, 1)
    eigval, eigvec = jnp.linalg.eigh(cov)
    order = jnp.argsort(eigval)[::-1]
    return Xc, eigval[order], eigvec[:, order]


@functools.partial(jax.jit, static_argnames=("bf16",))
def _pca_project(Xc, V, nrows, bf16: bool = False):
    """Fused projection + row-validity iota (one program per component
    count instead of a matmul + per-column slice/iota chain)."""
    return mm(Xc, V, bf16), jnp.arange(Xc.shape[0]) < nrows


def PCA_latentFeatures(
    idf: Table,
    list_of_cols="all",
    drop_cols=[],
    explained_variance_cutoff: float = 0.95,
    pre_existing_model: bool = False,
    model_path: str = "NA",
    standardization: bool = False,
    standardization_configs: dict = {},
    imputation: bool = False,
    imputation_configs: dict = {},
    output_mode: str = "replace",
    print_impact: bool = False,
    **_ignored,
) -> Table:
    """PCA with k = smallest component count reaching the explained-variance
    cutoff (reference :2915-3168).  SVD runs on device; components persist as
    parquet [attribute, loadings…]."""
    num_all, _, _ = idf.attribute_type_segregation()
    cols = parse_cols(list_of_cols if list_of_cols != "all" else num_all, idf.col_names, drop_cols)
    cols = [c for c in cols if c in num_all]
    if len(cols) < 2:
        warnings.warn("No PCA Computation - need ≥2 numerical columns")
        return idf
    X, mean, std = _prep_block(idf, cols, standardization, imputation=True)
    if pre_existing_model:
        # scoring path: the spectrum comes from the saved model — run
        # the centering-only program, not the cov+eigh it would discard
        Xc = _pca_center(X, np.int32(idf.nrows))
    else:
        # whole-chain program: centering + covariance + eigh + descending
        # reorder lowered as ONE compiled program.  Xc stays a device
        # handle for projection.
        Xc, eigval, eigvec = _pca_cov_eig(
            X, np.int32(idf.nrows), bf16=bf16_sweep())

    if pre_existing_model:
        dfm = load_model_df(model_path, "PCA_latentFeatures")
        comp = np.stack([np.asarray(r, dtype=np.float32) for r in dfm["loadings"]])
        saved_cols = list(dfm["attribute"]) if "attribute" in dfm else cols
        k = comp.shape[0]
        V = jnp.asarray(comp.T)
    else:
        # k selection on host from the (k,)-small spectrum
        ev_h = np.asarray(eigval)
        ratio = np.cumsum(ev_h) / max(float(ev_h.sum()), 1e-30)
        k = int(np.searchsorted(ratio, explained_variance_cutoff) + 1)
        k = max(1, min(k, len(cols)))
        V = eigvec[:, :k]
        if model_path != "NA":
            save_model_df(
                pd.DataFrame(
                    {
                        "component": [f"latent_{i}" for i in range(k)],
                        "loadings": [np.asarray(V[:, i], dtype=float).tolist() for i in range(k)],
                    }
                ),
                model_path,
                "PCA_latentFeatures",
            )
    # one projection program over the k-sliced V: (padded_rows, k)
    Z, in_range = _pca_project(Xc, V, np.int32(idf.nrows),
                               bf16=bf16_sweep())
    odf = idf
    for i in range(int(Z.shape[1])):
        odf = odf.with_column(
            f"latent_{i}", Column("num", Z[:, i].astype(jnp.float32), in_range, dtype_name="float")
        )
    if output_mode == "replace":
        odf = odf.drop(cols)
    if print_impact:
        logger.info(f"PCA latent features: {int(Z.shape[1])} components (cutoff {explained_variance_cutoff})")
    return odf
