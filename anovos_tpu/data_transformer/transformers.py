"""Tabular transformers (reference: data_transformer/transformers.py:7-24).

Each function keeps the reference's signature surface (list_of_cols/drop_cols,
``output_mode`` replace/append with per-function postfix, ``pre_existing_model``
+ ``model_path`` persistence) but runs as jitted device kernels on the sharded
Table: the per-row ``bucket_label`` UDF (ref :248-280) becomes a batched
``searchsorted``; Spark ML Imputer/StringIndexer/MinMaxScaler become masked
reductions + dictionary-code gathers; the boxcox λ search is a vectorized KS
kernel over the λ grid.
"""

from __future__ import annotations

import contextlib
import logging
import functools
import math
import os
import warnings
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd

from anovos_tpu.data_transformer.model_io import load_model_df, save_model_df
from anovos_tpu.ops.histogram import digitize, masked_bincount
from anovos_tpu.ops.quantiles import masked_quantiles
from anovos_tpu.ops.reductions import masked_moments
from anovos_tpu.obs import get_tracer
from anovos_tpu.ops.segment import (
    _bucket_segments, code_counts, code_label_counts, masked_nunique, on_one_device, segment_routes, vocab_lookup)
from anovos_tpu.shared.runtime import get_runtime
from anovos_tpu.shared.table import Column, Table, pad_lane_params
from anovos_tpu.shared.utils import parse_cols

logger = logging.getLogger(__name__)


# ---------------------------------------------------------------------------
# fused apply programs: each transformer's glue chain — digitize/cast,
# affine scale, elementwise math + finite-mask, per-column impute fills —
# lowered as ONE program over the padded (rows, k_pad) block.
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("scope",))
def _bin_apply_program(X, edges, scope: Optional[str] = None):
    """digitize + the 1-based int cast in one program: (bins0, bins1), under
    the ``jax.named_scope`` the caller names, if it names one."""
    with jax.named_scope(scope) if scope else contextlib.nullcontext():
        bins0 = digitize(X, edges)
        return bins0, (bins0 + 1).astype(jnp.int32)


@jax.jit
def _affine_scale_program(X, center, scale):
    """(X − center) / scale over the padded block (IQR/z-scaling apply)."""
    return (X - center[None, :]) / scale[None, :]


@functools.partial(jax.jit, static_argnames=("method", "n"))
def _mathop_apply_program(X, M, method: str, n=None):
    """fn(X) + finite-mask + zero-fill in one program (feature_transformation)."""
    fn = _MATH_OPS[method] if n is None else (lambda x: _MATH_OPS_N[method](x, n))
    Y = fn(X)
    ok = M & jnp.isfinite(Y)
    return jnp.where(ok, Y, 0.0).astype(jnp.float32), ok


@jax.jit
def _impute_num_program(data, mask, fill):
    """where(mask, x, fill) as f32 — the numeric MMM fill."""
    return jnp.where(mask, data.astype(jnp.float32), fill)


@jax.jit
def _impute_num_int_program(data, mask, fill):
    """Integer-column MMM fill with an integral value: the int cast stays
    INSIDE the program (an eager astype after the fused fill re-added the
    per-column convert dispatch this layer exists to remove), and it is the
    fill that is cast: a value that is there stays the int32 it is, also
    beyond 2^24 where a pass through f32 would round it."""
    return jnp.where(mask, data, fill.astype(jnp.int32))


@jax.jit
def _row_valid_program(mask, nrows):
    """(padded,) bool row-validity iota — one shared program instead of a
    per-call eager ones/iota/and chain."""
    return jnp.arange(mask.shape[0]) < nrows


@jax.jit
def _impute_cat_program(data, mask, code, nrows):
    """(filled codes, full-validity mask) for the categorical MMM fill."""
    valid = mask & (data >= 0)
    rv = jnp.arange(data.shape[0]) < nrows
    return jnp.where(valid, data, code).astype(jnp.int32), rv


@jax.jit
def _label_encode_program(lut, data, mask):
    """vocab-LUT gather + null fold + validity in one program
    (cat_to_num_unsupervised label encoding)."""
    idx = jnp.where(data >= 0, lut[jnp.clip(data, 0, lut.shape[0] - 1)], -1)
    valid = mask & (idx >= 0)
    return jnp.where(valid, idx, 0).astype(jnp.int32), valid


@jax.jit
def _event_vector_cat_program(data, code):
    return (data == code).astype(jnp.float32)


@jax.jit
def _event_vector_num_program(data, value):
    return (data.astype(jnp.float32) == value).astype(jnp.float32)

__all__ = [
    "attribute_binning",
    "monotonic_binning",
    "cat_to_num_transformer",
    "cat_to_num_unsupervised",
    "cat_to_num_supervised",
    "z_standardization",
    "IQR_standardization",
    "normalization",
    "imputation_MMM",
    "imputation_sklearn",
    "imputation_matrixFactorization",
    "auto_imputation",
    "feature_transformation",
    "boxcox_transformation",
    "outlier_categories",
    "expression_parser",
    "autoencoder_latentFeatures",
    "PCA_latentFeatures",
    # serving-state export (anovos_tpu.serving rides these)
    "SERVABLE_TRANSFORMERS",
    "FittedTransformer",
    "fitted_state",
    "from_state",
]


def _num_cols_of(idf: Table, list_of_cols, drop_cols, extra_drop: Sequence[str] = ()):
    num_all, _, _ = idf.attribute_type_segregation()
    cols = parse_cols(list_of_cols if list_of_cols != "all" else num_all, idf.col_names, drop_cols)
    cols = [c for c in cols if c not in set(extra_drop)]
    bad = [c for c in cols if c not in num_all]
    if bad:
        raise TypeError(f"Invalid input for Column(s): non-numerical {bad}")
    return cols


def _cat_cols_of(idf: Table, list_of_cols, drop_cols, extra_drop: Sequence[str] = ()):
    _, cat_all, _ = idf.attribute_type_segregation()
    cols = parse_cols(list_of_cols if list_of_cols != "all" else cat_all, idf.col_names, drop_cols)
    cols = [c for c in cols if c not in set(extra_drop)]
    bad = [c for c in cols if c not in cat_all]
    if bad:
        raise TypeError(f"Invalid input for Column(s): non-categorical {bad}")
    return cols


def _emit(idf: Table, new_cols: "OrderedDict[str, Column]", output_mode: str, postfix: str) -> Table:
    """Apply the universal output_mode convention: replace in place or append
    with postfix (reference convention, e.g. transformers.py:281-286)."""
    odf = idf
    for name, col in new_cols.items():
        odf = odf.with_column(name if output_mode == "replace" else name + postfix, col)
    return odf


# ----------------------------------------------------------------------
# binning
# ----------------------------------------------------------------------
def binning_cutoffs(X, M, k: int, method_type: str, bin_size: int, scope: Optional[str] = None) -> np.ndarray:
    """(k, bin_size - 1) interior cut-offs of the ``k`` live lanes of a numeric
    block, on the host; NaN for a lane without a value.  equal_frequency: the
    lower order statistic at j / bin_size of the values present; equal_range:
    min + j (max - min) / bin_size.  The block-level step of
    ``attribute_binning``, which ``association_evaluator`` takes too, so that
    a measure's bins are the transformer's (``scope``: see
    ``masked_quantiles``; the sketch and equal_range's moments carry none)."""
    if method_type not in ("equal_frequency", "equal_range"):
        raise TypeError("Invalid input for method_type")
    if method_type == "equal_frequency":
        qs = jnp.array([j / bin_size for j in range(1, bin_size)], jnp.float32)
        # exact sort quantiles up to ~64M cells; beyond that the sort's
        # O(rows·k) temp buffers crowd HBM → histogram sketch (O(k·nbins)
        # state, error ≤ range/2048 — the approxQuantile analogue)
        if X.size > int(os.environ.get("ANOVOS_EXACT_QUANTILE_CELLS", 64_000_000)):
            from anovos_tpu.ops.quantiles import histogram_quantiles

            return np.asarray(histogram_quantiles(X, M, qs))[:, :k].T.astype(np.float64)
        # (k, B-1) — sliced to the live k of the column-bucketed block
        return np.asarray(masked_quantiles(X, M, qs, interpolation="lower", scope=scope))[:, :k].T
    mom = masked_moments(X, M)
    lo = np.asarray(mom["min"], dtype=np.float64)[:k]
    hi = np.asarray(mom["max"], dtype=np.float64)[:k]
    return lo[:, None] + np.arange(1, bin_size)[None, :] * ((hi - lo) / bin_size)[:, None]


def bin_block(X, cutoffs: np.ndarray, scope: Optional[str] = None):
    """(0-based, 1-based) bin of every cell of a numeric block whose live
    lanes have these interior ``cutoffs``: the number of cut-offs below the
    value (value <= cut-off stays under it)."""
    # digitize expects (k, nb+1) edges with sentinels; interior cutoffs only
    # matter.  Edges are padded to the bucketed lane count (dead-lane bins
    # are never read — every consumer indexes the live lanes).
    k = len(cutoffs)
    edges = np.concatenate([np.full((k, 1), -np.inf), cutoffs, np.full((k, 1), np.inf)], axis=1)
    # digitize (0-indexed) + 1-based cast in one program; the host edge
    # array rides in through the jit boundary (no convert program)
    return _bin_apply_program(X, pad_lane_params(edges, X.shape[1]).astype(np.float32), scope=scope)


def attribute_binning(
    idf: Table,
    list_of_cols="all",
    drop_cols=[],
    method_type: str = "equal_range",
    bin_size: int = 10,
    bin_dtype: str = "numerical",
    pre_existing_model: bool = False,
    model_path: str = "NA",
    output_mode: str = "replace",
    print_impact: bool = False,
) -> Table:
    """Bucket numeric columns into ``bin_size`` bins (reference :87-291).

    equal_range: interior cutoffs at min + j·(max−min)/B; equal_frequency:
    exact quantiles at j/B (the approxQuantile call site, ref :210-215).
    Bin ids are 1..B via value ≤ cutoff (batched searchsorted — the Python
    ``bucket_label`` UDF collapsed into one kernel).  Model artifact:
    parquet [attribute, parameters=interior cutoffs] (ref :241-246).
    """
    if method_type not in ("equal_frequency", "equal_range"):
        raise TypeError("Invalid input for method_type")
    if bin_size < 2:
        raise TypeError("Invalid input for bin_size")
    if output_mode not in ("replace", "append"):
        raise TypeError("Invalid input for output_mode")
    cols = _num_cols_of(idf, list_of_cols, drop_cols)
    if not cols:
        warnings.warn("No Binning Computation - No numerical column(s) to transform")
        return idf

    if pre_existing_model:
        dfm = load_model_df(model_path, "attribute_binning")
        cut_map = {r["attribute"]: list(r["parameters"]) for _, r in dfm.iterrows()}
        cols = [c for c in cols if c in cut_map]
        cutoffs = np.array([cut_map[c] for c in cols], dtype=np.float64)
    else:
        X, M = idf.numeric_block(cols)
        cutoffs = binning_cutoffs(X, M, len(cols), method_type, bin_size)
        keep = ~np.isnan(cutoffs[:, 0])
        if method_type == "equal_range" and not keep.all():
            dropped = [c for c, k in zip(cols, keep) if not k]
            warnings.warn("Columns contains too much null values. Dropping " + ", ".join(dropped))
            cols = [c for c, k in zip(cols, keep) if k]
            cutoffs = cutoffs[keep]
        if model_path != "NA":
            save_model_df(
                pd.DataFrame({"attribute": cols, "parameters": [list(map(float, c)) for c in cutoffs]}),
                model_path,
                "attribute_binning",
            )
    if not cols:
        return idf

    X, _ = idf.numeric_block(cols)
    nb = cutoffs.shape[1] + 1
    bins0, bins1 = bin_block(X, cutoffs)
    new_cols: "OrderedDict[str, Column]" = OrderedDict()
    if bin_dtype == "numerical":
        for i, c in enumerate(cols):
            new_cols[c] = Column("num", bins1[:, i], idf.columns[c].mask, dtype_name="int")
    else:
        bins_host = np.asarray(bins0)
        for i, c in enumerate(cols):
            cuts = cutoffs[i]
            labels = []
            for b in range(nb):
                if b == 0:
                    labels.append("<= " + str(round(float(cuts[0]), 4)))
                elif b == nb - 1:
                    labels.append("> " + str(round(float(cuts[-1]), 4)))
                else:
                    labels.append(str(round(float(cuts[b - 1]), 4)) + "-" + str(round(float(cuts[b]), 4)))
            new_cols[c] = Column(
                "cat",
                bins0[:, i].astype(jnp.int32),
                idf.columns[c].mask,
                vocab=np.array(labels, dtype=object),
                dtype_name="string",
            )
    odf = _emit(idf, new_cols, output_mode, "_binned")
    if print_impact:
        from anovos_tpu.data_analyzer.stats_generator import uniqueCount_computation

        out = cols if output_mode == "replace" else [c + "_binned" for c in cols]
        logger.info(uniqueCount_computation(odf, out).to_string(index=False))
    return odf


def monotonic_binning(
    idf: Table,
    list_of_cols="all",
    drop_cols=[],
    label_col: str = "label",
    event_label=1,
    bin_method: str = "equal_range",
    bin_size: int = 10,
    bin_dtype: str = "numerical",
    output_mode: str = "replace",
) -> Table:
    """Search n=20→3 for a bin count whose (bin mean value, bin event rate)
    relationship is perfectly monotonic by Spearman ρ = ±1; fall back to
    ``bin_size`` (reference :294-426)."""
    from scipy import stats as sps

    cols = _num_cols_of(idf, list_of_cols, drop_cols, extra_drop=[label_col])
    y, ym = _event_vector(idf, label_col, event_label)
    odf = idf
    for c in cols:
        chosen = bin_size
        X, M = idf.numeric_block([c])
        x, m = X[:, 0], M[:, 0]
        for n in range(20, 2, -1):
            binned = attribute_binning(
                idf.select([c]), [c], [], method_type=bin_method, bin_size=n, output_mode="append"
            )
            bcol = binned[c + "_binned"]
            bidx = jnp.where(bcol.mask, bcol.data - 1, 0).astype(jnp.int32)
            bm = bcol.mask
            # per-bin: row count, value sum, labeled-row count, event sum
            cnt = np.asarray(jax.ops.segment_sum(bm.astype(jnp.float32), bidx, num_segments=n))
            vals = np.asarray(jax.ops.segment_sum(jnp.where(bm, x, 0.0), bidx, num_segments=n))
            lblcnt = np.asarray(jax.ops.segment_sum((bm & ym).astype(jnp.float32), bidx, num_segments=n))
            evs = np.asarray(jax.ops.segment_sum(jnp.where(bm & ym, y, 0.0), bidx, num_segments=n))
            ok = (cnt > 0) & (lblcnt > 0)
            if ok.sum() < 2:
                continue
            mean_val = vals[ok] / cnt[ok]
            mean_label = evs[ok] / lblcnt[ok]
            r, _ = sps.spearmanr(mean_val, mean_label)
            if abs(r) == 1.0:
                chosen = n
                break
        odf = attribute_binning(
            odf, [c], [], method_type=bin_method, bin_size=chosen,
            bin_dtype=bin_dtype, output_mode=output_mode,
        )
    return odf


# ----------------------------------------------------------------------
# categorical encoding
# ----------------------------------------------------------------------
def _event_vector(idf: Table, label_col: str, event_label):
    """(y, mask): y[r]=1.0 where label==event_label (device)."""
    if label_col not in idf.columns:
        raise TypeError("Invalid input for Label Column")
    col = idf.columns[label_col]
    if col.kind == "cat":
        hits = np.nonzero(col.vocab == str(event_label))[0]
        code = int(hits[0]) if len(hits) else -2
        y = _event_vector_cat_program(col.data, np.int32(code))
    else:
        y = _event_vector_num_program(col.data, np.float32(float(event_label)))
    return y, col.mask


def cat_to_num_transformer(
    idf: Table,
    list_of_cols="all",
    drop_cols=[],
    method_type: str = "unsupervised",
    encoding: str = "label_encoding",
    label_col=None,
    event_label=None,
    **kwargs,
) -> Table:
    """Dispatcher (reference :428-503)."""
    if method_type == "unsupervised":
        return cat_to_num_unsupervised(idf, list_of_cols, drop_cols, method_type=encoding, **kwargs)
    if method_type == "supervised":
        return cat_to_num_supervised(
            idf, list_of_cols, drop_cols, label_col=label_col, event_label=event_label, **kwargs
        )
    raise TypeError("Invalid input for method_type")


def cat_to_num_unsupervised(
    idf: Table,
    list_of_cols="all",
    drop_cols=[],
    method_type: str = "label_encoding",
    index_order: str = "frequencyDesc",
    cardinality_threshold: int = 50,
    pre_existing_model: bool = False,
    model_path: str = "NA",
    stats_unique: dict = {},
    output_mode: str = "replace",
    print_impact: bool = False,
) -> Table:
    """Label / one-hot encoding (reference :506-773).

    label_encoding: category → index by ``index_order`` (frequencyDesc/Asc,
    alphabetDesc/Asc — StringIndexer semantics); columns above
    ``cardinality_threshold`` are skipped with a warning for onehot.
    onehot_encoding: explodes into ``<col>_<index>`` 0/1 int columns.
    Model artifact: CSV [attribute, category, index].
    """
    if method_type not in ("label_encoding", "onehot_encoding"):
        raise TypeError("Invalid input for method_type")
    cols = _cat_cols_of(idf, list_of_cols, drop_cols)
    if not cols:
        warnings.warn("No Encoding Computation - No categorical column(s) to transform")
        return idf

    if pre_existing_model:
        dfm = load_model_df(model_path, "cat_to_num_unsupervised", fmt="csv")
        mapping = {
            c: dict(zip(g["category"].astype(str), g["index"].astype(int)))
            for c, g in dfm.groupby("attribute")
        }
    else:
        mapping = {}
        for c in cols:
            col = idf.columns[c]
            vsize = max(len(col.vocab), 1)
            cnts = np.asarray(code_counts(col.data, col.mask, vsize))[:vsize]
            if index_order == "frequencyDesc":
                order = np.lexsort((np.arange(vsize), -cnts))
            elif index_order == "frequencyAsc":
                order = np.lexsort((np.arange(vsize), cnts))
            elif index_order == "alphabetDesc":
                order = np.argsort(col.vocab.astype(str))[::-1]
            else:  # alphabetAsc
                order = np.argsort(col.vocab.astype(str))
            mapping[c] = {str(col.vocab[j]): int(i) for i, j in enumerate(order[: len(col.vocab)])}
        if model_path != "NA":
            rows = [
                {"attribute": c, "category": cat, "index": i}
                for c, mp in mapping.items()
                for cat, i in mp.items()
            ]
            save_model_df(pd.DataFrame(rows), model_path, "cat_to_num_unsupervised", fmt="csv")

    new_cols: "OrderedDict[str, Column]" = OrderedDict()
    odf = idf
    for c in cols:
        col = idf.columns[c]
        mp = mapping.get(c, {})
        if method_type == "onehot_encoding" and len(mp) > cardinality_threshold:
            warnings.warn(f"{c} skipped for onehot encoding: cardinality > {cardinality_threshold}")
            continue
        # host code→index table, device gather
        code_map = np.full(max(len(col.vocab), 1), -1, dtype=np.int32)
        for j, v in enumerate(col.vocab):
            if str(v) in mp:
                code_map[j] = mp[str(v)]
        if method_type == "label_encoding":
            # LUT gather + null fold + validity in one program; the LUT
            # is padded to its 2^k class so every vocab size shares one
            # compiled program per row shape (vocab_lookup discipline)
            p = _bucket_segments(len(code_map))
            lut = np.concatenate(
                [code_map, np.zeros(p - len(code_map), code_map.dtype)]
            ) if p > len(code_map) else code_map
            data, valid = _label_encode_program(jnp.asarray(lut), col.data, col.mask)
            new_cols[c] = Column("num", data, valid, dtype_name="int")
            continue
        idx = jnp.where(col.data >= 0, vocab_lookup(code_map, col.data), -1)
        valid = col.mask & (idx >= 0)
        k = len(mp)
        oh = (idx[:, None] == jnp.arange(k)[None, :]).astype(jnp.int32)
        for j in range(k):
            name = f"{c}_{j}"
            odf = odf.with_column(name, Column("num", oh[:, j], valid, dtype_name="int"))
        if output_mode == "replace":
            odf = odf.drop([c])
    if method_type == "label_encoding":
        odf = _emit(idf, new_cols, output_mode, "_index")
    if print_impact:
        logger.info(f"Encoded columns: {cols}")
    return odf


def cat_to_num_supervised(
    idf: Table,
    list_of_cols="all",
    drop_cols=[],
    label_col: str = "label",
    event_label=1,
    pre_existing_model: bool = False,
    model_path: str = "NA",
    output_mode: str = "replace",
    print_impact: bool = False,
    **_ignored,
) -> Table:
    """Target (event-rate) encoding: category → P(event | category), 4dp
    (reference :776-962, the groupBy-pivot-count loop → one segment kernel
    per column).  Model artifact: CSV per column [<col>, <col>_encoded]."""
    cols = _cat_cols_of(idf, list_of_cols, drop_cols, extra_drop=[label_col])
    if not cols:
        warnings.warn("No Categorical Encoding - No categorical column(s) to transform")
        return idf
    # the event vector is FIT-time state only: the pre-existing-model path
    # applies the persisted rate maps and must not require the label column
    # (serving requests carry features, never labels)
    tracer = get_tracer()
    k, rows = len(cols), idf.padded_rows
    vocab_max = max(len(idf.columns[c].vocab) for c in cols)
    classes = [_bucket_segments(max(len(idf.columns[c].vocab), 1)) for c in cols]
    lanes = sum(classes)
    sharded = not on_one_device(idf.columns[cols[0]].data)
    shape = dict(cols=k, rows=rows, vocab_max=vocab_max, segments_max=_bucket_segments(vocab_max))
    # what the calls below read and write, summed: padded rows into a group
    # count (with and without the label), lanes of the padded count vectors,
    # rows gathered, bytes of the padded LUTs (a bool and an f32 a column)
    # and of the gathered columns; and how many of the calls (two a column)
    # take which route of ops/segment.py
    fitted = {} if pre_existing_model else dict(count_rows=k * rows, label_rows=k * rows, seg_lanes=2 * lanes,
                                                **segment_routes(2 * classes, "counts", sharded))
    rates_of: Dict[str, np.ndarray] = {}
    with tracer.phase("transform/fit", **shape, **fitted):
        if not pre_existing_model:
            y, ym = _event_vector(idf, label_col, event_label)
        for c in cols:
            col = idf.columns[c]
            vsize = max(len(col.vocab), 1)
            if pre_existing_model:
                dfm = load_model_df(model_path, f"cat_to_num_supervised/{c}", fmt="csv")
                rate_map = dict(zip(dfm[c].astype(str), dfm[c + "_encoded"].astype(float)))
                rates = np.array([rate_map.get(str(v), np.nan) for v in col.vocab], dtype=np.float32)
            else:
                # one group count in flight at a time: each is fetched before
                # the next is dispatched (on a mesh each holds a collective)
                m_eff = col.mask & ym
                tot = np.asarray(code_counts(col.data, m_eff, vsize))[:vsize]
                ev = np.asarray(code_label_counts(col.data, m_eff, y, vsize))[:vsize]
                with np.errstate(divide="ignore", invalid="ignore"):
                    rates = np.round(ev / np.maximum(tot, 1e-30), 4).astype(np.float32)
                rates[tot == 0] = np.nan
            rates_of[c] = rates
        if not pre_existing_model and model_path != "NA":
            # the model frame holds one Python str a distinct value: built
            # only where it is written
            for c, rates in rates_of.items():
                dfm = pd.DataFrame({c: [str(v) for v in idf.columns[c].vocab],
                                    c + "_encoded": rates.astype(np.float64)})
                save_model_df(dfm, model_path, f"cat_to_num_supervised/{c}", fmt="csv")
    new_cols: "OrderedDict[str, Column]" = OrderedDict()
    with tracer.phase("transform/apply", **shape, gather_rows=2 * k * rows, lut_bytes=5 * lanes,
                      gather_out_bytes=5 * k * rows, **segment_routes(2 * classes, "gathers", sharded)):
        for c, rates in rates_of.items():
            col = idf.columns[c]
            valid_code = col.data >= 0
            nanmask_h = ~np.isnan(rates) if len(rates) else np.zeros(1, bool)
            ok = col.mask & valid_code & vocab_lookup(nanmask_h, col.data)
            enc = jnp.where(ok, vocab_lookup(np.nan_to_num(rates, nan=0.0), col.data), 0.0)
            new_cols[c] = Column("num", enc.astype(jnp.float32), ok, dtype_name="double")
    odf = _emit(idf, new_cols, output_mode, "_encoded")
    if print_impact:
        logger.info(f"Target-encoded columns: {cols}")
    return odf


# ----------------------------------------------------------------------
# rescaling
# ----------------------------------------------------------------------
def z_standardization(
    idf: Table,
    list_of_cols="all",
    drop_cols=[],
    pre_existing_model: bool = False,
    model_path: str = "NA",
    output_mode: str = "replace",
    print_impact: bool = False,
) -> Table:
    """(x−μ)/σ; zero-σ columns skipped with a warning (reference :965-1099).
    Model artifact: parquet [attribute, mean, stddev]."""
    cols = _num_cols_of(idf, list_of_cols, drop_cols)
    if not cols:
        warnings.warn("No Standardization Computation - No numerical column(s) to transform")
        return idf
    tracer = get_tracer()
    with tracer.phase("transform/fit", cols=len(cols), rows=idf.padded_rows):
        if pre_existing_model:
            dfm = load_model_df(model_path, "z_standardization").set_index("attribute")
            cols = [c for c in cols if c in dfm.index]
            mean = dfm.loc[cols, "mean"].to_numpy(np.float32)
            std = dfm.loc[cols, "stddev"].to_numpy(np.float32)
        else:
            X, M = idf.numeric_block(cols)
            mom = masked_moments(X, M)
            mean = np.asarray(mom["mean"], np.float32)[: len(cols)]
            std = np.asarray(mom["stddev"], np.float32)[: len(cols)]
            if model_path != "NA":
                save_model_df(
                    pd.DataFrame({"attribute": cols, "mean": mean.astype(float), "stddev": std.astype(float)}),
                    model_path,
                    "z_standardization",
                )
    keep = (std > 0) & ~np.isnan(std)
    skipped = [c for c, k in zip(cols, keep) if not k]
    if skipped:
        warnings.warn("Following columns are dropped from standardization due to zero stddev: " + ",".join(skipped))
    cols = [c for c, k in zip(cols, keep) if k]
    mean, std = mean[keep], std[keep]
    if not cols:
        return idf
    with tracer.phase("transform/apply", cols=len(cols), rows=idf.padded_rows):
        X, M = idf.numeric_block(cols)
        # params padded to the bucketed lane count (σ=1 keeps dead lanes finite)
        mean_p = pad_lane_params(mean, X.shape[1])
        std_p = pad_lane_params(std, X.shape[1], fill=1.0)
        Z = (X - jnp.asarray(mean_p)[None, :]) / jnp.asarray(std_p)[None, :]
        new_cols = OrderedDict(
            (c, Column("num", Z[:, i].astype(jnp.float32), idf.columns[c].mask, dtype_name="double"))
            for i, c in enumerate(cols)
        )
    odf = _emit(idf, new_cols, output_mode, "_scaled")
    if print_impact:
        logger.info(f"z-standardized: {cols}")
    return odf


def IQR_standardization(
    idf: Table,
    list_of_cols="all",
    drop_cols=[],
    pre_existing_model: bool = False,
    model_path: str = "NA",
    output_mode: str = "replace",
    print_impact: bool = False,
) -> Table:
    """(x−median)/(Q3−Q1) (reference :1102-1230).  Model artifact: parquet
    [attribute, median, iqr] (25/50/75 from exact device quantiles)."""
    cols = _num_cols_of(idf, list_of_cols, drop_cols)
    if not cols:
        warnings.warn("No Standardization Computation - No numerical column(s) to transform")
        return idf
    if pre_existing_model:
        dfm = load_model_df(model_path, "IQR_standardization").set_index("attribute")
        cols = [c for c in cols if c in dfm.index]
        med = dfm.loc[cols, "median"].to_numpy(np.float32)
        iqr = dfm.loc[cols, "iqr"].to_numpy(np.float32)
    else:
        X, M = idf.numeric_block(cols)
        q = np.asarray(
            masked_quantiles(X, M, jnp.array([0.25, 0.5, 0.75], jnp.float32), interpolation="lower")
        )[:, : len(cols)]
        med = q[1].astype(np.float32)
        iqr = (q[2] - q[0]).astype(np.float32)
        if model_path != "NA":
            save_model_df(
                pd.DataFrame({"attribute": cols, "median": med.astype(float), "iqr": iqr.astype(float)}),
                model_path,
                "IQR_standardization",
            )
    keep = (iqr > 0) & ~np.isnan(iqr)
    skipped = [c for c, k in zip(cols, keep) if not k]
    if skipped:
        warnings.warn("Following columns are dropped from standardization due to zero IQR: " + ",".join(skipped))
    cols = [c for c, k in zip(cols, keep) if k]
    med, iqr = med[keep], iqr[keep]
    if not cols:
        return idf
    X, M = idf.numeric_block(cols)
    med_p = pad_lane_params(med, X.shape[1])
    iqr_p = pad_lane_params(iqr, X.shape[1], fill=1.0)
    # one affine program; host params ride through the jit boundary
    Z = _affine_scale_program(X, med_p.astype(np.float32), iqr_p.astype(np.float32))
    new_cols = OrderedDict(
        (c, Column("num", Z[:, i].astype(jnp.float32), idf.columns[c].mask, dtype_name="double"))
        for i, c in enumerate(cols)
    )
    odf = _emit(idf, new_cols, output_mode, "_scaled")
    if print_impact:
        logger.info(f"IQR-standardized: {cols}")
    return odf


def normalization(
    idf: Table,
    list_of_cols="all",
    drop_cols=[],
    pre_existing_model: bool = False,
    model_path: str = "NA",
    output_mode: str = "replace",
    print_impact: bool = False,
) -> Table:
    """Min-max scaling to [0,1] (reference :1233-1366 — MinMaxScaler +
    vector-explode round-trip collapsed to one fused elementwise kernel).
    Model artifact: parquet [attribute, min, max]."""
    cols = _num_cols_of(idf, list_of_cols, drop_cols)
    if not cols:
        warnings.warn("No Normalization Computation - No numerical column(s) to transform")
        return idf
    if pre_existing_model:
        dfm = load_model_df(model_path, "normalization").set_index("attribute")
        cols = [c for c in cols if c in dfm.index]
        lo = dfm.loc[cols, "min"].to_numpy(np.float32)
        hi = dfm.loc[cols, "max"].to_numpy(np.float32)
    else:
        X, M = idf.numeric_block(cols)
        mom = masked_moments(X, M)
        lo = np.asarray(mom["min"], np.float32)[: len(cols)]
        hi = np.asarray(mom["max"], np.float32)[: len(cols)]
        if model_path != "NA":
            save_model_df(
                pd.DataFrame({"attribute": cols, "min": lo.astype(float), "max": hi.astype(float)}),
                model_path,
                "normalization",
            )
    keep = (hi > lo) & ~np.isnan(lo)
    skipped = [c for c, k in zip(cols, keep) if not k]
    if skipped:
        warnings.warn("Following columns dropped from normalization due to zero range: " + ",".join(skipped))
    cols = [c for c, k in zip(cols, keep) if k]
    lo, hi = lo[keep], hi[keep]
    if not cols:
        return idf
    X, M = idf.numeric_block(cols)
    lo_p = pad_lane_params(lo, X.shape[1])
    rng_p = pad_lane_params(hi - lo, X.shape[1], fill=1.0)
    Z = (X - jnp.asarray(lo_p)[None, :]) / jnp.asarray(rng_p)[None, :]
    new_cols = OrderedDict(
        (c, Column("num", Z[:, i].astype(jnp.float32), idf.columns[c].mask, dtype_name="double"))
        for i, c in enumerate(cols)
    )
    odf = _emit(idf, new_cols, output_mode, "_normalized")
    if print_impact:
        logger.info(f"normalized: {cols}")
    return odf


# ----------------------------------------------------------------------
# imputation (MMM; model-based imputers live in imputers.py)
# ----------------------------------------------------------------------
def imputation_MMM(
    idf: Table,
    list_of_cols="missing",
    drop_cols=[],
    method_type: str = "median",
    pre_existing_model: bool = False,
    model_path: str = "NA",
    output_mode: str = "replace",
    stats_missing: dict = {},
    stats_mode: dict = {},
    print_impact: bool = False,
) -> Table:
    """Mean/Median (numeric) + Mode (categorical) fill (reference :1369-1674;
    Spark ML Imputer + groupBy-mode → two batched kernels).  Model artifact:
    parquet [attribute, fill_value(str), kind]."""
    if method_type not in ("mean", "median"):
        raise TypeError("Invalid input for method_type")
    num_all, cat_all, _ = idf.attribute_type_segregation()
    if list_of_cols == "missing":
        if stats_missing:
            from anovos_tpu.data_ingest.data_ingest import read_dataset

            miss = read_dataset(**stats_missing).to_pandas()
            cols = list(miss.loc[miss["missing_count"] > 0, "attribute"])
        else:
            from anovos_tpu.ops.reductions import masked_count
            from anovos_tpu.shared.table import stack_masks_padded

            M = stack_masks_padded([idf.columns[c].mask for c in idf.col_names])
            fill = np.asarray(masked_count(M))  # zip() truncates the dead lanes
            cols = [c for c, f in zip(idf.col_names, fill) if f < idf.nrows]
    else:
        cols = parse_cols(list_of_cols, idf.col_names, [])
    cols = [c for c in cols if c not in set(drop_cols if not isinstance(drop_cols, str) else drop_cols.split("|"))]
    cols = [c for c in cols if c in idf.columns and idf.columns[c].kind in ("num", "cat")]
    if not cols:
        return idf

    num_cols = [c for c in cols if idf.columns[c].kind == "num"]
    cat_cols = [c for c in cols if idf.columns[c].kind == "cat"]
    tracer = get_tracer()
    vocab_max = max((len(idf.columns[c].vocab) for c in cat_cols), default=0)
    shape = dict(cols=len(cols), rows=idf.padded_rows, vocab_max=vocab_max,
                 segments_max=_bucket_segments(vocab_max) if cat_cols else 0)
    # a group count a categorical: the padded rows it reads, the lanes of its count vector, summed, and its route
    classes = [_bucket_segments(max(len(idf.columns[c].vocab), 1)) for c in cat_cols]
    fitted = {} if pre_existing_model or not cat_cols else dict(
        count_rows=len(cat_cols) * idf.padded_rows, seg_lanes=sum(classes),
        **segment_routes(classes, "counts", not on_one_device(idf.columns[cat_cols[0]].data)))
    fills: Dict[str, object] = {}
    mode_code: Dict[str, int] = {}  # a mode counted here: its code, so nobody looks its value up again
    with tracer.phase("transform/fit", **shape, **fitted):
        if pre_existing_model:
            dfm = load_model_df(model_path, "imputation_MMM")
            for _, r in dfm.iterrows():
                fills[r["attribute"]] = (r["kind"], r["fill_value"])
        else:
            if num_cols:
                X, M = idf.numeric_block(num_cols)
                if method_type == "mean":
                    vals = np.asarray(masked_moments(X, M)["mean"])
                else:
                    vals = np.asarray(
                        masked_quantiles(X, M, jnp.array([0.5], jnp.float32), interpolation="lower")
                    )[0]
                for c, v in zip(num_cols, vals):
                    fills[c] = ("num", float(v))
            for c in cat_cols:
                col = idf.columns[c]
                vsize = max(len(col.vocab), 1)
                cnts = np.asarray(code_counts(col.data, col.mask, vsize))[:vsize]
                code = int(np.argmax(cnts))  # a tie: the lowest code, the first value in code-point order
                if len(col.vocab) and cnts[code] > 0:
                    fills[c] = ("cat", str(col.vocab[code]))
                    mode_code[c] = code
                else:
                    fills[c] = ("cat", None)
            if model_path != "NA":
                save_model_df(
                    pd.DataFrame(
                        [{"attribute": c, "kind": k, "fill_value": str(v)} for c, (k, v) in fills.items()]
                    ),
                    model_path,
                    "imputation_MMM",
                )

    new_cols: "OrderedDict[str, Column]" = OrderedDict()
    with tracer.phase("transform/apply", **shape):
        for c in cols:
            if c not in fills:
                continue
            kind, v = fills[c]
            col = idf.columns[c]
            if col.kind == "num":
                fv = float(v)
                if np.isnan(fv):
                    continue
                # fill + cast in one shared program per (shape, dtype)
                if col.data.dtype == jnp.int32 and float(fv).is_integer():
                    data = _impute_num_int_program(col.data, col.mask,
                                                   np.float32(fv))
                else:
                    data = _impute_num_program(col.data, col.mask,
                                               np.float32(fv))
                rv = _row_valid_program(col.mask, np.int32(idf.nrows))
                new_cols[c] = Column("num", data, rv, dtype_name=col.dtype_name)
            else:
                if v is None:
                    continue
                if c in mode_code:
                    vocab, code = col.vocab, mode_code[c]
                else:  # a saved model's value: found in this table's vocab, or added to it
                    hits = np.nonzero(col.vocab == v)[0]
                    if len(hits) == 0:
                        vocab = np.append(col.vocab, v).astype(object)
                        code = len(vocab) - 1
                    else:
                        vocab, code = col.vocab, int(hits[0])
                data, rv = _impute_cat_program(col.data, col.mask,
                                               np.int32(code),
                                               np.int32(idf.nrows))
                new_cols[c] = Column("cat", data, rv, vocab=vocab,
                                     dtype_name="string")
    odf = _emit(idf, new_cols, output_mode, "_imputed")
    if print_impact:
        logger.info(f"imputed ({method_type}): {list(new_cols)}")
    return odf


# ----------------------------------------------------------------------
# elementwise math / boxcox
# ----------------------------------------------------------------------
_MATH_OPS = {
    "ln": jnp.log,
    "log10": jnp.log10,
    "log2": jnp.log2,
    "exp": jnp.exp,
    "powOf2": lambda x, N=None: jnp.power(2.0, x),
    "powOf10": lambda x, N=None: jnp.power(10.0, x),
    "sqrt": jnp.sqrt,
    "cbrt": jnp.cbrt,
    "sq": lambda x, N=None: x**2,
    "cb": lambda x, N=None: x**3,
    "sin": jnp.sin,
    "cos": jnp.cos,
    "tan": jnp.tan,
    "asin": jnp.arcsin,
    "acos": jnp.arccos,
    "atan": jnp.arctan,
    "radians": jnp.radians,
    "factorial": lambda x, N=None: jnp.exp(jax.scipy.special.gammaln(x + 1.0)),
    "mul_inv": lambda x, N=None: 1.0 / x,
    "floor": jnp.floor,
    "ceil": jnp.ceil,
}
_MATH_OPS_N = {
    "powOfN": lambda x, N: jnp.power(float(N), x),
    "toPowerN": lambda x, N: x ** float(N),
    "remainderDivByN": lambda x, N: x % float(N),
    "roundN": lambda x, N: jnp.round(x, int(N)),
}


def feature_transformation(
    idf: Table,
    list_of_cols="all",
    drop_cols=[],
    method_type: str = "sqrt",
    N=None,
    boolean_drop: bool = False,
    output_mode: str = "replace",
    print_impact: bool = False,
) -> Table:
    """24 elementwise math ops (reference :3171-3324) as one fused kernel.
    Domain violations (log of ≤0, sqrt of <0 …) become nulls, matching Spark's
    null-on-NaN column expr behavior."""
    cols = _num_cols_of(idf, list_of_cols, drop_cols)
    if not cols:
        warnings.warn("No Transformation Computation - No numerical column(s) to transform")
        return idf
    if method_type in _MATH_OPS_N:
        if N is None:
            raise TypeError(f"N required for method_type {method_type}")
        postfix = "_" + method_type[:-1] + str(N)
    elif method_type in _MATH_OPS:
        postfix = "_" + method_type
    else:
        raise TypeError("Invalid input for method_type")
    X, M = idf.numeric_block(cols)
    # math op + finite-mask + zero-fill in one program over the block
    Yc, ok = _mathop_apply_program(
        X, M, method_type, n=N if method_type in _MATH_OPS_N else None)
    new_cols = OrderedDict(
        (c, Column("num", Yc[:, i], ok[:, i], dtype_name="double"))
        for i, c in enumerate(cols)
    )
    odf = idf
    for name, col in new_cols.items():
        odf = odf.with_column(name if output_mode == "replace" else name + postfix, col)
    if print_impact:
        logger.info(f"{method_type} applied to {cols}")
    return odf


_BOXCOX_LAMBDAS = [1.0, -1.0, 0.5, -0.5, 2.0, -2.0, 0.25, -0.25, 3.0, -3.0, 4.0, -4.0, 5.0, -5.0, 0.0]


def _ks_vs_normal(X: jax.Array, M: jax.Array) -> jax.Array:
    """Per-column KS statistic of standardized data vs N(0,1) — the MLlib
    kolmogorovSmirnovTest call site (reference transformers.py:3424-3443).
    The per-column sort runs column-parallel on a multi-device mesh
    (runtime.column_parallel)."""
    from anovos_tpu.shared.runtime import wants_column_parallel

    return _ks_vs_normal_jit(X, M, cp=wants_column_parallel(X, M))


@functools.partial(jax.jit, static_argnames=("cp",))
def _ks_vs_normal_jit(X: jax.Array, M: jax.Array, cp: bool = False) -> jax.Array:
    from anovos_tpu.shared.runtime import column_parallel

    X, M = column_parallel(X, cp), column_parallel(M, cp)
    mom_n = M.sum(0).astype(jnp.float32)
    mean = jnp.where(M, X, 0).sum(0) / jnp.maximum(mom_n, 1)
    d = jnp.where(M, X - mean, 0)
    std = jnp.sqrt((d * d).sum(0) / jnp.maximum(mom_n - 1, 1))
    Z = jnp.where(M, (X - mean) / jnp.maximum(std, 1e-30), jnp.inf)
    Zs = jnp.sort(Z, axis=0)
    rows = X.shape[0]
    pos = jnp.arange(1, rows + 1, dtype=jnp.float32)[:, None]
    ecdf_hi = pos / jnp.maximum(mom_n, 1)[None, :]
    ecdf_lo = (pos - 1) / jnp.maximum(mom_n, 1)[None, :]
    cdf = jax.scipy.stats.norm.cdf(Zs)
    valid = (jnp.arange(rows)[:, None] < mom_n[None, :])
    dev = jnp.maximum(jnp.abs(cdf - ecdf_hi), jnp.abs(cdf - ecdf_lo))
    return jnp.where(valid, dev, 0.0).max(axis=0)


def _boxcox_fit_lambdas(X: jax.Array, M: jax.Array, ncols: int) -> np.ndarray:
    """Grid-search λ per column by KS distance to a normal — the fit half
    of :func:`boxcox_transformation`, extracted so ``fitted_state`` can
    export the selected λs without re-deriving the search."""
    best_ks = np.full(ncols, np.inf)
    lam = np.ones(ncols)
    for lmb in _BOXCOX_LAMBDAS:
        # score with the SAME transform that apply uses, so the selected λ
        # is the one actually emitted
        Y = jnp.log(X) if lmb == 0.0 else jnp.sign(X) * jnp.abs(X) ** lmb
        ok = M & jnp.isfinite(Y)
        ks = np.asarray(_ks_vs_normal(jnp.where(ok, Y, 0.0), ok))[:ncols]
        better = ks < best_ks
        lam = np.where(better, lmb, lam)
        best_ks = np.where(better, ks, best_ks)
    return lam


def boxcox_transformation(
    idf: Table,
    list_of_cols="all",
    drop_cols=[],
    boxcox_lambda=None,
    output_mode: str = "replace",
    print_impact: bool = False,
) -> Table:
    """Power-transform each column with the λ (from the reference's grid,
    :3424-3443) minimizing the KS distance to a normal; λ=0 → ln x
    (reference :3327-3486).  Entire λ search is vectorized on device."""
    cols = _num_cols_of(idf, list_of_cols, drop_cols)
    if not cols:
        warnings.warn("No Transformation Computation - No numerical column(s) to transform")
        return idf
    X, M = idf.numeric_block(cols)
    if boxcox_lambda is not None:
        if isinstance(boxcox_lambda, (int, float)):
            lam = np.full(len(cols), float(boxcox_lambda))
        else:
            lam = np.array([float(v) for v in boxcox_lambda])
    else:
        lam = _boxcox_fit_lambdas(X, M, len(cols))
    # λ=1 (identity) on the dead bucketed lanes keeps them finite
    lam_d = jnp.asarray(pad_lane_params(lam, X.shape[1], fill=1.0), jnp.float32)[None, :]
    Y = jnp.where(lam_d == 0.0, jnp.log(X), jnp.sign(X) * jnp.abs(X) ** lam_d)
    ok = M & jnp.isfinite(Y)
    new_cols = OrderedDict(
        (c, Column("num", jnp.where(ok[:, i], Y[:, i], 0.0).astype(jnp.float32), ok[:, i], dtype_name="double"))
        for i, c in enumerate(cols)
    )
    odf = _emit(idf, new_cols, output_mode, "_bxcx")
    if print_impact:
        logger.info(f"boxcox lambdas: {dict(zip(cols, lam.tolist()))}")
    return odf


# ----------------------------------------------------------------------
# categorical outliers + expressions
# ----------------------------------------------------------------------
def outlier_categories(
    idf: Table,
    list_of_cols="all",
    drop_cols=[],
    coverage: float = 1.0,
    max_category: int = 50,
    pre_existing_model: bool = False,
    model_path: str = "NA",
    output_mode: str = "replace",
    print_impact: bool = False,
) -> Table:
    """Club rare categories into ``outlier_categories`` keeping the smallest
    set of most-frequent categories reaching ``coverage`` (cumulative count
    pct), capped at max_category−1 (reference :3489-3671 — the window-cumsum
    becomes a host cumsum over the device-computed code counts)."""
    cols = _cat_cols_of(idf, list_of_cols, drop_cols)
    if not cols:
        warnings.warn("No Outlier Categories Computation - No categorical column(s) to transform")
        return idf
    keep_map: Dict[str, List[str]] = {}
    if pre_existing_model:
        dfm = load_model_df(model_path, "outlier_categories", fmt="csv")
        for c, g in dfm.groupby("attribute"):
            keep_map[c] = list(g["parameters"].astype(str))
    else:
        for c in cols:
            col = idf.columns[c]
            vsize = max(len(col.vocab), 1)
            cnts = np.asarray(code_counts(col.data, col.mask, vsize))[:vsize]
            order = np.lexsort((np.arange(vsize), -cnts))
            sorted_cnts = cnts[order]
            pct = sorted_cnts / max(sorted_cnts.sum(), 1)
            cumu = np.cumsum(pct)
            lag = np.concatenate([[0.0], cumu[:-1]])
            sel = ~((cumu >= coverage) & (lag >= coverage))
            sel &= np.arange(vsize) <= (max_category - 2)
            sel &= sorted_cnts > 0
            keep_map[c] = [str(col.vocab[j]) for j, s in zip(order, sel) if s]
        if model_path != "NA":
            rows = [{"attribute": c, "parameters": v} for c, vs in keep_map.items() for v in vs]
            save_model_df(pd.DataFrame(rows), model_path, "outlier_categories", fmt="csv")
    new_cols: "OrderedDict[str, Column]" = OrderedDict()
    for c in cols:
        col = idf.columns[c]
        keep = set(keep_map.get(c, []))
        new_vocab = np.array(sorted(keep | {"outlier_categories"}), dtype=object)
        lk = {v: i for i, v in enumerate(new_vocab)}
        out_code = lk["outlier_categories"]
        code_map = np.array(
            [lk.get(str(v), out_code) for v in col.vocab] or [out_code], dtype=np.int32
        )
        # vocab_lookup pads the LUT to a 2^k class: every column's remap
        # replays ONE compiled gather per row shape instead of one per
        # vocab size (the eager per-column indexing compiled a gather
        # program per column here — cold-compile census)

        data = jnp.where(col.data >= 0, vocab_lookup(code_map, col.data), -1)
        new_cols[c] = Column("cat", data.astype(jnp.int32), col.mask, vocab=new_vocab, dtype_name="string")
    odf = _emit(idf, new_cols, output_mode, "_outliered")
    if print_impact:
        logger.info({c: len(v) for c, v in keep_map.items()})
    return odf


_EXPR_FUNCS = {
    "log": jnp.log,
    "ln": jnp.log,
    "log2": jnp.log2,
    "log10": jnp.log10,
    "exp": jnp.exp,
    "sqrt": jnp.sqrt,
    "cbrt": jnp.cbrt,
    "abs": jnp.abs,
    "sin": jnp.sin,
    "cos": jnp.cos,
    "tan": jnp.tan,
    "asin": jnp.arcsin,
    "acos": jnp.arccos,
    "atan": jnp.arctan,
    "sinh": jnp.sinh,
    "cosh": jnp.cosh,
    "tanh": jnp.tanh,
    "floor": jnp.floor,
    "ceil": jnp.ceil,
    "round": jnp.round,
    "pow": jnp.power,
    "sign": jnp.sign,
    "greatest": jnp.maximum,
    "least": jnp.minimum,
}


def _validate_expr_ast(src: str, allowed_names) -> None:
    """AST whitelist for expression_parser: arithmetic, comparisons, calls of
    whitelisted function names, numeric constants, and known identifiers.
    Attribute access is rejected outright — with empty builtins an eval can
    still escape through ``().__class__`` chains; an AST gate cannot."""
    import ast

    tree = ast.parse(src, mode="eval")
    # elementwise & | ^ ~ are the array conjunctions jax supports; Python's
    # `and`/`or` would bool() a multi-element array, so they're excluded
    ok_nodes = (
        ast.Expression, ast.BinOp, ast.UnaryOp, ast.Compare, ast.IfExp,
        ast.Call, ast.Name, ast.Constant, ast.Load,
        ast.Add, ast.Sub, ast.Mult, ast.Div, ast.FloorDiv, ast.Mod, ast.Pow,
        ast.BitAnd, ast.BitOr, ast.BitXor, ast.Invert,
        ast.USub, ast.UAdd, ast.Lt, ast.LtE, ast.Gt, ast.GtE, ast.Eq, ast.NotEq,
    )

    def _fully_constant(n) -> bool:
        # no column/function reference anywhere → Python evaluates it as
        # pure scalar arithmetic (bignum-capable) before jnp is involved
        return not any(isinstance(x, ast.Name) for x in ast.walk(n))

    for node in ast.walk(tree):
        if not isinstance(node, ok_nodes):
            raise ValueError(f"disallowed syntax: {type(node).__name__}")
        if isinstance(node, ast.Call):
            if not isinstance(node.func, ast.Name) or node.func.id not in _EXPR_FUNCS:
                raise ValueError("only whitelisted functions may be called")
            if node.keywords:
                raise ValueError("keyword arguments are not allowed")
        if isinstance(node, ast.Name) and node.id not in allowed_names:
            raise ValueError(f"unknown identifier: {node.id}")
        if isinstance(node, ast.Constant):
            if not isinstance(node.value, (int, float, bool)):
                raise ValueError("only numeric constants are allowed")
            if abs(float(node.value)) > 1e12:
                raise ValueError("constant magnitude too large")
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Pow):
            # a fully-constant power tower (9**9**9…) is a bignum CPU/memory
            # bomb evaluated by Python before any jnp code runs
            if _fully_constant(node):
                raise ValueError("constant-only exponentiation is not allowed")


def expression_parser(idf: Table, list_of_expr, postfix: str = "", print_impact: bool = False) -> Table:
    """SQL-ish expression features (reference :3674-3766).  Column names (incl.
    special-char names, handled by longest-match substitution — the
    reference's rename round-trip) become device arrays; the restricted
    function namespace maps to jnp and an AST whitelist guards evaluation.
    New column is named after the expression."""
    if isinstance(list_of_expr, str):
        list_of_expr = [e.strip() for e in list_of_expr.split("|")]
    odf = idf
    for expr in list_of_expr:
        sub = expr
        namespace: Dict[str, jax.Array] = {}
        maskspace: List[jax.Array] = []
        import re

        for name in sorted(idf.col_names, key=len, reverse=True):
            pat = r"(?<![\w])" + re.escape(name) + r"(?![\w])"
            if re.search(pat, sub):
                san = "_c" + str(abs(hash(name)) % 10**8)
                sub = re.sub(pat, san, sub)
                col = idf.columns[name]
                namespace[san] = col.data.astype(jnp.float32)
                maskspace.append(col.mask)
        try:
            _validate_expr_ast(sub, set(_EXPR_FUNCS) | set(namespace))
            val = eval(sub, {"__builtins__": {}}, {**_EXPR_FUNCS, **namespace})  # noqa: S307 — AST-validated
        except Exception as e:
            raise ValueError(f"expression_parser: cannot evaluate {expr!r}: {e}")
        val = jnp.asarray(val, jnp.float32)
        if val.ndim == 0:
            val = jnp.full((idf.padded_rows,), val)
        mask = jnp.ones((idf.padded_rows,), bool)
        for m in maskspace:
            mask = mask & m
        mask = mask & jnp.isfinite(val) & (jnp.arange(idf.padded_rows) < idf.nrows)
        name = expr + postfix
        odf = odf.with_column(name, Column("num", jnp.where(mask, val, 0.0), mask, dtype_name="double"))
    if print_impact:
        logger.info(f"expressions added: {list_of_expr}")
    return odf


# ----------------------------------------------------------------------
# serving-state export: fitted_state() / from_state()
# ----------------------------------------------------------------------
# The online-serving subsystem (anovos_tpu.serving) needs every fitted
# transformer's state as a portable, JSON-able document: binning edges,
# scaler params, boxcox λs, encoder vocab maps, imputer fills, outlier
# keep-sets.  The round-trip contract is byte-exactness: ``from_state``
# APPLIES THROUGH THE BATCH FUNCTIONS THEMSELVES (their pre-existing-model
# branches, with the state materialized back into the exact model-artifact
# format ``model_io`` persists), so a served apply replays the very same
# jitted programs as a batch re-apply — parity is by construction, and
# tests/test_serving.py pins it byte-identically per family.

SERVABLE_TRANSFORMERS = (
    "attribute_binning",
    "z_standardization",
    "IQR_standardization",
    "normalization",
    "imputation_MMM",
    "cat_to_num_unsupervised",
    "cat_to_num_supervised",
    "outlier_categories",
    "boxcox_transformation",
    "feature_transformation",
)

# model-artifact format each family persists through model_io (None =
# stateless or exported directly, no on-disk model round-trip needed)
_STATE_MODEL_FMT = {
    "attribute_binning": "parquet",
    "z_standardization": "parquet",
    "IQR_standardization": "parquet",
    "normalization": "parquet",
    "imputation_MMM": "parquet",
    "cat_to_num_unsupervised": "csv",
    "cat_to_num_supervised": "csv",
    "outlier_categories": "csv",
    "boxcox_transformation": None,
    "feature_transformation": None,
}

# config keys the APPLY path consumes — everything else (bin counts,
# index orders, coverage thresholds, label columns' event values …) is
# fit-time material and deliberately absent from the exported state
_STATE_APPLY_KEYS = {
    "attribute_binning": ("bin_dtype", "output_mode"),
    "z_standardization": ("output_mode",),
    "IQR_standardization": ("output_mode",),
    "normalization": ("output_mode",),
    "imputation_MMM": ("method_type", "output_mode"),
    "cat_to_num_unsupervised": ("method_type", "cardinality_threshold", "output_mode"),
    "cat_to_num_supervised": ("label_col", "output_mode"),
    "outlier_categories": ("output_mode",),
    "boxcox_transformation": ("output_mode",),
    "feature_transformation": ("method_type", "N", "output_mode"),
}

STATE_VERSION = 1


def _jsonable(v):
    """Recursive numpy→python coercion so states json.dumps cleanly and
    floats round-trip bit-exactly (Python json preserves float64)."""
    if isinstance(v, (np.floating,)):
        return float(v)
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, np.bool_):
        return bool(v)
    if isinstance(v, np.ndarray):
        return [_jsonable(x) for x in v.tolist()]
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    return str(v)


def _read_model_tables(model_dir: str, fmt: str) -> Dict[str, dict]:
    """Every model table under ``model_dir`` as columnar JSON-able dicts,
    keyed by the model name (relative dir) ``save_model_df`` wrote it as.
    CSV tables read ``dtype=str`` — the same verbatim-string discipline as
    ``load_model_df`` — so category values like ``"01"`` survive."""
    tables: Dict[str, dict] = {}
    for dirpath, _dirs, files in sorted(os.walk(model_dir)):
        parts = sorted(f for f in files if f.endswith("." + fmt))
        if not parts:
            continue
        frames = [
            pd.read_parquet(os.path.join(dirpath, f)) if fmt == "parquet"
            else pd.read_csv(os.path.join(dirpath, f), dtype=str)
            for f in parts
        ]
        df = pd.concat(frames, ignore_index=True)
        rel = os.path.relpath(dirpath, model_dir).replace(os.sep, "/")
        tables[rel] = {c: _jsonable(df[c].tolist()) for c in df.columns}
    return tables


def fitted_state(idf: Table, name: str, config: Optional[dict] = None) -> dict:
    """Fit transformer ``name`` on ``idf`` under ``config`` and export its
    complete apply-time state as a JSON-able document.

    The fit runs through the batch function itself (persisting its model
    artifact into a scratch dir, then lifting the artifact verbatim into
    the state), so the exported parameters are EXACTLY what a batch
    ``pre_existing_model=True`` re-apply would read."""
    import tempfile

    if name not in SERVABLE_TRANSFORMERS:
        raise ValueError(
            f"{name!r} is not a servable transformer (one of {SERVABLE_TRANSFORMERS})")
    config = dict(config or {})
    config.pop("pre_existing_model", None)
    config.pop("model_path", None)
    config.setdefault("print_impact", False)
    apply_config = {k: config[k] for k in _STATE_APPLY_KEYS[name] if k in config}
    state = {
        "state_version": STATE_VERSION,
        "family": name,
        "apply_config": _jsonable(apply_config),
    }
    list_of_cols = config.get("list_of_cols", "all")
    drop_cols = config.get("drop_cols", [])

    if name == "feature_transformation":
        state["cols"] = _num_cols_of(idf, list_of_cols, drop_cols)
        state["model"] = None
        return state
    if name == "boxcox_transformation":
        cols = _num_cols_of(idf, list_of_cols, drop_cols)
        given = config.get("boxcox_lambda")
        if given is not None:
            lam = (np.full(len(cols), float(given))
                   if isinstance(given, (int, float))
                   else np.array([float(v) for v in given]))
        else:
            X, M = idf.numeric_block(cols)
            lam = _boxcox_fit_lambdas(X, M, len(cols))
        state["cols"] = cols
        state["model"] = {"fmt": None, "tables": {
            "boxcox_lambda": {"attribute": cols,
                              "lambda": [float(v) for v in lam]}}}
        return state

    fmt = _STATE_MODEL_FMT[name]
    fn = globals()[name]
    with tempfile.TemporaryDirectory(prefix="anovos_fitstate_") as mp:
        fn(idf, **{**config, "model_path": mp})
        tables = _read_model_tables(mp, fmt)
    if not tables:
        raise ValueError(
            f"{name} fitted no state on this table (no applicable columns?)")
    state["model"] = {"fmt": fmt, "tables": tables}
    if name == "cat_to_num_supervised":
        # per-column model dirs: recover the fit-order column list from the
        # same resolution the fit used
        state["cols"] = _cat_cols_of(
            idf, list_of_cols, drop_cols,
            extra_drop=[config.get("label_col", "label")])
    else:
        main = tables[name]
        cols = list(dict.fromkeys(main["attribute"]))
        if name == "imputation_MMM":
            # the fit resolves "missing" in table-column order but persists
            # fills num-block-first; re-applying must walk the fit's own
            # order or append-mode column order drifts
            in_table = [c for c in idf.col_names if c in set(cols)]
            cols = in_table + [c for c in cols if c not in set(in_table)]
        state["cols"] = cols
    return state


class FittedTransformer:
    """One transformer's apply-only form, rebuilt from a ``fitted_state``
    document.  ``apply`` routes through the batch function's pre-existing-
    model branch over a model dir materialized ONCE at construction, so a
    served apply and a batch re-apply execute identical code."""

    def __init__(self, state: dict):
        import tempfile

        if state.get("state_version") != STATE_VERSION:
            raise ValueError(
                f"fitted_state version {state.get('state_version')!r} != "
                f"supported {STATE_VERSION}")
        self.family: str = state["family"]
        if self.family not in SERVABLE_TRANSFORMERS:
            raise ValueError(f"unknown transformer family {self.family!r}")
        self.cols: List[str] = list(state["cols"])
        self.apply_config: dict = dict(state.get("apply_config") or {})
        self._lambdas: Optional[List[float]] = None
        self._model_tmp = None
        model = state.get("model")
        if self.family == "boxcox_transformation":
            tab = model["tables"]["boxcox_lambda"]
            by_col = dict(zip(tab["attribute"], tab["lambda"]))
            self._lambdas = [float(by_col[c]) for c in self.cols]
        elif model is not None:
            # materialize the model artifact exactly as the fit persisted it
            self._model_tmp = tempfile.TemporaryDirectory(
                prefix=f"anovos_serve_{self.family}_")
            fmt = model["fmt"]
            for rel, columns in model["tables"].items():
                save_model_df(pd.DataFrame(dict(columns)),
                              self._model_tmp.name, rel, fmt=fmt)

    @property
    def model_dir(self) -> Optional[str]:
        return self._model_tmp.name if self._model_tmp is not None else None

    def apply(self, idf: Table) -> Table:
        cfg = self.apply_config
        out_mode = cfg.get("output_mode", "replace")
        if self.family == "feature_transformation":
            return feature_transformation(
                idf, self.cols, method_type=cfg.get("method_type", "sqrt"),
                N=cfg.get("N"), output_mode=out_mode)
        if self.family == "boxcox_transformation":
            return boxcox_transformation(
                idf, self.cols, boxcox_lambda=self._lambdas,
                output_mode=out_mode)
        fn = globals()[self.family]
        kwargs = {"pre_existing_model": True, "model_path": self.model_dir,
                  "output_mode": out_mode}
        if self.family == "attribute_binning":
            kwargs["bin_dtype"] = cfg.get("bin_dtype", "numerical")
        elif self.family == "imputation_MMM":
            kwargs["method_type"] = cfg.get("method_type", "median")
        elif self.family == "cat_to_num_unsupervised":
            kwargs["method_type"] = cfg.get("method_type", "label_encoding")
            if "cardinality_threshold" in cfg:
                kwargs["cardinality_threshold"] = cfg["cardinality_threshold"]
        elif self.family == "cat_to_num_supervised":
            kwargs["label_col"] = cfg.get("label_col", "label")
        return fn(idf, self.cols, **kwargs)


def from_state(state: dict) -> FittedTransformer:
    """Rebuild the apply-only transformer from a ``fitted_state`` doc."""
    return FittedTransformer(state)


# model-based imputers and latent-feature transformers live in sibling
# modules but belong to this namespace for reflection dispatch parity with
# the reference (workflow.py getattr(transformers, fn))
from anovos_tpu.data_transformer.imputers import (  # noqa: E402
    auto_imputation,
    imputation_matrixFactorization,
    imputation_sklearn,
)
from anovos_tpu.data_transformer.latent_features import (  # noqa: E402
    PCA_latentFeatures,
    autoencoder_latentFeatures,
)
