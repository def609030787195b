"""Association measures (reference: data_analyzer/association_evaluator.py).

- ``correlation_matrix``: complete-case Pearson via MXU matmuls (the
  VectorAssembler(handleInvalid="skip") + ml.stat.Correlation path,
  ref :38-139).
- ``IV_calculation`` / ``IG_calculation``: per-column label/bin counts from
  one segment kernel each (the per-column Spark-job loops, ref :365-411 /
  :533-573, collapse into batched histograms), with the same 0.5 continuity
  correction and null-bin semantics (nulls form their own group).
- ``variable_clustering``: device correlation + host VarClus
  (association_eval_varclus.py).
"""

from __future__ import annotations

import functools
import logging

import math
import warnings
from typing import List, Union

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd

from anovos_tpu.data_analyzer.association_eval_varclus import VarClusJax
from anovos_tpu.ops.correlation import masked_corr_cc
from anovos_tpu.ops.segment import cat_valid_mask, code_counts, masked_nunique
from anovos_tpu.shared.table import Table
from anovos_tpu.shared.utils import parse_cols

logger = logging.getLogger(__name__)


def correlation_matrix(
    idf: Table,
    list_of_cols="all",
    drop_cols=[],
    use_sampling: bool = False,
    sample_size: int = 1000000,
    print_impact: bool = False,
) -> pd.DataFrame:
    """[attribute, <sorted attribute names>] Pearson correlation
    (reference :38-139).  Complete-case: rows with any null among the
    selected columns are skipped, matching handleInvalid="skip"."""
    num_all, _, _ = idf.attribute_type_segregation()
    cols = parse_cols(list_of_cols if list_of_cols != "all" else num_all, idf.col_names, drop_cols)
    if any(c not in num_all for c in cols) or not cols:
        raise TypeError("Invalid input for Column(s)")
    if use_sampling and idf.nrows > sample_size:
        warnings.warn(f"Using sampling. Only {sample_size} random sampled rows are considered.")
        from anovos_tpu.data_ingest.data_sampling import data_sample

        idf = data_sample(idf, fraction=float(sample_size) / idf.nrows, method_type="random")
    X, M = idf.numeric_block(cols)
    # complete-case over the LIVE lanes only: the block is column-bucketed
    # (dead lanes mask=False), so `M.all(axis=1)` would veto every row.
    # The live count rides in as a device scalar, keeping the program
    # keyed on the bucketed shape rather than recompiling per width.
    # the row-count/compare/combine glue is fused into the corr program
    C = np.asarray(masked_corr_cc(X, M, len(cols)))[: len(cols), : len(cols)]
    odf = pd.DataFrame(C, columns=cols, index=cols)
    odf["attribute"] = odf.index
    ordered = sorted(cols)
    odf = odf[["attribute"] + ordered].sort_values("attribute").reset_index(drop=True)
    if print_impact:
        logger.info(odf.to_string(index=False))
    return odf


def _label_group_counts_fused(data, mask, y, ym, nrows, vsize: int):
    """ONE program per column for the IV/IG group sweep: valid-mask
    combine, both label segment-sums, and the two null-group reductions,
    with no host sync mid-loop.  ``mask=None`` when the
    null semantics already live in the codes (−1 = invalid).  Returns host
    (tot, ev, null_tot, null_ev); tot/ev padded to the segment class."""
    from anovos_tpu.ops.segment import _bucket_segments

    args = (y, ym, np.int32(nrows))
    if mask is None:
        tot, ev, null_tot, null_ev = _label_group_program_nomask(
            data, *args, vsize=_bucket_segments(vsize))
    else:
        tot, ev, null_tot, null_ev = _label_group_program(
            data, mask, *args, vsize=_bucket_segments(vsize))
    return (np.asarray(tot), np.asarray(ev), float(null_tot), float(null_ev))


def _label_group_body(valid, data, y, ym, nrows, vsize: int):
    m_eff = valid & ym
    safe = jnp.where(m_eff, data, 0)
    ones = jnp.where(m_eff, 1.0, 0.0).astype(jnp.float32)
    tot = jax.ops.segment_sum(ones, safe, num_segments=vsize)
    ev = jax.ops.segment_sum(
        jnp.where(m_eff, y, 0.0).astype(jnp.float32), safe, num_segments=vsize)
    null_m = ym & ~valid
    rv = jnp.arange(data.shape[0]) < nrows
    null_tot = jnp.sum(null_m & rv)
    null_ev = jnp.sum(jnp.where(null_m, y, 0.0))
    return tot, ev, null_tot, null_ev


@functools.partial(jax.jit, static_argnames=("vsize",))
def _label_group_program(data, mask, y, ym, nrows, vsize: int):
    return _label_group_body(mask & (data >= 0), data, y, ym, nrows, vsize)


@functools.partial(jax.jit, static_argnames=("vsize",))
def _label_group_program_nomask(data, y, ym, nrows, vsize: int):
    return _label_group_body(data >= 0, data, y, ym, nrows, vsize)


@jax.jit
def _masked_sum_program(y, ym):
    """sum(where(ym, y, 0)) — the IV/IG total-event reduction as one
    program."""
    return jnp.sum(jnp.where(ym, y, 0.0))


def _grouped_label_counts(idf: Table, col: str, y, ym, nbins_cap: int = 0):
    """(label_0, label_1) count vectors over the groups of ``col`` —
    categories/bins plus one null group (Spark groupBy keeps nulls)."""
    import jax

    c = idf.columns[col]
    if c.kind == "cat":
        vsize = max(len(c.vocab), 1)
        tot, ev, null_tot, null_ev = _label_group_counts_fused(
            c.data, c.mask, y, ym, idf.nrows, vsize)
        tot, ev = tot[:vsize], ev[:vsize]
    else:
        # integer-binned or raw discrete numeric: group by exact value via codes
        vals = np.asarray(c.data)[: idf.nrows]
        mask = np.asarray(c.mask)[: idf.nrows]
        uniq, codes = np.unique(vals[mask], return_inverse=True)
        vsize = max(len(uniq), 1)
        code_arr = np.full(idf.nrows, -1, np.int32)
        code_arr[mask] = codes.astype(np.int32)
        from anovos_tpu.shared.runtime import get_runtime

        rt = get_runtime()
        pad = idf.padded_rows - idf.nrows
        codes_d = rt.shard_rows(np.concatenate([code_arr, np.full(pad, -1, np.int32)]))
        # null codes carry the mask (-1 = invalid), so the fused
        # program runs maskless (mask_none)
        tot, ev, null_tot, null_ev = _label_group_counts_fused(
            codes_d, None, y, ym, idf.nrows, vsize)
        tot, ev = tot[:vsize], ev[:vsize]
    tot = np.append(tot, null_tot)
    ev = np.append(ev, null_ev)
    keep = tot > 0
    label_1 = ev[keep]
    label_0 = tot[keep] - label_1
    return label_0, label_1


def _prep_encoded(idf: Table, cols: List[str], label_col, event_label, encoding_configs):
    """Bin numeric columns per encoding_configs (reference IV/IG preamble)."""
    from anovos_tpu.data_transformer.transformers import attribute_binning, monotonic_binning

    num_cols = [c for c in cols if idf.columns[c].kind == "num"]
    if not num_cols or not encoding_configs:
        return idf
    bin_method = encoding_configs.get("bin_method", "equal_frequency")
    bin_size = encoding_configs.get("bin_size", 10)
    mono = encoding_configs.get("monotonicity_check", 0)
    if mono == 1:
        return monotonic_binning(
            idf, num_cols, [], label_col=label_col, event_label=event_label,
            bin_method=bin_method, bin_size=bin_size,
        )
    return attribute_binning(idf, num_cols, [], method_type=bin_method, bin_size=bin_size)


def IV_calculation(
    idf: Table,
    list_of_cols="all",
    drop_cols=[],
    label_col: str = "label",
    event_label=1,
    encoding_configs={"bin_method": "equal_frequency", "bin_size": 10, "monotonicity_check": 0},
    print_impact: bool = False,
) -> pd.DataFrame:
    """[attribute, iv] Information Value (reference :253-424):
    IV = Σ (%nonevent − %event)·WOE, WOE = ln(%nonevent/%event) with 0.5
    continuity correction when a bin has zero events or non-events."""
    from anovos_tpu.data_transformer.transformers import _event_vector

    num_all, cat_all, _ = idf.attribute_type_segregation()
    cols = parse_cols(
        list_of_cols if list_of_cols != "all" else num_all + cat_all, idf.col_names, drop_cols
    )
    cols = [c for c in cols if c != label_col]
    if not cols:
        raise TypeError("Invalid input for Column(s)")
    y, ym = _event_vector(idf, label_col, event_label)
    idf_enc = _prep_encoded(idf, cols, label_col, event_label, encoding_configs)
    rows = []
    for c in cols:
        l0, l1 = _grouped_label_counts(idf_enc, c, y, ym)
        t0, t1 = l0.sum(), l1.sum()
        if t0 == 0 or t1 == 0:
            rows.append({"attribute": c, "iv": np.nan})
            continue
        ev_pcr = l1 / t1
        nev_pcr = l0 / t0
        woe = np.where(
            (nev_pcr != 0) & (ev_pcr != 0),
            np.log(np.maximum(nev_pcr, 1e-300) / np.maximum(ev_pcr, 1e-300)),
            np.log(((l0 + 0.5) / t0) / ((l1 + 0.5) / t1)),
        )
        iv = float(np.sum((nev_pcr - ev_pcr) * woe))
        rows.append({"attribute": c, "iv": round(iv, 4)})
    odf = pd.DataFrame(rows, columns=["attribute", "iv"])
    if print_impact:
        logger.info(odf.to_string(index=False))
    return odf


def IG_calculation(
    idf: Table,
    list_of_cols="all",
    drop_cols=[],
    label_col: str = "label",
    event_label=1,
    encoding_configs={"bin_method": "equal_frequency", "bin_size": 10, "monotonicity_check": 0},
    print_impact: bool = False,
) -> pd.DataFrame:
    """[attribute, ig] Information Gain = total entropy − Σ segment entropy
    (reference :427-585).  Segments with event_pct ∈ {0,1} contribute 0
    (Spark's null log2 is dropped by F.sum)."""
    from anovos_tpu.data_transformer.transformers import _event_vector

    num_all, cat_all, _ = idf.attribute_type_segregation()
    cols = parse_cols(
        list_of_cols if list_of_cols != "all" else num_all + cat_all, idf.col_names, drop_cols
    )
    cols = [c for c in cols if c != label_col]
    if not cols:
        raise TypeError("Invalid input for Column(s)")
    y, ym = _event_vector(idf, label_col, event_label)
    total_event = float(_masked_sum_program(y, ym)) / max(idf.nrows, 1)
    if total_event in (0.0, 1.0):
        warnings.warn("IG undefined: label has a single class")
        return pd.DataFrame({"attribute": cols, "ig": [np.nan] * len(cols)})
    total_entropy = -(
        total_event * math.log2(total_event) + (1 - total_event) * math.log2(1 - total_event)
    )
    idf_enc = _prep_encoded(idf, cols, label_col, event_label, encoding_configs)
    rows = []
    for c in cols:
        l0, l1 = _grouped_label_counts(idf_enc, c, y, ym)
        tot = l0 + l1
        seg_pct = tot / max(tot.sum(), 1e-30)
        ev_pct = np.divide(l1, np.maximum(tot, 1e-30))
        with np.errstate(divide="ignore", invalid="ignore"):
            ent = -seg_pct * (ev_pct * np.log2(ev_pct) + (1 - ev_pct) * np.log2(1 - ev_pct))
        ent = np.where((ev_pct > 0) & (ev_pct < 1), ent, np.nan)
        ig = total_entropy - np.nansum(ent)
        rows.append({"attribute": c, "ig": round(float(ig), 4)})
    odf = pd.DataFrame(rows, columns=["attribute", "ig"])
    if print_impact:
        logger.info(odf.to_string(index=False))
    return odf


def variable_clustering(
    idf: Table,
    list_of_cols="all",
    drop_cols=[],
    sample_size: int = 100000,
    stats_unique: dict = {},
    stats_mode: dict = {},
    persist: bool = True,
    print_impact: bool = False,
) -> pd.DataFrame:
    """[Cluster, Attribute, RS_Ratio] (reference :142-250): drop unique<2
    columns, frequency-ordered label-encode categoricals, mean-impute, then
    VarClus over the device-computed correlation matrix."""
    from anovos_tpu.data_transformer.transformers import cat_to_num_unsupervised, imputation_MMM

    num_all, cat_all, _ = idf.attribute_type_segregation()
    cols = parse_cols(
        list_of_cols if list_of_cols != "all" else num_all + cat_all, idf.col_names, drop_cols
    )
    if not cols:
        raise TypeError("Invalid input for Column(s)")
    if idf.nrows > sample_size:
        from anovos_tpu.data_ingest.data_sampling import data_sample

        idf = data_sample(idf, fraction=float(sample_size) / idf.nrows, method_type="random")
    sub = idf.select(cols)
    # drop constant / single-valued columns (column-bucketed stack; the
    # nunique readback is sliced to the live k)
    from anovos_tpu.shared.table import stack_padded

    vc_masks = [
        cat_valid_mask(sub.columns[c].data, sub.columns[c].mask)
        if sub.columns[c].kind == "cat" else sub.columns[c].mask
        for c in cols
    ]
    X, M = stack_padded([sub.columns[c].data for c in cols], vc_masks)
    nu = np.asarray(masked_nunique(X, M))[: len(cols)]
    cols = [c for c, u in zip(cols, nu) if u >= 2]
    sub = sub.select(cols)
    cat_cols = [c for c in cols if sub.columns[c].kind == "cat"]
    if cat_cols:
        sub = cat_to_num_unsupervised(sub, cat_cols, method_type="label_encoding")
    sub = imputation_MMM(sub, list_of_cols="missing", method_type="mean")
    Xn, Mn = sub.numeric_block(cols)
    # complete-case over live lanes (see correlation_matrix): dead bucketed
    # lanes are mask=False and must not veto rows
    C = np.asarray(masked_corr_cc(Xn, Mn, len(cols)),
                   dtype=np.float64)[: len(cols), : len(cols)]
    # harden for eigendecomposition: f32 device numerics can leave NaNs for
    # near-constant columns (zero-variance denominators) and tiny asymmetry;
    # either makes eigh fail to converge.  masked_corr pins the diagonal to
    # 1.0, so degeneracy shows as all-NaN OFF-diagonal rows.
    offdiag_nan = (~np.isfinite(C)).sum(axis=1) >= max(len(cols) - 1, 1)
    if offdiag_nan.any() and len(cols) > 1:
        warnings.warn(
            "variable_clustering: dropping degenerate column(s) "
            + ",".join(c for c, bad in zip(cols, offdiag_nan) if bad)
        )
        keepm = ~offdiag_nan
        cols = [c for c, k in zip(cols, keepm) if k]
        C = C[np.ix_(keepm, keepm)]
    if not cols:
        warnings.warn("variable_clustering: no usable columns after degeneracy drop")
        return pd.DataFrame(columns=["Cluster", "Attribute", "RS_Ratio"])
    C = np.where(np.isfinite(C), C, 0.0)
    C = (C + C.T) / 2.0
    np.fill_diagonal(C, 1.0)
    corr_df = pd.DataFrame(C, columns=cols, index=cols)
    vc = VarClusJax(corr_df, maxeigval2=1.0, maxclus=None).fit()
    rs = vc.rsquare_table()
    odf = pd.DataFrame(
        {
            "Cluster": rs["Cluster"],
            "Attribute": rs["Variable"],
            "RS_Ratio": np.round(rs["RS_Ratio"].to_numpy(), 4),
        }
    )
    if print_impact:
        logger.info(odf.to_string(index=False))
    return odf
