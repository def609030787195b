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
import threading
import warnings
from types import MappingProxyType
from typing import List

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd

from anovos_tpu.data_analyzer.association_eval_varclus import VarClusJax
from anovos_tpu.obs import get_tracer
from anovos_tpu.ops.correlation import _masked_corr_cc
from anovos_tpu.ops.mxu import bf16_sweep
from anovos_tpu.ops.segment import _block_label_counts_p, cat_valid_mask, count_route, masked_nunique
from anovos_tpu.shared.runtime import get_runtime
from anovos_tpu.shared.table import Table, counted_fetch, stack_masks_padded, stack_padded
from anovos_tpu.shared.utils import parse_cols

# the upstream's default of IV_calculation and IG_calculation, read-only
_ENCODING = MappingProxyType({"bin_method": "equal_frequency", "bin_size": 10, "monotonicity_check": 0})

logger = logging.getLogger(__name__)


def correlation_matrix(
    idf: Table,
    list_of_cols="all",
    drop_cols=(),
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
    C, _ = _complete_case_corr(X, M, len(cols))
    odf = pd.DataFrame(C, columns=cols, index=cols)
    odf["attribute"] = odf.index
    ordered = sorted(cols)
    odf = odf[["attribute"] + ordered].sort_values("attribute").reset_index(drop=True)
    if print_impact:
        logger.info(odf.to_string(index=False))
    return odf


# The stage row on which a correlation states how many rows were complete
# (``complete_rows``): what a comparison of the matrix reads beside it.
COMPLETE_ROWS_ROW = "assoc/corr"


# Every program of the block runs under a ``jax.named_scope`` of its own
# (assoc/cutoffs, assoc/bin_apply, assoc/group_counts, assoc/corr), whatever
# the jitted functions around them are called: a reader of the device trace
# finds the block's seconds by them.  The first two are ``attribute_binning``'s
# programs, run under the scope this module names to them.
@functools.partial(jax.jit, static_argnames=("bf16",))
def _corr_program(X, M, k_live, bf16: bool = False):
    """The complete-case correlation of a column-bucketed block's live lanes
    and the number of complete rows, in one program."""
    with jax.named_scope("assoc/corr"):
        complete = (M.sum(axis=1) == k_live).sum()
        return _masked_corr_cc(X, M, k_live, bf16=bf16), complete


def _complete_case_corr(X, M, k: int):
    """(host (k, k) correlation, complete rows) under the open stage row
    ``assoc/corr`` (``lanes``, ``rows`` as the program takes them)."""
    with get_tracer().phase(COMPLETE_ROWS_ROW, cat="block", lanes=X.shape[1], rows=X.shape[0]) as sp:
        C, complete = jax.device_get(_corr_program(X, M, np.int32(k), bf16=bf16_sweep()))
        sp.add(complete_rows=int(complete))
    return C[:k, :k], int(complete)


@functools.partial(jax.jit, static_argnames=("vocab_size", "dense"))
def _group_counts_program(codes, M, y, ym, vocab_size: int, dense: bool):
    """(labelled rows, events) per code of every column of a (rows, k) block
    of codes, each (k, vocab_size): ``ops/segment.py``'s label counts, once
    with the labelled rows as the weight and once with the events."""
    with jax.named_scope("assoc/group_counts"):
        labelled = ym.astype(jnp.float32)
        events = jnp.where(ym, y, 0.0).astype(jnp.float32)
        return (_block_label_counts_p(codes, M, labelled, vocab_size, dense),
                _block_label_counts_p(codes, M, events, vocab_size, dense),
                labelled.sum(), events.sum())


def _block_label_groups(idf: Table, codes, M, sizes: List[int], y, ym, span):
    """([(label_0, label_1)], the table's events) of the columns of one block of codes (column i has
    ``sizes[i]`` codes; a row that is not valid there is in its null group):
    ONE program and one fetch a block, nothing as long as the table."""
    p, dense = count_route(max(sizes), codes)
    tot, ev, labelled, events = counted_fetch(
        _group_counts_program(codes, M, y, ym, vocab_size=p, dense=dense), idf, span)
    rows, lanes = codes.shape
    # what the program read and wrote, padding included.  Not under the names ``label_rows`` / ``seg_lanes``
    # that the transformers' rows carry: those count bytes of ``ops/segment.py``'s own three programs, and
    # this block's counts run inside a program of this module
    span.add(columns=len(sizes), cells=rows * lanes, block_rows=rows, count_lanes=2 * p * lanes)
    out = []
    for i, size in enumerate(sizes):
        t = np.append(tot[i, :size], labelled - tot[i].sum())  # the null group: labelled rows in no code
        e = np.append(ev[i, :size], events - ev[i].sum())
        keep = t > 0
        out.append((t[keep] - e[keep], e[keep]))
    return out, float(events)


def _value_codes(idf: Table, col: str, span):
    """Host codes of a numeric column grouped by its exact values (an
    unbinned discrete numeric, or the bins of ``monotonic_binning``'s table):
    the column is fetched whole, which its ``host_rows`` say."""
    c = idf.columns[col]
    vals, mask = counted_fetch((c.data, c.mask), idf, span)
    uniq, inv = np.unique(vals[: idf.nrows][mask[: idf.nrows]], return_inverse=True)
    codes = np.full(idf.padded_rows, -1, np.int32)
    codes[: idf.nrows][mask[: idf.nrows]] = inv.astype(np.int32)
    return codes, max(len(uniq), 1)


def _label_groups(idf: Table, cols: List[str], label_col, event_label, encoding_configs):
    """({attribute: (label_0, label_1)}, the table's events): the labelled rows without and with the
    event in every group of every attribute: its categories or its bins, and
    the rows where it is null as one more group (Spark's groupBy keeps nulls).

    Memoized on the table, single-flight, as ``table_describe`` is:
    ``IV_calculation`` and ``IG_calculation`` of one pass ask for the same
    counts of the same table, and the second takes the first's (its node
    waits under ``assoc/wait`` where both run at once)."""
    from anovos_tpu.data_transformer.transformers import _event_vector, bin_block, binning_cutoffs, monotonic_binning

    enc = dict(encoding_configs or {})
    key = (tuple(cols), label_col, str(event_label), tuple(sorted(enc.items())))
    tracer = get_tracer()
    lock = idf.__dict__.setdefault("_assoc_lock", threading.Lock())
    with tracer.holding(lock, "assoc/wait", cat="block"):
        cache = idf.__dict__.setdefault("_assoc_cache", {})
        if key in cache:
            return cache[key]
        y, ym = _event_vector(idf, label_col, event_label)
        cat_cols = [c for c in cols if idf.columns[c].kind == "cat"]
        num_cols = [c for c in cols if idf.columns[c].kind == "num"]
        blocks = []  # (columns, codes, mask, sizes)
        # the numeric columns: binned on the device, or grouped by their exact values on the host (no
        # ``encoding_configs``; or the monotonicity search, whose bin count differs by column: the transformer's table)
        searched = bool(num_cols and enc) and enc.get("monotonicity_check", 0) == 1
        binned = bool(num_cols and enc) and not searched
        by_value, table = ([] if binned else num_cols), idf
        if searched:
            table = monotonic_binning(
                idf, num_cols, [], label_col=label_col, event_label=event_label,
                bin_method=enc.get("bin_method", "equal_frequency"), bin_size=enc.get("bin_size", 10))
        if binned:
            bin_size = enc.get("bin_size", 10)
            X, M = idf.numeric_block(num_cols)
            with tracer.phase("assoc/bin", cat="block", columns=len(num_cols), lanes=X.shape[1], rows=X.shape[0]):
                # attribute_binning's own cut-offs and bins, a cell's bin minus one as its code
                cutoffs = binning_cutoffs(X, M, len(num_cols), enc.get("bin_method", "equal_frequency"), bin_size,
                                          scope="assoc/cutoffs")
                codes = jax.block_until_ready(bin_block(X, cutoffs, scope="assoc/bin_apply")[0])
            blocks.append((num_cols, codes, M, [bin_size] * len(num_cols)))
        with tracer.phase("assoc/group_counts", cat="block") as sp:
            if by_value:
                host = [_value_codes(table, c, sp) for c in by_value]
                codes = get_runtime().shard_rows(np.stack([h[0] for h in host], axis=1))
                blocks.append((by_value, codes, stack_masks_padded([table.columns[c].mask for c in by_value],
                                                                   pad_cols=False), [h[1] for h in host]))
            if cat_cols:
                codes, M = stack_padded([idf.columns[c].data for c in cat_cols],
                                        [idf.columns[c].mask for c in cat_cols], dtype=jnp.int32)
                blocks.append((cat_cols, codes, M, [max(len(idf.columns[c].vocab), 1) for c in cat_cols]))
            groups, events = {}, 0.0
            for names, codes, M, sizes in blocks:  # one program in flight at a time: on a mesh each ends in a collective
                found, events = _block_label_groups(idf, codes, M, sizes, y, ym, sp)
                groups.update(zip(names, found))
        cache[key] = groups, events
    return cache[key]


def _attributes(idf: Table, list_of_cols, drop_cols, label_col) -> List[str]:
    num_all, cat_all, _ = idf.attribute_type_segregation()
    cols = parse_cols(
        list_of_cols if list_of_cols != "all" else num_all + cat_all, idf.col_names, drop_cols
    )
    cols = [c for c in cols if c != label_col]
    if not cols:
        raise TypeError("Invalid input for Column(s)")
    return cols


def IV_calculation(
    idf: Table,
    list_of_cols="all",
    drop_cols=(),
    label_col: str = "label",
    event_label=1,
    encoding_configs=_ENCODING,
    print_impact: bool = False,
) -> pd.DataFrame:
    """[attribute, iv] Information Value (reference :253-424):
    IV = Σ (%nonevent − %event)·WOE, WOE = ln(%nonevent/%event) with 0.5
    continuity correction when a bin has zero events or non-events."""
    cols = _attributes(idf, list_of_cols, drop_cols, label_col)
    groups, _ = _label_groups(idf, cols, label_col, event_label, encoding_configs)
    rows = []
    for c in cols:
        l0, l1 = groups[c]
        t0, t1 = l0.sum(), l1.sum()
        if t0 == 0 or t1 == 0:
            rows.append({"attribute": c, "iv": np.nan})
            continue
        ev_pcr = l1 / t1
        nev_pcr = l0 / t0
        with np.errstate(divide="ignore", over="ignore"):  # a group of one class: the other branch is taken
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
    drop_cols=(),
    label_col: str = "label",
    event_label=1,
    encoding_configs=_ENCODING,
    print_impact: bool = False,
) -> pd.DataFrame:
    """[attribute, ig] Information Gain = total entropy − Σ segment entropy
    (reference :427-585).  Segments with event_pct ∈ {0,1} contribute 0
    (Spark's null log2 is dropped by F.sum)."""
    cols = _attributes(idf, list_of_cols, drop_cols, label_col)
    groups, events = _label_groups(idf, cols, label_col, event_label, encoding_configs)
    total_event = events / max(idf.nrows, 1)
    if total_event in (0.0, 1.0):
        warnings.warn("IG undefined: label has a single class")
        return pd.DataFrame({"attribute": cols, "ig": [np.nan] * len(cols)})
    total_entropy = -(
        total_event * math.log2(total_event) + (1 - total_event) * math.log2(1 - total_event)
    )
    rows = []
    for c in cols:
        l0, l1 = groups[c]
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
    drop_cols=(),
    sample_size: int = 100000,
    stats_unique: dict = MappingProxyType({}),
    stats_mode: dict = MappingProxyType({}),
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
    tracer = get_tracer()
    with tracer.phase("assoc/prep", cat="block", columns=len(cols)) as sp:  # sample, drop, encode, fill
        if idf.nrows > sample_size:
            from anovos_tpu.data_ingest.data_sampling import data_sample

            idf = data_sample(idf, fraction=float(sample_size) / idf.nrows, method_type="random")
        sub = idf.select(cols)
        # drop constant / single-valued columns (column-bucketed stack; the
        # nunique readback is sliced to the live k)
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
        sp.add(sample_rows=sub.nrows, kept=len(cols))
    # complete-case over live lanes (see correlation_matrix): dead bucketed
    # lanes are mask=False and must not veto rows
    C = _complete_case_corr(Xn, Mn, len(cols))[0].astype(np.float64)
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
    with tracer.phase("assoc/varclus", cat="block", columns=len(cols), sample_rows=sub.nrows) as sp:  # on the host
        vc = VarClusJax(corr_df, maxeigval2=1.0, maxclus=None).fit()
        rs = vc.rsquare_table()
        sp.add(clusters=int(rs["Cluster"].nunique()))
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
