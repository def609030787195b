"""Covariate-drift statistics (reference: drift_stability/drift_detector.py:18).

The BASELINE comparison target.  Mechanism (reference :216-344): bin the
source with cutoffs persisted as a binning model, apply the same cutoffs to
the target, build per-column relative-frequency tables p/q with 0→0.0001
smoothing, then PSI / Hellinger / JSD / KS per column.

TPU shape (SURVEY.md §3.4) with dispatch-count discipline: per dataset side
the ENTIRE histogram computation — every numeric column binned + every
categorical column counted — is one fused jitted program
(ops/drift_kernels.py); cutoff fitting is one more.  The reference's
thousands of Spark jobs become ~5 device dispatches total, and the metric
arithmetic is vectorized host numpy over the (cols × bins) arrays.
"""

from __future__ import annotations

import logging

import os
import warnings
from typing import Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd

from anovos_tpu.drift_stability.validations import check_distance_method
from anovos_tpu.obs import get_tracer
from anovos_tpu.shared.table import Table
from anovos_tpu.shared.utils import parse_cols

logger = logging.getLogger(__name__)

_SMOOTH = 0.0001


def load_frequency_map(model_dir: str, col: str) -> Optional[Dict[str, float]]:
    """{key: probability} from one column's persisted source-frequency
    CSV (``<model_dir>/frequency_counts/<col>/part-00000.csv``), or None
    when absent.  THE read path for the persisted drift model — shared by
    the in-memory ``pre_existing_source`` branch, the streaming variant,
    and the continuum feed, so the on-disk format has exactly one parser
    (keys kept verbatim as strings; pandas numeric inference would mangle
    "01" vs "1" vocab keys)."""
    path = os.path.join(model_dir, "frequency_counts", col, "part-00000.csv")
    if not os.path.exists(path):
        return None
    f = pd.read_csv(path, dtype=str)
    kcol = f.columns[0]
    return dict(zip(f[kcol].astype(str), f["p"].astype(float)))


def save_frequency_map(model_dir: str, col: str, keys, p) -> None:
    """Persist one column's source frequencies — the write half of
    :func:`load_frequency_map`, byte-compatible with every prior round's
    model layout."""
    d = os.path.join(model_dir, "frequency_counts", col)
    os.makedirs(d, exist_ok=True)
    pd.DataFrame({col: keys, "p": p}).to_csv(
        os.path.join(d, "part-00000.csv"), index=False)


def _freqs_to_metrics(p: np.ndarray, q: np.ndarray, methods: List[str]) -> dict:
    """Vectorized drift metrics over (k, nb) frequency arrays with the
    reference's 0→0.0001 smoothing (:266-271)."""
    p = np.where(p <= 0, _SMOOTH, p)
    q = np.where(q <= 0, _SMOOTH, q)
    out = {}
    if "PSI" in methods:
        out["PSI"] = ((p - q) * np.log(p / q)).sum(axis=1)
    if "HD" in methods:
        out["HD"] = np.sqrt(((np.sqrt(p) - np.sqrt(q)) ** 2).sum(axis=1) / 2)
    if "JSD" in methods:
        m = (p + q) / 2
        out["JSD"] = ((p * np.log(p / m)).sum(axis=1) + (q * np.log(q / m)).sum(axis=1)) / 2
    if "KS" in methods:
        out["KS"] = np.abs(np.cumsum(p, axis=1) - np.cumsum(q, axis=1)).max(axis=1)
    return out


def _drop_allnan_cutoffs(cutoffs: np.ndarray, cols: List[str]):
    """Drop columns whose every cutoff is NaN (all-null in source) with the
    reference's warning.  Returns (cutoffs, cols, keep mask)."""
    cutoffs = np.asarray(cutoffs, np.float64)
    keep = ~np.isnan(cutoffs).all(axis=1)
    if not keep.all():
        dropped = [c for c, k in zip(cols, keep) if not k]
        warnings.warn("Columns contains too much null values. Dropping " + ", ".join(dropped))
    return cutoffs[keep], [c for c, k in zip(cols, keep) if k], keep


def statistics(
    idf_target: Table,
    idf_source: Optional[Table] = None,
    list_of_cols="all",
    drop_cols=None,
    method_type: str = "PSI",
    bin_method: str = "equal_range",
    bin_size: int = 10,
    threshold: float = 0.1,
    use_sampling: bool = True,
    sample_method: str = "random",
    strata_cols="all",
    stratified_type: str = "population",
    sample_size: int = 100000,
    sample_seed: int = 42,
    pre_existing_source: bool = False,
    source_save: bool = True,
    source_path: str = "NA",
    model_directory: str = "drift_statistics",
    print_impact: bool = False,
    **_ignored,
) -> pd.DataFrame:
    """[attribute, <PSI|HD|JSD|KS…>, flagged] drift between source and target.

    With ``pre_existing_source=True`` the persisted binning model and source
    frequency CSVs under ``source_path/model_directory`` are reused and
    ``idf_source`` may be None (reference :245-250 source-free re-runs).
    """
    methods = check_distance_method(method_type)
    drop_cols = drop_cols or []
    num_all, cat_all, _ = idf_target.attribute_type_segregation()
    cols = parse_cols(
        list_of_cols if list_of_cols != "all" else num_all + cat_all,
        idf_target.col_names,
        drop_cols,
    )
    if idf_source is not None and not pre_existing_source:
        # a string column without a value in one dataset: ingest types an all-null
        # column numeric whatever its file said, and the other side has its values
        kinds = {c: (idf_target.columns[c].kind, idf_source.columns[c].kind) for c in cols}
        idf_target = _empty_as_strings(idf_target, [c for c, k in kinds.items() if k == ("num", "cat")])
        idf_source = _empty_as_strings(idf_source, [c for c, k in kinds.items() if k == ("cat", "num")])
    num_cols = [c for c in cols if idf_target.columns[c].kind == "num"]
    cat_cols = [c for c in cols if idf_target.columns[c].kind == "cat"]
    if source_path == "NA":
        source_path = "intermediate_data"
    model_dir = os.path.join(source_path, model_directory)

    if use_sampling:
        from anovos_tpu.data_ingest.data_sampling import data_sample

        if idf_target.nrows > sample_size:
            idf_target = data_sample(
                idf_target, strata_cols=strata_cols, fraction=sample_size / idf_target.nrows,
                method_type=sample_method, stratified_type=stratified_type, seed_value=sample_seed,
            )
        if not pre_existing_source and idf_source is not None and idf_source.nrows > sample_size:
            idf_source = data_sample(
                idf_source, strata_cols=strata_cols, fraction=sample_size / idf_source.nrows,
                method_type=sample_method, stratified_type=stratified_type, seed_value=sample_seed,
            )

    count_target = idf_target.nrows
    from anovos_tpu.data_transformer.model_io import load_model_df, save_model_df
    from anovos_tpu.ops.drift_kernels import cutoffs_from_bounds, device_cutoffs, drift_side_full, fit_bounds
    from anovos_tpu.ops.segment import bucket_segments_pow2
    from anovos_tpu.shared.runtime import get_runtime

    # single-device meshes have no collectives, so the cutoff-fit and both
    # side programs can be pipelined on device with ONE host sync at the end;
    # multi-device stays strictly sequential (two collective programs in
    # flight can interleave their rendezvous — see Table.gather_rows)
    one_device = get_runtime().n_devices == 1
    # equal_range cut-offs are float64 arithmetic on the host over the source's fetched bounds
    # (cutoffs_from_bounds); equal_frequency ones are values of the column and stay on the device
    pipeline_ok = bool(one_device and not pre_existing_source and num_cols and bin_method != "equal_range")

    # the cutoffs fitted on the source (dispatched; fetched with the sides where one chip
    # pipelines them) or the saved model read back, and the union vocabularies on the host
    phase = get_tracer().phase
    with phase("drift/fit", cat="block", cols=len(cols)):
        # ---- numeric cutoffs: fit on source (1 kernel) or load the model ------
        num_cols_eff = list(num_cols)
        cutoffs = None
        cuts_d = None
        if num_cols:
            if pre_existing_source:
                dfm = load_model_df(model_dir, "attribute_binning")
                cut_map = {r["attribute"]: list(r["parameters"]) for _, r in dfm.iterrows()}
                num_cols_eff = [c for c in num_cols if c in cut_map]
                cutoffs = np.array([cut_map[c] for c in num_cols_eff], dtype=np.float64)
            elif bin_method == "equal_range":
                lo, hi, n = jax.device_get(fit_bounds(*_padded_col_tuples(idf_source, num_cols)))
                k = len(num_cols)  # the column bucket's dead lanes are no dropped columns
                cutoffs, num_cols_eff, _ = _drop_allnan_cutoffs(
                    cutoffs_from_bounds(lo[:k], hi[:k], n[:k], bin_size), num_cols)
            else:
                cuts_d = _fit_cutoffs_dev(idf_source, num_cols, bin_size, bin_method)
                if not pipeline_ok:
                    # slice the column-bucketed fit back to the live columns
                    # BEFORE the all-NaN drop — the dead lanes are all-NaN by
                    # construction and must not masquerade as dropped columns
                    cutoffs, num_cols_eff, _ = _drop_allnan_cutoffs(
                        np.asarray(cuts_d)[: len(num_cols)], num_cols
                    )

        # ---- union vocabularies for categorical columns -----------------------
        union_vocabs: Dict[str, np.ndarray] = {}
        freq_p: Dict[str, np.ndarray] = {}
        if pre_existing_source:
            for c in cols:
                smap = load_frequency_map(model_dir, c)
                if smap is None:
                    # e.g. a column the fit run dropped (all-null in source)
                    warnings.warn(f"drift statistics: no persisted source frequencies for {c}; skipping")
                    continue
                if c in num_cols_eff:
                    freq_p[c] = np.array([smap.get(str(k), 0.0) for k in range(1, bin_size + 1)])
                elif c in cat_cols:
                    tgt_vocab = {str(v) for v in idf_target.columns[c].vocab}
                    uni = np.array(sorted(set(smap) | tgt_vocab), dtype=object)
                    union_vocabs[c] = uni
                    freq_p[c] = np.array([smap.get(str(v), 0.0) for v in uni])
                # numeric columns absent from the binning model are skipped
            cat_cols = [c for c in cat_cols if c in union_vocabs]

    # a ``str`` and a set entry a value of either side's vocabulary, then the union sorted
    if not pre_existing_source:
        with phase("drift/union", cat="block", cols=len(cat_cols)) as sp:
            union_vocabs = _union_vocabs_for(idf_source, idf_target, cat_cols)
            sp.add(values=sum(len(idf.columns[c].vocab) for idf in (idf_source, idf_target) for c in cat_cols))

    # ---- ONE fused program per dataset side --------------------------------
    # the lanes of the union histograms are a power of two, as the LUT's are: two seeds'
    # unions differ by a few values, and an exact size made each compile its own side program
    n_union = bucket_segments_pow2(max((len(union_vocabs[c]) for c in cat_cols), default=1))
    # a dict entry a value of the union and a look-up a local value, once a side
    sides = [idf_target] if pre_existing_source else [idf_target, idf_source]
    with phase("drift/lut", cat="block", cols=len(cat_cols), sides=len(sides)) as sp:
        luts = [_lut_for(idf, cat_cols, union_vocabs) for idf in sides]
        sp.add(values=sum(len(union_vocabs[c]) + len(idf.columns[c].vocab) for idf in sides for c in cat_cols))
    if pipeline_ok:
        cuts_dev = cuts_d  # stays on device; NaN rows dropped post-hoc
        num_cols_eff = list(num_cols)
    else:
        cuts_dev = jnp.asarray(device_cutoffs(cutoffs)) if num_cols_eff else jnp.zeros((0, bin_size - 1))

    # both sides' histograms: two programs, one fetch where the fit stayed on the device
    live = len(num_cols_eff) + len(cat_cols)
    with phase("drift/sides", cat="block", rows=idf_target.padded_rows, cols=live,
               # what no implementation of the histograms can avoid moving (the benchmark's drift_hist_hbm_pct):
               # every live column's cell once a side, the cut-offs, the counts at their own sizes
               cells=sum(idf.padded_rows for idf in sides) * live,
               cutoffs=len(sides) * len(num_cols_eff) * (bin_size - 1),
               hist_lanes=len(sides) * (len(num_cols_eff) * bin_size + sum(len(union_vocabs[c]) for c in cat_cols))):
        def side(i: int, sync: bool = True):
            out = drift_side_full(
                *_side_args(sides[i], num_cols_eff, cat_cols, cuts_dev, luts[i], bin_size, n_union)
            )
            return jax.device_get(out) if sync else out

        if pipeline_ok:
            # async dispatch of all three programs, one host sync
            tgt_pair = side(0, sync=False)
            src_pair = side(1, sync=False)
            cutoffs, (tgt_num, tgt_cat), (src_num, src_cat) = jax.device_get(
                (cuts_dev, tgt_pair, src_pair)
            )
            # live-column slice first (column-bucketed dead lanes are all-NaN
            # cutoffs + all-zero histogram rows), then the real all-null drop
            k_live = len(num_cols_eff)
            cutoffs, num_cols_eff, keep = _drop_allnan_cutoffs(cutoffs[:k_live], num_cols_eff)
            tgt_num = tgt_num[:k_live][keep]
            src_num = src_num[:k_live][keep]
        elif one_device and not pre_existing_source:
            # no collectives on one device: both programs dispatched, one host sync
            (tgt_num, tgt_cat), (src_num, src_cat) = jax.device_get((side(0, sync=False), side(1, sync=False)))
        else:
            tgt_num, tgt_cat = side(0)
            if not pre_existing_source:
                src_num, src_cat = side(1)

    # the binning model and the source frequencies, a file a column
    with phase("drift/model", cat="block", cols=len(num_cols_eff) + len(cat_cols)) as sp_model:
        if not pre_existing_source and cutoffs is not None:
            save_model_df(
                pd.DataFrame(
                    {"attribute": num_cols_eff, "parameters": [list(map(float, c)) for c in cutoffs]}
                ),
                model_dir,
                "attribute_binning",
            )

        freq_q: Dict[str, np.ndarray] = {}
        for i, c in enumerate(num_cols_eff):
            freq_q[c] = tgt_num[i] / max(count_target, 1)
        for j, c in enumerate(cat_cols):
            freq_q[c] = tgt_cat[j][: len(union_vocabs[c])] / max(count_target, 1)

        if not pre_existing_source:
            for i, c in enumerate(num_cols_eff):
                freq_p[c] = src_num[i] / max(idf_source.nrows, 1)
            for j, c in enumerate(cat_cols):
                freq_p[c] = src_cat[j][: len(union_vocabs[c])] / max(idf_source.nrows, 1)
            if source_save:
                values = 0
                for c in num_cols_eff + cat_cols:
                    keys = (
                        list(range(1, bin_size + 1)) if c in num_cols_eff else list(union_vocabs[c])
                    )
                    save_frequency_map(model_dir, c, keys, freq_p[c])
                    values += len(keys)
                sp_model.add(values=values)  # a line of a column's CSV each

    with phase("drift/frame", cat="block", cols=len(cols)):
        odf = _metrics_frame(freq_p, freq_q, cols, methods, threshold)
    if print_impact:
        logger.info(odf.to_string(index=False))
    return odf


def _metrics_frame(freq_p: Dict[str, np.ndarray], freq_q: Dict[str, np.ndarray],
                   cols: List[str], methods: List[str],
                   threshold: float) -> pd.DataFrame:
    """Vectorized metrics over padded (k, max_bins) arrays — the shared
    tail of the in-memory and streaming drift paths (one rounding/
    flagging policy, so the two are byte-identical given equal
    frequencies)."""
    cols_eff = [c for c in cols if c in freq_p and c in freq_q]
    if not cols_eff:
        return pd.DataFrame(columns=["attribute"] + methods + ["flagged"])
    nb = max(len(freq_p[c]) for c in cols_eff)
    P = np.full((len(cols_eff), nb), _SMOOTH)
    Q = np.full((len(cols_eff), nb), _SMOOTH)
    for i, c in enumerate(cols_eff):
        P[i, : len(freq_p[c])] = freq_p[c]
        q = freq_q[c]
        if len(q) < len(freq_p[c]):  # pre-existing source saw more categories
            q = np.concatenate([q, np.zeros(len(freq_p[c]) - len(q))])
        Q[i, : len(q)] = q
    # padding lanes hold equal smoothing on both sides → zero contribution
    mets = _freqs_to_metrics(P, Q, methods)
    odf = pd.DataFrame({"attribute": cols_eff})
    for m in methods:
        odf[m] = np.round(mets[m], 4)
    odf["flagged"] = (odf[methods] > threshold).any(axis=1).astype(int)
    return odf


def _empty_as_strings(idf: Table, names: List[str]) -> Table:
    """``idf`` with each of ``names``, numeric there, as a string column of no
    values.  Ingest types a column without a value numeric (all NaN) whatever
    its file declared, so a string column that is empty in one dataset and
    filled in the other arrives as two kinds; with a value on the numeric side
    the two datasets disagree on the type and there is nothing to compare."""
    from anovos_tpu.shared.table import Column

    swaps = []
    for c in names:
        col = idf.columns[c]
        if bool(jnp.any(col.mask)):
            raise TypeError(f"drift statistics: {c} is numeric in one dataset and categorical in the other")
        swaps.append((c, Column("cat", jnp.where(col.mask, 0, -1).astype(jnp.int32), col.mask,
                                vocab=np.array([], dtype=object), dtype_name="string")))
    return idf.with_columns(swaps) if swaps else idf


def _padded_col_tuples(idf: Table, cols: List[str]):
    """(datas, masks) tuples extended to the column-bucketed lane count.

    The drift programs stack raw column tuples INSIDE the jit, so the tuple
    arity is the program key — extending it to ``Runtime.pad_cols`` makes
    nearby column counts share one compiled side program, the same contract
    as ``Table.numeric_block``.  Dead lanes reuse the first column's data
    array (free — no new device buffer) under an all-False mask, so every
    histogram count in those lanes is zero; host consumers slice back to
    the live k.
    """
    from anovos_tpu.shared.runtime import get_runtime

    datas = [idf.columns[c].data for c in cols]
    masks = [idf.columns[c].mask for c in cols]
    k_pad = get_runtime().pad_cols(len(cols))
    if datas and k_pad > len(datas):
        dead = jnp.zeros_like(masks[0])
        datas.extend([datas[0]] * (k_pad - len(cols)))
        masks.extend([dead] * (k_pad - len(cols)))
    return tuple(datas), tuple(masks)


def _fit_cutoffs_dev(idf_source: Table, num_cols: List[str], bin_size: int, bin_method: str):
    """Device cutoff fit over the source side's column arrays (one kernel).
    Column-bucketed: dead lanes fit all-null cutoffs (NaN rows, sliced off
    by the caller before ``_drop_allnan_cutoffs``)."""
    from anovos_tpu.ops.drift_kernels import fit_cutoffs

    return fit_cutoffs(*_padded_col_tuples(idf_source, num_cols), bin_size, bin_method)


def _union_vocabs_for(idf_source: Table, idf_target: Table, cat_cols: List[str]):
    """Per-column union vocabulary over both sides (string-keyed, sorted)."""
    return {
        c: np.array(
            sorted(
                {str(v) for v in idf_source.columns[c].vocab}
                | {str(v) for v in idf_target.columns[c].vocab}
            ),
            dtype=object,
        )
        for c in cat_cols
    }


def _lut_for(idf: Table, cat_cols: List[str], union_vocabs: Dict[str, np.ndarray]):
    """(k, maxv) LUT mapping each column's LOCAL codes to union indices.

    ``maxv`` is bucketed to a 2^k size class (``bucket_segments_pow2`` —
    NOT the coarse {16, 256, …} vocab classes, because the LUT is a real
    (k, maxv) matrix whose dead lanes cost bytes): the two dataset sides
    usually differ only in their max local vocab size, and an unbucketed
    maxv made each side compile its own ``drift_side_full`` program."""
    from anovos_tpu.ops.segment import bucket_segments_pow2

    if not cat_cols:
        return jnp.zeros((0, 1), jnp.int32)
    maxv = max(max(len(idf.columns[c].vocab), 1) for c in cat_cols)
    maxv = bucket_segments_pow2(maxv)
    luts = np.zeros((len(cat_cols), maxv), np.int32)
    for j, c in enumerate(cat_cols):
        pos = {v: i for i, v in enumerate(union_vocabs[c])}
        for i, v in enumerate(idf.columns[c].vocab):
            luts[j, i] = pos[str(v)]
    return jnp.asarray(luts)


def _side_args(
    idf: Table,
    num_cols: List[str],
    cat_cols: List[str],
    cuts_dev,
    lut,
    bin_size: int,
    n_union: int,
):
    """The ``drift_side_full`` argument tuple ``statistics`` dispatches for
    one dataset side.

    Column-bucketed (``_padded_col_tuples``): both tuple families are
    extended to their lane classes, the cutoff matrix rows pad with NaN and
    the LUT rows with zeros — dead lanes produce all-zero histogram rows
    which the metric assembly never reads (it indexes the live columns)."""
    num_datas, num_masks = _padded_col_tuples(idf, num_cols)
    cat_datas, cat_masks = _padded_col_tuples(idf, cat_cols)
    k_num_pad = len(num_datas)
    if num_cols and k_num_pad > int(cuts_dev.shape[0]):
        cuts_dev = jnp.pad(
            cuts_dev.astype(jnp.float32),
            ((0, k_num_pad - int(cuts_dev.shape[0])), (0, 0)),
            constant_values=jnp.nan,
        )
    k_cat_pad = len(cat_datas)
    if cat_cols and k_cat_pad > int(lut.shape[0]):
        lut = jnp.pad(lut, ((0, k_cat_pad - int(lut.shape[0])), (0, 0)))
    return (
        num_datas,
        num_masks,
        cuts_dev,
        cat_datas,
        cat_masks,
        lut,
        bin_size,
        max(n_union, 1),
    )


# ---------------------------------------------------------------------------
# out-of-core streaming drift (round 12): the two-pass histogram machinery
# applied chunkwise over the prefetch iterator — source cutoffs fitted from
# streamed global bounds (cutoffs_from_bounds, the in-memory fit's own arithmetic),
# per-chunk binned counts summed exactly, categorical counts tallied
# host-side — so a dataset that never fits in memory produces the SAME
# drift frame and the SAME persisted binning/frequency model, byte for
# byte, as the in-memory path (use_sampling=False).
# ---------------------------------------------------------------------------
def _drift_side_host_part(df: pd.DataFrame, cat_cols: List[str]) -> dict:
    """Host partial of one raw chunk: live row count + per-categorical
    value counts (string-keyed, exactly the union-vocab key space the
    in-memory LUT remap counts into)."""
    out = {"rows": np.asarray(len(df), np.int64)}
    for j, c in enumerate(cat_cols):
        vc = df[c].dropna().astype(str).value_counts()
        out[f"cat{j}_v"] = vc.index.to_numpy(dtype="U")
        out[f"cat{j}_n"] = vc.to_numpy(np.int64)
    return out


def _merge_side_parts(parts: dict, cat_cols: List[str]):
    """(total rows, per-column value Counter, moment partial list) from a
    pass' committed partials."""
    from collections import Counter

    rows = 0
    counters = [Counter() for _ in cat_cols]
    for i in sorted(parts):
        p = parts[i]
        rows += int(p["rows"])
        for j in range(len(cat_cols)):
            vals = p.get(f"cat{j}_v")
            cnts = p.get(f"cat{j}_n")
            if vals is None:
                continue
            for v, n in zip(vals, cnts):
                counters[j][str(v)] += int(n)
    return rows, counters


def statistics_streaming(
    file_path: str,
    file_type: str,
    source_file_path: Optional[str] = None,
    list_of_cols="all",
    drop_cols=None,
    method_type: str = "PSI",
    bin_method: str = "equal_range",
    bin_size: int = 10,
    threshold: float = 0.1,
    chunk_rows: int = 1_000_000,
    file_configs: Optional[dict] = None,
    pre_existing_source: bool = False,
    source_save: bool = True,
    source_path: str = "NA",
    model_directory: str = "drift_statistics",
    checkpoint_dir: Optional[str] = None,
    resume: bool = False,
    print_impact: bool = False,
) -> pd.DataFrame:
    """Streaming ``statistics``: drift between two part-file datasets of
    ANY size (passes: source bounds+cat counts → source histograms →
    target histograms; device residency O(chunk_rows·k) throughout).

    Restrictions vs the in-memory path: ``bin_method`` must be
    ``equal_range`` when fitting (equal_frequency needs exact whole-table
    quantiles) and there is no sampling — parity target is
    ``statistics(..., use_sampling=False)``.  With
    ``pre_existing_source=True`` the persisted binning model and source
    frequency CSVs are reused and only the target streams.  With
    ``checkpoint_dir``/``resume`` every chunk of every pass commits —
    a mid-run kill resumes re-reading only undone chunks, and a cutoff
    shift (a quarantined source part came back) invalidates exactly the
    histogram passes binned over the stale edges."""
    from anovos_tpu.data_ingest.data_ingest import _resolve_files
    from anovos_tpu.data_ingest.guard import IngestError
    from anovos_tpu.data_ingest.prefetch import StreamController, StreamStats
    from anovos_tpu.data_transformer.model_io import load_model_df, save_model_df
    from anovos_tpu.ops import streaming as st
    from anovos_tpu.ops.drift_kernels import binned_histograms, cutoffs_from_bounds, device_cutoffs
    from anovos_tpu.shared.runtime import get_runtime
    from anovos_tpu.shared.utils import parse_cols as _parse

    methods = check_distance_method(method_type)
    drop_cols = drop_cols or []
    cfg = dict(file_configs or {})
    if not pre_existing_source:
        if source_file_path is None:
            raise ValueError(
                "statistics_streaming: source_file_path required unless "
                "pre_existing_source=True")
        if bin_method != "equal_range":
            raise ValueError(
                "statistics_streaming fits cutoffs from streamed global "
                "bounds — only bin_method='equal_range' is supported "
                "(equal_frequency needs exact whole-table quantiles)")
    if source_path == "NA":
        source_path = "intermediate_data"
    model_dir = os.path.join(source_path, model_directory)

    tgt_files = _resolve_files(file_path, file_type)
    src_files = _resolve_files(source_file_path, file_type) \
        if source_file_path else []
    schema = st.stream_schema(tgt_files, file_type, cfg)
    all_names = [c for c, _k in schema]
    num_all = [c for c, k in schema if k == "num"]
    cat_all = [c for c, k in schema if k == "cat"]
    cols = _parse(list_of_cols if list_of_cols != "all" else num_all + cat_all,
                  all_names, drop_cols)
    num_cols = [c for c in cols if c in num_all]
    cat_cols = [c for c in cols if c in cat_all]

    ctl, stats = StreamController(), StreamStats()
    ckpt = None
    if checkpoint_dir:
        ckpt = st.StreamCheckpoint(
            checkpoint_dir,
            st._stream_sig(
                tgt_files + src_files, file_type, cols, chunk_rows, bin_size,
                op=f"drift:{method_type}:{bin_method}:{pre_existing_source}"),
            resume=resume)
    # pass-scoped invalidation: source passes (1, 2) number chunks over
    # the source files, the target pass (3) over the target files — a
    # shift in one set must not unlink the other's intact partials.  A
    # source shift that moves the CUTOFFS stales pass 3 too; check_bounds
    # below owns that cross-set dependency.
    on_rows_src = st.checkpoint_on_file_rows(ckpt, passes=(1, 2))
    on_rows_tgt = st.checkpoint_on_file_rows(ckpt, passes=(3,))

    def _skip(pass_no):
        return ckpt.committed(pass_no) if (ckpt is not None and resume) \
            else frozenset()

    # ---- numeric cutoffs + source frequencies -----------------------------
    union_vocabs: Dict[str, np.ndarray] = {}
    freq_p: Dict[str, np.ndarray] = {}
    num_cols_eff = list(num_cols)
    cutoffs = None
    src_rows = 0
    src_counters = None
    if pre_existing_source:
        dfm = load_model_df(model_dir, "attribute_binning")
        cut_map = {r["attribute"]: list(r["parameters"]) for _, r in dfm.iterrows()}
        num_cols_eff = [c for c in num_cols if c in cut_map]
        cutoffs = np.array([cut_map[c] for c in num_cols_eff], dtype=np.float64)
    else:
        parts1 = st._run_pass(
            src_files, file_type, num_cols, chunk_rows, cfg,
            pass_no=1,
            dispatch=lambda v, m: st._chunk_stats(jnp.asarray(v), jnp.asarray(m)),
            host_part=lambda df: _drift_side_host_part(df, cat_cols),
            ctl=ctl, stats=stats, ckpt=ckpt, skip_chunks=_skip(1),
            on_file_rows=on_rows_src)
        if not parts1:
            raise IngestError(
                f"statistics_streaming: no readable rows in "
                f"{len(src_files)} source part file(s)")
        src_rows, src_counters = _merge_side_parts(parts1, cat_cols)
        if num_cols:
            agg = st._pairwise_merge([parts1[i] for i in sorted(parts1)])
            cuts_full = cutoffs_from_bounds(
                np.asarray(agg["min"], np.float32), np.asarray(agg["max"], np.float32), agg["n"], bin_size)
            cutoffs, num_cols_eff, _ = _drop_allnan_cutoffs(
                cuts_full[: len(num_cols)], num_cols)
        else:
            num_cols_eff = []

    # histogram passes are binned over THESE edges: a cutoff shift since
    # the prior run (or a changed model) stales every committed histogram
    # chunk, including ones upstream of the file that shifted
    if ckpt is not None:
        edges = (np.asarray(cutoffs, np.float64)
                 if cutoffs is not None and len(num_cols_eff)
                 else np.zeros((0, max(bin_size - 1, 1))))
        ckpt.check_bounds(edges.astype(np.float32),
                          np.asarray([bin_size], np.float32),
                          passes=(3,) if pre_existing_source else (2, 3))

    cuts_pad = None
    k_pad = 0
    if num_cols_eff:
        k_pad = get_runtime().pad_cols(len(num_cols_eff))
        cuts_pad = np.full((k_pad, bin_size - 1), np.nan, np.float32)
        cuts_pad[: len(num_cols_eff)] = device_cutoffs(cutoffs)

    def _hist_dispatch(v, m):
        return {"hist": binned_histograms(
            jnp.asarray(v), jnp.asarray(m), jnp.asarray(cuts_pad), bin_size)}

    def _sum_hists(parts) -> Optional[np.ndarray]:
        if not parts:
            return None
        out = None
        for i in sorted(parts):
            h = parts[i]["hist"].astype(np.float32)
            out = h if out is None else out + h
        return out

    # ---- source histograms (fresh fit only) -------------------------------
    if not pre_existing_source:
        if num_cols_eff:
            parts2 = st._run_pass(
                src_files, file_type, num_cols_eff, chunk_rows, cfg,
                pass_no=2, dispatch=_hist_dispatch,
                ctl=ctl, stats=stats, ckpt=ckpt, skip_chunks=_skip(2),
                on_file_rows=on_rows_src)
            src_num = _sum_hists(parts2)[: len(num_cols_eff)]
        else:
            src_num = None

    # ---- target pass ------------------------------------------------------
    parts3 = st._run_pass(
        tgt_files, file_type, num_cols_eff, chunk_rows, cfg,
        pass_no=3,
        dispatch=_hist_dispatch if num_cols_eff else (lambda v, m: {}),
        host_part=lambda df: _drift_side_host_part(df, cat_cols),
        ctl=ctl, stats=stats, ckpt=ckpt, skip_chunks=_skip(3),
        on_file_rows=on_rows_tgt)
    if not parts3:
        raise IngestError(
            f"statistics_streaming: no readable rows in {len(tgt_files)} "
            "target part file(s)")
    count_target, tgt_counters = _merge_side_parts(parts3, cat_cols)
    tgt_num = _sum_hists(parts3) if num_cols_eff else None
    if tgt_num is not None:
        tgt_num = tgt_num[: len(num_cols_eff)]
    # counters keyed by NAME: cat_cols is re-filtered below (columns with
    # no persisted source frequencies drop out), which would shift
    # positional indexing
    tgt_cnt = {c: tgt_counters[j] for j, c in enumerate(cat_cols)}
    src_cnt = ({c: src_counters[j] for j, c in enumerate(cat_cols)}
               if src_counters is not None else {})

    # ---- union vocabularies + frequencies ---------------------------------
    freq_q: Dict[str, np.ndarray] = {}
    if pre_existing_source:
        for c in cols:
            smap = load_frequency_map(model_dir, c)
            if smap is None:
                warnings.warn(
                    f"drift statistics: no persisted source frequencies for {c}; skipping")
                continue
            if c in num_cols_eff:
                freq_p[c] = np.array([smap.get(str(k), 0.0) for k in range(1, bin_size + 1)])
            elif c in cat_cols:
                uni = np.array(sorted(set(smap) | set(tgt_cnt[c])), dtype=object)
                union_vocabs[c] = uni
                freq_p[c] = np.array([smap.get(str(v), 0.0) for v in uni])
        cat_cols = [c for c in cat_cols if c in union_vocabs]
    else:
        for c in cat_cols:
            union_vocabs[c] = np.array(
                sorted(set(src_cnt[c]) | set(tgt_cnt[c])), dtype=object)
        if cutoffs is not None and len(num_cols_eff):
            save_model_df(
                pd.DataFrame(
                    {"attribute": num_cols_eff,
                     "parameters": [list(map(float, c)) for c in cutoffs]}),
                model_dir,
                "attribute_binning",
            )
        for i, c in enumerate(num_cols_eff):
            freq_p[c] = src_num[i] / max(src_rows, 1)
        for c in cat_cols:
            cnt = src_cnt[c]
            freq_p[c] = np.array(
                [cnt.get(str(v), 0) for v in union_vocabs[c]],
                np.float32) / max(src_rows, 1)
        if source_save:
            for c in num_cols_eff + cat_cols:
                keys = (
                    list(range(1, bin_size + 1)) if c in num_cols_eff
                    else list(union_vocabs[c])
                )
                save_frequency_map(model_dir, c, keys, freq_p[c])

    for i, c in enumerate(num_cols_eff):
        freq_q[c] = tgt_num[i] / max(count_target, 1)
    for c in cat_cols:
        cnt = tgt_cnt[c]
        freq_q[c] = np.array(
            [cnt.get(str(v), 0) for v in union_vocabs[c]],
            np.float32) / max(count_target, 1)

    odf = _metrics_frame(freq_p, freq_q, cols, methods, threshold)
    st._publish_stats("drift_statistics_streaming", ctl, stats)
    if print_impact:
        logger.info(odf.to_string(index=False))
    return odf
