"""Stats persistence + chart-object generation
(reference: data_report/report_preprocessing.py).

``save_stats`` (ref :40) → ``<master_path>/<function_name>.csv``.
``charts_to_objects`` (ref :469) → plotly-JSON chart files per column:
``freqDist_<col>``, ``eventDist_<col>`` (binary label), ``drift_<col>``
(source vs target frequencies, reusing the drift binning model + persisted
source frequency CSVs), ``outlier_<col>`` (numeric distribution), plus
``data_type.csv``.  Chart payloads are plotly figure dicts written as JSON —
the report embeds them with plotly.js; no plotly python dependency.
"""

from __future__ import annotations

import json
import os
import re
from pathlib import Path
from typing import List, Optional

import jax.numpy as jnp
import numpy as np
import pandas as pd

from anovos_tpu.obs import get_tracer
from anovos_tpu.ops.drift_kernels import binned_histograms, fit_cutoffs
from anovos_tpu.ops.quantiles import masked_quantiles
from anovos_tpu.ops.segment import code_counts, code_label_counts
from anovos_tpu.shared.table import Table, pad_lane_params
from anovos_tpu.shared.utils import ends_with, parse_cols

global_theme = "#8000ff"
global_theme_r = "#ff0055"


def save_stats(
    idf: pd.DataFrame,
    master_path: str,
    function_name: str,
    reread: bool = False,
    run_type: str = "local",
    mlflow_config=None,
    auth_key: str = "NA",
    async_writer=None,
    async_key: str = "",
) -> pd.DataFrame:
    """Persist a stats frame as ``<master_path>/<function_name>.csv``
    (reference :40-119).  The ``run_type`` axis routes through the pluggable
    artifact store: writes land in the store's local staging dir and are
    pushed to the configured (possibly remote) ``master_path``.

    With ``async_writer`` (an ``AsyncArtifactWriter``) and no ``reread``,
    the CSV serialization + push is queued under ``async_key`` and the
    in-memory frame returns immediately; consumers of the CSV wait on the
    key before reading.  ``reread`` callers need the round-tripped frame,
    so that path stays synchronous."""
    from anovos_tpu.shared.artifact_store import for_run_type

    store = for_run_type(run_type, auth_key)
    local_dir = store.staging_dir(master_path)
    Path(local_dir).mkdir(parents=True, exist_ok=True)
    local_file = ends_with(local_dir) + function_name + ".csv"

    def _persist():
        idf.to_csv(local_file, index=False)
        store.push(local_file, master_path)
        if mlflow_config is not None:
            try:  # pragma: no cover - optional dependency
                import mlflow

                mlflow.log_artifact(local_dir)
            except ImportError:
                pass

    if async_writer is not None and not reread:
        async_writer.submit(async_key or f"stats:{function_name}", _persist)
        return idf
    _persist()
    if reread:
        return pd.read_csv(local_file)
    return idf


def _bar_fig(x, y, name: str, color: str = global_theme) -> dict:
    return {
        "data": [{"type": "bar", "x": list(x), "y": list(y), "name": name, "marker": {"color": color}}],
        "layout": {"title": {"text": name}, "template": "plotly_white"},
    }


def _grouped_fig(x, series: dict, title: str) -> dict:
    data = [
        {"type": "bar", "x": list(x), "y": list(np.asarray(v, dtype=float)), "name": k}
        for k, v in series.items()
    ]
    return {"data": data, "layout": {"title": {"text": title}, "barmode": "group", "template": "plotly_white"}}


def _violin_fig(values: np.ndarray, name: str) -> dict:
    return {
        "data": [
            {
                "type": "violin",
                "y": [float(v) for v in values],
                "name": name,
                "box": {"visible": True},
                "line": {"color": global_theme},
            }
        ],
        "layout": {"title": {"text": f"outlier distribution: {name}"}, "template": "plotly_white"},
    }


def _write_json(fig: dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump(fig, f)


import functools as _functools

import jax as _jax


@_functools.partial(_jax.jit, static_argnames=("nbins",))
def _binned_label_counts(X, M, cutoffs, ym, y, nbins):
    """Per-column (tot, event) bin counts for the event-rate charts, fused:
    digitize against the (k_pad, nb-1) cutoffs + label-masked bincounts in
    ONE program (dead bucketed lanes are mask=False → zero rows)."""
    from anovos_tpu.ops.drift_kernels import compare_digitize
    from anovos_tpu.ops.histogram import masked_bincount

    bins = compare_digitize(X, cutoffs)
    Mv = M & ym[:, None]
    return (
        masked_bincount(bins, Mv, nbins),
        masked_bincount(bins, Mv & (y[:, None] > 0), nbins),
    )


_BIN_RANGE = re.compile(r"^(-?\d+(?:\.\d+)?)-(-?\d+(?:\.\d+)?)$")


def edit_binRange(col):
    """Collapse degenerate "x-x" bin-range labels to "x" (reference :130-152).
    The split keys on the separator hyphen, not a leading minus sign, so
    negative-bound ranges like "-10--5" survive intact."""
    m = _BIN_RANGE.match(str(col))
    if m and m.group(1) == m.group(2):
        return m.group(1)
    return col


def _load_cut_map(cutoffs_path: Optional[str]) -> dict:
    """{attribute: cutoff array} from a persisted attribute_binning model;
    {} when the path holds no model (the one loader every binning consumer
    in this file shares)."""
    if not cutoffs_path:
        return {}
    from anovos_tpu.data_transformer.model_io import load_model_df

    try:
        dfm = load_model_df(cutoffs_path, "attribute_binning")
    except (FileNotFoundError, ValueError):
        return {}
    return {r["attribute"]: np.asarray(list(r["parameters"]), float) for _, r in dfm.iterrows()}


def binRange_to_binIdx(idf: Table, col: str, cutoffs_path: str) -> Table:
    """Map a column's values to 1-based bin indices using a persisted binning
    model (reference :158-197): the report-side re-binning primitive."""
    from anovos_tpu.ops.drift_kernels import compare_digitize
    from anovos_tpu.shared.table import Column

    cut_map = _load_cut_map(cutoffs_path)
    if col not in cut_map:
        raise ValueError(f"no binning model for column {col} under {cutoffs_path}")
    c = idf.columns[col]
    bins = compare_digitize(c.data[:, None], jnp.asarray(cut_map[col][None, :], jnp.float32))[:, 0] + 1
    return idf.with_column(
        col + "_binIdx", Column("num", bins.astype(jnp.float32), c.mask, dtype_name="double")
    )


def plot_frequency(idf: Table, col: str, cutoffs_path: Optional[str] = None, bin_size: int = 10) -> dict:
    """Frequency-distribution figure for one column (reference :200-257).
    Numeric columns bin against the persisted model when given, else fresh
    equal-frequency cutoffs; categoricals count by dictionary code."""
    c = idf.columns[col]
    if c.kind == "cat":
        vsize = max(len(c.vocab), 1)
        cnts = np.asarray(code_counts(c.data, c.mask, vsize))[:vsize]
        order = np.argsort(-cnts)
        return _bar_fig(
            [str(c.vocab[j]) for j in order if cnts[j] > 0],
            [float(cnts[j]) for j in order if cnts[j] > 0],
            col,
        )
    cuts = _col_cutoffs(idf, col, cutoffs_path, bin_size)
    bin_size = len(cuts) + 1  # a persisted model may have been fit with another bin count
    counts = np.asarray(
        binned_histograms(c.data[:, None], c.mask[:, None], jnp.asarray(cuts[None, :], jnp.float32), bin_size)
    )[0]
    return _bar_fig([f"{j + 1}" for j in range(bin_size)], counts.tolist(), col)


def plot_outlier(idf: Table, col: str, split_var: Optional[str] = None, sample_size: int = 500000) -> dict:
    """Violin figure of a numeric column on a ≤sample_size sample; with
    ``split_var`` one violin trace per category of that column
    (reference :260-300)."""
    vals = np.asarray(idf.columns[col].data)[: idf.nrows].astype(float)
    mask = np.asarray(idf.columns[col].mask)[: idf.nrows]
    if split_var is None:
        sample = vals[mask]
        if len(sample) > sample_size:
            sample = np.random.default_rng(0).choice(sample, sample_size, replace=False)
        return _violin_fig(sample, col)
    sc = idf.columns[split_var]
    if sc.kind != "cat":
        raise ValueError(f"split_var must be a categorical column, got {sc.kind!r} ({split_var})")
    codes = np.asarray(sc.data)[: idf.nrows]
    smask = mask & np.asarray(sc.mask)[: idf.nrows] & (codes >= 0)
    fig = None
    for code, name in enumerate(sc.vocab):
        sample = vals[smask & (codes == code)]
        if not len(sample):
            continue
        if len(sample) > sample_size:
            sample = np.random.default_rng(code).choice(sample, sample_size, replace=False)
        part = _violin_fig(sample, str(name))
        if fig is None:
            fig = part
            fig["layout"]["title"] = {"text": f"{col} by {split_var}"}
        else:
            fig["data"].extend(part["data"])
    return fig if fig is not None else _violin_fig(vals[mask], col)


def plot_eventRate(
    idf: Table, col: str, label_col: str, event_label, cutoffs_path: Optional[str] = None, bin_size: int = 10
) -> dict:
    """Per-bin / per-category event-rate figure (reference :303-367)."""
    from anovos_tpu.data_transformer.transformers import _event_vector

    y, ym = _event_vector(idf, label_col, event_label)
    c = idf.columns[col]
    if c.kind == "cat":
        from anovos_tpu.ops.segment import code_label_counts

        vsize = max(len(c.vocab), 1)
        m_eff = c.mask & ym
        tot = np.asarray(code_label_counts(c.data, m_eff, jnp.ones_like(y), vsize))[:vsize]
        evs = np.asarray(code_label_counts(c.data, m_eff, y, vsize))[:vsize]
        with np.errstate(invalid="ignore", divide="ignore"):
            rate = np.where(tot > 0, evs / np.maximum(tot, 1), 0.0)
        order = np.argsort(-tot)
        return _bar_fig(
            [str(c.vocab[j]) for j in order if tot[j] > 0],
            [float(rate[j]) for j in order if tot[j] > 0],
            f"event rate: {col}",
            global_theme_r,
        )
    from anovos_tpu.ops.drift_kernels import compare_digitize
    from anovos_tpu.ops.histogram import masked_bincount

    cuts = _col_cutoffs(idf, col, cutoffs_path, bin_size)
    bin_size = len(cuts) + 1  # a persisted model may have been fit with another bin count
    bins = compare_digitize(c.data[:, None], jnp.asarray(cuts[None, :], jnp.float32))
    Mv = c.mask[:, None] & ym[:, None]
    tot = np.asarray(masked_bincount(bins, Mv, bin_size))[0]
    evs = np.asarray(masked_bincount(bins, Mv & (y[:, None] > 0), bin_size))[0]
    with np.errstate(invalid="ignore", divide="ignore"):
        rate = np.where(tot > 0, evs / np.maximum(tot, 1), 0.0)
    return _bar_fig([f"{j + 1}" for j in range(bin_size)], rate.tolist(), f"event rate: {col}", global_theme_r)


def plot_comparative_drift(idf: Table, source_path: str, col: str, model_directory: str = "drift_statistics") -> dict:
    """Source-vs-target frequency figure from the persisted drift model CSVs
    (reference :370-466)."""
    fpath = os.path.join(source_path, model_directory, "frequency_counts", col, "part-00000.csv")
    if not os.path.exists(fpath):
        raise FileNotFoundError(f"no persisted source frequencies for {col} under {source_path}")
    fdf = pd.read_csv(fpath, dtype=str)
    skeys = fdf.iloc[:, 0].astype(str).tolist()
    sfreq = fdf["p"].astype(float).to_numpy()
    fig_t = plot_frequency(idf, col, cutoffs_path=os.path.join(source_path, model_directory))
    t_x = [str(v) for v in fig_t["data"][0]["x"]]
    t_y = np.asarray(fig_t["data"][0]["y"], float)
    t_y = t_y / max(t_y.sum(), 1)
    tmap = dict(zip(t_x, t_y))
    return _grouped_fig(skeys, {"source": sfreq, "target": [tmap.get(k, 0.0) for k in skeys]}, f"drift: {col}")


def _col_cutoffs(idf: Table, col: str, cutoffs_path: Optional[str], bin_size: int) -> np.ndarray:
    """Cutoffs from a persisted binning model when available, else a fresh fit."""
    cut_map = _load_cut_map(cutoffs_path)
    if col in cut_map:
        return cut_map[col]
    c = idf.columns[col]
    return np.asarray(fit_cutoffs((c.data,), (c.mask,), bin_size, "equal_frequency"))[0]


def charts_to_objects(
    idf: Table,
    list_of_cols="all",
    drop_cols=[],
    label_col=None,
    event_label=None,
    bin_method: str = "equal_frequency",
    bin_size: int = 10,
    coverage: float = 1.0,
    drift_detector: bool = False,
    source_path: str = "NA",
    model_directory: str = "drift_statistics",
    outlier_charts: bool = False,
    stats_unique: dict = {},
    master_path: str = ".",
    run_type: str = "local",
    auth_key: str = "NA",
    chart_sample: int = 500000,
    async_writer=None,
    async_key: str = "charts:objects",
    **_ignored,
) -> None:
    """Write per-column chart JSONs + data_type.csv (reference :469-735).

    With ``async_writer`` each chart JSON dump is queued on the artifact
    writer under ``async_key`` so file serialization overlaps the device
    histogram/frequency computation of the next chart; the queue is waited
    on before the publish loop so every staged file exists when pushed."""
    from anovos_tpu.shared.artifact_store import for_run_type

    if async_writer is not None:
        def _emit(fig, path):
            async_writer.submit(async_key, _write_json, fig, path)
    else:
        _emit = _write_json

    store = for_run_type(run_type, auth_key)
    dest_path, master_path = master_path, store.staging_dir(master_path)
    Path(master_path).mkdir(parents=True, exist_ok=True)
    num_all, cat_all, _ = idf.attribute_type_segregation()
    cols = parse_cols(
        list_of_cols if list_of_cols != "all" else num_all + cat_all, idf.col_names, drop_cols
    )
    cols = [c for c in cols if c != label_col]
    num_cols = [c for c in cols if idf.columns[c].kind == "num"]
    cat_cols = [c for c in cols if idf.columns[c].kind == "cat"]

    phase = get_tracer().phase
    with phase("charts/read", cat="block", cols=len(cols)):
        # label event vector (for eventDist charts)
        y = ym = None
        if label_col and label_col in idf.columns:
            from anovos_tpu.data_transformer.transformers import _event_vector

            y, ym = _event_vector(idf, label_col, event_label)

        # drift source frequencies (reuse the persisted drift model when present;
        # "NA" falls back to the drift detector's default dir, reference :573-574)
        drift_freqs = {}
        drift_model_dir = os.path.join(
            source_path if source_path != "NA" else "intermediate_data", model_directory
        )
        if drift_detector and drift_model_dir and os.path.isdir(os.path.join(drift_model_dir, "frequency_counts")):
            for c in cols:
                fpath = os.path.join(drift_model_dir, "frequency_counts", c, "part-00000.csv")
                if os.path.exists(fpath):
                    fdf = pd.read_csv(fpath, dtype=str)
                    drift_freqs[c] = (fdf.iloc[:, 0].astype(str).tolist(), fdf["p"].astype(float).to_numpy())

    # ---- numeric columns: bin once (reuse drift cutoffs when available) ----
    if num_cols:
        # one stage for the block: cutoffs fitted where no model has them, the
        # histograms (and the label's) fetched, then a figure or three a column
        with phase("charts/num", cat="block", rows=idf.padded_rows, cols=len(num_cols)):
            cut_map = _load_cut_map(drift_model_dir)
            fit_cols = [c for c in num_cols if c not in cut_map]
            if fit_cols:
                # column-bucketed fit (dead lanes all-NaN); zip() truncates the
                # readback to the live fit_cols
                from anovos_tpu.drift_stability.drift_detector import _padded_col_tuples

                cuts = np.asarray(
                    fit_cutoffs(*_padded_col_tuples(idf, fit_cols), bin_size, bin_method)
                )
                for c, row in zip(fit_cols, cuts):
                    cut_map[c] = row
            X, M = idf.numeric_block(num_cols)
            # cutoff rows padded to the block's bucketed lane count (dead-lane
            # histogram rows are all-masked zeros, never indexed below); cast
            # f32 on HOST — the eager jnp.asarray cast compiled one convert
            # program per width, and a host np cast rounds identically
            cutoffs = pad_lane_params(
                np.stack([cut_map[c] for c in num_cols]), X.shape[1]
            ).astype(np.float32)
            counts = np.asarray(binned_histograms(X, M, cutoffs, bin_size))
            ev_counts = None
            if y is not None:
                # one fused program: the eager digitize → mask-combine →
                # two-bincount chain compiled ~5 programs per width here
                tot_d, evs_d = _binned_label_counts(X, M, cutoffs, ym, y, bin_size)
                ev_counts = (np.asarray(tot_d), np.asarray(evs_d))
            for i, c in enumerate(num_cols):
                labels = [f"{j + 1}" for j in range(bin_size)]
                _emit(_bar_fig(labels, counts[i].tolist(), c), ends_with(master_path) + "freqDist_" + c)
                if ev_counts is not None:
                    tot, evs = ev_counts
                    with np.errstate(invalid="ignore", divide="ignore"):
                        rate = np.where(tot[i] > 0, evs[i] / np.maximum(tot[i], 1), 0.0)
                    _emit(
                        _bar_fig(labels, rate.tolist(), f"event rate: {c}", global_theme_r),
                        ends_with(master_path) + "eventDist_" + c,
                    )
                if c in drift_freqs:
                    skeys, sfreq = drift_freqs[c]
                    tfreq = counts[i] / max(counts[i].sum(), 1)
                    _emit(
                        _grouped_fig(skeys, {"source": sfreq, "target": tfreq[: len(skeys)]}, f"drift: {c}"),
                        ends_with(master_path) + "drift_" + c,
                    )
                if outlier_charts:
                    vals = np.asarray(idf.columns[c].data)[: idf.nrows].astype(float)
                    mask = np.asarray(idf.columns[c].mask)[: idf.nrows]
                    sample = vals[mask]
                    if len(sample) > chart_sample:
                        sample = np.random.default_rng(0).choice(sample, chart_sample, replace=False)
                    _emit(_violin_fig(sample, c), ends_with(master_path) + "outlier_" + c)

    # ---- categorical columns ------------------------------------------------
    for c in cat_cols:
        col = idf.columns[c]
        vsize = max(len(col.vocab), 1)
        # a stage a column: its group count fetched (and the label's, where there is one), its figures
        with phase("charts/cat", cat="block", rows=idf.padded_rows, distinct=vsize):
            cnts = np.asarray(code_counts(col.data, col.mask, vsize))[:vsize]
            order = np.argsort(-cnts)
            cats = [str(col.vocab[j]) for j in order if cnts[j] > 0]
            vals = [float(cnts[j]) for j in order if cnts[j] > 0]
            _emit(_bar_fig(cats, vals, c), ends_with(master_path) + "freqDist_" + c)
            if y is not None:
                # the labelled rows and the events per category, one group
                # count in flight at a time as cat_to_num_supervised takes them
                m_eff = col.mask & ym
                tot = np.asarray(code_counts(col.data, m_eff, vsize))[:vsize]
                evs = np.asarray(code_label_counts(col.data, m_eff, y, vsize))[:vsize]
                with np.errstate(invalid="ignore", divide="ignore"):
                    rate = np.where(tot > 0, evs / np.maximum(tot, 1), 0.0)
                _emit(
                    _bar_fig([str(col.vocab[j]) for j in order if cnts[j] > 0],
                             [float(rate[j]) for j in order if cnts[j] > 0],
                             f"event rate: {c}", global_theme_r),
                    ends_with(master_path) + "eventDist_" + c,
                )
            if c in drift_freqs:
                skeys, sfreq = drift_freqs[c]
                tmap = {str(col.vocab[j]): cnts[j] / max(cnts.sum(), 1) for j in range(vsize)}
                _emit(
                    _grouped_fig(skeys, {"source": sfreq, "target": [tmap.get(k, 0.0) for k in skeys]}, f"drift: {c}"),
                    ends_with(master_path) + "drift_" + c,
                )

    # ---- label distribution chart (exec-summary pie source, reference :560) --
    # the label is excluded from the per-attribute loops above, but its own
    # frequency chart must exist for the report's label pie
    if label_col and label_col in idf.columns:
        _emit(plot_frequency(idf, label_col), ends_with(master_path) + "freqDist_" + label_col)

    with phase("charts/write", cat="block"):  # the dtype manifest, the queued chart files landed, the publish
        # ---- dtype manifest (reference :712) -----------------------------------
        pd.DataFrame(idf.dtypes(), columns=["attribute", "data_type"]).to_csv(
            ends_with(master_path) + "data_type.csv", index=False
        )

        # publish the staged chart/manifest files to the configured destination
        # (no-op for local; aws/azcopy per file for emr/ak8s — ref :634-710 cp's);
        # queued chart writes must land before the dir listing sees them
        if async_writer is not None:
            async_writer.wait([async_key])
        for fname in sorted(os.listdir(master_path)):
            fpath = os.path.join(master_path, fname)
            if os.path.isfile(fpath):
                store.push(fpath, dest_path)
