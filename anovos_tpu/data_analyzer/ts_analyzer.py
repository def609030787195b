"""Time-series inspection (reference: data_analyzer/ts_analyzer.py).

For each timestamp column: calendar-feature extraction (dayparts :52,
weekday/weekend), eligibility scoring (``ts_eligiblity_check`` :160), and
visualization data dumps at daily/hourly/weekly grain (``ts_viz_data`` :259)
written into ``output_path`` as ``ts_*`` CSVs for the report's time-series
tabs.  Calendar decomposition is int32 epoch math in one vectorized pass.
"""

from __future__ import annotations

import functools
from pathlib import Path
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd

from anovos_tpu.obs import get_tracer
from anovos_tpu.shared.table import Table, counted_fetch
from anovos_tpu.shared.utils import ends_with
from anovos_tpu.shared.utils import write_csv_counted as _write_csv

# the ts_stats.csv schema — shared by eligibility rows and the empty case
TS_STATS_COLUMNS = [
    "attribute", "eligible", "reason", "span_days", "distinct_days",
    "null_pct", "min_ts", "max_ts",
]


def _ts_frame(idf: Table, col: str) -> pd.Series:
    c = idf.columns[col]
    secs = np.asarray(c.data)[: idf.nrows].astype("int64")
    mask = np.asarray(c.mask)[: idf.nrows]
    ts = pd.Series(secs.astype("datetime64[s]"))
    ts[~mask] = pd.NaT
    return ts


def daypart_cat(hour: pd.Series) -> pd.Series:
    """Reference dayparts (:52): late_hours / early_hours / work_hours …"""
    bins = pd.cut(
        hour,
        bins=[-1, 5, 9, 16, 20, 23],
        labels=["late_hours", "early_hours", "work_hours", "evening_hours", "night_hours"],
    )
    return bins.astype(str)


def ts_processed_feats(idf: Table, col: str) -> pd.DataFrame:
    """Per-row calendar features for one ts column (reference :87-158): the
    public per-row API, a host frame of the table's length.  ``ts_analyzer``
    does not call it: everything the inspection writes is a count over a
    calendar bucket and comes from :func:`ts_calendar`."""
    ts = _ts_frame(idf, col)
    out = pd.DataFrame({col: ts})
    out["date"] = ts.dt.date
    out["hour"] = ts.dt.hour
    out["dayofweek"] = ts.dt.dayofweek
    out["is_weekend"] = ts.dt.dayofweek >= 5
    out["daypart"] = daypart_cat(ts.dt.hour)
    out["month"] = ts.dt.month
    # vectorized day formatting: datetime64[D] → str is the same ISO
    # "%Y-%m-%d" rendering as strftime at ~10× the speed
    days = ts.to_numpy().astype("datetime64[D]")
    ymd = days.astype(str).astype(object)
    ymd[pd.isna(ts).to_numpy()] = np.nan
    out["yyyymmdd_col"] = ymd
    return out


def ts_calendar(idf: Table, col: str, span=None) -> dict:
    """One timestamp column's calendar counts, from ONE device program and
    one fetch (``ops/datetime_kernels.calendar_counts``): ``n`` valid rows of
    ``rows``, ``min`` / ``max`` second, ``day_lo`` (epoch day of the first
    valid row), ``daily`` (records per day from ``day_lo`` to the last day,
    zeros included), ``hour`` (24) and ``dow`` (7, Mon=0) as int64.  What
    eligibility, the count tables, the landscape and the daily series of the
    decomposition are built from; an all-null column has ``n`` 0 and no days."""
    from anovos_tpu.ops.datetime_kernels import SECS_PER_DAY, calendar_counts

    c = idf.columns[col]
    got = {k: np.asarray(v).astype("int64")
           for k, v in counted_fetch(calendar_counts(c.data, c.mask), idf, span).items()}
    n = int(got["n"])
    lo, hi = (int(got["min"]), int(got["max"])) if n else (0, -1)
    day_lo = lo // SECS_PER_DAY
    return {"n": n, "rows": idf.nrows, "min": lo, "max": hi, "day_lo": day_lo,
            "daily": got["daily"][: hi // SECS_PER_DAY - day_lo + 1] if n else got["daily"][:0],
            "hour": got["hour"], "dow": got["dow"]}


def _daypart_counts(cal: dict) -> list:
    """[(daypart label, records), ...] of the dayparts that have rows, in label order."""
    part = np.bincount(_DAYPART_LUT, weights=cal["hour"], minlength=len(_DAYPART_NAMES)).astype("int64")
    return sorted((lbl, n) for lbl, n in zip(_DAYPART_NAMES, part) if n)


def _count_frames(cal: dict):
    """(daily, hourly, weekly, dayparts) as the host ``groupby(...).size()``
    over the per-row frame gave them: only the buckets that have rows, in
    key order (days as ``%Y-%m-%d`` strings, daypart labels as strings), the
    hour and the weekday as floats where the column has a null (pandas'
    ``.dt.hour`` of a column with ``NaT``) and as int32 where it has none."""
    key = np.float64 if cal["n"] < cal["rows"] else np.int32
    days = np.nonzero(cal["daily"])[0]
    daily = pd.DataFrame({
        "yyyymmdd_col": (days + cal["day_lo"]).astype("datetime64[D]").astype(str).astype(object),
        "count": cal["daily"][days]})
    hours = np.nonzero(cal["hour"])[0]
    hourly = pd.DataFrame({"hour": hours.astype(key), "count": cal["hour"][hours]})
    dows = np.nonzero(cal["dow"])[0]
    weekly = pd.DataFrame({"dayofweek": dows.astype(key), "count": cal["dow"][dows]})
    return daily, hourly, weekly, pd.DataFrame(_daypart_counts(cal), columns=["daypart", "count"])


def _ts_str(sec: int) -> str:
    return str(pd.Timestamp(sec, unit="s"))


def ts_eligiblity_check(idf: Table, col: str, id_col: Optional[str] = None, max_days: int = 3600,
                        _calendar: Optional[dict] = None) -> dict:
    """Eligibility stats (reference :160-257): span, distinct days, null pct.
    ``id_col`` is unused: it stays in the signature for parity with the
    reference's API."""
    cal = _calendar if _calendar is not None else ts_calendar(idf, col)
    if cal["n"] == 0:
        return {"attribute": col, "eligible": 0, "reason": "all null"}
    span_days = (cal["max"] - cal["min"]) // 86400
    distinct_days = int(np.count_nonzero(cal["daily"]))
    return {
        "attribute": col,
        "eligible": int(0 < span_days <= max_days and distinct_days > 1),
        "span_days": span_days,
        "distinct_days": distinct_days,
        "null_pct": round(1 - cal["n"] / max(idf.nrows, 1), 4),
        "min_ts": _ts_str(cal["min"]),
        "max_ts": _ts_str(cal["max"]),
    }


# daypart labels per hour 0..23 (reference dayparts :52)
_DAYPART_LUT = np.array(
    [0] * 6 + [1] * 4 + [2] * 7 + [3] * 4 + [4] * 3, np.int32
)
_DAYPART_NAMES = ["late_hours", "early_hours", "work_hours", "evening_hours", "night_hours"]
_DOW_NAMES = ["Mon", "Tue", "Wed", "Thu", "Fri", "Sat", "Sun"]


@functools.partial(jax.jit, static_argnames=("grain",))
def _grain_ids(tdata, grain: str):
    """One fused program per grain (clip, LUT gather, shift)."""
    from anovos_tpu.ops import datetime_kernels as dk

    if grain == "hourly":
        hour = dk.extract_unit(tdata, "hour")
        return jnp.asarray(_DAYPART_LUT)[jnp.clip(hour, 0, 23)]
    return jnp.clip(dk.extract_unit(tdata, "dayofweek") - 1, 0, 6)  # Mon=0


def _grain_buckets(tcol, grain: str):
    """Device bucket ids + host labels for hourly (daypart) / weekly (dow)."""
    return _grain_ids(tcol.data, grain), (_DAYPART_NAMES if grain == "hourly" else _DOW_NAMES)


def _small_grain_frame(agg, num_cols: List[str], labels: List[str]) -> pd.DataFrame:
    """Host frame from one grain's (cnt, sm, sq, mn, mx, med) aggregate —
    the ONE copy of the formatting shared by the per-grain and fused-pair
    paths."""
    cnt, sm, _, mn, mx, med = agg
    rows = []
    for j, c in enumerate(num_cols):
        for b, lbl in enumerate(labels):
            if cnt[j][b] > 0:
                rows.append(
                    {
                        "bucket": lbl,
                        "attribute": c,
                        "count": int(cnt[j][b]),
                        "min": round(float(mn[j][b]), 4),
                        "max": round(float(mx[j][b]), 4),
                        "mean": round(float(sm[j][b] / cnt[j][b]), 4),
                        "median": round(float(med[j][b]), 4),
                    }
                )
    return pd.DataFrame(rows, columns=["bucket", "attribute", "count", "min", "max", "mean", "median"])


def _num_viz_small_grain(idf: Table, ts_col: str, num_cols: List[str], grain: str,
                         span=None) -> pd.DataFrame:
    """min/max/mean/median of every numeric column per daypart / weekday —
    one device segment program (reference ts_viz_data :259-406 hourly/weekly)."""
    from anovos_tpu.data_transformer.datetime import _segment_aggregate

    tcol = idf.columns[ts_col]
    ids, labels = _grain_buckets(tcol, grain)
    V, Mv = idf.numeric_block(num_cols)
    agg = counted_fetch(_segment_aggregate(ids, tcol.mask, V, Mv, len(labels)), idf, span)
    return _small_grain_frame(agg, num_cols, labels)


@functools.partial(jax.jit, static_argnames=("nseg_d", "nseg_h", "nseg_w", "cp"))
def _ts_num_viz_program(day_lo, tdata, valid, V, Mv,
                        nseg_d: int, nseg_h: int, nseg_w: int, cp: bool):
    """ALL THREE numeric viz grains — day (offset from ``day_lo``), daypart
    and weekday ids, and the three segment aggregates — in ONE compiled
    program: the per-grain path dispatched three id programs and three
    aggregate programs with blocking fetches between them."""
    from anovos_tpu.data_transformer.datetime import _bucket_ids, _segment_aggregate_jit

    return (
        _segment_aggregate_jit(_bucket_ids(tdata, "day") - day_lo, valid, V, Mv, nseg_d, cp=cp),
        _segment_aggregate_jit(_grain_ids(tdata, "hourly"), valid, V, Mv, nseg_h, cp=cp),
        _segment_aggregate_jit(_grain_ids(tdata, "weekly"), valid, V, Mv, nseg_w, cp=cp),
    )


_TS_NUM_AGGS = ["count", "min", "max", "mean", "median"]


def _ts_num_viz_all(idf: Table, ts_col: str, num_cols: List[str], cal: dict, span=None):
    """(daily frame, hourly frame, weekly frame) from ONE device dispatch
    + ONE fetch; the day span is the calendar's (``cal``: a column with valid
    rows).  Daily formatting goes through the aggregator's shared
    ``format_segment_aggregate`` so the frames match the per-grain path
    byte-for-byte."""
    from anovos_tpu.data_transformer.datetime import aggregate_routes, format_segment_aggregate
    from anovos_tpu.ops.segment import segment_class
    from anovos_tpu.shared.runtime import wants_column_parallel

    tcol = idf.columns[ts_col]
    lo = cal["day_lo"]
    # same segment classes as _segment_aggregate's wrapper, so the fused and
    # per-grain programs reduce over identical widths
    nseg_d, nseg_h, nseg_w = (segment_class(n) for n in
                              (len(cal["daily"]), len(_DAYPART_NAMES), len(_DOW_NAMES)))
    V, Mv = idf.numeric_block(num_cols)
    cp = wants_column_parallel(tcol.data, tcol.mask, V, Mv,
                               replicate=(tcol.data, tcol.mask))
    if span is not None:
        # the bucket lanes of the call, for its roofline, how its medians are taken, and what of it is a wide class
        span.add(segments=nseg_d + nseg_h + nseg_w,
                 **aggregate_routes(idf.padded_rows, len(num_cols), nseg_d, nseg_h, nseg_w))
    agg_d, agg_h, agg_w = counted_fetch(_ts_num_viz_program(
        np.int32(lo), tcol.data, tcol.mask, V, Mv, nseg_d, nseg_h, nseg_w, cp), idf, span)
    dv = format_segment_aggregate(agg_d, num_cols, _TS_NUM_AGGS, ts_col,
                                  "%Y-%m-%d", lo, "day")
    return (dv,
            _small_grain_frame(agg_h, num_cols, _DAYPART_NAMES),
            _small_grain_frame(agg_w, num_cols, _DOW_NAMES))


def _cat_viz(idf: Table, ts_col: str, cat_cols: List[str], cal: dict, n_cat: int = 10,
             span=None) -> pd.DataFrame:
    """Top-N + Others category counts per day per categorical column
    (reference's string branch of ts_viz_data); the day span is the
    calendar's (``cal``).

    Batched (round 5): ONE vocab-padded histogram program for every column
    and ONE stacked day×category combo program — two device dispatches
    total instead of two per column."""
    from anovos_tpu.data_transformer.datetime import _bucket_start_secs

    tcol = idf.columns[ts_col]
    lo, hi = cal["day_lo"], cal["day_lo"] + len(cal["daily"]) - 1
    if lo > hi or not cat_cols:
        return pd.DataFrame(columns=["date", "attribute", "category", "count"])
    ndays = hi - lo + 1
    k = len(cat_cols)
    # power-of-two size classes for the static jit dims (the
    # _bucket_segments discipline, ops/segment.py): one compiled program
    # per row shape instead of one per distinct vocab size / day span —
    # each novel shape is a fresh XLA compile
    nv = max(max(len(idf.columns[c].vocab) for c in cat_cols), 1)
    nv_b = max(8, 1 << (nv - 1).bit_length())
    ndays_b = max(8, 1 << (int(ndays) - 1).bit_length())
    # stacks fold INTO the jitted programs (tuple args): a jnp.stack pair
    # out here would compile broadcast+concat programs per arity
    datas = tuple(idf.columns[c].data for c in cat_cols)
    masks = tuple(idf.columns[c].mask for c in cat_cols)
    cnts = np.asarray(counted_fetch(_all_code_counts_cols(datas, masks, nv_b), idf, span))  # (k, nv_b)
    # top-N per column (codes beyond a column's own vocab count zero)
    lut = np.full((k, nv_b), n_cat, np.int32)  # → Others
    tops = []
    for j, c in enumerate(cat_cols):
        v = len(idf.columns[c].vocab)
        top = np.argsort(-cnts[j, :v])[:n_cat]
        lut[j, top] = np.arange(len(top), dtype=np.int32)
        tops.append(top)
    combo = np.asarray(counted_fetch(_combo_counts_all_cols(
        datas, masks, tcol.data, tcol.mask, lut, np.int32(lo), ndays_b, n_cat + 1
    ), idf, span)).reshape(k, ndays_b, n_cat + 1)[:, :ndays, :]
    rows = []
    for j, c in enumerate(cat_cols):
        labels = [str(idf.columns[c].vocab[t]) for t in tops[j]] + ["Others"]
        day_idx, cat_idx = np.nonzero(combo[j])
        dates = pd.Series(
            _bucket_start_secs(day_idx + lo, "day").astype("datetime64[s]")
        ).dt.strftime("%Y-%m-%d")
        for d, ci, cval in zip(dates, cat_idx, combo[j][day_idx, cat_idx]):
            rows.append({"date": d, "attribute": c, "category": labels[ci], "count": int(cval)})
    return pd.DataFrame(rows, columns=["date", "attribute", "category", "count"])


@functools.partial(jax.jit, static_argnames=("nv",))
def _all_code_counts(C, M, nv: int):
    """(rows, k) codes → (k, nv) histograms in one segment_sum."""
    k = C.shape[1]
    valid = M & (C >= 0)
    seg = jnp.where(valid, C + jnp.arange(k, dtype=C.dtype)[None, :] * nv, k * nv)
    return jax.ops.segment_sum(
        valid.astype(jnp.float32).ravel(), seg.ravel(), num_segments=k * nv + 1
    )[: k * nv].reshape(k, nv)


@functools.partial(jax.jit, static_argnames=("nv",))
def _all_code_counts_cols(datas, masks, nv: int):
    """Column-tuple variant: the stack happens inside the program."""
    return _all_code_counts(jnp.stack(datas, axis=1), jnp.stack(masks, axis=1), nv)


@functools.partial(jax.jit, static_argnames=("ndays", "ncat"))
def _combo_counts_all_cols(datas, masks, tdata, tmask, lut, day_lo,
                           ndays: int, ncat: int):
    """Column-tuple variant of _combo_counts_all: stack + ts-mask combine
    + day ids and their offset + LUT upload fold into the one program."""
    from anovos_tpu.data_transformer.datetime import _bucket_ids

    C = jnp.stack(datas, axis=1)
    Mc = jnp.stack(masks, axis=1) & tmask[:, None]
    return _combo_counts_all(C, Mc, lut, _bucket_ids(tdata, "day") - day_lo, ndays, ncat)


@functools.partial(jax.jit, static_argnames=("ndays", "ncat"))
def _combo_counts_all(C, M, lut, day0, ndays: int, ncat: int):
    """Stacked day×category counts for every column in one segment_sum:
    (rows, k) codes + per-column (k, nv) LUT → (k, ndays·ncat)."""
    k = C.shape[1]
    valid = M & (C >= 0)
    cb = jnp.take_along_axis(
        lut.T, jnp.clip(C, 0, lut.shape[1] - 1), axis=0
    )  # (rows, k): lut[j, C[:, j]]
    base = jnp.arange(k, dtype=jnp.int32)[None, :] * (ndays * ncat)
    seg = jnp.where(valid, base + day0[:, None] * ncat + cb, k * ndays * ncat)
    return jax.ops.segment_sum(
        valid.astype(jnp.float32).ravel(), seg.ravel(),
        num_segments=k * ndays * ncat + 1,
    )[: k * ndays * ncat]


def ts_viz_data(
    idf: Table, col: str, output_path: str, output_type: str = "daily",
    _calendar: Optional[dict] = None,
) -> None:
    """Per-column visualization data at THREE grains (reference :259-406):
    daily (date buckets), hourly (dayparts), weekly (weekdays) — numeric
    columns get min/max/mean/median per bucket via the device segment
    kernels; categorical columns get top-10+Others daily counts.  Plus the
    daily count series with seasonal decomposition and ADF/KPSS
    stationarity (report_generation.py:1942-3208 tab suite inputs)."""
    from anovos_tpu.data_transformer.datetime import aggregator

    phase = get_tracer().phase
    out = ends_with(output_path)
    num_all, cat_all, _ = idf.attribute_type_segregation()
    num_cols = list(num_all)  # every one, as the upstream's loop takes them
    cat_cols = [c for c in cat_all][:10]

    cal = _calendar if _calendar is not None else ts_calendar(idf, col)
    with phase("ts/viz/counts", cat="block") as sp:  # the count tables, from the calendar's few hundred integers
        daily, hourly, weekly, dayparts = _count_frames(cal)
        sp.add(rows=cal["n"])
    with phase("ts/viz/write", cat="block") as sp:
        _write_csv(daily, out + f"ts_daily_{col}.csv", sp)

    # numeric viz: all three grains in ONE fused dispatch
    # (_ts_num_viz_all); the per-grain path — daily via the device
    # groupby-aggregator, small grains via one segment program each — is
    # taken for a column without a valid row (no span to fuse over)
    if num_cols:
        with phase("ts/viz/num", cat="block", cols=len(num_cols), rows=idf.padded_rows) as sp:
            if cal["n"]:
                dv, hourly_df, weekly_df = _ts_num_viz_all(idf, col, num_cols, cal, sp)
            else:
                dv = aggregator(idf, num_cols, _TS_NUM_AGGS, col, "%Y-%m-%d")
                hourly_df = _num_viz_small_grain(idf, col, num_cols, "hourly", sp)
                weekly_df = _num_viz_small_grain(idf, col, num_cols, "weekly", sp)
        with phase("ts/viz/frame", cat="block", cols=len(num_cols)) as sp:  # the daily aggregate in long form
            long_rows = []
            for c in num_cols:
                sub = pd.DataFrame(
                    {
                        "date": dv[col],
                        "attribute": c,
                        "count": dv[f"{c}_count"],
                        "min": dv[f"{c}_min"].round(4),
                        "max": dv[f"{c}_max"].round(4),
                        "mean": dv[f"{c}_mean"].round(4),
                        "median": dv[f"{c}_median"].round(4),
                    }
                )
                long_rows.append(sub[sub["count"] > 0])
            num_daily = pd.concat(long_rows, ignore_index=True)
            sp.add(rows=len(num_daily))
        with phase("ts/viz/write", cat="block") as sp:
            _write_csv(num_daily, out + f"ts_num_daily_{col}.csv", sp)
            _write_csv(hourly_df, out + f"ts_num_hourly_{col}.csv", sp)
            _write_csv(weekly_df, out + f"ts_num_weekly_{col}.csv", sp)
    if cat_cols:
        with phase("ts/viz/cat", cat="block", cols=len(cat_cols), rows=idf.padded_rows) as sp:
            cat_daily = _cat_viz(idf, col, cat_cols, cal, span=sp)
        with phase("ts/viz/write", cat="block") as sp:
            _write_csv(cat_daily, out + f"ts_cat_daily_{col}.csv", sp)

    # seasonal decomposition + stationarity of the daily count series
    with phase("ts/viz/decompose", cat="block", rows=len(daily)):
        dec = seasonal_decompose_ma(daily["count"].to_numpy(), period=7)
        adf = adf_test(daily["count"].to_numpy())
        kpss = kpss_test(daily["count"].to_numpy())
    with phase("ts/viz/write", cat="block") as sp:
        if dec is not None:
            trend, seas, resid = dec
            _write_csv(pd.DataFrame(
                {
                    "date": daily["yyyymmdd_col"],
                    "observed": daily["count"],
                    "trend": np.round(trend, 4),
                    "seasonal": np.round(seas, 4),
                    "residual": np.round(resid, 4),
                }
            ), out + f"ts_decompose_{col}.csv", sp)
        if adf is not None or kpss is not None:
            _write_csv(pd.DataFrame([{"attribute": col, **(adf or {}), **(kpss or {})}]),
                       out + f"ts_stationarity_{col}.csv", sp)
        _write_csv(hourly, out + f"ts_hourly_{col}.csv", sp)
        _write_csv(weekly, out + f"ts_weekly_{col}.csv", sp)
        _write_csv(dayparts, out + f"ts_daypart_{col}.csv", sp)


def seasonal_decompose_ma(series: np.ndarray, period: int = 7):
    """Additive moving-average decomposition (the statsmodels
    seasonal_decompose recipe the reference's report uses — statsmodels
    itself is optional here): centered-MA trend, mean-by-phase seasonal,
    residual."""
    y = np.asarray(series, float)
    n = len(y)
    if n < 2 * period:
        return None
    kernel = np.ones(period) / period
    if period % 2 == 0:  # centered MA for even periods
        kernel = np.concatenate([[0.5], np.ones(period - 1), [0.5]]) / period
    trend = np.convolve(y, kernel, mode="same")
    half = len(kernel) // 2
    trend[:half] = np.nan
    trend[n - half :] = np.nan
    detr = y - trend
    seasonal = np.array([np.nanmean(detr[p::period]) for p in range(period)])
    seasonal = seasonal - np.nanmean(seasonal)
    seas_full = np.tile(seasonal, n // period + 1)[:n]
    resid = y - trend - seas_full
    return trend, seas_full, resid


def adf_test(series: np.ndarray, max_lag: int = None):
    """Augmented Dickey-Fuller t-statistic (constant-only regression) with
    MacKinnon critical values — the stationarity check the reference's
    report runs via statsmodels.adfuller."""
    y = np.asarray(series, float)
    y = y[~np.isnan(y)]
    n = len(y)
    if n < 10:
        return None
    if np.allclose(y, y[0]):
        # constant series: the level/intercept regressors are collinear and
        # the degenerate t-stat would misreport maximal stationarity as
        # non-stationary (statsmodels raises here); report stationary
        return {"adf_stat": float("-inf"), "stationary_1%": 1, "stationary_5%": 1, "stationary_10%": 1}
    if max_lag is None:
        max_lag = min(int(np.ceil(12 * (n / 100) ** 0.25)), n // 2 - 2)
    dy = np.diff(y)
    best = None
    lag = max_lag
    while lag >= 0:
        rows = len(dy) - lag
        if rows < 5 + lag:
            lag -= 1
            continue
        Xcols = [y[lag : lag + rows], np.ones(rows)]
        for i in range(1, lag + 1):
            Xcols.append(dy[lag - i : lag - i + rows])
        Xm = np.column_stack(Xcols)
        target = dy[lag : lag + rows]
        beta, res, rank, _ = np.linalg.lstsq(Xm, target, rcond=None)
        resid = target - Xm @ beta
        dof = rows - Xm.shape[1]
        if dof <= 0:
            lag -= 1
            continue
        sigma2 = resid @ resid / dof
        cov = sigma2 * np.linalg.pinv(Xm.T @ Xm)
        se = np.sqrt(max(cov[0, 0], 1e-300))
        best = float(beta[0] / se)
        break
    if best is None:
        return None
    crit = {"1%": -3.43, "5%": -2.86, "10%": -2.57}
    return {"adf_stat": round(best, 4), **{f"stationary_{k}": int(best < v) for k, v in crit.items()}}


def kpss_test(series: np.ndarray, regression: str = "c"):
    """KPSS level-stationarity statistic with Bartlett-window long-run
    variance (the statsmodels kpss recipe the reference's report imports,
    report_generation.py:54-55).  Null hypothesis: series IS stationary —
    complements ADF, whose null is a unit root."""
    y = np.asarray(series, float)
    y = y[~np.isnan(y)]
    n = len(y)
    if n < 10 or np.allclose(y, y[0]):
        return None
    resid = y - y.mean()
    S = np.cumsum(resid)
    lags = int(np.ceil(12.0 * (n / 100.0) ** 0.25))  # statsmodels 'legacy'
    lags = min(lags, n - 1)
    s2 = float(resid @ resid) / n
    for k in range(1, lags + 1):
        w = 1.0 - k / (lags + 1.0)
        s2 += 2.0 / n * w * float(resid[k:] @ resid[:-k])
    if s2 <= 0:
        return None
    stat = float((S @ S) / (n * n * s2))
    crit = {"1%": 0.739, "5%": 0.463, "10%": 0.347}
    # KPSS rejects stationarity when stat EXCEEDS the critical value
    return {"kpss_stat": round(stat, 4), **{f"kpss_stationary_{k}": int(stat < v) for k, v in crit.items()}}


def ts_landscape(idf: Table, ts_cols: List[str], id_col: Optional[str], output_path: str,
                 _calendars: Optional[dict] = None) -> None:
    """Per-ts-column landscape summary (reference ts_landscape :2636-2733):
    span, distinct days, records/day, weekend share, top daypart."""
    rows = []
    for c in ts_cols:
        cal = _calendars[c] if _calendars and c in _calendars else ts_calendar(idf, c)
        if not cal["n"]:
            continue
        per_day = cal["daily"][cal["daily"] > 0]
        rows.append(
            {
                "attribute": c,
                "records": cal["n"],
                "distinct_days": len(per_day),
                "avg_records_per_day": round(float(per_day.mean()), 2),
                "max_records_per_day": int(per_day.max()),
                "weekend_pct": round(float(cal["dow"][5:].sum() / cal["n"]), 4),
                # in label order, so a tie goes to the first label, as mode()'s
                "top_daypart": max(_daypart_counts(cal), key=lambda part: part[1])[0],
                "start": _ts_str(cal["min"]),
                "end": _ts_str(cal["max"]),
            }
        )
    if rows:
        pd.DataFrame(rows).to_csv(ends_with(output_path) + "ts_landscape.csv", index=False)


def ts_analyzer(
    idf: Table,
    id_col: Optional[str] = None,
    max_days: int = 3600,
    output_path: str = ".",
    output_type: str = "daily",
    tz_offset: str = "local",
    run_type: str = "local",
    auth_key: str = "NA",
    **_ignored,
) -> None:
    """Entry (reference :408-550): run eligibility + viz dumps for every
    timestamp column; write ``ts_stats.csv`` summary."""
    Path(output_path).mkdir(parents=True, exist_ok=True)
    phase = get_tracer().phase
    ts_cols = [c for c in idf.col_names if idf.columns[c].kind == "ts"]
    rows = []
    calendars: dict = {}  # of the eligible columns: computed ONCE, shared by the viz dump and the landscape
    for c in ts_cols:
        # a stage a column: the calendar program and its one fetch
        with phase("ts/eligibility", cat="block", rows=idf.nrows) as sp:
            cal = ts_calendar(idf, c, sp)
            stats = ts_eligiblity_check(idf, c, id_col, max_days, _calendar=cal)
        rows.append(stats)
        if stats.get("eligible"):
            calendars[c] = cal
            with phase("ts/viz", cat="block"):
                ts_viz_data(idf, c, output_path, output_type, _calendar=cal)
    if calendars:
        with phase("ts/landscape", cat="block", cols=len(calendars)):
            ts_landscape(idf, list(calendars), id_col, output_path, _calendars=calendars)
    # always emit the same headered schema — a headerless empty CSV breaks
    # readers and per-run schema drift breaks downstream joins
    with phase("ts/write", cat="block") as sp:
        _write_csv(pd.DataFrame(rows).reindex(columns=TS_STATS_COLUMNS),
                   ends_with(output_path) + "ts_stats.csv", sp)
