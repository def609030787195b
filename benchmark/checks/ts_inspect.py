"""The time-series inspection of the ``ts_inspect`` mix against float64 pandas
and numpy on the same parquet files: the ``groupby``s a per-row frame invites
are what this reference IS.  Written from the upstream's description of each
table (Anovos v1.1.0 ``data_analyzer/ts_analyzer.py``) and independent of the
program.  For every timestamp column of ``timestamps``:

exact: records per day, per hour, per weekday and per daypart (``late_hours``
0-5, ``early_hours`` 6-9, ``work_hours`` 10-16, ``evening_hours`` 17-20,
``night_hours`` 21-23); the row of ``ts_stats.csv`` (eligible, span in whole
days, distinct days, null share to 4 decimals, first and last second) and of
``ts_landscape.csv`` (records, distinct days, mean and largest day, weekend
share, the fullest daypart, a tie to the first label in sort order); per day,
daypart and weekday the count, minimum and maximum of every column of
``numeric`` (a minimum is the float32 the table stores, as the file's 4
decimals hold it: within one unit of the fourth decimal or of float32's last
bit, whichever way the writer rounds; the row counts the entries beyond); per
day the rows of each value of ``categorical`` (the ten most frequent, the rest
as ``Others``); which rows of the decomposition have a trend; that the
stationarity file holds an ADF and a KPSS statistic.
toleranced: ``bucket_mean`` and ``bucket_median`` (every bucket of every grain
and column), ``decompose`` (trend, seasonal and residual of the daily counts:
a centred moving average of ``period`` days, the mean detrended value of each
phase re-centred, the rest) and ``kpss`` (level stationarity, Bartlett window of
ceil(12 (n/100)^(1/4)) lags).  The ADF statistic is held to presence and, by
the run's digest, to the same bytes in every pass: the program picks its lag
by a rule of its own where the upstream's statsmodels picks by AIC, so a plain
OLS of mine would test my reading of that rule and not the number.
args: ``timestamps``, ``numeric``, ``categorical``, ``max_days``, ``period``.
Tables: ts_stats, ts_landscape and, per timestamp column, ts_daily, ts_hourly,
ts_weekly, ts_daypart, ts_num_daily, ts_num_hourly, ts_num_weekly,
ts_cat_daily, ts_decompose, ts_stationarity."""

import glob
import os

import numpy as np
import pandas as pd

from benchmark.harness.check import exact, toleranced

DAYPARTS = ["late_hours", "early_hours", "work_hours", "evening_hours", "night_hours"]
DAYPART_LAST_HOUR = [5, 9, 16, 20]  # of the first four; the fifth runs to 23
WEEKDAYS = ["Mon", "Tue", "Wed", "Thu", "Fri", "Sat", "Sun"]
GRAINS = {"daily": "date", "hourly": "bucket", "weekly": "bucket"}  # file infix -> its key column
STATS = ["eligible", "span_days", "distinct_days", "null_pct", "min_ts", "max_ts"]
LANDSCAPE = ["records", "distinct_days", "avg_records_per_day", "max_records_per_day", "weekend_pct",
             "top_daypart", "start", "end"]
TOP_CATEGORIES = 10


def _csv(out_dir, traffic, name):
    found = glob.glob(os.path.join(out_dir, traffic["tables"][name]))
    return pd.read_csv(found[0], float_precision="round_trip") if found else None


EXACT = ("daily", "hourly", "weekday", "daypart", "stats", "landscape", "bucket_count", "bucket_min", "bucket_max",
         "cat_daily", "decompose_rows", "stationarity")
TOLERANCED = ("bucket_mean", "bucket_median", "decompose", "kpss")


def _empty():
    """The exact answers (dicts by key) and the toleranced ones (later Series), empty."""
    return {k: {} for k in EXACT}, {k: {} for k in TOLERANCED}


def _plain(v):
    return v.item() if isinstance(v, np.generic) else v


def read(out_dir, traffic, args):
    ans, tol = _empty()
    for name, table in (("stats", "ts_stats"), ("landscape", "ts_landscape")):
        fields = STATS if name == "stats" else LANDSCAPE
        for _, r in _csv(out_dir, traffic, table).iterrows():
            ans[name].update({f"{r['attribute']}|{f}": _plain(r[f]) for f in fields})
    for col in args["timestamps"]:
        for key, table, column in (("daily", "ts_daily", "yyyymmdd_col"), ("hourly", "ts_hourly", "hour"),
                                   ("weekday", "ts_weekly", "dayofweek"), ("daypart", "ts_daypart", "daypart")):
            t = _csv(out_dir, traffic, f"{table}_{col}")
            ans[key].update({f"{col}|{k if isinstance(k, str) else int(k)}": int(n) for k, n in zip(t[column], t["count"])})
        for grain, column in GRAINS.items():
            t = _csv(out_dir, traffic, f"ts_num_{grain}_{col}")
            keys = [f"{col}|{grain}|{b}|{a}" for b, a in zip(t[column], t["attribute"])]
            ans["bucket_count"].update(zip(keys, t["count"].astype(int).tolist()))
            ans["bucket_min"].update(zip(keys, t["min"].tolist()))
            ans["bucket_max"].update(zip(keys, t["max"].tolist()))
            tol["bucket_mean"].update(zip(keys, t["mean"].tolist()))
            tol["bucket_median"].update(zip(keys, t["median"].tolist()))
        t = _csv(out_dir, traffic, f"ts_cat_daily_{col}")
        ans["cat_daily"].update({f"{col}|{a}|{d}|{c}": int(n)
                                 for d, a, c, n in zip(t["date"], t["attribute"], t["category"], t["count"])})
        t = _csv(out_dir, traffic, f"ts_decompose_{col}")
        if t is not None:
            ans["decompose_rows"][col] = f"{len(t)} rows, {int(t['trend'].notna().sum())} with a trend"
            for part in ("trend", "seasonal", "residual"):
                tol["decompose"].update({f"{col}|{d}|{part}": v for d, v in zip(t["date"], t[part]) if v == v})
        t = _csv(out_dir, traffic, f"ts_stationarity_{col}")
        if t is not None:
            ans["stationarity"][col] = ", ".join(c for c in ("adf_stat", "kpss_stat") if c in t and t[c].notna().all())
            if "kpss_stat" in t:
                tol["kpss"][col] = float(t["kpss_stat"].iloc[0])
    return {**ans, **{k: pd.Series(v, dtype="float64") for k, v in tol.items()}}


def decompose(y: np.ndarray, period: int):
    """(trend, seasonal, residual) of an additive decomposition by a centred
    moving average of an odd ``period``; None for a series under two periods."""
    n = len(y)
    if n < 2 * period:
        return None
    half = period // 2
    trend = np.full(n, np.nan)
    for i in range(half, n - half):
        trend[i] = y[i - half:i + half + 1].mean()
    phase = np.array([np.nanmean((y - trend)[p::period]) for p in range(period)])
    seasonal = np.tile(phase - phase.mean(), n // period + 1)[:n]
    return trend, seasonal, y - trend - seasonal


def kpss(y: np.ndarray):
    """The KPSS statistic of level stationarity; None under ten points or for a constant."""
    n = len(y)
    if n < 10 or np.ptp(y) == 0:
        return None
    e = y - y.mean()
    lags = min(int(np.ceil(12.0 * (n / 100.0) ** 0.25)), n - 1)
    s2 = (e @ e + 2.0 * sum((1.0 - k / (lags + 1.0)) * (e[k:] @ e[:-k]) for k in range(1, lags + 1))) / n
    return float((np.cumsum(e) ** 2).sum() / (n * n * s2)) if s2 > 0 else None


def _answers(main: pd.DataFrame, args: dict, hold=lambda x: x) -> dict:
    """Every answer from the frame.  ``hold`` is the precision the value
    columns and the daily series are held in (the control's bfloat16)."""
    ans, tol = _empty()
    values = main[args["numeric"]].astype("float64").apply(hold)
    for col in args["timestamps"]:
        ok = main[col].notna().to_numpy()
        secs = main[col].to_numpy().astype("datetime64[s]").astype("int64")[ok]
        n, day, hour = len(secs), secs // 86400, secs % 86400 // 3600
        dow = (day + 3) % 7  # 1 January 1970 was a Thursday; Monday is 0
        date = (day.astype("datetime64[D]")).astype(str)
        part = np.asarray(DAYPARTS)[np.searchsorted(DAYPART_LAST_HOUR, hour, side="left")]
        per_day = pd.Series(date).value_counts().sort_index()
        per_part = pd.Series(part).value_counts()
        ans["daily"].update({f"{col}|{d}": int(c) for d, c in per_day.items()})
        ans["hourly"].update({f"{col}|{int(h)}": int(c) for h, c in pd.Series(hour).value_counts().items()})
        ans["weekday"].update({f"{col}|{int(d)}": int(c) for d, c in pd.Series(dow).value_counts().items()})
        ans["daypart"].update({f"{col}|{p}": int(c) for p, c in per_part.items()})
        if n:
            first, last = (str(pd.Timestamp(int(s), unit="s")) for s in (secs.min(), secs.max()))
            span = int((secs.max() - secs.min()) // 86400)
            ans["stats"].update({f"{col}|{f}": v for f, v in zip(STATS, (
                int(0 < span <= args["max_days"] and len(per_day) > 1), span, len(per_day),
                round(1 - n / max(len(main), 1), 4), first, last))})
            ans["landscape"].update({f"{col}|{f}": v for f, v in zip(LANDSCAPE, (
                n, len(per_day), round(n / len(per_day), 2), int(per_day.max()), round(float((dow >= 5).sum()) / n, 4),
                min(per_part[per_part == per_part.max()].index), first, last))})
        for grain, key in (("daily", date), ("hourly", part), ("weekly", np.asarray(WEEKDAYS)[dow])):
            agg = values[ok].groupby(key).agg(["count", "min", "max", "mean", "median"])
            for a in args["numeric"]:
                sub = agg[a][agg[a]["count"] > 0]
                keys = [f"{col}|{grain}|{b}|{a}" for b in sub.index]
                ans["bucket_count"].update(zip(keys, sub["count"].astype(int).tolist()))
                # the table stores float32: its smallest value is the float32 of the smallest
                for name in ("min", "max"):
                    ans[f"bucket_{name}"].update(zip(keys, np.round(sub[name].to_numpy(np.float32).astype(np.float64), 4).tolist()))
                tol["bucket_mean"].update(zip(keys, sub["mean"].tolist()))
                tol["bucket_median"].update(zip(keys, sub["median"].tolist()))
        for a in args["categorical"]:
            cat = main[a][ok]
            top = cat.value_counts().index[:TOP_CATEGORIES]
            shown = cat.where(cat.isin(top), "Others").where(cat.notna())
            counts = pd.crosstab(date, shown.to_numpy())
            ans["cat_daily"].update({f"{col}|{a}|{d}|{c}": int(v) for (d, c), v in counts.stack().items() if v})
        y = hold(per_day.to_numpy("float64"))
        dec = decompose(y, args["period"])
        if dec is not None:
            ans["decompose_rows"][col] = f"{len(y)} rows, {int(np.isfinite(dec[0]).sum())} with a trend"
            for name, series in zip(("trend", "seasonal", "residual"), dec):
                tol["decompose"].update({f"{col}|{d}|{name}": v for d, v in zip(per_day.index, series) if v == v})
        stat = kpss(y)
        if stat is not None:
            tol["kpss"][col] = stat
        if len(y) >= 10:
            ans["stationarity"][col] = "adf_stat, kpss_stat" if stat is not None else "adf_stat"
    return {**ans, **{k: pd.Series(v, dtype="float64") for k, v in tol.items()}}


def reference(frames, args):
    return _answers(frames.main, args)


def control(ref, frames, args):
    """The control: the reference's toleranced answers computed from value
    columns and a daily series held in bfloat16 (sums still accumulate in
    float64), in the program's place; the exact answers stay the reference's."""
    import ml_dtypes

    low = _answers(frames.main, args, hold=lambda x: np.asarray(x, np.float64).astype(ml_dtypes.bfloat16).astype(np.float64))
    return {**ref, **{k: low[k] for k in TOLERANCED}}


def stored(name: str, got: dict, want: dict) -> dict:
    """The entries of ``got`` that are not the stored float32 of ``want`` as
    4 decimals can hold it, counted against the limit 0."""
    off = [k for k in set(want) | set(got) if k not in got or k not in want
           or not abs(got[k] - want[k]) <= 1.000001e-4 + 2.0 ** -23 * abs(want[k])]
    return {"name": name, "value": len(off), "limit": 0, "ok": not off,
            "detail": ", ".join(f"{k}: {got.get(k)} != {want.get(k)}" for k in sorted(off)[:5])}


def compare(ans, ref, tolerances, args):
    rows = [exact(name, ans[key], ref[key]) for name, key in (
        ("daily_counts", "daily"), ("hourly_counts", "hourly"), ("weekday_counts", "weekday"),
        ("daypart_counts", "daypart"), ("ts_stats", "stats"), ("ts_landscape", "landscape"),
        ("bucket_count", "bucket_count"), ("cat_daily", "cat_daily"), ("decompose_rows", "decompose_rows"),
        ("stationarity", "stationarity"))]
    rows += [stored("bucket_min", ans["bucket_min"], ref["bucket_min"]),
             stored("bucket_max", ans["bucket_max"], ref["bucket_max"])]
    for name in TOLERANCED:
        extra = ans[name].index.difference(ref[name].index)
        row = toleranced(name, ans[name], ref[name], tolerances[name]) if len(ref[name]) else {
            "name": name, "value": 0.0, "limit": 1.0, "ok": True, "detail": "nothing to compare"}
        if len(extra):  # an entry the reference does not have
            row = dict(row, value=float("inf"), ok=False, detail=f"{len(extra)} entries the reference lacks: {extra[0]}")
        rows.append(row)
    return rows
