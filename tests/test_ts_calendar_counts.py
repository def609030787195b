"""The time-series inspection without a per-row host frame (PR 39): the
device calendar program against pandas on the edges a calendar has;
``ts_analyzer``'s CSVs against the per-row frame path they replaced (a copy
of it is kept here: byte for byte in every file that counts decide) and
against the files the parent commit wrote for the same table
(``tests/golden/ts_analyzer_parent``: ``ts_num_*`` equal in every count,
minimum, maximum and median, a mean within one unit of its fourth decimal);
the small-class segment aggregate against ``jax.ops.segment_*`` and float64
numpy; and the manifest's ``host_rows``."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from anovos_tpu.data_analyzer import ts_analyzer as ta
from anovos_tpu.data_transformer import datetime as dtt
from anovos_tpu.ops.datetime_kernels import CALENDAR_DAY_LANES, calendar_counts
from anovos_tpu.shared.table import Table

GOLDEN = os.path.join(os.path.dirname(__file__), "golden", "ts_analyzer_parent")


def _secs(text: str) -> int:
    return int(np.datetime64(text, "s").astype("int64"))


def _column(secs, mask=None) -> Table:
    ts = pd.Series(np.asarray(secs, "int64").astype("datetime64[s]"))
    if mask is not None:
        ts = ts.where(np.asarray(mask))
    return Table.from_pandas(pd.DataFrame({"t": ts, "x": np.arange(len(ts), dtype="float64")}))


def _by_pandas(ts: pd.Series) -> dict:
    valid = ts.dropna()
    day = valid.dt.floor("D").value_counts().sort_index()
    return {"n": len(valid), "min": valid.min(), "max": valid.max(),
            "daily": {str(d)[:10]: int(n) for d, n in day.items()},
            "hour": valid.dt.hour.value_counts().reindex(range(24), fill_value=0).to_numpy(),
            "dow": valid.dt.dayofweek.value_counts().reindex(range(7), fill_value=0).to_numpy()}


CASES = {
    "seeded_with_nulls": lambda g: (g.integers(_secs("2021-03-01"), _secs("2022-09-01"), 20_000), g.random(20_000) > 0.13),
    "one_day": lambda g: (g.integers(_secs("2024-05-05"), _secs("2024-05-06"), 500), None),
    "leap_day_and_years_end": lambda g: (g.integers(_secs("2023-12-27T00:00:00"), _secs("2024-03-02T00:00:00"), 4000), g.random(4000) > 0.02),
    "before_1970": lambda g: (g.integers(_secs("1969-11-20"), _secs("1970-02-10"), 3000), g.random(3000) > 0.3),
    "two_rows_far_apart": lambda g: (np.array([_secs("1902-01-01T00:00:01"), _secs("2037-12-31T23:59:59")]), None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_calendar_program_against_pandas(case):
    secs, mask = CASES[case](np.random.default_rng(39))
    t = _column(secs, mask)
    cal, want = ta.ts_calendar(t, "t"), _by_pandas(t.to_pandas()["t"])
    assert cal["n"] == want["n"] and cal["rows"] == len(secs)
    assert pd.Timestamp(cal["min"], unit="s") == want["min"] and pd.Timestamp(cal["max"], unit="s") == want["max"]
    days = np.nonzero(cal["daily"])[0]
    got = dict(zip((days + cal["day_lo"]).astype("datetime64[D]").astype(str), cal["daily"][days].tolist()))
    assert got == want["daily"] and len(cal["daily"]) == (cal["max"] // 86400 - cal["min"] // 86400 + 1)
    assert (cal["hour"] == want["hour"]).all() and (cal["dow"] == want["dow"]).all()


def test_calendar_program_on_an_all_null_column_and_its_raw_lanes():
    t = _column(np.zeros(300, "int64"), np.zeros(300, bool))
    cal = ta.ts_calendar(t, "t")
    assert cal["n"] == 0 and len(cal["daily"]) == 0 and cal["hour"].sum() == 0 and cal["dow"].sum() == 0
    assert ta.ts_eligiblity_check(t, "t") == {"attribute": "t", "eligible": 0, "reason": "all null"}
    # the program itself: one static class of day lanes whatever the span, lane 0 the first valid day
    secs = jnp.asarray([86400 * 10 + 5, 86400 * 12, 0, 86400 * 10 + 7], jnp.int32)
    raw = jax.device_get(calendar_counts(secs, jnp.asarray([True, True, False, True])))
    assert raw["daily"].shape == (CALENDAR_DAY_LANES,) and raw["daily"][:3].tolist() == [2, 0, 1]
    assert int(raw["daily"].sum()) == int(raw["n"]) == 3 and int(raw["min"]) == 86400 * 10 + 5


@pytest.mark.parametrize("span_days,max_days,eligible", [(10, 10, 1), (11, 10, 0), (10, 3600, 1), (0, 3600, 0)])
def test_eligibility_at_the_max_days_edge(span_days, max_days, eligible):
    t0 = _secs("2020-01-01T06:00:00")
    t = _column([t0, t0 + 3600, t0 + 86400 * span_days + 7200])
    got = ta.ts_eligiblity_check(t, "t", max_days=max_days)
    assert got["span_days"] == span_days and got["eligible"] == eligible
    assert got["distinct_days"] == (2 if span_days else 1) and got["null_pct"] == 0.0
    assert got["min_ts"] == "2020-01-01 06:00:00"


# ---- the per-row frame path as the parent commit had it, for the files that counts decide ----
def _frame_path(idf: Table, out: str, max_days: int = 3600) -> None:
    stats, eligible = [], {}
    for c in [c for c in idf.col_names if idf.columns[c].kind == "ts"]:
        ts = ta._ts_frame(idf, c)
        valid = ts.dropna()
        if len(valid) == 0:
            stats.append({"attribute": c, "eligible": 0, "reason": "all null"})
            continue
        span_days = (valid.max() - valid.min()).days
        distinct_days = valid.dt.date.nunique()
        stats.append({"attribute": c, "eligible": int(0 < span_days <= max_days and distinct_days > 1),
                      "span_days": span_days, "distinct_days": distinct_days,
                      "null_pct": round(1 - len(valid) / max(idf.nrows, 1), 4),
                      "min_ts": str(valid.min()), "max_ts": str(valid.max())})
        if not stats[-1]["eligible"]:
            continue
        feats = eligible[c] = ta.ts_processed_feats(idf, c).dropna(subset=[c])
        daily = feats.groupby("yyyymmdd_col").size().reset_index(name="count")
        daily.to_csv(f"{out}/ts_daily_{c}.csv", index=False)
        for name, key in (("hourly", "hour"), ("weekly", "dayofweek"), ("daypart", "daypart")):
            feats.groupby(key).size().reset_index(name="count").to_csv(f"{out}/ts_{name}_{c}.csv", index=False)
        y = daily["count"].to_numpy()
        dec, adf, kpss = ta.seasonal_decompose_ma(y, period=7), ta.adf_test(y), ta.kpss_test(y)
        if dec is not None:
            pd.DataFrame({"date": daily["yyyymmdd_col"], "observed": daily["count"], "trend": np.round(dec[0], 4),
                          "seasonal": np.round(dec[1], 4), "residual": np.round(dec[2], 4)}
                         ).to_csv(f"{out}/ts_decompose_{c}.csv", index=False)
        if adf is not None or kpss is not None:
            pd.DataFrame([{"attribute": c, **(adf or {}), **(kpss or {})}]).to_csv(
                f"{out}/ts_stationarity_{c}.csv", index=False)
    rows = []
    for c, feats in eligible.items():
        daily = feats.groupby("yyyymmdd_col").size()
        rows.append({"attribute": c, "records": len(feats), "distinct_days": int(daily.shape[0]),
                     "avg_records_per_day": round(float(daily.mean()), 2), "max_records_per_day": int(daily.max()),
                     "weekend_pct": round(float(feats["is_weekend"].mean()), 4),
                     "top_daypart": feats["daypart"].mode().iloc[0],
                     "start": str(feats[c].min()), "end": str(feats[c].max())})
    if rows:
        pd.DataFrame(rows).to_csv(f"{out}/ts_landscape.csv", index=False)
    pd.DataFrame(stats).reindex(columns=ta.TS_STATS_COLUMNS).to_csv(f"{out}/ts_stats.csv", index=False)


def trips(rows=3000, seed=11) -> pd.DataFrame:
    """The table the parent's files under tests/golden/ts_analyzer_parent were written for."""
    rng = np.random.default_rng(seed)
    a = _secs("2019-12-20T00:00:00") + rng.integers(0, 86400 * 75, rows)
    b = a + rng.integers(60, 86400 * 3, rows)
    return pd.DataFrame({
        "pickup": a.astype("datetime64[s]"),
        "dropoff": pd.Series(b.astype("datetime64[s]")).where(rng.random(rows) > 0.07),
        "fare": np.round(rng.lognormal(2.3, 0.8, rows), 2),
        "dist": pd.Series(np.round(rng.gamma(2.0, 1.5, rows), 2)).where(rng.random(rows) > 0.1),
        "pax": rng.integers(0, 7, rows),
        "flag": rng.choice(["Y", "N"], rows, p=[0.1, 0.9]),
        "zone": pd.Series(rng.choice([f"z{i:02d}" for i in range(14)], rows)).where(rng.random(rows) > 0.05),
    })


def _tie_and_single_day() -> pd.DataFrame:
    """Two dayparts with the same count (mode()'s tie: the first label in sort
    order), a column of one day only (not eligible) and one all null."""
    day = _secs("2022-02-27T00:00:00")
    hours = [7] * 5 + [12] * 5 + [22] * 2  # early_hours and work_hours tie at 5
    t = np.array([day + 86400 * (i % 4) + h * 3600 + i for i, h in enumerate(hours)])
    return pd.DataFrame({"t": t.astype("datetime64[s]"),
                         "single": (day + np.arange(12) * 60).astype("datetime64[s]"),
                         "none": pd.Series([pd.NaT] * 12, dtype="datetime64[s]"),
                         "v": np.arange(12, dtype="float64")})


COUNT_FILES = ("ts_daily_", "ts_hourly_", "ts_weekly_", "ts_daypart_", "ts_decompose_", "ts_stationarity_",
               "ts_landscape", "ts_stats")


@pytest.mark.parametrize("table", [trips, _tie_and_single_day])
def test_csvs_that_counts_decide_are_the_frame_paths_byte_for_byte(table, tmp_path):
    idf = Table.from_pandas(table())
    new, old = tmp_path / "new", tmp_path / "old"
    old.mkdir()
    ta.ts_analyzer(idf, output_path=str(new))
    _frame_path(idf, str(old))
    theirs = sorted(os.listdir(old))
    assert theirs and theirs == sorted(f for f in os.listdir(new) if f.startswith(COUNT_FILES))
    for f in theirs:
        assert (new / f).read_bytes() == (old / f).read_bytes(), f
    if table is _tie_and_single_day:
        land = pd.read_csv(new / "ts_landscape.csv")
        assert land["top_daypart"].tolist() == ["early_hours"] and land["attribute"].tolist() == ["t"]
        assert pd.read_csv(new / "ts_stats.csv")["eligible"].tolist() == [1, 0, 0]


def test_numeric_and_category_files_against_the_parent_commits(tmp_path):
    ta.ts_analyzer(Table.from_pandas(trips()), output_path=str(tmp_path))
    for f in sorted(os.listdir(GOLDEN)):
        got, want = pd.read_csv(tmp_path / f), pd.read_csv(os.path.join(GOLDEN, f))
        if f.startswith("ts_cat_daily_"):
            assert (tmp_path / f).read_bytes() == open(os.path.join(GOLDEN, f), "rb").read(), f
            continue
        exact = [c for c in want.columns if c != "mean"]
        pd.testing.assert_frame_equal(got[exact], want[exact], check_exact=True, obj=f)
        assert (got["mean"] - want["mean"]).abs().max() <= 1.0001e-4, f


# ---- the per-bucket aggregate: one function, the moments chosen by the static class ----
def _block(rows, k, nseg, seed, empty=()):
    g = np.random.default_rng(seed)
    ids = g.integers(0, nseg, rows)
    ids = np.where(np.isin(ids, empty), (ids + 1) % nseg, ids).astype(np.int32)  # (3, nseg - 1) -> 4, 0
    valid = g.random(rows) > 0.1
    V = np.round(g.normal(40.0, 30.0, (rows, k)), 2).astype(np.float32)
    Mv = g.random((rows, k)) > 0.15
    return ids, valid, V, Mv


def _by_scatter(ids, valid, V, Mv, nseg):
    """count, sum, sum of squares, min, max with jax.ops.segment_* as the parent took them."""
    out = []
    for j in range(V.shape[1]):
        o = Mv[:, j] & valid
        s = jnp.where(o, ids, nseg)
        v = jnp.asarray(V[:, j])
        out.append([jax.ops.segment_sum(jnp.where(o, x, 0.0), s, num_segments=nseg + 1)[:nseg] for x in (1.0, v, v * v)]
                   + [jax.ops.segment_min(jnp.where(o, v, jnp.inf), s, num_segments=nseg + 1)[:nseg],
                      jax.ops.segment_max(jnp.where(o, v, -jnp.inf), s, num_segments=nseg + 1)[:nseg]])
    return [np.stack([np.asarray(col[i]) for col in out]) for i in range(5)]


def _by_numpy(ids, valid, V, Mv, nseg):
    k = V.shape[1]
    cnt, sm, mn, mx, med = (np.zeros((k, nseg)) for _ in range(5))
    for j in range(k):
        for b in range(nseg):
            x = V[(ids == b) & valid & Mv[:, j], j].astype(np.float64)
            cnt[j, b] = len(x)
            if len(x):
                sm[j, b], mn[j, b], mx[j, b], med[j, b] = x.sum(), x.min(), x.max(), np.median(x)
    return cnt, sm, mn, mx, med


@pytest.mark.parametrize("nseg,rows", [(8, 5000), (32, 5000), (32, 3 * dtt._DENSE_CHUNK_ROWS)])
def test_small_class_aggregate_takes_no_scatter_and_agrees(nseg, rows):
    ids, valid, V, Mv = _block(rows, 5, nseg, seed=nseg + rows, empty=(3, nseg - 1))
    args = (jnp.asarray(ids), jnp.asarray(valid), jnp.asarray(V), jnp.asarray(Mv))
    lowered = dtt._segment_aggregate_jit.lower(*args, nseg=nseg)
    assert "scatter" not in lowered.as_text() and "dot_general" in lowered.as_text()
    assert "ts/segment_aggregate" in lowered.as_text(debug_info=True)  # the scope a trace reader finds
    cnt, sm, sq, mn, mx, med = (np.asarray(a) for a in dtt._segment_aggregate_jit(*args, nseg=nseg))
    s_cnt, s_sm, s_sq, s_mn, s_mx = _by_scatter(ids, valid, V, Mv, nseg)
    assert (cnt == s_cnt).all() and (mn == s_mn).all() and (mx == s_mx).all()
    assert np.allclose(sm, s_sm, rtol=1e-5) and np.allclose(sq, s_sq, rtol=1e-5)  # f32 sums in another order
    n_cnt, n_sm, n_mn, n_mx, n_med = _by_numpy(ids, valid, V, Mv, nseg)
    live = n_cnt > 0
    assert (cnt == n_cnt).all() and not live[:, 3].any() and not live[:, nseg - 1].any()
    assert (mn[live] == n_mn[live]).all() and (mx[live] == n_mx[live]).all()
    assert np.isposinf(mn[~live]).all() and np.isneginf(mx[~live]).all()
    assert np.allclose(sm[live], n_sm[live], rtol=3e-6) and np.allclose(med[live], n_med[live], rtol=1e-6, atol=1e-6)


def test_wide_class_takes_no_scatter_and_keeps_the_scatters_results():
    """Count, min and max are the parent's five scatters' bit for bit; the sums are f32 sums in another order."""
    nseg, (ids, valid, V, Mv) = 4096, _block(6000, 3, 4096, seed=7)
    args = (jnp.asarray(ids), jnp.asarray(valid), jnp.asarray(V), jnp.asarray(Mv))
    lowered = dtt._segment_aggregate_jit.lower(*args, nseg=nseg)
    assert "scatter" not in lowered.as_text() and "dot_general" in lowered.as_text()
    named = lowered.as_text(debug_info=True)  # the scopes a trace reader tells the wide class by
    assert "ts/segment_aggregate/wide/moments" in named and "ts/segment_aggregate/wide/medians" in named
    cnt, sm, sq, mn, mx, med = (np.asarray(a) for a in dtt._segment_aggregate_jit(*args, nseg=nseg))
    s_cnt, s_sm, s_sq, s_mn, s_mx = _by_scatter(ids, valid, V, Mv, nseg)
    assert (cnt == s_cnt).all() and (mn == s_mn).all() and (mx == s_mx).all()
    assert np.allclose(sm, s_sm, rtol=1e-6) and np.allclose(sq, s_sq, rtol=1e-6)
    n_cnt, _, _, _, n_med = _by_numpy(ids, valid, V, Mv, nseg)
    assert np.allclose(med[n_cnt > 0], n_med[n_cnt > 0], rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("rows", [4096, 3 * dtt._DENSE_CHUNK_ROWS], ids=["a_sort_a_column", "grouped_rows"])
def test_the_median_sorts_in_column_blocks_with_the_same_result(rows, monkeypatch):
    """At a class that still sorts (128: above ``_DENSE_SEGMENTS_MAX``), on both sides of the wide side's rule:
    few rows a bucket (one two-key sort a column, ``_SORT_BLOCK_CELLS // rows`` columns at a time) and many (ONE
    sort of the buckets whatever the columns: the block size is nothing to it)."""
    ids, valid, V, Mv = _block(rows, 6, 128, seed=3)
    args = (jnp.asarray(ids), jnp.asarray(valid), jnp.asarray(V), jnp.asarray(Mv))
    grouped = dtt._groups_rows(rows, 128)
    assert grouped == (rows > 4096)
    loops = dtt._segment_aggregate_jit.lower(*args, nseg=128).as_text().count("stablehlo.while")
    assert loops == (4 if grouped else 0)  # grouped: the moments' scan, the digits, a digit's steps, the last pass's
    whole = [np.asarray(a) for a in dtt._segment_aggregate_jit(*args, nseg=128)]
    assert dtt.aggregate_routes(rows, 6, 128)["wide_sorts"] == (1 if grouped else 6)
    monkeypatch.setattr(dtt, "_SORT_BLOCK_CELLS", 2 * rows)  # two columns at a time: a lax.map of three steps
    dtt._segment_aggregate_jit.clear_cache()
    try:
        text = dtt._segment_aggregate_jit.lower(*args, nseg=128).as_text()
        assert text.count("stablehlo.while") == loops + (0 if grouped else 1) and text.count("stablehlo.sort") == 1
        blocked = [np.asarray(a) for a in dtt._segment_aggregate_jit(*args, nseg=128)]
    finally:
        dtt._segment_aggregate_jit.clear_cache()
    for a, b in zip(whole, blocked):
        assert (a == b).all()


def test_the_inspection_brings_no_column_to_the_host(tmp_path):
    """Every stage that fetches counts ``fetches`` and ``host_rows``; none is as long as the table."""
    from anovos_tpu.obs import get_tracer

    idf = Table.from_pandas(trips(rows=700))
    tracer = get_tracer()
    ta.ts_analyzer(idf, output_path=str(tmp_path))
    spans = [s for s in tracer.drain() if s.name.startswith("ts/")]
    fetching = [s for s in spans if "fetches" in s.args]
    assert {s.name for s in fetching} == {"ts/eligibility", "ts/viz/num", "ts/viz/cat"}
    assert all(s.args["host_rows"] == 0 for s in fetching) and not any(s.name == "ts/feats" for s in spans)
    # and the counter counts: a fetched column is seen
    class Row:
        attrs: dict = {}

        def add(self, **kw):
            self.attrs.update(kw)

    ta.counted_fetch(idf.columns["fare"].data, idf, Row())
    assert Row.attrs == {"fetches": 1, "host_rows": idf.padded_rows}


def test_auto_detection_looks_at_a_device_slice(monkeypatch):
    from anovos_tpu.data_ingest import ts_auto_detection as tad

    g = np.random.default_rng(5)
    idf = Table.from_pandas(pd.DataFrame({"epoch": g.integers(1_500_000_000, 1_600_000_000, 5000),
                                          "small": g.integers(0, 9, 5000), "x": g.normal(size=5000)}))
    seen = []
    real = np.asarray


    def watched(a, *rest, **kw):
        if isinstance(a, jax.Array):  # what comes from the device
            seen.append(a.shape)
        return real(a, *rest, **kw)

    monkeypatch.setattr(tad.np, "asarray", watched)
    assert tad.ts_loop_cols_pre(idf) == ["epoch"]
    assert len(seen) == 4 and all(shape == (1000,) for shape in seen)  # two integer columns, data and mask
