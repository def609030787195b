"""``drift_detector.statistics`` and ``stability_index_computation`` where the
columns of one call differ by orders of magnitude in cardinality and by which
dataset has a value at all: a string column of 2 values beside one of 5,000
(the compare-and-reduce route and the flattened scatter-add, by
``ANOVOS_DENSE_HIST_BUDGET`` on either side of the sweep), values seen on one
side only, a column empty in the source and full in the target and the
reverse, a period without a value.  Every answer against float64 numpy and
pandas written here."""

import warnings

import jax
import numpy as np
import pandas as pd
import pytest

from anovos_tpu.drift_stability import drift_detector as dd
from anovos_tpu.drift_stability.stability import stability_index_computation
from anovos_tpu.obs import get_tracer
from anovos_tpu.ops import drift_kernels
from anovos_tpu.ops.segment import bucket_segments_pow2
from anovos_tpu.shared.table import Table

ROWS_SOURCE, ROWS_TARGET, MANY = 6_000, 7_000, 5_000
METHODS = ["PSI", "HD", "JSD", "KS"]


def _frame(rows: int, seed: int, shift: float, first: int) -> pd.DataFrame:
    """``many``: ``MANY`` values by a power law from ``first`` on, so that two
    frames share their frequent values and each has values the other lacks."""
    rng = np.random.default_rng(seed)
    ranks = first + np.minimum((rng.pareto(0.9, rows) * 40).astype(np.int64), MANY - 1)
    many = np.array([f"v{r:05d}" for r in ranks], dtype=object)
    many[rng.random(rows) < 0.07] = None
    two = np.where(rng.random(rows) < 0.7 + shift, "36 months", "60 months").astype(object)
    wide = np.round(np.exp(rng.normal(10.0 + shift, 1.2, rows)))
    wide[rng.random(rows) < 0.3] = np.nan
    counts = rng.poisson(3.0 + 4 * shift, rows).astype(np.float64)  # whole numbers: values on the cut-offs
    return pd.DataFrame({"two": two, "many": many, "wide": wide.astype(np.float32).astype(np.float64),
                         "counts": counts})


@pytest.fixture(scope="module")
def frames():
    src, tgt = _frame(ROWS_SOURCE, 11, 0.0, 0), _frame(ROWS_TARGET, 12, 0.15, 300)
    src["only_target"], tgt["only_target"] = None, np.where(np.arange(ROWS_TARGET) % 3 == 0, "x", "y")
    tgt["only_target"] = tgt["only_target"].astype(object)
    src["only_source"], tgt["only_source"] = np.where(np.arange(ROWS_SOURCE) % 4 == 0, "p", "q").astype(object), None
    src["num_only_target"], tgt["num_only_target"] = np.nan, np.arange(ROWS_TARGET, dtype=np.float64)
    src["num_only_source"], tgt["num_only_source"] = np.arange(ROWS_SOURCE, dtype=np.float64) % 17, np.nan
    return src, tgt


def _distances(p, q):
    p, q = np.where(p == 0, 1e-4, p), np.where(q == 0, 1e-4, q)
    m = (p + q) / 2
    return {"PSI": ((p - q) * np.log(p / q)).sum(), "HD": np.sqrt(((np.sqrt(p) - np.sqrt(q)) ** 2).sum() / 2),
            "JSD": ((p * np.log(p / m)).sum() + (q * np.log(q / m)).sum()) / 2,
            "KS": np.abs(np.cumsum(p) - np.cumsum(q)).max() if len(p) else 0.0}


def _reference(src: pd.DataFrame, tgt: pd.DataFrame, bins: int = 10) -> pd.DataFrame:
    """Float64: equal-range bins from the source's min and max (right-closed),
    frequencies over all rows, an empty bin 1e-4; a numeric column without a
    value in the source has no bins and leaves."""
    out = {}
    for c in tgt.columns:
        s, t = src[c], tgt[c]
        if s.dropna().map(lambda v: isinstance(v, str)).any() or t.dropna().map(lambda v: isinstance(v, str)).any():
            ps, qs = s.dropna().value_counts(), t.dropna().value_counts()
            keys = sorted(set(ps.index) | set(qs.index))
            p, q = ps.reindex(keys).fillna(0).to_numpy(float), qs.reindex(keys).fillna(0).to_numpy(float)
        else:
            sv, tv = s.to_numpy(float), t.to_numpy(float)
            sv, tv = sv[~np.isnan(sv)], tv[~np.isnan(tv)]
            if not len(sv):
                continue
            cuts = sv.min() + np.arange(1, bins) * ((sv.max() - sv.min()) / bins)
            p = np.bincount(np.searchsorted(cuts, sv, side="left"), minlength=bins).astype(float)
            q = np.bincount(np.searchsorted(cuts, tv, side="left"), minlength=bins).astype(float)
        out[c] = _distances(p / len(s), q / len(t))
    return pd.DataFrame(out).T[METHODS].astype(float)


def _statistics(src: pd.DataFrame, tgt: pd.DataFrame, tmp_path, budget=None, monkeypatch=None, **kw):
    if budget is not None:
        monkeypatch.setenv("ANOVOS_DENSE_HIST_BUDGET", str(budget))
    jax.clear_caches()  # the route is chosen when the side program is traced
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return dd.statistics(Table.from_pandas(tgt), Table.from_pandas(src), method_type="all", use_sampling=False,
                             source_path=str(tmp_path), **kw).set_index("attribute")


@pytest.fixture(scope="module")
def answers(frames, tmp_path_factory):
    """The same call by both routes of the categorical count: every sweep
    inside the dense budget, and none."""
    mp = pytest.MonkeyPatch()
    try:
        return {name: _statistics(*frames, tmp_path_factory.mktemp(name), budget, mp)
                for name, budget in (("dense", 1 << 40), ("scatter", 1))}
    finally:
        mp.undo()
        jax.clear_caches()


@pytest.mark.parametrize("route", ["dense", "scatter"])
def test_two_values_beside_five_thousand_against_float64(frames, answers, route):
    got, want = answers[route], _reference(*frames)
    assert sorted(got.index) == sorted(want.index) and "num_only_target" not in got.index
    assert np.abs(got[METHODS].to_numpy(float) - want.loc[got.index].to_numpy(float)).max() <= 5.1e-5  # four decimals
    assert (got["flagged"] == (got[METHODS] > 0.1).any(axis=1).astype(int)).all()
    assert got.loc["two", "flagged"] == 1 and got.loc["many", "PSI"] > 1.0


def test_both_routes_give_the_same_answers(answers):
    pd.testing.assert_frame_equal(answers["dense"], answers["scatter"])


def test_the_route_follows_the_budget(frames, tmp_path, monkeypatch):
    """On either side of the sweep: rows x lanes x values of the widest class."""
    seen = []
    flat = drift_kernels._flat_counts

    def spy(idx, valid, nbins):
        rows, k = idx.shape
        seen.append((nbins, nbins <= drift_kernels._CMP_LANES_MAX and rows * k * nbins <= drift_kernels._dense_budget()))
        return flat(idx, valid, nbins)

    monkeypatch.setattr(drift_kernels, "_flat_counts", spy)
    _statistics(*frames, tmp_path / "a", 1 << 40, monkeypatch)
    dense = [d for nbins, d in seen if nbins > 10]
    seen.clear()
    _statistics(*frames, tmp_path / "b", 1, monkeypatch)
    assert dense and all(dense) and not any(d for _, d in seen)
    jax.clear_caches()


def test_values_on_one_side_only_count_as_an_empty_bin_on_the_other(frames, answers):
    src, tgt = frames
    s, t = set(src["many"].dropna()), set(tgt["many"].dropna())
    assert len(s - t) > 100 and len(t - s) > 100 and len(s & t) > 100
    want = _reference(src, tgt).loc["many"]
    assert answers["dense"].loc["many", "PSI"] == pytest.approx(want["PSI"], abs=5.1e-5)
    # the union is what is counted: leaving the target's own values out moves the answer
    one_sided = _reference(src, tgt.assign(many=tgt["many"].where(tgt["many"].isin(s)))).loc["many"]
    assert abs(one_sided["PSI"] - want["PSI"]) > 0.01


def test_a_string_column_empty_in_the_source_stays_and_every_p_is_an_empty_bin(frames, answers):
    got = answers["dense"].loc["only_target"]
    q = np.array([np.mean(np.arange(ROWS_TARGET) % 3 == 0), np.mean(np.arange(ROWS_TARGET) % 3 != 0)])
    want = _distances(np.zeros(2), q)
    assert [got[m] for m in METHODS] == pytest.approx([want[m] for m in METHODS], abs=5.1e-5) and got["flagged"] == 1


def test_a_string_column_empty_in_the_target_stays_and_every_q_is_an_empty_bin(frames, answers):
    got = answers["scatter"].loc["only_source"]
    want = _distances(np.array([0.25, 0.75]), np.zeros(2))
    assert [got[m] for m in METHODS] == pytest.approx([want[m] for m in METHODS], abs=5.1e-5)


def test_a_numeric_column_empty_in_the_source_leaves_with_the_warning_and_one_empty_in_the_target_stays(frames, tmp_path):
    src, tgt = frames
    with pytest.warns(UserWarning, match="too much null values. Dropping num_only_target"):
        got = dd.statistics(Table.from_pandas(tgt), Table.from_pandas(src), method_type="PSI", use_sampling=False,
                            source_path=str(tmp_path)).set_index("attribute")
    assert "num_only_target" not in got.index
    p = np.bincount(np.searchsorted(1.6 * np.arange(1, 10), np.arange(ROWS_SOURCE) % 17, side="left"), minlength=10)
    want = _distances(p / ROWS_SOURCE, np.zeros(10))["PSI"]
    assert got.loc["num_only_source", "PSI"] == pytest.approx(want, abs=5.1e-5)


def test_a_column_numeric_with_values_in_one_dataset_and_strings_in_the_other_is_refused(tmp_path):
    src = pd.DataFrame({"a": [1.0, 2.0, 3.0, 4.0]})
    tgt = pd.DataFrame({"a": np.array(["x", "y", "x", "y"], dtype=object)})
    with pytest.raises(TypeError, match="numeric in one dataset and categorical in the other"):
        dd.statistics(Table.from_pandas(tgt), Table.from_pandas(src), use_sampling=False, source_path=str(tmp_path))


def test_the_union_lanes_are_a_size_class_and_the_stage_rows_count_the_values(frames, tmp_path, monkeypatch):
    src, tgt = frames
    lanes = []
    full = drift_kernels.drift_side_full
    monkeypatch.setattr(drift_kernels, "drift_side_full", lambda *a: lanes.append(a[-1]) or full(*a))
    tracer = get_tracer()
    with tracer.run_pass(), warnings.catch_warnings():
        warnings.simplefilter("ignore")
        dd.statistics(Table.from_pandas(tgt), Table.from_pandas(src), method_type="all", use_sampling=False,
                      source_path=str(tmp_path))
    rows = {r["name"]: r["counts"] for r in tracer.phases() if r["name"].startswith("drift/")}
    union = len(set(src["many"].dropna()) | set(tgt["many"].dropna()))
    # a power of two over the widest union, both sides alike
    assert lanes == [bucket_segments_pow2(union)] * 2 and lanes[0] & (lanes[0] - 1) == 0 and union < lanes[0] < 2 * union
    local = {c: (src[c].nunique(), tgt[c].nunique()) for c in ("two", "many", "only_target", "only_source")}
    unions = {"two": 2, "many": union, "only_target": 2, "only_source": 2}
    assert rows["drift/union"]["values"] == sum(a + b for a, b in local.values())
    assert rows["drift/lut"]["values"] == sum(2 * unions[c] + sum(local[c]) for c in local)
    assert rows["drift/model"]["values"] == sum(unions.values()) + 10 * 3  # wide, counts, num_only_source
    padded = Table.from_pandas(src).padded_rows + Table.from_pandas(tgt).padded_rows
    assert rows["drift/sides"]["cells"] == padded * 7 and rows["drift/sides"]["cutoffs"] == 2 * 3 * 9
    assert rows["drift/sides"]["hist_lanes"] == 2 * (3 * 10 + sum(unions.values()))
    assert set(rows) == {"drift/fit", "drift/union", "drift/lut", "drift/sides", "drift/model", "drift/frame"}


# ------------------------------------------------------------------- the stability index ----
def _period(rows: int, seed: int, late: bool) -> pd.DataFrame:
    rng = np.random.default_rng(seed)
    return pd.DataFrame({
        "steady": rng.normal(50.0, 5.0, rows), "moving": rng.normal(10.0 + 3 * seed, 2.0 + 0.3 * seed, rows),
        "late": rng.gamma(2.0, 3.0, rows) if late else np.full(rows, np.nan),  # the column appears in its year
        "never": np.full(rows, np.nan), "constant": np.ones(rows),
        "text_late": np.array(["a", "b"], dtype=object)[rng.integers(0, 2, rows)] if late else None})


@pytest.fixture(scope="module")
def periods():
    return [_period(900 + 100 * i, i, late=i >= 3) for i in range(7)]


def test_a_column_empty_in_three_of_seven_periods_is_scored_over_the_four_that_have_it(periods, tmp_path):
    out = stability_index_computation(*[Table.from_pandas(p) for p in periods],
                                      appended_metric_path=str(tmp_path / "m"), threshold=2).set_index("attribute")
    hist = pd.read_csv(tmp_path / "m" / "part-00000.csv")
    # "all" is what is numeric in every period: the string column that is empty at first is none of them
    assert list(out.index) == ["steady", "moving", "late", "never", "constant"]
    assert len(hist) == 7 * 5 and hist[hist["attribute"] == "late"]["mean"].isna().sum() == 3
    for c in ("steady", "moving", "late"):
        kurt = [((p[c] - p[c].mean()) ** 4).mean() / ((p[c] - p[c].mean()) ** 2).mean() ** 2
                for p in periods if p[c].notna().any()]
        mom = {"mean": [p[c].mean() for p in periods if p[c].notna().any()],
               "stddev": [p[c].std(ddof=1) for p in periods if p[c].notna().any()], "kurtosis": kurt}
        assert len(kurt) == (4 if c == "late" else 7)
        for stat, values in mom.items():
            cv = np.std(values, ddof=1) / np.mean(values)
            assert out.loc[c, stat + "_cv"] == pytest.approx(cv, abs=2e-4), (c, stat)
            assert out.loc[c, stat + "_si"] == 4 - np.searchsorted([0.03, 0.1, 0.2, 0.5], abs(cv), side="right")
    assert out.loc["steady", "flagged"] == 0 and out.loc["moving", "flagged"] == 1
    # no period has a value, or none has a spread: no index, and flagged
    for c in ("never", "constant"):
        assert pd.isna(out.loc[c, "stability_index"]) and out.loc[c, "flagged"] == 1
    assert out.loc["constant", "mean_si"] == 4 and pd.isna(out.loc["never", "mean_si"])


def test_a_named_string_column_is_still_refused(periods):
    with pytest.raises(TypeError, match="Invalid input"):
        stability_index_computation(*[Table.from_pandas(p) for p in periods], list_of_cols=["steady", "text_late"])


# ------------------------------------------------------------------- the cut-offs ----
def test_the_device_compares_with_a_cut_off_that_decides_as_float64_does():
    """A cut-off rounded DOWN to f32: for every f32 value, above it or not as in float64."""
    rng = np.random.default_rng(5)
    cuts = np.concatenate([rng.uniform(-1e6, 1e6, 2000), np.arange(-50.0, 50.0), rng.uniform(0, 40, 2000).round(2),
                           np.float32(18.15) + np.array([0.0, 1e-9, -1e-9]), [1e-30, -1e-30, 3e38]])
    dev = drift_kernels.device_cutoffs(cuts)
    assert dev.dtype == np.float32 and (dev.astype(np.float64) <= cuts).all()
    near = cuts.astype(np.float32)
    for x in (near, np.nextafter(near, np.float32(np.inf)), np.nextafter(near, np.float32(-np.inf))):
        assert ((x > dev) == (x.astype(np.float64) > cuts)).all()
    assert np.isnan(drift_kernels.device_cutoffs(np.array([np.nan, 1.0]))[0])


def test_equal_range_cut_offs_are_the_upstreams_float64_arithmetic():
    lo, hi = np.float32([0.0, 5.31, 660.0, 1.0]), np.float32([12.0, 30.99, 845.0, 1.0])
    cuts = drift_kernels.cutoffs_from_bounds(lo, hi, np.array([5, 9, 3, 0]), 10)
    assert cuts.dtype == np.float64 and cuts.shape == (4, 9) and np.isnan(cuts[3]).all()
    for i in range(3):
        want = [float(lo[i]) + j * ((float(hi[i]) - float(lo[i])) / 10) for j in range(1, 10)]
        assert list(cuts[i]) == want
    assert cuts[0, 4] == 6.0 and cuts[2, 1] == 697.0  # whole numbers that lie on a cut-off stay under it


def test_whole_numbers_on_the_cut_offs_are_binned_as_float64_bins_them(tmp_path):
    """0 ... 20 in the source: every second cut-off is a value of the column."""
    rng = np.random.default_rng(3)
    src = pd.DataFrame({"n": rng.integers(0, 21, 4000).astype(np.float64), "r": rng.integers(531, 3100, 4000) / 100})
    tgt = pd.DataFrame({"n": rng.integers(0, 25, 5000).astype(np.float64), "r": rng.integers(531, 3100, 5000) / 100})
    src.loc[:1, "n"], src.loc[:1, "r"] = [0.0, 20.0], [5.31, 30.99]
    got = _statistics(src, tgt, tmp_path)
    want = _reference(src.astype(np.float32).astype(np.float64), tgt.astype(np.float32).astype(np.float64))
    assert np.abs(got[METHODS].to_numpy(float) - want.loc[got.index].to_numpy(float)).max() <= 5.1e-5
    model = pd.read_parquet(tmp_path / "drift_statistics" / "attribute_binning").set_index("attribute")
    assert list(model.loc["n", "parameters"]) == [2.0 * j for j in range(1, 10)]  # the saved model holds them in float64


def test_a_column_of_one_value_has_no_spread_whatever_the_devices_division_left():
    """What the chip reads for a constant (PERF.md section 6, PR 53): a mean a unit of the last place off, so a
    spread of 2e-7 and a kurtosis of exactly 1; the moments state stddev 0 and no kurtosis."""
    import jax.numpy as jnp

    from anovos_tpu.drift_stability.stability import _without_spread

    mom = {"min": jnp.array([1.0, 3.0, 0.0, 5.0]), "max": jnp.array([1.0, 3.0, 9.0, 5.0]),
           "count": jnp.array([7.0, 1.0, 7.0, 0.0]), "mean": jnp.array([0.99999994, 3.0, 4.0, jnp.nan]),
           "stddev": jnp.array([2.4e-7, jnp.nan, 2.5, jnp.nan]), "kurtosis": jnp.array([-2.0, -2.0, -1.2, jnp.nan])}
    mean, std, kurt = (np.asarray(a, np.float64) for a in _without_spread(mom))
    assert std[0] == 0.0 and np.isnan(std[1]) and std[2] == 2.5 and np.isnan(std[3])
    assert np.isnan(kurt[[0, 1, 3]]).all() and kurt[2] == pytest.approx(-1.2) and mean[0] == pytest.approx(1.0)
