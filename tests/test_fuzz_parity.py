"""Randomized parity sweep: the fused stats path vs a pandas oracle over
many generated frames (SURVEY §4 "numerical parity vs oracles", widened
beyond the fixed golden fixtures).

Each trial draws a frame with a random mix of dtypes, null patterns, and
degenerate shapes (constant columns, single-distinct, heavy ties, tiny
row counts relative to the mesh) and checks the fused describe program —
the kernel every stats_generator function dispatches — against pandas on
the same data.  The golden fixtures pin exact reference semantics on one
dataset; this sweep guards the kernel against shape/null edge cases the
fixtures never visit (padding leaks, mask handling, sort sentinels).
"""

import numpy as np
import pandas as pd
import pytest

from anovos_tpu.shared import Table
from tests.oracles import pandas_reference_psi


def _random_frame(rng: np.random.Generator) -> pd.DataFrame:
    n = int(rng.choice([3, 17, 100, 997, 4096]))
    cols = {}
    k = rng.integers(2, 6)
    for j in range(k):
        kind = rng.choice(["normal", "ties", "constant", "intlike", "gamma"])
        if kind == "normal":
            v = rng.normal(rng.uniform(-50, 50), rng.uniform(0.1, 100), n)
        elif kind == "ties":
            v = rng.choice([1.0, 2.5, 2.5, 7.0, -3.0], n)
        elif kind == "constant":
            v = np.full(n, float(rng.integers(-5, 5)))
        elif kind == "intlike":
            v = rng.integers(-1000, 1000, n).astype(float)
        else:
            v = rng.gamma(2.0, 3.0, n)
        v = v.astype(np.float32).astype(float)  # Table stores f32: quantize first
        null_frac = float(rng.choice([0.0, 0.02, 0.5, 0.95]))
        if null_frac:
            v[rng.random(n) < null_frac] = np.nan
        cols[f"c{j}"] = v
    return pd.DataFrame(cols)


@pytest.mark.parametrize("seed", range(12))
def test_describe_matches_pandas_on_random_frames(seed):
    from anovos_tpu.ops.describe import PCTL_QS, describe_numeric

    rng = np.random.default_rng(1000 + seed)
    df = _random_frame(rng)
    t = Table.from_pandas(df)
    num_cols = list(df.columns)
    X, M = t.numeric_block(num_cols)
    out = {k: np.asarray(v) for k, v in describe_numeric(X, M).items()}

    for i, c in enumerate(num_cols):
        s = df[c].dropna()
        n = len(s)
        assert out["count"][i] == n, c
        if n == 0:
            assert np.isnan(out["mean"][i])
            continue
        v = s.to_numpy()
        np.testing.assert_allclose(out["mean"][i], v.mean(), rtol=2e-5, err_msg=c)
        if n > 1 and v.std(ddof=1) > 0:
            np.testing.assert_allclose(
                out["stddev"][i], v.std(ddof=1), rtol=1e-4, err_msg=c)
        assert out["min"][i] == v.min() and out["max"][i] == v.max(), c
        assert out["nunique"][i] == len(np.unique(v)), c
        assert out["nonzero"][i] == (v != 0).sum(), c
        # percentile grid: 'lower' interpolation — an actual element at the
        # exact index pandas' method='lower' picks
        want = np.quantile(v, PCTL_QS, method="lower")
        np.testing.assert_array_equal(out["percentiles"][:, i], want, err_msg=c)
        # mode: most frequent value, smallest on count ties
        vc = pd.Series(v).value_counts()
        top = vc[vc == vc.iloc[0]].index.min()
        assert out["mode_value"][i] == top, c
        assert out["mode_count"][i] == vc.iloc[0], c


@pytest.mark.parametrize("seed", range(8))
def test_drift_matches_pandas_loop_on_random_frames(seed):
    """The full drift pipeline (binning with source cutoffs, union-vocab
    cat counts, PSI) vs the pandas per-column oracle on random mixed
    frames with disjoint vocab tails and nulls."""
    import os
    import tempfile

    from anovos_tpu.drift_stability import statistics

    rng = np.random.default_rng(7000 + seed)
    n = int(rng.choice([400, 2000]))
    src = pd.DataFrame({
        "x": rng.normal(0, 1, n).astype(np.float32).astype(float),
        "y": rng.gamma(2, 3, n).astype(np.float32).astype(float),
        "c": rng.choice(["a", "b", "c", "src_only"], n),
    })
    tgt = pd.DataFrame({
        "x": rng.normal(0.4, 1.3, n).astype(np.float32).astype(float),
        "y": rng.gamma(2, 4, n).astype(np.float32).astype(float),
        "c": rng.choice(["a", "b", "d", "tgt_only"], n),
    })
    src.loc[rng.random(n) < 0.05, "x"] = np.nan
    ref = pandas_reference_psi(src, tgt, bin_size=10)
    with tempfile.TemporaryDirectory() as d:
        odf = statistics(
            Table.from_pandas(tgt), Table.from_pandas(src),
            method_type="PSI", use_sampling=False,
            source_path=os.path.join(d, "s"), bin_size=10,
        )
    ours = dict(zip(odf["attribute"], odf["PSI"]))
    for c, want in ref.items():
        assert abs(ours[c] - want) < 0.02, (c, ours[c], want)


def _golden_module():
    # plain import (same idiom as test_golden.py) — monkeypatch restores any
    # patched globals at teardown, so sharing the module instance is safe
    import tests.golden.generate_golden as gg

    return gg


@pytest.mark.parametrize("seed", range(4))
def test_iv_ig_match_golden_encoder_on_random_frames(seed, monkeypatch):
    """IV/IG vs the committed pandas encoding of the reference semantics
    (equal-frequency binning, null bin, WOE +0.5 fallback, log2 entropies
    with pure-segment drop) on random frames — the encoder is the same
    code that generated the fixtures, here exercised on fresh data."""
    from anovos_tpu.data_analyzer.association_evaluator import (
        IG_calculation, IV_calculation)

    rng = np.random.default_rng(4000 + seed)
    n = int(rng.choice([500, 3000]))
    df = pd.DataFrame({
        "n1": rng.normal(0, 1, n).astype(np.float32).astype(float),
        "n2": rng.gamma(2, 2, n).astype(np.float32).astype(float),
        "k1": rng.choice(["p", "q", "r"], n, p=[0.5, 0.3, 0.2]),
        "lab": rng.choice(["no", "yes"], n, p=[0.7, 0.3]),
    })
    # a predictive column so IV/IG aren't all ~0
    df.loc[df["lab"] == "yes", "n1"] += 1.0
    df.loc[rng.random(n) < 0.05, "n2"] = np.nan

    gg = _golden_module()
    monkeypatch.setattr(gg, "NUM_COLS", ["n1", "n2"])
    monkeypatch.setattr(gg, "CAT_COLS", ["k1", "lab"])
    monkeypatch.setattr(gg, "LABEL_COL", "lab")
    monkeypatch.setattr(gg, "EVENT", "yes")
    iv_frame = gg.golden_iv(df)
    ig_frame = gg.golden_ig(df)
    want_iv = dict(zip(iv_frame["attribute"], iv_frame["iv"]))
    want_ig = dict(zip(ig_frame["attribute"], ig_frame["ig"]))

    t = Table.from_pandas(df)
    got_iv = IV_calculation(t, label_col="lab", event_label="yes")
    got_ig = IG_calculation(t, label_col="lab", event_label="yes")
    for _, r in got_iv.iterrows():
        assert abs(r["iv"] - want_iv[r["attribute"]]) < 5e-3, r["attribute"]
    for _, r in got_ig.iterrows():
        assert abs(r["ig"] - want_ig[r["attribute"]]) < 5e-3, r["attribute"]


@pytest.mark.parametrize("seed", range(4))
def test_outlier_matches_golden_encoder_on_random_frames(seed, monkeypatch):
    """Outlier fences (pctile / mean±3σ / 1.5·IQR voted at min_validation=2,
    skewed columns excluded) vs the golden pandas encoding on random
    frames with heavy tails and zero-inflation."""
    from anovos_tpu.data_analyzer.quality_checker import outlier_detection

    rng = np.random.default_rng(5000 + seed)
    n = int(rng.choice([600, 2500]))
    df = pd.DataFrame({
        "g": rng.gamma(1.5, 10, n).astype(np.float32).astype(float),
        "z": np.where(rng.random(n) < 0.9, 0.0,
                      rng.gamma(2, 100, n)).astype(np.float32).astype(float),
        "u": rng.normal(50, 5, n).astype(np.float32).astype(float),
        # ~98% zeros: p5 == p95 == 0, so the skew-exclusion branch FIRES and
        # the same-verdicts assertion below actually tests it
        "skewed": np.where(rng.random(n) < 0.98, 0.0,
                           rng.gamma(2, 50, n)).astype(np.float32).astype(float),
    })
    gg = _golden_module()
    monkeypatch.setattr(gg, "NUM_COLS", list(df.columns))
    want = gg.golden_outlier(df).set_index("attribute")

    t = Table.from_pandas(df)
    _, stats = outlier_detection(t, detection_side="both", treatment=False)
    got = stats.set_index("attribute")
    assert "skewed" not in want.index  # the oracle really excluded it
    assert set(got.index) == set(want.index)  # same skew-exclusion verdicts
    for c in want.index:
        assert int(got.loc[c, "lower_outliers"]) == int(want.loc[c, "lower_outliers"]), c
        assert int(got.loc[c, "upper_outliers"]) == int(want.loc[c, "upper_outliers"]), c


@pytest.mark.parametrize("seed", range(3))
def test_binning_matches_golden_encoder_on_random_frames(seed, monkeypatch, tmp_path):
    """attribute_binning (equal_range + equal_frequency cutoffs, 'left'
    searchsorted labels) vs the golden encoding on random frames with
    integer ties sitting exactly on cutoff boundaries."""
    from anovos_tpu.data_transformer.transformers import attribute_binning

    rng = np.random.default_rng(6000 + seed)
    n = int(rng.choice([800, 3000]))
    df = pd.DataFrame({
        "t": rng.integers(0, 20, n).astype(float),  # heavy boundary ties
        "r": rng.normal(0, 10, n).astype(np.float32).astype(float),
    })
    df.loc[rng.random(n) < 0.04, "r"] = np.nan
    gg = _golden_module()
    monkeypatch.setattr(gg, "NUM_COLS", list(df.columns))
    want = gg.golden_binning(df).set_index(["attribute", "method"])

    t = Table.from_pandas(df)
    for method in ("equal_range", "equal_frequency"):
        odf = attribute_binning(
            t, list_of_cols=list(df.columns), method_type=method,
            bin_size=10, model_path=str(tmp_path / method),
        )
        host = odf.to_pandas()  # the supported host surface (nrows slice + mask)
        for c in df.columns:
            codes = host[c].dropna().astype(int).to_numpy()
            counts = np.bincount(codes, minlength=11)[1:]
            w = want.loc[(c, method)]
            for j in range(1, 11):
                assert counts[j - 1] == w[f"bin_{j}"], (method, c, j)


@pytest.mark.parametrize("seed", range(3))
def test_drift_all_metrics_match_golden_encoder(seed, monkeypatch):
    """All four drift metrics (PSI, HD, JSD, KS) + the flagged verdict vs
    the golden encoder on random frames — the PSI-only fuzz above uses the
    bench oracle; this one pins the full metric family including the
    1e-4 zero-replacement and the cumulative KS ordering."""
    import tempfile

    from anovos_tpu.drift_stability import statistics

    rng = np.random.default_rng(8000 + seed)
    n = int(rng.choice([600, 2400]))
    src = pd.DataFrame({
        "x": rng.normal(0, 1, n).astype(np.float32).astype(float),
        "c": rng.choice(["a", "b", "c", "only_src"], n, p=[0.5, 0.3, 0.15, 0.05]),
    })
    tgt = pd.DataFrame({
        "x": rng.normal(0.6, 1.2, n).astype(np.float32).astype(float),
        "c": rng.choice(["a", "b", "d"], n, p=[0.4, 0.3, 0.3]),
    })
    gg = _golden_module()
    monkeypatch.setattr(gg, "NUM_COLS", ["x"])
    monkeypatch.setattr(gg, "CAT_COLS", ["c"])
    want = gg.golden_drift(src, tgt).set_index("attribute")

    import os as _os

    with tempfile.TemporaryDirectory() as d:
        odf = statistics(
            Table.from_pandas(tgt), Table.from_pandas(src),
            method_type="all", use_sampling=False,
            source_path=_os.path.join(d, "s"), bin_size=10,
        ).set_index("attribute")
    for col in ("x", "c"):
        for m in ("PSI", "HD", "JSD", "KS"):
            assert abs(float(odf.loc[col, m]) - float(want.loc[col, m])) < 5e-3, (col, m)
        assert int(odf.loc[col, "flagged"]) == int(want.loc[col, "flagged"]), col


@pytest.mark.parametrize("seed", range(3))
def test_stability_matches_golden_encoder_on_random_histories(seed):
    """stability_index_computation vs the golden encoder on RANDOM
    multi-dataset histories (3-5 periods, drifting and steady columns,
    varying lengths) — CV computation (sample stddev), the CV->SI score
    map, and the 50/30/20 weighted index."""
    from anovos_tpu.drift_stability import stability_index_computation

    rng = np.random.default_rng(9000 + seed)
    periods = int(rng.integers(3, 6))
    datasets = [
        pd.DataFrame({
            "s": rng.normal(50.0, 2.0, 1500).astype(np.float32).astype(float),
            "d": rng.normal(50.0 + 25.0 * i, 2.0 + 1.5 * i, 1500)
                 .astype(np.float32).astype(float),
            "w": rng.gamma(2.0 + 0.2 * i, 3.0, 1500).astype(np.float32).astype(float),
        })
        for i in range(periods)
    ]
    gg = _golden_module()
    want = gg.golden_stability(datasets).set_index("attribute")

    import tempfile

    with tempfile.TemporaryDirectory() as d:
        got = stability_index_computation(
            *[Table.from_pandas(p) for p in datasets],
            appended_metric_path=d,
        ).set_index("attribute")
    for c in ("s", "d", "w"):
        for m in ("mean_si", "stddev_si", "kurtosis_si"):
            assert int(got.loc[c, m]) == int(want.loc[c, m]), (c, m, got.loc[c], want.loc[c])
        assert abs(float(got.loc[c, "stability_index"]) - float(want.loc[c, "stability_index"])) < 1e-6, c
