"""stats_generator golden tests (mirroring the reference's
test_stats_generator.py style: small frames, hand-computed expectations,
plus income-dataset spot checks against pandas)."""

import numpy as np
import pandas as pd
import pytest

from anovos_tpu.data_analyzer import stats_generator as sg
from anovos_tpu.shared.table import Table


@pytest.fixture()
def tdf():
    return Table.from_pandas(
        pd.DataFrame(
            {
                "num": [1.0, 2.0, 2.0, np.nan],
                "intc": [5, 5, 7, 9],
                "cat": ["a", "b", "a", None],
            }
        )
    )


def test_global_summary(tdf):
    out = sg.global_summary(tdf)
    d = dict(zip(out["metric"], out["value"]))
    assert d["rows_count"] == "4"
    assert d["columns_count"] == "3"
    assert d["numcols_count"] == "2"
    assert d["catcols_count"] == "1"
    assert "cat" in d["catcols_name"]


def test_missing_and_counts(tdf):
    out = sg.missingCount_computation(tdf).set_index("attribute")
    assert out.loc["num", "missing_count"] == 1
    assert out.loc["num", "missing_pct"] == 0.25
    assert out.loc["cat", "missing_count"] == 1
    moc = sg.measures_of_counts(tdf).set_index("attribute")
    assert moc.loc["num", "fill_count"] == 3
    assert moc.loc["intc", "nonzero_count"] == 4
    assert np.isnan(moc.loc["cat", "nonzero_count"])  # cat has no nonzero stat


def test_central_tendency(tdf):
    out = sg.measures_of_centralTendency(tdf).set_index("attribute")
    np.testing.assert_allclose(out.loc["num", "mean"], 5 / 3, rtol=1e-3)
    assert out.loc["num", "median"] == 2.0
    assert out.loc["cat", "mode"] == "a"
    assert out.loc["cat", "mode_rows"] == 2
    assert out.loc["intc", "mode"] == "5"
    assert out.loc["intc", "mode_pct"] == 0.5
    # float columns get a mode too (reference computes mode for EVERY column);
    # smallest value among max-count ties
    assert out.loc["num", "mode"] == "2.0"


def test_cardinality(tdf):
    out = sg.measures_of_cardinality(tdf).set_index("attribute")
    assert out.loc["cat", "unique_values"] == 2
    np.testing.assert_allclose(out.loc["cat", "IDness"], 2 / 3, atol=1e-4)
    assert out.loc["intc", "unique_values"] == 3


def test_dispersion_and_shape(tdf):
    out = sg.measures_of_dispersion(tdf).set_index("attribute")
    s = pd.Series([5, 5, 7, 9.0])
    np.testing.assert_allclose(out.loc["intc", "stddev"], round(s.std(), 4))
    np.testing.assert_allclose(out.loc["intc", "range"], 4.0)
    sh = sg.measures_of_shape(tdf).set_index("attribute")
    from scipy import stats as sps

    np.testing.assert_allclose(sh.loc["intc", "skewness"], round(sps.skew(s), 4), atol=1e-3)


def test_percentiles(tdf):
    out = sg.measures_of_percentiles(tdf).set_index("attribute")
    assert out.loc["intc", "min"] == 5
    assert out.loc["intc", "max"] == 9
    assert out.loc["intc", "50%"] == 5  # lower interpolation → dataset element


def test_invalid_cols_raise(tdf):
    with pytest.raises(TypeError):
        sg.missingCount_computation(tdf, ["nope"])
    with pytest.raises(TypeError):
        sg.global_summary(tdf, [])


def test_income_parity(income_df):
    t = Table.from_pandas(income_df)
    out = sg.measures_of_centralTendency(t, drop_cols=["ifa"]).set_index("attribute")
    np.testing.assert_allclose(out.loc["age", "mean"], round(income_df["age"].mean(), 4), atol=1e-3)
    assert out.loc["sex", "mode"] == income_df["sex"].mode()[0]
    card = sg.measures_of_cardinality(t, drop_cols=["ifa"]).set_index("attribute")
    assert card.loc["education", "unique_values"] == income_df["education"].nunique()


def test_subset_describe_cache_then_full_counts():
    """A describe computed over a column SUBSET must not poison the
    count-only fast path for the full table (TPU e2e crash: positions from
    the full column list indexed into a subset-sized cache entry)."""
    g = np.random.default_rng(9)
    df = pd.DataFrame({f"n{i}": g.normal(size=50) for i in range(9)})
    df["c1"] = g.choice(["x", "y"], 50)
    t = Table.from_pandas(df)
    from anovos_tpu.ops.describe import table_describe

    # warm the cache with an 8-of-9 numeric subset
    table_describe(t, [f"n{i}" for i in range(8)], ["c1"])
    out = sg.missingCount_computation(t).set_index("attribute")
    assert len(out) == 10 and (out["missing_count"] == 0).all()


def test_describe_sorts_a_large_vocabulary_and_refuses_one_float32_cannot_index(monkeypatch):
    """Above ``_CAT_SWEEP_MAX_VOCAB`` a categorical is described by a sort of
    its codes, which go through float32: exact below 2^24 codes.  A vocabulary
    of 2^24 values or more is refused before anything is dispatched, not
    rounded (the length is fabricated: a view of one string, not 16 M)."""
    from anovos_tpu.ops import describe as dsc

    n = 3000
    ids = np.array([f"id{i:05d}" for i in range(n)], dtype=object)
    ids[[5, 17]] = ids[3]  # one value three times, the rest once
    df = pd.DataFrame({"x": np.arange(n, dtype=float), "id": ids})
    t = Table.from_pandas(df)
    assert len(t.columns["id"].vocab) == n - 2 > dsc._CAT_SWEEP_MAX_VOCAB
    _, cat = dsc.table_describe(t, ["x"], ["id"])
    assert (cat["count"][0], cat["nunique"][0], cat["mode_count"][0]) == (n, n - 2, 3)
    assert t.columns["id"].vocab[int(cat["mode_code"][0])] == "id00003"

    t2 = Table.from_pandas(df)
    t2.columns["id"].vocab = np.broadcast_to(np.array("v", dtype=object), (dsc._CAT_SORT_MAX_VOCAB,))
    dispatched = []
    monkeypatch.setattr(dsc, "describe_numeric", lambda *a, **k: dispatched.append("numeric"))
    monkeypatch.setattr(dsc, "describe_cat", lambda *a, **k: dispatched.append("cat"))
    with pytest.raises(ValueError, match="2\\^24"):
        dsc.table_describe(t2, ["x"], ["id"])
    assert dispatched == []
    t2.columns["id"].vocab = np.broadcast_to(np.array("v", dtype=object), (dsc._CAT_SORT_MAX_VOCAB - 1,))
    monkeypatch.undo()
    _, cat = dsc.table_describe(t2, ["x"], ["id"])  # one below the limit goes through
    assert cat["nunique"][0] == n - 2
