"""Transformer tests (reference style: test_transformers.py, 23 tests)."""

import numpy as np
import pandas as pd
import pytest

from anovos_tpu.data_transformer import transformers as T
from anovos_tpu.shared.table import Table


@pytest.fixture()
def num_t():
    return Table.from_pandas(
        pd.DataFrame(
            {
                "x": [1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0, 9.0, 10.0],
                "y": [10.0, 10.0, 10.0, 20.0, 20.0, 30.0, 30.0, 30.0, 40.0, np.nan],
                "g": ["a", "a", "a", "b", "b", "b", "c", "c", "c", None],
                "label": [0, 0, 1, 0, 1, 1, 1, 0, 1, 0],
            }
        )
    )


def test_attribute_binning_equal_range(num_t):
    out = T.attribute_binning(num_t, ["x"], bin_size=5)
    bins = out.to_pandas()["x"]
    # width (10-1)/5 = 1.8; cutoffs 2.8,4.6,6.4,8.2 ; value<=cutoff → bin
    assert bins.tolist() == [1, 1, 2, 2, 3, 3, 4, 4, 5, 5]


def test_attribute_binning_equal_frequency(num_t):
    out = T.attribute_binning(num_t, ["x"], method_type="equal_frequency", bin_size=2)
    bins = out.to_pandas()["x"]
    assert set(bins[:5]) == {1} and set(bins[5:]) == {2}


def test_binning_model_roundtrip(num_t, tmp_path):
    mp = str(tmp_path / "m")
    T.attribute_binning(num_t, ["x"], bin_size=4, model_path=mp)
    out = T.attribute_binning(num_t, ["x"], bin_size=4, pre_existing_model=True, model_path=mp)
    assert out.to_pandas()["x"].max() == 4


def test_binning_categorical_labels(num_t):
    out = T.attribute_binning(num_t, ["x"], bin_size=2, bin_dtype="categorical")
    vals = out.to_pandas()["x"]
    assert vals[0].startswith("<= ") and vals[9].startswith("> ")


def test_binning_null_preserved(num_t):
    out = T.attribute_binning(num_t, ["y"], bin_size=3)
    assert np.isnan(out.to_pandas()["y"].iloc[9])


def test_cat_to_num_label_encoding(num_t):
    out = T.cat_to_num_unsupervised(num_t, ["g"], method_type="label_encoding")
    enc = out.to_pandas()["g"]
    # frequencyDesc with tie a=4? a appears 3, b 3, c 3 → ties broken by code order (a,b,c)
    assert enc[:3].tolist() == [0, 0, 0]
    assert np.isnan(enc.iloc[9])


def test_cat_to_num_onehot(num_t):
    out = T.cat_to_num_unsupervised(num_t, ["g"], method_type="onehot_encoding")
    df = out.to_pandas()
    assert "g_0" in df.columns and "g" not in df.columns
    assert df[["g_0", "g_1", "g_2"]].iloc[0].sum() == 1


def test_cat_to_num_supervised(num_t):
    out = T.cat_to_num_supervised(num_t, ["g"], label_col="label", event_label=1)
    enc = out.to_pandas()["g"]
    # group a rows: labels 0,0,1 → 1/3
    np.testing.assert_allclose(enc[0], round(1 / 3, 4), atol=1e-4)


@pytest.mark.parametrize("fn,kwargs,fit,apply", [
    ("cat_to_num_supervised", dict(list_of_cols=["g"], label_col="label", event_label=1),
     dict(count_rows=16, label_rows=16, seg_lanes=32, dense_counts=0, scatter_counts=2),
     dict(gather_rows=32, lut_bytes=80, gather_out_bytes=80, dense_gathers=0, index_gathers=2)),
    ("imputation_MMM", dict(method_type="median"),
     dict(count_rows=16, seg_lanes=16, dense_counts=0, scatter_counts=1), {}),
])
def test_the_stage_rows_count_what_their_group_counts_move_and_the_route_they_take(num_t, fn, kwargs, fit, apply):
    """On the suite's mesh every call keeps the scatter-add and the index gather
    (``tests/test_segment_dense_layout.py`` has the same rows of a table on one device)."""
    from anovos_tpu.obs import get_tracer

    assert num_t.padded_rows == 16
    tracer = get_tracer()
    with tracer.run_pass():
        getattr(T, fn)(num_t, **kwargs)
    rows = {r["name"]: r["counts"] for r in tracer.phases() if r["name"].startswith("transform/")}
    shape = {"cols", "rows", "vocab_max", "segments_max"}
    assert {k: v for k, v in rows["transform/fit"].items() if k not in shape} == fit
    assert {k: v for k, v in rows["transform/apply"].items() if k not in shape} == apply


def test_z_standardization(num_t):
    out = T.z_standardization(num_t, ["x"])
    z = out.to_pandas()["x"]
    np.testing.assert_allclose(z.mean(), 0, atol=1e-6)
    np.testing.assert_allclose(z.std(ddof=1), 1, atol=1e-4)


def test_iqr_standardization(num_t):
    out = T.IQR_standardization(num_t, ["x"])
    z = out.to_pandas()["x"]
    assert abs(z.median()) < 0.2


def test_normalization(num_t):
    out = T.normalization(num_t, ["x"])
    z = out.to_pandas()["x"]
    assert z.min() == 0.0 and z.max() == 1.0


def test_normalization_model_roundtrip(num_t, tmp_path):
    mp = str(tmp_path / "m")
    T.normalization(num_t, ["x"], model_path=mp)
    out2 = T.normalization(num_t, ["x"], pre_existing_model=True, model_path=mp)
    assert out2.to_pandas()["x"].max() == 1.0


def test_imputation_MMM_median(num_t):
    out = T.imputation_MMM(num_t, method_type="median")
    df = out.to_pandas()
    assert not df["y"].isna().any()
    assert df["y"].iloc[9] == 20.0  # median of [10,10,10,20,20,30,30,30,40]
    assert df["g"].iloc[9] in ("a", "b", "c")


def test_imputation_MMM_mean_append(num_t):
    out = T.imputation_MMM(num_t, list_of_cols=["y"], method_type="mean", output_mode="append")
    df = out.to_pandas()
    assert "y_imputed" in df.columns
    np.testing.assert_allclose(df["y_imputed"].iloc[9], np.nanmean(df["y"]), rtol=1e-5)


def test_feature_transformation_sqrt(num_t):
    out = T.feature_transformation(num_t, ["x"], method_type="sqrt")
    np.testing.assert_allclose(out.to_pandas()["x"], np.sqrt(np.arange(1, 11)), rtol=1e-6)


def test_feature_transformation_ln_domain(num_t):
    t = Table.from_pandas(pd.DataFrame({"v": [-1.0, 0.0, 1.0, np.e]}))
    out = T.feature_transformation(t, ["v"], method_type="ln")
    v = out.to_pandas()["v"]
    assert np.isnan(v[0]) and np.isnan(v[1])
    # rtol covers TPU's f32 transcendental approximation (ln(e) ≈ 1 ± 1.2e-5
    # on v5e); outputs are reported at 4dp so this is within contract
    np.testing.assert_allclose(v[3], 1.0, rtol=5e-5)


def test_boxcox(num_t):
    skewed = Table.from_pandas(pd.DataFrame({"v": np.exp(np.random.default_rng(0).normal(size=500))}))
    out = T.boxcox_transformation(skewed, ["v"])
    v = out.to_pandas()["v"]
    from scipy import stats as sps

    assert abs(sps.skew(v.dropna())) < 2.0


def test_outlier_categories():
    df = pd.DataFrame({"c": ["a"] * 50 + ["b"] * 30 + ["c"] * 15 + ["d"] * 4 + ["e"]})
    t = Table.from_pandas(df)
    out = T.outlier_categories(t, ["c"], coverage=0.9, max_category=10)
    vals = set(out.to_pandas()["c"].unique())
    assert "outlier_categories" in vals
    assert "a" in vals and "b" in vals
    assert "e" not in vals


def test_expression_parser(num_t):
    out = T.expression_parser(num_t, "log(x) + 1.5")
    df = out.to_pandas()
    assert "log(x) + 1.5" in df.columns
    np.testing.assert_allclose(df["log(x) + 1.5"][0], 1.5, atol=1e-5)


def test_monotonic_binning(num_t):
    out = T.monotonic_binning(num_t, ["x"], label_col="label", event_label=1, bin_size=4)
    assert out.to_pandas()["x"].nunique() <= 20
