"""The reference's module-level public names resolve and work here.

A user switching from the reference imports these by name (reference
report_generation.py:78-3981, geospatial_analyzer.py:64-1117,
featrec_init.py:231, feast_exporter.py:95-130); each test drives the
function on real inputs rather than only asserting existence.
"""

import json

import numpy as np
import pandas as pd
import pytest

from anovos_tpu.shared.table import Table


# ----------------------------------------------------------------- report
def test_report_utils():
    from anovos_tpu.data_report.report_generation import (
        lambda_cat,
        list_ts_remove_append,
        remove_u_score,
    )

    assert remove_u_score("nullColumns_detection") == "Null Detection"
    assert remove_u_score("measures_of_counts") == "Measures Of Counts"
    assert lambda_cat(0.2) == "Log Transform"
    assert lambda_cat(1.5) == "No Transform"
    assert list_ts_remove_append(["a_ts", "b"], 1) == ["a", "b"]
    assert list_ts_remove_append(["a_ts", "b"], 0) == ["a_ts", "b_ts"]


def test_drift_stability_ind():
    from anovos_tpu.data_report.report_generation import drift_stability_ind

    stab_tab = ["stability_index", "stabilityIndex_metrics"]
    assert drift_stability_ind(["drift_statistics"], ["drift_statistics"], [], stab_tab) == (0, 1)
    assert drift_stability_ind([], ["drift_statistics"], ["stabilityIndex_metrics"], stab_tab) == (1, 0.5)
    assert drift_stability_ind([], ["drift_statistics"], stab_tab, stab_tab) == (1, 0)


def test_chart_gen_list_and_loc_charts(tmp_path):
    from anovos_tpu.data_report.report_generation import chart_gen_list, read_loc_charts

    fig = {"data": [{"type": "bar", "x": [1], "y": [2]}], "layout": {}}
    (tmp_path / "freqDist_age").write_text(json.dumps(fig))
    (tmp_path / "freqDist_fare").write_text(json.dumps(fig))
    (tmp_path / "geo_scatter_lat_lon").write_text(json.dumps(fig))
    assert len(chart_gen_list(str(tmp_path), "freqDist_")) == 2
    assert len(chart_gen_list(str(tmp_path), "freqDist_", type_col=["age"])) == 1
    assert len(read_loc_charts(str(tmp_path))) == 1


def test_line_chart_gen_stability():
    from anovos_tpu.data_report.report_generation import line_chart_gen_stability

    df1 = pd.DataFrame({"attribute": ["x"], "stability_index": [3.7]})
    df2 = pd.DataFrame(
        {"attribute": ["x"] * 3, "mean": [1.0, 1.1, 1.2], "stddev": [0.1] * 3, "kurtosis": [0.0] * 3}
    )
    figs = line_chart_gen_stability(df1, df2, "x")
    kinds = {f["data"][0]["type"] for f in figs}
    assert "indicator" in kinds and "scatter" in kinds
    gauge = [f for f in figs if f["data"][0]["type"] == "indicator"][0]
    assert "Very Stable" in gauge["data"][0]["title"]["text"]


def test_report_section_generators(tmp_path):
    from anovos_tpu.data_report.report_generation import (
        attribute_associations,
        data_analyzer_output,
        descriptive_statistics,
        quality_check,
        wiki_generator,
    )

    pd.DataFrame({"metric": ["rows_count"], "value": [10]}).to_csv(tmp_path / "global_summary.csv", index=False)
    pd.DataFrame({"attribute": ["a"], "fill_pct": [1.0]}).to_csv(tmp_path / "measures_of_counts.csv", index=False)
    pd.DataFrame({"attribute": ["a"], "duplicates": [0]}).to_csv(tmp_path / "duplicate_detection.csv", index=False)
    pd.DataFrame({"attribute": ["a"], "a": [1.0]}).to_csv(tmp_path / "correlation_matrix.csv", index=False)
    pd.DataFrame({"attribute": ["a"], "data_type": ["double"]}).to_csv(tmp_path / "data_type.csv", index=False)
    assert "measures_of_counts" in descriptive_statistics(str(tmp_path))
    assert "duplicate_detection" in quality_check(str(tmp_path))
    assert "corrheat" in attribute_associations(str(tmp_path))
    assert "observed data types" in wiki_generator(str(tmp_path))
    assert "global_summary" in data_analyzer_output(str(tmp_path), ["global_summary"], "stats")


def test_ts_viz_builders(tmp_path):
    from anovos_tpu.data_report.report_generation import (
        gen_time_series_plots,
        plotSeasonalDecompose,
        ts_viz_1_2,
        ts_viz_2_1,
        ts_viz_3_3,
    )

    pd.DataFrame({"date": ["2024-01-01", "2024-01-02"], "count": [5, 7]}).to_csv(
        tmp_path / "ts_daily_dt.csv", index=False
    )
    pd.DataFrame({"bucket": [0, 1], "count": [3, 4]}).to_csv(tmp_path / "ts_daypart_dt.csv", index=False)
    pd.DataFrame(
        {"attribute": ["v", "v"], "date": ["2024-01-01", "2024-01-02"], "mean": [1.0, 2.0], "median": [1.0, 2.0]}
    ).to_csv(tmp_path / "ts_num_daily_dt.csv", index=False)
    pd.DataFrame({"attribute": ["v"], "bucket": [2], "mean": [1.5]}).to_csv(
        tmp_path / "ts_num_weekly_dt.csv", index=False
    )
    pd.DataFrame(
        {"date": ["2024-01-01"], "observed": [5.0], "trend": [5.0], "seasonal": [0.0], "residual": [0.0]}
    ).to_csv(tmp_path / "ts_decompose_dt.csv", index=False)

    assert gen_time_series_plots(str(tmp_path), "dt", "count", "Daily") is not None
    assert gen_time_series_plots(str(tmp_path), "dt", "v", "Daily") is not None
    assert len(ts_viz_1_2(str(tmp_path), "dt", ["v"])) == 2  # volume + trend
    assert len(ts_viz_2_1(str(tmp_path), "dt", None)) == 1  # daypart volume only
    assert len(ts_viz_3_3(str(tmp_path), "dt", ["v"])) == 1  # weekly mean only
    assert len(plotSeasonalDecompose(str(tmp_path), "dt")) == 4


def test_geo_report_readers(tmp_path):
    from anovos_tpu.data_report.report_generation import (
        loc_field_stats,
        overall_stats_gen,
        read_cluster_stats_ll_geo,
        read_stats_ll_geo,
    )

    d, n_ll, n_gh = overall_stats_gen(["lat"], ["lon"], ["gh"])
    assert d["Latitude Col"] == "lat" and n_ll == 1 and n_gh == 1
    frame = loc_field_stats(["lat"], ["lon"], ["gh"], 1000)
    assert "Max Records Analyzed" in frame["stats"].values
    pd.DataFrame({"stats": ["x"], "count": [1]}).to_csv(tmp_path / "geospatial_overall_lat_lon.csv", index=False)
    pd.DataFrame({"lat": [1.0], "lon": [2.0], "count": [3]}).to_csv(tmp_path / "geospatial_top_lat_lon.csv", index=False)
    pd.DataFrame({"cluster": [0], "count": [5]}).to_csv(tmp_path / "geospatial_kmeans_lat_lon.csv", index=False)
    stats = read_stats_ll_geo(["lat"], ["lon"], [], str(tmp_path), 10)
    assert set(stats) == {"geospatial_overall_lat_lon", "geospatial_top_lat_lon"}
    clusters = read_cluster_stats_ll_geo(["lat"], ["lon"], [], str(tmp_path))
    assert set(clusters) == {"kmeans_lat_lon"}


# ----------------------------------------------- geospatial analyzer names
@pytest.fixture()
def geo_table():
    g = np.random.default_rng(0)
    n = 400
    lat = np.where(g.random(n) < 0.5, 1.3 + g.normal(0, 0.05, n), 48.8 + g.normal(0, 0.05, n))
    lon = np.where(g.random(n) < 0.5, 103.8 + g.normal(0, 0.05, n), 2.35 + g.normal(0, 0.05, n))
    return Table.from_pandas(pd.DataFrame({"latitude": lat, "longitude": lon}))


def test_descriptive_stats_gen_and_controllers(geo_table, tmp_path):
    from anovos_tpu.data_analyzer.geospatial_analyzer import (
        descriptive_stats_gen,
        generate_loc_charts_controller,
        lat_long_col_stats_gen,
        stats_gen_lat_long_geo,
    )

    row = descriptive_stats_gen(geo_table, "latitude", "longitude", None, None, str(tmp_path), 50)
    assert row["records"] == 400
    assert (tmp_path / "geospatial_overall_latitude_longitude.csv").exists()
    assert (tmp_path / "geospatial_top_latitude_longitude.csv").exists()
    rows = lat_long_col_stats_gen(geo_table, ["latitude"], ["longitude"], None, str(tmp_path), 50)
    assert len(rows) == 1
    stats_gen_lat_long_geo(geo_table, ["latitude"], ["longitude"], [], None, str(tmp_path), 50)
    assert (tmp_path / "geospatial_stats.csv").exists()
    generate_loc_charts_controller(
        geo_table, None, ["latitude"], ["longitude"], [], 50, None, str(tmp_path)
    )
    assert (tmp_path / "geo_scatter_latitude_longitude").exists()


def test_geo_cluster_generator(geo_table, tmp_path):
    from anovos_tpu.data_analyzer.geospatial_analyzer import geo_cluster_generator

    geo_cluster_generator(
        geo_table, ["latitude"], ["longitude"], [], max_cluster=4,
        eps="0.3,0.3,0.1", min_samples="40,40,10", master_path=str(tmp_path),
    )
    for algo in ("kmeans", "dbscan"):
        assert (tmp_path / f"geospatial_{algo}_latitude_longitude.csv").exists()
        assert (tmp_path / f"cluster_output_{algo}_latitude_longitude.csv").exists()
    km = pd.read_csv(tmp_path / "geospatial_kmeans_latitude_longitude.csv")
    assert km["count"].sum() == 400


def test_geohash_stats_all_null_column(tmp_path):
    from anovos_tpu.data_analyzer.geospatial_analyzer import geohash_col_stats_gen

    t = Table.from_pandas(pd.DataFrame({"gh": pd.Series([None, None, None], dtype=object), "v": [1.0, 2.0, 3.0]}))
    rows = geohash_col_stats_gen(t, ["gh"], None, str(tmp_path), 10)
    assert rows and rows[0]["records"] == 0


# ------------------------------------------------------- recommender/feast
def test_embeddings_train_fer():
    from anovos_tpu.feature_recommender.featrec_init import EmbeddingsTrainFer

    holder = EmbeddingsTrainFer(["credit card spend", "monthly income"])
    first = holder.get
    assert first.shape[0] == 2
    assert holder.get is first  # cached after the first encode


def test_feast_field_helpers():
    from anovos_tpu.feature_store.feast_exporter import generate_field, generate_fields, generate_prefix

    line = generate_field("age", "Int64")
    assert 'name="age"' in line and "Int64" in line
    assert generate_fields([("age", "int"), ("id", "string")], ["id"]) == generate_field("age", "Int64")
    assert "from feast import" in generate_prefix()


def test_shared_utils_reshapes():
    from anovos_tpu.shared.utils import (
        attributeType_segregation,
        flatten_dataframe,
        get_dtype,
        transpose_dataframe,
    )

    df = pd.DataFrame({"attribute": ["a", "b"], "mean": [1.0, 2.0], "skew": [np.nan, np.nan]})
    flat = flatten_dataframe(df, ["attribute"])
    assert set(flat.columns) == {"attribute", "key", "value"} and len(flat) == 4
    t = transpose_dataframe(df, "attribute")
    assert list(t["key"]) == ["mean", "skew"]  # source order, all-NaN row kept
    assert list(t.columns) == ["key", "a", "b"]
    assert float(t.loc[t["key"] == "mean", "a"].iloc[0]) == 1.0
    assert attributeType_segregation(df) == (["mean", "skew"], ["attribute"], [])
    assert get_dtype(df, "mean") == "float64"
    tbl = Table.from_pandas(pd.DataFrame({"x": [1.0, 2.0], "c": ["u", "v"]}))
    assert attributeType_segregation(tbl) == (["x"], ["c"], [])
    flat_tbl = flatten_dataframe(tbl, ["c"])
    assert set(flat_tbl["key"]) == {"x"}


def _canon(l):
    """Cluster ids renumbered by first appearance; noise stays -1."""
    out = np.full(len(l), -1)
    seen, nxt = {}, 0
    for i, v in enumerate(l):
        if v < 0:
            continue
        if v not in seen:
            seen[v] = nxt
            nxt += 1
        out[i] = seen[v]
    return out


def test_dbscan_grid_matches_per_combo_fit():
    from anovos_tpu.ops.cluster import dbscan_fit, dbscan_grid, neighbor_counts

    g = np.random.default_rng(3)
    # lat/lon-magnitude blobs: the coordinates that exposed the bf16 matmul
    # precision bug on TPU (distance error >> eps^2 before pinning f32)
    X = np.concatenate(
        [g.normal((10, 70), 0.08, (800, 2)), g.normal((12, 75), 0.1, (800, 2)), g.uniform(8, 77, (400, 2))]
    ).astype(np.float32)
    counts = neighbor_counts(X, 0.3)
    grid = dbscan_grid(X, 0.3, [15, 40, 90], counts=counts)

    for b, ms in enumerate([15, 40, 90]):
        ref = dbscan_fit(X, 0.3, ms, counts=counts)
        assert ((ref < 0) == (grid[b] < 0)).all()
        assert (_canon(ref) == _canon(grid[b])).all()
    assert len(set(grid[0][grid[0] >= 0])) == 2  # the two blobs separate


def _dbscan_on_d2(D2, eps, ms):
    """Plain numpy DBSCAN over a squared-distance matrix: core = within-eps
    count (self included) >= ms, clusters = components of the core-core
    graph by min-label propagation, a border point takes its nearest
    within-eps core's cluster, ties to the lowest index; -1 is noise."""
    n = len(D2)
    within = D2 <= eps * eps
    within[np.arange(n), np.arange(n)] = True
    core = within.sum(axis=1) >= ms
    lab = np.full(n, -1, np.int64)
    ci = np.nonzero(core)[0]
    if not len(ci):
        return lab
    A = within[np.ix_(ci, ci)]
    comp = np.arange(len(ci))
    while True:
        new = np.where(A, comp[None, :], len(ci)).min(axis=1)
        new = new[new]  # pointer jump: a label is the rank of a core point
        if (new == comp).all():
            break
        comp = new
    lab[ci] = comp
    bi = np.nonzero(~core)[0]
    Db = np.where(within[np.ix_(bi, ci)], D2[np.ix_(bi, ci)], np.inf)
    j = Db.argmin(axis=1)
    hit = np.isfinite(Db[np.arange(len(bi)), j])
    lab[bi[hit]] = comp[j[hit]]
    return lab


def _blobs_in_noise(g):
    return np.concatenate([
        g.normal((0, 0), 0.2, (700, 2)),
        g.normal((3, 3), 0.25, (700, 2)),
        g.uniform(-6, 6, (600, 2)),
    ])


def _satellite(g):
    # 100 tight points one eps away from a 400-point core: each sees only
    # part of the core, so it is a border point whose nearest neighbours
    # are all border points too
    return np.concatenate([
        g.normal((0, 0), 0.02, (400, 2)),
        g.normal((1, 0), 0.02, (100, 2)),
    ])


@pytest.mark.parametrize(
    "table, eps_l, ms_l",
    [
        (_blobs_in_noise, [0.3, 0.4, 0.5], [5, 15, 40]),
        (_blobs_in_noise, [0.05], [2, 3]),
        (_blobs_in_noise, [1.5], [300, 900]),
        (_satellite, [1.0], [420]),
    ],
    ids=["blobs", "sparse-cores", "dense-eps", "prefix-without-core"],
)
def test_dbscan_host_grid_matches_numpy_dbscan(table, eps_l, ms_l):
    """Every (eps, min_samples) of the host grid — shared edge list, native
    union-find, nearest-neighbour border prefix — against an independent
    DBSCAN on the same distances: from many small clusters over no core
    point at all to border points whose prefix holds no core, which take
    the full-row adoption."""
    import jax
    import jax.numpy as jnp

    from anovos_tpu.ops.cluster import dbscan_host_grid_multi, pairwise_d2

    pts = table(np.random.default_rng(23)).astype(np.float32)
    Xc = pts - pts.mean(axis=0, keepdims=True)
    D2 = np.asarray(jax.device_get(pairwise_d2(jnp.asarray(Xc))))
    out = dbscan_host_grid_multi(D2, eps_l, ms_l)
    assert out.shape == (len(eps_l), len(ms_l), len(pts))
    for a, eps in enumerate(eps_l):
        for b, ms in enumerate(ms_l):
            ref = _dbscan_on_d2(D2, eps, ms)
            np.testing.assert_array_equal(out[a, b] < 0, ref < 0)
            np.testing.assert_array_equal(_canon(out[a, b]), _canon(ref))
    if table is _satellite:
        # the case is what it says: adopted points among whose 64 nearest
        # (the prefix length) there is no core point
        core = (D2 <= eps_l[0] ** 2).sum(axis=1) >= ms_l[0]
        adopted = np.nonzero(~core & (ref >= 0))[0]
        nearest = np.argsort(D2[adopted], axis=1, kind="stable")[:, :64]
        assert len(adopted) and not core[nearest].any(axis=1).all()


def test_kmeans_iters_budget():
    import jax
    import jax.numpy as jnp

    from anovos_tpu.ops.cluster import kmeans_fit

    g = np.random.default_rng(0)
    X = jnp.asarray(g.normal(size=(500, 2)).astype(np.float32))
    cen0, _, _ = kmeans_fit(X, 3, iters=0)
    # iters=0 must return the seed centers untouched (exact step budget)
    init = np.asarray(X)[np.asarray(jax.random.choice(jax.random.PRNGKey(0), 500, (3,), replace=False))]
    assert np.allclose(np.asarray(cen0), init)


def test_correlation_large_offset_columns():
    """Pre-centering guards the n·Sxy − Sx·Sy cancellation: a year-like
    column (huge offset, ~unit spread) correlated r≈0.33 came back 0.27 on
    TPU and worse in plain f32 before the fix."""
    import jax.numpy as jnp

    from anovos_tpu.ops.correlation import masked_corr, masked_cov

    g = np.random.default_rng(0)
    n = 30000
    year = 2019 + g.integers(0, 3, n).astype(np.float32)
    y = (0.3 * (year - 2020) + 0.7 * g.normal(size=n)).astype(np.float32)
    X = np.stack([year, y, (2e5 + 1e4 * g.normal(size=n)).astype(np.float32)], axis=1)
    M = np.ones_like(X, bool)
    M[g.random((n, 3)) < 0.1] = False
    ours = np.asarray(masked_corr(jnp.asarray(X), jnp.asarray(M)))
    ref = pd.DataFrame(np.where(M, X, np.nan)).corr().to_numpy()
    assert np.nanmax(np.abs(ours - ref)) < 1e-3
    cov_ours = np.asarray(masked_cov(jnp.asarray(X), jnp.asarray(M)))
    cov_ref = pd.DataFrame(np.where(M, X, np.nan)).cov().to_numpy()
    assert np.nanmax(np.abs(cov_ours - cov_ref) / np.maximum(np.abs(cov_ref), 1e-6)) < 1e-3


def test_knn_distance_large_offset_columns():
    """The nan-euclidean expansion loses f32 bits at raw magnitudes; donors
    must be chosen by the (translation-invariant) centered distances."""
    import jax.numpy as jnp

    from anovos_tpu.ops.knn import knn_impute_tile

    n = 500
    a = 1e4 + np.arange(n, dtype=np.float32)          # huge offset, unit spacing
    b = np.arange(n, dtype=np.float32)                # the value to impute
    Xs = np.stack([a, b], axis=1)
    Ms = np.ones_like(Xs, bool)
    Xq = np.array([[1e4 + 250.4, 0.0]], np.float32)   # true neighbors: 248..252
    Mq = np.array([[True, False]])
    out = np.asarray(knn_impute_tile(jnp.asarray(Xq), jnp.asarray(Mq), jnp.asarray(Xs), jnp.asarray(Ms), 5))
    assert abs(float(out[0, 1]) - 250.4) < 2.5
