"""Multi-chip correctness: sharded execution must be numerically equivalent
to single-device execution (the property the virtual 8-device mesh exists to
test — SURVEY.md §4 'fake backend')."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from anovos_tpu.models.autoencoder import AutoEncoder
from anovos_tpu.shared.runtime import DATA_AXIS, MODEL_AXIS


def _loss_and_grads(mesh, shard: bool):
    ae = AutoEncoder(16, 8, seed=3)
    params = ae.init_params()
    g = np.random.default_rng(7)
    x_host = jnp.asarray(g.normal(size=(64, 16)), jnp.float32)
    if shard:
        shardings = ae.param_shardings(mesh)
        params = jax.tree_util.tree_map(
            lambda leaf, s: jax.device_put(leaf, s), params, shardings,
            is_leaf=lambda v: not isinstance(v, dict),
        )
        x = jax.device_put(x_host, NamedSharding(mesh, P(DATA_AXIS, None)))
    else:
        x = x_host

    def loss_fn(p, batch):
        x_hat, _ = ae.forward(p, batch, train=True)
        return jnp.mean((x_hat - batch) ** 2)

    with mesh:
        loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params, x)
    return float(loss), jax.tree_util.tree_map(lambda a: np.asarray(a), grads)


def test_sharded_train_step_matches_single_device():
    """DP(batch) × TP(wide layers) sharding must reproduce the single-device
    loss and gradients — grads are compared (an Adam step would amplify sign
    noise of near-zero gradient components to ±lr)."""
    devs = jax.devices()
    mesh = Mesh(np.array(devs[:8]).reshape(4, 2), (DATA_AXIS, MODEL_AXIS))
    loss_s, grads_s = _loss_and_grads(mesh, shard=True)
    mesh1 = Mesh(np.array(devs[:1]).reshape(1, 1), (DATA_AXIS, MODEL_AXIS))
    loss_r, grads_r = _loss_and_grads(mesh1, shard=False)
    assert abs(loss_s - loss_r) < 1e-5
    flat_s, _ = jax.tree_util.tree_flatten(grads_s)
    flat_r, _ = jax.tree_util.tree_flatten(grads_r)
    for a, b in zip(flat_s, flat_r):
        scale = max(float(np.abs(b).max()), 1e-3)
        np.testing.assert_allclose(a, b, atol=2e-5 * scale + 1e-7, rtol=2e-3)


def test_drift_pipeline_path_matches_multidevice(tmp_path):
    """The single-device drift fast path (async-pipelined programs,
    device-resident cutoffs, post-hoc NaN drop) must equal the sequential
    multi-device path — including a column that's all-null in the source."""
    import pandas as pd

    from anovos_tpu.drift_stability import statistics
    from anovos_tpu.shared.runtime import init_runtime
    from anovos_tpu.shared.table import Table

    g = np.random.default_rng(9)
    n = 8000
    src = pd.DataFrame(
        {"a": g.normal(0, 1, n), "b": g.normal(5, 2, n), "dead": np.full(n, np.nan), "c": g.choice(["x", "y"], n)}
    )
    tgt = pd.DataFrame(
        {"a": g.normal(0.8, 1, n), "b": g.normal(5, 2, n), "dead": np.full(n, np.nan), "c": g.choice(["x", "y"], n, p=[0.8, 0.2])}
    )
    out8 = statistics(
        Table.from_pandas(tgt), Table.from_pandas(src), method_type="all",
        use_sampling=False, source_path=str(tmp_path / "m8"),
    )
    init_runtime(devices=jax.devices()[:1])
    try:
        out1 = statistics(
            Table.from_pandas(tgt), Table.from_pandas(src), method_type="all",
            use_sampling=False, source_path=str(tmp_path / "m1"),
        )
    finally:
        init_runtime()
    import pandas.testing as pdt

    pdt.assert_frame_equal(
        out8.sort_values("attribute").reset_index(drop=True),
        out1.sort_values("attribute").reset_index(drop=True),
    )
    assert "dead" not in set(out1["attribute"])  # all-null column dropped on both paths


def test_sharded_stats_match_single_device(income_df):
    """The whole stats path on the 8-device mesh equals pandas on host —
    already covered elsewhere — here: DP sharding leaves results identical
    when the mesh shrinks to one device."""
    import pandas as pd

    from anovos_tpu.data_analyzer import stats_generator as sg
    from anovos_tpu.shared.runtime import init_runtime
    from anovos_tpu.shared.table import Table

    sub = income_df[["age", "fnlwgt", "hours-per-week", "sex"]].head(4096)
    t8 = Table.from_pandas(sub)
    out8 = sg.measures_of_centralTendency(t8)
    init_runtime(devices=jax.devices()[:1])
    try:
        t1 = Table.from_pandas(sub)
        out1 = sg.measures_of_centralTendency(t1)
    finally:
        init_runtime()  # restore the 8-device mesh for other tests
    pd.testing.assert_frame_equal(out8, out1)


def test_column_sharded_describe_matches_row_sharded():
    """Wide-table path: (rows, cols) block sharded over (data, model) axes
    must give identical stats to the row-sharded layout."""
    import jax
    import numpy as np
    import pandas as pd

    from anovos_tpu.ops.reductions import masked_moments
    from anovos_tpu.shared.runtime import MODEL_AXIS, init_runtime
    from anovos_tpu.shared.table import Table

    init_runtime(mesh_shape=(4, 2))
    try:
        g = np.random.default_rng(11)
        df = pd.DataFrame({f"w{i}": g.normal(i, 1 + i / 10, 500) for i in range(8)})
        df.iloc[::7, 3] = np.nan
        t = Table.from_pandas(df)
        cols = list(df.columns)
        Xr, Mr = t.numeric_block(cols)
        Xc, Mc = t.numeric_block(cols, shard_cols=True)
        assert MODEL_AXIS in str(Xc.sharding.spec), Xc.sharding
        mr = {k: np.asarray(v) for k, v in masked_moments(Xr, Mr).items()}
        mc = {k: np.asarray(v) for k, v in masked_moments(Xc, Mc).items()}
        for k in mr:
            np.testing.assert_allclose(mr[k], mc[k], rtol=1e-5, err_msg=k)
    finally:
        init_runtime()  # restore the default 8-device data mesh


# ------------------------------------------ a stats pass on 1 and 4 devices --

_STATS_ROWS = 2000
_STATS_METRICS = ["global_summary", "measures_of_counts", "measures_of_centralTendency",
                  "measures_of_cardinality", "measures_of_percentiles", "measures_of_dispersion",
                  "measures_of_shape"]


def _stats_pass(work, n_devices, tag):
    """One ``workflow.run`` of ``input_dataset`` + ``stats_generator`` (all
    seven measures) + ``write_stats`` at 2,000 rows with the runtime on the
    first ``n_devices`` of the suite's eight: its manifest, its seven tables
    and the bytes it booked as copied from chip to chip."""
    import os

    import pandas as pd
    import yaml

    from anovos_tpu import obs, workflow
    from anovos_tpu.data_ingest import synthetic
    from anovos_tpu.shared.runtime import init_runtime

    data = synthetic.generate(_STATS_ROWS, 7, dest=work / "income_dataset")
    out = work / tag
    out.mkdir()
    cfg = {
        "input_dataset": {
            "read_dataset": {"file_path": os.path.join(data, "parquet"), "file_type": "parquet"},
            "delete_column": ["logfnl", "empty", "dt_2"],
        },
        "stats_generator": {"metric": _STATS_METRICS,
                            "metric_args": {"list_of_cols": "all", "drop_cols": ["ifa"]}},
        "write_stats": {"file_path": "stats", "file_type": "parquet", "file_configs": {"mode": "overwrite"}},
    }
    (out / "pipeline.yaml").write_text(yaml.safe_dump(cfg, sort_keys=False))

    def d2d():
        return sum(v for _, v in obs.get_metrics().counter("transfer_d2d_bytes_total").items())

    cwd = os.getcwd()
    os.chdir(out)
    init_runtime(devices=jax.devices()[:n_devices])
    try:
        before = d2d()
        workflow.run(str(out / "pipeline.yaml"), "local")
        copied = d2d() - before
    finally:
        init_runtime()  # the suite's 8-device mesh again
        os.chdir(cwd)
    tables = {m: pd.read_parquet(out / "stats" / "data_analyzer" / "stats_generator" / m) for m in _STATS_METRICS}
    return {"manifest": obs.load_manifest(workflow.LAST_MANIFEST_PATH), "tables": tables, "d2d_bytes": copied}


@pytest.fixture(scope="module")
def one_device_stats_pass(tmp_path_factory):
    work = tmp_path_factory.mktemp("stats_pass")
    return work, _stats_pass(work, 1, "reference")


@pytest.mark.parametrize("n_devices", [1, 4])
def test_stats_pass_computes_one_describe_copies_nothing_and_agrees_with_one_device(
        one_device_stats_pass, n_devices):
    """The kept path of PR 27 whatever the device count: the seven nodes read
    one Table instance, one of them computes the (on four devices partitioned)
    describe and five read its memo, no table is copied from chip to chip, and
    the seven tables are the 1-device pass's — counts, distinct counts and
    labels exactly, the float statistics within the tolerances the benchmark's
    configurations hold a pass to."""
    import pandas as pd

    work, ref = one_device_stats_pass
    got = _stats_pass(work, n_devices, f"devices{n_devices}")
    sched = got["manifest"]["scheduler"]
    assert sched.get("n_devices", 1) == n_devices
    nodes = [r for r in got["manifest"]["phases"] if r["parent"] == "dag"]
    assert {r["name"] for r in nodes} == {f"stats_generator/{m}" for m in _STATS_METRICS}
    assert sorted(r["counts"].get("describe_computed", -1) for r in nodes) == [-1, 0, 0, 0, 0, 0, 1]
    assert len({r["thread"] for r in nodes}) > 1  # in flight together, not one after the other
    assert got["d2d_bytes"] == 0 and not [r for r in got["manifest"]["phases"] if r["name"] == "place/d2d"]
    if n_devices > 1:
        assert {n["lane"] for n in sched["nodes"].values()} == {"mesh"}
    for m in _STATS_METRICS:
        a = got["tables"][m].set_index(got["tables"][m].columns[0]).sort_index()
        b = ref["tables"][m].set_index(ref["tables"][m].columns[0]).sort_index()
        assert list(a.index) == list(b.index) and list(a.columns) == list(b.columns), m
        for c in a.columns:
            x, y = pd.to_numeric(a[c], errors="coerce"), pd.to_numeric(b[c], errors="coerce")
            floats = pd.api.types.is_float_dtype(a[c]) and not c.endswith(("_count", "_rows", "_values"))
            if floats and n_devices > 1:  # another partial-sum tree: the last ulps may differ
                np.testing.assert_allclose(x.to_numpy(float), y.to_numpy(float), rtol=1e-4, atol=5.1e-5,
                                           err_msg=f"{m}.{c}")
            else:
                pd.testing.assert_series_equal(a[c], b[c], check_names=False, obj=f"{m}.{c}")
