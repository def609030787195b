"""Where the codes sit decides the route of ``ops/segment.py``'s three
programs (PR 44): a column laid over the mesh keeps the partitioned
scatter-add and gather, a ``Table`` on one device takes the contraction, and
both give the same frame."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from anovos_tpu.ops import segment as sg
from segment_cases import column, luts, routes_seen, want_counts


@pytest.mark.parametrize("p", [16, 4096, 131_072])
def test_a_row_sharded_column_keeps_the_partitioned_programs_and_gives_the_one_device_answer(runtime, monkeypatch, p):
    rows = runtime.n_data * 2 * 8192
    codes, M, y = column(p, rows, seed=7 * p)
    c, m, w = (runtime.shard_rows(a) for a in (codes, M, y))
    assert not sg.on_one_device(c) and sg.on_one_device(jnp.asarray(codes)) and sg.on_one_device(codes)
    seen = routes_seen(monkeypatch)
    assert np.array_equal(np.asarray(sg.code_counts(c, m, p)), want_counts(codes, M, p))
    assert np.array_equal(np.asarray(sg.code_label_counts(c, m, w, p)), want_counts(codes, M, p, y))
    lut = luts(p, seed=p)["f32_inf_nan"]
    got = sg.vocab_lookup(lut, c)
    assert got.sharding.is_equivalent_to(c.sharding, 1)  # the gathered column stays on its rows' shards
    assert np.asarray(got).tobytes() == lut[np.clip(codes, 0, p - 1)].tobytes()
    assert seen == [False, False, False]
    monkeypatch.undo()
    counts = sg._code_counts_p.lower(c, m, vocab_size=p).compile().as_text()
    assert "all-gather" not in counts and "all-to-all" not in counts and counts.count("all-reduce(") == 1
    gather = sg._lut_gather.lower(jnp.asarray(lut), c).compile().as_text()
    assert not any(op in gather for op in ("all-gather", "all-to-all", "all-reduce(", "collective-permute"))


@pytest.fixture()
def one_device_table():
    """A click-log shaped frame (a small, a middling and a large vocabulary, nulls, a label) as a ``Table`` on
    the suite's mesh and re-placed onto one of its devices, as a scheduler node placed ``device`` sees it."""
    import pandas as pd

    from anovos_tpu.shared.runtime import derive_runtime, placement_scope
    from anovos_tpu.shared.table import Table

    g = np.random.default_rng(44)
    rows = 100_000
    frame = pd.DataFrame({
        "small": np.array(["a", "b", "c", None], dtype=object)[g.integers(0, 4, rows)],
        "middle": np.array([f"m{v}" for v in g.integers(0, 3000, rows)], dtype=object),
        "large": np.array([f"{v:05x}" for v in g.integers(0, 200_000, rows)], dtype=object),  # some 78,000 distinct values
        "label": (g.random(rows) < 0.3).astype(np.int32)})
    frame.loc[g.random(rows) < 0.05, "middle"] = None
    table = Table.from_pandas(frame)
    with placement_scope(derive_runtime(jax.devices()[:1])):
        yield table, table.to_active_placement()


@pytest.mark.parametrize("fn,kwargs", [
    ("cat_to_num_supervised", dict(list_of_cols=["small", "middle", "large"], label_col="label", event_label=1)),
    ("imputation_MMM", dict(list_of_cols=["small", "middle"], method_type="median")),
])
def test_a_table_on_one_device_takes_the_contraction_and_gives_the_meshs_frame(one_device_table, fn, kwargs):
    from anovos_tpu.data_transformer import transformers as T
    from anovos_tpu.obs import get_tracer

    meshed, single = one_device_table
    assert not sg.on_one_device(meshed.columns["small"].data) and sg.on_one_device(single.columns["small"].data)
    frames, routes = [], []
    tracer = get_tracer()
    for table in (meshed, single):
        with tracer.run_pass():
            frames.append(getattr(T, fn)(table, **kwargs).to_pandas())
        routes.append({k: v for r in tracer.phases() if r["name"].startswith("transform/")
                       for k, v in r["counts"].items() if k.startswith(("dense_", "scatter_", "index_"))})
    assert frames[0].equals(frames[1])  # every value and every null, to the bit
    calls = 2 if fn == "cat_to_num_supervised" else 1
    large = 1 if fn == "cat_to_num_supervised" else 0  # its class is 131,072: above both limits
    assert routes[0]["dense_counts"] == 0 and routes[0]["scatter_counts"] == calls * (2 + large)
    assert (routes[1]["dense_counts"], routes[1]["scatter_counts"]) == (calls * 2, calls * large)
    if fn == "cat_to_num_supervised":
        assert (routes[0]["dense_gathers"], routes[0]["index_gathers"]) == (0, 6)
        assert (routes[1]["dense_gathers"], routes[1]["index_gathers"]) == (4, 2)


def test_a_lut_shorter_than_its_class_is_padded_with_zeros():
    lut = np.array([0.25, -1.5, 3.0], np.float32)
    got = np.asarray(sg.vocab_lookup(lut, jnp.asarray(np.array([2, 0, -1, 1, 9], np.int32))))
    assert got.tolist() == [3.0, 0.25, 0.25, -1.5, 0.0]  # 9 clips to lane 15 of the padded class
