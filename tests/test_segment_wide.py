"""The wide side of the per-bucket aggregate's one rule (PR 49: a class of more
than ``_DENSE_SEGMENTS_MAX`` buckets takes its moments from ``ops/segment.py``'s
``dense_block_sums`` and min, max and median from one two-key sort a column):
against float64 numpy at classes 128, 1,024 and 4,096, at a bucket of 2^20
values of one sign, beside the parent's scatters (kept here as the control),
against the narrow side at the boundary, and through ``aggregator``.  (A file
of its own: the suite's workers take files by their number of tests, see
``test_segment_medians.py``.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from anovos_tpu.data_transformer import datetime as dtt
from anovos_tpu.ops import segment
from anovos_tpu.shared.table import Table

LIMIT = 1e-6  # relative, of a float64 sum: what the wide class keeps


def _block(rows, k, nseg, seed, sign=1.0):
    """Seeded values of one sign a column (log-normal around 6 x 10^5: an id's magnitude; every other
    column negative), nulls in a tenth of the time column and in 15 % of the values, buckets 3 and
    ``nseg - 1`` empty."""
    g = np.random.default_rng(seed)
    ids = g.integers(0, nseg - 1, rows)
    ids = np.where(ids == 3, 4, ids).astype(np.int32)
    valid = g.random(rows) > 0.1
    V = (sign * g.lognormal(13.3, 0.6, (rows, k)) * np.where(np.arange(k) % 2, -1.0, 1.0)).astype(np.float32)
    Mv = g.random((rows, k)) > 0.15
    return ids, valid, V, Mv


def _by_numpy(ids, valid, V, Mv, nseg):
    """count, sum, sum of squares in float64; min, max and the sort's pick of the median as f32 stores them."""
    k = V.shape[1]
    cnt = np.zeros((k, nseg))
    sm, sq = np.zeros((k, nseg)), np.zeros((k, nseg))
    mn, mx = np.full((k, nseg), np.inf, np.float32), np.full((k, nseg), -np.inf, np.float32)
    med = np.zeros((k, nseg), np.float32)
    for j in range(k):
        ok = valid & Mv[:, j]
        order = np.lexsort((V[ok, j], ids[ok]))
        b, x = ids[ok][order], V[ok, j][order]
        cnt[j] = np.bincount(b, minlength=nseg)
        sm[j] = np.bincount(b, weights=x.astype(np.float64), minlength=nseg)
        sq[j] = np.bincount(b, weights=x.astype(np.float64) ** 2, minlength=nseg)
        start = np.cumsum(cnt[j]).astype(int) - cnt[j].astype(int)
        for s in np.nonzero(cnt[j])[0]:
            n, lo = int(cnt[j, s]), start[s]
            mn[j, s], mx[j, s] = x[lo], x[lo + n - 1]
            med[j, s] = (x[lo + (n - 1) // 2] + x[lo + n // 2]) / np.float32(2)
    return cnt, sm, sq, mn, mx, med


def _scatter_moments(ids0, valid, ok, V, nseg: int):
    """The parent's wide side (commit 4b375ed, ``datetime.py:722``), line for line: three f32
    ``segment_sum``s, a ``segment_min`` and a ``segment_max`` a column under ``vmap``.  The control."""
    seg = jnp.where(valid, ids0, nseg)

    def per_col(v, o):
        s = jnp.where(o, ids0, nseg)
        cnt = jax.ops.segment_sum(jnp.where(o, 1.0, 0.0), seg, num_segments=nseg + 1)[:nseg]
        sm = jax.ops.segment_sum(jnp.where(o, v, 0.0), seg, num_segments=nseg + 1)[:nseg]
        sq = jax.ops.segment_sum(jnp.where(o, v * v, 0.0), seg, num_segments=nseg + 1)[:nseg]
        mn = jax.ops.segment_min(jnp.where(o, v, jnp.inf), s, num_segments=nseg + 1)[:nseg]
        mx = jax.ops.segment_max(jnp.where(o, v, -jnp.inf), s, num_segments=nseg + 1)[:nseg]
        return cnt, sm, sq, mn, mx

    return jax.vmap(per_col, in_axes=(1, 1), out_axes=0)(V, ok)


def _aggregate(ids, valid, V, Mv, nseg):
    return [np.asarray(a) for a in dtt._segment_aggregate_jit(
        jnp.asarray(ids), jnp.asarray(valid), jnp.asarray(V), jnp.asarray(Mv), nseg=nseg)]


def _worst(got, want, live):
    return float(np.abs(got[live] / want[live] - 1.0).max())


def _holds(got, want):
    """Count, min, max and median exact, the two sums inside ``LIMIT``; the dead buckets as the narrow side leaves them."""
    cnt, sm, sq, mn, mx, med = got
    n_cnt, n_sm, n_sq, n_mn, n_mx, n_med = want
    live = n_cnt > 0
    assert (cnt == n_cnt).all() and not live[:, 3].any() and not live[:, -1].any()
    assert (mn[live] == n_mn[live]).all() and (mx[live] == n_mx[live]).all() and (med[live] == n_med[live]).all()
    assert np.isposinf(mn[~live]).all() and np.isneginf(mx[~live]).all() and (sm[~live] == 0).all()
    assert _worst(sm, n_sm, live) < LIMIT and _worst(sq, n_sq, live) < LIMIT


@pytest.mark.parametrize("nseg,rows", [(128, 3 * segment._DENSE_CHUNK_ROWS + 100), (1024, 50_000), (4096, 20_000)])
def test_a_wide_class_against_float64_numpy(nseg, rows):
    """Several chunks of the scan with a ragged end (128), the two classes of a daily grain over years."""
    assert dtt._is_wide(nseg)
    block = _block(rows, 3, nseg, seed=nseg)
    _holds(_aggregate(*block, nseg), _by_numpy(*block, nseg))


@pytest.fixture(scope="module")
def full_bucket():
    """2^20 + 2^17 rows of class 128, of which 2^20 lie in bucket 5: values of one sign a column, none masked."""
    rows, nseg = 2**20 + 2**17, 128
    ids, valid, V, Mv = _block(rows, 2, nseg, seed=49)
    ids[: 2**20] = 5
    valid[: 2**20], Mv[: 2**20] = True, True
    g = np.random.default_rng(5)
    order = g.permutation(rows)  # in no order of time
    return ids[order], valid[order], V[order], Mv[order], nseg


def test_a_bucket_of_a_million_values_of_one_sign_keeps_1e_6(full_bucket):
    got, want = _aggregate(*full_bucket), _by_numpy(*full_bucket)
    assert want[0][:, 5].min() >= 2**20
    _holds(got, want)
    assert _worst(got[1][:, 5:6], want[1][:, 5:6], np.ones((2, 1), bool)) < 2e-7  # the full bucket itself


def test_the_parents_scatter_add_beside_it_the_control(full_bucket):
    """On the chip the parent's f32 scatter-add, one update at a time, left a bucket of 640,000 to
    1.4 M values 1.2-1.6 % off (PERF.md section 6, PR 39: ``bucket_mean`` 530-710 x its limit).  The
    CPU's scatter-add adds in the rows' order too, and misses the limit the wide class keeps: by less
    than the chip (its adds round to nearest; the chip's lost 1.6 %), by orders of magnitude all the same."""
    ids, valid, V, Mv, nseg = full_bucket
    ok = Mv & valid[:, None]
    cnt, sm, sq, mn, mx = (np.asarray(a) for a in jax.jit(_scatter_moments, static_argnums=4)(
        jnp.asarray(ids), jnp.asarray(valid), jnp.asarray(ok), jnp.asarray(V), nseg))
    n_cnt, n_sm, n_sq, n_mn, n_mx, _ = _by_numpy(ids, valid, V, Mv, nseg)
    live = n_cnt > 0
    assert (cnt == n_cnt).all() and (mn[live] == n_mn[live]).all() and (mx[live] == n_mx[live]).all()
    full = np.zeros_like(live)
    full[:, 5] = True
    assert _worst(sm, n_sm, full) > 10 * LIMIT and _worst(sq, n_sq, full) > 10 * LIMIT  # the control fails
    assert _worst(sm, n_sm, full) < 1e-2  # and is no nonsense
    got = _aggregate(ids, valid, V, Mv, nseg)
    assert (got[0] == cnt).all() and (got[3] == mn).all() and (got[4] == mx).all()


def test_the_two_sides_of_the_rule_agree_at_the_boundary():
    """One input of 60 live buckets as class 64 (contraction at ``highest``, masked reduce, selection)
    and as class 128 (contraction of bfloat16 parts, one sort): the same counts, minima, maxima and
    medians to the bit, the same sums to 1e-6."""
    narrow, wide = dtt._DENSE_SEGMENTS_MAX, 2 * dtt._DENSE_SEGMENTS_MAX
    assert not dtt._is_wide(narrow) and dtt._is_wide(narrow + 1) and dtt._is_wide(wide)
    ids, valid, V, Mv = _block(3 * dtt._DENSE_CHUNK_ROWS, 4, narrow - 3, seed=64)
    a, b = _aggregate(ids, valid, V, Mv, narrow), _aggregate(ids, valid, V, Mv, wide)
    live = a[0] > 0
    assert live.sum() == 4 * (narrow - 5) and not (b[0][:, narrow:] > 0).any()
    for i in (0, 3, 4, 5):  # count, min, max, median
        assert (a[i][live] == b[i][:, :narrow][live]).all(), i
    for i in (1, 2):
        assert _worst(b[i][:, :narrow], a[i].astype(np.float64), live) < LIMIT
    assert np.isposinf(b[3][:, narrow:]).all() and np.isneginf(b[4][:, narrow:]).all()


def test_bf16_parts_add_up_to_the_value_and_block_sums_count_nothing_out_of_range():
    g = np.random.default_rng(3)
    x = np.concatenate([g.normal(0, 1e6, 5000), g.lognormal(-20, 8, 5000), [0.0, -0.0, 16_777_215.0, 1.2e6, 3.3e38]])
    x = x.astype(np.float32)
    parts = [np.asarray(p).astype(np.float32) for p in segment.bf16_parts(jnp.asarray(x))]
    assert ((parts[2] + parts[1]) + parts[0] == x).all()
    assert (np.abs(parts[0]) >= np.abs(parts[1])).all() and (np.abs(parts[1]) >= np.abs(parts[2])).all()
    ids = np.array([0, 1, 1, -1, 8, 7, 1], np.int32)  # -1 and 8 are no bucket of a class of 8
    x = jnp.asarray(np.arange(14, dtype=np.float32).reshape(7, 2))
    sums = np.asarray(segment.dense_block_sums(jnp.asarray(ids), (x,), lambda x_c: x_c.astype(jnp.bfloat16), 8))
    assert sums.shape == (2, 8) and sums[:, 1].tolist() == [2 + 4 + 12, 3 + 5 + 13] and sums[:, 7].tolist() == [10, 11]
    assert sums[:, 0].tolist() == [0, 1] and (sums[:, 2:7] == 0).all()


def test_aggregator_takes_a_daily_grain_over_a_year_by_the_wide_side():
    """``aggregator`` at a fine grain is the same one program: 400 days are class 512."""
    g = np.random.default_rng(11)
    rows = 6000
    start = int(np.datetime64("2013-01-07T00:00:00", "s").astype("int64"))
    frame = pd.DataFrame({
        "t": pd.Series((start + g.integers(0, 400 * 86400, rows)).astype("datetime64[s]")).where(g.random(rows) > 0.05),
        "user": g.integers(0, 1_198_786, rows),
        "miles": pd.Series(np.round(g.lognormal(6.6, 1.6, rows), 4)).where(g.random(rows) > 0.36),
    })
    got = dtt.aggregator(Table.from_pandas(frame), ["user", "miles"], ["count", "sum", "mean", "min", "max", "median", "stddev"],
                         "t", "%Y-%m-%d").set_index("t")
    day = frame["t"].dt.strftime("%Y-%m-%d")
    want = frame.groupby(day)[["user", "miles"]].agg(["count", "sum", "mean", "min", "max", "median", "std"])
    assert len(got) == len(want) == 400 and (got.index == want.index).all()
    for c in ("user", "miles"):
        f32 = lambda s: s.to_numpy(np.float32)  # noqa: E731  what the table stores
        assert (got[f"{c}_count"].to_numpy() == want[c]["count"].to_numpy()).all()
        some = want[c]["count"].to_numpy() > 0
        assert (f32(got[f"{c}_min"])[some] == f32(want[c]["min"])[some]).all()
        assert (f32(got[f"{c}_max"])[some] == f32(want[c]["max"])[some]).all()
        for ours, theirs, rtol in (("sum", "sum", 2e-6), ("mean", "mean", 2e-6), ("median", "median", 1e-6)):
            assert np.allclose(got[f"{c}_{ours}"].to_numpy()[some], want[c][theirs].to_numpy()[some], rtol=rtol), (c, ours)
        two = want[c]["count"].to_numpy() > 1
        assert np.allclose(got[f"{c}_stddev"].to_numpy()[two], want[c]["std"].to_numpy()[two], rtol=1e-3)


def test_a_grouped_class_on_the_mesh_gives_the_one_device_results():
    """On a mesh the block is column-parallel and the ids and the validity replicated: the grouped
    selection (many rows a bucket: 3 chunks at class 128) gives the one device's counts, minima,
    maxima and medians to the bit and its sums to the limit."""
    from anovos_tpu.shared.runtime import get_runtime, wants_column_parallel

    rt = get_runtime()
    rows, nseg = 3 * dtt._DENSE_CHUNK_ROWS, 128
    assert dtt._groups_rows(rows, nseg) and rows % rt.mesh.size == 0
    ids, valid, V, Mv = _block(rows, 8, nseg, seed=52)
    on_mesh = [rt.shard_rows(a) for a in (ids, valid, V, Mv)]
    assert wants_column_parallel(*on_mesh, replicate=on_mesh[:2]) == (rt.mesh.size > 1)
    got = [np.asarray(a) for a in dtt._segment_aggregate(*on_mesh, nseg)]
    want = _aggregate(ids, valid, V, Mv, nseg)
    live = want[0] > 0
    for i in (0, 3, 4, 5):
        assert (got[i][live] == want[i][live]).all(), i
    for i in (1, 2):
        assert _worst(got[i], want[i].astype(np.float64), live) < LIMIT


def test_the_hand_timing_runs_the_programs_own_functions_at_a_small_shape(tmp_path, capsys):
    """``tools/probes/wide_select_probe.py`` (PERF.md section 6, PR 52) on the CPU: a rehearsal of the
    chip call at 3 chunks x 4 columns, class 128.  Every variant gets a row, the grouped route's picks
    are the sort's, and the probe times ``datetime.py``'s functions, not copies of them."""
    import json

    from tools.probes import wide_select_probe as probe

    rows = 3 * dtt._DENSE_CHUNK_ROWS
    (found,) = probe.main(["--shape", f"{rows},4,128", "--reps", "1", "--out", str(tmp_path)])
    table = found["table"]
    assert set(table) == {"sort_picks", "group_keys", "windowed_picks", "grouped_picks", "group_index",
                          "gather_rows", "gather_rows_32", "gather_cols"}  # operands_3: no divisor of four columns
    assert table["grouped_picks"]["same"] and table["windowed_picks"]["same"] and found["groups_rows"]
    assert 3 <= table["windowed_picks"]["steps"] <= table["windowed_picks"]["bound"] == dtt._group_layout(rows, 128).steps
    assert all(r["s"] > 0 and r["first_s"] > 0 for r in table.values())
    assert json.loads((tmp_path / "wide_select_probe.jsonl").read_text())["table"].keys() == table.keys()
    assert "same as sort_picks" in capsys.readouterr().out
    source = open(probe.__file__).read()
    assert "def _sort_picks" not in source and "def _group_keys" not in source and "dtt._windowed_picks" in source
