"""The medians of a small segment class by counting selection (PR 40):
``_select_medians`` against the kept sort path to the bit on the edges a
selection has, what a class lowers to, and the counts the numeric stage row
of the time-series inspection carries.  (Beside, not in,
``test_ts_calendar_counts.py``: the suite's workers take files by their
number of tests, and a file that outgrows ``tests/benchmark/test_benchmark_harness.py``
moves that file behind one that warms its programs; PERF.md section 7.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from anovos_tpu.data_analyzer import ts_analyzer as ta
from anovos_tpu.data_transformer import datetime as dtt
from anovos_tpu.shared.table import Table


_KINDS = 8


def _hard_block(rows, k, nseg, seed):
    """A block on the edges a selection has.  The kind of a (column, bucket) cell's values is
    ``(bucket + column) % 8``, so every column meets every kind: free values, one value repeated,
    three values with ties across the middle, zeros of both signs among negatives, infinities of
    both signs, the largest and the smallest magnitudes (denormals), small integers, and cents.  Bucket
    ``nseg - 1`` is empty, bucket ``nseg - 2`` holds one row, valid in every column; a tenth of the
    rows is invalid and 15 % of the values are masked."""
    g = np.random.default_rng(seed)
    ids = g.integers(0, nseg - 2, rows).astype(np.int32)
    ids[rows // 2] = nseg - 2
    valid = g.random(rows) > 0.1
    Mv = g.random((rows, k)) > 0.15
    valid[rows // 2], Mv[rows // 2] = True, True
    big, tiny = np.finfo(np.float32).max, np.float32(1e-45)
    draws = np.stack([
        g.normal(0.0, 50.0, (rows, k)),
        np.full((rows, k), 7.25),
        g.choice([1.0, 2.0, 3.0], (rows, k), p=[0.3, 0.4, 0.3]),
        g.choice([-3.5, -0.0, 0.0, -1e-3, 2.0], (rows, k), p=[0.2, 0.3, 0.3, 0.1, 0.1]),
        g.choice([-np.inf, np.inf, -1.0, 1.0], (rows, k), p=[0.3, 0.3, 0.2, 0.2]),
        g.choice([big, -big, tiny, -tiny, 1.0], (rows, k)),
        g.integers(-3, 4, (rows, k)).astype(np.float64),
        np.round(g.lognormal(2.3, 0.8, (rows, k)), 2),
    ]).astype(np.float32)  # (kind, rows, k)
    kind = (ids[:, None] + np.arange(k)[None, :]) % _KINDS
    V = np.take_along_axis(draws, kind[None], axis=0)[0]
    return ids, valid, V, Mv


@pytest.mark.parametrize("rows", [4096, 3 * dtt._DENSE_CHUNK_ROWS], ids=["one_chunk", "the_scan"])
@pytest.mark.parametrize("nseg", [8, 32, 64])
def test_the_selection_picks_the_sorts_medians_bit_for_bit(nseg, rows):
    k = _KINDS
    ids, valid, V, Mv = _hard_block(rows, k, nseg, seed=nseg * 7 + rows)
    ok = Mv & valid[:, None]
    cnt = np.stack([np.bincount(ids[ok[:, j]], minlength=nseg) for j in range(k)]).astype(np.float32)
    assert (cnt[:, nseg - 1] == 0).all() and (cnt[:, nseg - 2] == 1).all()  # the empty bucket, the bucket of one
    live = cnt > 0
    assert (cnt[live] % 2 == 0).any() and (cnt[live] % 2 == 1).any() and cnt.max() < rows
    args = (jnp.asarray(ids), jnp.asarray(ok), jnp.asarray(V), jnp.asarray(cnt))
    by_count = np.asarray(jax.jit(dtt._select_medians, static_argnums=4)(*args, nseg))
    by_sort = np.asarray(jax.jit(dtt._sort_picks, static_argnums=4)(*args, nseg)[2])
    assert by_count.shape == (k, nseg) and not np.isnan(by_count[~live]).any()  # nothing for jax_debug_nans
    same = by_count.view(np.int32) == by_sort.view(np.int32)
    same |= (by_count == 0) & (by_sort == 0)  # a zero's sign: the unstable sort leaves it to chance
    same |= np.isnan(by_count) & np.isnan(by_sort)  # the mean of -inf and +inf
    assert same[live].all(), (by_count[live & ~same], by_sort[live & ~same])
    assert np.isnan(by_count).sum() < live.sum() // 8  # the infinities' buckets at most
    # and the middles are numpy's, in float64, where they are finite
    want = np.array([[np.median(V[ok[:, j] & (ids == b), j].astype(np.float64)) if live[j, b] else 0.0
                      for b in range(nseg)] for j in range(k)])
    finite = live & np.isfinite(want) & (np.abs(want) < 1e37)
    assert np.allclose(by_count[finite], want[finite], rtol=1e-6, atol=1e-30)


def _same_picks(got, want, live):
    """Bit for bit where a bucket is live, but for what the device's compare cannot tell apart: a zero's
    sign (the unstable sort leaves it to chance, the selection reads +0.0), a denormal (flushed: a zero
    to both), and the NaN that the mean of -inf and +inf is."""
    got, want = np.asarray(got), np.asarray(want)
    tiny = np.finfo(np.float32).tiny
    same = got.view(np.int32) == want.view(np.int32)
    same |= (np.abs(got) < tiny) & (np.abs(want) < tiny)
    same |= np.isnan(got) & np.isnan(want)
    return same[live].all(), (got[live & ~same][:5], want[live & ~same][:5])


def _skewed_block(rows, nseg, seed):
    """``_hard_block``'s eight kinds on the edges a grouped selection has: one bucket holds half the rows
    (so it is cut by every chunk's edge it meets); the last quarter of the class is a tail of one-row
    buckets with an empty one after each (so the chunk that holds the tail spans more than
    ``_WINDOW_LANES`` buckets from 512 buckets up); column 3 has no valid value; and twelve rows with a
    time carry a bucket outside ``[0, nseg)``: no bucket's rows."""
    ids, valid, V, Mv = _hard_block(rows, _KINDS, nseg, seed)
    g = np.random.default_rng(seed + 1)
    body = 3 * nseg // 4
    ids %= body
    ids[g.random(rows) < 0.5] = nseg // 3
    tail = np.arange(body, nseg - 2, 2, dtype=np.int32)
    picked = g.choice(rows, tail.size + 12, replace=False)
    ids[picked] = np.concatenate([tail, np.array([-1, -5, nseg, nseg + 7] * 3, np.int32)])
    valid[picked], Mv[picked] = True, True
    Mv[:, 3] = False
    return ids, valid, V, Mv, tail


@pytest.mark.parametrize("length", ["one_chunk", "three_chunks_and_100"])
@pytest.mark.parametrize("nseg", [128, 1024, 4096])
def test_the_grouped_selection_picks_the_sorts_values_bit_for_bit(nseg, length, monkeypatch):
    """min, max and median of a wide class from grouped rows (``_grouped_picks``) against one two-key sort
    a column (``_sort_picks``, the oracle), at chunks of 2,048 rows."""
    monkeypatch.setattr(dtt, "_DENSE_CHUNK_ROWS", 2048)
    rows = 2048 if length == "one_chunk" else 3 * 2048 + 100
    ids, valid, V, Mv, tail = _skewed_block(rows, nseg, seed=nseg + rows)
    inside = (ids >= 0) & (ids < nseg)
    ok = Mv & valid[:, None]
    counted = ok & inside[:, None]
    cnt = np.stack([np.bincount(ids[counted[:, j]], minlength=nseg) for j in range(_KINDS)]).astype(np.float32)
    live = cnt > 0
    assert cnt[:, nseg // 3].max() > rows // 4 and not live[3].any() and (~inside & valid).sum() == 12
    assert (cnt[:3, tail] == 1).all() and not live[:, tail + 1].any()  # one-row buckets, an empty one after each
    padded, chunk, nblk, steps = dtt._group_layout(rows, nseg)
    assert (padded, chunk, nblk, steps) == (rows + -rows % 2048, 2048, nseg // dtt._WINDOW_LANES, padded // 2048 + nblk)
    s_sorted = np.asarray(jax.jit(dtt._group_keys, static_argnums=4)(
        jnp.asarray(ids), jnp.asarray(valid), jnp.asarray(ok), jnp.asarray(V), nseg)[0]).reshape(-1, chunk)
    spans = np.minimum(s_sorted[:, -1], nseg - 1) - s_sorted[:, 0] + 1
    assert spans.max() > dtt._WINDOW_LANES  # a chunk walks several windows
    walked = int(dtt._window_steps(jnp.asarray(s_sorted.reshape(-1)), nseg)[2])
    assert padded // chunk <= walked + (s_sorted[:, 0] == nseg).sum() and walked <= steps  # the bound holds under this skew
    got = jax.jit(dtt._grouped_picks, static_argnums=5)(
        jnp.asarray(ids), jnp.asarray(valid), jnp.asarray(ok), jnp.asarray(V), jnp.asarray(cnt), nseg)
    want = jax.jit(dtt._sort_picks, static_argnums=4)(
        jnp.asarray(np.where(inside, ids, 0)), jnp.asarray(counted), jnp.asarray(V), jnp.asarray(cnt), nseg)
    for name, a, b in zip(("min", "max", "median"), got, want):
        same, differ = _same_picks(a, b, live)
        assert np.asarray(a).shape == (_KINDS, nseg) and same, (name, differ)
    mn, mx, med = (np.asarray(a) for a in got)
    assert np.isposinf(mn[~live]).all() and np.isneginf(mx[~live]).all() and not np.isnan(med[~live]).any()
    # and min and max are numpy's own
    j, b = (int(x[0]) for x in np.nonzero(cnt == cnt[:3].max()))
    own = V[counted[:, j] & (ids == b), j]
    assert mn[j, b] == own.min() and mx[j, b] == own.max()


def _routes(selects=0, sorts=0, wide=(), cells=0, wide_sorts=0, steps=0):
    return {"median_selects": selects, "median_sorts": sorts, "select_passes": dtt._SELECT_PASSES if selects else 0,
            "wide_segments": sum(wide), "wide_cells": cells, "wide_sorts": wide_sorts, "wide_select_steps": steps}


@pytest.mark.parametrize("nseg,rows,sorts,whiles", [
    (8, 4096, 0, 3), (32, 4096, 0, 3), (64, 4096, 0, 3), (128, 4096, 3, 0), (4096, 4096, 3, 0),
    (128, 3 * dtt._DENSE_CHUNK_ROWS, 1, None)])
def test_a_small_class_lowers_without_a_sort_or_a_scatter(nseg, rows, sorts, whiles):
    """... and a wide class with one sort: a column where its rows are few a bucket (one batched sort under
    ``vmap``, no loop), one for the whole grain, of rank 1, where the rows are grouped first."""
    ids, valid, V, Mv = _hard_block(rows, 3, nseg, seed=nseg)
    text = dtt._segment_aggregate_jit.lower(
        jnp.asarray(ids), jnp.asarray(valid), jnp.asarray(V), jnp.asarray(Mv), nseg=nseg).as_text()
    assert text.count("stablehlo.sort") == (1 if sorts else 0) and "scatter" not in text  # no class scatters (PR 49)
    assert whiles is None or text.count("stablehlo.while") == whiles
    grouped = dtt._groups_rows(rows, nseg)
    assert grouped == (sorts == 1)
    if grouped:  # the grain's one sort: the buckets its one key, the row index behind them
        (line,) = [ln for ln in text.split("\n") if "stablehlo.sort" in ln]
        assert line.split("(")[1].count("%") == 2 and "is_stable = false" in line and "tensor<%dxi32>" % rows in text
    steps = dtt._group_layout(rows, nseg).steps if grouped else 0
    assert dtt.aggregate_routes(rows, 3, nseg) == (
        _routes(selects=3) if not sorts else
        _routes(sorts=3, wide=(nseg,), cells=3 * rows, wide_sorts=sorts, steps=steps))


# sha256 (first 16 hex digits) of the StableHLO text the narrow side lowers to at commit 44bb041, the parent of
# PR 52 (jax 0.9.0, three f32 columns, the suite's default matmul precision ``highest``): a change of the narrow side's program changes these with it, and says so
_PARENT_NARROW_TEXT = {
    (4096, 8): "4496a80455782e8e", (4096, 32): "f75790bbae6b74f8", (4096, 64): "8d3e027b34e128ca",
    (3 * 32768, 8): "cc82e4f04c2bbdb9", (3 * 32768, 32): "3e429e6ccebef98c", (3 * 32768, 64): "15f41f030131a1a0",
}


def _sha(text):
    import hashlib

    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _shapes(rows, k=3):
    sd = jax.ShapeDtypeStruct
    return sd((rows,), jnp.int32), sd((rows,), jnp.bool_), sd((rows, k), jnp.float32), sd((rows, k), jnp.bool_)


@pytest.mark.parametrize("rows,nseg", sorted(_PARENT_NARROW_TEXT))
def test_the_narrow_side_lowers_to_the_parents_text(rows, nseg):
    """The grouped selection is the wide side's: a class of at most 64 buckets (``nyc_taxi.ts_inspect``'s
    32 / 8 / 8, whose fused program is three calls of this one) lowers to the very text it had before PR 52,
    with no sort, no scatter and no loop but the scans' and the digits'."""
    with jax.default_matmul_precision("highest"):  # what the suite runs under; stated, so that the text is one text
        text = dtt._segment_aggregate_jit.lower(*_shapes(rows), nseg=nseg).as_text()
    assert "sort" not in text and "scatter" not in text and text.count("stablehlo.while") == (3 if rows == 4096 else 4)
    assert _sha(text) == _PARENT_NARROW_TEXT[rows, nseg]


def _days_of_trips(days: int, rows: int = 900) -> pd.DataFrame:
    g = np.random.default_rng(days)
    start = int(np.datetime64("2015-01-01T00:00:00", "s").astype("int64"))
    return pd.DataFrame({
        "pickup": (start + g.integers(0, 86400 * days, rows)).astype("datetime64[s]"),
        "fare": np.round(g.lognormal(2.3, 0.8, rows), 2),
        "dist": pd.Series(np.round(g.gamma(2.0, 1.5, rows), 2)).where(g.random(rows) > 0.1),
        "pax": g.integers(0, 7, rows),
        "flag": g.choice(["Y", "N"], rows, p=[0.1, 0.9]),
    })


@pytest.mark.parametrize("days,selects,sorts", [(31, 9, 0), (1500, 6, 3)], ids=["a_month", "years_of_days"])
def test_the_numeric_stage_row_says_how_its_medians_were_taken(days, selects, sorts, tmp_path):
    """Three numeric columns over three grains: a month of days is class 32 and selects with the
    dayparts and the weekdays; years of days (``income_32k``'s dates) are a wide class and sort."""
    from anovos_tpu.obs import get_tracer

    tracer, frame = get_tracer(), _days_of_trips(days)
    tracer.drain()
    ta.ts_analyzer(Table.from_pandas(frame), output_path=str(tmp_path))
    (row,) = [s for s in tracer.drain() if s.name == "ts/viz/num"]
    assert row.args["cols"] == 3
    assert (row.args["median_selects"], row.args["median_sorts"]) == (selects, sorts)
    assert row.args["select_passes"] == dtt._SELECT_PASSES == 9
    # the daily grain of years is the one wide class: its buckets, and the cells (padded rows x columns) it aggregates
    assert (row.args["wide_segments"], row.args["wide_cells"]) == ((2048, 3 * row.args["rows"]) if sorts else (0, 0))
    assert row.args["host_rows"] == 0
    hourly = pd.read_csv(tmp_path / "ts_num_hourly_pickup.csv")
    part = ta._DAYPART_LUT[frame["pickup"].dt.hour.to_numpy()]
    for (label, attribute), got in hourly.set_index(["bucket", "attribute"])["median"].items():
        want = frame.loc[part == ta._DAYPART_NAMES.index(label), attribute].median()
        assert abs(got - want) <= 1e-4 + 1e-6 * abs(want), (label, attribute)


