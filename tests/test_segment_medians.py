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


@pytest.mark.parametrize("nseg,sorts", [(8, False), (32, False), (64, False), (128, True), (4096, True)])
def test_a_small_class_lowers_without_a_sort_or_a_scatter(nseg, sorts):
    ids, valid, V, Mv = _hard_block(4096, 3, nseg, seed=nseg)
    text = dtt._segment_aggregate_jit.lower(
        jnp.asarray(ids), jnp.asarray(valid), jnp.asarray(V), jnp.asarray(Mv), nseg=nseg).as_text()
    assert ("stablehlo.sort" in text) == sorts and "scatter" not in text  # no class scatters (PR 49)
    assert dtt.aggregate_routes(4096, 3, nseg) == {
        "median_selects": 0 if sorts else 3, "median_sorts": 3 if sorts else 0,
        "select_passes": 0 if sorts else dtt._SELECT_PASSES,
        "wide_segments": nseg if sorts else 0, "wide_cells": 3 * 4096 if sorts else 0}


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


