"""The share of the chip's memory bandwidth that the aggregates of a wide
segment class reach in the traced pass: the bytes no implementation can avoid
over the device seconds under the scope ``ts/segment_aggregate/wide``
(``ts_wide_device_s``) x the chip's peak (``harness/peaks.json``,
``hbm_bytes_per_s``).  The bytes, per wide grain, every array read or written
once, from the counts of the ``ts/viz/num`` stage rows of the traced pass's
``phases`` (``cols``: the numeric columns; ``wide_cells``: padded rows x
columns x wide grains of the call; ``wide_segments``: their bucket lanes):

    the numeric block     values f32 + mask bool         5 bytes a cell
    the time column       ids int32 + validity bool      5 bytes a row
    the aggregates        count, sum, sum of squares, min, max, median:
                          6 x f32 a column and bucket lane

as ``ts_agg_hbm_pct.aggregate_bytes`` counts the whole call.  So it reads the
same work whether scatters, a contraction, a sort or one fused pass take the
aggregates, and no implementation can push it above 100.  A sort a column
makes hundreds of passes over its keys, so this reads a few per cent at most:
it is what a selection or a segmented pass in place of the sort would start
from.  A mesh shares the rows.  Nothing without a trace, or where the manifest
carries no such count or the trace no such scope."""

import json
import os

from benchmark.harness import phases
from benchmark.harness.names import BENCH, load_module


def wide_bytes(wide_cells: int, cols: int, wide_segments: int) -> int:
    """The least bytes the wide grains of one call move."""
    return wide_cells * 5 + wide_cells // cols * 5 + 6 * 4 * cols * wide_segments


def stage_bytes(rows: list) -> int:
    """``rows``: a manifest's ``phases``."""
    return sum(wide_bytes(r["counts"]["wide_cells"], r["counts"]["cols"], r["counts"]["wide_segments"])
               for r in load_module("layer_metrics", "ts_wide_s").wide_rows(rows))


def read(run):
    seconds = load_module("layer_metrics", "ts_wide_device_s").read(run)
    nbytes = stage_bytes(phases.rows(run.get("traced")))
    if not seconds or not nbytes:
        return None
    import jax

    with open(os.path.join(BENCH, "harness", "peaks.json")) as f:
        peak = json.load(f)["devices"].get(jax.devices()[0].device_kind)
    if peak is None:
        return None
    return load_module("layer_metrics", "ts_agg_hbm_pct").share_pct(
        nbytes, seconds, peak["hbm_bytes_per_s"], jax.device_count())
