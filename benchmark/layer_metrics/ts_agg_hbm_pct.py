"""The share of the chip's memory bandwidth that the per-bucket aggregates of
the time-series inspection reach in the traced pass: the bytes no
implementation can avoid over the device seconds under the scope
``ts/segment_aggregate`` (``ts_device_s``'s reading of it) x the chip's peak
(``harness/peaks.json``, ``hbm_bytes_per_s``).  The bytes, per call (one a
timestamp column: the three grains in one program), every array read or
written once, from the counts of the ``ts/viz/num`` stage rows of the traced
pass's ``phases`` (``rows``: the table's padded length, as every program
takes it; ``cols``: the numeric columns aggregated; ``segments``: the bucket
lanes of the call's three grains together):

    the numeric block     values f32 + mask bool         5 bytes a cell
    the timestamp column  seconds int32 + validity bool  5 bytes a row
    the aggregates        count, sum, sum of squares, min, max, median:
                          6 x f32 a column and bucket lane

So it reads the same work whether scatters, a contraction or one fused pass
take the moments, and no implementation can push it above 100: each must
read its inputs and write its answers at least once.  A median by sort makes
many passes over the data, so this reads well under 10 %: it is where a
selection in place of the sort would start from.  A mesh shares the rows.
Nothing without a trace, or where the manifest carries no such count or the
trace no such scope (a program from before them)."""

import json
import os

from benchmark.harness import phases
from benchmark.harness.names import BENCH, load_module

SCOPE = "ts/segment_aggregate"


def aggregate_bytes(rows: int, cols: int, segments: int) -> int:
    """The least bytes one call of the aggregate moves."""
    return rows * cols * 5 + rows * 5 + 6 * 4 * cols * segments


def stage_bytes(rows: list) -> int:
    """``rows``: a manifest's ``phases``."""
    return sum(aggregate_bytes(r["counts"]["rows"], r["counts"]["cols"], r["counts"]["segments"])
               for r in rows if r["name"] == "ts/viz/num" and "segments" in r["counts"])


def share_pct(nbytes: float, seconds: float, bytes_per_s: float, chips: int = 1) -> float:
    return 100.0 * nbytes / chips / (seconds * bytes_per_s)


def read(run):
    seconds = load_module("layer_metrics", "ts_device_s").by_scope(run).get(SCOPE)
    nbytes = stage_bytes(phases.rows(run.get("traced")))
    if not seconds or not nbytes:
        return None
    import jax

    with open(os.path.join(BENCH, "harness", "peaks.json")) as f:
        peak = json.load(f)["devices"].get(jax.devices()[0].device_kind)
    if peak is None:
        return None
    return share_pct(nbytes, seconds, peak["hbm_bytes_per_s"], jax.device_count())
