"""The share of the chip's memory bandwidth that the drift block's side
histograms reach in the traced pass: the bytes no implementation can avoid
over the device seconds under the scope ``drift/side_histograms``
(``drift_device_s``'s reading of it) x the chip's peak (``harness/peaks.json``,
``hbm_bytes_per_s``).  The bytes, every array read or written once, from the
counts of the ``drift/sides`` stage rows of the traced pass's ``phases`` (one
row a call of ``drift_detector.statistics``, both sides in it):

    a live column      value f32 or code int32 + validity bool   5 bytes a cell   cells
                       at each side's padded rows
    the cut-offs       f32, bins - 1 a numeric column, a side    4 bytes each     cutoffs
    the histograms     f32, bins a numeric column and the        4 bytes a lane   hist_lanes
                       union's values a string column, a side

So it reads the same work whether a compare-and-reduce, a scatter-add, a sort
or a contraction counts, whatever lane class pads the counts and whichever
columns share a program, and no implementation can push it above 100: each
must read the columns and write the counts at least once.  A mesh shares the
rows.  Nothing without a trace, or where the manifest carries no such count or
the trace no such scope (a program from before them)."""

import json
import os

from benchmark.harness import phases
from benchmark.harness.names import BENCH, load_module

SCOPE, ROW = "drift/side_histograms", "drift/sides"


def side_bytes(cells: int, cutoffs: int, hist_lanes: int) -> int:
    """The least bytes the histograms of both sides move."""
    return 5 * cells + 4 * cutoffs + 4 * hist_lanes


def stage_bytes(rows: list) -> int:
    """``rows``: a manifest's ``phases``."""
    return sum(side_bytes(r["counts"]["cells"], r["counts"]["cutoffs"], r["counts"]["hist_lanes"])
               for r in rows if r["name"] == ROW and "cells" in r["counts"])


def share_pct(nbytes: float, seconds: float, bytes_per_s: float, chips: int = 1) -> float:
    return 100.0 * nbytes / chips / (seconds * bytes_per_s)


def read(run):
    seconds = load_module("layer_metrics", "drift_device_s").by_scope(run).get(SCOPE)
    nbytes = stage_bytes(phases.rows(run.get("traced")))
    if not seconds or not nbytes:
        return None
    import jax

    with open(os.path.join(BENCH, "harness", "peaks.json")) as f:
        peak = json.load(f)["devices"].get(jax.devices()[0].device_kind)
    if peak is None:
        return None
    return share_pct(nbytes, seconds, peak["hbm_bytes_per_s"], jax.device_count())
