"""The share of the chip's memory bandwidth that the association block's
group counts reach in the traced pass: the bytes no implementation can avoid
over the device seconds under the scope ``assoc/group_counts``
(``assoc_device_s``'s reading of it) x the chip's peak (``harness/peaks.json``,
``hbm_bytes_per_s``).  The bytes, every array read or written once, from the
counts of the ``assoc/group_counts`` stage rows of the traced pass's
``phases`` (one row a computation of the counts; ``IG_calculation`` takes
``IV_calculation``'s and opens none):

    a block of codes    int32 + validity bool        5 bytes a cell   cells
    the label           event f32 + validity bool    5 bytes a row    block_rows (once a block)
    the counts          labelled rows and events,    4 bytes a lane   count_lanes
                        f32, padded to their class

So it reads the same work whether a contraction, a scatter-add or a fused
compare-and-reduce counts, and no implementation can push it above 100: each
must read the codes and the label and write the counts at least once.  A
count by one-hot contraction makes the one-hots on the vector unit, a few
lanes wide, so this reads far under 100: it is where a ``perf_opt`` on the
block's kernels starts from.  A mesh shares the rows.  Nothing without a
trace, or where the manifest carries no such count or the trace no such scope
(a program from before them)."""

import json
import os

from benchmark.harness import phases
from benchmark.harness.names import BENCH, load_module

SCOPE, ROW = "assoc/group_counts", "assoc/group_counts"


def group_count_bytes(cells: int, block_rows: int, count_lanes: int) -> int:
    """The least bytes the group counts of those blocks move."""
    return 5 * cells + 5 * block_rows + 4 * count_lanes


def stage_bytes(rows: list) -> int:
    """``rows``: a manifest's ``phases``."""
    return sum(group_count_bytes(r["counts"]["cells"], r["counts"]["block_rows"], r["counts"]["count_lanes"])
               for r in rows if r["name"] == ROW and "cells" in r["counts"])


def share_pct(nbytes: float, seconds: float, bytes_per_s: float, chips: int = 1) -> float:
    return 100.0 * nbytes / chips / (seconds * bytes_per_s)


def read(run):
    seconds = load_module("layer_metrics", "assoc_device_s").by_scope(run).get(SCOPE)
    nbytes = stage_bytes(phases.rows(run.get("traced")))
    if not seconds or not nbytes:
        return None
    import jax

    with open(os.path.join(BENCH, "harness", "peaks.json")) as f:
        peak = json.load(f)["devices"].get(jax.devices()[0].device_kind)
    if peak is None:
        return None
    return share_pct(nbytes, seconds, peak["hbm_bytes_per_s"], jax.device_count())
