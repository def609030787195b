"""The describe's share of the chip's memory bandwidth in the traced pass:
the bytes it cannot avoid over ``describe_device_s`` x the chip's peak
(``harness/peaks.json``, ``hbm_bytes_per_s``).  The bytes are every input
array of its programs read once, from the ``describe/*`` spans of the traced
pass's manifest (``rows`` x ``cols`` of the stacked block as the program
takes it, padding included):

    describe/numeric    values f32 + mask bool                    5 bytes a cell
    describe/wide       the exact pair, 2 x int32, + mask bool    9 bytes a cell
    describe/cat_sweep  codes int32 + mask bool                   5 bytes a cell
    describe/cat_sort   codes int32 + mask bool                   5 bytes a cell

A mesh shares the rows, so a chip reads its share of them.  A full sort makes
many passes over the data, so this reads far under 1 %: it is where a
selection instead of a sort would start from.  Nothing without a trace, or
where the manifest has no such span (a program from before them)."""

import json
import os

from benchmark.harness import phases
from benchmark.harness.names import BENCH, load_module

BYTES_A_CELL = {"describe/numeric": 5, "describe/wide": 9, "describe/cat_sweep": 5,
                "describe/cat_sort": 5}


def describe_bytes(rows: list) -> int:
    """``rows``: a manifest's ``phases``."""
    return sum(BYTES_A_CELL[r["name"]] * r["counts"].get("rows", 0) * r["counts"].get("cols", 0)
               for r in rows if r["name"] in BYTES_A_CELL)


def share_pct(nbytes: float, seconds: float, bytes_per_s: float, chips: int = 1) -> float:
    return 100.0 * nbytes / chips / (seconds * bytes_per_s)


def read(run):
    seconds = load_module("layer_metrics", "describe_device_s").read(run)
    nbytes = describe_bytes(phases.rows(run.get("traced")))
    if not seconds or not nbytes:
        return None
    import jax

    with open(os.path.join(BENCH, "harness", "peaks.json")) as f:
        peak = json.load(f)["devices"].get(jax.devices()[0].device_kind)
    if peak is None:
        return None
    return share_pct(nbytes, seconds, peak["hbm_bytes_per_s"], jax.device_count())
