"""The share of the chip's memory bandwidth that the group counts and LUT
gathers of ``ops/segment.py`` reach in the traced pass: the bytes they cannot
avoid over ``segment_device_s`` x the chip's peak (``harness/peaks.json``,
``hbm_bytes_per_s``).  The bytes, per call, every array read or written once
(padding included), from the counts that a transformer puts on its
``transform/fit`` and ``transform/apply`` rows of the traced pass's ``phases``
(``imputation_MMM`` and ``cat_to_num_supervised``; a caller of the three
programs that opens no such row adds seconds and no bytes, so a pass with
such callers reads low, never high):

    code_counts        codes int32 + mask bool           5 bytes a row     count_rows
    code_label_counts  codes + mask + the label f32      9 bytes a row     label_rows
    either             the padded count vector, f32      4 bytes a lane    seg_lanes
    vocab_lookup       codes int32                       4 bytes a row     gather_rows
                       the padded LUT, as uploaded                         lut_bytes
                       the gathered column, as written                     gather_out_bytes

A mesh shares the rows, so a chip moves its share of them.  A scatter-add into
10^5-10^6 segments serialises on its destination, so this reads far under
1 %: it is where a ``perf_opt`` on ``ops/segment.py`` starts from.  Nothing
without a trace, or where the manifest carries no such count (a program from
before them)."""

import json
import os

from benchmark.harness import phases
from benchmark.harness.names import BENCH, load_module

BYTES = {"count_rows": 5, "label_rows": 9, "seg_lanes": 4, "gather_rows": 4, "lut_bytes": 1,
         "gather_out_bytes": 1}


def segment_bytes(rows: list) -> int:
    """``rows``: a manifest's ``phases``."""
    return sum(size * r["counts"].get(count, 0) for r in rows for count, size in BYTES.items())


def share_pct(nbytes: float, seconds: float, bytes_per_s: float, chips: int = 1) -> float:
    return 100.0 * nbytes / chips / (seconds * bytes_per_s)


def read(run):
    seconds = load_module("layer_metrics", "segment_device_s").read(run)
    nbytes = segment_bytes(phases.rows(run.get("traced")))
    if not seconds or not nbytes:
        return None
    import jax

    with open(os.path.join(BENCH, "harness", "peaks.json")) as f:
        peak = json.load(f)["devices"].get(jax.devices()[0].device_kind)
    if peak is None:
        return None
    return share_pct(nbytes, seconds, peak["hbm_bytes_per_s"], jax.device_count())
