"""What kind each column was read as, and the mode of the low-cardinality
strings, all exact.  The three name lists of ``global_summary`` (numeric,
categorical, other) against the part files' parquet schema by the upstream's
rule (``shared/utils.py::attributeType_segregation``: string and boolean are
categorical; integers, floats and decimals numeric; everything else, dates
and timestamps among it, other); the most frequent value of each of
``mode_columns`` and the rows that hold it, a tie settled by the count.
args: ``mode_columns``.  Tables: global_summary, measures_of_centralTendency."""

import glob
import os

import pyarrow.parquet as pq
import pyarrow.types as pat

from benchmark.harness.check import exact, table

KINDS = ("numcols", "catcols", "othercols")


def read(out_dir, traffic, args):
    gs = table(out_dir, traffic["tables"]["global_summary"])
    gs = dict(zip(gs["metric"], gs["value"]))
    ct = table(out_dir, traffic["tables"]["measures_of_centralTendency"]).set_index("attribute")
    ct = ct.reindex(args["mode_columns"])
    return {"kinds": {k: str(gs[k + "_name"]) for k in KINDS},
            "kind_counts": {k: int(gs[k + "_count"]) for k in KINDS},
            "mode": {c: str(m) for c, m in ct["mode"].items()},
            "mode_rows": {c: int(n) for c, n in ct["mode_rows"].fillna(-1).items()}}


def _kind(t) -> str:
    if pat.is_string(t) or pat.is_large_string(t) or pat.is_boolean(t):
        return "catcols"
    if pat.is_integer(t) or pat.is_floating(t) or pat.is_decimal(t):
        return "numcols"
    return "othercols"


def reference(frames, args):
    spec = frames.pipeline["input_dataset"]
    rd = spec["read_dataset"]
    schema = pq.read_schema(sorted(glob.glob(os.path.join(rd["file_path"], "*." + rd["file_type"])))[0])
    ren = spec.get("rename_column") or {}
    ren = dict(zip(ren.get("list_of_cols", []), ren.get("list_of_newcols", [])))
    kinds = {k: [] for k in KINDS}
    for f in schema:
        if f.name not in (spec.get("delete_column") or []):
            kinds[_kind(f.type)].append(ren.get(f.name, f.name))
    modes, mode_rows = {}, {}
    for c in args["mode_columns"]:
        counts = frames.main[c].value_counts()
        modes[c] = sorted(counts.index[counts == counts.iloc[0]])  # more than one on a tie
        mode_rows[c] = int(counts.iloc[0])
    return {"kinds": {k: ", ".join(v) for k, v in kinds.items()},
            "kind_counts": {k: len(v) for k, v in kinds.items()},
            "modes": modes, "mode_rows": mode_rows}


def compare(ans, ref, tolerances, args):
    return [
        exact("column_kinds", ans["kinds"], ref["kinds"]),
        exact("column_kind_counts", ans["kind_counts"], ref["kind_counts"]),
        exact("mode", ans["mode"], {c: ans["mode"].get(c) if ans["mode"].get(c) in m else m[0]
                                    for c, m in ref["modes"].items()}),
        exact("mode_rows", ans["mode_rows"], ref["mode_rows"]),
    ]
