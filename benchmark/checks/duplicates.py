"""Rows that repeat an earlier row (the check's ``drop_cols`` aside), and the
histogram of rows by their number of null columns, which the pipeline
takes after dropping those repeats.  Exact.
Tables: duplicate_detection, nullRows_detection."""

from benchmark.harness.check import exact, table


def read(out_dir, traffic, args):
    dd = table(out_dir, traffic["tables"]["duplicate_detection"])
    nr = table(out_dir, traffic["tables"]["nullRows_detection"])
    return {"duplicates": int(float(dict(zip(dd["metric"], dd["value"]))["duplicate_rows"])),
            "null_rows": {int(k): int(v) for k, v in zip(nr["null_cols_count"], nr["row_count"])}}


def reference(frames, args):
    return {"duplicates": len(frames.main) - len(frames.kept),
            "null_rows": {int(k): int(v) for k, v in
                          frames.kept.isna().sum(axis=1).value_counts().items()}}


def compare(ans, ref, tolerances, args):
    return [exact(k, ans[k], ref[k]) for k in ("duplicates", "null_rows")]
