"""Population stability index of the columns no quality treatment alters,
as the upstream defines it: ten equal-range bins from the SOURCE's min and
max (right-closed), frequencies over the full row count, nulls dropped, an
empty bin counted as 1e-4, natural log.  The target is the table after the
repeats are dropped.  args: ``columns``.  Table: drift_statistics."""

import numpy as np
import pandas as pd

from benchmark.harness.check import table, toleranced


def read(out_dir, traffic, args):
    return table(out_dir, traffic["tables"]["drift_statistics"]).set_index("attribute")["PSI"]


def _psi(src: pd.Series, tgt: pd.Series, bins: int = 10) -> float:
    if pd.api.types.is_numeric_dtype(src):
        s, t = src.to_numpy(float), tgt.to_numpy(float)
        lo, hi = np.nanmin(s), np.nanmax(s)
        cuts = lo + (hi - lo) * np.arange(1, bins) / bins
        p = np.bincount(np.searchsorted(cuts, s[~np.isnan(s)], side="left"), minlength=bins)
        q = np.bincount(np.searchsorted(cuts, t[~np.isnan(t)], side="left"), minlength=bins)
    else:
        keys = sorted(set(src.dropna().unique()) | set(tgt.dropna().unique()))
        p = src.value_counts().reindex(keys).fillna(0).to_numpy()
        q = tgt.value_counts().reindex(keys).fillna(0).to_numpy()
    p, q = p / len(src), q / len(tgt)
    p, q = np.where(p == 0, 1e-4, p), np.where(q == 0, 1e-4, q)
    return float(((p - q) * np.log(p / q)).sum())


def reference(frames, args):
    return pd.Series({c: _psi(frames.source[c], frames.kept[c]) for c in args["columns"]})


def compare(ans, ref, tolerances, args):
    return [toleranced("psi", ans, ref, tolerances["psi"])]
