"""Reference implementations that tests compare the program against: plain
pandas / numpy, independent of ``anovos_tpu``."""

import numpy as np
import pandas as pd


def pandas_reference_psi(src: pd.DataFrame, tgt: pd.DataFrame, bin_size: int) -> dict:
    """The reference algorithm, column at a time (host single-core)."""
    out = {}
    for col in src.columns:
        s, t = src[col], tgt[col]
        if pd.api.types.is_numeric_dtype(s):
            lo, hi = s.min(), s.max()
            cuts = [lo + j * (hi - lo) / bin_size for j in range(1, bin_size)]
            sb = np.searchsorted(cuts, s.to_numpy(), side="left")
            tb = np.searchsorted(cuts, t.to_numpy(), side="left")
            p = np.bincount(sb[~s.isna()], minlength=bin_size) / len(s)
            q = np.bincount(np.clip(tb[~t.isna()], 0, bin_size - 1), minlength=bin_size) / len(t)
        else:
            cats = sorted(set(s.dropna().unique()) | set(t.dropna().unique()))
            p = s.value_counts(normalize=False).reindex(cats).fillna(0).to_numpy() / len(s)
            q = t.value_counts(normalize=False).reindex(cats).fillna(0).to_numpy() / len(t)
        p = np.where(p <= 0, 1e-4, p)
        q = np.where(q <= 0, 1e-4, q)
        out[col] = float(((p - q) * np.log(p / q)).sum())
    return out
