"""Shared demo/benchmark material for the serving subsystem.

One synthetic income-shaped dataset and one FULL-COVERAGE transformer
chain (every servable family fires at least once), used by two
consumers that must agree on shape: the ``python -m anovos_tpu.serving
smoke`` CLI and ``tools/chaos_run.py --scenario serve-fault``.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np
import pandas as pd

__all__ = ["demo_frame", "DEMO_CHAIN", "build_demo_bundle"]


def demo_frame(rows: int = 2000, seed: int = 7) -> pd.DataFrame:
    """Income-shaped synthetic rows with nulls in both planes."""
    rng = np.random.default_rng(seed)
    df = pd.DataFrame({
        "age": rng.normal(40, 9, rows).round(1),
        "fnlwgt": rng.normal(2e5, 4e4, rows).round(0),
        "hours": rng.integers(1, 99, rows).astype(float),
        "workclass": rng.choice(["private", "gov", "self", "never"], rows),
        "education": rng.choice(["hs", "college", "ba", "ms", "phd"], rows),
        "label": rng.choice(["0", "1"], rows),
    })
    df.loc[rng.choice(rows, rows // 20, replace=False), "age"] = np.nan
    df.loc[rng.choice(rows, rows // 25, replace=False), "workclass"] = None
    return df


# every servable family at least once; later stages consume earlier
# stages' outputs (fnlwgt_binned) so the chain-threading contract is
# exercised, not just per-stage state
DEMO_CHAIN: List[Tuple[str, dict]] = [
    ("imputation_MMM", {"list_of_cols": ["age", "workclass"],
                        "method_type": "median"}),
    ("attribute_binning", {"list_of_cols": ["fnlwgt"], "bin_size": 8,
                           "output_mode": "append"}),
    ("outlier_categories", {"list_of_cols": ["education"], "coverage": 0.9,
                            "max_category": 5}),
    ("cat_to_num_supervised", {"list_of_cols": ["workclass"],
                               "label_col": "label", "event_label": "1",
                               "output_mode": "append"}),
    ("cat_to_num_unsupervised", {"list_of_cols": ["workclass", "education"],
                                 "method_type": "label_encoding"}),
    ("z_standardization", {"list_of_cols": ["age"]}),
    ("IQR_standardization", {"list_of_cols": ["hours"],
                             "output_mode": "append"}),
    ("normalization", {"list_of_cols": ["fnlwgt"]}),
    ("boxcox_transformation", {"list_of_cols": ["hours"]}),
    ("feature_transformation", {"list_of_cols": ["fnlwgt_binned"],
                                "method_type": "sq", "output_mode": "append"}),
]


def build_demo_bundle(cache_dir: str, rows: int = 2000, seed: int = 7) -> str:
    """Fit the demo chain and commit the bundle; returns its version."""
    from anovos_tpu.serving.bundle import fit_bundle, save_bundle
    from anovos_tpu.shared.table import Table

    idf = Table.from_pandas(demo_frame(rows, seed))
    bundle = fit_bundle(idf, DEMO_CHAIN, source="serving-demo")
    return save_bundle(bundle, cache_dir)
