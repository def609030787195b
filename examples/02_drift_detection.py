"""Drift + stability in one script: split the dataset, perturb the target
half, measure PSI/JSD/HD/KS, then score a 3-run stability history.

Mirrors the reference's drift walkthrough (examples/guides; reference
drift_detector.statistics + stability_index): the whole per-side
histogramming runs as ONE device program per side.

    python examples/02_drift_detection.py
"""

import sys
import tempfile
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from anovos_tpu.data_ingest.synthetic import load_income  # noqa: E402
from anovos_tpu.drift_stability import drift_detector, stability  # noqa: E402
from anovos_tpu.shared import Table  # noqa: E402


def main() -> None:
    df = load_income().drop(columns=["dt_1", "dt_2", "empty", "logfnl"])
    n = len(df)
    source = df.iloc[: n // 2].reset_index(drop=True)
    target = df.iloc[n // 2 :].reset_index(drop=True).copy()
    # inject drift: ages shift up, one education level doubles its share
    target["age"] = target["age"] + 6
    mask = target.sample(frac=0.15, random_state=0).index
    target.loc[mask, "education"] = "Bachelors"

    with tempfile.TemporaryDirectory() as d:
        odf = drift_detector.statistics(
            Table.from_pandas(target),
            Table.from_pandas(source),
            method_type="all",  # PSI + JSD + HD + KS
            use_sampling=False,
            source_path=d,
        )
    print("— drift statistics (perturbed columns should flag) —")
    print(odf.to_string(index=False))

    # stability: three synthetic runs of the same metric set
    rng = np.random.default_rng(1)
    runs = []
    for i in range(3):
        jitter = source[["age", "hours-per-week", "capital-gain"]].copy()
        jitter += rng.normal(0, 0.01 * jitter.std(ddof=0), jitter.shape)
        runs.append(Table.from_pandas(jitter))
    with tempfile.TemporaryDirectory() as d:
        si = stability.stability_index_computation(*runs, appended_metric_path=d)
    print("\n— stability index —")
    print(si.to_string(index=False))


if __name__ == "__main__":
    # entrypoint-only root-logger setup: surface the per-block INFO timing
    # lines while the demo runs (library code no longer calls basicConfig)
    import logging

    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(name)s %(message)s")
    main()
