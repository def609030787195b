"""Basic statistics in ~20 lines: load → Table → fused stats generator.

Mirrors the reference's getting-started flow (examples/guides): every stats
function dispatches against the SAME fused device program, so running all
seven costs two compiles, not fourteen.

    python examples/01_basic_stats.py
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from anovos_tpu.data_ingest.synthetic import load_income  # noqa: E402
from anovos_tpu.data_analyzer import stats_generator as sg  # noqa: E402
from anovos_tpu.shared import Table  # noqa: E402


def main() -> None:
    df = load_income().drop(columns=["dt_1", "dt_2", "empty", "logfnl"])
    t = Table.from_pandas(df)
    print(f"loaded {t.nrows} rows × {len(t.col_names)} cols\n")

    print("— global summary —")
    print(sg.global_summary(t).to_string(index=False))

    for name, fn in [
        ("central tendency", sg.measures_of_centralTendency),
        ("dispersion", sg.measures_of_dispersion),
        ("percentiles", sg.measures_of_percentiles),
        ("counts", sg.measures_of_counts),
        ("cardinality", sg.measures_of_cardinality),
        ("shape", sg.measures_of_shape),
    ]:
        print(f"\n— {name} —")
        print(fn(t).head(8).to_string(index=False))


if __name__ == "__main__":
    # entrypoint-only root-logger setup: surface the per-block INFO timing
    # lines while the demo runs (library code no longer calls basicConfig)
    import logging

    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(name)s %(message)s")
    main()
