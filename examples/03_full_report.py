"""The full pipeline: YAML config → workflow runner → ml_anovos_report.html.

This is exactly what `python main.py config/configs_basic.yaml local` does —
the reference's demo flow (demo/run_anovos_demo.sh) — run in-process so you
can step through it.  The seeded income dataset is generated under
``data/income_dataset`` on first use.

    python examples/03_full_report.py [output_dir]
"""

import os
import sys
from pathlib import Path

import yaml

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from anovos_tpu import workflow  # noqa: E402
from anovos_tpu.data_ingest.synthetic import generate, rebase_config  # noqa: E402


def main() -> None:
    out = Path(sys.argv[1]).resolve() if len(sys.argv) > 1 else Path.cwd() / "demo_output"
    out.mkdir(parents=True, exist_ok=True)

    with open(REPO / "config" / "configs_basic.yaml") as f:
        cfg = rebase_config(yaml.safe_load(f))  # data/... → absolute: we chdir below
    generate()

    os.chdir(out)
    workflow.main(cfg, "local")
    for name in ("ml_anovos_report.html", "basic_report.html"):
        p = out / "report_stats" / name
        if p.exists():
            print(f"report written: {p}")


if __name__ == "__main__":
    # entrypoint-only root-logger setup: surface the per-block INFO timing
    # lines while the demo runs (library code no longer calls basicConfig)
    import logging

    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(name)s %(message)s")
    main()
