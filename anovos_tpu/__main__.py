"""``python -m anovos_tpu <config.yaml> <run_type> [--resume]``
(reference: anovos/__main__.py:5)."""

import logging
import os
import sys

if __name__ == "__main__":
    # --resume re-runs a killed config, restoring crash-committed node
    # results from the cache store (anovos_tpu.cache); it needs a cache root
    resume = "--resume" in sys.argv
    if resume:
        sys.argv = [a for a in sys.argv if a != "--resume"]
        os.environ.setdefault("ANOVOS_TPU_CACHE", ".anovos_cache")

    from anovos_tpu import workflow

    # entrypoint-only root-logger setup: library modules must never call
    # logging.basicConfig (the importing application owns the root logger)
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(name)s %(message)s")
    workflow.run(sys.argv[1], sys.argv[2] if len(sys.argv) > 2 else "local",
                 resume=resume)
