"""CLI entry (reference: src/main/main.py:6-13):
``python main.py <config.yaml> <run_type> [auth_key_json] [--resume]``.

Runs ``workflow.run`` in this process, on the devices ``jax.devices()``
reports (``JAX_PLATFORMS=cpu`` in the environment asks for the CPU)."""

import json
import logging
import os
import sys

if __name__ == "__main__":
    # --resume: re-run a killed config against the same output directory;
    # nodes whose results were committed to the cache store before the
    # crash restore instead of executing (anovos_tpu.cache).  Resume needs
    # a cache root — default one next to the outputs when unset.
    resume = "--resume" in sys.argv
    if resume:
        sys.argv = [a for a in sys.argv if a != "--resume"]
        os.environ.setdefault("ANOVOS_TPU_CACHE", ".anovos_cache")
    if len(sys.argv) < 2:
        sys.exit("usage: python main.py <config.yaml> [run_type] "
                 "[auth_key_json] [--resume]")

    # entrypoint-only root-logger setup: library modules must never call
    # logging.basicConfig (the importing application owns the root logger)
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(name)s %(message)s")

    from anovos_tpu import workflow

    config_path = sys.argv[1]
    run_type = sys.argv[2] if len(sys.argv) > 2 else "local"
    if len(sys.argv) > 3:
        # reference main.py:10 passes a JSON dict; anything else (bare token,
        # JSON scalar) is wrapped so workflow.run always receives a dict
        try:
            auth_key_val = json.loads(sys.argv[3])
        except json.JSONDecodeError:
            auth_key_val = {"auth_key": sys.argv[3]}
        if not isinstance(auth_key_val, dict):
            auth_key_val = {"auth_key": sys.argv[3]}
    else:
        auth_key_val = {}
    workflow.run(config_path, run_type, auth_key_val, resume=resume)
