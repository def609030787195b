"""CLI wrapper for the multi-chip dry run.

``__graft_entry__.dryrun_multichip(n)`` is the driver's entry point; this
wrapper makes the same gate runnable by hand::

    python -m tools.dryrun_multichip            # 8 virtual devices
    python -m tools.dryrun_multichip --devices 4
    python -m tools.dryrun_multichip --executor-only

It builds an (data x model) mesh over N virtual CPU devices, compiles +
executes the flagship kernels sharded, and — since round 8 — runs the
collective-aware concurrent-executor pass: the synthetic pipeline once per
executor mode, asserting byte-identical artifacts, >= 2 nodes concurrently
in flight, and concurrent wall <= sequential wall on the same box.

Must run in a FRESH process (the virtual-device count is latched at
backend init).
"""

from __future__ import annotations

import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="multi-chip dry run: sharded kernels + the concurrent-"
                    "executor parity/overlap gate on N virtual devices")
    ap.add_argument("--devices", type=int, default=8,
                    help="virtual CPU device count (default 8)")
    ap.add_argument("--executor-only", action="store_true",
                    help="skip the kernel dry run; only the executor pass")
    ns = ap.parse_args(argv)

    import __graft_entry__ as entry

    if ns.executor_only:
        # same backend forcing as the full dry run, without the kernels
        jax = entry.force_virtual_devices(ns.devices)
        from anovos_tpu.shared.runtime import init_runtime

        init_runtime(devices=jax.devices()[: ns.devices])
        entry.executor_pass()
    else:
        entry.dryrun_multichip(ns.devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
