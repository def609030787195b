"""The largest ``block_seconds`` value of the window's median pass."""

from benchmark.harness.manifest import slowest_blocks


def read(run):
    blocks = slowest_blocks(run["passes"], top=1)
    return blocks[0][1] if blocks else None
