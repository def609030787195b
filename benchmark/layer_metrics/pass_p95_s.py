"""95th percentile of the window's pass walls; nothing where the window
holds fewer than twenty passes."""

import statistics


def read(run):
    walls = [p["wall_s"] for p in run["passes"]]
    return statistics.quantiles(walls, n=20)[-1] if len(walls) >= 20 else None
