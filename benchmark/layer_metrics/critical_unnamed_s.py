"""Seconds of the median pass's critical path that no stage row names: over
the nodes of ``scheduler.critical_path``, each node row's seconds outside the
union of the rows whose ``parent`` is that node (clipped to it), summed.  What
a ``perf_opt`` on the critical path still cannot put a name to.  A node of the
path with no row of its own (restored from the node cache) counts whole.
Nothing where no row of the pass carries ``usage`` (a program from before the
stage rows: nearly its whole path would read as unnamed)."""

from benchmark.harness import phases
from benchmark.harness.manifest import median_pass


def uncovered(node: dict, kids: list) -> float:
    """Seconds of ``node`` outside the union of ``kids``' intervals."""
    covered, reach = 0.0, node["start_s"]
    for k in sorted(kids, key=lambda k: k["start_s"]):
        start, end = max(k["start_s"], reach), min(k["end_s"], node["end_s"])
        if end > start:
            covered += end - start
            reach = end
    return node["end_s"] - node["start_s"] - covered


def read(run):
    p = median_pass(run["passes"])
    rows = phases.rows(p)
    if not any(r.get("usage") for r in rows):
        return None
    scheduler = p["manifest"].get("scheduler") or {}
    nodes = {r["name"]: r for r in rows if r["parent"] == "dag"}
    total = 0.0
    for name in scheduler.get("critical_path") or []:
        if name in nodes:
            total += uncovered(nodes[name], [r for r in rows if r["parent"] == name])
        else:
            total += (scheduler.get("nodes") or {}).get(name, {}).get("dur_s") or 0.0
    return total
