"""From the end of the ``dag`` phase to the end of the root span ``run`` in
the window's median pass: the drain of the queued table writes, the
manifest, the writer's close, the final dataset."""

from benchmark.harness import phases
from benchmark.harness.manifest import median_pass


def read(run):
    rows = phases.rows(median_pass(run["passes"]))
    root, dag = phases.one(rows, "run", parent=None), phases.one(rows, "dag")
    return root["end_s"] - dag["end_s"] if root and dag else None
