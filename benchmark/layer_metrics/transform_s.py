"""Seconds of the transformer nodes of the window's median pass: the spans of
the scheduler's spine nodes named ``transformers/<function>`` (rows of the
manifest's ``phases`` under ``dag``), summed; each holds its function's
``transform/fit`` and ``transform/apply`` spans and the queueing of its
intermediate write.  Nothing where a pass runs no transformer (the ``stats``
mixes) or where the manifest's ``phases`` hold no scheduler node."""

from benchmark.harness import phases
from benchmark.harness.manifest import median_pass


def read(run):
    found = [r for r in phases.rows(median_pass(run["passes"]))
             if r["parent"] == "dag" and r["name"].startswith("transformers/")]
    return phases.seconds(found) if found else None
