"""Seconds of what the autoencoder's node does around its fit in the
window's median pass: the stage rows ``ae/prep`` (stack, moments, median,
fill and standardise), ``ae/apply`` (the encoder over every row and the
hand-over of the latent columns to the table) and ``ae/save`` (``model.npz``
and ``history.csv``), summed.  Nothing where a pass has none of them."""

from benchmark.harness import phases
from benchmark.harness.manifest import median_pass


def read(run):
    found = [r for r in phases.rows(median_pass(run["passes"])) if r["name"] in ("ae/prep", "ae/apply", "ae/save")]
    return phases.seconds(found) if found else None
