"""Seconds of the autoencoder's fit in the window's median pass: the ``ae/fit``
stage row (under the node ``transformers/autoencoder_latentFeatures``), from
the first slice of the standardised block to the fetch of the history, which
waits for the last step.  Nothing where a pass fits no autoencoder."""

from benchmark.harness import phases
from benchmark.harness.manifest import median_pass


def read(run):
    found = [r for r in phases.rows(median_pass(run["passes"])) if r["name"] == "ae/fit"]
    return phases.seconds(found) if found else None
