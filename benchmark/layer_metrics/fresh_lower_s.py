"""Summed seconds of the fresh pass's ``compile/lower`` rows: every program's
jaxpr made into an MLIR module (thread-seconds)."""

from benchmark.harness import setup


def read(run):
    return setup.stage_seconds(run, "lower")
