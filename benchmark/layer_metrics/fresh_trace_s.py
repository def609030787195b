"""Summed seconds of the fresh pass's ``compile/trace`` rows: the Python of
every program run once on tracers (thread-seconds: nodes trace side by side)."""

from benchmark.harness import setup


def read(run):
    return setup.stage_seconds(run, "trace")
