"""Summed seconds of the fresh pass's ``compile/build`` rows: the programs the
compile cache did not hold (or is never given: those that compile in under
its threshold), compiled by the backend (thread-seconds)."""

from benchmark.harness import setup


def read(run):
    return setup.stage_seconds(run, "build")
