"""Summed seconds of the fresh pass's ``compile/load`` rows: the programs the
persistent compile cache held, read and deserialized (thread-seconds)."""

from benchmark.harness import setup


def read(run):
    return setup.stage_seconds(run, "load")
