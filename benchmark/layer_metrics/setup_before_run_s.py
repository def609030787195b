"""Seconds of ``process/caller`` in the fresh pass's manifest: from the end of
the program's imports to the start of its first pass, which is the caller's
own work (here the data made from ``--seed``): the share of ``setup_s`` that
is the benchmark's, so that what is left can be held against the program.  0.0
where the fresh pass was not its process's first; nothing where the manifest has
no ``process`` section."""

from benchmark.harness import setup


def read(run):
    return setup.process_seconds(run, "process/caller")
