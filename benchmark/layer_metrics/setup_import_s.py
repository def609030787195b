"""Seconds of ``process/import`` in the fresh pass's manifest: from the first
statement of ``anovos_tpu/__init__.py`` to the end of ``workflow.py``'s imports
(pandas, pyarrow, the package; jax too where the caller had not imported it).
0.0 where the fresh pass was not its process's first; nothing where the manifest
has no ``process`` section."""

from benchmark.harness import setup


def read(run):
    return setup.process_seconds(run, "process/import")
