"""Seconds of the ``runtime/init`` row(s) of the fresh pass: ``init_runtime``
(the backend's start where nothing touched it before, the listing of the
compile cache's directory, the mesh).  0.0 where the runtime was up before the
pass; nothing where the program records no such row."""

from benchmark.harness import setup


def read(run):
    return setup.runtime_seconds(run)
