"""Seconds of CPU the whole process spent inside the ``ingest`` phase of the
window's median pass: user + system of every thread, Arrow's and XLA's own
among them (``usage.proc_cpu_s`` of the row, one ``getrusage`` reading at each
of its ends).  Over ``ingest_s`` it is the cores ingest kept busy.  Nothing
where the row carries no usage (a program from before it)."""

from benchmark.harness import phases
from benchmark.harness.manifest import median_pass


def read(run):
    row = phases.one(phases.rows(median_pass(run["passes"])), "ingest")
    return ((row or {}).get("usage") or {}).get("proc_cpu_s")
