"""Seconds of CPU the whole process spent inside the ``dag`` phase of the
window's median pass (``usage.proc_cpu_s`` of the row: user + system of every
thread, native ones too).  Over ``dag_s`` it is the cores the DAG kept busy,
where ``overlap_x`` counts the threads that were inside a block.  Nothing
where the row carries no usage (a program from before it)."""

from benchmark.harness import phases
from benchmark.harness.manifest import median_pass


def read(run):
    row = phases.one(phases.rows(median_pass(run["passes"])), "dag")
    return ((row or {}).get("usage") or {}).get("proc_cpu_s")
