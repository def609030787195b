"""What the readers of set-up read from the fresh pass's run manifest: the
``process`` rows (what the process did before its first pass), the
``compile/*`` rows of ``phases`` (a program's trace, lowering, load from the
compile cache or build, under the row that waited for it) and
``runtime/init``.  A manifest from before them (no ``process`` section, a
census without ``built_programs``) gives every reader nothing."""

from __future__ import annotations

from typing import Optional

from benchmark.harness import phases


def _fresh(run: dict) -> dict:
    return (run.get("fresh") or {}).get("manifest") or {}


def process_seconds(run: dict, name: str) -> Optional[float]:
    """Seconds of the one ``process`` row of that name.  0.0 where the fresh
    pass was not its process's first (a rehearsal in a long-lived process:
    nothing came before that pass on its account); None where the manifest has
    no ``process`` section."""
    process = _fresh(run).get("process")
    if not process:
        return None
    found = [r for r in process.get("rows") or [] if r["name"] == name]
    if len(found) == 1:
        return float(found[0]["end_s"] - found[0]["start_s"])
    return 0.0 if process.get("pass_index") and not found else None


def runtime_seconds(run: dict) -> Optional[float]:
    """Seconds of the fresh pass's ``runtime/init`` rows; 0.0 where the
    runtime was up before the pass, None where the program records no such row
    (known by its manifest having no ``process`` section)."""
    man = _fresh(run)
    if not man.get("process"):
        return None
    return float(phases.seconds([r for r in man.get("phases") or [] if r["name"] == "runtime/init"]))


def stage_rows(run: dict, *stages: str) -> Optional[list]:
    """The fresh pass's ``compile/<stage>`` rows (all four stages where none is
    named); empty where the pass had none of them, None where the program
    records no stages."""
    man = _fresh(run)
    if "built_programs" not in (man.get("compile_census") or {}):
        return None
    names = {"compile/" + s for s in stages or ("trace", "lower", "load", "build")}
    return [r for r in man.get("phases") or [] if r["name"] in names]


def stage_seconds(run: dict, stage: str) -> Optional[float]:
    """Summed seconds of one stage's rows: thread-seconds, nodes compile side by side."""
    found = stage_rows(run, stage)
    return None if found is None else float(phases.seconds(found))


def union_seconds(found: list) -> float:
    """Seconds covered by at least one of the rows, whatever their threads."""
    covered, reach = 0.0, float("-inf")
    for start, end in sorted((r["start_s"], r["end_s"]) for r in found):
        if end > reach:
            covered += end - max(start, reach)
            reach = end
    return covered
