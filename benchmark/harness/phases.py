"""What the per-layer readers of the phase spans read from a pass's run
manifest: ``phases``, one row ``{name, parent, start_s, end_s, thread,
counts}`` per phase span of the pass, seconds from the start of the root
span ``run``.  A manifest without them (a program from before the spans)
gives every reader nothing."""

from __future__ import annotations

from typing import Optional


def rows(p: Optional[dict]) -> list:
    """The phase rows of a pass; empty where its manifest has none."""
    return ((p or {}).get("manifest") or {}).get("phases") or []


def one(phases: list, name: str, parent: str = "run") -> Optional[dict]:
    """The one row of that name under that parent (the children of ``run``
    are one of a kind), or None."""
    found = [r for r in phases if r["name"] == name and r["parent"] == parent]
    return found[0] if len(found) == 1 else None


def inside(phases: list, outer: Optional[dict], *names: str) -> list:
    """The rows of those names that lie within ``outer``: the tree is one
    thread's, so containment in time is descent."""
    if outer is None:
        return []
    return [r for r in phases if r["name"] in names
            and outer["start_s"] <= r["start_s"] and r["end_s"] <= outer["end_s"]]


def seconds(found: list) -> float:
    return sum(r["end_s"] - r["start_s"] for r in found)
