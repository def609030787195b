"""What several per-layer readers read from a pass's run manifest."""

from __future__ import annotations

from typing import Optional


def dag_span(p: dict) -> Optional[float]:
    """Seconds from the scheduler's start to the end of its last node: the
    largest ``end_s`` of ``scheduler.nodes`` in the pass's manifest."""
    nodes = (p["manifest"].get("scheduler") or {}).get("nodes") or {}
    return max((n["end_s"] for n in nodes.values()), default=None)


def median_pass(passes: list) -> Optional[dict]:
    """The pass whose wall is the (lower) median of ``passes``."""
    ordered = sorted(passes, key=lambda p: p["wall_s"])
    return ordered[(len(ordered) - 1) // 2] if ordered else None


def slowest_blocks(passes: list, top: int = 10) -> list:
    """``[[block, seconds], ...]`` of the median pass, slowest first."""
    p = median_pass(passes)
    blocks = (p["manifest"].get("block_seconds") or {}) if p else {}
    return [[k, v] for k, v in sorted(blocks.items(), key=lambda kv: -kv[1])[:top]]
