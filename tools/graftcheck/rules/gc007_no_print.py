"""GC007 — print()/logging.basicConfig() in library code.

Library output goes through module loggers (the importing application owns stdout
and the root logger); ``logging.basicConfig`` belongs in the entrypoints
(``main.py`` / ``anovos_tpu/__main__.py``) only.  Calls inside a module's
top-level ``if __name__ == "__main__":`` block are allowlisted — that
block IS an entrypoint (CLI protocols like the backend probe's stdout
handshake live there), and prints inside string literals never
false-positive because the check is AST-based.
"""

from __future__ import annotations

import ast
from typing import List, Tuple

from tools.graftcheck.registry import FileContext, Rule, register


def main_guard_ranges(tree: ast.Module) -> List[Tuple[int, int]]:
    """Line ranges of top-level ``if __name__ == "__main__":`` bodies."""
    out = []
    for node in tree.body:
        if not isinstance(node, ast.If):
            continue
        t = node.test
        is_guard = (
            isinstance(t, ast.Compare)
            and isinstance(t.left, ast.Name) and t.left.id == "__name__"
            and len(t.comparators) == 1
            and isinstance(t.comparators[0], ast.Constant)
            and t.comparators[0].value == "__main__"
        )
        if is_guard:
            out.append((node.lineno, max(
                n.end_lineno or n.lineno
                for n in ast.walk(node) if hasattr(n, "end_lineno"))))
    return out


def check_nodes(tree: ast.Module) -> List[Tuple[ast.Call, str]]:
    """[(offending call node, message), …] — THE implementation; the rule
    and ``check_tree`` are thin views over it."""
    guards = main_guard_ranges(tree)

    def allowlisted(lineno: int) -> bool:
        return any(lo <= lineno <= hi for lo, hi in guards)

    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or allowlisted(node.lineno):
            continue
        f_ = node.func
        if isinstance(f_, ast.Name) and f_.id == "print":
            out.append((node, "print() in library code — use the module logger"))
        elif (
            isinstance(f_, ast.Attribute) and f_.attr == "basicConfig"
            and isinstance(f_.value, ast.Name) and f_.value.id == "logging"
        ):
            out.append((node, "logging.basicConfig() in library code — "
                              "root-logger setup belongs in entrypoints"))
    return out


def check_tree(tree: ast.Module) -> List[Tuple[int, str]]:
    """[(lineno, message), …] — the view ``tests/test_no_print.py`` reads."""
    return [(node.lineno, msg) for node, msg in check_nodes(tree)]


@register
class NoPrintRule(Rule):
    id = "GC007"
    title = "print()/logging.basicConfig() outside __main__ guards in library code"

    def applies(self, relpath: str) -> bool:
        return relpath.startswith("anovos_tpu/") or "gc007" in relpath

    def check(self, ctx: FileContext):
        for node, msg in check_nodes(ctx.tree):
            yield ctx.finding(self.id, node, msg)
