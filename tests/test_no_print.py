"""Tier-1 wiring for graftcheck's GC007: library modules must not call
``print()`` (module loggers own diagnostics) or ``logging.basicConfig()``
(the importing application owns the root logger).  ``__main__``-guarded
blocks are entrypoints and exempt (e.g. the backend probe's stdout
handshake protocol)."""

import ast
import os
import textwrap

from tools.graftcheck.engine import iter_py_files
from tools.graftcheck.rules.gc007_no_print import check_tree

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_package_is_print_free():
    violations = []
    for path in iter_py_files([os.path.join(REPO, "anovos_tpu")]):
        with open(path) as f:
            tree = ast.parse(f.read(), filename=path)
        violations += [f"{os.path.relpath(path, REPO)}:{lineno}: {msg}" for lineno, msg in check_tree(tree)]
    assert not violations, "\n".join(
        ["library print()/basicConfig() found — route through module loggers:"]
        + violations
    )


def test_checker_flags_and_allowlists():
    """The checker itself: flags library print/basicConfig, allowlists the
    __main__ guard, and ignores prints inside string literals."""
    found = check_tree(ast.parse(textwrap.dedent("""\
        import logging
        logging.basicConfig(level=logging.INFO)
        def f():
            print("library chatter")
        CODE = "print('inside a string: not a call')"
        if __name__ == "__main__":
            print("cli output: allowed")
    """)))
    assert len(found) == 2, found
    lines = sorted(l for l, _ in found)
    assert lines == [2, 4]
