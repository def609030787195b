"""The package's boundary and its layer map, as a rule that fails.

``anovos_tpu`` imports nothing of the repo around it (tools, tests, the
benchmark, the entry scripts), and inside it a layer imports only from the
layers below:

    version < obs < shared < cache, resilience < ops < parallel < models < the rest

Read from the source with ``ast`` (function-local imports included), so the
test loads nothing of the package.  The upward edges that exist today are
listed in ``KNOWN_UPWARD``: a case fails on an edge that is not listed and on
a listed edge that is gone, so the list can only shrink.
"""

import ast
import functools
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = "anovos_tpu"

OUTSIDE = {"tools", "bench", "benchmark", "tests", "chip_smoke", "__graft_entry__"}

ORDER = [("version",), ("obs",), ("shared",), ("cache", "resilience"), ("ops",), ("parallel",), ("models",)]
RANK = {layer: i for i, layers in enumerate(ORDER) for layer in layers}

# (importer, imported), both below ``anovos_tpu.``
KNOWN_UPWARD = {
    ("obs.manifest", "cache.fingerprint"),
    ("obs.telemetry", "resilience.policy"),
    ("obs.telemetry", "resilience.chaos"),
    ("obs.telemetry", "resilience.failover"),
    ("obs.telemetry", "data_ingest.guard"),
    ("shared.artifact_store", "cache.capture"),
    ("shared.host_pool", "parallel.scheduler"),
    ("ops.streaming", "data_ingest.guard"),
    ("ops.streaming", "data_ingest.prefetch"),
    ("ops.streaming", "data_ingest.data_ingest"),
}


def _is_module(dotted: str) -> bool:
    path = os.path.join(REPO, *dotted.split("."))
    return os.path.isfile(path + ".py") or os.path.isfile(os.path.join(path, "__init__.py"))


@functools.lru_cache(maxsize=None)
def _imports():
    """[(importing module, imported module, line)] over every file of the package."""
    out = []
    for dirpath, dirs, files in os.walk(os.path.join(REPO, PACKAGE)):
        dirs[:] = sorted(d for d in dirs if d != "__pycache__")
        for fname in sorted(f for f in files if f.endswith(".py")):
            path = os.path.join(dirpath, fname)
            mod = os.path.relpath(path, REPO)[:-3].replace(os.sep, ".")
            mod = mod[: -len(".__init__")] if mod.endswith(".__init__") else mod
            with open(path) as f:
                tree = ast.parse(f.read(), filename=path)
            for node in ast.walk(tree):
                if isinstance(node, ast.Import):
                    out += [(mod, a.name, node.lineno) for a in node.names]
                elif isinstance(node, ast.ImportFrom):
                    # the package has no relative imports; one would be resolved here
                    base = ".".join(mod.split(".")[: -node.level] + ([node.module] if node.module else [])) \
                        if node.level else node.module
                    for a in node.names:
                        sub = f"{base}.{a.name}"
                        out.append((mod, sub if _is_module(sub) else base, node.lineno))
    return out


def _layer(mod: str) -> str:
    parts = mod.split(".")
    return parts[1] if len(parts) > 1 else ""


def test_the_package_imports_nothing_outside_itself():
    reaching_out = [f"{mod}:{line} imports {target}" for mod, target, line in _imports()
                    if target.split(".")[0] in OUTSIDE]
    assert not reaching_out, "\n".join(reaching_out)


@pytest.mark.parametrize("layer", [name for names in ORDER[1:] for name in names])
def test_a_layer_imports_only_from_below(layer):
    upward = {}
    for mod, target, line in _imports():
        if _layer(mod) != layer or target.split(".")[0] != PACKAGE or _layer(target) in ("", layer):
            continue
        if RANK.get(_layer(target), len(ORDER)) >= RANK[layer]:
            edge = (mod[len(PACKAGE) + 1:], target[len(PACKAGE) + 1:])
            upward.setdefault(edge, []).append(line)
    known = {e for e in KNOWN_UPWARD if e[0].split(".")[0] == layer}
    new = {e: lines for e, lines in upward.items() if e not in known}
    assert not new, f"{layer} imports from a layer that is not below it: {new}"
    gone = known - set(upward)
    assert not gone, f"listed in KNOWN_UPWARD and no longer in the source (take them off the list): {sorted(gone)}"
