"""Finding a file of the benchmark by the name ``BENCHMARK.json`` gives it."""

from __future__ import annotations

import importlib.util
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` by its file, whatever characters the name has."""
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"benchmark_{kind}_{name}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
