"""What decides ``correct`` for a pipeline pass.  Here: the clean-run check on
a pass's manifest and artifacts (a copy of ``chip_smoke.py``'s ``check_run``
as PR 22 left it), the digest that holds every pass of a run to the same
bytes, and the two ways a number is compared.  The comparisons themselves
are files of their own, ``benchmark/checks/<name>.py``, which a traffic mix
names under ``compare`` with their arguments:

    read(out_dir, traffic, args) -> answers    what the pass left on disk
    reference(frames, args) -> answers         plain float64 pandas (harness/frames.py)
    compare(answers, reference, tolerances, args) -> rows

Every number compared is a row ``{"name", "value", "limit", "ok", "detail"}``;
a run prints them all.
"""

from __future__ import annotations

import fnmatch
import glob
import hashlib
import os

import numpy as np
import pandas as pd

from benchmark.harness.names import load_module

RESILIENCE_COUNTS = ("retries", "timeout_retries", "failover_retries", "timeout_escalations")


def clean_run(manifest: dict, out_dir: str, traffic: dict, platform: str) -> list:
    """Reasons why a pass does not count (empty when it does): a backend other
    than ``platform``, a degraded section, a retry, a failover, or a missing
    artifact of those the traffic mix says a pass must leave."""
    res = manifest.get("resilience") or {}
    bad = []
    if manifest.get("backend") != platform:
        bad.append(f"manifest backend {manifest.get('backend')!r}, expected {platform!r}")
    degraded = res.get("degraded_sections") or res.get("degraded")
    if degraded:
        bad.append(f"degraded sections: {degraded}")
    if res.get("failovers"):
        bad.append(f"backend failovers: {res['failovers']}")
    for key in RESILIENCE_COUNTS:
        if res.get(key):
            bad.append(f"resilience.{key} = {res[key]}")
    for rel in list(traffic["tables"].values()) + list(traffic["artifacts"]):
        if not glob.glob(os.path.join(out_dir, rel)):
            bad.append(f"missing artifact {rel}")
    return bad


def digest(out_dir: str, traffic: dict) -> dict:
    """sha256 of every file a pass left, by relative path, but for those the
    traffic mix lists as ``not_repeatable`` (the manifest: it holds clocks)."""
    out = {}
    for dirpath, _, files in os.walk(out_dir):
        for name in files:
            rel = os.path.relpath(os.path.join(dirpath, name), out_dir)
            if any(fnmatch.fnmatch(rel, pat) for pat in traffic.get("not_repeatable", ())):
                continue
            with open(os.path.join(dirpath, name), "rb") as f:
                out[rel] = hashlib.sha256(f.read()).hexdigest()
    return out


def table(out_dir: str, rel: str) -> pd.DataFrame:
    path = glob.glob(os.path.join(out_dir, rel))[0]
    return pd.read_parquet(path) if path.endswith(".parquet") else pd.read_csv(path)


def exact(name: str, got, want) -> dict:
    """A count, or a dict of counts: the row is the number of entries that
    differ, against the limit 0."""
    if isinstance(want, dict):
        diff = [k for k in set(want) | set(got) if want.get(k) != got.get(k)]
        return {"name": name, "value": len(diff), "limit": 0, "ok": not diff,
                "detail": ", ".join(f"{k}: {got.get(k)} != {want.get(k)}"
                                    for k in sorted(diff, key=str)[:5])}
    return {"name": name, "value": abs(int(got) - int(want)), "limit": 0,
            "ok": int(got) == int(want), "detail": f"{got} vs {want}"}


def toleranced(name: str, got: pd.Series, want: pd.Series, tol: dict) -> dict:
    """Entry by entry ``|ours - reference| / (atol + rtol * |reference|)``,
    reported by its worst entry against the limit 1.  An entry the pass did
    not write is NaN, and fails."""
    got = got.reindex(want.index).to_numpy(float)
    w = want.to_numpy(float)
    gap = np.abs(got - w)
    with np.errstate(divide="ignore", invalid="ignore"):
        ratio = gap / (tol.get("atol", 0.0) + tol.get("rtol", 0.0) * np.abs(w))
    ratio = np.where(gap == 0, 0.0, np.where(np.isnan(ratio), np.inf, ratio))
    i = int(np.argmax(ratio))
    rel = gap[i] / max(abs(w[i]), 1e-12)
    return {"name": name, "value": float(ratio[i]), "limit": 1.0, "ok": bool(ratio[i] <= 1.0),
            "detail": f"worst {want.index[i]}: {got[i]} vs {w[i]} (rel {rel:.2e})"}


def compare_all(out_dir: str, traffic: dict, frames, tolerances: dict) -> list:
    """The rows of every comparison the traffic mix names, in its order."""
    rows = []
    for name, args in traffic["compare"].items():
        mod = load_module("checks", name)
        rows += mod.compare(mod.read(out_dir, traffic, args), mod.reference(frames, args),
                            tolerances, args)
    return rows
