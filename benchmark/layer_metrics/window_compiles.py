"""Compiles inside the window, summed over its passes (manifest
``compile_census``); anything but 0 means a shape was not warmed up."""


def read(run):
    passes = run["passes"] + ([run["traced"]] if run.get("traced") else [])
    if not passes:
        return None
    return sum((p["manifest"].get("compile_census") or {}).get("compiles_total", 0) for p in passes)
