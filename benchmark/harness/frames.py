"""The tables a cell's pipeline reads, as plain pandas frames for the
reference side of ``correct``: found through the cell's own pipeline YAML
(re-rooted at the run's data), read with pandas, never through the program.
Each frame is read once, when a check first asks for it."""

from __future__ import annotations

import glob
import os
from functools import cached_property

import pandas as pd


def _read(spec: dict) -> pd.DataFrame:
    """One ``read_dataset`` + column edits section of the YAML (delete, rename)."""
    rd = spec["read_dataset"]
    files = sorted(glob.glob(os.path.join(rd["file_path"], "*." + rd["file_type"])))
    read = pd.read_parquet if rd["file_type"] == "parquet" else pd.read_csv
    df = pd.concat([read(f) for f in files], ignore_index=True)
    df = df.drop(columns=spec.get("delete_column") or [])
    ren = spec.get("rename_column") or {}
    return df.rename(columns=dict(zip(ren.get("list_of_cols", []), ren.get("list_of_newcols", []))))


class Frames:
    def __init__(self, pipeline: dict):
        self.pipeline = pipeline  # the parsed pipeline YAML, paths re-rooted

    @cached_property
    def main(self) -> pd.DataFrame:
        """The input table after the YAML's column edits."""
        return _read(self.pipeline["input_dataset"])

    @cached_property
    def kept(self) -> pd.DataFrame:
        """``main`` without the rows ``duplicate_detection`` drops when it
        treats: every repeat of an earlier row, its ``drop_cols`` aside."""
        dd = (self.pipeline.get("quality_checker") or {}).get("duplicate_detection")
        if not (dd and dd.get("treatment")):
            return self.main
        return self.main[~self.main.drop(columns=dd.get("drop_cols") or []).duplicated()]

    @cached_property
    def source(self) -> pd.DataFrame:
        """The drift baseline."""
        return _read(self.pipeline["drift_detector"]["drift_statistics"]["source_dataset"])

    @cached_property
    def periods(self) -> list:
        """The stability-index period slices, in order."""
        si = self.pipeline["drift_detector"]["stability_index"]
        return [_read(si[k]) for k in sorted(si, key=lambda k: (len(k), k)) if k.startswith("dataset")]
