"""Timestamp auto-detection (reference: data_ingest/ts_auto_detection.py).

The reference triages candidate columns by dtype and value length ∈
{4, 6, 8, 10, 13} (``ts_loop_cols_pre`` :554-619), then parses with a
regex/heuristic battery (``regex_date_time_parser`` :51).  Here the triage is
the same but parsing rides the column dictionary: each DISTINCT value is
parsed once on host (pandas' inference + the reference's epoch-length rules)
and conversion maps back through codes; detection stats persist to
``ts_cols_stats.csv`` (ref :735).
"""

from __future__ import annotations

import os
import re
from pathlib import Path
from typing import List, Optional, Tuple

import numpy as np
import pandas as pd

from anovos_tpu.shared.runtime import get_runtime
from anovos_tpu.shared.table import Column, Table, _host_to_column
from anovos_tpu.shared.utils import ends_with

_VALID_LENGTHS = {4, 6, 8, 10, 13}
_MIN_PARSE_FRACTION = 0.8

# ---------------------------------------------------------------------------
# format-detection battery (the reference's regex pattern matrix,
# ts_auto_detection.py:95-260, recast as detect-then-parse: each family is a
# full-match regex + explicit strptime format(s); the family that parses the
# LARGEST fraction of distinct values wins, which also resolves the
# dd/mm-vs-mm/dd ambiguity the reference fixes by always assuming day-first)
_Y = r"(?:19[4-9]\d|20[0-3]\d)"  # 1940-2039 (reference year window)
_y = r"\d\d"
_m = r"(?:1[012]|0?[1-9])"
_d = r"(?:3[01]|[12]\d|0?[1-9])"
_H = r"(?:2[0-4]|[01]?\d)"
_MS = r"[0-5]\d"
_B = (
    r"(?:JAN(?:UARY)?|FEB(?:RUARY)?|MAR(?:CH)?|APR(?:IL)?|MAY|JUNE?|JULY?|"
    r"AUG(?:UST)?|SEP(?:T(?:EMBER)?)?|OCT(?:OBER)?|NOV(?:EMBER)?|DEC(?:EMBER)?)"
)
_TH = r"(?:ST|ND|RD|TH)?"
_TIME = rf"(?:[T ]{_H}:{_MS}(?::{_MS}(?:\.\d+)?)?(?: ?(?:Z|UTC|GMT|[+-]\d{{2}}:?\d{{2}}))?)?"
_SEP = r"[/\.\- ]"

# (name, fullmatch regex, strptime formats to try in order, kwargs)
_FORMAT_MATRIX = [
    ("epoch_s", r"\d{10}", None, {"unit": "s"}),
    ("epoch_ms", r"\d{13}", None, {"unit": "ms"}),
    ("YYYYmmdd", r"(?:19[4-9]\d|20[0-3]\d)(?:1[012]|0[1-9])(?:3[01]|[12]\d|0[1-9])",
     ["%Y%m%d"], {}),
    ("yymmdd", r"\d\d(?:1[012]|0[1-9])(?:3[01]|[12]\d|0[1-9])", ["%y%m%d"], {}),
    ("YYYY", _Y, ["%Y"], {}),
    ("iso", rf"{_Y}-{_m}-{_d}{_TIME}", None, {"iso": True}),
    ("YYYY_mm_dd", rf"{_Y}{_SEP}{_m}{_SEP}{_d}{_TIME}", ["%Y/%m/%d", "%Y.%m.%d", "%Y %m %d"], {}),
    ("dd_mm_YYYY", rf"{_d}{_SEP}{_m}{_SEP}{_Y}{_TIME}", None, {"dayfirst": True}),
    ("mm_dd_YYYY", rf"{_m}{_SEP}{_d}{_SEP}{_Y}{_TIME}", None, {"dayfirst": False}),
    ("dd_mm_yy", rf"{_d}{_SEP}{_m}{_SEP}{_y}", None, {"dayfirst": True}),
    ("mm_dd_yy", rf"{_m}{_SEP}{_d}{_SEP}{_y}", None, {"dayfirst": False}),
    ("dd_mmm_YYYY", rf"{_d}{_TH} ?{_SEP}? ?{_B} ?{_SEP}? ?,? ?'?{_Y}{_TIME}", None, {"dayfirst": True}),
    ("dd_mmm_yy", rf"{_d}{_TH} ?{_SEP}? ?{_B} ?{_SEP}? ?,? ?'?{_y}", None, {"dayfirst": True}),
    ("mmm_dd_YYYY", rf"{_B} ?{_SEP}? ?{_d}{_TH} ?,? ?{_Y}{_TIME}", None, {"dayfirst": False}),
    ("mmm_YYYY", rf"{_B} ?{_SEP} ?{_Y}", None, {"dayfirst": False}),
    ("YYYY_mmm_dd", rf"{_Y} ?{_SEP}? ?{_B} ?{_SEP}? ?{_d}{_TH}", None, {"yearfirst": True}),
]
_COMPILED_MATRIX = [
    (name, re.compile(rx, re.IGNORECASE), fmts, kw) for name, rx, fmts, kw in _FORMAT_MATRIX
]


def _parse_family(s: pd.Series, fmts, kw) -> pd.Series:
    if kw.get("unit"):
        return pd.to_datetime(pd.to_numeric(s, errors="coerce"), unit=kw["unit"], errors="coerce")
    if kw.get("iso"):
        try:
            parsed = pd.to_datetime(s, errors="coerce", utc=True)
            return parsed.dt.tz_localize(None)
        except (ValueError, TypeError):
            return pd.to_datetime(pd.Series([None] * len(s)))
    if fmts:
        best = None
        for f in fmts:
            p = pd.to_datetime(s, format=f, errors="coerce")
            if best is None or p.notna().sum() > best.notna().sum():
                best = p
        return best
    try:  # dateutil path with explicit day-/year-first disambiguation
        parsed = pd.to_datetime(
            s, errors="coerce", dayfirst=kw.get("dayfirst", False),
            yearfirst=kw.get("yearfirst", False), format="mixed", utc=True,
        )
        return parsed.dt.tz_localize(None)
    except (ValueError, TypeError):
        return pd.to_datetime(pd.Series([None] * len(s)))


def _try_parse_values(values: np.ndarray) -> Tuple[Optional[pd.Series], float, str]:
    """Parse distinct values to timestamps via the format matrix.
    Returns (parsed series aligned to input, fraction parsed, family)."""
    s = pd.Series(values.astype(str)).str.strip()
    # score every matching family on a sample, parse with the best few
    sample = s.iloc[: min(len(s), 500)]
    scored = []
    for name, rx, fmts, kw in _COMPILED_MATRIX:
        frac = sample.str.fullmatch(rx).mean()
        if frac >= _MIN_PARSE_FRACTION:
            scored.append((frac, name, fmts, kw))
    scored.sort(reverse=True, key=lambda t: t[0])
    best: Optional[pd.Series] = None
    best_frac, best_name = 0.0, ""
    for _, name, fmts, kw in scored[:4]:  # ambiguous families: parse-off
        parsed = _parse_family(s, fmts, kw)
        frac = float(parsed.notna().mean())
        if frac > best_frac:
            best, best_frac, best_name = parsed, frac, name
        if frac == 1.0:
            break
    if best is not None and best_frac >= _MIN_PARSE_FRACTION:
        return best, best_frac, best_name
    # fallback: pandas' own mixed inference (covers free-form strings like
    # "Tue Apr 03 18:00:09 +0000 2012")
    with pd.option_context("mode.chained_assignment", None):
        try:
            parsed = pd.to_datetime(s, errors="coerce", format="mixed")
            if parsed.dtype == object:  # mixed tz offsets → parse as UTC
                raise ValueError("mixed offsets")
        except (ValueError, TypeError):
            try:
                parsed = pd.to_datetime(s, errors="coerce", format="mixed", utc=True).dt.tz_localize(None)
            except (ValueError, TypeError):
                return None, 0.0, ""
    if getattr(parsed.dtype, "tz", None) is not None:
        parsed = parsed.dt.tz_localize(None)
    return parsed, float(parsed.notna().mean()), "inferred"


def ts_loop_cols_pre(idf: Table, id_col: Optional[str] = None) -> List[str]:
    """Candidate triage (reference :554-619): string columns whose values
    look date-length-ish, plus int columns with epoch-plausible magnitudes."""
    candidates = []
    for c, col in idf.columns.items():
        if c == id_col:
            continue
        if col.kind == "ts":
            continue
        if col.kind == "cat":
            vocab = col.vocab
            if len(vocab) == 0:
                continue
            lengths = {len(str(v)) for v in vocab[: min(len(vocab), 1000)]}
            if lengths & _VALID_LENGTHS or any(
                re.search(r"\d{4}-\d{2}-\d{2}", str(v)) for v in vocab[:50]
            ):
                candidates.append(c)
                continue
            # generic probe: a small vocab sample that pandas parses cleanly
            # (covers e.g. "Tue Apr 03 18:00:09 +0000 2012")
            sample = pd.Series([str(v) for v in vocab[:20]])
            if sample.str.len().min() >= 8 and sample.str.contains(r"\d").all():
                try:
                    parsed = pd.to_datetime(sample, errors="coerce", format="mixed", utc=True)
                    if parsed.notna().mean() > 0.9:
                        candidates.append(c)
                except (ValueError, TypeError):
                    pass
        elif col.kind == "num" and col.dtype_name in ("int", "bigint", "long"):
            # a thousand values decide: sliced on the device, so that the
            # fetch does not grow with the table
            head = min(idf.nrows, 1000)
            host, hmask = np.asarray(col.data[:head]), np.asarray(col.mask[:head])
            vals = host[hmask]  # null cells store 0 — judge valid entries only
            if len(vals) and np.all((vals >= 1e9) & (vals < 2e9)):
                candidates.append(c)
    return candidates


def regex_date_time_parser(idf: Table, col: str) -> Tuple[Optional[Column], float, str]:
    """Parse one candidate column through its dictionary (cat) or values."""
    rt = get_runtime()
    c = idf.columns[col]
    if c.kind == "cat":
        parsed, frac, fam = _try_parse_values(c.vocab) if len(c.vocab) else (None, 0.0, "")
        if parsed is None or frac < _MIN_PARSE_FRACTION:
            return None, frac, fam
        # map vocab → epoch seconds, then gather through the codes
        # (astype datetime64[s] first — pandas returns ns/us/s units depending
        # on the parse path, so integer division by 1e9 would be unit-dependent)
        epoch = parsed.to_numpy().astype("datetime64[s]").astype("int64")
        valid = parsed.notna().to_numpy()
        codes = np.asarray(c.data)
        mask = np.asarray(c.mask)
        safe = np.clip(codes, 0, len(epoch) - 1)
        secs = np.where((codes >= 0) & valid[safe], epoch[safe], 0).astype(np.int32)
        ok = mask & (codes >= 0) & valid[safe]
        return Column("ts", rt.shard_rows(secs), rt.shard_rows(ok), dtype_name="timestamp"), frac, fam
    host = np.asarray(c.data)[: idf.nrows]
    mask = np.asarray(c.mask)[: idf.nrows]
    parsed, frac, fam = _try_parse_values(host[mask])
    if parsed is None or frac < _MIN_PARSE_FRACTION:
        return None, frac, fam
    secs = np.zeros(idf.padded_rows, np.int32)
    ok = np.zeros(idf.padded_rows, bool)
    vals = parsed.to_numpy().astype("datetime64[s]").astype("int64")
    good = parsed.notna().to_numpy()
    idxs = np.nonzero(mask)[0]
    secs[idxs] = np.where(good, vals, 0).astype(np.int32)
    ok[idxs] = good
    return Column("ts", rt.shard_rows(secs), rt.shard_rows(ok), dtype_name="timestamp"), frac, fam


def ts_preprocess(
    idf: Table,
    id_col: Optional[str] = None,
    output_path: str = ".",
    tz_offset: str = "local",
    run_type: str = "local",
    mlflow_config=None,
    auth_key: str = "NA",
    **_ignored,
) -> Table:
    """Detect + convert timestamp columns; persist ``ts_cols_stats.csv``
    (reference :622-761)."""
    from anovos_tpu.obs import get_tracer

    phase = get_tracer().phase
    odf = idf
    rows = []
    for c in ts_loop_cols_pre(idf, id_col):
        # a stage a candidate column: its values parsed against the format families
        with phase("ts/detect", cat="block", rows=idf.nrows) as sp:
            try:
                new_col, frac, fam = regex_date_time_parser(idf, c)
            except Exception:  # detection must never break the pipeline (ref :707)
                new_col, frac, fam = None, 0.0, ""
            sp.add(converted=int(new_col is not None))
        rows.append(
            {
                "attribute": c,
                "parsed_fraction": round(frac, 4),
                "format_family": fam,
                "status": "converted" if new_col is not None else "skipped",
            }
        )
        if new_col is not None:
            odf = odf.with_column(c, new_col)
    if output_path and output_path != "NA":
        with phase("ts/write", cat="block", files=1, rows=len(rows)) as sp:
            Path(output_path).mkdir(parents=True, exist_ok=True)
            out = ends_with(output_path) + "ts_cols_stats.csv"
            pd.DataFrame(
                rows, columns=["attribute", "parsed_fraction", "format_family", "status"]
            ).to_csv(out, index=False)
            sp.add(bytes=os.path.getsize(out))
    return odf
