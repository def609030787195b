"""Datetime transformers (reference: data_transformer/datetime.py — the full
31-function surface: timestamp_to_unix :126 … lagged_ts :1933).

Device-native design (round-2): ts columns are int32 epoch-seconds + mask
(shared/table.py) and every conversion / extraction / arithmetic / predicate
runs as int32 calendar kernels on device (ops/datetime_kernels.py — Hinnant
civil-date math on the VPU).  Host work is limited to what inherently needs
it: strptime/strftime of *distinct vocabulary* strings, timezone transition
tables (tiny), and the final small aggregated frames.  Round 1 pulled every
column to host pandas per call — a full transfer per op on the remote-TPU
backend; the only remaining full-column pulls are the two string-producing
ops (timestamp_to_string, and ms-precision unix output), where the result
itself must live host-side.
"""

from __future__ import annotations

import warnings
from typing import List, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd

from anovos_tpu.ops import datetime_kernels as dk
from anovos_tpu.ops.segment import bf16_parts, dense_block_sums, dense_chunks
from anovos_tpu.shared.runtime import get_runtime
from anovos_tpu.shared.table import Column, Table, _host_to_column

_UNITS_SECONDS = {
    "second": 1, "seconds": 1, "minute": 60, "minutes": 60, "hour": 3600,
    "hours": 3600, "day": 86400, "days": 86400, "week": 604800, "weeks": 604800,
}

_I32_BIG = np.iinfo(np.int32).max


def _cols(list_of_cols) -> List[str]:
    if isinstance(list_of_cols, str):
        return [x.strip() for x in list_of_cols.split("|")]
    return list(list_of_cols)


def argument_checker(func_name: str, args: dict) -> None:
    """Shared validation (reference :39-124)."""
    oc = args.get("output_mode")
    if oc is not None and oc not in ("replace", "append"):
        raise TypeError(f"{func_name}: Invalid input for output_mode")


def _ts_col(idf: Table, col: str) -> Column:
    c = idf.columns[col]
    if c.kind != "ts":
        raise TypeError(f"{col} is not a timestamp column")
    return c


def _div_for(unit: str) -> int:
    return _UNITS_SECONDS.get(unit.rstrip("s") if unit not in _UNITS_SECONDS else unit, 86400)


def _out_name(name: str, output_mode: str, postfix: str) -> str:
    return name if output_mode == "replace" else name + postfix


def _emit_flag(idf: Table, name: str, flag: jax.Array, mask: jax.Array,
               output_mode: str, postfix: str) -> Table:
    """Boolean predicate → int32 num column (NaN via mask where ts null)."""
    col = Column("num", flag.astype(jnp.int32), mask, dtype_name="int")
    return idf.with_column(_out_name(name, output_mode, postfix), col)


def _emit_num(idf: Table, name: str, vals: jax.Array, mask: jax.Array,
              output_mode: str, postfix: str) -> Table:
    dtn = "int" if vals.dtype in (jnp.int32, jnp.int16) else "double"
    col = Column("num", vals, mask, dtype_name=dtn)
    return idf.with_column(_out_name(name, output_mode, postfix), col)


def _emit_ts(idf: Table, name: str, secs: jax.Array, mask: jax.Array,
             output_mode: str, postfix: str = "_ts") -> Table:
    col = Column("ts", secs.astype(jnp.int32), mask, dtype_name="timestamp")
    return idf.with_column(_out_name(name, output_mode, postfix), col)


def _ts_series(idf: Table, col: str) -> pd.Series:
    """Host materialization — used ONLY by the string-producing ops."""
    c = _ts_col(idf, col)
    secs = np.asarray(jax.device_get(c.data))[: idf.nrows].astype("int64")
    mask = np.asarray(jax.device_get(c.mask))[: idf.nrows]
    s = pd.Series(secs.astype("datetime64[s]"))
    s[~mask] = pd.NaT
    return s


# ----------------------------------------------------------------------
# conversions (:126-549)
# ----------------------------------------------------------------------
def timestamp_to_unix(idf: Table, list_of_cols, precision: str = "s", tz: str = "local", output_mode: str = "replace") -> Table:
    """Seconds precision is a zero-copy device view of the epoch storage;
    millisecond precision exceeds int32 so the exact value goes through the
    wide-int64 (hi, lo) pair — built host-side from one int32 pull (the one
    conversion that cannot stay on a 32-bit device path)."""
    argument_checker("timestamp_to_unix", {"output_mode": output_mode})
    odf = idf
    for c in _cols(list_of_cols):
        col = _ts_col(idf, c)
        if precision == "s":
            new = Column("num", col.data, col.mask, dtype_name="int")
            odf = odf.with_column(_out_name(c, output_mode, "_unix"), new)
        else:
            # ms epochs exceed int32: exact wide-int64 (hi, lo) pair, with
            # nulls riding the mask (a float fallback would quantize ~1.7e12
            # ms values by minutes in f32 — never degrade silently)
            from anovos_tpu.shared.table import wide_int_parts

            rt = get_runtime()
            npad = idf.pad_target()
            secs = np.asarray(jax.device_get(col.data))[: idf.nrows].astype("int64")
            mask_h = np.asarray(jax.device_get(col.mask))[: idf.nrows]
            v64 = np.where(mask_h, secs * 1000, 0)
            whi, wlo = wide_int_parts(v64)
            pad_i = np.zeros(npad - idf.nrows, np.int32)
            new = Column(
                "num",
                rt.shard_rows(np.concatenate([v64.astype(np.float32), pad_i.astype(np.float32)])),
                rt.shard_rows(np.concatenate([mask_h, pad_i.astype(bool)])),
                dtype_name="bigint",
                wide_hi=rt.shard_rows(np.concatenate([whi, pad_i])),
                wide_lo=rt.shard_rows(np.concatenate([wlo, pad_i - (1 << 31)])),
            )
            odf = odf.with_column(_out_name(c, output_mode, "_unix"), new)
    return odf


def unix_to_timestamp(idf: Table, list_of_cols, precision: str = "s", tz: str = "local", output_mode: str = "replace") -> Table:
    argument_checker("unix_to_timestamp", {"output_mode": output_mode})
    odf = idf
    rt = get_runtime()
    for c in _cols(list_of_cols):
        col = idf.columns[c]
        if col.is_wide_int:
            # exact int64 epochs (ms or s) — divide host-side, re-upload int32
            v = col.exact_host(idf.nrows) // (1000 if precision == "ms" else 1)
            mask_h = np.asarray(jax.device_get(col.mask))[: idf.nrows]
            npad = idf.pad_target()
            pad = np.zeros(npad - idf.nrows, np.int64)
            secs_d = rt.shard_rows(np.concatenate([v, pad]).astype(np.int32))
            mask_d = rt.shard_rows(
                np.concatenate([mask_h, np.zeros(npad - idf.nrows, bool)])
            )
            odf = _emit_ts(odf, c, secs_d, mask_d, output_mode)
        else:
            secs = _unix_to_secs(col.data, precision == "ms")
            odf = _emit_ts(odf, c, secs, col.mask, output_mode)
    return odf


@jax.jit
def _unix_to_secs_ms(data: jax.Array) -> jax.Array:
    return jnp.floor_divide(data.astype(jnp.float32), 1000.0).astype(jnp.int32)


@jax.jit
def _unix_to_secs_s(data: jax.Array) -> jax.Array:
    return data.astype(jnp.int32)


def _unix_to_secs(data: jax.Array, is_ms: bool) -> jax.Array:
    return _unix_to_secs_ms(data) if is_ms else _unix_to_secs_s(data)


def timezone_conversion(idf: Table, list_of_cols, given_tz: str, output_tz: str, output_mode: str = "replace") -> Table:
    """(:272) device epoch shift through a host-built tz transition table
    (ops/datetime_kernels.apply_offset_table) — DST-exact, no column pull."""
    argument_checker("timezone_conversion", {"output_mode": output_mode})
    odf = idf
    for c in _cols(list_of_cols):
        col = _ts_col(idf, c)
        lo, hi = _col_min_max(col.data, col.mask)
        if lo > hi:  # all-null column: nothing to shift
            odf = _emit_ts(odf, c, col.data, col.mask, output_mode)
            continue
        tr, off = dk.tz_offset_table(given_tz, output_tz, int(lo), int(hi))
        shifted = dk.apply_offset_table(col.data, jnp.asarray(tr), jnp.asarray(off))
        odf = _emit_ts(odf, c, shifted, col.mask, output_mode)
    return odf


@jax.jit
def _min_max_program(data: jax.Array, mask: jax.Array):
    lo = jnp.where(mask, data, _I32_BIG).min()
    hi = jnp.where(mask, data, -_I32_BIG).max()
    return lo, hi


def _col_min_max(data: jax.Array, mask: jax.Array):
    lo, hi = jax.device_get(_min_max_program(data, mask))
    return int(lo), int(hi)


def string_to_timestamp(idf: Table, list_of_cols, input_format: str = "%Y-%m-%d %H:%M:%S", output_type: str = "ts", output_mode: str = "replace") -> Table:
    """(:338) parse through the dictionary — each distinct string ONCE on
    host, then a device gather maps codes → epoch seconds."""
    argument_checker("string_to_timestamp", {"output_mode": output_mode})
    odf = idf
    for c in _cols(list_of_cols):
        col = idf.columns[c]
        if col.kind != "cat":
            continue
        parsed = pd.to_datetime(pd.Series(col.vocab.astype(str)), format=input_format, errors="coerce")
        arr = parsed.to_numpy().astype("datetime64[s]")
        if output_type == "dt":
            arr = arr.astype("datetime64[D]").astype("datetime64[s]")
        ok_h = ~np.isnat(arr)
        secs_h = np.where(ok_h, arr.astype("int64"), 0).astype(np.int32)
        secs, mask = _gather_vocab_ts(
            col.data, col.mask, jnp.asarray(secs_h), jnp.asarray(ok_h)
        )
        odf = odf.with_column(
            _out_name(c, output_mode, "_ts"), Column("ts", secs, mask, dtype_name="timestamp")
        )
    return odf


@jax.jit
def _gather_vocab_ts(codes: jax.Array, mask: jax.Array, vocab_secs: jax.Array, vocab_ok: jax.Array):
    nv = vocab_secs.shape[0]
    safe = jnp.clip(codes, 0, max(nv - 1, 0))
    if nv == 0:
        return jnp.zeros_like(codes), jnp.zeros_like(mask)
    secs = vocab_secs[safe]
    ok = mask & (codes >= 0) & vocab_ok[safe]
    return jnp.where(ok, secs, 0), ok


def timestamp_to_string(idf: Table, list_of_cols, output_format: str = "%Y-%m-%d %H:%M:%S", output_mode: str = "replace") -> Table:
    """String output lives host-side by design (vocab discipline): one int32
    pull, host strftime, dictionary re-encode."""
    argument_checker("timestamp_to_string", {"output_mode": output_mode})
    odf = idf
    rt = get_runtime()
    for c in _cols(list_of_cols):
        s = _ts_series(idf, c)
        vals = np.array(s.dt.strftime(output_format).to_numpy(dtype=object), copy=True)
        vals[s.isna().to_numpy()] = None
        new = _host_to_column(vals, idf.nrows, idf.pad_target(), rt)
        odf = odf.with_column(_out_name(c, output_mode, "_str"), new)
    return odf


def dateformat_conversion(idf: Table, list_of_cols, input_format: str = "%Y-%m-%d", output_format: str = "%d-%m-%Y", output_mode: str = "replace") -> Table:
    """(:480) string date → string date purely via the dictionary (distinct
    values only; the code array never leaves the device)."""
    argument_checker("dateformat_conversion", {"output_mode": output_mode})
    odf = idf
    for c in _cols(list_of_cols):
        col = idf.columns[c]
        if col.kind != "cat":
            continue
        parsed = pd.to_datetime(pd.Series(col.vocab.astype(str)), format=input_format, errors="coerce")
        formatted = parsed.dt.strftime(output_format)
        good = formatted.notna().to_numpy()
        # distinct input dates can format to the SAME output string — dedup
        # the vocab and remap codes on device (unique-vocab invariant; bad
        # parses map to -1 / mask False)
        fmt_strs = formatted.to_numpy(dtype=object)
        new_vocab, inv = (
            np.unique(fmt_strs[good].astype(str), return_inverse=True)
            if good.any()
            else (np.array([], dtype=object), np.array([], dtype=np.int64))
        )
        lut = np.full(max(len(col.vocab), 1), -1, np.int32)
        lut[np.nonzero(good)[0]] = inv.astype(np.int32)
        data = _remap_codes_lut(col.data, jnp.asarray(lut))
        mask = col.mask & (data >= 0)
        newc = Column("cat", data, mask, vocab=new_vocab.astype(object), dtype_name="string")
        odf = odf.with_column(_out_name(c, output_mode, "_fmt"), newc)
    return odf


@jax.jit
def _remap_codes_lut(codes, lut):
    nv = lut.shape[0]
    safe = jnp.clip(codes, 0, nv - 1)
    return jnp.where(codes >= 0, lut[safe], -1)


_EXTRACT_UNITS = (
    "year", "month", "day", "dayofmonth", "hour", "minute", "second",
    "dayofweek", "dayofyear", "weekofyear", "quarter",
)


def timeUnits_extraction(idf: Table, list_of_cols, units: Union[str, List[str]] = "all", output_mode: str = "append") -> Table:
    """(:550) calendar components as numeric columns — ONE device program
    per timestamp column computes every requested unit."""
    argument_checker("timeUnits_extraction", {"output_mode": output_mode})
    units = list(_EXTRACT_UNITS[:3]) + list(_EXTRACT_UNITS[4:]) if units == "all" else _cols(units)
    for u in units:
        if u not in _EXTRACT_UNITS:
            raise TypeError(f"Invalid unit {u}")
    odf = idf
    for c in _cols(list_of_cols):
        col = _ts_col(idf, c)
        stacked = _extract_units_program(col.data, tuple(units))
        for i, u in enumerate(units):
            odf = _emit_num(odf, f"{c}_{u}", stacked[i], col.mask, "append", "")
        if output_mode == "replace":
            odf = odf.drop([c])
    return odf


@jax.jit
def _civil(secs):
    return dk.civil_from_epoch(secs)


import functools as _functools


@_functools.partial(jax.jit, static_argnames=("units",))
def _extract_units_program(secs: jax.Array, units: tuple) -> jax.Array:
    c = dk.civil_from_epoch(secs)
    outs = []
    for u in units:
        if u in ("day", "dayofmonth"):
            outs.append(c["day"])
        elif u == "dayofweek":
            outs.append(c["dayofweek"] + 1)
        else:
            outs.append(c[u])
    return jnp.stack(outs, axis=0)


# ----------------------------------------------------------------------
# arithmetic (:624-921)
# ----------------------------------------------------------------------
def time_diff(idf: Table, ts1: str, ts2: str, unit: str = "days", output_mode: str = "append") -> Table:
    argument_checker("time_diff", {"output_mode": output_mode})
    a, b = _ts_col(idf, ts1), _ts_col(idf, ts2)
    vals, mask = _time_diff_program(a.data, a.mask, b.data, b.mask, float(_div_for(unit)))
    odf = _emit_num(idf, f"{ts1}_{ts2}_timediff", vals, mask, "append", "")
    if output_mode == "replace":
        odf = odf.drop([ts1, ts2])
    return odf


@jax.jit
def _time_diff_program(a, ma, b, mb, div):
    d = jnp.abs(b - a).astype(jnp.float32) / div
    return d, ma & mb


def time_elapsed(idf: Table, list_of_cols, unit: str = "days", output_mode: str = "append") -> Table:
    """(:696) now − ts."""
    argument_checker("time_elapsed", {"output_mode": output_mode})
    odf = idf
    now = int(pd.Timestamp.now().timestamp())
    div = float(_div_for(unit))
    for c in _cols(list_of_cols):
        col = _ts_col(idf, c)
        vals = _elapsed_program(col.data, jnp.int32(now), div)
        odf = _emit_num(odf, f"{c}_timeelapsed", vals, col.mask, "append", "")
        if output_mode == "replace":
            odf = odf.drop([c])
    return odf


@jax.jit
def _elapsed_program(secs, now, div):
    return (now - secs).astype(jnp.float32) / div


def adding_timeUnits(idf: Table, list_of_cols, unit: str = "days", unit_value: float = 1, output_mode: str = "replace") -> Table:
    """(:771) shift timestamps by N units — month/year-aware on device
    (end-of-month clamping parity with DateOffset, dk.add_months)."""
    argument_checker("adding_timeUnits", {"output_mode": output_mode})
    odf = idf
    key = unit if unit.endswith("s") else unit + "s"
    for c in _cols(list_of_cols):
        col = _ts_col(idf, c)
        if key in ("months", "years"):
            months = int(unit_value) * (12 if key == "years" else 1)
            shifted = dk.add_months(col.data, months)
        else:
            if key in _UNITS_SECONDS:
                delta = int(round(unit_value * _UNITS_SECONDS[key]))
            else:  # alias spellings (min, sec, w, …): let pandas resolve
                delta = int(round(pd.to_timedelta(float(unit_value), unit=unit).total_seconds()))
            shifted = _shift_program(col.data, jnp.int32(delta))
        odf = _emit_ts(odf, c, shifted, col.mask, output_mode, "_adjusted")
    return odf


@jax.jit
def _shift_program(secs, delta):
    return secs + delta


def timestamp_comparison(
    idf: Table,
    list_of_cols,
    comparison_type: str = "greater_than",
    comparison_value: str = "1970-01-01 00:00:00",
    comparison_format: str = "%Y-%m-%d %H:%M:%S",
    output_mode: str = "append",
) -> Table:
    """(:829) boolean flag vs a fixed timestamp parsed with
    ``comparison_format`` (reference :835)."""
    argument_checker("timestamp_comparison", {"output_mode": output_mode})
    if comparison_type not in ("greater_than", "less_than", "greaterThan_equalTo", "lessThan_equalTo"):
        raise TypeError("Invalid input for comparison_type")
    # pd naive-as-UTC matches the module's epoch convention (strptime would
    # apply the host timezone).  An EXPLICIT format is strict like the
    # reference (a silent auto-parse fallback would undo the day-first/
    # month-first disambiguation the parameter exists for); only the
    # default format is lenient, accepting e.g. bare dates
    try:
        cmp_ts = pd.to_datetime(str(comparison_value), format=comparison_format)
    except ValueError:
        if comparison_format != "%Y-%m-%d %H:%M:%S":
            raise
        cmp_ts = pd.to_datetime(str(comparison_value))
    ref = jnp.int32(int(cmp_ts.timestamp()))
    odf = idf
    for c in _cols(list_of_cols):
        col = _ts_col(idf, c)
        flag = _compare_program(col.data, ref, comparison_type)
        odf = _emit_flag(odf, c, flag, col.mask, output_mode, "_comparison")
    return odf


@_functools.partial(jax.jit, static_argnames=("op",))
def _compare_program(secs, ref, op):
    return {
        "greater_than": secs > ref,
        "less_than": secs < ref,
        "greaterThan_equalTo": secs >= ref,
        "lessThan_equalTo": secs <= ref,
    }[op]


# ----------------------------------------------------------------------
# calendar predicates (:923-1719) — all device int32 kernels
# ----------------------------------------------------------------------
def _boundary_ts(idf: Table, list_of_cols, which: str, period: str, postfix: str, output_mode: str) -> Table:
    odf = idf
    for c in _cols(list_of_cols):
        col = _ts_col(idf, c)
        odf = _emit_ts(odf, c, dk.period_boundary(col.data, which, period), col.mask, output_mode, postfix)
    return odf


def _boundary_flag(idf: Table, list_of_cols, which: str, period: str, postfix: str, output_mode: str) -> Table:
    odf = idf
    for c in _cols(list_of_cols):
        col = _ts_col(idf, c)
        odf = _emit_flag(odf, c, dk.is_period_boundary(col.data, which, period), col.mask, output_mode, postfix)
    return odf


def start_of_month(idf, list_of_cols, output_mode="replace"):
    return _boundary_ts(idf, list_of_cols, "start", "month", "_monthStart", output_mode)


def is_monthStart(idf, list_of_cols, output_mode="append"):
    return _boundary_flag(idf, list_of_cols, "start", "month", "_ismonthStart", output_mode)


def end_of_month(idf, list_of_cols, output_mode="replace"):
    return _boundary_ts(idf, list_of_cols, "end", "month", "_monthEnd", output_mode)


def is_monthEnd(idf, list_of_cols, output_mode="append"):
    return _boundary_flag(idf, list_of_cols, "end", "month", "_ismonthEnd", output_mode)


def start_of_year(idf, list_of_cols, output_mode="replace"):
    return _boundary_ts(idf, list_of_cols, "start", "year", "_yearStart", output_mode)


def is_yearStart(idf, list_of_cols, output_mode="append"):
    return _boundary_flag(idf, list_of_cols, "start", "year", "_isyearStart", output_mode)


def end_of_year(idf, list_of_cols, output_mode="replace"):
    return _boundary_ts(idf, list_of_cols, "end", "year", "_yearEnd", output_mode)


def is_yearEnd(idf, list_of_cols, output_mode="append"):
    return _boundary_flag(idf, list_of_cols, "end", "year", "_isyearEnd", output_mode)


def start_of_quarter(idf, list_of_cols, output_mode="replace"):
    return _boundary_ts(idf, list_of_cols, "start", "quarter", "_quarterStart", output_mode)


def is_quarterStart(idf, list_of_cols, output_mode="append"):
    return _boundary_flag(idf, list_of_cols, "start", "quarter", "_isquarterStart", output_mode)


def end_of_quarter(idf, list_of_cols, output_mode="replace"):
    return _boundary_ts(idf, list_of_cols, "end", "quarter", "_quarterEnd", output_mode)


def is_quarterEnd(idf, list_of_cols, output_mode="append"):
    return _boundary_flag(idf, list_of_cols, "end", "quarter", "_isquarterEnd", output_mode)


def is_yearFirstHalf(idf, list_of_cols, output_mode="append"):
    odf = idf
    for c in _cols(list_of_cols):
        col = _ts_col(idf, c)
        flag = dk.extract_unit(col.data, "month") <= 6
        odf = _emit_flag(odf, c, flag, col.mask, output_mode, "_isFirstHalf")
    return odf


def is_selectedHour(idf, list_of_cols, start_hour: int = 0, end_hour: int = 23, output_mode="append"):
    """(:1553) hour ∈ [start, end] with wraparound."""
    odf = idf
    for c in _cols(list_of_cols):
        col = _ts_col(idf, c)
        flag = _selected_hour_program(col.data, int(start_hour), int(end_hour))
        odf = _emit_flag(odf, c, flag, col.mask, output_mode, "_isselectedHour")
    return odf


@_functools.partial(jax.jit, static_argnames=("lo", "hi"))
def _selected_hour_program(secs, lo, hi):
    h = dk.extract_unit(secs, "hour")
    if lo <= hi:
        return (h >= lo) & (h <= hi)
    return (h >= lo) | (h <= hi)


def is_leapYear(idf, list_of_cols, output_mode="append"):
    odf = idf
    for c in _cols(list_of_cols):
        col = _ts_col(idf, c)
        odf = _emit_flag(odf, c, _leap_program(col.data), col.mask, output_mode, "_isleapYear")
    return odf


@jax.jit
def _leap_program(secs):
    return dk.civil_from_epoch(secs)["leap"]


def is_weekend(idf, list_of_cols, output_mode="append"):
    odf = idf
    for c in _cols(list_of_cols):
        col = _ts_col(idf, c)
        odf = _emit_flag(odf, c, _weekend_program(col.data), col.mask, output_mode, "_isweekend")
    return odf


@jax.jit
def _weekend_program(secs):
    return dk.civil_from_epoch(secs)["dayofweek"] >= 5


# ----------------------------------------------------------------------
# time-series aggregation (:1721-2012)
# ----------------------------------------------------------------------
_AGG_FUNCS = {"count", "min", "max", "sum", "mean", "median", "stddev"}

# strftime directive → bucket granularity rank (coarse → fine)
_GRAIN_RANK = {"Y": 0, "y": 0, "m": 1, "b": 1, "B": 1, "d": 2, "j": 2, "a": 2,
               "A": 2, "w": 2, "H": 3, "I": 3, "M": 4, "S": 5}


def _format_grain(fmt: str) -> Optional[str]:
    """Finest calendar field in a strftime format, if the format is a
    standard 'prefix chain' (year[-month[-day[-hour…]]]).  Returns None for
    exotic formats (e.g. bare %d) → host groupby fallback."""
    import re

    fields = re.findall(r"%(\w)", fmt)
    if not fields or any(f not in _GRAIN_RANK for f in fields):
        return None
    ranks = sorted({_GRAIN_RANK[f] for f in fields})
    if ranks != list(range(len(ranks))) or 0 not in ranks:
        return None  # not a prefix chain from year down
    return ["year", "month", "day", "hour", "minute", "second"][max(ranks)]


@_functools.partial(jax.jit, static_argnames=("grain",))
def _bucket_ids(secs: jax.Array, grain: str) -> jax.Array:
    c = dk.civil_from_epoch(secs)
    if grain == "year":
        return c["year"]
    if grain == "month":
        return c["year"] * 12 + (c["month"] - 1)
    if grain == "day":
        return c["days"]
    if grain == "hour":
        return c["days"] * 24 + c["hour"]
    if grain == "minute":
        return c["days"] * 1440 + c["sod"] // 60
    return secs  # second grain


def _bucket_start_secs(ids: np.ndarray, grain: str) -> np.ndarray:
    """Host: bucket id → epoch seconds of the bucket start (for labels)."""
    ids = ids.astype("int64")
    if grain in ("year", "month"):
        y = ids if grain == "year" else ids // 12
        m = np.ones_like(ids) if grain == "year" else ids % 12 + 1
        dt = pd.to_datetime(pd.DataFrame({"year": y, "month": m, "day": 1}))
        return dt.astype("datetime64[ns]").astype("int64").to_numpy() // 10**9
    mult = {"day": 86400, "hour": 3600, "minute": 60, "second": 1}[grain]
    return ids * mult


@_functools.partial(jax.jit, static_argnames=("grain",))
def _bucket_ids_minmax(secs: jax.Array, mask: jax.Array, grain: str):
    """Bucket ids + masked span in one program (the aggregator's fused
    preamble — ids and min/max used to dispatch separately)."""
    ids = _bucket_ids(secs, grain)
    lo = jnp.where(mask, ids, _I32_BIG).min()
    hi = jnp.where(mask, ids, -_I32_BIG).max()
    return ids, lo, hi


def _segment_aggregate(ids0: jax.Array, valid: jax.Array, V: jax.Array, Mv: jax.Array, nseg: int,
                       off: "int | None" = None):
    """Per-bucket count/sum/sumsq/min/max/median for every value column.

    ids0: (rows,) int32 bucket ids already offset to [0, nseg); valid:
    (rows,) row validity; V: (rows, k) f32 values; Mv: (rows, k) value
    validity.  One program, no host loop (``_segment_aggregate_jit``: a
    class of at most ``_DENSE_SEGMENTS_MAX`` buckets by contraction, masked
    reduce and counting selection, a wider one by contraction and the same
    selection over rows that one sort has grouped, or one sort a column
    where its rows are few a bucket; no scatter on any side).  On a
    multi-device mesh the block is re-laid column-parallel (each device
    sorts whole columns locally; ids/validity replicate, and so does the
    order that groups the rows) — see runtime.column_parallel.

    The static segment count is bucketed into 2^k classes (min 8 —
    ops/segment.py ``segment_class``: NOT the coarse vocab classes, the
    output is six (k, nseg) arrays): a daypart sweep (nseg 5), a weekday
    sweep (7) and a small date span then share one compiled program per
    (rows, k) shape.  The returned arrays keep the padded ``(k, nseg_pad)``
    width — dead buckets count zero rows, and every consumer either loops
    over its own label list or filters ``cnt > 0``, so the extra buckets are
    never read."""
    from anovos_tpu.ops.segment import segment_class
    from anovos_tpu.shared.runtime import wants_column_parallel

    nseg = segment_class(nseg)
    cp = wants_column_parallel(ids0, valid, V, Mv, replicate=(ids0, valid))
    if off is not None:
        # lo-offset subtraction fused into the aggregate program
        return _segment_aggregate_jit_off(
            ids0, np.int32(off), valid, V, Mv, nseg, cp=cp)
    return _segment_aggregate_jit(ids0, valid, V, Mv, nseg, cp=cp)


@_functools.partial(jax.jit, static_argnames=("nseg", "cp"))
def _segment_aggregate_jit_off(ids: jax.Array, off: jax.Array, valid: jax.Array,
                               V: jax.Array, Mv: jax.Array, nseg: int,
                               cp: bool = False):
    return _segment_aggregate_jit(ids - off, valid, V, Mv, nseg, cp=cp)


# The one rule of the per-bucket aggregate, by the static class.  A class of
# at most this many buckets (dayparts, weekdays, the days of a month or two)
# takes its count, sum and sum of squares from a one-hot contraction at
# precision ``highest``, its min and max from a masked reduce (a broadcast
# of (chunk, columns, buckets): what keeps the limit where it is) and its
# medians from a selection by counting, whose passes grow with the class.  A
# wider class (a daily grain over years, ``aggregator`` at a fine grain)
# takes the three moments from a contraction too (``ops/segment.py``'s
# ``dense_block_sums`` over the values' bfloat16 parts) and min, max and
# median from the same selection by counting, made narrow: ONE sort a grain
# puts the rows in bucket order, every column's keys are gathered behind
# them, a chunk of grouped rows then holds a short range of buckets, and the
# selection's one-hot is a window of ``_WINDOW_LANES`` lanes that slides
# along the chunk's own buckets (``_grouped_picks``; PERF.md section 6,
# PR 52: 21 sorts a grain became one, 0.80 s became 0.30).  Inside the wide
# side one more rule, on what a call can see, (rows, class): the windows a
# pass crosses into are the class over ``_WINDOW_LANES`` whatever the rows,
# so a class with few rows a bucket (``_groups_rows``: fewer chunks than
# windows; a daily grain over 32,768 rows, a grain of minutes) keeps ONE
# two-key sort a column (``_sort_picks``: 2.3 ms there, where the windows'
# walk takes 5 to 12).  No class takes a scatter: an f32 scatter-add, one
# update at a time, lost 1.6 % of a bucket of 10^6 values and cost 7.2 ns an
# update on the chip (PERF.md section 6, PR 39).
_DENSE_SEGMENTS_MAX = 64
# rows a step of the dense path's scan: bounds the one-hot and the stacked
# operand of the contraction whatever the table's length
_DENSE_CHUNK_ROWS = 1 << 15
# cells (rows x columns) the sort of a column takes at once: the sort holds its
# keys twice, so a block of 2^25 cells is about 0.5 GB of the chip's memory
_SORT_BLOCK_CELLS = 1 << 25
# bits of the 32-bit key that one counting pass of a small class's selection
# settles (a digit: its 15 candidates a bucket are counted together), and the
# passes over the rows a grain makes: one a digit, and one for the upper middle
_SELECT_BITS = 4
_SELECT_PASSES = 32 // _SELECT_BITS + 1
# lanes of the window that a grouped class's selection slides along a chunk's own buckets
_WINDOW_LANES = 64


def _dense_moments(ids0, ok, V, nseg: int):
    """(cnt, sm, sq, mn, mx), each (k, nseg), for a small segment class:
    per chunk of rows the bucket one-hot (chunk, nseg) is contracted with
    [ok, v, v*v] (chunk, 3k) at precision ``highest`` (f32 accumulation), and
    min / max are reduces of the values masked by bucket; a ``lax.scan``
    over the chunks carries the five results."""
    rows, k = V.shape
    chunk = dense_chunks(rows, _DENSE_CHUNK_ROWS)
    lanes = jnp.arange(nseg, dtype=ids0.dtype)

    def one(ids_c, ok_c, v_c):
        hot = ids_c[:, None] == lanes  # (chunk, nseg)
        x = jnp.where(ok_c, v_c, 0.0)
        moments = jnp.einsum(
            "rs,rk->ks", hot.astype(jnp.float32),
            jnp.concatenate([ok_c.astype(jnp.float32), x, x * x], axis=1),
            precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32)
        sel = hot[:, None, :] & ok_c[:, :, None]  # (chunk, k, nseg)
        return (moments,
                jnp.where(sel, v_c[:, :, None], jnp.inf).min(axis=0),
                jnp.where(sel, v_c[:, :, None], -jnp.inf).max(axis=0))

    n = rows // chunk
    if n == 1:
        moments, mn, mx = one(ids0, ok, V)
    else:
        def step(carry, xs):
            m, lo, hi = one(*xs)
            return (carry[0] + m, jnp.minimum(carry[1], lo), jnp.maximum(carry[2], hi)), None

        init = (jnp.zeros((3 * k, nseg), jnp.float32),
                jnp.full((k, nseg), jnp.inf, jnp.float32), jnp.full((k, nseg), -jnp.inf, jnp.float32))
        (moments, mn, mx), _ = jax.lax.scan(
            step, init, (ids0.reshape(n, chunk), ok.reshape(n, chunk, k), V.reshape(n, chunk, k)))
    return moments[:k], moments[k:2 * k], moments[2 * k:], mn, mx


def _is_wide(nseg: int) -> bool:
    """The rule: whether a static segment class is on the wide side."""
    return nseg > _DENSE_SEGMENTS_MAX


def _wide_moments(ids0, ok, V, nseg: int):
    """(cnt, sm, sq), each (k, nseg), for a wide segment class: the buckets'
    one-hot contracted with the validity and with the three bfloat16 parts
    of the values and of their squares (``bf16_parts``: they add up to the
    f32 exactly, so every product is exact and the sums are f32 sums taken
    chunk by chunk, ``dense_block_sums``).  The parts' sums are added
    smallest first."""
    k = V.shape[1]

    def planes(ok_c, v_c):  # (chunk, 7k)
        x = jnp.where(ok_c, v_c, 0.0)
        return jnp.concatenate([ok_c.astype(jnp.bfloat16), *bf16_parts(x), *bf16_parts(x * x)], axis=1)

    sums = dense_block_sums(ids0, (ok, V), planes, nseg)
    hi, mid, lo = sums[k:4 * k].reshape(3, k, nseg)
    hi2, mid2, lo2 = sums[4 * k:].reshape(3, k, nseg)
    return sums[:k], (lo + mid) + hi, (lo2 + mid2) + hi2


_NAN_KEY = int(np.float32(np.nan).view(np.int32))


def _flip_negative(bits):
    """An f32's int32 bits to a key whose signed order is the float's, and
    back: all bits but the sign flipped where the sign is set."""
    return jnp.where(bits < 0, bits ^ _I32_BIG, bits)


def _sort_keys(v):
    """int32 keys whose signed order is the order ``lax.sort`` gives the f32
    ``v``: -0.0 as +0.0, every NaN one NaN above +inf."""
    bits = jax.lax.bitcast_convert_type(v, jnp.int32)
    return _flip_negative(jnp.where(jnp.isnan(v), _NAN_KEY, jnp.where(v == 0, 0, bits)))


def _select_medians(ids0, ok, V, cnt, nseg: int):
    """(k, nseg) medians of a small segment class by counting, the same
    stored values the sort picks.  The key of a bucket's lower middle is
    built from the top, ``_SELECT_BITS`` bits a pass over the rows (in the
    chunks ``_dense_moments`` takes): a row looks up the prefix its own
    bucket has so far (the bucket one-hot contracted with the prefix's four
    bytes), its keys are compared with the prefix extended by each value of
    the next digit, and the one-hot contracted with the 0/1 results counts a
    bucket's valid keys below each candidate (0/1 and a byte are exact in
    bf16, the sums in f32); the digit is the number of candidates whose count
    does not pass the rank.  One more pass counts the keys up to the lower
    middle and takes the least key above it, which is the upper middle where
    the count stops at the rank.  No sort, no scatter, and nothing as long as
    the rows but the inputs."""
    rows, k = V.shape
    chunk = dense_chunks(rows, _DENSE_CHUNK_ROWS)
    lanes = jnp.arange(nseg, dtype=ids0.dtype)
    xs = (ids0.reshape(-1, chunk), ok.reshape(-1, chunk, k), V.reshape(-1, chunk, k))
    sign = jnp.uint32(1 << 31)  # the prefix is built as an unsigned number: its top bit is the key's sign, flipped
    digits = jnp.arange(1, 1 << _SELECT_BITS, dtype=jnp.uint32)

    def over_rows(one, init, merge):
        def step(carry, x):
            ids_c, ok_c, v_c = x
            # an invalid value's key is above every candidate, so it is never counted
            keys = jnp.where(ok_c, _sort_keys(v_c), _I32_BIG)  # (chunk, k)
            return merge(carry, one(ids_c[:, None] == lanes, keys)), None

        return jax.lax.scan(step, init, xs)[0]

    c = cnt.astype(jnp.int32)
    live = c > 0
    lo_rank, hi_rank = jnp.maximum(c - 1, 0) // 2, c // 2  # 0-based, as the sort path picks them

    def digit(i, prefix):
        shift = (32 - _SELECT_BITS * (i + 1)).astype(jnp.uint32)
        planes = jnp.stack([((prefix ^ sign) >> s) & 255 for s in (0, 8, 16, 24)]).astype(jnp.bfloat16)  # (4, k, nseg)

        def below(hot, keys):
            hot = hot.astype(jnp.bfloat16)
            own = jnp.einsum("rs,bks->rbk", hot, planes, preferred_element_type=jnp.float32).astype(jnp.uint32)
            own = own[:, 0] | (own[:, 1] << 8) | (own[:, 2] << 16) | (own[:, 3] << 24)  # (chunk, k)
            # xor: below the prefix's bits it sets the digit, at the top it clears the flipped sign
            cands = jax.lax.bitcast_convert_type(own[:, None, :] ^ (digits[None, :, None] << shift), jnp.int32)
            under = (keys[:, None, :] < cands).astype(jnp.bfloat16)  # (chunk, 15, k)
            return jnp.einsum("rs,rjk->jks", hot, under, preferred_element_type=jnp.float32).astype(jnp.int32)

        counts = over_rows(below, jnp.zeros((digits.size, k, nseg), jnp.int32), jnp.add)
        return prefix | ((counts <= lo_rank).sum(axis=0).astype(jnp.uint32) << shift)

    prefix = jax.lax.fori_loop(0, 32 // _SELECT_BITS, digit, jnp.zeros((k, nseg), jnp.uint32))
    lo = jnp.where(live, jax.lax.bitcast_convert_type(prefix ^ sign, jnp.int32), 0)

    def around(hot, keys):
        hot, keys = hot[:, None, :], keys[:, :, None]  # (chunk, k, nseg) by broadcast
        return ((hot & (keys <= lo)).sum(axis=0, dtype=jnp.int32),
                jnp.where(hot & (keys > lo), keys, _I32_BIG).min(axis=0))

    upto, above = over_rows(
        around, (jnp.zeros((k, nseg), jnp.int32), jnp.full((k, nseg), _I32_BIG, jnp.int32)),
        lambda a, b: (a[0] + b[0], jnp.minimum(a[1], b[1])))
    hi = jnp.where(live & (upto <= hi_rank), above, lo)

    lo, hi = (jax.lax.bitcast_convert_type(_flip_negative(key), jnp.float32) for key in (lo, hi))
    return (lo + hi) / 2  # an empty bucket reads 0.0; no consumer reads it


def _sort_picks(ids0, ok, V, cnt, nseg: int):
    """(mn, mx, med), each (k, nseg), of a wide class whose rows are few a
    bucket (``_groups_rows``), and the oracle the grouped selection is held
    to: each column sorted by (bucket, value),
    the first, the last and the middle one or two of every bucket picked
    through the cumulative counts (an empty bucket reads +inf, -inf and
    whatever lies at its place: no consumer reads it).  The columns sort
    ``_SORT_BLOCK_CELLS // rows`` at a time (a ``lax.map`` over column
    blocks inside the one program), so the sort's memory does not grow with
    the number of columns."""
    rows, k = V.shape

    def per_col(v, o, c):
        s = jnp.where(o, ids0, nseg)
        # both operands are keys: the unstable sort gives the same values
        _, v_sorted = jax.lax.sort((s, v), num_keys=2, is_stable=False)
        c = c.astype(jnp.int32)
        starts = jnp.cumsum(c) - c  # (nseg,)
        c_i = jnp.maximum(c - 1, 0)

        def at(i):  # the i-th of every bucket's run
            return v_sorted[jnp.clip(starts + i, 0, rows - 1)]

        return (jnp.where(c > 0, at(0), jnp.inf), jnp.where(c > 0, at(c_i), -jnp.inf),
                (at(c_i // 2) + at((c_i + 1) // 2)) / 2)

    block = jax.vmap(per_col, in_axes=(1, 1, 0), out_axes=0)
    b = max(1, min(k, _SORT_BLOCK_CELLS // rows))
    while k % b:
        b -= 1
    if b == k:
        return block(V, ok, cnt)
    cut = jax.lax.dynamic_slice_in_dim
    picks = jax.lax.map(
        lambda i: block(cut(V, i * b, b, 1), cut(ok, i * b, b, 1), cut(cnt, i * b, b, 0)),
        jnp.arange(k // b))
    return tuple(p.reshape(k, nseg) for p in picks)


def _groups_rows(rows: int, nseg: int) -> bool:
    """The rule inside the wide side: whether a wide class groups its rows
    for a selection (the windows a pass crosses into are then no more than
    the chunks it walks) or sorts every column (``_sort_picks``)."""
    return rows // _DENSE_CHUNK_ROWS >= -(-nseg // _WINDOW_LANES)


class _GroupLayout(NamedTuple):
    """A grouped class's rows padded to whole chunks, the chunk, the blocks of
    ``_WINDOW_LANES`` buckets, and the static bound of (chunk, window) steps
    a pass makes."""
    padded: int
    chunk: int
    blocks: int
    steps: int


def _group_layout(rows: int, nseg: int) -> _GroupLayout:
    """The chunks partition the sorted buckets, so a pass walks every chunk
    once and crosses into every window block at most once, whatever the
    skew: ``steps`` = chunks + blocks."""
    chunk = min(_DENSE_CHUNK_ROWS, rows)
    padded = rows + -rows % chunk
    blocks = -(-nseg // _WINDOW_LANES)
    return _GroupLayout(padded, chunk, blocks, padded // chunk + blocks)


def _group_keys(ids0, valid, ok, V, nseg: int):
    """(s, keys): the rows' buckets in order, (padded,), a row with no time
    or a bucket outside ``[0, nseg)`` as ``nseg`` at the end; and every
    column's int32 keys behind them, (k, padded), a value that does not
    count as ``_I32_BIG``.  ONE sort whatever the number of columns: of the
    buckets alone, carrying the row index (a rank-1 sort of two operands:
    12 ms at 6,291,456 rows on the chip, where a column's two-key sort under
    ``vmap`` takes 38), and the key block gathered by it a row at a time
    (0.19 s there: 1.4 ns a cell; the keys as operands of the sort took 0.25
    and compiled for 10 s an operand; PERF.md section 6, PR 52).  Within a
    bucket the rows come in no order, and need none."""
    rows, k = V.shape
    padded = _group_layout(rows, nseg).padded
    s = jnp.where(valid & (ids0 >= 0) & (ids0 < nseg), ids0, nseg).astype(jnp.int32)
    keys = jnp.where(ok, _sort_keys(V), _I32_BIG)  # (rows, k)
    if padded > rows:  # whole chunks: the rows added hold no time
        s = jnp.concatenate([s, jnp.full((padded - rows,), nseg, jnp.int32)])
        keys = jnp.concatenate([keys, jnp.full((padded - rows, k), _I32_BIG, jnp.int32)])
    s, order = jax.lax.sort((s, jnp.arange(padded, dtype=jnp.int32)), num_keys=1, is_stable=False)
    return s, keys[order].T


def _window_steps(s, nseg: int):
    """(chunk of every step, window block of every step, live steps) of a
    pass over grouped rows: a chunk's rows hold the buckets ``[first, last]``
    and take one step a block of ``_WINDOW_LANES`` buckets in that range (a
    chunk of rows with no time takes none); the chunks in order and each
    chunk's blocks in order, built by a cumulative sum, the live steps first
    in a list of the static bound's length."""
    padded, chunk, nblk, steps = _group_layout(s.shape[0], nseg)
    n = padded // chunk
    first = s[::chunk]
    last = jnp.where(s < nseg, s, -1).reshape(n, chunk).max(axis=1)  # the rows with no time lie at the end: no bucket
    windows = jnp.where(last >= 0, last // _WINDOW_LANES - first // _WINDOW_LANES + 1, 0)
    ends = jnp.cumsum(windows)
    t = jnp.arange(steps, dtype=jnp.int32)
    c = jnp.minimum((ends[None, :] <= t[:, None]).sum(axis=1), n - 1).astype(jnp.int32)
    blk = first[c] // _WINDOW_LANES + (t - (ends[c] - windows[c]))
    return c, jnp.clip(blk, 0, nblk - 1).astype(jnp.int32), ends[-1]


def _grouped_picks(ids0, valid, ok, V, cnt, nseg: int):
    """(mn, mx, med), each (k, nseg), of a wide class whose rows are many a
    bucket: the stored values ``_sort_picks`` picks, to the bit but for a
    zero's sign (+0.0 here; a denormal, which the device's compare flushes,
    reads as one), by ``_select_medians``' counting over rows that lie in
    bucket order.  No scatter, and one sort a grain."""
    return _windowed_picks(*_group_keys(ids0, valid, ok, V, nseg), cnt, nseg)


def _windowed_picks(s, keys, cnt, nseg: int):
    """``_grouped_picks`` of rows already grouped (``_group_keys``' pair).
    A chunk of grouped rows holds a contiguous range of buckets, so its
    one-hot is ``_WINDOW_LANES`` wide whatever the class: a pass walks the
    (chunk, window) steps of ``_window_steps``, the prefix planes and the
    counts being the step's own block of the (blocks, k, lanes) state.  The
    last pass takes, beside the upper middle, the least and the greatest key
    of every bucket."""
    k = keys.shape[0]
    _, chunk, nblk, _ = _group_layout(s.shape[0], nseg)
    step_chunk, step_blk, live_steps = _window_steps(s, nseg)
    s, keys = s.reshape(-1, chunk), keys.reshape(k, -1, chunk)
    lanes = jnp.arange(_WINDOW_LANES, dtype=jnp.int32)
    sign = jnp.uint32(1 << 31)
    digits = jnp.arange(1, 1 << _SELECT_BITS, dtype=jnp.uint32)
    at = _functools.partial(jax.lax.dynamic_index_in_dim, keepdims=False)

    def blocks(a):  # (k, nseg) -> (blocks, k, lanes)
        return jnp.pad(a, ((0, 0), (0, nblk * _WINDOW_LANES - nseg))).reshape(k, nblk, _WINDOW_LANES).transpose(1, 0, 2)

    def buckets(a):  # and back
        return a.transpose(1, 0, 2).reshape(k, nblk * _WINDOW_LANES)[:, :nseg]

    def merged(f, state, blk, part):  # state[blk] = f(state[blk], part): a slice updated in place, no scatter
        return jax.lax.dynamic_update_index_in_dim(state, f(at(state, blk, 0), part), blk, 0)

    def over_steps(one, init):
        def step(t, carry):
            c, blk = step_chunk[t], step_blk[t]
            hot = at(s, c, 0)[:, None] == blk * _WINDOW_LANES + lanes  # (chunk, lanes)
            return one(carry, blk, hot, at(keys, c, 1))  # keys (k, chunk)

        return jax.lax.fori_loop(0, live_steps, step, init)

    c = blocks(cnt.astype(jnp.int32))
    live = c > 0
    lo_rank, hi_rank = jnp.maximum(c - 1, 0) // 2, c // 2

    def digit(i, prefix):  # as _select_medians', the rows on the minor axis
        shift = (32 - _SELECT_BITS * (i + 1)).astype(jnp.uint32)
        planes = jnp.stack([((prefix ^ sign) >> sh) & 255 for sh in (0, 8, 16, 24)], axis=1).astype(jnp.bfloat16)

        def below(counts, blk, hot, keys_c):
            hot = hot.astype(jnp.bfloat16)
            own = jnp.einsum("rs,bks->bkr", hot, at(planes, blk, 0),
                             preferred_element_type=jnp.float32).astype(jnp.uint32)
            own = own[0] | (own[1] << 8) | (own[2] << 16) | (own[3] << 24)  # (k, chunk)
            cands = jax.lax.bitcast_convert_type(own[None] ^ (digits[:, None, None] << shift), jnp.int32)
            under = (keys_c[None] < cands).astype(jnp.bfloat16)  # (15, k, chunk)
            part = jnp.einsum("rs,jkr->jks", hot, under, preferred_element_type=jnp.float32)
            return merged(jnp.add, counts, blk, part.astype(jnp.int32))

        counts = over_steps(below, jnp.zeros((nblk, digits.size, k, _WINDOW_LANES), jnp.int32))
        return prefix | ((counts <= lo_rank[:, None]).sum(axis=1).astype(jnp.uint32) << shift)

    prefix = jax.lax.fori_loop(0, 32 // _SELECT_BITS, digit, jnp.zeros((nblk, k, _WINDOW_LANES), jnp.uint32))
    lo = jnp.where(live, jax.lax.bitcast_convert_type(prefix ^ sign, jnp.int32), 0)

    def around(carry, blk, hot, keys_c):
        hot, keys_c = hot.T[None], keys_c[:, None, :]  # (k, lanes, chunk) by broadcast
        lo_b = at(lo, blk, 0)[:, :, None]
        parts = ((hot & (keys_c <= lo_b)).sum(axis=2, dtype=jnp.int32),
                 jnp.where(hot & (keys_c > lo_b), keys_c, _I32_BIG).min(axis=2),
                 jnp.where(hot, keys_c, _I32_BIG).min(axis=2),
                 jnp.where(hot & (keys_c < _I32_BIG), keys_c, -_I32_BIG - 1).max(axis=2))
        return tuple(merged(f, a, blk, p) for f, a, p in
                     zip((jnp.add, jnp.minimum, jnp.minimum, jnp.maximum), carry, parts))

    big = jnp.full((nblk, k, _WINDOW_LANES), _I32_BIG, jnp.int32)
    upto, above, least, greatest = over_steps(around, (jnp.zeros_like(big), big, big, -big - 1))
    hi = jnp.where(live & (upto <= hi_rank), above, lo)

    def value(key):
        return jax.lax.bitcast_convert_type(_flip_negative(key), jnp.float32)

    return (buckets(jnp.where(live, value(least), jnp.inf)), buckets(jnp.where(live, value(greatest), -jnp.inf)),
            buckets((value(lo) + value(hi)) / 2))  # an empty bucket reads +inf, -inf, 0.0; no consumer reads it


def aggregate_routes(rows: int, k: int, *nsegs: int) -> dict:
    """What a call of ``k`` columns of ``rows`` padded rows over grains of
    these classes counts on its stage row, by the one rule: the (column,
    grain) medians of the narrow classes (``median_selects``, with the
    counting passes such a grain makes) and of the wide ones
    (``median_sorts``: medians of a class whose rows a sort orders, be it one
    sort a column or one that groups the grain's rows for a selection); the
    buckets of the wide grains and the cells (rows x columns, a wide grain)
    they aggregate; the sorts the call makes for its wide grains (one for a
    grouped grain, one a column of the others) and the static bound of
    (chunk, window) steps a pass over its grouped grains walks."""
    wide = [n for n in nsegs if _is_wide(n)]
    grouped = [n for n in wide if _groups_rows(rows, n)]
    selects = k * (len(nsegs) - len(wide))
    return {"median_selects": selects, "median_sorts": k * len(wide),
            "select_passes": _SELECT_PASSES if selects else 0,
            "wide_segments": sum(wide), "wide_cells": rows * k * len(wide),
            "wide_sorts": len(grouped) + k * (len(wide) - len(grouped)),
            "wide_select_steps": sum(_group_layout(rows, n).steps for n in grouped)}


@_functools.partial(jax.jit, static_argnames=("nseg", "cp"))
def _segment_aggregate_jit(ids0: jax.Array, valid: jax.Array, V: jax.Array,
                           Mv: jax.Array, nseg: int, cp: bool = False):
    """(cnt, sm, sq, mn, mx, med), each (k, nseg): the ONE per-bucket
    aggregate of ``aggregator``, the time-series inspection's fused
    three-grain program and its per-grain path.  The static ``nseg`` picks
    how they are taken (``_DENSE_SEGMENTS_MAX``); a wide class has a scope
    of its own under the aggregate's, for a trace to tell it by."""
    from anovos_tpu.shared.runtime import column_parallel, replicated

    with jax.named_scope("ts/segment_aggregate"):
        V, Mv = column_parallel(V, cp), column_parallel(Mv, cp)
        ids0, valid = replicated(ids0, cp), replicated(valid, cp)
        ok = Mv & valid[:, None]
        if not _is_wide(nseg):
            moments = _dense_moments(ids0, ok, V, nseg)
            return (*moments, _select_medians(ids0, ok, V, moments[0], nseg))
        with jax.named_scope("wide"):
            with jax.named_scope("moments"):
                cnt, sm, sq = _wide_moments(ids0, ok, V, nseg)
            with jax.named_scope("medians"):
                if _groups_rows(V.shape[0], nseg):
                    return (cnt, sm, sq, *_grouped_picks(ids0, valid, ok, V, cnt, nseg))
                return (cnt, sm, sq, *_sort_picks(ids0, ok, V, cnt, nseg))


def aggregator(
    idf: Table, list_of_cols, list_of_aggs, time_col: str, granularity_format: str = "%Y-%m-%d", **_ignored
) -> pd.DataFrame:
    """(:1721) groupBy over the formatted timestamp → aggregated frame.

    Standard year→second prefix formats bucket ON DEVICE (civil kernels +
    segment reductions; only the small per-bucket result frame reaches
    host).  Exotic formats fall back to a host groupby with a warning."""
    cols = _cols(list_of_cols)
    aggs = _cols(list_of_aggs)
    bad = [a for a in aggs if a not in _AGG_FUNCS]
    if bad:
        raise TypeError(f"Invalid aggregate function(s): {bad}")
    tcol = _ts_col(idf, time_col)
    grain = _format_grain(granularity_format)
    if grain is None:
        warnings.warn(
            f"aggregator: non-standard granularity_format {granularity_format!r}; "
            "falling back to host groupby"
        )
        return _aggregator_host(idf, cols, aggs, time_col, granularity_format)

    # bucket ids + span min/max in ONE dispatch, and the lo-offset
    # subtraction folds into the aggregate program below
    ids, lo_d, hi_d = _bucket_ids_minmax(tcol.data, tcol.mask, grain)
    lo, hi = int(lo_d), int(hi_d)
    if lo > hi:  # all-null time column: empty result
        return pd.DataFrame(columns=[time_col] + [f"{c}_{a}" for c in cols for a in aggs])
    nseg = hi - lo + 1
    if nseg > 4_000_000:  # degenerate span: seconds-grain over decades
        return _aggregator_host(idf, cols, aggs, time_col, granularity_format)
    V, Mv = idf.numeric_block(cols)
    cnt, sm, sq, mn, mx, med = jax.device_get(
        _segment_aggregate(ids, tcol.mask, V, Mv, int(nseg), off=lo)
    )
    return format_segment_aggregate(
        (cnt, sm, sq, mn, mx, med), cols, aggs, time_col, granularity_format,
        lo, grain)


def format_segment_aggregate(agg, cols, aggs, time_col, granularity_format,
                             lo: int, grain: str) -> pd.DataFrame:
    """Host frame from one grain's (cnt, sm, sq, mn, mx, med) aggregate —
    the ONE copy of the aggregator's bucket formatting, shared with the
    ts-analyzer's fused three-grain dispatch."""
    cnt, sm, sq, mn, mx, med = agg
    present = cnt.max(axis=0) > 0  # buckets with any data
    idx = np.nonzero(present)[0]
    keys = pd.Series(
        _bucket_start_secs(idx + lo, grain).astype("datetime64[s]")
    ).dt.strftime(granularity_format)
    out = {time_col: keys.to_numpy()}
    with np.errstate(divide="ignore", invalid="ignore"):
        for j, c in enumerate(cols):
            n = cnt[j][idx]
            for a in aggs:
                if a == "count":
                    vals = n
                elif a == "sum":
                    vals = sm[j][idx]
                elif a == "mean":
                    vals = np.where(n > 0, sm[j][idx] / np.maximum(n, 1), np.nan)
                elif a == "min":
                    vals = np.where(n > 0, mn[j][idx], np.nan)
                elif a == "max":
                    vals = np.where(n > 0, mx[j][idx], np.nan)
                elif a == "median":
                    vals = np.where(n > 0, med[j][idx], np.nan)
                else:  # stddev (sample)
                    var = (sq[j][idx] - sm[j][idx] ** 2 / np.maximum(n, 1)) / np.maximum(n - 1, 1)
                    vals = np.where(n > 1, np.sqrt(np.maximum(var, 0)), np.nan)
                out[f"{c}_{a}"] = vals
    return pd.DataFrame(out)


def _aggregator_host(idf: Table, cols, aggs, time_col, granularity_format) -> pd.DataFrame:
    s = _ts_series(idf, time_col)
    key = s.dt.strftime(granularity_format)
    data = {time_col: key}
    for c in cols:
        col = idf.columns[c]
        vals = np.asarray(jax.device_get(col.data))[: idf.nrows].astype(float)
        vals[~np.asarray(jax.device_get(col.mask))[: idf.nrows]] = np.nan
        data[c] = vals
    df = pd.DataFrame(data)
    pa = [a if a != "stddev" else "std" for a in aggs]
    out = df.groupby(time_col)[cols].agg(pa)
    out.columns = [f"{c}_{a if a != 'std' else 'stddev'}" for c, a in out.columns]
    return out.reset_index()


def window_aggregator(
    idf: Table,
    list_of_cols,
    list_of_aggs,
    order_col: str,
    window_type: str = "expanding",
    window_size: int = 3,
    partition_col: str = "",
    output_mode: str = "append",
    **_ignored,
) -> Table:
    """(:1824) expanding / rolling window aggregates ordered by a ts col —
    device cumsum / reduce-window kernels (pandas min_periods semantics:
    rolling needs a full window of valid values, expanding needs one).
    ``partition_col`` restarts every window at its group boundary
    (reference :1899-1905 Window.partitionBy)."""
    argument_checker("window_aggregator", {"output_mode": output_mode})
    ocol = _ts_col(idf, order_col)
    aggs = _cols(list_of_aggs)
    w = int(window_size)
    pcode = None
    if partition_col:
        pc = idf.columns[partition_col]
        if pc.kind != "cat":
            raise TypeError("partition_col must be a categorical column")
        pcode = pc.data
    odf = idf
    for c in _cols(list_of_cols):
        col = idf.columns[c]
        for a in aggs:
            if a not in _AGG_FUNCS:
                raise TypeError(f"Invalid aggregate function {a}")
            if a == "median" and window_type == "expanding":
                # expanding median has no O(n) device form; host fallback
                vals_h, ok_h = _expanding_median_host(idf, c, order_col, partition_col)
                rt = get_runtime()
                v = vals_h.astype(np.float64)
                v[~ok_h] = np.nan
                newc = _host_to_column(v, idf.nrows, idf.pad_target(), rt)
                odf = odf.with_column(f"{c}_{a}_{window_type}", newc)
                continue
            vals, ok = _window_program(
                ocol.data, ocol.mask, col.data.astype(jnp.float32), col.mask,
                idf.row_mask(), a, window_type, w, pcode,
            )
            odf = _emit_num(odf, f"{c}_{a}_{window_type}", vals, ok, "append", "")
        if output_mode == "replace":
            odf = odf.drop([c])
    return odf


def _expanding_median_host(idf: Table, c: str, order_col: str, partition_col: str = ""):
    s = _ts_series(idf, order_col)
    col = idf.columns[c]
    vals = np.asarray(jax.device_get(col.data))[: idf.nrows].astype(float)
    vals[~np.asarray(jax.device_get(col.mask))[: idf.nrows]] = np.nan
    back = np.empty(idf.nrows)
    if partition_col:
        pc = idf.columns[partition_col]
        codes = np.asarray(jax.device_get(pc.data))[: idf.nrows]
        order = np.lexsort((s.to_numpy(), codes))
        ser = pd.Series(vals[order])
        res = ser.groupby(codes[order]).expanding().median().to_numpy()
        back[order] = res
    else:
        order = np.argsort(s.to_numpy(), kind="stable")
        res = pd.Series(vals[order]).expanding().median().to_numpy()
        back[order] = res
    return back, ~np.isnan(back)


def _segmented_cummin(x, newseg):
    """Running min that restarts where ``newseg`` is True — an associative
    scan over (boundary, min) pairs."""

    def combine(a, b):
        fa, ma = a
        fb, mb = b
        return fa | fb, jnp.where(fb, mb, jnp.minimum(ma, mb))

    _, out = jax.lax.associative_scan(combine, (newseg, x))
    return out


def _window_program(osecs, omask, v, mv, row_valid, agg, window_type, w, pcode=None):
    """``pcode`` (int32 partition codes) makes every window restart at its
    partition boundary: rows lex-sort by (partition, ts) and cumulatives
    subtract their value at the segment start (reference :1899-1905
    Window.partitionBy).  On a multi-device mesh the 1-D arrays replicate
    (size-guarded) so the ts argsorts stay device-local."""
    from anovos_tpu.shared.runtime import replicate_gate

    return _window_program_jit(
        osecs, omask, v, mv, row_valid, agg, window_type, w, pcode,
        cp=replicate_gate(osecs, omask, v, mv, row_valid, pcode),
    )


@_functools.partial(jax.jit, static_argnames=("agg", "window_type", "w", "cp"))
def _window_program_jit(osecs, omask, v, mv, row_valid, agg, window_type, w,
                        pcode=None, *, cp=False):
    from anovos_tpu.shared.runtime import replicated

    osecs, omask = replicated(osecs, cp), replicated(omask, cp)
    v, mv = replicated(v, cp), replicated(mv, cp)
    row_valid = replicated(row_valid, cp)
    if pcode is not None:
        pcode = replicated(pcode, cp)
    rows = v.shape[0]
    key = jnp.where(omask, osecs, _I32_BIG)
    order = jnp.argsort(key, stable=True)
    if pcode is not None:  # stable two-pass lexsort: ts first, partition second
        order = order[jnp.argsort(pcode[order], stable=True)]
        po = pcode[order]
        newseg = jnp.concatenate([jnp.ones(1, bool), po[1:] != po[:-1]])
    else:
        po = None
        newseg = jnp.zeros(rows, bool).at[0].set(True)
    # index of each row's segment start (cummax propagates the last boundary)
    seg_start = jax.lax.cummax(jnp.where(newseg, jnp.arange(rows), 0))
    vo = v[order]
    mo = mv[order]
    vz = jnp.where(mo, vo, 0.0)
    cnt = jnp.cumsum(mo.astype(jnp.float32))
    cs = jnp.cumsum(vz)
    cq = jnp.cumsum(vz * vz)
    # cumulatives at the element just before the segment start (0 for row 0)
    def base(c):
        prev = jnp.concatenate([jnp.zeros(1, c.dtype), c])[seg_start]
        return prev

    cnt0, cs0, cq0 = base(cnt), base(cs), base(cq)
    # positions since segment start, for rolling windows that must not
    # reach into the previous partition
    idx = jnp.arange(rows)
    in_seg = idx - seg_start + 1  # rows available within the segment
    if window_type == "expanding":
        n = cnt - cnt0
        s = cs - cs0
        q = cq - cq0
        ok = n >= 1
        if agg == "min":
            res = _segmented_cummin(jnp.where(mo, vo, jnp.inf), newseg)
        elif agg == "max":
            res = -_segmented_cummin(jnp.where(mo, -vo, jnp.inf), newseg)
    else:  # rolling, min_periods = w
        pad = jnp.zeros(w, jnp.float32)
        shifted = lambda c: jnp.concatenate([pad.astype(c.dtype), c])[:rows]
        # window start = max(i - w + 1, segment start): clamp the subtracted
        # cumulative to the segment base
        n = jnp.minimum(cnt - shifted(cnt), cnt - cnt0)
        s = jnp.where(in_seg >= w, cs - shifted(cs), cs - cs0)
        q = jnp.where(in_seg >= w, cq - shifted(cq), cq - cq0)
        ok = (n >= w) & (in_seg >= w)
        if agg in ("min", "max", "median"):
            # windowed gather: (rows, w) value matrix per position
            pos = jnp.arange(rows)[:, None] - (w - 1) + jnp.arange(w)[None, :]
            safe = jnp.clip(pos, 0, rows - 1)
            Wv = jnp.where(pos >= 0, vo[safe], jnp.nan)
            Wm = (pos >= 0) & mo[safe] & (pos >= seg_start[:, None])
            if agg == "min":
                res = jnp.where(Wm, Wv, jnp.inf).min(axis=1)
            elif agg == "max":
                res = jnp.where(Wm, Wv, -jnp.inf).max(axis=1)
            else:
                Ws = jnp.sort(jnp.where(Wm, Wv, jnp.inf), axis=1)
                res = (Ws[:, (w - 1) // 2] + Ws[:, w // 2]) / 2
    if agg == "count":
        res = n
        # pandas count gates on window ROW coverage, not valid-value count:
        # NaN only while the window extends past the start of the series
        if window_type == "rolling":
            ok = in_seg >= w
        else:
            ok = jnp.ones_like(ok)
    elif agg == "sum":
        res = s
    elif agg == "mean":
        res = s / jnp.maximum(n, 1)
    elif agg == "stddev":
        var = (q - s * s / jnp.maximum(n, 1)) / jnp.maximum(n - 1, 1)
        res = jnp.sqrt(jnp.maximum(var, 0.0))
        ok = ok & (n >= 2)
    elif agg == "median" and window_type != "expanding":
        pass  # computed above
    # scatter back to original row order; padding rows (beyond nrows) must
    # come back masked — they sort to the end and would otherwise inherit a
    # running count ≥ min_periods (Table invariant: mask False on padding)
    inv = jnp.zeros(rows, jnp.int32).at[order].set(jnp.arange(rows, dtype=jnp.int32))
    out = res[inv]
    okb = ok[inv] & row_valid
    # results persist as Table columns: hand them back ROW-sharded, not
    # replicated — N resident copies per appended column otherwise
    from anovos_tpu.shared.runtime import row_sharded

    return (
        row_sharded(jnp.where(okb, out, 0.0).astype(jnp.float32), cp),
        row_sharded(okb, cp),
    )


def lagged_ts(
    idf: Table,
    list_of_cols,
    lag: int = 1,
    output_type: str = "ts",
    tsdiff_unit: str = "days",
    order_col: str = "",
    partition_col: str = "",
    output_mode: str = "append",
    **_ignored,
) -> Table:
    """(:1933) lag a ts column (ordered by itself or order_col) and
    optionally emit the lag difference — argsort + shift + inverse scatter,
    one device program per column.  ``partition_col`` lags within each group
    only (reference :1939 Window.partitionBy)."""
    argument_checker("lagged_ts", {"output_mode": output_mode})
    odf = idf
    lag = int(lag)
    pcode = None
    if partition_col:
        pc = idf.columns[partition_col]
        if pc.kind != "cat":
            raise TypeError("partition_col must be a categorical column")
        pcode = pc.data
    for c in _cols(list_of_cols):
        col = _ts_col(idf, c)
        kcol = _ts_col(idf, order_col) if order_col else col
        lag_secs, lag_ok = _lag_program(
            col.data, col.mask, kcol.data, kcol.mask, idf.row_mask(), lag, pcode
        )
        name = f"{c}_lag{lag}"
        if output_type == "ts":
            odf = odf.with_column(name, Column("ts", lag_secs, lag_ok, dtype_name="timestamp"))
        else:  # ts_diff
            div = float(_div_for(tsdiff_unit))
            diff, ok = _lag_diff_program(col.data, col.mask, lag_secs, lag_ok, div)
            odf = _emit_num(odf, name + "_diff", diff, ok, "append", "")
        if output_mode == "replace":
            odf = odf.drop([c])
    return odf


def _lag_program(secs, mask, ksecs, kmask, row_valid, lag, pcode=None):
    """Mesh note: 1-D inputs replicate (size-guarded) so the ts argsorts
    stay device-local — see _window_program."""
    from anovos_tpu.shared.runtime import replicate_gate

    return _lag_program_jit(
        secs, mask, ksecs, kmask, row_valid, lag, pcode,
        cp=replicate_gate(secs, mask, ksecs, kmask, row_valid, pcode),
    )


@_functools.partial(jax.jit, static_argnames=("lag", "cp"))
def _lag_program_jit(secs, mask, ksecs, kmask, row_valid, lag, pcode=None, *, cp=False):
    from anovos_tpu.shared.runtime import replicated

    secs, mask = replicated(secs, cp), replicated(mask, cp)
    ksecs, kmask = replicated(ksecs, cp), replicated(kmask, cp)
    row_valid = replicated(row_valid, cp)
    if pcode is not None:
        pcode = replicated(pcode, cp)
    rows = secs.shape[0]
    key = jnp.where(kmask, ksecs, _I32_BIG)
    order = jnp.argsort(key, stable=True)
    if pcode is not None:  # lexsort (partition, ts); lags stay in-partition
        order = order[jnp.argsort(pcode[order], stable=True)]
    so = secs[order]
    mo = mask[order]
    shift_s = jnp.concatenate([jnp.zeros(lag, so.dtype), so])[:rows]
    shift_m = jnp.concatenate([jnp.zeros(lag, bool), mo])[:rows]
    if pcode is not None:
        po = pcode[order]
        shift_p = jnp.concatenate([jnp.full(lag, -1, po.dtype), po])[:rows]
        shift_m = shift_m & (shift_p == po)
    inv = jnp.zeros(rows, jnp.int32).at[order].set(jnp.arange(rows, dtype=jnp.int32))
    # padding rows sort last and would inherit the tail's mask — re-mask them;
    # row-sharded returns (persisted as Table columns — see _window_program_jit)
    from anovos_tpu.shared.runtime import row_sharded

    return row_sharded(shift_s[inv], cp), row_sharded(shift_m[inv] & row_valid, cp)


@jax.jit
def _lag_diff_program(secs, mask, lsecs, lmask, div):
    ok = mask & lmask
    return (secs - lsecs).astype(jnp.float32) / div, ok
