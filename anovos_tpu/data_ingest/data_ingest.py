"""Dataset I/O and combination (reference: data_ingest/data_ingest.py).

``read_dataset`` (ref :23-51) decodes files on host via pyarrow (CSV/Parquet/
JSON) or the built-in Avro codec, then dictionary-encodes and uploads the
columns row-sharded across the mesh.  ``write_dataset`` (ref :99-117) mirrors
the repartition/coalesce → n-part-files semantics.  ``concatenate_dataset``
(ref :120-152) and ``join_dataset`` (ref :155-198) keep payload columns on
device (vocab-union code remap + device gathers); only join-key matching runs
host-side (SURVEY.md §2.10: "cross-shard joins via … host-side hash partition").
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import logging
import os
import shutil
import threading
import warnings
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.csv as pacsv
import pyarrow.parquet as pq

from anovos_tpu.data_ingest import avro_io
from anovos_tpu.data_ingest import guard
from anovos_tpu.shared.host_pool import UnitsRun, get_host_pool, record_units
from anovos_tpu.shared.runtime import get_runtime
from anovos_tpu.shared import table as _table
from anovos_tpu.shared.table import (
    FETCH_PHASE, Column, Table, _host_to_column, _pad_to, arrow_typed_kind, arrow_typed_to_numpy,
    host_table_frame)
from anovos_tpu.shared.utils import ends_with, pairwise_reduce, parse_cols

logger = logging.getLogger(__name__)

# one-shot notice when the pyarrow CSV checkpoint writer falls back to
# pandas (mixed-format directories must be observable, not silent).
# Lock-guarded: concurrent async-writer threads checkpointing CSVs race
# this flag, and an unsynchronized check-then-set could log the notice
# N times or (worse, on sufficiently relaxed memory models) tear — the
# round-10 satellite replaces the bare module global with a lock.
_PANDAS_CSV_FALLBACK_LOCK = threading.Lock()
_PANDAS_CSV_FALLBACK_LOGGED = False


def _csv_fallback_first_notice() -> bool:
    """True exactly once per process (thread-safe one-shot)."""
    global _PANDAS_CSV_FALLBACK_LOGGED
    with _PANDAS_CSV_FALLBACK_LOCK:
        if _PANDAS_CSV_FALLBACK_LOGGED:
            return False
        _PANDAS_CSV_FALLBACK_LOGGED = True
        return True

_EXTENSIONS = {
    "csv": (".csv",),
    "parquet": (".parquet", ".pq"),
    "avro": (".avro",),
    "json": (".json", ".json.gz", ".jsonl"),
}


def _resolve_files(file_path: str, file_type: str) -> List[str]:
    if os.path.isfile(file_path):
        return [file_path]
    if os.path.isdir(file_path):
        exts = _EXTENSIONS.get(file_type, ())
        files = sorted(
            f
            for f in glob.glob(os.path.join(file_path, "*"))
            if f.endswith(exts) or (os.path.basename(f).startswith("part-") and not f.endswith((".crc", "_SUCCESS")))
        )
        files = [f for f in files if not os.path.basename(f).startswith((".", "_"))]
        if files:
            return files
    matched = sorted(glob.glob(file_path))
    if matched:
        out = []
        for m in matched:
            out.extend(_resolve_files(m, file_type))
        return out
    raise FileNotFoundError(f"no {file_type} files at {file_path}")


def shard_files_for_process(files: List[str]) -> List[str]:
    """Per-host slice of a part-file list for EXPLICIT multi-host ingest.

    Not applied automatically by read_dataset: process-local reads must be
    assembled into one global array (jax.make_array_from_process_local_data
    with a globally-agreed row count) before any collective runs, and
    metadata/stats reads must stay complete on every host.  A multi-host
    loader should read its slice, all-gather row counts, and build global
    Tables; until that loader lands, read_dataset is global-per-process.
    """
    import jax as _jax

    if _jax.process_count() <= 1:
        return files
    return files[_jax.process_index() :: _jax.process_count()]


# The part files of a read of this many bytes or more are decoded side by side
# on the host pool; a smaller read's in a loop.  What ``read_host_frame`` can
# see of a read's size before it has decoded it is its files' bytes: 8 MiB of
# parquet is 1.4-2.2 x 10^5 rows of the benchmark's tables, about where
# ``shared/table.py`` starts to run a frame's column units side by side
# (``_POOLED_COLUMNS_MIN_ROWS``; a shorter frame's arrays go to the device
# by typed block on the calling thread).  Once the parts are decoded their
# rows are known: the columns of the frame assembled from them are units of
# the pool by that rule of rows itself (``_assemble_frames``).
_POOLED_DECODE_MIN_BYTES = 1 << 23


def _file_bytes(f: str) -> int:
    """Size of a part file for its decode span; 0 where it cannot be read
    (the guarded read that follows reports that fault)."""
    try:
        return os.path.getsize(f)
    except OSError:
        return 0


def _coerce_numeric_strings(decoded: dict) -> dict:
    """Schema-inference parity for the decoded-Table path: a string column
    whose every value parses numeric becomes numeric (the pandas route's
    inferSchema re-coercion).  Cheap — the parse runs over the VOCAB."""
    from anovos_tpu.shared.native import NativeEncodedStrings

    out = {}
    for name, arr in decoded.items():
        if isinstance(arr, NativeEncodedStrings) and len(arr.vocab):
            parsed = pd.to_numeric(pd.Series(arr.vocab.astype(str)), errors="coerce")
            if parsed.notna().all():
                lut = parsed.to_numpy(np.float64)
                vals = np.full(len(arr.codes), np.nan)
                valid = arr.codes >= 0
                vals[valid] = lut[arr.codes[valid]]
                out[name] = vals
                continue
        out[name] = arr
    return out


def read_dataset(file_path: str, file_type: str, file_configs: Optional[dict] = None) -> Table:
    """Read csv/parquet/avro/json into a device Table.

    ``file_configs`` mirrors the Spark reader options the reference forwards
    (data_ingest.py:23-51): ``header``, ``delimiter``/``sep``, ``inferSchema``
    (always on — pyarrow infers).  Multi-file (part-file) directories are
    concatenated host-side before upload.
    """
    from anovos_tpu.obs import get_metrics, get_tracer

    cfg = dict(file_configs or {})
    tracer = get_tracer()
    with tracer.phase("io:read_dataset", cat="io", path=str(file_path),
                      file_type=file_type):
        if jax.process_count() > 1:
            # multi-host runtime: each host reads its file slice and columns
            # are assembled into global arrays (distributed_ingest module)
            from anovos_tpu.data_ingest.distributed_ingest import read_dataset_distributed

            out = read_dataset_distributed(file_path, file_type, file_configs)
        else:
            out = None
            files = _resolve_files(file_path, file_type)
            if file_type == "avro":
                # native-friendly path: per-file decode straight to Tables
                # (string columns stay dictionary codes), row-union via
                # concatenate_dataset's vocab-union remap.  Falls through to
                # pandas only on a SCHEMA this codec can't express (empty
                # decode); an unreadable part is quarantined by the guard —
                # re-attempting it through pandas would just fail (and
                # quarantine) again.
                pol = guard.policy_from_env()
                tables = []
                bad = set()
                for f in files:
                    with tracer.phase("ingest/decode", cat="io",
                                      bytes=_file_bytes(f)) as sp:
                        decoded = guard.guarded_part_read(
                            f, lambda f=f: avro_io.read_avro(f),
                            file_type="avro", policy=pol)
                        if decoded is None:
                            bad.add(f)
                            continue
                        if not decoded:
                            tables = None
                            break
                        n = len(next(iter(decoded.values())))
                        sp.add(rows=n)
                    tables.append(Table.from_numpy(_coerce_numeric_strings(decoded), nrows=n))
                if tables is not None and not tables:
                    raise guard.IngestError(
                        f"every avro part under {file_path} was quarantined "
                        f"({len(bad)} part(s)) — no schema left to build a Table")
                # empty-decode fallback: don't re-attempt (and re-quarantine)
                # the parts the guard already set aside
                files = [f for f in files if f not in bad]
                if tables:
                    with tracer.phase("ingest/assemble", cat="io"):
                        out = tables[0] if len(tables) == 1 else concatenate_dataset(
                            *tables, method_type="name")
            if out is None:
                df = read_host_frame(files, file_type, cfg)
                out = Table.from_pandas(df)
    get_metrics().counter("rows_ingested_total",
                          "rows read into device Tables").inc(out.nrows)
    return out


@guard.raw_reader
def _read_one_part(f: str, file_type: str, cfg: dict) -> pd.DataFrame:
    """RAW single-part decode — the guard layer's designated reader.

    Only :func:`guarded part reads <anovos_tpu.data_ingest.guard.guarded_part_read>`
    may call this (graftcheck GC012 keeps it that way): a decode failure
    here is exactly the fault class the guard retries and quarantines."""
    if file_type == "csv":
        delim = str(cfg.get("delimiter", cfg.get("sep", ",")))
        header = cfg.get("header", True)
        header = str(header).lower() in ("true", "1")
        ropts = pacsv.ReadOptions(autogenerate_column_names=not header)
        popts = pacsv.ParseOptions(delimiter=delim)
        tbl = pacsv.read_csv(f, read_options=ropts, parse_options=popts)
        # pyarrow does NOT fail on undecodable UTF-8 — it silently types the
        # column binary, and those bytes objects would poison every cat
        # vocab downstream.  Surface it as the decode failure it is (with
        # the exact byte offset from the first offending value) so the
        # guard quarantines the part instead.
        import pyarrow.types as pat

        bad = [fld.name for fld in tbl.schema
               if pat.is_binary(fld.type) or pat.is_large_binary(fld.type)]
        if bad:
            for chunk in tbl.column(bad[0]).chunks:
                for v in chunk:
                    b = v.as_py()
                    if b is not None:
                        b.decode("utf-8")  # raises UnicodeDecodeError w/ offset
            raise ValueError(f"CSV part {f}: columns {bad} are not valid UTF-8")
        return tbl.to_pandas()
    if file_type == "parquet":
        # pd.read_parquet but for the columns whose Arrow type pandas has no
        # numpy dtype for: they stay Arrow until _assemble_frames converts them;
        # and for integers with nulls, which stay integers
        tbl = pq.read_table(f)
        return tbl.to_pandas(types_mapper=_part_types_mapper(tbl))
    if file_type == "avro":
        from anovos_tpu.shared.native import NativeEncodedStrings

        dec = avro_io.read_avro(f)
        dec = {
            k: (v.to_object_array() if isinstance(v, NativeEncodedStrings) else v)
            for k, v in dec.items()
        }
        return pd.DataFrame(dec)
    if file_type == "json":
        opener = gzip.open if f.endswith(".gz") else open
        with opener(f, "rt") as fh:
            return pd.read_json(fh, lines=True)
    raise ValueError(f"unsupported file_type: {file_type}")


def _keep_arrow_typed(t: pa.DataType) -> Optional[pd.ArrowDtype]:
    """``types_mapper`` of a part's ``to_pandas``: a decimal or a date (the
    types that pandas would make one Python object a value of: a
    ``decimal.Decimal``, a ``datetime.date``) stays an Arrow array in the part
    frame; every other type converts as ``pd.read_parquet`` converts it."""
    dtype = pd.ArrowDtype(t)
    return dtype if arrow_typed_kind(dtype) else None


_NULLABLE_INTEGERS = {
    pa.int8(): pd.Int8Dtype(), pa.int16(): pd.Int16Dtype(), pa.int32(): pd.Int32Dtype(),
    pa.int64(): pd.Int64Dtype(), pa.uint8(): pd.UInt8Dtype(), pa.uint16(): pd.UInt16Dtype(),
    pa.uint32(): pd.UInt32Dtype(), pa.uint64(): pd.UInt64Dtype()}


def _part_types_mapper(tbl: pa.Table):
    """``types_mapper`` of one parquet part: :func:`_keep_arrow_typed`, and
    an integer type of which a column of this part holds a null becomes
    pandas' nullable integer of that width (values and validity taken from
    Arrow's buffers), where ``to_pandas`` alone makes such a column float64
    with NaN: exact only to 2^53, and a ``double`` to everything after it.
    A part without a null in the type converts as it always did (a plain
    numpy integer); ``pd.concat`` of the two is the nullable one."""
    nullable = {col.type for col in tbl.columns if col.null_count and col.type in _NULLABLE_INTEGERS}
    if not nullable:
        return _keep_arrow_typed
    return lambda t: _NULLABLE_INTEGERS[t] if t in nullable else _keep_arrow_typed(t)


def _arrow_typed(df: pd.DataFrame) -> List[tuple]:
    """``(column, kind)`` of the columns :func:`_keep_arrow_typed` left in
    Arrow: ``decimal`` or ``date``."""
    return [(c, kind) for c in df.columns if (kind := arrow_typed_kind(df[c].dtype))]


def read_host_frame(files: List[str], file_type: str, cfg: dict) -> pd.DataFrame:
    """Host pandas frame from part files (shared by the single-process and
    multi-host loaders) — GUARDED: each part decodes under the quarantine/
    retry policy, schemas reconcile across parts, and hostile values are
    sanitized at this boundary (anovos_tpu.data_ingest.guard).

    The parts of a read of ``_POOLED_DECODE_MIN_BYTES`` or more are units of
    the host pool (``shared.host_pool``), decoded side by side, each under
    its own guard and its own ``ingest/decode`` span; the frame is assembled
    in file order, a quarantined part left out, and under a ``raise`` policy
    the error is the first bad part's in file order (parts after it may have
    been read by then, which a loop would not have started).
    ``decode_workers`` (threads that decoded a part; 0 for the loop) and
    ``decode_wall_s`` (first start to last end) go on the row of the pass's
    tree the call runs under, and beside them ``assemble_workers``,
    ``assemble_wall_s`` and ``assemble_columns`` of the columns' units inside
    the one ``ingest/assemble`` span (``_assemble_frames``)."""
    if file_type not in ("csv", "parquet", "avro", "json"):
        raise ValueError(f"unsupported file_type: {file_type}")
    from anovos_tpu.obs import get_tracer

    tracer = get_tracer()
    pol = guard.policy_from_env()
    sizes = {f: _file_bytes(f) for f in files}

    def decode(f: str):
        with tracer.phase("ingest/decode", cat="io", bytes=sizes[f]) as sp:
            df = guard.guarded_part_read(
                f, lambda: _read_one_part(f, file_type, cfg),
                file_type=file_type, policy=pol)
            if df is not None:
                sp.add(rows=len(df))
            return df

    ran = get_host_pool().run(
        decode, files, side_by_side=sum(sizes.values()) >= _POOLED_DECODE_MIN_BYTES)
    record_units("decode", ran)
    frames = [(f, df) for f, df in zip(files, ran.results) if df is not None]
    if not frames:
        raise guard.IngestError(
            f"every {file_type} part was quarantined ({len(files)} file(s), "
            f"first: {files[0] if files else '<none>'}) — no schema left to "
            "build a frame")
    with tracer.phase("ingest/assemble", cat="io") as sp:
        # how many columns arrive in their Arrow type and are converted here:
        # 0 says that this program converts and had nothing to convert
        sp.add(arrow_typed=len(_arrow_typed(frames[0][1])))
        df, units = _assemble_frames(frames, cfg, pol)
    # after the span: the counts belong on the row the span is a child of
    record_units("assemble", units, columns=df.shape[1])
    return df


def _assemble_frames(frames: List, cfg: dict, pol) -> Tuple[pd.DataFrame, UnitsRun]:
    """One frame from the decoded parts: schemas reconciled on this thread
    (cheap where they agree, order-dependent where they do not), then the
    frame a column at a time (:func:`_assemble_column`: no column's work
    reads another column), and the frame built once over the finished
    columns' arrays.  The columns of a frame of ``_POOLED_COLUMNS_MIN_ROWS``
    rows or more (the rule of the uploads that follow) are units of the host
    pool side by side, a shorter frame's run here one after the other: the
    same function either way.  Also how the units ran, for the caller's
    ``assemble_workers`` / ``assemble_wall_s``."""
    aligned = guard.reconcile_frames(frames, pol)
    infer = str(cfg.get("inferSchema", True)).lower() in ("true", "1", "none")
    names = list(aligned[0].columns)
    rows = sum(len(part) for part in aligned)
    # the columns' views are taken here: a part frame is shared by every unit
    parts = {c: [part[c] for part in aligned] for c in names}
    ran = get_host_pool().run(
        lambda c: _assemble_column(c, parts[c], infer, pol), names,
        side_by_side=rows >= _table._POOLED_COLUMNS_MIN_ROWS)
    index = aligned[0].index if len(aligned) == 1 else pd.RangeIndex(rows)
    # an object column goes in as a Series that states its dtype: of a bare
    # object array the constructor makes ``str`` where it holds only strings
    columns = {c: pd.Series(v, index=index, dtype=object, copy=False) if v.dtype == object else v
               for c, v in zip(names, ran.results)}
    return pd.DataFrame(columns, index=index, copy=False), ran


def _column_array(s: pd.Series):
    """A Series' values as the frame is built over them: the ndarray itself
    for a numpy dtype (the frame's constructor scans a wrapped one for
    nulls), the extension array for any other."""
    return np.asarray(s.array) if isinstance(s.dtype, np.dtype) else s.array


def _join_parts(parts: List[pd.Series]):
    """What ``pd.concat`` of the part frames makes of one column, as its
    array (:func:`_column_array`): where the parts agree on the dtype (the
    common case) by the call pandas itself ends in, without a Series, an
    Index and a name's check a part on the way (0.3 ms a column, under the
    GIL, which is the whole assembly of a short frame): a numpy column one
    array of the final length, an extension column its type's own join (a
    nullable integer its values and mask, an Arrow-backed one, strings or a
    decimal or date not yet converted, its chunks).  Parts that drifted
    apart take ``pd.concat`` and its promotion rules."""
    dtype = parts[0].dtype
    if len(parts) == 1:
        return _column_array(parts[0])
    if any(part.dtype != dtype for part in parts):
        return _column_array(pd.concat(parts, ignore_index=True))
    arrays = [_column_array(part) for part in parts]
    if isinstance(dtype, np.dtype):
        return np.concatenate(arrays)
    return type(arrays[0])._concat_same_type(arrays)


def _assemble_column(name, parts: List[pd.Series], infer: bool, pol):
    """One column of the frame a read returns, from the column's part of
    every part frame, as the array the frame is built over."""
    values = _join_parts(parts)
    kind = arrow_typed_kind(values.dtype)
    if kind:
        # what _keep_arrow_typed left in Arrow, converted once for the whole
        # column, in Arrow and numpy: a decimal to float64 (``num``;
        # ``_plain_to_host`` adds the exact wide pair where f32 does not hold
        # the value), a date to ``datetime64[s]`` at midnight (``ts``, an
        # ``other`` column as in the upstream; null: NaT)
        from anovos_tpu.obs import get_tracer

        with get_tracer().phase("ingest/convert", cat="io", kind=kind, rows=len(values)):
            values = arrow_typed_to_numpy(pd.Series(values, copy=False))
    if infer and (values.dtype == object or str(values.dtype) in ("string", "str")):
        # whole-dataset schema inference (Spark inferSchema parity): per-part
        # readers can disagree (an all-null part decodes as string/null), so
        # re-coerce an object column that is numeric across ALL parts
        s = pd.Series(values, dtype=values.dtype, copy=False)
        nonnull = s.notna()
        if not nonnull.any():
            values = _column_array(pd.to_numeric(s, errors="coerce"))  # all-null column → numeric NaN column
        # cheap pre-check: a genuinely-string column (the common case) is
        # rejected on a small head sample instead of paying a full-column
        # to_numeric per string column
        elif not pd.to_numeric(s[nonnull].iloc[:1024], errors="coerce").isna().any():
            coerced = pd.to_numeric(s, errors="coerce")
            if coerced[nonnull].notna().all():
                values = _column_array(coerced)
    # hostile-value sanitization LAST (after inferSchema may have produced a
    # new float column): downstream device kernels never see inf/overflow
    if values.dtype.kind == "f":
        joined_as_they_are = all(part.dtype == values.dtype for part in parts)
        fixed = guard.sanitize_values(
            name, values if isinstance(values, np.ndarray) else pd.Series(values, copy=False).to_numpy(), pol,
            gate_over=[part.to_numpy() for part in parts] if joined_as_they_are else None)
        if fixed is not None:
            return fixed
    return values


def write_dataset(
    idf: Union[Table, pd.DataFrame],
    file_path: str,
    file_type: str,
    file_configs: Optional[dict] = None,
    column_order: Optional[List[str]] = None,
) -> None:
    """Write a Table as spark-style part files (reference :54-117).

    ``repartition`` in file_configs sets the number of part files; ``mode``
    ∈ {overwrite, append, error}.  Other keys (header/delimiter) map to the
    writers.

    ``idf`` is on the device or on the host, and its type says which.  A
    ``Table`` is fetched (``Table.to_pandas``: the arrays' copies in flight
    ahead of the column being converted, one ``d2h`` transfer record a
    column; on the pass's thread a row ``write/column`` a column under
    ``write/d2h``).  A pandas frame (a stats table a node
    computed on the host) is written from where it is:
    ``host_table_frame`` gives the frame that ``Table.from_pandas`` +
    ``to_pandas`` would, so the part files have the bytes they had when the
    frame went through the device, and the write touches no device, books
    no transfer and opens no ``ingest/*`` span; it puts ``host_frame=1`` on
    the ``write:<key>`` span it runs under.

    A parquet part is pyarrow's default file (snappy, one row group up to
    1,048,576 rows) except for the page encoding of float columns whose
    values hardly repeat: those are written plain, not through a dictionary
    (``_dictionary_columns``); a reader gets the same values bit for bit.
    """
    cfg = dict(file_configs or {})
    mode = cfg.pop("mode", "error")
    repartition = int(cfg.pop("repartition", 1) or 1)
    on_host = isinstance(idf, pd.DataFrame)
    if column_order:
        idf = idf[list(column_order)] if on_host else idf.select(column_order)
    if os.path.exists(file_path):
        if mode == "overwrite":
            shutil.rmtree(file_path) if os.path.isdir(file_path) else os.remove(file_path)
        elif mode == "error":
            raise FileExistsError(f"{file_path} exists (mode=error)")
    os.makedirs(file_path, exist_ok=True)
    from anovos_tpu.obs import get_tracer

    tracer = get_tracer()
    if on_host:
        df = host_table_frame(idf)
        write_span = tracer.enclosing("artifact")
        if write_span is not None:
            write_span.add(host_frame=1)
    else:
        # the fetch of a device table: every array of every column, padding included
        arrays = [a for c in idf.columns.values() for a in c.device_arrays()]
        with _write_phase(tracer, FETCH_PHASE, arrays=len(arrays), bytes=sum(a.nbytes for a in arrays)):
            df = idf.to_pandas()
    with _write_phase(tracer, "write/" + file_type, rows=len(df)) as write_files:
        written, plain_columns = _write_parts(df, file_path, file_type, cfg, repartition)
        try:
            n_bytes = sum(os.path.getsize(f) for f in written)
        except OSError:
            n_bytes = 0
        if write_files is not None:
            write_files.add(bytes=n_bytes)
            if file_type == "parquet":
                write_files.add(plain_columns=plain_columns,
                                dict_columns=len(written) * df.shape[1] - plain_columns)
    # incremental-recompute capture: the pyarrow writers bypass the
    # builtins.open hook, so this choke point books every part explicitly
    # (a no-op unless a cache recorder is active on this thread)
    from anovos_tpu.cache import capture as _capture

    for f in written + [os.path.join(file_path, "_SUCCESS")]:
        _capture.record_artifact(f)
    from anovos_tpu.obs import get_metrics

    reg = get_metrics()
    reg.counter("bytes_written_total", "artifact bytes written to disk").inc(n_bytes)
    reg.counter("rows_written_total", "rows persisted by write_dataset").inc(len(df))
    reg.counter(
        "parquet_plain_columns_total",
        "parquet columns (summed over part files) written PLAIN because a dictionary could not pay",
    ).inc(plain_columns)


def _write_phase(tracer, name: str, **counts):
    """A row of the pass's phases where the write runs on the pass's own
    thread (the final dataset under ``write_main``); a queued write on a
    writer thread is its ``write:<key>`` span already and opens nothing more."""
    return tracer.phase(name, **counts) if tracer.in_pass() else contextlib.nullcontext()


# The encoding plan of a parquet part.  A dictionary of doubles pays when the
# distinct values are far fewer than the rows: then the pages hold short
# indices.  Where nearly every value is distinct the dictionary page is the
# column over again, the indices come on top, and every value is hashed into a
# memo table on the way (1,000 latent columns of 25,000 rows: 2.0 s and 201 MB
# with dictionaries, 0.9 s and 154 MB without; PERF.md section 6, PR 48).
# Arrow drops a dictionary by itself only once its page passes
# ``dictionary_pagesize_limit`` (1 MiB = 131,072 doubles), which a part under
# 131,072 rows never reaches, so the choice is made here, per float column,
# from a strided sample of at most ``_PLAN_SAMPLE_ROWS`` of the part's own
# rows (1,024: of a dictionary that Arrow would keep, at most 131,072 values,
# such a sample shows 1,024^2 / 2 / 131,072 = 4 repeats or more).  A column is
# written plain only where at least ``_PLAIN_MIN_SAMPLE`` sampled values are
# not null (below that a dictionary costs nothing either way, and the file
# keeps the bytes it had) and the sample shows no more repeats than it would
# of a column in which ``_PLAIN_MIN_DISTINCT_SHARE`` of the values are distinct.
_PLAN_SAMPLE_ROWS = 1024
_PLAIN_MIN_SAMPLE = 64
_PLAIN_MIN_DISTINCT_SHARE = 0.9


def _dictionary_columns(part: pd.DataFrame) -> Optional[List[str]]:
    """The columns of ``part`` that keep pyarrow's dictionary encoding, in
    the frame's order, or ``None`` where every column does (the frame then
    goes through the default call).  Only numpy float columns are judged;
    every other dtype keeps the default, as does a frame whose column names
    are not unique strings (``use_dictionary`` names columns by path)."""
    names = list(part.columns)
    if len(set(names)) != len(names) or not all(isinstance(c, str) for c in names):
        return None
    floats = [c for c, t in zip(names, part.dtypes) if isinstance(t, np.dtype) and t.kind == "f"]
    if not floats or len(part) < _PLAIN_MIN_SAMPLE:
        return None
    # the row slice first: a view of <= 1,024 rows, so no full-column pass
    sample = part.iloc[:: -(-len(part) // _PLAN_SAMPLE_ROWS)]
    block = np.sort(sample[floats].to_numpy(dtype=np.float64), axis=0)  # NaN sorts last
    valid = ~np.isnan(block)
    n_valid = valid.sum(axis=0)
    distinct = ((block[1:] != block[:-1]) & valid[1:]).sum(axis=0) + (n_valid > 0)
    # Repeats among s rows drawn from n grow with s * s / n, not with s: a
    # column of 10,000 values looks all distinct to 1,024 rows of a million.
    # So the bar is the distinct share that a sample of this fraction f shows
    # of a column whose values each fill 1 / share rows: share itself where
    # the sample is the whole part, and 1 - (1 / share - 1) * f / 2 as f -> 0.
    f = len(sample) / len(part)
    share = _PLAIN_MIN_DISTINCT_SHARE
    bar = share / f * (1.0 - (1.0 - f) ** (1.0 / share))
    plain = (n_valid >= _PLAIN_MIN_SAMPLE) & (distinct >= bar * n_valid)
    if not plain.any():
        return None
    written_plain = {c for c, p in zip(floats, plain) if p}
    return [c for c in names if c not in written_plain]


def _write_parts(df: pd.DataFrame, file_path: str, file_type: str, cfg: dict,
                 repartition: int) -> Tuple[List[str], int]:
    """``df`` as ``repartition`` part files of ``file_type`` under
    ``file_path`` and the ``_SUCCESS`` marker after them; the part files
    THIS call wrote (append mode must not re-book pre-existing parts), and
    how many columns of them, summed over the parquet parts,
    ``_dictionary_columns`` had written plain."""
    parts = np.array_split(np.arange(len(df)), max(repartition, 1))
    written: List[str] = []
    plain_columns = 0
    for i, part_idx in enumerate(parts):
        # single-part writes (the checkpoint default) skip the fancy-index
        # row copy — df.iloc[arange] materializes a full second frame
        part = df if len(parts) == 1 else df.iloc[part_idx]
        stem = os.path.join(file_path, f"part-{i:05d}")
        if file_type == "csv":
            header = str(cfg.get("header", True)).lower() in ("true", "1")
            delim = str(cfg.get("delimiter", ","))
            try:
                # pyarrow's C++ writer is ~7× pandas' on the checkpoint hot
                # path (booleans land lowercase like Spark's writer).  One
                # formatting trap: pyarrow renders whole-valued floats
                # without the '.0', so a null-free all-integral float64
                # column would reread as int64 — pre-format exactly those
                # columns (C-speed int→str) so the dtype survives.
                part = part.copy(deep=False)
                for c in part.columns:
                    v = part[c]
                    if (
                        v.dtype.kind == "f"
                        and not v.isna().any()
                        and len(v)
                        and np.abs(v.to_numpy()).max() < 2**62
                        and (v.to_numpy() == np.trunc(v.to_numpy())).all()
                    ):
                        part[c] = np.char.add(
                            v.to_numpy().astype(np.int64).astype(str), ".0"
                        ).astype(object)
                pacsv.write_csv(
                    pa.Table.from_pandas(part, preserve_index=False),
                    stem + ".csv",
                    write_options=pacsv.WriteOptions(include_header=header, delimiter=delim),
                )
                written.append(stem + ".csv")
            except Exception as e:
                # arrow conversion limits (mixed-type object columns,
                # duplicate column names in the pre-format loop, ...):
                # pandas handles those.  The except stays broad so the
                # fallback is total, but it logs ONCE with the cause so a
                # mixed-format checkpoint directory is observable, not
                # silent (round-4 advisor); the one-shot is lock-guarded
                # (async-writer threads race it) and metered so the
                # manifest shows every occurrence even after the log
                # went quiet.
                try:
                    from anovos_tpu.obs import get_metrics as _gm

                    _gm().counter(
                        "csv_pandas_fallback_total",
                        "checkpoint CSV parts written by the pandas fallback "
                        "writer (mixed-format directory risk)",
                    ).inc()
                except Exception:
                    pass  # telemetry must not break the fallback it counts
                if _csv_fallback_first_notice():
                    logging.getLogger(__name__).info(
                        "pyarrow CSV writer fell back to pandas for %s "
                        "(%s: %s); later parts may mix formats "
                        "(quoting/boolean case)", stem, type(e).__name__, e)
                part.to_csv(stem + ".csv", index=False, header=header, sep=delim)
                written.append(stem + ".csv")
        elif file_type == "parquet":
            keep = _dictionary_columns(part)
            if keep is None:
                part.to_parquet(stem + ".parquet", index=False)
            else:
                part.to_parquet(stem + ".parquet", index=False, use_dictionary=keep)
                plain_columns += part.shape[1] - len(keep)
            written.append(stem + ".parquet")
        elif file_type == "avro":
            avro_io.write_avro(part, stem + ".avro")
            written.append(stem + ".avro")
        elif file_type == "json":
            part.to_json(stem + ".json", orient="records", lines=True)
            written.append(stem + ".json")
        else:
            raise ValueError(f"unsupported file_type: {file_type}")
    open(os.path.join(file_path, "_SUCCESS"), "w").close()
    return written, plain_columns


# ----------------------------------------------------------------------
# combination
# ----------------------------------------------------------------------
def _concat_columns(cols: List[Column], nrows: List[int], name: str) -> Column:
    from anovos_tpu.obs import devprof

    rt = get_runtime()
    kinds = {c.kind for c in cols}
    if len(kinds) > 1:
        raise TypeError(f"column {name}: mixed kinds {kinds} across concatenated tables")
    kind = kinds.pop()
    # d2h materialization boundary (host-side shard assembly): book the
    # fetched bytes before the device_gets below pull them down.  Wide
    # columns' payloads are EXCLUDED here — they materialize through
    # Column.exact_host, whose own bracket books the (hi, lo) pair, and
    # pre-booking them too would double-count d2h bytes
    devprof.record_transfer(
        "d2h",
        sum(c.mask.nbytes + (0 if c.is_wide else c.data.nbytes) for c in cols),
        0.0, label="data_ingest.concat")
    # host-side assembly: concat is a stage boundary, and device-side eager
    # concatenation of differently-sharded arrays would dispatch independent
    # collective programs per column (rendezvous-interleave hazard — see
    # Table.gather_rows).  device_get assembles shards without collectives.
    if kind == "cat":
        new_vocab = np.unique(np.concatenate([c.vocab for c in cols])).astype(object)
        lookups = []
        for c in cols:
            lk = {v: i for i, v in enumerate(new_vocab)}
            lookups.append(np.array([lk[v] for v in c.vocab], dtype=np.int32) if len(c.vocab) else np.zeros(1, np.int32))
        hosts = []
        for c, n, cm in zip(cols, nrows, lookups):
            h = np.asarray(jax.device_get(c.data))[:n]
            hosts.append(np.where(h >= 0, cm[np.clip(h, 0, len(cm) - 1)], -1).astype(np.int32))
    elif any(c.is_wide for c in cols):
        # wide (exact int64 OR exact float64) in any slice: keep exactness —
        # nulls ride the mask, so nullable slices must NOT degrade silently
        from anovos_tpu.shared.table import wide_int_parts

        total = sum(nrows)
        npad = rt.pad_rows(max(total, 1))
        mask_h = np.concatenate(
            [np.asarray(jax.device_get(c.mask))[:n] for c, n in zip(cols, nrows)]
        )
        int_ok = all(c.is_wide_int or c.data.dtype == jnp.int32 for c in cols)
        if not int_ok:  # float-wide or mixed with float slices: float64 semantics
            parts = [
                c.exact_host(n).astype(np.float64) if c.is_wide
                else np.asarray(jax.device_get(c.data))[:n].astype(np.float64)
                for c, n in zip(cols, nrows)
            ]
            data_h = np.concatenate(parts)
            data_h[~mask_h] = np.nan
            return _host_to_column(data_h, total, npad, rt)
        v64 = np.concatenate(
            [
                c.exact_host(n).astype(np.int64) if c.is_wide_int
                else np.asarray(jax.device_get(c.data))[:n].astype(np.int64)
                for c, n in zip(cols, nrows)
            ]
        )
        v64[~mask_h] = 0  # masked lanes: any value, mask gates all consumers
        whi, wlo = wide_int_parts(v64)
        return Column(
            "num",
            rt.shard_rows(_pad_to(v64.astype(np.float32), npad, np.float32(0))),
            rt.shard_rows(_pad_to(mask_h, npad, False)),
            dtype_name="bigint",
            wide_hi=rt.shard_rows(_pad_to(whi, npad, np.int32(0))),
            wide_lo=rt.shard_rows(_pad_to(wlo, npad, np.int32(-(1 << 31)))),
        )
    else:
        new_vocab = None
        np_dtypes = {np.asarray(jax.device_get(c.data[:1])).dtype for c in cols}
        tgt = np.float32 if len(np_dtypes) > 1 else next(iter(np_dtypes))
        hosts = [np.asarray(jax.device_get(c.data))[:n].astype(tgt) for c, n in zip(cols, nrows)]
    total = sum(nrows)
    npad = rt.pad_rows(max(total, 1))
    data_h = np.concatenate(hosts) if hosts else np.zeros(0, np.float32)
    mask_h = np.concatenate([np.asarray(jax.device_get(c.mask))[:n] for c, n in zip(cols, nrows)])
    data = rt.shard_rows(_pad_to(data_h, npad, data_h.dtype.type(0)))
    mask = rt.shard_rows(_pad_to(mask_h, npad, False))
    return Column(kind, data, mask, vocab=new_vocab, dtype_name=cols[0].dtype_name)


def concatenate_dataset(*idfs: Table, method_type: str = "name") -> Table:
    """Row-union of Tables (reference :120-152).

    "name": columns follow the FIRST table's order; errors if any column of
    the first table is absent elsewhere.  "index": positional, renamed to the
    first table's names.
    """
    if method_type not in ("index", "name"):
        raise TypeError("Invalid input for concatenate_dataset method")
    first = idfs[0]
    names = first.col_names
    aligned = []
    for t in idfs:
        if method_type == "name":
            missing = [c for c in names if c not in t.columns]
            if missing:
                raise ValueError(f"concatenate_dataset: columns {missing} missing")
            aligned.append(t.select(names))
        else:
            if t.ncols != len(names):
                raise ValueError("concatenate_dataset index method: column count mismatch")
            aligned.append(t.rename(dict(zip(t.col_names, names))).select(names))
    cols = OrderedDict(
        (
            name,
            _concat_columns([t.columns[name] for t in aligned], [t.nrows for t in aligned], name),
        )
        for name in names
    )
    return Table(cols, sum(t.nrows for t in aligned))


def _host_keys(t: Table, join_cols: List[str]) -> pd.DataFrame:
    """Join keys as a host frame (decoded values; tiny vs payload)."""
    out = {}
    for c in join_cols:
        col = t.columns[c]
        data = np.asarray(col.data)[: t.nrows]
        mask = np.asarray(col.mask)[: t.nrows]
        if col.kind == "cat":
            vals = np.empty(t.nrows, dtype=object)
            valid = mask & (data >= 0)
            vals[valid] = col.vocab[data[valid]]
            vals[~valid] = None
            out[c] = vals
        elif col.is_wide_int:
            # id-like int64 keys must match exactly — the f32 view collides
            out[c] = pd.arrays.IntegerArray(col.exact_host(t.nrows), ~mask)
        elif col.is_wide:  # exact float64 keys
            vals = col.exact_host(t.nrows).copy()
            vals[~mask] = np.nan
            out[c] = vals
        else:
            vals = data.astype(np.float64)
            vals[~mask] = np.nan
            out[c] = vals
    return pd.DataFrame(out)


def join_dataset(*idfs: Table, join_cols: Union[str, List[str]], join_type: str) -> Table:
    """Key join of Tables (reference :155-198).

    Key matching runs host-side (hash join on the small key frame); payload
    columns move by device gather.  join_type ∈ inner/full/left/right/
    left_semi/left_anti.
    """
    if isinstance(join_cols, str):
        join_cols = [x.strip() for x in join_cols.split("|")]
    all_cols = [c for t in idfs for c in t.col_names]
    nonjoin = [c for c in all_cols if c not in join_cols]
    if len(nonjoin) != len(all_cols) - len(idfs) * len(join_cols):
        raise ValueError("Specified join_cols do not match all the Input Dataframe(s)")
    if len(nonjoin) != len(set(nonjoin)):
        raise ValueError("Duplicate column(s) present in non joining column(s) in Input Dataframe(s)")

    def join2(left: Table, right: Table) -> Table:
        lk = _host_keys(left, join_cols).assign(_li=np.arange(left.nrows))
        rk = _host_keys(right, join_cols).assign(_ri=np.arange(right.nrows))
        how = {"full": "outer", "left_semi": "inner", "left_anti": "left"}.get(join_type, join_type)
        merged = lk.merge(rk, on=join_cols, how=how)
        if join_type == "left_semi":
            li = np.unique(merged["_li"].to_numpy())
            return left.gather_rows(li)
        if join_type == "left_anti":
            anti = merged[merged["_ri"].isna()]
            li = np.unique(anti["_li"].to_numpy()).astype(np.int64)
            return left.gather_rows(li)
        li = merged["_li"].to_numpy()
        ri = merged["_ri"].to_numpy()
        lvalid = ~pd.isna(li)
        rvalid = ~pd.isna(ri)
        li = np.where(lvalid, li, 0).astype(np.int64)
        ri = np.where(rvalid, ri, 0).astype(np.int64)
        lg = left.gather_rows(li, valid=lvalid)
        rg = right.gather_rows(ri, valid=rvalid)
        # key columns: prefer left values, fall back to right (outer join)
        key_frame = merged[join_cols]
        out = OrderedDict()
        for name in left.col_names:
            if name in join_cols:
                s = key_frame[name]
                if str(s.dtype) == "Int64":  # wide-int keys from _host_keys
                    if not s.isna().any():
                        key_arr = s.to_numpy(dtype=np.int64)
                    else:  # null int keys (rare): degrade to float64
                        key_arr = s.astype("float64").to_numpy()
                else:
                    key_arr = np.asarray(s.to_numpy())
                out[name] = _host_to_column(
                    key_arr, len(merged),
                    get_runtime().pad_rows(max(len(merged), 1)), get_runtime(),
                )
            else:
                out[name] = lg.columns[name]
        for name in right.col_names:
            if name not in join_cols:
                out[name] = rg.columns[name]
        return Table(out, len(merged))

    return pairwise_reduce(join2, idfs)


# ----------------------------------------------------------------------
# column ops (reference :201-367)
# ----------------------------------------------------------------------
def delete_column(idf: Table, list_of_cols, print_impact: bool = False) -> Table:
    cols = parse_cols(list_of_cols, idf.col_names)
    odf = idf.drop(cols)
    if print_impact:
        logger.info(f"Before: \nNo. of Columns-  {idf.ncols} \n {idf.col_names}")
        logger.info(f"After: \nNo. of Columns-  {odf.ncols} \n {odf.col_names}")
    return odf


def select_column(idf: Table, list_of_cols, print_impact: bool = False) -> Table:
    cols = parse_cols(list_of_cols, idf.col_names)
    odf = idf.select(cols)
    if print_impact:
        logger.info(f"Before: \nNo. of Columns-  {idf.ncols} \n {idf.col_names}")
        logger.info(f"After: \nNo. of Columns-  {odf.ncols} \n {odf.col_names}")
    return odf


def rename_column(idf: Table, list_of_cols, list_of_newcols, print_impact: bool = False) -> Table:
    if isinstance(list_of_cols, str):
        list_of_cols = [x.strip() for x in list_of_cols.split("|")]
    if isinstance(list_of_newcols, str):
        list_of_newcols = [x.strip() for x in list_of_newcols.split("|")]
    odf = idf.rename(dict(zip(list_of_cols, list_of_newcols)))
    if print_impact:
        logger.info(f"Before: \nNo. of Columns-  {idf.ncols} \n {idf.col_names}")
        logger.info(f"After: \nNo. of Columns-  {odf.ncols} \n {odf.col_names}")
    return odf


_NUM_TARGETS = {"int", "integer", "bigint", "long", "float", "double", "decimal", "smallint"}


def recast_column(idf: Table, list_of_cols, list_of_dtypes, print_impact: bool = False) -> Table:
    """Cast columns (reference :297-367).  num↔num changes storage dtype;
    cat→num parses the vocab once on host and gathers through it on device;
    num→string re-encodes to a dictionary."""
    if isinstance(list_of_cols, str):
        list_of_cols = [x.strip() for x in list_of_cols.split("|")]
    if isinstance(list_of_dtypes, str):
        list_of_dtypes = [x.strip() for x in list_of_dtypes.split("|")]
    rt = get_runtime()
    odf = idf
    for name, dt in zip(list_of_cols, list_of_dtypes):
        dt = dt.strip().lower()
        col = idf.columns[name]
        if dt in _NUM_TARGETS:
            tgt = jnp.int32 if dt in ("int", "integer", "bigint", "long", "smallint") else jnp.float32
            if col.kind == "cat":
                parsed = np.full(len(col.vocab) + 1, np.nan, dtype=np.float64)
                for i, v in enumerate(col.vocab):
                    try:
                        parsed[i] = float(v)
                    except (TypeError, ValueError):
                        pass
                pv = jnp.asarray(parsed, jnp.float32)
                vals = pv[jnp.clip(col.data, 0, len(col.vocab))]
                ok = col.mask & (col.data >= 0) & ~jnp.isnan(vals)
                data = jnp.where(ok, vals, 0.0).astype(tgt)
                new = Column("num", data, ok, dtype_name=dt if dt != "integer" else "int")
            elif col.is_wide_int:
                if dt in ("bigint", "long"):
                    new = col  # already exact int64: no-op recast keeps the pair
                elif tgt == jnp.float32:
                    new = Column("num", col.data, col.mask, dtype_name=dt)
                else:  # narrowing to int32 genuinely truncates: go via exact host
                    v = col.exact_host(idf.nrows)
                    new = _host_to_column(
                        np.clip(v, np.iinfo(np.int32).min, np.iinfo(np.int32).max).astype(np.int64),
                        idf.nrows, idf.pad_target(), rt,
                    )
            elif col.is_wide and dt in ("double", "float64"):
                # float-wide → double is a no-op recast: keep the exact pair
                new = Column(
                    "num", col.data, col.mask, dtype_name="double",
                    wide_hi=col.wide_hi, wide_lo=col.wide_lo, wide_kind="float",
                )
            elif col.is_wide and tgt == jnp.int32:
                # float-wide → integer must truncate the EXACT double — the
                # values the (hi,lo) pair exists to keep exact — not the f32
                # approximation (the reference casts the exact double)
                v = np.nan_to_num(col.exact_host(idf.nrows), nan=0.0)
                v = np.trunc(v)
                if dt in ("int", "integer", "smallint"):
                    v = np.clip(v, np.iinfo(np.int32).min, np.iinfo(np.int32).max)
                else:
                    v = np.clip(v, -(2.0**63), 2.0**63 - 1024)
                new = _host_to_column(v.astype(np.int64), idf.nrows, idf.pad_target(), rt)
                new = Column(new.kind, new.data, new.mask & col.mask[: new.mask.shape[0]],
                             dtype_name=dt if dt != "integer" else "int",
                             wide_hi=new.wide_hi, wide_lo=new.wide_lo, wide_kind=new.wide_kind)
            else:
                new = Column("num", col.data.astype(tgt), col.mask, dtype_name=dt if dt != "integer" else "int")
        elif dt == "string":
            if col.kind == "cat":
                new = col
            else:
                host = col.exact_host(idf.nrows)  # wide ints render exactly
                mask = np.asarray(col.mask)[: idf.nrows]
                vals = np.empty(idf.nrows, dtype=object)
                if np.issubdtype(host.dtype, np.integer):
                    vals[:] = [str(int(v)) for v in host]
                else:
                    vals[:] = [repr(float(v)) for v in host]
                vals[~mask] = None
                new = _host_to_column(vals, idf.nrows, idf.pad_target(), rt)
        elif dt == "timestamp":
            host = np.asarray(col.data)[: idf.nrows]
            mask = np.asarray(col.mask)[: idf.nrows]
            if col.kind == "cat":
                vals = np.empty(idf.nrows, dtype=object)
                valid = mask & (host >= 0)
                vals[valid] = col.vocab[host[valid]]
                ts = pd.to_datetime(pd.Series(vals), errors="coerce")
            else:
                ts = pd.to_datetime(pd.Series(host.astype("int64"), dtype="int64"), unit="s", errors="coerce")
                ts[~mask] = pd.NaT
            new = _host_to_column(ts.to_numpy(), idf.nrows, idf.pad_target(), rt)
        else:
            raise ValueError(f"unsupported recast dtype: {dt}")
        odf = odf.with_column(name, new)
    if print_impact:
        logger.info(f"Before:  {idf.dtypes()}")
        logger.info(f"After:  {odf.dtypes()}")
    return odf


def recommend_type(
    idf: Table,
    list_of_cols="all",
    drop_cols=[],
    dynamic_threshold: float = 0.01,
    static_threshold: int = 100,
) -> pd.DataFrame:
    """Cardinality-based form/datatype recommendation (reference :370-533):
    unique < min(static_threshold, rows·dynamic_threshold) → categorical/
    string, else numerical/double.  Returns the same 6-column stats frame."""
    cols = parse_cols(list_of_cols, idf.col_names, drop_cols)
    if not (0 < dynamic_threshold <= 1):
        raise TypeError("Invalid input for dynamic_threshold: Value need to be between 0 and 1")
    if not cols:
        warnings.warn("No recommend_attributeType analysis - No column(s) to analyze")
        return pd.DataFrame(
            columns=[
                "attribute",
                "original_form",
                "original_dataType",
                "recommended_form",
                "recommended_dataType",
                "distinct_value_count",
            ]
        )
    from anovos_tpu.ops.segment import masked_nunique

    X, M = [], []
    for c in cols:
        col = idf.columns[c]
        X.append(col.data.astype(jnp.float32))
        M.append(col.mask & ((col.data >= 0) if col.kind == "cat" else True))
    nu = np.asarray(masked_nunique(jnp.stack(X, 1), jnp.stack(M, 1)))
    threshold = min(static_threshold, idf.nrows * dynamic_threshold)
    rows = []
    for c, u in zip(cols, nu):
        col = idf.columns[c]
        o_form = "categorical" if col.kind == "cat" else "numerical"
        r_form = "categorical" if u < threshold else "numerical"
        rows.append(
            {
                "attribute": c,
                "original_form": o_form,
                "original_dataType": col.dtype_name,
                "recommended_form": r_form,
                "recommended_dataType": "string" if r_form == "categorical" else "double",
                "distinct_value_count": int(u),
            }
        )
    return pd.DataFrame(rows)
