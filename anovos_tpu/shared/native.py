"""ctypes bridge to the native host-decode library (native/anovos_native.cpp).

Builds the shared object on first use if a toolchain is present (cached next
to the source); every caller degrades gracefully to the pure-Python path when
the library is unavailable.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional

import numpy as np

_LIB = None
_TRIED = False


class NativeEncodedStrings:
    """A string column already dictionary-encoded: int32 codes (−1 null) +
    a vocab in code-point order (``np.unique``'s over Python ``str``; Arrow
    computes it over UTF-8 bytes where the distinct values are an Arrow
    string array), from the native avro decoder or from
    ``shared.table.encode_strings`` (csv / parquet / json columns, object
    arrays).  Table construction consumes this directly, so string payloads
    never materialize as a Python object per row."""

    dtype = np.dtype(object)  # duck-type for callers checking .dtype

    def __init__(self, codes: np.ndarray, vocab: np.ndarray):
        self.codes = codes
        self.vocab = vocab

    def __len__(self) -> int:
        return len(self.codes)

    def to_object_array(self) -> np.ndarray:
        out = np.empty(len(self.codes), dtype=object)
        valid = self.codes >= 0
        out[valid] = self.vocab[self.codes[valid]]
        out[~valid] = None
        return out

    def __getitem__(self, idx):
        return self.to_object_array()[idx]

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), "native")
_SO_PATH = os.path.join(_NATIVE_DIR, "libanovos_native.so")


def _build_so(src: str, out: Optional[str] = None) -> None:
    subprocess.run(
        ["g++", "-O3", "-std=c++17", "-fPIC", "-shared", src,
         "-o", out or _SO_PATH, "-lz"],
        check=True,
        capture_output=True,
    )


def _load_and_register(path: Optional[str] = None) -> ctypes.CDLL:
    """CDLL + full argtypes.  Raises AttributeError if the .so predates a
    newer export (the caller rebuilds from source and retries once)."""
    lib = ctypes.CDLL(path or _SO_PATH)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    dpp = ctypes.POINTER(ctypes.POINTER(ctypes.c_double))
    u8pp = ctypes.POINTER(u8p)
    i64pp = ctypes.POINTER(i64p)
    lib.avro_decode.restype = ctypes.c_int64
    # full argtypes — ctypes' default c_int marshaling would truncate the
    # int64_t length/offset params
    lib.avro_decode.argtypes = [
        u8p, ctypes.c_int64, i32p, i32p, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int64, u8p, ctypes.c_int32, dpp, u8pp, i64pp, u8pp, i64p,
    ]
    lib.dict_encode.restype = ctypes.c_int64
    lib.dict_encode.argtypes = [
        u8p, i64p, u8p, ctypes.c_int64, i32p, i64p, u8p, ctypes.c_int64, i64p,
    ]
    lib.avro_encode.restype = ctypes.c_int64
    lib.avro_encode.argtypes = [
        i32p, ctypes.c_int32, ctypes.c_int64,
        dpp, i64pp, u8pp, i64pp, u8pp,
        ctypes.c_int32, u8p, ctypes.c_int64, u8p, ctypes.c_int64,
    ]
    lib.edge_components_minc.restype = ctypes.c_int64
    lib.edge_components_minc.argtypes = [i64p, i64p, i64p, ctypes.c_int64,
                                         ctypes.c_int64, ctypes.c_int64, i64p]
    return lib


def get_native() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None if unavailable."""
    global _LIB, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    _TRIED = True
    src = os.path.join(_NATIVE_DIR, "anovos_native.cpp")
    try:
        stale = (
            os.path.exists(_SO_PATH)
            and os.path.exists(src)
            and os.path.getmtime(src) > os.path.getmtime(_SO_PATH)
        )
        if not os.path.exists(_SO_PATH) or stale:
            if not os.path.exists(src):
                return None
            # rebuild whenever the source is newer — a stale cached .so would
            # silently lack newer exports and route callers to slow fallbacks
            _build_so(src)
        try:
            _LIB = _load_and_register()
        except AttributeError:
            # a prebuilt .so missing a newer export with mtimes the staleness
            # check can't see (rsync -a / tar deployment): rebuild from the
            # source sitting right next to it and retry ONCE — disabling the
            # whole native layer over one missing symbol would silently drop
            # every avro ingest to the slow Python path.  The retry loads
            # from a FRESH filename: dlopen refcounts by path, so reloading
            # the overwritten original would hand back the stale mapping.
            if not os.path.exists(src):
                raise
            rebuilt = _SO_PATH + ".rebuilt.so"
            _build_so(src, out=rebuilt)
            _LIB = _load_and_register(rebuilt)
    except (OSError, subprocess.CalledProcessError, AttributeError):
        _LIB = None
    return _LIB


def _ptr_array(arrays, ctype):
    """Array-of-pointers for a list of numpy arrays (None → NULL)."""
    ptrs = (ctypes.POINTER(ctype) * len(arrays))()
    for i, a in enumerate(arrays):
        ptrs[i] = a.ctypes.data_as(ctypes.POINTER(ctype)) if a is not None else None
    return ptrs


def native_avro_decode(raw: bytes, header_offset: int, sync: bytes, codec: str, fields):
    """Decode a whole Avro container natively.

    ``fields``: list of (name, base_type, null_branch_index) where base_type ∈
    {bool,int,long,float,double,string} and null_branch_index is the union
    branch holding "null" (−1 if not nullable).
    Returns dict name → numpy array (float64 with NaN, or object strings),
    or None if the native path is unavailable/unsupported.
    """
    lib = get_native()
    if lib is None:
        return None
    type_map = {"boolean": 1, "int": 2, "long": 2, "float": 3, "double": 4, "string": 5}
    ftypes = []
    nullidx = []
    for _, base, nb in fields:
        if base not in type_map:
            return None
        ftypes.append(type_map[base])
        nullidx.append(nb)
    nfields = len(fields)
    buf = np.frombuffer(raw, dtype=np.uint8)
    ftypes_a = np.asarray(ftypes, np.int32)
    nullidx_a = np.asarray(nullidx, np.int32)
    sync_a = np.frombuffer(sync, dtype=np.uint8)
    codec_i = {"null": 0, "deflate": 1, "snappy": 2}.get(codec)
    if codec_i is None:
        return None
    used = np.zeros(nfields, np.int64)

    # phase 1: count records + string bytes
    nulld = [None] * nfields
    nrec = lib.avro_decode(
        buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), len(raw),
        ftypes_a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        nullidx_a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        nfields, codec_i, header_offset,
        sync_a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        0,
        _ptr_array(nulld, ctypes.c_double), _ptr_array(nulld, ctypes.c_uint8),
        _ptr_array(nulld, ctypes.c_int64), _ptr_array(nulld, ctypes.c_uint8),
        used.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
    )
    if nrec < 0:
        return None
    # phase 2: allocate + fill
    doubles = [np.zeros(nrec, np.float64) if t != 5 else None for t in ftypes]
    valid = [np.zeros(nrec, np.uint8) for _ in ftypes]
    str_off = [np.zeros(nrec + 1, np.int64) if t == 5 else None for t in ftypes]
    str_bytes = [np.zeros(max(int(u), 1), np.uint8) if t == 5 else None for t, u in zip(ftypes, used)]
    used2 = np.zeros(nfields, np.int64)
    nrec2 = lib.avro_decode(
        buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), len(raw),
        ftypes_a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        nullidx_a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        nfields, codec_i, header_offset,
        sync_a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        1,
        _ptr_array(doubles, ctypes.c_double), _ptr_array(valid, ctypes.c_uint8),
        _ptr_array(str_off, ctypes.c_int64), _ptr_array(str_bytes, ctypes.c_uint8),
        used2.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
    )
    if nrec2 != nrec:
        return None
    out = {}
    for i, (name, base, _) in enumerate(fields):
        v = valid[i].astype(bool)
        if ftypes[i] == 5:
            # dict-encode straight from the decode buffers — strings never
            # become Python objects (the point of the native path)
            enc = _dict_encode_buffers(lib, str_bytes[i], str_off[i], valid[i], nrec)
            if enc is None:
                return None
            out[name] = enc
        elif base == "boolean":
            # parity with the pure-Python path (avro_io.read_avro): booleans
            # collapse nulls to False in a plain bool array
            out[name] = (doubles[i] != 0) & v
        else:
            arr = doubles[i]
            arr[~v] = np.nan
            if base in ("int", "long") and v.all():
                out[name] = arr.astype(np.int64)
            else:
                out[name] = arr
    return out


def _dict_encode_buffers(lib, arena: np.ndarray, offsets: np.ndarray, valid: np.ndarray, n: int):
    """lib.dict_encode over raw (bytes, offsets, valid); sorted-vocab codes."""
    codes = np.zeros(max(n, 1), np.int32)
    vocab_off = np.zeros(n + 2, np.int64)
    vocab_bytes = np.zeros(max(len(arena), 1), np.uint8)
    vb_used = np.zeros(1, np.int64)
    vsize = lib.dict_encode(
        arena.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        valid.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        n,
        codes.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        vocab_off.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        vocab_bytes.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        len(vocab_bytes),
        vb_used.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
    )
    if vsize < 0:
        return None
    vb = vocab_bytes.tobytes()
    vocab0 = np.array(
        [vb[vocab_off[j] : vocab_off[j + 1]].decode("utf-8", "replace") for j in range(vsize)],
        dtype=object,
    )
    # canonical sorted-vocab convention (matches np.unique-based encoding)
    order = np.argsort(vocab0.astype(str), kind="stable")
    remap = np.empty(max(len(order), 1), np.int32)
    remap[order] = np.arange(len(order), dtype=np.int32)
    codes = codes[:n]
    sorted_codes = np.where(codes >= 0, remap[np.clip(codes, 0, max(len(order) - 1, 0))], -1).astype(np.int32)
    return NativeEncodedStrings(sorted_codes, vocab0[order])




def native_avro_encode(df, sync: bytes, codec: str, block_rows: int):
    """Encode a pandas frame's record blocks natively (write half of the IO
    layer).  Returns the encoded body bytes (blocks + sync markers) or None
    when the native path is unavailable/unsupported — callers fall back to
    the per-value Python loop."""
    import pandas.api.types as pdt

    lib = get_native()
    if lib is None:
        return None
    codec_i = {"null": 0, "deflate": 1}.get(codec)
    if codec_i is None:
        return None
    n = len(df)
    ftypes, doubles, longs, valids, str_offs, str_bytes_l = [], [], [], [], [], []
    bound = 0
    for name in df.columns:
        s = df[name]
        dt = s.dtype
        if pdt.is_bool_dtype(dt):
            ftypes.append(1)  # FT_BOOL
            isna = s.isna().to_numpy()
            doubles.append(s.to_numpy(np.float64, na_value=0.0))
            longs.append(None)
            valids.append((~isna).astype(np.uint8))  # nullable 'boolean' NA → null branch
            str_offs.append(None)
            str_bytes_l.append(None)
            bound += n * 2
        elif pdt.is_integer_dtype(dt):
            ftypes.append(2)  # FT_INT (zigzag varint long)
            vals = s.to_numpy()
            longs.append(vals.astype(np.int64))
            doubles.append(None)
            valids.append(np.ones(n, np.uint8))
            str_offs.append(None)
            str_bytes_l.append(None)
            bound += n * 11
        elif pdt.is_float_dtype(dt):
            ftypes.append(4)  # FT_DOUBLE
            vals = s.to_numpy(np.float64)
            doubles.append(np.nan_to_num(vals, nan=0.0))
            longs.append(None)
            valids.append((~np.isnan(vals)).astype(np.uint8))
            str_offs.append(None)
            str_bytes_l.append(None)
            bound += n * 9
        elif dt == object or str(dt) in ("string", "str", "category"):
            vals = s.to_numpy(dtype=object)
            isnull = np.array([v is None or (isinstance(v, float) and np.isnan(v)) for v in vals])
            encs = [b"" if b else str(v).encode("utf-8") for v, b in zip(vals, isnull)]
            offs = np.zeros(n + 1, np.int64)
            np.cumsum([len(e) for e in encs], out=offs[1:])
            arena = np.frombuffer(b"".join(encs) or b"\0", dtype=np.uint8).copy()
            ftypes.append(5)  # FT_STRING
            doubles.append(None)
            longs.append(None)
            valids.append((~isnull).astype(np.uint8))
            str_offs.append(offs)
            str_bytes_l.append(arena)
            bound += n * 6 + int(offs[-1])
        else:
            return None  # datetimes etc.: python writer handles
    nblocks = max(1, -(-n // block_rows))
    bound += nblocks * 40 + 64
    out = np.zeros(bound, np.uint8)
    ftypes_a = np.asarray(ftypes, np.int32)
    sync_a = np.frombuffer(sync, dtype=np.uint8)
    used = lib.avro_encode(
        ftypes_a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        len(ftypes), n,
        _ptr_array(doubles, ctypes.c_double),
        _ptr_array(longs, ctypes.c_int64),
        _ptr_array(valids, ctypes.c_uint8),
        _ptr_array(str_offs, ctypes.c_int64),
        _ptr_array(str_bytes_l, ctypes.c_uint8),
        codec_i,
        sync_a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        block_rows,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        len(out),
    )
    if used < 0:
        return None
    return out[:used].tobytes()


def native_edge_components(ei: np.ndarray, ej: np.ndarray, n_nodes: int):
    """Connected components over an undirected edge list (union-find in the
    C++ layer, O(E a(N))) — dense labels in smallest-member order, matching
    scipy.sparse.csgraph.connected_components on the same graph.  Returns
    (n_components, labels) or None when the native library is unavailable
    (callers fall back to scipy).  Unfiltered view of the thresholded
    variant — one marshaling path."""
    ei = np.ascontiguousarray(ei, np.int64)
    return native_edge_components_minc(
        ei, ej, ei, np.iinfo(np.int64).min, n_nodes
    )


def native_edge_components_minc(ei: np.ndarray, ej: np.ndarray,
                                minc: np.ndarray, thresh: int, n_nodes: int):
    """Union-find components using only edges with minc >= thresh (both
    endpoints core at this min_samples level) — one native pass per DBSCAN
    grid combo, no Python-side edge compress.  Returns (n_components,
    labels over ALL n_nodes) or None when the library is unavailable."""
    lib = get_native()
    if lib is None:
        return None
    ei = np.ascontiguousarray(ei, np.int64)
    ej = np.ascontiguousarray(ej, np.int64)
    minc = np.ascontiguousarray(minc, np.int64)
    out = np.empty(n_nodes, np.int64)
    i64p = ctypes.POINTER(ctypes.c_int64)
    ncomp = lib.edge_components_minc(
        ei.ctypes.data_as(i64p), ej.ctypes.data_as(i64p),
        minc.ctypes.data_as(i64p), len(ei), int(thresh), n_nodes,
        out.ctypes.data_as(i64p),
    )
    if ncomp < 0:
        return None
    return int(ncomp), out
