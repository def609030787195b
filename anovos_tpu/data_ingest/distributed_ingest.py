"""Multi-host ingest: per-host file shards assembled into ONE global Table.

The reference scales ingest by giving each Spark executor a slice of the
part files; the TPU-native analogue (SURVEY.md §2.10/§5) is: each *process*
(host) reads ``files[process_index::process_count]``, processes agree on
schema / categorical vocabularies / row counts through host allgathers, and
every column becomes a global ``jax.Array`` via
``jax.make_array_from_process_local_data`` over the global mesh — after
which every stats kernel runs unchanged, with XLA inserting the cross-host
collectives (DCN) that the psum-style reductions need.

Alignment: with P processes each holding L local devices, the global padded
row count is P·L·s where s = ceil(max_local_rows / L); every process pads
its local block to L·s rows with mask=False.  Padding is therefore
*interleaved* (at the end of each process block, not the global end), so
the Table carries an explicit ``valid_rows`` mask instead of arange<nrows.

Scope: device-side stats/aggregation kernels (describe, drift, moments,
correlation) are fully supported on the result.  Host materialization
(``to_pandas``/``gather_rows``) needs fully-addressable arrays and raises
on multi-process tables — write results per host instead (the reference
writes part files per executor for the same reason).
"""

from __future__ import annotations

import json
from collections import OrderedDict
from typing import List, Optional

import numpy as np
import pandas as pd

from anovos_tpu.data_ingest.data_ingest import _resolve_files, read_host_frame
from anovos_tpu.data_ingest.guard import IngestError, policy_from_env
from anovos_tpu.shared.table import Column, Table, _spark_dtype_name, wide_int_parts
from anovos_tpu.shared.runtime import DATA_AXIS, get_runtime


def _allgather_obj(obj) -> list:
    """Allgather an arbitrary (small, json-able) host object across
    processes: serialize → pad to the global max byte length → allgather
    uint8 → decode.  Control-plane only; data rows never take this path."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import multihost_utils

    blob = json.dumps(obj).encode()
    n = np.int32(len(blob))
    lens = np.asarray(multihost_utils.process_allgather(jnp.asarray([n])))
    maxlen = int(lens.max())
    padded = np.zeros(maxlen, np.uint8)
    padded[: len(blob)] = np.frombuffer(blob, np.uint8)
    mats = np.asarray(multihost_utils.process_allgather(jnp.asarray(padded)))
    out = []
    for i in range(mats.shape[0]):
        raw = mats[i, : int(lens[i, 0])].tobytes()
        out.append(json.loads(raw.decode()))
    return out


def _global_sharded(local: np.ndarray, fill) -> "jax.Array":
    """Pad a process-local block and lift it to a global row-sharded array."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    rt = get_runtime()
    sharding = NamedSharding(rt.mesh, P(*((DATA_AXIS,) + (None,) * (local.ndim - 1))))
    return jax.make_array_from_process_local_data(sharding, local)


def _empty_with_schema(files: List[str], file_type: str, cfg: dict) -> pd.DataFrame:
    """A zero-row frame with the dataset's schema, from the first part
    whose decode succeeds (guarded: a corrupt head part quarantines and
    the next one is asked).  Every process must end up with the SAME
    column set here or the schema allgather raises — which is correct:
    if no part anywhere is readable the dataset is gone."""
    for f in files:
        try:
            return read_host_frame([f], file_type, cfg).iloc[:0]
        except IngestError:
            continue
    raise IngestError(
        f"no readable {file_type} part among {len(files)} file(s) — cannot "
        "even recover the schema")


def read_dataset_distributed(
    file_path: str, file_type: str, file_configs: Optional[dict] = None
) -> Table:
    """Global Table from per-host part-file slices (one read per host)."""
    import jax
    import jax.numpy as jnp

    cfg = dict(file_configs or {})
    files = _resolve_files(file_path, file_type)
    pid, nproc = jax.process_index(), jax.process_count()
    local_files = files[pid::nproc]
    if local_files:
        try:
            df = read_host_frame(local_files, file_type, cfg)
        except IngestError:
            if policy_from_env().on_corrupt == "raise":
                # fail-fast policy: the guard raised on the FIRST bad part
                # without quarantining anything — degrading to an empty
                # slice here would silently drop this host's readable
                # parts with no loss accounting anywhere
                raise
            # EVERY part in this host's slice was quarantined: degrade to
            # an empty slice with a schema read from some still-readable
            # part so the schema allgather below converges — the other
            # hosts' rows survive, this host contributes none (its
            # quarantine records carry the loss accounting)
            df = _empty_with_schema(files, file_type, cfg)
    else:
        # more hosts than files: empty slice with the schema of file 0
        df = _empty_with_schema(files, file_type, cfg)

    # ---- schema agreement -------------------------------------------------
    def _col_kind(s: pd.Series) -> str:
        if s.dtype == object or str(s.dtype) in ("string", "str", "category"):
            return "cat"
        if s.dtype.kind == "M":
            return "ts"
        # distinguish int/float: hosts MUST agree on the device dtype branch
        # (a host whose shard has nulls reads float64 where another reads
        # int64 — divergent branches would run mismatched collective
        # sequences and hang the cluster).  A parquet integer column with
        # nulls is pandas' nullable integer on the hosts whose slice holds a
        # null and a plain one on the others: "num_i" on all of them, and the
        # integer branch below carries a mask, as the one-host reader does
        return "num_f" if s.dtype.kind == "f" else "num_i"

    local_schema = {c: _col_kind(df[c]) for c in df.columns}
    schemas = _allgather_obj({"cols": list(df.columns), "kinds": local_schema, "n": len(df)})
    cols0 = schemas[0]["cols"]
    for s in schemas[1:]:
        if s["cols"] != cols0:
            raise ValueError(f"distributed read: column sets differ across hosts: {s['cols']} vs {cols0}")
    # combine: cat if ANY host parsed cat; float if ANY host parsed float
    kinds = {}
    for c in cols0:
        ks = {s["kinds"][c] for s in schemas if s["n"] > 0} or {"num_f"}
        if "cat" in ks:
            kinds[c] = "cat"
        elif "ts" in ks:
            kinds[c] = "ts"
        else:
            kinds[c] = "num_f" if "num_f" in ks else "num_i"

    counts = [s["n"] for s in schemas]
    total = sum(counts)
    rt = get_runtime()
    n_local_dev = max(jax.local_device_count(), 1)
    per_dev = max(-(-max(counts) // n_local_dev), 1)
    local_pad = per_dev * n_local_dev
    n = len(df)

    def _pad(arr: np.ndarray, fill) -> np.ndarray:
        out = np.full((local_pad,) + arr.shape[1:], fill, dtype=arr.dtype)
        out[:n] = arr
        return out

    row_valid = _global_sharded(_pad(np.ones(n, bool), False), False)
    columns: "OrderedDict[str, Column]" = OrderedDict()
    for c in cols0:
        s = df[c]
        kind = kinds[c]
        if kind == "cat":
            vals = s.to_numpy(dtype=object)
            isnull = pd.isna(s).to_numpy()
            strs = np.array(["" if b else str(v) for v, b in zip(vals, isnull)], dtype=object)
            local_vocab = sorted(set(strs[~isnull]))
            # vocab union across hosts (control-plane allgather, distinct
            # values only — the reference's executors exchange nothing here
            # because strings stay in the row shuffle; we pay a tiny vocab
            # sync instead and the rows never leave their host)
            all_vocabs = _allgather_obj(local_vocab)
            vocab = np.array(sorted({v for vs in all_vocabs for v in vs}), dtype=object)
            codes = np.full(n, -1, np.int32)
            nz = ~isnull
            if vocab.size and nz.any():  # vocab is sorted: searchsorted = exact code
                codes[nz] = np.searchsorted(vocab, strs[nz]).astype(np.int32)
            columns[c] = Column(
                "cat",
                _global_sharded(_pad(codes, np.int32(-1)), -1),
                _global_sharded(_pad(~isnull, False), False),
                vocab=vocab,
                dtype_name="string",
            )
        elif kind == "ts":
            vals = s.to_numpy().astype("datetime64[s]")
            isnull = np.isnat(vals)
            secs = np.where(isnull, 0, vals.astype("int64")).astype(np.int32)
            columns[c] = Column(
                "ts",
                _global_sharded(_pad(secs, np.int32(0)), 0),
                _global_sharded(_pad(~isnull, False), False),
                dtype_name="timestamp",
            )
        else:
            if kind == "num_f":  # globally-agreed branch, never local dtype
                fvals = s.to_numpy(dtype=np.float64, na_value=np.nan)
                isnull = np.isnan(fvals)
                host = np.where(isnull, 0.0, fvals).astype(np.float32)
                columns[c] = Column(
                    "num",
                    _global_sharded(_pad(host, np.float32(0)), 0.0),
                    _global_sharded(_pad(~isnull, False), False),
                    dtype_name="double",
                )
            else:
                isnull = pd.isna(s).to_numpy()
                v64 = s.to_numpy(dtype=np.int64, na_value=0)  # zero under the mask, as a padding row holds
                # wide detection must agree globally: allgather local ranges
                ranges = _allgather_obj([int(v64.min(initial=0)), int(v64.max(initial=0))])
                gmin = min(r[0] for r in ranges)
                gmax = max(r[1] for r in ranges)
                if gmin >= np.iinfo(np.int32).min and gmax <= np.iinfo(np.int32).max:
                    columns[c] = Column(
                        "num",
                        _global_sharded(_pad(v64.astype(np.int32), np.int32(0)), 0),
                        _global_sharded(_pad(~isnull, False), False),
                        dtype_name=_spark_dtype_name(getattr(s.dtype, "numpy_dtype", s.dtype)),
                    )
                else:
                    whi, wlo = wide_int_parts(v64)
                    columns[c] = Column(
                        "num",
                        _global_sharded(_pad(v64.astype(np.float32), np.float32(0)), 0.0),
                        _global_sharded(_pad(~isnull, False), False),
                        dtype_name="bigint",
                        wide_hi=_global_sharded(_pad(whi, np.int32(0)), 0),
                        wide_lo=_global_sharded(_pad(wlo, np.int32(-(1 << 31))), 0),
                    )
    return Table(columns, total, valid_rows=row_valid)
