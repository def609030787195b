"""Streaming (out-of-HBM) statistics over part-file datasets.

SURVEY.md §5's long-context analogue: datasets whose row count exceeds
per-chip HBM are described by streaming row chunks host→device and merging
per-chunk statistics with Chan et al.'s pairwise moment combination
(mirroring the reference's ``pairwise_reduce``, shared/utils.py:113) — the
full table never materializes on device:

- moments (count/mean/M2/M3/M4 → var/std/skew/kurtosis): exact, combined
  pairwise so f32 error stays O(log chunks);
- min/max/nonzero: exact;
- distinct: HyperLogLog sketch union (ops/hll.py, the approx_count_distinct
  analogue);
- quantiles: fixed-width histogram refinement against the global min/max
  from pass 1 (error ≤ range/nbins — the approxQuantile analogue).

One warm-up pass fixes shapes: every chunk is padded to ``chunk_rows`` so
XLA compiles the two kernels once.

Hardened-ingest integration (round 10): every part decode runs through
the guarded reader (``data_ingest.guard`` — corrupt parts retry, then
quarantine, and the stream continues over the survivors), and the path
is RESUMABLE: with ``checkpoint_dir`` set, each drained chunk's partial
statistics commit (tmp+rename ``.npz``) and journal ``chunk_begin`` /
``chunk_commit`` WAL events; ``resume=True`` after a mid-stream crash
re-reads only the files still feeding undone chunks and recomputes
nothing that committed.

Round 12 made the pipeline ASYNCHRONOUS: part decode runs in a bounded
background pool (``data_ingest.prefetch.DecodePool``) that stages
host-ready frames ahead of the consumer, and the in-flight window is
AUTOTUNED (``ANOVOS_STREAM_INFLIGHT=auto``, the default) from the
per-chunk decode-vs-drain split; an integer value pins the round-10
behavior.  ``ANOVOS_STREAM_DECODE_WORKERS=0`` restores the fully
synchronous pipeline (artifacts are identical either way — assembly is
ordered and the drain FIFO).
"""

from __future__ import annotations

import functools
import json
import os
import time
from collections import deque
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd

from anovos_tpu.data_ingest.guard import IngestError, policy_from_env, raw_reader
from anovos_tpu.data_ingest.prefetch import DecodePool, StreamController, StreamStats
from anovos_tpu.obs import timed

# the most recent streaming pass' instrumentation (tests + tooling read
# it after a call; pure telemetry, never an input).  Lock-guarded:
# concurrently scheduled streaming nodes (the aside fan-out) race the
# rebind otherwise.
import threading as _threading

_LAST_STREAM: Dict[str, object] = {}
_LAST_STREAM_LOCK = _threading.Lock()


def last_stream_summary() -> dict:
    """Decode/overlap instrumentation of the most recent streaming call
    in this process."""
    with _LAST_STREAM_LOCK:
        return dict(_LAST_STREAM)


def _publish_stats(op: str, ctl: StreamController, stats: StreamStats) -> None:
    with _LAST_STREAM_LOCK:
        _LAST_STREAM.clear()
        _LAST_STREAM.update({"op": op, "window": ctl.window,
                             "workers": ctl.workers, "resizes": ctl.resizes,
                             **stats.summary()})


@jax.jit
def _chunk_stats(X: jax.Array, M: jax.Array) -> Dict[str, jax.Array]:
    """Per-chunk raw statistics for one (chunk, k) block."""
    Xf = X.astype(jnp.float32)
    n = M.sum(axis=0, dtype=jnp.float32)
    safe_n = jnp.maximum(n, 1.0)
    mean = jnp.where(M, Xf, 0).sum(axis=0) / safe_n
    d = jnp.where(M, Xf - mean, 0)
    d2 = d * d
    big = jnp.asarray(jnp.finfo(jnp.float32).max, jnp.float32)
    return {
        "n": n,
        "mean": mean,
        "M2": d2.sum(axis=0),
        "M3": (d2 * d).sum(axis=0),
        "M4": (d2 * d2).sum(axis=0),
        "min": jnp.where(M, Xf, big).min(axis=0),
        "max": jnp.where(M, Xf, -big).max(axis=0),
        "nonzero": (M & (Xf != 0)).sum(axis=0, dtype=jnp.float32),
    }


def _combine(a: dict, b: dict) -> dict:
    """Chan et al. pairwise moment combination (numerically stable merge)."""
    n = a["n"] + b["n"]
    safe = np.maximum(n, 1.0)
    delta = b["mean"] - a["mean"]
    na, nb = a["n"], b["n"]
    mean = a["mean"] + delta * nb / safe
    M2 = a["M2"] + b["M2"] + delta**2 * na * nb / safe
    M3 = (
        a["M3"] + b["M3"]
        + delta**3 * na * nb * (na - nb) / safe**2
        + 3 * delta * (na * b["M2"] - nb * a["M2"]) / safe
    )
    M4 = (
        a["M4"] + b["M4"]
        + delta**4 * na * nb * (na**2 - na * nb + nb**2) / safe**3
        + 6 * delta**2 * (na**2 * b["M2"] + nb**2 * a["M2"]) / safe**2
        + 4 * delta * (na * b["M3"] - nb * a["M3"]) / safe
    )
    return {
        "n": n, "mean": mean, "M2": M2, "M3": M3, "M4": M4,
        "min": np.minimum(a["min"], b["min"]),
        "max": np.maximum(a["max"], b["max"]),
        "nonzero": a["nonzero"] + b["nonzero"],
    }


def _pairwise_merge(parts: List[dict]) -> dict:
    """Tree-reduce the chunk stats (pairwise_reduce parity — a linear fold
    would accumulate f32 error linearly in the chunk count)."""
    while len(parts) > 1:
        parts = [
            _combine(parts[i], parts[i + 1]) if i + 1 < len(parts) else parts[i]
            for i in range(0, len(parts), 2)
        ]
    return parts[0]


@functools.partial(jax.jit, static_argnames=("nbins",))
def _chunk_hist(X: jax.Array, M: jax.Array, lo: jax.Array, hi: jax.Array, nbins: int) -> jax.Array:
    """(k, nbins) histogram of one chunk against fixed global edges (same
    binning rule as ops/quantiles.histogram_quantiles; the quantile
    finalization is shared via quantiles_from_histogram)."""
    Xf = X.astype(jnp.float32)
    width = jnp.maximum(hi - lo, 1e-30)
    idx = jnp.clip(((Xf - lo) / width * nbins).astype(jnp.int32), 0, nbins - 1)
    k = X.shape[1]
    flat = jnp.where(M, idx + jnp.arange(k, dtype=jnp.int32)[None, :] * nbins, k * nbins)
    return jax.ops.segment_sum(
        jnp.ones(flat.size, jnp.float32), flat.reshape(-1), num_segments=k * nbins + 1
    )[: k * nbins].reshape(k, nbins)


# sentinel for host-only passes (emit=False): distinguishes "no numeric
# block was built" from the committed-chunk skip (None)
_NO_BLOCK = object()


def _iter_chunks(
    files: List[str], file_type: str, cols: List[str], chunk_rows: int, cfg: dict,
    skip_chunks: frozenset = frozenset(),
    file_rows: Optional[dict] = None,
    on_file_rows=None,
    pool: Optional[DecodePool] = None,
    on_raw: Optional[Callable] = None,
    stats: Optional[StreamStats] = None,
    emit: bool = True,
) -> Iterator[Tuple[int, Optional[np.ndarray], Optional[np.ndarray]]]:
    """(chunk index, (chunk_rows, k_pad) float32 block, mask) triples,
    padded to constant shape.

    Both axes are shape-bucketed: rows to ``chunk_rows`` (the warm-up pass
    contract above) and columns to ``Runtime.pad_cols`` — so two streamed
    datasets with nearby column counts share the chunk kernels' compiled
    programs.  Dead lanes are zero/False; ``describe_streaming`` slices its
    outputs back to the live k.

    Resume support: a chunk whose index is in ``skip_chunks`` (committed
    by a prior run) yields ``(idx, None, None)`` — the caller loads its
    committed partial instead.  When ``file_rows`` (the prior run's
    per-file row counts) proves an entire file feeds only committed
    chunks AND the file ends on a chunk boundary (or is the last file),
    the file is not even READ — that is what "--resume re-reads only
    undone chunks" means.  Files straddling a boundary into an undone
    chunk are conservatively re-read (decode is re-paid, device compute
    still is not).  ``on_file_rows(path, nrows, at_chunk)`` reports each
    file's decoded row count for the next run's checkpoint; it returns
    True when that count DIFFERS from the prior run's record (a
    transiently-failing part came back, or a good one went bad) — chunk
    contents from ``at_chunk`` on have shifted, the caller invalidated
    its committed partials, and the local skip set forgets them too
    (``pool.cancel_skip_plan`` then voids any planned decode skips).

    Round 12: with ``pool`` set, decode is PREFETCHED — the pool's
    workers stage frames ahead through the same guarded per-part read,
    and this generator merely assembles them in file order (quarantine /
    raise / reconcile / sanitize semantics byte-identical).  ``on_raw``
    receives each non-skipped chunk's raw frame slice (host-side
    consumers: categorical counts, row tallies).  ``stats`` collects the
    decode/fetch-wait split the AUTOTUNE controller steers on."""
    from anovos_tpu.obs import devprof

    def _fetch(fi: int, f: str) -> pd.DataFrame:
        if pool is not None:
            return pool.fetch(fi, f)
        # synchronous decode on the consuming thread: meter it so devprof
        # can split host time into decode vs consume (the whole decode
        # wall is also consumer wait — there is nothing to overlap with)
        from anovos_tpu.data_ingest.data_ingest import read_host_frame

        t0 = time.perf_counter()
        try:
            return read_host_frame([f], file_type, cfg)
        finally:
            dt = time.perf_counter() - t0
            try:
                nbytes = os.path.getsize(f)
            except OSError:
                nbytes = 0
            devprof.record_decode(dt, nbytes, label=os.path.basename(f))
            if stats is not None:
                stats.add_decode(dt, nbytes)
                stats.add_fetch_wait(dt)

    buf: List[pd.DataFrame] = []
    nbuf = 0
    idx = 0  # next chunk index to yield; buffer holds rows idx*chunk_rows + ...

    if emit:
        from anovos_tpu.shared.runtime import get_runtime

        k_pad = get_runtime().pad_cols(len(cols))

        def _emit(df: pd.DataFrame):
            vals = df[cols].to_numpy(np.float32, na_value=np.nan)
            mask = ~np.isnan(vals)
            out_v = np.zeros((chunk_rows, k_pad), np.float32)
            out_m = np.zeros((chunk_rows, k_pad), bool)
            out_v[: len(vals), : len(cols)] = np.where(mask, vals, 0)
            out_m[: len(vals), : len(cols)] = mask
            return out_v, out_m
    else:
        # host-only pass (emit=False): the consumer reads raw frames via
        # on_raw — building the padded float block per chunk would be
        # ~chunk_rows·k_pad·5 bytes of pure waste in a decode-bound pass
        def _emit(df: pd.DataFrame):
            return _NO_BLOCK, _NO_BLOCK

    for fi, f in enumerate(files):
        known = (file_rows or {}).get(f)
        if known is not None and known > 0 and nbuf == 0 and skip_chunks:
            # buffer empty ⇒ we sit exactly on chunk boundary idx*chunk_rows
            start = idx * chunk_rows
            hi = (start + known - 1) // chunk_rows
            if all(c in skip_chunks for c in range(idx, hi + 1)) and (
                    (start + known) % chunk_rows == 0 or fi == len(files) - 1):
                for c in range(idx, hi + 1):
                    yield c, None, None
                idx = hi + 1
                continue
        try:
            df = _fetch(fi, f)
        except IngestError:
            if policy_from_env().on_corrupt == "raise":
                # fail-fast policy: nothing was quarantined or recorded —
                # silently skipping the part here would be exactly the
                # unaccounted data loss the knob exists to forbid
                raise
            # the whole part was quarantined (the guard already recorded
            # it): the stream continues over the survivors — downstream
            # chunk boundaries simply shift up by the lost rows
            if on_file_rows is not None and on_file_rows(f, 0, idx):
                skip_chunks = frozenset(c for c in skip_chunks if c < idx)
                if pool is not None:
                    pool.cancel_skip_plan()
            continue
        if on_file_rows is not None and on_file_rows(f, len(df), idx):
            skip_chunks = frozenset(c for c in skip_chunks if c < idx)
            if pool is not None:
                pool.cancel_skip_plan()
        buf.append(df)
        nbuf += len(df)
        while nbuf >= chunk_rows:
            cat = pd.concat(buf, ignore_index=True) if len(buf) > 1 else buf[0]
            if idx in skip_chunks:
                yield idx, None, None
            else:
                chunk = cat.iloc[:chunk_rows]
                if on_raw is not None:
                    on_raw(idx, chunk)
                v, m = _emit(chunk)
                yield idx, v, m
            idx += 1
            rest = cat.iloc[chunk_rows:]
            buf, nbuf = ([rest] if len(rest) else []), len(rest)
    if nbuf:
        cat = pd.concat(buf, ignore_index=True) if len(buf) > 1 else buf[0]
        if idx in skip_chunks:
            yield idx, None, None
        else:
            if on_raw is not None:
                on_raw(idx, cat)
            v, m = _emit(cat)
            yield idx, v, m


@raw_reader
def _read_schema_numeric_raw(f: str) -> List[str]:
    """RAW parquet schema read (footer only) — guarded callers only."""
    import pyarrow.parquet as pq
    import pyarrow.types as pat

    return [
        fld.name for fld in pq.read_schema(f)
        if pat.is_integer(fld.type) or pat.is_floating(fld.type) or pat.is_decimal(fld.type)
    ]


@raw_reader
def _read_schema_kinds_raw(f: str) -> List[Tuple[str, str]]:
    """RAW parquet schema read: every column with its coarse kind
    (``num`` | ``cat`` | ``other``) — guarded callers only."""
    import pyarrow.parquet as pq
    import pyarrow.types as pat

    out = []
    for fld in pq.read_schema(f):
        if pat.is_integer(fld.type) or pat.is_floating(fld.type) or pat.is_decimal(fld.type):
            kind = "num"
        elif pat.is_string(fld.type) or pat.is_large_string(fld.type):
            kind = "cat"
        else:
            kind = "other"
        out.append((fld.name, kind))
    return out


def stream_schema(files: List[str], file_type: str,
                  cfg: Optional[dict] = None) -> List[Tuple[str, str]]:
    """[(column, num|cat|other)] of a part-file dataset WITHOUT reading
    row data: the parquet footer of the first readable part (a corrupt
    head part quarantines and the next one is asked).  Non-self-describing
    formats decode one head part — the one synchronous read the streaming
    consumers are allowed (see graftcheck GC014's schema-probe exemption)."""
    from anovos_tpu.data_ingest.guard import guarded_part_read

    if file_type == "parquet":
        for f in files:
            kinds = guarded_part_read(
                f, lambda f=f: _read_schema_kinds_raw(f),
                file_type="parquet", stage="schema")
            if kinds is not None:
                return kinds
        raise IngestError(
            f"no parquet part with a readable footer among {len(files)} file(s)")
    from anovos_tpu.data_ingest.data_ingest import read_host_frame

    head = read_host_frame(files[:1], file_type, dict(cfg or {}))
    out = []
    for c in head.columns:
        if pd.api.types.is_numeric_dtype(head[c]):
            kind = "num"
        elif head[c].dtype == object or str(head[c].dtype) in ("string", "str"):
            kind = "cat"
        else:
            kind = "other"
        out.append((str(c), kind))
    return out


def _parquet_numeric_cols(files: List[str]) -> List[str]:
    """Numeric column names from the first part whose footer is readable.
    A corrupt head part (truncated footer) quarantines here instead of
    killing the stream before it starts."""
    from anovos_tpu.data_ingest.guard import IngestError, guarded_part_read

    for f in files:
        cols = guarded_part_read(
            f, lambda f=f: _read_schema_numeric_raw(f),
            file_type="parquet", stage="schema")
        if cols is not None:
            return cols
    raise IngestError(
        f"no parquet part with a readable footer among {len(files)} file(s)")


class StreamCheckpoint:
    """Per-chunk WAL progress for a resumable streaming pass.

    Layout under ``root``: ``stream_manifest.json`` (the stream
    signature + per-file row counts, tmp+rename), ``pass<p>_chunk_<i>.npz``
    partials (tmp+rename — the durability point, PR 5 store discipline),
    and ``stream_journal.jsonl`` (``chunk_begin``/``chunk_commit`` WAL
    events through :class:`~anovos_tpu.cache.journal.RunJournal` — the
    tooling/postmortem record of what committed when).

    A signature mismatch (files changed, different chunk_rows/cols/nbins)
    invalidates silently: the checkpoint restarts from nothing rather
    than resuming against drifted inputs."""

    MANIFEST = "stream_manifest.json"

    def __init__(self, root: str, sig: str, resume: bool = False):
        from anovos_tpu.cache.journal import RunJournal

        from collections import defaultdict

        self.root = os.path.abspath(root)
        self.sig = sig
        os.makedirs(self.root, exist_ok=True)
        self.file_rows: Dict[str, int] = {}
        # pass number -> committed chunk indices; passes are whatever the
        # consumer uses (describe: 1/2, drift: 1/2/3, quality: 1)
        self._committed: Dict[int, set] = defaultdict(set)
        mpath = os.path.join(self.root, self.MANIFEST)
        prior = None
        if os.path.exists(mpath):
            try:
                # own checkpoint state, not external data: a torn/stale
                # manifest just restarts the stream (crash-tolerant by
                # design), so the guard's quarantine machinery would be
                # noise here — and it is a tiny resume-time JSON read, not
                # a part decode on the per-chunk path
                with open(mpath) as f:  # graftcheck: disable=GC012,GC014
                    prior = json.load(f)
            except (OSError, ValueError):
                prior = None
        if prior is not None and prior.get("sig") == sig:
            if resume:
                self.file_rows = dict(prior.get("file_rows", {}))
                # the .npz on disk is the durability point: trust files,
                # not the manifest's (possibly stale) committed list
                for pk, idxs in (prior.get("committed", {}) or {}).items():
                    p = int(pk)
                    self._committed[p] = {
                        i for i in idxs
                        if os.path.exists(self._part_path(p, i))
                    }
        elif prior is not None:
            import logging

            logging.getLogger(__name__).warning(
                "stream checkpoint at %s belongs to a different stream "
                "(files/params changed) — starting fresh", self.root)
        self.journal = RunJournal(os.path.join(self.root, "stream_journal.jsonl"))
        self.journal.append("run_begin", stream=sig[:16], resume=bool(resume),
                            committed_p1=len(self._committed[1]),
                            committed_p2=len(self._committed[2]))

    def _part_path(self, pass_no: int, idx: int) -> str:
        return os.path.join(self.root, f"pass{pass_no}_chunk_{idx}.npz")

    def committed(self, pass_no: int) -> frozenset:
        return frozenset(self._committed[pass_no])

    def record_file_rows(self, path: str, n: int) -> bool:
        """Record ``path``'s decoded row count.  Returns True when a
        DIFFERENT count was recorded by a prior run — the file's
        readability changed (same bytes, transient fault), so every
        chunk index downstream of it covers different rows now."""
        prior = self.file_rows.get(path)
        if prior == n:
            return False
        self.file_rows[path] = int(n)
        self._flush_manifest()
        return prior is not None

    def _drop_committed(self, pass_no: int, from_idx: int) -> int:
        """Uncommit (and unlink — the ``.npz`` is the durability point a
        future resume would otherwise trust) chunks at/after ``from_idx``."""
        n = 0
        for c in sorted(c for c in self._committed[pass_no] if c >= from_idx):
            self._committed[pass_no].discard(c)
            try:
                os.unlink(self._part_path(pass_no, c))
            except OSError:
                pass
            n += 1
        return n

    def invalidate_from(self, idx: int,
                        passes: Optional[Tuple[int, ...]] = None) -> None:
        """Drop every committed chunk at/after ``idx``: a file's decoded
        row count changed since the prior run, so the prior partials from
        there on describe different row ranges.  ``passes`` scopes the
        drop to the passes that stream THAT file set — drift's target
        pass numbers chunks over different files than its source passes,
        and a target shift must not unlink intact source partials
        (``None`` = all passes, the single-file-set default)."""
        dropped = sum(self._drop_committed(p, idx)
                      for p in sorted(passes if passes is not None
                                      else self._committed))
        if dropped:
            import logging

            logging.getLogger(__name__).warning(
                "stream checkpoint: a part's readability changed since the "
                "prior run — %d committed chunk(s) from index %d on cover "
                "shifted rows and will recompute", dropped, idx)
            self.journal.append("chunks_invalidated", stream=self.sig[:16],
                                from_chunk=idx, dropped=dropped)
            self._flush_manifest()

    def check_bounds(self, lo: np.ndarray, hi: np.ndarray,
                     passes: Tuple[int, ...] = (2,)) -> None:
        """Partials of ``passes`` are histogram counts binned over pass
        1's derived edges (describe: ``[lo, hi]``; drift: the fitted
        cutoff matrix): if those differ from the prior run's (any
        surviving row changed — e.g. a quarantined part came back),
        EVERY committed chunk of those passes was binned over different
        bucket edges and must recompute — including chunks upstream of
        the shift point, which ``invalidate_from`` alone keeps.
        Bit-exact equality is the right test: identical surviving rows
        reduce to identical f32 bounds deterministically."""
        bpath = os.path.join(self.root, "pass2_bounds.npz")
        prior = None
        if os.path.exists(bpath):
            try:
                with np.load(bpath) as z:
                    prior = (z["lo"], z["hi"])
            except (OSError, ValueError):
                prior = None
        same = (prior is not None and prior[0].shape == lo.shape
                and np.array_equal(prior[0], lo) and np.array_equal(prior[1], hi))
        if same:
            return
        dropped = sum(self._drop_committed(p, 0) for p in passes)
        if dropped:
            self.journal.append("chunks_invalidated", stream=self.sig[:16],
                                from_chunk=0, dropped=dropped,
                                phase=passes[0])
            self._flush_manifest()
        tmp = bpath + ".tmp.npz"
        with open(tmp, "wb") as f:
            np.savez(f, lo=lo, hi=hi)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, bpath)

    def begin(self, pass_no: int, idx: int) -> None:
        self.journal.append("chunk_begin", stream=self.sig[:16],
                            phase=pass_no, chunk=idx)

    def commit(self, pass_no: int, idx: int, arrays: Dict[str, np.ndarray]) -> None:
        path = self._part_path(pass_no, idx)
        tmp = path + ".tmp.npz"  # np.savez appends .npz to bare names
        with open(tmp, "wb") as f:
            np.savez(f, **arrays)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        self._committed[pass_no].add(idx)
        self.journal.append("chunk_commit", stream=self.sig[:16],
                            phase=pass_no, chunk=idx)
        self._flush_manifest()

    def load(self, pass_no: int, idx: int) -> Dict[str, np.ndarray]:
        with np.load(self._part_path(pass_no, idx)) as z:
            return {k: z[k] for k in z.files}

    def _flush_manifest(self) -> None:
        mpath = os.path.join(self.root, self.MANIFEST)
        tmp = mpath + ".tmp"
        doc = {
            "sig": self.sig,
            "file_rows": self.file_rows,
            "committed": {str(p): sorted(s) for p, s in self._committed.items()},
        }
        with open(tmp, "w") as f:
            json.dump(doc, f, sort_keys=True)
        os.replace(tmp, mpath)


def _stream_sig(files: List[str], file_type: str, cols: List[str],
                chunk_rows: int, nbins: int, op: str = "describe") -> str:
    """Identity of one streaming computation: the operation, the exact
    file set (stat signatures — same policy as
    cache.fingerprint.dataset_fingerprint) and the chunking/binning
    parameters.  Any change invalidates checkpointed progress wholesale."""
    from anovos_tpu.cache.fingerprint import digest

    sigs = []
    for f in files:
        try:
            st = os.stat(f)
            sigs.append(f"{f}:{st.st_size}:{st.st_mtime_ns}")
        except OSError:
            sigs.append(f"{f}:gone")
    return digest(op, file_type, ",".join(cols), str(chunk_rows), str(nbins),
                  *sigs)


def checkpoint_on_file_rows(ckpt: Optional["StreamCheckpoint"],
                            passes: Optional[Tuple[int, ...]] = None):
    """The standard ``on_file_rows`` hook for a checkpointed stream: a
    readability change shifts every downstream chunk, so the checkpoint
    drops the prior partials (they recompute) and the iterator's local
    skip set forgets them.  ``passes`` scopes the invalidation to the
    passes whose chunk indices are numbered over THIS hook's file set
    (multi-file-set streams like drift pass it explicitly)."""
    if ckpt is None:
        return None

    def _on_file_rows(path, n, at_chunk):
        if ckpt.record_file_rows(path, n):
            ckpt.invalidate_from(at_chunk, passes=passes)
            return True
        return False

    return _on_file_rows


def _open_pool(files: List[str], file_type: str, cfg: dict,
               ctl: StreamController, stats: StreamStats,
               ckpt: Optional["StreamCheckpoint"],
               skip_chunks: frozenset, chunk_rows: int) -> Optional[DecodePool]:
    """A decode pool for one pass (None when decode is pinned synchronous).
    Resume-planned files are excluded from speculation so a resumed run
    re-reads exactly what the synchronous pipeline would."""
    if ctl.workers <= 0:
        return None
    from anovos_tpu.data_ingest.prefetch import plan_file_skips

    plan = frozenset()
    if ckpt is not None and skip_chunks:
        plan = plan_file_skips(files, ckpt.file_rows, skip_chunks, chunk_rows)
    return DecodePool(files, file_type, cfg, ctl, skip_plan=plan, stats=stats,
                      journal=ckpt.journal if ckpt is not None else None)


def _run_pass(
    files: List[str], file_type: str, cols: List[str], chunk_rows: int,
    cfg: dict, *,
    pass_no: int,
    dispatch: Callable,
    ctl: StreamController,
    stats: StreamStats,
    ckpt: Optional["StreamCheckpoint"] = None,
    skip_chunks: frozenset = frozenset(),
    on_file_rows=None,
    host_part: Optional[Callable] = None,
    need_block: bool = True,
) -> Dict[int, Dict[str, np.ndarray]]:
    """One windowed streaming pass: prefetch-fed chunks dispatched to
    ``dispatch(v, m) -> {name: device array}`` and drained a WINDOW
    behind (upload/compute overlap under the documented
    O(window·chunk_rows·k) residency bound), optionally joined with
    ``host_part(raw_frame) -> {name: np array}`` host-side partials
    (categorical counts, row tallies) and committed per chunk to the
    checkpoint.  Returns {chunk idx: host partial} — committed chunks of
    a resumed run load from disk without decode or device compute.

    The AUTOTUNE controller observes each chunk's consumer-side split
    (blocked-on-decode vs blocked-on-drain) and resizes the window /
    decode worker pool live; artifacts are invariant to both knobs."""
    pool = _open_pool(files, file_type, cfg, ctl, stats, ckpt,
                      skip_chunks, chunk_rows)
    pending: deque = deque()
    parts: Dict[int, Dict[str, np.ndarray]] = {}
    raw_parts: Dict[int, Dict[str, np.ndarray]] = {}
    t_pass = time.perf_counter()
    last_drain_t = t_pass

    def _drain_oldest():
        nonlocal last_drain_t
        i, dev, host = pending.popleft()
        t0 = time.perf_counter()
        # deliberate bounded-window download: the tiny per-chunk partial
        # must materialize to merge (and to commit, when checkpointed) —
        # the window keeps uploads/compute overlapped ahead of this sync
        part = {k: np.asarray(s) for k, s in dev.items()}
        now = time.perf_counter()
        stats.add_drain_wait(now - t0)
        if host:
            part.update(host)
        parts[i] = part
        if ckpt is not None:
            ckpt.commit(pass_no, i, part)
        stats.chunks += 1
        fetch_w, drain_w = stats.take_chunk_signals()
        ctl.observe(fetch_w, drain_w, now - last_drain_t)
        last_drain_t = now
        if pool is not None:
            pool.maybe_grow()

    on_raw = None
    if host_part is not None:
        def on_raw(idx, frame):
            raw_parts[idx] = host_part(frame)

    try:
        for idx, v, m in _iter_chunks(
                files, file_type, cols, chunk_rows, cfg,
                skip_chunks=skip_chunks,
                file_rows=ckpt.file_rows if ckpt is not None else None,
                on_file_rows=on_file_rows,
                pool=pool, on_raw=on_raw, stats=stats, emit=need_block):
            if v is None:
                parts[idx] = ckpt.load(pass_no, idx)
                continue
            if ckpt is not None:
                ckpt.begin(pass_no, idx)
            dev = {} if v is _NO_BLOCK else dispatch(v, m)
            pending.append((idx, dev, raw_parts.pop(idx, None)))
            stats.high_water = max(stats.high_water, len(pending))
            while len(pending) >= max(1, ctl.window):
                _drain_oldest()
        while pending:
            _drain_oldest()
    finally:
        if pool is not None:
            pool.close()
        stats.wall_s = round(stats.wall_s + time.perf_counter() - t_pass, 4)
    return parts


@timed("ops.describe_streaming")
def describe_streaming(
    file_path: str,
    file_type: str,
    list_of_cols: Optional[List[str]] = None,
    chunk_rows: int = 1_000_000,
    nbins: int = 2048,
    file_configs: Optional[dict] = None,
    quantiles: Tuple[float, ...] = (0.01, 0.05, 0.10, 0.25, 0.50, 0.75, 0.90, 0.95, 0.99),
    checkpoint_dir: Optional[str] = None,
    resume: bool = False,
) -> pd.DataFrame:
    """Two-pass streaming description of a part-file dataset of ANY size.

    Pass 1 streams chunks through ``_chunk_stats`` (pairwise-merged moments,
    min/max); pass 2 refines quantiles against the global range via
    fixed-width histograms.  Device memory is O(chunk_rows·k + k·nbins)
    regardless of total rows.  Returns the stats frame
    [attribute, count, mean, stddev, variance, skewness, kurtosis, min,
    max, nonzero, <quantiles…>].

    With ``checkpoint_dir`` each drained chunk's partial commits to disk
    (WAL-journaled — :class:`StreamCheckpoint`); ``resume=True`` after a
    mid-stream crash skips every committed chunk's decode+compute and
    produces EXACTLY the uninterrupted result (the committed partials
    are the same f32 arrays the merge would recompute, combined in the
    same chunk order).  Checkpointed pass 2 accumulates per-chunk
    histograms via host adds (each chunk's counts must materialize to
    commit) instead of the uncheckpointed device-side accumulation; the
    sums are integer-valued f32 in the same order, so the results are
    identical.
    """
    from anovos_tpu.data_ingest.data_ingest import _resolve_files
    from anovos_tpu.obs import get_metrics

    cfg = dict(file_configs or {})
    files = _resolve_files(file_path, file_type)
    if list_of_cols is None:
        if file_type == "parquet":
            # schema without reading row groups — no redundant full-part
            # read; a corrupt head part quarantines and the next one is
            # asked (the stream itself will quarantine it again for data)
            list_of_cols = _parquet_numeric_cols(files)
        else:
            list_of_cols = [c for c, k in stream_schema(files, file_type, cfg)
                            if k == "num"]
    cols = list(list_of_cols)
    if not cols:
        raise ValueError("describe_streaming: no numeric columns")

    ctl = StreamController()
    stats = StreamStats()
    inflight_gauge = get_metrics().gauge(
        "stream_inflight_high_water",
        "max dispatched-but-undrained chunks (device-residency bound)")
    ckpt = None
    if checkpoint_dir:
        ckpt = StreamCheckpoint(
            checkpoint_dir,
            _stream_sig(files, file_type, cols, chunk_rows, nbins),
            resume=resume,
        )

    # pass 1 rides the generic windowed pass (_run_pass): the prefetch
    # pool stages decoded frames ahead, each chunk's moment program is
    # dispatched as it assembles, and the (tiny) per-chunk partials drain
    # a WINDOW behind — fetching inside the loop blocked chunk k+1's
    # upload behind chunk k's download (graftcheck GC001), while
    # dispatching everything unsynchronized would let the read-loop keep
    # every chunk's input buffers resident at once.  The f64 pairwise
    # merge stays on host by design (Chan et al.)
    _on_file_rows = checkpoint_on_file_rows(ckpt)

    skip1 = ckpt.committed(1) if (ckpt is not None and resume) else frozenset()
    parts = _run_pass(
        files, file_type, cols, chunk_rows, cfg,
        pass_no=1,
        dispatch=lambda v, m: _chunk_stats(jnp.asarray(v), jnp.asarray(m)),
        ctl=ctl, stats=stats, ckpt=ckpt, skip_chunks=skip1,
        on_file_rows=_on_file_rows)
    # host dict of already-materialized np partials — not a device value
    if not parts:  # graftcheck: disable=GC001
        raise IngestError(
            f"describe_streaming: no readable rows in {len(files)} part "
            "file(s) (every part quarantined?)")
    agg = _pairwise_merge([parts[i] for i in sorted(parts)])

    lo = jnp.asarray(agg["min"], jnp.float32)
    hi = jnp.asarray(agg["max"], jnp.float32)
    # accumulate the histogram ON DEVICE: downloading each chunk's counts
    # to add them in numpy forced a blocking round-trip per chunk
    # (graftcheck GC001); one transfer at the quantile step suffices.  A
    # periodic block_until_ready keeps the host read-loop from racing
    # ahead of the device with unbounded in-flight chunk uploads.
    # (Checkpointed runs instead commit each chunk's counts — see the
    # docstring; the per-chunk download is the price of resumability.)
    hist_d = jnp.zeros((int(lo.shape[0]), nbins), jnp.float32)  # k_pad lanes
    if ckpt is not None:
        # drops ALL pass-2 partials if the bucket bounds drifted since
        # the prior run (they were binned over different edges); the
        # bounds are k_pad floats — a deliberate, tiny durability read
        ckpt.check_bounds(np.asarray(lo), np.asarray(hi))  # graftcheck: disable=GC001
    skip2 = ckpt.committed(2) if (ckpt is not None and resume) else frozenset()
    pool2 = _open_pool(files, file_type, cfg, ctl, stats, ckpt,
                       skip2, chunk_rows)
    t_pass2 = time.perf_counter()
    try:
        for i, v, m in _iter_chunks(
                files, file_type, cols, chunk_rows, cfg, skip_chunks=skip2,
                file_rows=ckpt.file_rows if ckpt is not None else None,
                on_file_rows=_on_file_rows, pool=pool2, stats=stats):
            if v is None:
                hist_d = hist_d + ckpt.load(2, i)["hist"]
                continue
            if ckpt is None:
                hist_d = hist_d + _chunk_hist(jnp.asarray(v), jnp.asarray(m), lo, hi, nbins)
                if (i + 1) % max(1, ctl.window) == 0:
                    jax.block_until_ready(hist_d)
            else:
                ckpt.begin(2, i)
                # deliberate per-chunk download: the chunk's counts must
                # materialize on host to COMMIT (resumability is the point);
                # the uncheckpointed branch above keeps the device-side
                # accumulation for the no-checkpoint fast path
                h = np.asarray(  # graftcheck: disable=GC001
                    _chunk_hist(jnp.asarray(v), jnp.asarray(m), lo, hi, nbins))
                ckpt.commit(2, i, {"hist": h})
                hist_d = hist_d + h
    finally:
        if pool2 is not None:
            pool2.close()
        stats.wall_s = round(stats.wall_s + time.perf_counter() - t_pass2, 4)
    inflight_gauge.set_max(float(stats.high_water), window=ctl.label)
    _publish_stats("describe_streaming", ctl, stats)

    # shared finalizer (ops/reductions.finalize_moments) — one statistical
    # policy for GSPMD, shard_map, and streaming paths alike
    from anovos_tpu.ops.reductions import finalize_moments

    # slice every per-column array back to the live k (the chunk kernels ran
    # on the column-bucketed k_pad; dead lanes are zero-count noise)
    kk = len(cols)
    n = agg["n"][:kk]
    fin = {
        k: np.asarray(v)[:kk]
        for k, v in finalize_moments(
            jnp.asarray(agg["n"]), jnp.asarray(agg["mean"] * agg["n"]), jnp.asarray(agg["M2"]),
            jnp.asarray(agg["M3"]), jnp.asarray(agg["M4"]),
            jnp.asarray(agg["min"]), jnp.asarray(agg["max"]), jnp.asarray(agg["nonzero"]),
        ).items()
    }
    out = {
        "attribute": cols,
        "count": n.astype(np.int64),
        "mean": np.round(fin["mean"], 4),
        "stddev": np.round(fin["stddev"], 4),
        "variance": np.round(fin["variance"], 4),
        "skewness": np.round(fin["skewness"], 4),
        "kurtosis": np.round(fin["kurtosis"], 4),
        "min": fin["min"],
        "max": fin["max"],
        "nonzero": agg["nonzero"][:kk].astype(np.int64),
    }
    from anovos_tpu.ops.quantiles import quantiles_from_histogram

    width = (agg["max"] - agg["min"]) / nbins
    qvals = quantiles_from_histogram(np.asarray(hist_d), agg["min"], width,
                                     np.asarray(quantiles, np.float32))
    for i, q in enumerate(quantiles):
        out[f"{int(q * 100)}%"] = np.round(qvals[i][:kk], 4)
    return pd.DataFrame(out)
