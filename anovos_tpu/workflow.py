"""Config-driven pipeline runner (reference: src/main/anovos/workflow.py).

Same YAML schema, same reflection dispatch — top-level keys are module
blocks, nested keys are function names resolved by ``getattr`` (ref ETL
:45-61, stats :495, quality :528, transformers :745).  ``stats_args``
(ref :91-145) injects previously-saved stats CSVs into downstream functions;
``save(..., reread=True)`` (ref :64-88) checkpoints intermediates.  The
``run_type`` axis routes through the pluggable artifact store
(``shared/artifact_store.py``): local/databricks are path mappings,
emr/ak8s stage locally and shell out to aws/azcopy like the reference;
mlflow hooks activate when the package is importable.

Execution model: the YAML walk REGISTERS each block as a node on a
dependency-aware DAG scheduler (``parallel/scheduler.py``) instead of
executing it inline.  Blocks that mutate ``df`` (ingest, quality
treatments, transformers, ts auto-detection) form the sequential spine —
each reads ``df`` version N and writes version N+1 — while read-only
analyzers (stats metrics, associations, drift, geo, ts inspection, charts)
fan out from the spine version current at their YAML position and run
concurrently.  ``report_generation`` waits only on the analyzer nodes whose
outputs it reads.  Artifact persistence (stats CSVs, chart JSONs,
intermediate checkpoints) rides an async write queue
(``shared.artifact_store.AsyncArtifactWriter``) drained at a single barrier
before the report reads and before ``main()`` returns.

``ANOVOS_TPU_EXECUTOR=sequential`` runs the registered nodes in
registration order on the caller thread with synchronous writes — byte-for-
byte the pre-scheduler behavior, and the golden-comparison mode for the
concurrent executor.  ``ANOVOS_TPU_NODE_TIMEOUT`` (seconds, default 900)
is the per-node hang watchdog; ``ANOVOS_TPU_EXECUTOR_WORKERS`` bounds the
pool.
"""

from __future__ import annotations

import contextlib
import copy
import logging
import os
import threading
import time
from typing import Optional

import pandas as pd
import yaml

from anovos_tpu import IMPORT_STARTED
from anovos_tpu.data_ingest import data_ingest
from anovos_tpu.data_ingest import guard as ingest_guard
from anovos_tpu.data_ingest.ts_auto_detection import ts_preprocess
from anovos_tpu.data_analyzer import association_evaluator, quality_checker, stats_generator
from anovos_tpu.data_report.basic_report_generation import (
    ARGS_TO_STATSFUNC,
    CHECKER_STATS_ARGS,
    anovos_basic_report,
)
from anovos_tpu.data_report.report_generation import anovos_report
from anovos_tpu.data_report.report_preprocessing import charts_to_objects, save_stats
from anovos_tpu.data_transformer import transformers
from anovos_tpu.drift_stability import drift_detector as ddetector
from anovos_tpu.drift_stability import stability as dstability
from anovos_tpu.cache import (
    CacheStore,
    NodeCachePolicy,
    RunJournal,
    base_material,
    cache_root,
    committed_fingerprints,
    dataset_fingerprint,
    node_fingerprint,
    read_journal,
)
from anovos_tpu.cache import capture as cache_capture
from anovos_tpu.obs import (
    build_manifest,
    compile_census,
    config_hash,
    devprof,
    flight,
    get_metrics,
    get_tracer,
    maybe_rotator,
    process_section,
    record_cache_stats,
    record_device_memory,
    telemetry,
    trace_destination,
    write_chrome_trace,
    write_manifest,
)
from anovos_tpu.parallel.scheduler import DagScheduler
from anovos_tpu.resilience import ErrorPolicy, chaos
from anovos_tpu.resilience import failover as res_failover
from anovos_tpu.resilience import policy as res_policy
from anovos_tpu.shared.artifact_store import AsyncArtifactWriter
from anovos_tpu.shared.table import Table

# the imports are done: the end of the manifest's ``process/import``
_IMPORTS_DONE = time.perf_counter()
# passes this process has opened; the first one's manifest tells what the
# process did before it (``process``)
_PASSES = 0

logger = logging.getLogger("anovos_tpu.workflow")

# scheduler summary (mode, wall/serial/critical-path seconds, speedup,
# per-node spans) of the most recent main() run
LAST_RUN_SUMMARY: dict = {}

# absolute path of the most recent run's obs/run_manifest.json — the
# machine-readable record tests and tooling read instead of re-deriving
# timings from module globals
LAST_MANIFEST_PATH: str = ""

# what _main leaves for _pass to write once the root span has ended:
# (manifest, path, the scheduler's origin on time.monotonic(), store, base)
_PASS_MANIFEST: Optional[tuple] = None

# stats CSVs each downstream function reads (via stats_args):
# CHECKER_STATS_ARGS is the shared wiring table (one copy, used by the
# basic report too); the workflow path additionally routes stats into
# transformers and charts
MAINFUNC_TO_ARGS = {
    **CHECKER_STATS_ARGS,
    "charts_to_objects": ["stats_unique"],
    "cat_to_num_unsupervised": ["stats_unique"],
    "PCA_latentFeatures": ["stats_missing"],
    "autoencoder_latentFeatures": ["stats_missing"],
}


def _log_block_time(label: str, start: float) -> None:
    """Book one block's wall time into the metrics registry (the reference
    logs these per block, workflow.py:227-244; the run manifest carries them
    as ``block_seconds``).
    The registry is lock-protected, so concurrent-executor worker threads
    accumulate safely; timings are monotonic-clock based."""
    secs = round(time.monotonic() - start, 4)
    get_metrics().counter(
        "anovos_block_seconds",
        "per-block wall time of the most recent workflow.main run",
    ).inc(secs, block=label)
    # device-memory high-water mark sampled at every block boundary — the
    # cheapest cadence that still catches which block peaked HBM
    record_device_memory()
    logger.info(f"{label}: execution time (in secs) = {secs}")


def block_times() -> dict:
    """Per-block wall seconds of the most recent ``main()`` run, read from
    the metrics registry."""
    counter = get_metrics().counter("anovos_block_seconds")
    return {
        labels["block"]: round(v, 4)
        for labels, v in counter.items()
        if "block" in labels
    }


def ETL(args: dict) -> Table:
    """read_dataset + chained column ops by reflection (reference :45-61)."""
    read_args = args.get("read_dataset", None)
    if not read_args:
        raise TypeError("Invalid input for reading dataset")
    df = data_ingest.read_dataset(**read_args)
    for key, value in args.items():
        if key != "read_dataset" and value is not None:
            f = getattr(data_ingest, key)
            with get_tracer().phase(f"ingest/{key}", cat="io"):
                df = f(df, **value) if isinstance(value, dict) else f(df, value)
    return df


def _read_inside(name: str, spec: dict) -> Table:
    """:func:`ETL` of a table that a node reads for itself (the drift source, a
    stability period) as a stage row ``name`` of that node: the read's own
    ``io:read_dataset`` and ``ingest/*`` rows under it, as the pass's
    ``ingest`` has them, and the table's rows, columns and file bytes on it."""
    with get_tracer().phase(name, cat="io") as sp:
        df = ETL(spec)
        rd = spec.get("read_dataset") or {}
        try:
            files = data_ingest._resolve_files(rd.get("file_path"), rd.get("file_type"))
            nbytes = sum(os.path.getsize(f) for f in files)
        except (OSError, TypeError, ValueError):
            nbytes = 0
        sp.add(rows=df.nrows, columns=df.ncols, bytes=nbytes)
    return df


def save(
    data,
    write_configs: Optional[dict],
    folder_name: str,
    reread: bool = False,
    writer: Optional[AsyncArtifactWriter] = None,
    key: Optional[str] = None,
):
    """Checkpoint a Table (or stats frame) under the write config's path
    (reference :64-88).

    No write config → return the data untouched, before any path handling
    (every intermediate step calls this; constructing paths for a ``None``
    config would be pure waste).

    The reference's ``reread`` loads the checkpoint back to CUT THE SPARK
    LINEAGE — a lazy-DAG concern this framework does not have: a Table is
    already materialized device arrays.  So reread writes the checkpoint
    artifact (same files on disk) and returns the in-memory data, skipping
    ~15 disk read-backs per configs_full run.  ``ANOVOS_REREAD_FROM_DISK=1``
    restores the literal read-back (for chasing a writer/reader parity bug:
    it re-applies the CSV round-trip's dtype coercions mid-pipeline).

    With ``writer`` (and no read-back requested) the disk write is queued on
    the async artifact writer under ``key`` and the in-memory data returns
    immediately; the queue is drained before ``main()`` returns.

    ``data`` goes to ``write_dataset`` as it is, queued or not: a ``Table``
    is on the device and is fetched there; a pandas frame (a stats table) is
    on the host and is written from it, the same files with the same bytes
    as when it went through ``Table.from_pandas``, with no ``device_put``,
    no ``device_get`` and no ``ingest/*`` span from the writer thread.
    """
    if not write_configs:
        return data
    if "file_path" not in write_configs:
        raise TypeError("file path missing for writing data")
    write = copy.deepcopy(write_configs)
    write.pop("mlflow_run_id", "")
    write.pop("log_mlflow", False)
    write["file_path"] = os.path.join(write["file_path"], folder_name)
    from_disk = reread and os.environ.get("ANOVOS_REREAD_FROM_DISK", "0") == "1"
    if writer is not None and not from_disk:
        writer.submit(key or f"ckpt:{folder_name}", data_ingest.write_dataset, data, **write)
        return data
    data_ingest.write_dataset(data, **write)
    if from_disk:
        back = data_ingest.read_dataset(
            write["file_path"], write.get("file_type", "csv"), _clean_read_cfg(write.get("file_configs"))
        )
        return back.to_pandas() if isinstance(data, pd.DataFrame) else back
    return data


def _clean_read_cfg(cfg):
    cfg = copy.deepcopy(cfg) if cfg else {}
    cfg.pop("repartition", None)
    cfg.pop("mode", None)
    return cfg


def stats_args(
    all_configs: dict, func: str, run_type: str = "local", auth_key: str = "NA"
) -> dict:
    """Wire cached stats CSVs into downstream kwargs (reference :91-145).

    The configured ``master_path`` may be remote (s3://, wasbs://) on
    emr/ak8s, but the consumers read with the local reader — so the path is
    resolved through the run_type store's staging dir, which is exactly
    where ``save_stats`` just wrote the same CSV."""
    stats_configs = all_configs.get("stats_generator", None)
    write_configs = all_configs.get("write_stats", None)
    report_configs = all_configs.get("report_preprocessing", None)
    report_input_path = ""
    if report_configs is not None:
        if "master_path" not in report_configs:
            raise TypeError("Master path missing for saving report statistics")
        report_input_path = report_configs.get("master_path")
    result = {}
    if not stats_configs:
        return result
    if report_input_path:
        from anovos_tpu.shared.artifact_store import for_run_type

        store = for_run_type(run_type, auth_key)
        configured = report_input_path
        report_input_path = store.staging_dir(report_input_path)
        # split-job runs (stats produced by an EARLIER job on another
        # cluster) find an empty staging dir — pull the remote contents
        # down before handing consumers a local path
        if report_input_path != configured and not (
            os.path.isdir(report_input_path) and os.listdir(report_input_path)
        ):
            try:
                report_input_path = store.pull_dir(configured, report_input_path)
            except Exception as e:  # nothing remote yet: same-process flow
                logger.warning("stats pull from %s failed (%s); using staging", configured, e)
    for arg in MAINFUNC_TO_ARGS.get(func, []):
        if report_input_path:
            result[arg] = {
                "file_path": os.path.join(report_input_path, ARGS_TO_STATSFUNC[arg] + ".csv"),
                "file_type": "csv",
                "file_configs": {"header": True, "inferSchema": True},
            }
        elif write_configs:
            read = copy.deepcopy(write_configs)
            read["file_configs"] = _clean_read_cfg(read.get("file_configs"))
            read["file_path"] = os.path.join(
                read["file_path"], "data_analyzer/stats_generator", ARGS_TO_STATSFUNC[arg]
            )
            result[arg] = read
    return result


def _stats_deps(all_configs: dict, func: str) -> tuple:
    """Scheduler resources ``func`` will READ through ``stats_args`` — the
    ``stats:<metric>`` CSVs the configured stats_generator produces.  Only
    resources some node actually writes become edges (the scheduler ignores
    reads of never-written resources, mirroring the sequential runner where
    a consumer simply finds whatever pre-exists on disk)."""
    stats_configs = all_configs.get("stats_generator") or {}
    if not stats_configs:
        return ()
    if not (all_configs.get("report_preprocessing") or all_configs.get("write_stats")):
        return ()
    metrics = set(stats_configs.get("metric", []) or [])
    return tuple(
        f"stats:{ARGS_TO_STATSFUNC[a]}"
        for a in MAINFUNC_TO_ARGS.get(func, [])
        if ARGS_TO_STATSFUNC[a] in metrics
    )


def _auth_key(auth_key_val: Optional[dict]) -> str:
    """The SAS token is the last value of the auth dict (reference :148-157
    sets each pair on the spark conf and keeps the last value as auth_key)."""
    return list(auth_key_val.values())[-1] if auth_key_val else "NA"


def _clean_spec(d: Optional[dict]) -> dict:
    """Spec comparison form: None-valued keys are ignored by ETL, so they
    are ignored by equality too (shared by the registration-time check and
    the drift node body — one comparison rule)."""
    return {k: v for k, v in (d or {}).items() if v is not None}


def _drift_source_matches_input(all_configs: dict) -> bool:
    """True when drift_statistics will diff the dataset against itself —
    the only case worth pinning the pre-treatment ingest Table for."""
    dd = (all_configs.get("drift_detector") or {}).get("drift_statistics") or {}
    if (dd.get("configs") or {}).get("pre_existing_source", False):
        return False
    src = dd.get("source_dataset")
    return bool(src) and _clean_spec(src) == _clean_spec(all_configs.get("input_dataset"))


def _uses_preexisting(cfg) -> bool:
    """True when a config subtree loads pre-existing models/sources from
    disk — state the cache key cannot see, so such nodes stay uncacheable
    rather than risk a stale hit."""
    if isinstance(cfg, dict):
        for k, v in cfg.items():
            if k in ("pre_existing_model", "pre_existing_source") and bool(v):
                return True
            if _uses_preexisting(v):
                return True
    elif isinstance(cfg, (list, tuple)):
        return any(_uses_preexisting(v) for v in cfg)
    return False


def _slice_or_none(slice_: dict, *gate_cfgs) -> Optional[dict]:
    """The cache slice, or None (uncacheable) when any gate config pulls
    pre-existing on-disk state into the computation."""
    if any(_uses_preexisting(g) for g in gate_cfgs):
        return None
    return slice_


def _node_policies() -> tuple:
    """(spine policy, fanout policy) for this run's registrations.

    Both classes retry transient failures (``ANOVOS_TPU_RETRIES``
    re-executions, default 1 — a flaky node no longer costs the run);
    retry is sound here because every registration's effect contract is
    GC006-verified exact, so a re-execution overwrites the discarded
    partial artifacts.  They differ on the two policy axes the scheduler
    exposes:

    * **timeout escalation** — spine nodes (df treatments, transformers)
      get 2x patience on escalation: they are load-bearing and
      legitimately slow on big tables.  Read-only fan-out analyzers get
      1.5x: a stuck analyzer should resolve to degradation quickly.
    * **exhaustion** — a spine node that still fails aborts (its output
      df version is every downstream node's input); a fan-out analytics
      node degrades: the run completes, the manifest ``resilience``
      section records the section, and the report renders a placeholder.
      ``ANOVOS_TPU_DEGRADE=0`` restores abort-on-exhaustion everywhere.
    """
    retries = int(os.environ.get("ANOVOS_TPU_RETRIES", "1"))
    degrade = os.environ.get("ANOVOS_TPU_DEGRADE", "1") != "0"
    spine = ErrorPolicy(mode="retry", retries=retries, on_exhausted="raise",
                        timeout_factor=2.0)
    fanout = ErrorPolicy(mode="retry", retries=retries,
                         on_exhausted="degrade" if degrade else "raise",
                         timeout_factor=1.5)
    return spine, fanout


class _LazyTable:
    """A df version restored from the cache, loaded on first access.

    On a fully-cached run only the FINAL version is ever touched (by the
    ``write_main`` save), so every intermediate spine checkpoint stays on
    disk; an incremental run loads exactly the versions its re-executing
    cone reads.  Resolution is lock-guarded — two fan-out nodes pinned to
    the same restored version may race their first read."""

    __slots__ = ("_path", "_table", "_lock")

    def __init__(self, path: str):
        self._path = path
        self._table = None
        self._lock = threading.Lock()

    def get(self) -> Table:
        with self._lock:
            if self._table is None:
                self._table = data_ingest.read_dataset(self._path, "parquet")
            return self._table


def _write_frame_csv(df, path: str) -> None:
    """Async-writer body for a streaming stats frame (tiny CSV)."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    df.to_csv(path, index=False)


class _PipelineRun:
    """Per-run registrar: turns the YAML walk into scheduler nodes.

    Spine nodes thread ``df`` through explicit versions (``df:N`` →
    ``df:N+1``); fan-out nodes pin the version current at their YAML
    position, so a later spine mutation can never leak backwards into a
    concurrently-running analyzer.  Versions are dropped once their last
    registered reader releases them, bounding peak memory to the live
    working set instead of the whole version history.

    With ``cache_base`` set (``ANOVOS_TPU_CACHE``), registrations that
    pass a ``cache_slice`` get a :class:`NodeCachePolicy`: the slice is
    the node's OWN config material, folded with the run base (version,
    env knobs, dataset fingerprint, global paths) and, by the scheduler,
    with RAW-dep fingerprints.  Spine nodes additionally checkpoint their
    output df version into the store's payload dir so a cache hit can
    skip the body yet still hand downstream nodes (and the final
    ``write_main``) the table — lazily, via :class:`_LazyTable`."""

    def __init__(self, sched: DagScheduler, writer: AsyncArtifactWriter, df0: Table,
                 cache_base: Optional[str] = None):
        self.sched = sched
        self.writer = writer
        self.cache_base = cache_base
        self.spine_policy, self.fanout_policy = _node_policies()
        self._versions = {0: df0}
        self._version_locks: dict = {}  # df version -> lock of its share_lane readers
        self._planned_readers: dict = {}
        self._ver = 0
        self._lock = threading.Lock()
        self.artifact_keys: list = []  # registration-ordered unique resources

    # -- df version store ------------------------------------------------
    def _claim(self, v: int) -> None:
        self._planned_readers[v] = self._planned_readers.get(v, 0) + 1

    def _release(self, v: int) -> None:
        with self._lock:
            self._planned_readers[v] -= 1
            if self._planned_readers[v] <= 0 and v != self._ver:
                self._versions.pop(v, None)

    def _resolve(self, v: int) -> Table:
        df = self._versions[v]
        if isinstance(df, _LazyTable):
            df = df.get()
        return df

    def current_df(self) -> Table:
        return self._resolve(self._ver)

    def _track(self, writes) -> None:
        for w in writes:
            if w not in self.artifact_keys:
                self.artifact_keys.append(w)

    # -- placement ---------------------------------------------------------
    @staticmethod
    def _effective_placement(placement: str) -> str:
        """``ANOVOS_TPU_PLACEMENT=mesh`` forces device-placed fan-out
        analytics back onto the global mesh — the escape hatch for tables
        too large for a single chip's replica (registrations keep literal
        placements so graftcheck GC011 can audit them; the override is
        applied here, at registration time, for both executors alike)."""
        if placement == "device" and os.environ.get(
                "ANOVOS_TPU_PLACEMENT", "") == "mesh":
            return "mesh"
        return placement

    # -- cache wiring ------------------------------------------------------
    def _policy(self, name, cache_slice, writes, placement="mesh",
                payload_write=None, on_hit=None):
        if self.cache_base is None or cache_slice is None:
            return None
        # placement is part of node identity: a device-placed analyzer and
        # its mesh-placed twin legitimately differ in float artifacts
        # (different reduction layouts), so they must never share entries
        return NodeCachePolicy(
            key_material=node_fingerprint(
                self.cache_base, name,
                {"placement": placement, "slice": cache_slice}, writes),
            flush=self.writer.wait,
            payload_write=payload_write,
            on_hit=on_hit,
        )

    def _save_df(self, v: int, payload_dir: str) -> None:
        """Checkpoint a spine node's output version into the cache payload
        (parquet through the pipeline's own writer/reader pair, so the
        round trip has exactly the checkpoint path's tested semantics)."""
        data_ingest.write_dataset(
            self._resolve(v), os.path.join(payload_dir, "df"), "parquet",
            {"mode": "overwrite"},
        )

    # -- node registration -------------------------------------------------
    def spine(self, name, fn, reads=(), writes=(), timed=None, cache_slice=None,
              on_error=None, placement="mesh") -> None:
        """``fn(df) -> df`` mutates the table: df version N → N+1.

        Spine nodes are ``mesh``-placed: their output version is every
        downstream node's input and must stay on the global mesh layout."""
        v, out_v = self._ver, self._ver + 1
        self._ver = out_v
        self._claim(v)
        reads = tuple(reads)
        placement = self._effective_placement(placement)

        def body():
            self.writer.wait(reads)
            df_in = self._resolve(v)
            t0 = time.monotonic()
            df_out = fn(df_in)
            if timed:
                _log_block_time(timed, t0)
            self._versions[out_v] = df_out if df_out is not None else df_in
            self._release(v)

        def on_hit(payload_dir, v=v, out_v=out_v):
            # skipped body: hand downstream the checkpointed output version
            if payload_dir is None:  # entry committed without its df: unusable
                raise RuntimeError("spine cache entry has no df payload")
            self._versions[out_v] = _LazyTable(os.path.join(payload_dir, "df"))
            self._release(v)

        self.sched.add(name, body, reads=(f"df:{v}",) + reads,
                       writes=(f"df:{out_v}",) + tuple(writes),
                       on_error=on_error if on_error is not None else self.spine_policy,
                       placement=placement,
                       cache=self._policy(name, cache_slice, writes,
                                          placement=placement,
                                          payload_write=lambda d: self._save_df(out_v, d),
                                          on_hit=on_hit))
        self._track(writes)

    def aside(self, name, fn, reads=(), writes=(), timed=None, cache_slice=None,
              on_error=None, placement="host") -> None:
        """``fn()`` never touches the df spine: an out-of-core node that
        reads its OWN part files through the streaming/prefetch pipeline
        (the table may not even exist — streaming-only runs skip ETL).
        No ``df:N`` read is declared, so the scheduler is free to overlap
        it with the entire spine."""
        reads = tuple(reads)
        placement = self._effective_placement(placement)

        def body():
            self.writer.wait(reads)
            t0 = time.monotonic()
            fn()
            if timed:
                _log_block_time(timed, t0)

        self.sched.add(name, body, reads=reads, writes=tuple(writes),
                       on_error=on_error if on_error is not None else self.fanout_policy,
                       placement=placement,
                       cache=self._policy(name, cache_slice, writes,
                                          placement=placement))
        self._track(writes)

    def fanout(self, name, fn, reads=(), writes=(), timed=None, cache_slice=None,
               on_error=None, placement="mesh", share_lane=False) -> None:
        """``fn(df)`` only reads the table: pinned to the current version.

        ``share_lane`` (with ``placement="mesh"``): the readers of one
        table version hold the rendezvous lane as one claim, are in flight
        together and run ``fn`` one at a time under the version's lock — for
        a family whose members all want one memoized result of the
        mesh-resident table (``stats_generator``: the first computes the
        partitioned describe, the others wait for it and read the memo).

        ``placement="device"`` fans the node out onto one leased chip: the
        executor's placement scope re-places the pinned df version onto a
        single-device mesh (``Table.to_active_placement``) before the body
        sees it, so every program the analyzer dispatches is rendezvous-
        free and overlaps the collective lane.  ``"host"`` skips the
        re-place entirely (report rendering reads CSVs, not the table)."""
        v = self._ver
        self._claim(v)
        reads = tuple(reads)
        placement = self._effective_placement(placement)
        turn = self._version_locks.setdefault(v, threading.Lock()) if share_lane else None

        def body():
            self.writer.wait(reads)
            df_in = self._resolve(v).to_active_placement()
            t0 = time.monotonic()
            # where another reader of this version has the turn, the wait is a
            # row of the pass's tree under this node (``lane/wait``)
            with (get_tracer().holding(turn, "lane/wait", cat="node") if turn is not None
                  else contextlib.nullcontext()):
                fn(df_in)
            if timed:
                _log_block_time(timed, t0)
            self._release(v)

        self.sched.add(name, body, reads=(f"df:{v}",) + reads, writes=tuple(writes),
                       on_error=on_error if on_error is not None else self.fanout_policy,
                       placement=placement,
                       lane_group=f"df:{v}" if share_lane else None,
                       cache=self._policy(name, cache_slice, writes,
                                          placement=placement,
                                          on_hit=lambda _pdir, v=v: self._release(v)))
        self._track(writes)


@contextlib.contextmanager
def _pass():
    """The pass's root span ``run``.  ``run()`` opens it, so that the config
    pull and a profiler's start and export lie inside; ``main()`` called
    directly opens its own.  Once the root has ended the run manifest is
    written, last of all, with the pass's phases and the scheduler's origin
    on their clock (so the file's own write is on no span), and with what the
    process did before its first pass (``process``)."""
    global LAST_MANIFEST_PATH, _PASS_MANIFEST, _PASSES
    tracer = get_tracer()
    if tracer.in_pass():  # main() under run(): the root is run()'s
        yield
        return
    _PASS_MANIFEST = None
    pass_index, _PASSES = _PASSES, _PASSES + 1
    try:
        with tracer.run_pass():
            yield
    finally:
        pending, _PASS_MANIFEST = _PASS_MANIFEST, None
        if pending is not None:
            manifest, path, origin, store, base = pending
            manifest["phases"] = tracer.phases()
            manifest["clock"] = {
                "run_id": tracer.run_id,
                "scheduler_origin_s": None if origin is None else tracer.seconds_at(origin),
            }
            manifest["process"] = process_section(
                pass_index, IMPORT_STARTED, _IMPORTS_DONE,
                lambda t: tracer.seconds_at(t, perf_counter=True))
            write_manifest(manifest, path)
            LAST_MANIFEST_PATH = path
            try:  # remote run_types publish the manifest next to the staged stats
                store.push(path, os.path.join(base, "obs"))
            except Exception:
                logger.exception("manifest push failed; local copy kept at %s", path)


@contextlib.contextmanager
def _profiler_session(profile_dir: str):
    """A JAX profiler session into ``profile_dir`` (none where that is empty)
    in which the tracer annotates phase and node spans.  Host and device
    tracers as they are by default, the python tracer off: with it a traced
    pass took twice an untraced one's time and left a 58 MB trace (PERF.md,
    PR 24), and ``TraceAnnotation``s, which the host tracer records, do not
    need it.  The session's start and export are phases of the pass."""
    if not profile_dir:
        yield
        return
    import jax

    from anovos_tpu.obs import tracing

    tracer = get_tracer()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with tracer.phase("profiler:start"):
        jax.profiler.start_trace(profile_dir, profiler_options=options)
    tracing.annotate_with(jax.profiler.TraceAnnotation)
    try:
        yield
    finally:
        tracing.annotate_with(None)
        with tracer.phase("profiler:export"):
            jax.profiler.stop_trace()


def main(
    all_configs: dict,
    run_type: str = "local",
    auth_key_val: Optional[dict] = None,
    resume: bool = False,
) -> None:
    """One pass over ``all_configs``.  Called directly it is a pass of its
    own; under ``run()`` it is the part of that pass after the config."""
    with _pass():
        _main(all_configs, run_type, auth_key_val, resume)


def _main(all_configs: dict, run_type: str, auth_key_val: Optional[dict],
          resume: bool) -> None:
    global LAST_RUN_SUMMARY, LAST_MANIFEST_PATH, _PASS_MANIFEST
    tracer = get_tracer()
    start_main = time.monotonic()
    with tracer.phase("reset"):
        # per-run accounting: the metrics registry (and the trace buffer,
        # which the pass's root span cleared) always describe the most
        # recent run (the successor of BLOCK_TIMES.clear());
        # the op-level compile caches persist, so a warm run's manifest shows
        # cache hits instead of compiles — exactly the steady-state picture
        get_metrics().reset()
        # compile census delta for THIS run: the listener is process-wide
        # (installed at init_runtime), the manifest embeds only what compiled
        # after this mark — a warm in-process rerun shows ~zero compiles
        compile_census.install()
        census_mark = compile_census.mark()
        LAST_RUN_SUMMARY = {}
        LAST_MANIFEST_PATH = ""
        # resilience state is per-run: a fresh chaos plan from the env spec
        # (inert when ANOVOS_TPU_CHAOS is unset), an empty degradation
        # registry, and a re-armed failover (a new run may probe/flip again)
        chaos.install_from_env()
        res_policy.reset_degraded()
        res_failover.reset()
        # the ingest guard's quarantine registry is per-run too; its manifest
        # destination is configured once the obs/ subtree is known below —
        # parts quarantined during the ETL read buffer until then
        ingest_guard.reset()
    auth_key = _auth_key(auth_key_val)
    stream_cfg = all_configs.get("streaming_analysis")
    if all_configs.get("input_dataset") is None and (
            stream_cfg or all_configs.get("continuous_analysis")):
        # out-of-core / continuum mode: the dataset never materializes as
        # a Table — every registered node reads its own part files
        # through the prefetch pipeline (streaming passes, or the
        # continuum arrival loop folding newly-landed partitions)
        df = None
    else:
        with tracer.phase("ingest"):
            df = ETL(all_configs.get("input_dataset"))

    mlflow_config = all_configs.get("mlflow", None)
    mlflow_ctx = contextlib.nullcontext()
    if mlflow_config is not None:
        try:  # pragma: no cover - optional dependency
            import mlflow

            mlflow.set_tracking_uri(mlflow_config["tracking_uri"])
            mlflow.set_experiment(mlflow_config["experiment"])
            mlflow_ctx = mlflow.start_run()
        except ImportError:
            logger.warning("mlflow configured but not installed; skipping tracking")
            mlflow_config = None

    with mlflow_ctx:
        with tracer.phase("register"):
            # pre-treatment ingest result, pinned ONLY when a drift_statistics spec
            # will actually reuse it (pinning unconditionally would hold the full
            # ingest-time table in memory through the whole run for nothing)
            base_df = df if (df is not None and _drift_source_matches_input(all_configs)) else None

            write_main = all_configs.get("write_main", None)
            write_intermediate = all_configs.get("write_intermediate", None)
            write_stats = all_configs.get("write_stats", None)

            report_input_path = ""
            report_configs = all_configs.get("report_preprocessing", None)
            if report_configs is not None:
                if "master_path" not in report_configs:
                    raise TypeError("Master path missing for saving report statistics")
                report_input_path = report_configs.get("master_path")

            basic_report_flag = all_configs.get("anovos_basic_report", {}) or {}
            basic_report_flag = basic_report_flag.get("basic_report", False)

            # executor selection: ANOVOS_TPU_EXECUTOR wins; the auto default runs
            # the DAG concurrently wherever a second core exists and degenerates to
            # the sequential schedule on single-core hosts, where worker threads
            # can only timeshare the core and inflate the wall (measured +4-15%)
            from anovos_tpu.parallel.scheduler import available_cpus

            mode = os.environ.get("ANOVOS_TPU_EXECUTOR", "") or (
                "concurrent" if available_cpus() > 1 else "sequential"
            )
            # Multi-device meshes no longer degrade concurrent to sequential: every
            # registration below declares a placement (mesh | device | host —
            # audited by graftcheck GC011), and the scheduler's lane discipline
            # keeps at most one collective program set in flight mesh-wide (the
            # rendezvous lane) while device-placed analyzers fan out on leased
            # chips.  The old failure mode — two concurrently dispatched collective
            # programs enqueueing in different per-device stream orders and
            # deadlocking at the AllReduce rendezvous — is structurally excluded.
            writer = AsyncArtifactWriter(
                workers=int(os.environ.get("ANOVOS_TPU_WRITER_WORKERS", "2")),
                sync=(mode == "sequential"),
            )
            # incremental recompute (anovos_tpu.cache): ANOVOS_TPU_CACHE=<dir> opts
            # in.  Registrations below pass their config slice; the scheduler folds
            # RAW-edge fingerprints and skips nodes whose committed results match.
            cache_store = None
            cache_base = None
            cache_dir = cache_root()
            if cache_dir:
                cache_store = CacheStore(cache_dir)
                cache_base = base_material(all_configs, run_type)
                cache_capture.install_open_hook()
            elif resume:
                logger.warning("--resume requested but ANOVOS_TPU_CACHE is unset; "
                               "nothing to resume from — executing every node")
            sched = DagScheduler(name="workflow", cache_store=cache_store)
            pipe = _PipelineRun(sched, writer, df, cache_base=cache_base)

            for key, args in all_configs.items():
                if key == "concatenate_dataset" and args is not None:
                    def _concat(df, args=args):
                        idfs = [df] + [ETL(args[k]) for k in args if k not in ("method", "method_type")]
                        out = data_ingest.concatenate_dataset(
                            *idfs, method_type=args.get("method", args.get("method_type", "name"))
                        )
                        return save(out, write_intermediate, "data_ingest/concatenate_dataset",
                                    reread=True, writer=writer)
                    pipe.spine("concatenate_dataset", _concat, timed="concatenate_dataset",
                               placement="mesh",
                               cache_slice={"concatenate_dataset": args, "dataset_fps": [
                                   dataset_fingerprint(args[k])
                                   for k in args if k not in ("method", "method_type")]})
                    continue

                if key == "join_dataset" and args is not None:
                    def _join(df, args=args):
                        idfs = [df] + [ETL(args[k]) for k in args if k not in ("join_type", "join_cols")]
                        out = data_ingest.join_dataset(
                            *idfs, join_cols=args.get("join_cols"), join_type=args.get("join_type")
                        )
                        return save(out, write_intermediate, "data_ingest/join_dataset",
                                    reread=True, writer=writer)
                    pipe.spine("join_dataset", _join, timed="join_dataset",
                               placement="mesh",
                               cache_slice={"join_dataset": args, "dataset_fps": [
                                   dataset_fingerprint(args[k])
                                   for k in args if k not in ("join_type", "join_cols")]})
                    continue

                if key == "timeseries_analyzer" and args is not None:
                    # omit None-valued config keys so callee defaults apply
                    opt = {k: v for k, v in args.items() if v is not None}
                    if opt.get("auto_detection", False):
                        # auto-detection is best-effort in the reference too
                        # (ts_auto_detection.py:707 swallows per-column failures):
                        # a malformed timestamp column must not kill the pipeline,
                        # and a detection failure must not also cost the inspection
                        def _ts_auto(df, opt=opt):
                            try:
                                return ts_preprocess(
                                    df, opt.get("id_col"), output_path=report_input_path or ".",
                                    tz_offset=opt.get("tz_offset", "local"), run_type=run_type,
                                )
                            except Exception as e:
                                logger.exception("ts auto-detection failed; continuing with the raw table")
                                # best-effort fallback, but no longer a SILENT one:
                                # the manifest + report placeholder name the section
                                res_policy.record_degraded(
                                    "timeseries_analyzer/auto_detection",
                                    f"{type(e).__name__}: {e}")
                                return df
                        pipe.spine("timeseries_analyzer/auto_detection", _ts_auto,
                                   writes=("report:ts_autodetect",), timed="timeseries_analyzer",
                                   placement="mesh",
                                   cache_slice={"timeseries_analyzer": opt, "mode": "auto"})
                    if opt.get("inspection", False):
                        def _ts_inspect(df, opt=opt):
                            try:
                                from anovos_tpu.data_analyzer.ts_analyzer import ts_analyzer

                                kw = {k: opt[k] for k in ("max_days", "tz_offset") if k in opt}
                                if "analysis_level" in opt:
                                    kw["output_type"] = opt["analysis_level"]
                                ts_analyzer(
                                    df, opt.get("id_col"), output_path=report_input_path or ".",
                                    run_type=run_type, **kw,
                                )
                            except Exception as e:
                                logger.exception("ts inspection failed; continuing without ts analysis")
                                res_policy.record_degraded(
                                    "timeseries_analyzer/inspection",
                                    f"{type(e).__name__}: {e}")
                        # placement: the inspection body reaches ts_analyzer's
                        # column_parallel sharding constraints — a collective
                        # dispatch, so the node must ride the rendezvous lane
                        # (graftcheck GC011, whole-program closure)
                        pipe.fanout("timeseries_analyzer/inspection", _ts_inspect,
                                    writes=("report:ts_inspection",), timed="timeseries_analyzer",
                                    placement="mesh",
                                    cache_slice={"timeseries_analyzer": opt, "mode": "inspect"})
                    continue

                if key == "geospatial_controller" and args is not None:
                    ga = args.get("geospatial_analyzer", {}) or {}
                    if ga.get("auto_detection_analyzer", False):
                        kw = {
                            k: ga[k]
                            for k in (
                                "max_analysis_records", "top_geo_records", "max_cluster",
                                "eps", "min_samples", "global_map_box_val",
                            )
                            if ga.get(k) is not None
                        }

                        def _geo(df, ga=ga, kw=kw):
                            from anovos_tpu.data_analyzer.geospatial_analyzer import geospatial_autodetection

                            try:
                                geospatial_autodetection(
                                    df, ga.get("id_col"), report_input_path or ".", run_type=run_type, **kw
                                )
                            except Exception as e:
                                logger.exception("geospatial_analyzer failed; continuing without geo analysis")
                                res_policy.record_degraded(
                                    "geospatial_controller", f"{type(e).__name__}: {e}")
                        pipe.fanout("geospatial_controller", _geo,
                                    writes=("report:geo",), timed="geospatial_controller",
                                    placement="mesh",
                                    cache_slice={"geospatial_controller": ga})
                    continue

                if key == "anovos_basic_report" and args is not None and args.get("basic_report", False):
                    def _basic(df, args=args):
                        anovos_basic_report(df, **args.get("report_args", {}), run_type=run_type, auth_key=auth_key)
                    pipe.fanout("anovos_basic_report", _basic,
                                writes=("report:basic",), timed="Basic Report",
                                placement="mesh",
                                cache_slice={"anovos_basic_report": args})
                    continue

                if basic_report_flag:
                    continue

                if key == "stats_generator" and args is not None:
                    # dedupe: a repeated metric in a hand-edited YAML must not
                    # trip the scheduler's duplicate-node check (the sequential
                    # walk used to run it twice, overwriting the same CSV)
                    for m in dict.fromkeys(args["metric"]):
                        def _stat(df, m=m, args=args):
                            df_stats = getattr(stats_generator, m)(df, **args["metric_args"])
                            if report_input_path:
                                save_stats(df_stats, report_input_path, m, run_type=run_type,
                                           auth_key=auth_key, async_writer=writer, async_key=f"stats:{m}")
                            else:
                                save(df_stats, write_stats, "data_analyzer/stats_generator/" + m,
                                     reread=True, writer=writer, key=f"stats:{m}")
                        # mesh-placed on one shared claim: the seven nodes read ONE Table
                        # instance and with it one memoized describe, which on a multi-chip
                        # runtime is the partitioned program (column-parallel sort, psum'd
                        # moments).  Device-placed, each node got its own replica of the
                        # whole table on its leased chip, and with the replica a memo of its
                        # own: six unpartitioned describes and seven table copies a pass
                        # (PERF.md §6, PR 27).
                        pipe.fanout(f"stats_generator/{m}", _stat,
                                    writes=(f"stats:{m}",), timed=f"stats_generator, {m}",
                                    placement="mesh", share_lane=True,
                                    cache_slice={"metric": m, "metric_args": args["metric_args"]})

                if key == "quality_checker" and args is not None:
                    for subkey, value in args.items():
                        if value is None:
                            continue

                        def _qc(df, subkey=subkey, value=value, args=args):
                            extra_args = stats_args(all_configs, subkey, run_type, auth_key)
                            if subkey == "nullColumns_detection":
                                # upstream treatments invalidate cached missing stats (ref :552-566)
                                if (args.get("invalidEntries_detection") or {}).get("treatment"):
                                    extra_args["stats_missing"] = {}
                                if (args.get("outlier_detection") or {}).get("treatment") and (
                                    args.get("outlier_detection") or {}
                                ).get("treatment_method") == "null_replacement":
                                    extra_args["stats_missing"] = {}
                            df_out, df_stats = getattr(quality_checker, subkey)(df, **value, **extra_args)
                            df_out = save(
                                df_out, write_intermediate,
                                "data_analyzer/quality_checker/" + subkey + "/dataset",
                                reread=True, writer=writer,
                            )
                            if report_input_path:
                                save_stats(df_stats, report_input_path, subkey, run_type=run_type,
                                           auth_key=auth_key, async_writer=writer, async_key=f"stats:{subkey}")
                            else:
                                save(df_stats, write_stats, "data_analyzer/quality_checker/" + subkey,
                                     reread=True, writer=writer, key=f"stats:{subkey}")
                            return df_out
                        pipe.spine(f"quality_checker/{subkey}", _qc,
                                   reads=_stats_deps(all_configs, subkey),
                                   writes=(f"stats:{subkey}",), timed=f"quality_checker, {subkey}",
                                   placement="mesh",
                                   # the whole block: cross-subkey treatment flags
                                   # feed this node's stats_args invalidation
                                   cache_slice=_slice_or_none(
                                       {"quality_checker": args}, value))

                if key == "association_evaluator" and args is not None:
                    for subkey, value in args.items():
                        if value is None:
                            continue

                        def _assoc(df, subkey=subkey, value=value):
                            extra_args = stats_args(all_configs, subkey, run_type, auth_key)
                            if subkey == "correlation_matrix":
                                cat_params = all_configs.get("cat_to_num_transformer", None)
                                df_in = (
                                    transformers.cat_to_num_transformer(df, **cat_params) if cat_params else df
                                )
                            else:
                                df_in = df
                            df_stats = getattr(association_evaluator, subkey)(df_in, **value, **extra_args)
                            with get_tracer().phase("assoc/write", cat="block", rows=len(df_stats)):
                                if report_input_path:
                                    save_stats(df_stats, report_input_path, subkey, run_type=run_type,
                                               auth_key=auth_key, async_writer=writer, async_key=f"stats:{subkey}")
                                else:
                                    save(df_stats, write_stats, "data_analyzer/association_evaluator/" + subkey,
                                         reread=True, writer=writer, key=f"stats:{subkey}")
                        assoc_slice = {subkey: value}
                        if subkey == "correlation_matrix":
                            assoc_slice["cat_to_num_transformer"] = all_configs.get(
                                "cat_to_num_transformer")
                        pipe.fanout(f"association_evaluator/{subkey}", _assoc,
                                    reads=_stats_deps(all_configs, subkey),
                                    writes=(f"stats:{subkey}",), timed=f"{key}, {subkey}",
                                    placement="device",
                                    cache_slice=_slice_or_none(assoc_slice, value))

                if key == "drift_detector" and args is not None:
                    # one node body PER subkey (not a shared body branching on a
                    # registration-time default arg): the declared writes= of
                    # each registration then match the callee's actual effects
                    # EXACTLY, which is what graftcheck's GC006 contract audit
                    # verifies — a shared body makes every effect a may-effect
                    for subkey, value in args.items():
                        if value is None or subkey not in ("drift_statistics", "stability_index"):
                            continue

                        if subkey == "drift_statistics":
                            def _drift_stats(df, value=value):
                                source = None
                                if not value["configs"].get("pre_existing_source", False):
                                    src_spec = value.get("source_dataset")
                                    # the demo configs diff the dataset against
                                    # itself: an identical source spec reuses the
                                    # already-ingested base table instead of
                                    # re-paying the read + device upload
                                    if (
                                        base_df is not None
                                        and src_spec
                                        and _clean_spec(src_spec) == _clean_spec(all_configs.get("input_dataset"))
                                    ):
                                        source = base_df
                                    else:
                                        source = _read_inside("drift/read", src_spec)
                                # statistics() also persists the drift frequency
                                # model (the charts node's drift tab reads it)
                                df_stats = ddetector.statistics(df, source, **value["configs"])
                                if report_input_path:
                                    save_stats(df_stats, report_input_path, "drift_statistics",
                                               run_type=run_type, auth_key=auth_key,
                                               async_writer=writer, async_key="stats:drift_statistics")
                                else:
                                    save(df_stats, write_stats, "drift_detector/drift_statistics",
                                         reread=True, writer=writer, key="stats:drift_statistics")
                            pipe.fanout("drift_detector/drift_statistics", _drift_stats,
                                        writes=("stats:drift_statistics", "drift:model"),
                                        timed=f"{key}, drift_statistics",
                                        placement="mesh",
                                        # source files are a second input dataset:
                                        # their stat signature joins the slice
                                        cache_slice=_slice_or_none(
                                            {"drift_statistics": value,
                                             "source_fp": dataset_fingerprint(
                                                 value.get("source_dataset"))},
                                            value))
                        else:
                            def _stability(df, value=value):
                                idfs = [_read_inside("stability/read", value[k]) for k in value if k != "configs"]
                                df_stats = dstability.stability_index_computation(*idfs, **value["configs"])
                                if report_input_path:
                                    save_stats(df_stats, report_input_path, "stability_index",
                                               run_type=run_type, auth_key=auth_key,
                                               async_writer=writer, async_key="stats:stability_index")
                                    amp = value["configs"].get("appended_metric_path", "")
                                    if amp:
                                        metrics = data_ingest.read_dataset(amp, "csv", {"header": True})
                                        save_stats(metrics.to_pandas(), report_input_path,
                                                   "stabilityIndex_metrics", run_type=run_type,
                                                   auth_key=auth_key, async_writer=writer,
                                                   async_key="stats:stabilityIndex_metrics")
                                else:
                                    save(df_stats, write_stats, "drift_detector/stability_index",
                                         reread=True, writer=writer, key="stats:stability_index")
                            stab_cfg = value.get("configs") or {}
                            # the metric paths are APPENDED to across runs: a
                            # retry after a partial append could double-book a
                            # window, so this node opts out of re-execution
                            # (the discard pass protects append files, but not
                            # against the append itself having landed twice)
                            stab_retry = None
                            if stab_cfg.get("appended_metric_path") or stab_cfg.get(
                                    "existing_metric_path"):
                                stab_retry = "raise"
                            pipe.fanout("drift_detector/stability_index", _stability,
                                        writes=("stats:stability_index", "stats:stabilityIndex_metrics"),
                                        timed=f"{key}, stability_index",
                                        on_error=stab_retry,
                                        placement="device",
                                        # the metric paths are cross-RUN state (the
                                        # computation appends to them): their current
                                        # on-disk signature is part of the key, so a
                                        # populated dir recomputes exactly like the
                                        # uncached appending behavior would
                                        cache_slice=_slice_or_none(
                                            {"stability_index": value,
                                             "dataset_fps": {
                                                 k: dataset_fingerprint(value[k])
                                                 for k in sorted(value) if k != "configs"},
                                             "metric_path_fps": [
                                                 dataset_fingerprint(
                                                     {"read_dataset": {"file_path": p}})
                                                 for p in (stab_cfg.get("appended_metric_path", ""),
                                                           stab_cfg.get("existing_metric_path", ""))
                                                 if p]},
                                            value))

                if key == "transformers" and args is not None:
                    for subkey, value in args.items():
                        if value is None:
                            continue
                        for subkey2, value2 in value.items():
                            if value2 is None:
                                continue

                            def _tf(df, subkey2=subkey2, value2=value2):
                                extra_args = stats_args(all_configs, subkey2, run_type, auth_key)
                                f = getattr(transformers, subkey2)
                                df_out = f(df, **value2, **extra_args)
                                return save(
                                    df_out, write_intermediate,
                                    "data_transformer/transformers/" + subkey2,
                                    reread=True, writer=writer,
                                )
                            pipe.spine(f"transformers/{subkey2}", _tf,
                                       reads=_stats_deps(all_configs, subkey2),
                                       timed=f"{key}, {subkey2}",
                                       placement="mesh",
                                       cache_slice=_slice_or_none({subkey2: value2}, value2))

                if key == "streaming_analysis" and args is not None:
                    # out-of-core whole-table passes (round 12): each enabled
                    # sub-analysis streams its part files through the prefetch
                    # pipeline — the table never materializes, host RSS stays
                    # bounded by the in-flight window, and every pass is
                    # chunk-checkpointed under obs/stream_ckpt so --resume
                    # re-reads only undone chunks.  Artifacts are byte-
                    # identical to the in-memory equivalents.
                    s_path = args.get("file_path")
                    if not s_path:
                        raise TypeError("streaming_analysis requires file_path")
                    s_type = args.get("file_type", "parquet")
                    s_chunk = int(args.get("chunk_rows", 1_000_000) or 1_000_000)
                    s_fcfg = args.get("file_configs")
                    out_dir = (args.get("output_path") or report_input_path
                               or (write_stats or {}).get("file_path")
                               or "stream_stats")
                    ckpt_base = os.path.join(
                        report_input_path or (write_main or {}).get("file_path")
                        or ".", "obs", "stream_ckpt")
                    s_fp = dataset_fingerprint(
                        {"read_dataset": {"file_path": s_path}})

                    if args.get("describe") is not None and args.get("describe") is not False:
                        d_cfg = args["describe"] if isinstance(args["describe"], dict) else {}

                        def _stream_describe(d_cfg=d_cfg):
                            from anovos_tpu.ops.streaming import describe_streaming

                            odf = describe_streaming(
                                s_path, s_type, chunk_rows=s_chunk,
                                file_configs=s_fcfg,
                                checkpoint_dir=os.path.join(ckpt_base, "describe"),
                                resume=resume, **d_cfg)
                            writer.submit("stats:stream_describe", _write_frame_csv,
                                          odf, os.path.join(out_dir, "stream_describe.csv"))
                        pipe.aside("streaming_analysis/describe", _stream_describe,
                                   writes=("stats:stream_describe",),
                                   timed="streaming_analysis, describe",
                                   placement="device",
                                   cache_slice={"describe": d_cfg,
                                                "chunk_rows": s_chunk,
                                                "dataset_fp": s_fp})

                    if args.get("quality_missing") is not None and \
                            args.get("quality_missing") is not False:
                        q_cfg = args["quality_missing"] if isinstance(
                            args["quality_missing"], dict) else {}

                        def _stream_missing(q_cfg=q_cfg):
                            from anovos_tpu.data_analyzer.quality_checker import (
                                missing_stats_streaming)

                            odf = missing_stats_streaming(
                                s_path, s_type, chunk_rows=s_chunk,
                                file_configs=s_fcfg,
                                checkpoint_dir=os.path.join(ckpt_base, "quality_missing"),
                                resume=resume, **q_cfg)
                            writer.submit("stats:stream_missing", _write_frame_csv,
                                          odf, os.path.join(out_dir, "stream_missing.csv"))
                        pipe.aside("streaming_analysis/quality_missing", _stream_missing,
                                   writes=("stats:stream_missing",),
                                   timed="streaming_analysis, quality_missing",
                                   placement="host",
                                   cache_slice={"quality_missing": q_cfg,
                                                "chunk_rows": s_chunk,
                                                "dataset_fp": s_fp})

                    if args.get("quality_outlier"):
                        o_cfg = dict(args["quality_outlier"])
                        o_model = o_cfg.pop("model_path", None)
                        if not o_model:
                            raise TypeError(
                                "streaming_analysis.quality_outlier requires "
                                "model_path (pre-fitted outlier bounds)")

                        def _stream_outlier(o_cfg=o_cfg, o_model=o_model):
                            from anovos_tpu.data_analyzer.quality_checker import (
                                outlier_stats_streaming)

                            odf = outlier_stats_streaming(
                                s_path, s_type, o_model, chunk_rows=s_chunk,
                                file_configs=s_fcfg,
                                checkpoint_dir=os.path.join(ckpt_base, "quality_outlier"),
                                resume=resume, **o_cfg)
                            writer.submit("stats:stream_outlier", _write_frame_csv,
                                          odf, os.path.join(out_dir, "stream_outlier.csv"))
                        pipe.aside("streaming_analysis/quality_outlier", _stream_outlier,
                                   writes=("stats:stream_outlier",),
                                   timed="streaming_analysis, quality_outlier",
                                   placement="device",
                                   cache_slice={"quality_outlier": o_cfg,
                                                "chunk_rows": s_chunk,
                                                "dataset_fp": s_fp,
                                                "model_fp": dataset_fingerprint(
                                                    {"read_dataset": {"file_path": o_model}})})

                    if args.get("drift"):
                        dr_cfg = dict(args["drift"])
                        dr_src = dr_cfg.pop("source_file_path", None)

                        def _stream_drift(dr_cfg=dr_cfg, dr_src=dr_src):
                            from anovos_tpu.drift_stability.drift_detector import (
                                statistics_streaming)

                            odf = statistics_streaming(
                                s_path, s_type, dr_src, chunk_rows=s_chunk,
                                file_configs=s_fcfg,
                                checkpoint_dir=os.path.join(ckpt_base, "drift"),
                                resume=resume, **dr_cfg)
                            writer.submit("stats:stream_drift", _write_frame_csv,
                                          odf, os.path.join(out_dir, "stream_drift.csv"))
                        pipe.aside("streaming_analysis/drift", _stream_drift,
                                   writes=("stats:stream_drift", "drift:model"),
                                   timed="streaming_analysis, drift",
                                   placement="device",
                                   cache_slice={"drift": dr_cfg,
                                                "chunk_rows": s_chunk,
                                                "dataset_fp": s_fp,
                                                "source_fp": dataset_fingerprint(
                                                    {"read_dataset": {"file_path": dr_src}})})
                    continue

                if key == "continuous_analysis" and args is not None:
                    # one continuum arrival-loop step as a scheduler node
                    # (anovos_tpu.continuum): scan the feed directory, fold
                    # newly-landed partitions through the prefetch pool, re-
                    # finalize the incremental artifacts and re-render only
                    # the affected report sections.  Deliberately UNCACHEABLE
                    # (cache_slice=None): the node's output is a function of
                    # cross-run state (the fold frontier), which the node
                    # fingerprint cannot see.  The long-running loop is the
                    # `python -m anovos_tpu.continuum run` CLI; this node is
                    # the one-shot fold for workflow-driven deployments.
                    c_args = dict(args)

                    def _continuum_step(c_args=c_args):
                        from anovos_tpu.continuum.watcher import ContinuumConfig
                        from anovos_tpu.continuum.watcher import step as continuum_step

                        base = report_input_path or (write_main or {}).get("file_path") or "."
                        summary = continuum_step(
                            ContinuumConfig.from_dict(c_args, base_dir=base))
                        logger.info(
                            "continuous_analysis: folded=%d quarantined=%d "
                            "alerts=%d partitions=%d",
                            len(summary["folded"]), len(summary["quarantined"]),
                            summary["alerts"], summary["partitions"])
                    pipe.aside("continuous_analysis/step", _continuum_step,
                               timed="continuous_analysis",
                               placement="device")
                    continue

                if key == "report_preprocessing" and args is not None:
                    for subkey, value in args.items():
                        if subkey == "charts_to_objects" and value is not None:
                            chart_reads = _stats_deps(all_configs, subkey)
                            if value.get("drift_detector", False):
                                # the drift tab reuses the frequency model the
                                # drift_statistics node persists under
                                # intermediate_data/drift_statistics
                                chart_reads = chart_reads + ("drift:model",)

                            def _charts(df, subkey=subkey, value=value):
                                extra_args = stats_args(all_configs, subkey, run_type, auth_key)
                                charts_to_objects(df, **value, **extra_args, master_path=report_input_path,
                                                  run_type=run_type, auth_key=auth_key,
                                                  async_writer=writer, async_key="charts:objects")
                            # placement: charts_to_objects reaches column_parallel
                            # sharding constraints through the stats helpers — a
                            # collective dispatch, so the node must ride the
                            # rendezvous lane (graftcheck GC011, whole-program
                            # closure)
                            pipe.fanout(f"report_preprocessing/{subkey}", _charts,
                                        reads=chart_reads, writes=("charts:objects",),
                                        timed=f"{key}, {subkey}",
                                        placement="mesh",
                                        cache_slice={"charts_to_objects": value})

                if key == "report_generation" and args is not None:
                    # the report reads the whole master_path subtree: wait on
                    # every artifact-producing node registered so far, and on
                    # the async write queue having flushed them (the barrier)
                    art_reads = tuple(pipe.artifact_keys)

                    def _report(df, args=args):
                        anovos_report(**args, run_type=run_type, auth_key=auth_key)
                    # the report is the run's PRODUCT: retry a transient failure,
                    # never degrade it away
                    pipe.fanout("report_generation", _report, reads=art_reads,
                                timed=f"{key}, full_report",
                                placement="host",
                                on_error=ErrorPolicy(mode="retry", retries=1,
                                                     on_exhausted="raise",
                                                     timeout_factor=2.0))

            # ---- obs destinations (manifest + optional chrome trace) -------
            # the manifest lands next to the run's other artifacts: under the
            # report master_path when one is configured, else the main output
            # folder, else the working directory
            from anovos_tpu.shared.artifact_store import for_run_type

            obs_store = for_run_type(run_type, auth_key)
            obs_base = report_input_path or (write_main or {}).get("file_path") or "."
            obs_dir = obs_store.staging_dir(obs_base)
            trace_dest = trace_destination(obs_dir)
            manifest_path = os.path.abspath(os.path.join(obs_dir, "obs", "run_manifest.json"))
            # device-time attribution + flight recorder are armed per run: a
            # fresh devprof result set (and a warmed drain probe, so the first
            # node doesn't book the probe's compile), and postmortem dumps
            # pointed at this run's obs/ subtree (ANOVOS_TPU_FLIGHTREC=0 opts
            # out; a clean run writes no dump either way)
            devprof.reset()
            flight.configure(os.path.join(obs_dir, "obs"))
            # quarantine manifest lands in the same obs/ subtree (flushes any
            # parts the ETL read already set aside); clean runs write nothing
            ingest_guard.configure(os.path.join(obs_dir, "obs"))

            journal = None
            resumed_from = 0
            if cache_store is not None:
                journal_path = os.path.join(obs_dir, "obs", "run_journal.jsonl")
                # the journal is append-only ACROSS runs: a killed run's
                # committed frontier is still here when --resume re-runs
                prior = committed_fingerprints(read_journal(journal_path))
                if resume:
                    resumed_from = len(prior)
                    logger.info(
                        "resume: journal at %s records %d previously committed "
                        "node result(s); matching nodes will restore from %s",
                        journal_path, resumed_from, cache_store.root)
                journal = RunJournal(journal_path, writer)
                journal.append("run_begin", config_hash=config_hash(all_configs),
                               cache_root=cache_store.root, resume=bool(resume),
                               executor=mode)
                sched.journal = journal
                # parts quarantined from here on also land in the WAL as
                # part_quarantined events (the ETL read already ran; its
                # quarantines are in the manifest + registry regardless)
                ingest_guard.set_journal(journal)

        # live telemetry plane + trace segment rotation, both off by
        # default (ANOVOS_TPU_TELEMETRY / ANOVOS_TPU_TRACE_ROTATE unset
        # ⇒ zero new threads, byte-identical artifacts).  Rotation rides
        # the async artifact writer so a segment export never blocks the
        # traced threads; its destination anchors on the trace path.
        # Acquired IMMEDIATELY before the try whose finally releases them
        # — an exception in between would leak the listener refcount and
        # drop the final segment flush.
        telemetry_handle = telemetry.acquire(context="workflow")
        trace_rotator = maybe_rotator(obs_dir, submit=writer.submit)
        run_err = None
        try:
            with tracer.phase("dag"):
                summary = sched.run(mode=mode)
            if journal is not None:
                journal.append("run_end", hits=summary["cache"]["hits"],
                               misses=summary["cache"]["misses"])
            # barrier BEFORE the metrics snapshot: every queued artifact
            # write has landed and booked its counters, so sequential-mode
            # manifests are deterministic run-to-run
            writer.drain()
            with tracer.phase("manifest"):
                record_device_memory()
                record_cache_stats(cache_store)
                chaos_plan = chaos.plan()
                manifest = build_manifest(
                    all_configs, summary, get_metrics().snapshot(),
                    run_type=run_type, block_times=block_times(),
                    trace_path=trace_dest and os.path.abspath(trace_dest),
                    compile_census=compile_census.census(since=census_mark),
                    cache={
                        "enabled": cache_store is not None,
                        "root": cache_store.root if cache_store else None,
                        "resumed_from": resumed_from,
                        **summary.get("cache", {}),
                    } if cache_store is not None else None,
                    resilience={
                        **summary.get("resilience", {}),
                        "degraded_sections": res_policy.degraded_sections(),
                        # quarantined ingest parts with exact row counts (the
                        # data-plane degradation record; obs/quarantine_manifest
                        # .json is the crash-safe on-disk copy)
                        "quarantine": ingest_guard.summary(),
                        "chaos": chaos_plan.summary() if chaos_plan else None,
                        # postmortems written this run (empty on a clean run);
                        # each names the trigger + node in its own JSON
                        "flight_dumps": [os.path.basename(p)
                                         for p in flight.dump_paths()],
                    },
                    devprof=devprof.results() or None,
                )
            # written by _pass once the root span has ended, with the phases
            # on it (the queue's last drain comes first: close, below)
            _PASS_MANIFEST = (manifest, manifest_path, sched.origin_monotonic,
                              obs_store, obs_base)
        except BaseException as e:
            run_err = e
            raise
        finally:
            with tracer.phase("close"):
                if trace_rotator is not None:
                    # final segment flush goes through the writer: rotate
                    # BEFORE close() so the submit still has a live queue
                    try:
                        trace_rotator.close()
                    except Exception:
                        logger.exception("trace rotator close failed")
                try:
                    writer.close()  # drain: surface any queued-write failure
                except Exception as close_err:
                    if run_err is None:
                        raise
                    # an aborted run's close() failure must NOT mask the original
                    # node exception (the queued-write error is usually a
                    # downstream symptom of it): log it AND chain it onto the
                    # propagating exception's __context__ so the traceback shows
                    # both, with the node error on top
                    logger.exception("async artifact writes failed during aborted run")
                    if run_err.__context__ is None:
                        # raising inside this finally implicitly set
                        # close_err.__context__ = run_err; clear that
                        # back-reference first or the chain becomes a cycle
                        if close_err.__context__ is run_err:
                            close_err.__context__ = None
                        run_err.__context__ = close_err
                if cache_store is not None:
                    cache_capture.uninstall_open_hook()
                    max_bytes = os.environ.get("ANOVOS_TPU_CACHE_MAX_BYTES", "")
                    if max_bytes:
                        from anovos_tpu.cache.store import parse_bytes

                        try:  # capacity bound: same LRU sweep as tools/cache_gc.py
                            stats = cache_store.gc(parse_bytes(max_bytes))
                            if stats["evicted_nodes"]:
                                logger.info(
                                    "cache gc: %d node entr(ies) evicted (%d -> %d bytes)",
                                    len(stats["evicted_nodes"]),
                                    stats["before_bytes"], stats["after_bytes"])
                        except Exception:
                            logger.exception("cache gc failed; store left as-is")
                if trace_dest and trace_rotator is None:
                    # export even on failure: the trace of an aborted run is
                    # exactly what the post-mortem needs.  With rotation
                    # active the rotator's final flush above already drained
                    # the ring into its last numbered segment.
                    try:
                        out_path = write_chrome_trace(os.path.abspath(trace_dest))
                        logger.info(
                            "chrome trace written to %s — open it in Perfetto "
                            "(ui.perfetto.dev) or chrome://tracing", out_path)
                    except Exception:
                        logger.exception("chrome trace export to %s failed", trace_dest)
                elif trace_rotator is not None and trace_rotator.segments:
                    logger.info("chrome trace rotated into %d segment(s) next to %s",
                                len(trace_rotator.segments), trace_rotator.dest)
                telemetry.release(telemetry_handle)
        LAST_RUN_SUMMARY = summary
        logger.info(DagScheduler.format_summary(summary))
        with tracer.phase("write_main"):
            df = pipe.current_df()
            if df is None and (write_main or all_configs.get("write_feast_features")):
                raise ValueError(
                    "write_main/write_feast_features require input_dataset — a "
                    "streaming-only run has no materialized table to write")

            # feast export adds its timestamp columns BEFORE the single final
            # write (reference :854-866); config validated up front (ref :173-182)
            write_feast = all_configs.get("write_feast_features", None)
            if write_feast is not None:
                if write_main is None:
                    raise ValueError("write_feast_features requires write_main")
                from anovos_tpu.feature_store import feast_exporter

                repartition_count = (write_main.get("file_configs") or {}).get("repartition", -1)
                feast_exporter.check_feast_configuration(write_feast, repartition_count)
                df = feast_exporter.add_timestamp_columns(df, write_feast["file_source"])
            if write_main:
                save(df, write_main, "final_dataset", reread=False)
            if write_feast is not None:
                import glob as _glob

                from anovos_tpu.feature_store import feast_exporter

                path = os.path.join(write_main["file_path"], "final_dataset", "part*")
                files = _glob.glob(path)
                feast_exporter.generate_feature_description(df.dtypes(), write_feast, files[0] if files else "")
        with tracer.phase("release"):
            # the pass is over: its nodes' closures and the registrar hold
            # each other, so without this the last table would keep its
            # device memory until the cyclic collector next runs, some
            # passes later
            pipe._versions.clear()
            df = None
    logger.info(f"execution time w/o report (in sec) = {round(time.monotonic() - start_main, 4)}")


def run(
    config_path: str,
    run_type: str = "local",
    auth_key_val: Optional[dict] = None,
    resume: bool = False,
) -> None:
    """Entry (reference :873-888): load YAML → main.

    Tracing: the reference logs per-block wall times only (SURVEY.md §5);
    here ``ANOVOS_PROFILE=<dir>`` additionally wraps the run in a JAX
    profiler trace (xprof-compatible) for kernel-level timing, in which
    every phase and every scheduler node of the pass is an annotation on
    the host's plane, on the clock of the device's operations.

    ``resume=True`` (the CLI's ``--resume``) re-runs a killed config
    against the same output directory: nodes whose results the journal /
    cache store committed before the crash restore instead of executing.
    Requires ``ANOVOS_TPU_CACHE`` (the entrypoints default it).
    """
    from anovos_tpu.shared.artifact_store import for_run_type

    with _pass():
        with get_tracer().phase("config"):
            store = for_run_type(run_type, _auth_key(auth_key_val))
            if run_type == "ak8s" and not auth_key_val:
                raise ValueError("Invalid auth key for run_type")
            # remote configs (e.g. s3:// for emr) are pulled before reading
            # (reference workflow.py:877 "aws s3 cp <config> config.yaml")
            config_file = store.pull(config_path, "config.yaml")
            with open(config_file, "r") as f:
                all_configs = yaml.load(f, yaml.SafeLoader)
        with _profiler_session(os.environ.get("ANOVOS_PROFILE", "")):
            main(all_configs, run_type, auth_key_val, resume=resume)
