"""Run manifest: the machine-readable record of one ``workflow.main`` run.

``obs/run_manifest.json`` lands next to the run's other artifacts and is
the single source every timing consumer reads — the benchmark's harness
(``benchmark/harness/manifest.py``) and the tests take their block, node
and phase fields from it instead of re-deriving them from module globals,
the HTML report renders its node-timing table from it, and
``tools/perf_doctor.py`` diffs two of them (``stable_view`` strips the
timestamp-valued fields first).

Determinism contract: ``write_manifest`` serializes with sorted keys and
fixed separators, and every non-timing field (config hash, node names,
dependency lists, metric names, data-volume counters) is a pure function
of the config + input data — two sequential-mode runs of the same config
produce byte-identical manifests modulo the fields ``stable_view`` drops.
"""

from __future__ import annotations

import hashlib
import json
import os
import time
from typing import Callable, Optional

# 2: ``phases`` and ``clock``
MANIFEST_VERSION = 2

__all__ = [
    "MANIFEST_VERSION",
    "STABLE_TOP_FIELDS",
    "config_hash",
    "build_manifest",
    "write_manifest",
    "load_manifest",
    "stable_view",
    "process_section",
]


def _env_section(all_configs: dict) -> Optional[dict]:
    """The fingerprint inputs the perf doctor diffs: code version, the
    audited env knobs (values, not just the digest — a knob DIFF must name
    the knob), and the dataset/env fingerprints.  Telemetry for run
    comparison, stripped by ``stable_view`` (knob values embed chaos specs
    and spill-dir temp paths; the dataset signature embeds mtimes)."""
    try:
        from anovos_tpu.cache.fingerprint import (
            KNOWN_ENV_KNOBS,
            dataset_fingerprint,
            env_fingerprint,
        )
        from anovos_tpu.version import __version__

        return {
            "code_version": __version__,
            "knobs": {k: os.environ[k] for k in KNOWN_ENV_KNOBS
                      if os.environ.get(k) not in (None, "")},
            "env_fingerprint": env_fingerprint(),
            "dataset_fingerprint": dataset_fingerprint(
                all_configs.get("input_dataset")
                if isinstance(all_configs, dict) else None),
        }
    except Exception:  # a manifest must build even without the cache pkg
        return None


def config_hash(all_configs: dict) -> str:
    """sha256 of the canonical-JSON config — identifies WHAT ran."""
    blob = json.dumps(all_configs, sort_keys=True, default=str,
                      separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()


def build_manifest(
    all_configs: dict,
    summary: dict,
    metrics_snapshot: dict,
    run_type: str = "local",
    block_times: Optional[dict] = None,
    trace_path: Optional[str] = None,
    generated_unix: Optional[float] = None,
    compile_census: Optional[dict] = None,
    cache: Optional[dict] = None,
    resilience: Optional[dict] = None,
    devprof: Optional[dict] = None,
) -> dict:
    """Assemble the manifest dict from the scheduler summary + metrics.

    ``summary`` is ``DagScheduler.run()``'s return value (mode, wall,
    critical path, per-node spans) and is embedded verbatim under
    ``scheduler`` so downstream consumers need no second schema.
    """
    import time as _time

    backend = None
    try:  # backend name is informational; never import/init jax for it
        import sys

        jax = sys.modules.get("jax")
        if jax is not None:
            backend = jax.default_backend()
    except Exception:
        pass
    return {
        "manifest_version": MANIFEST_VERSION,
        "config_hash": config_hash(all_configs),
        "run_type": run_type,
        "executor": {
            "mode": summary.get("mode"),
            "workers": summary.get("workers"),
        },
        "critical_path": list(summary.get("critical_path", [])),
        "scheduler": summary,
        "block_seconds": {k: round(v, 4) for k, v in sorted((block_times or {}).items())},
        "metrics": metrics_snapshot,
        # per-run XLA compile census (obs.compile_census delta): compile
        # count, distinct program signatures, distinct kernels, the cache's
        # hits against real builds, the seconds of each stage, and the
        # top programs — the record the benchmark's fresh_programs /
        # window_compiles / fresh_built_programs and the
        # tools/compile_census.py gate read
        "compile_census": compile_census,
        # incremental-recompute record (anovos_tpu.cache): store root,
        # per-run hits/misses/restore wall, resumed frontier — present only
        # when ANOVOS_TPU_CACHE was set for the run
        "cache": cache,
        # recovery record (anovos_tpu.resilience): retries by kind, timeout
        # escalations, backend failovers, degraded sections (node -> failure
        # reason), and — under the chaos harness — what was injected where.
        # All zeros/empty on a healthy run; a transient fault leaves its
        # trace here instead of killing the run
        "resilience": resilience,
        # per-node device-time attribution (obs.devprof): node wall split
        # into device_time_s / dispatch_s / transfer_s / host_s plus
        # h2d/d2h byte counts and per-device HBM deltas — the section
        # the HTML report's devprof split and the perf doctor read
        "devprof": devprof,
        # fingerprint-input record (the perf doctor's knob/code/dataset
        # diff material): audited env-knob VALUES, code version, env and
        # dataset fingerprints — see anovos_tpu.obs.diffing
        "env": _env_section(all_configs),
        # the pass's phase tree (obs.tracing): ``[{name, parent, start_s,
        # end_s, thread, counts, usage}]``, seconds from the start of the root span
        # ``run``, and ``clock``: the pass's ``run_id`` and
        # ``scheduler_origin_s``, what ``scheduler.nodes[*].start_s/end_s``
        # count from, on the same origin (one addition places a node among
        # the phases).  The root ends after this dict is built, so
        # ``workflow`` fills both in just before it writes the file
        "phases": None,
        "clock": None,
        # which pass of its process this was and, on the first, what the
        # process did before it (``process_section``); filled in with the two
        # above
        "process": None,
        "trace_path": trace_path,
        "backend": backend,
        "generated_unix": round(
            _time.time() if generated_unix is None else generated_unix, 3),
    }


def _process_born() -> Optional[float]:
    """The process's start as a ``time.perf_counter()`` reading: field 22 of
    ``/proc/self/stat`` (clock ticks since boot) against ``CLOCK_BOOTTIME``.
    None where ``/proc`` cannot be read."""
    try:
        with open("/proc/self/stat") as f:
            # the command's name (field 2) may hold blanks: count from its closing bracket
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - ticks / os.sysconf("SC_CLK_TCK")
        return time.perf_counter() - age
    except (OSError, ValueError, IndexError, AttributeError):
        return None


def process_section(pass_index: int, import_started: float, import_done: float,
                    seconds_at: Callable[[float], Optional[float]]) -> dict:
    """The manifest's ``process``: ``pass_index`` (0 for the first pass the
    process opened) and, on that first pass, ``rows`` of the phases' shape
    (``name``, ``start_s``, ``end_s``; seconds from the root span's start, so
    negative) for what the process did before it: ``process/interpreter``
    (its start to the package's first statement: the interpreter and whatever
    the entry script imported first; left out where ``/proc`` cannot be
    read), ``process/import`` (from there to the end of ``workflow``'s
    imports) and ``process/caller`` (from there to the root span's start: the
    caller's own work).  ``import_started`` and ``import_done`` are
    ``time.perf_counter()`` readings, ``seconds_at`` brings one onto the
    pass's clock (``Tracer.seconds_at``)."""
    if pass_index:
        return {"pass_index": pass_index}
    born = _process_born()
    edges = [("process/interpreter", born, import_started),
             ("process/import", import_started, import_done),
             ("process/caller", import_done, None)]
    return {"pass_index": 0,
            "rows": [{"name": name, "start_s": seconds_at(start),
                      "end_s": 0.0 if end is None else seconds_at(end)}
                     for name, start, end in edges if start is not None]}


def write_manifest(manifest: dict, path: str) -> str:
    """Serialize deterministically (sorted keys, fixed separators, LF)."""
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(manifest, f, sort_keys=True, indent=1, separators=(",", ": "))
        f.write("\n")
    return path


def load_manifest(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


# fields whose values are wall-clock/duration-derived and therefore differ
# between two otherwise-identical runs ("cached" depends on STORE history:
# the same run misses cold and hits warm)
_VOLATILE_NODE_FIELDS = ("start_s", "end_s", "dur_s", "queue_wait_s", "thread",
                         "cached",
                         # which chips the lease registry handed out depends
                         # on worker timing; the node's LANE is identity,
                         # its leased device ids are not
                         "devices",
                         # recovery state depends on FAULT history (chaos
                         # plan, real flakes, watchdog timing), never on what
                         # the run computes
                         "attempts", "escalated", "degraded")
# Every key build_manifest writes must appear in exactly ONE of the two
# classification lists below — STABLE (survives stable_view: pure run
# identity, byte-equal across two sequential runs of one config) or
# VOLATILE (stripped: wall-clock / history / environment-derived).
# graftcheck GC017 audits build_manifest's keys against this partition, so
# a future obs field cannot silently break the byte-parity goldens.
STABLE_TOP_FIELDS = (
    "manifest_version",
    "config_hash",
    "run_type",
    "executor",
    "scheduler",
    "metrics",
)

_VOLATILE_TOP_FIELDS = (
    "generated_unix", "block_seconds", "trace_path", "backend",
    # the critical path is the longest chain BY MEASURED DURATION — two
    # runs can legitimately pick different chains when durations jitter
    "critical_path",
    # compile counts depend on PROCESS history (a warm in-process rerun
    # compiles nothing) — like the op_ metric families, not run identity
    "compile_census",
    # hit/miss split depends on cache-store history, not run identity
    "cache",
    # retries/failovers/degradations depend on fault history, not identity
    "resilience",
    # every devprof field is duration/byte-rate telemetry (and byte counts
    # depend on cache-store history: a restored node transfers nothing)
    "devprof",
    # fingerprint-input record for the perf doctor: knob VALUES embed
    # chaos directives and spill-dir temp paths, and the dataset signature
    # embeds mtimes — run-comparison telemetry, never run identity
    "env",
    # seconds, thread names and a per-pass id
    "phases",
    "clock",
    # the process's history, not the run's identity
    "process",
)


def stable_view(manifest: dict) -> dict:
    """The manifest minus timestamp/duration-valued fields.

    What survives is the run's *identity*: config hash, executor mode, the
    node set with states and dependency edges, metric names, and the
    data-volume counters (rows ingested, bytes written, artifact writes)
    that a deterministic pipeline reproduces exactly.  Two sequential-mode
    runs of one config must compare equal under this view.
    """
    out = {k: v for k, v in manifest.items() if k not in _VOLATILE_TOP_FIELDS}
    sched = dict(out.get("scheduler") or {})
    for k in ("wall_s", "serial_s", "critical_path_s", "parallel_speedup",
              "critical_path", "cache", "resilience",
              # measured-span overlap is wall-clock-derived, like speedup
              "multidev_overlap"):
        sched.pop(k, None)
    sched["nodes"] = {
        name: {k: v for k, v in node.items() if k not in _VOLATILE_NODE_FIELDS}
        for name, node in (sched.get("nodes") or {}).items()
    }
    out["scheduler"] = sched
    metrics = {}
    for name, m in (out.get("metrics") or {}).items():
        if (name.startswith("op_") or name.startswith("device_")
                or name.startswith("xla_") or name.startswith("cache_")
                # devprof_/transfer_ families are duration- and cache-
                # history-dependent, like the op_ families
                or name.startswith("devprof_") or name.startswith("transfer_")):
            # compile-cache state (op_compile vs op_execute/op_cache_hit)
            # depends on PROCESS history — a warm in-process rerun shifts
            # families even though the run is identical; device-memory
            # gauges depend on the backend; cache_ families depend on
            # STORE history.  None of them is run identity.
            continue
        # rows_ingested is the one data-volume counter that is pure run
        # identity: ingest always executes.  bytes_written/artifact_writes
        # stopped qualifying when incremental recompute landed — a node
        # restored from the cache writes through neither counter, so their
        # VALUES differ between a populate run and a warm re-run of the
        # identical config; only the series names remain identity.
        keep_values = name == "rows_ingested_total"
        metrics[name] = {
            "type": m.get("type"),
            "series": (m.get("series") if keep_values
                       else sorted((m.get("series") or {}).keys())),
        }
    out["metrics"] = metrics
    return out
