"""``anovos_tpu.obs`` — tracing, metrics, and run-manifest observability.

Three cooperating, stdlib-only pieces:

* **Tracing** (``obs.tracing``): a thread-safe :class:`Tracer` with
  nestable ``span()`` context managers and a Chrome-trace-format exporter
  (open the JSON in Perfetto / ``chrome://tracing``).  The DAG scheduler
  emits a span per node (worker lane, queue wait, deps), the hot ops emit
  compile-vs-execute spans via :func:`timed`, and the async artifact
  writer spans its writes and drain barrier.  One ``workflow.run`` is one
  ``Tracer.run_pass()``: its phase spans (``config``, ``ingest`` and its
  decode / encode / h2d parts, ``register``, ``dag``, ...) land in the
  manifest as ``phases`` and, under ``ANOVOS_PROFILE``, with the node
  spans in the profiler trace as ``TraceAnnotation`` events.
* **Metrics** (``obs.metrics``): a process-wide :class:`MetricsRegistry`
  of counters/gauges/histograms — node wall time, queue wait, rows
  ingested, bytes written, device-memory high-water mark, compile-cache
  hits — with Prometheus-style text exposition and a deterministic JSON
  snapshot.
* **Run manifest** (``obs.manifest``): ``workflow.main`` writes
  ``obs/run_manifest.json`` next to the run's artifacts (config hash,
  executor mode, critical path, per-node spans, metrics snapshot);
  the benchmark's harness and the HTML report read it instead of
  re-deriving timings.
* **Compile census** (``obs.compile_census``): ``jax.monitoring``
  listeners following every program's way to the device (trace, lowering,
  load from the persistent cache or build) with per-program attribution;
  the per-run delta lands in the manifest, each stage is a row of the
  pass's phase tree, and ``tools/compile_census.py`` renders / CI-gates it.
* **Device-time attribution** (``obs.devprof``): per-scheduler-node
  split of wall into device / dispatch / transfer / host via boundary
  drain probes, ``timed()`` dispatch brackets, and transfer brackets at
  the Table materialization choke points, plus per-device HBM deltas —
  the manifest ``devprof`` section.
* **Flight recorder** (``obs.flight``): a bounded ring of lifecycle
  events dumped synchronously to ``obs/flightrec_<node>.json`` on
  timeout escalation, abandonment, backend failover, or fatal error —
  the postmortem a merely-survived wedge used to throw away.
* **Perf doctor** (``obs.diffing``): the structural run-diff engine —
  two manifests in, one ranked diagnosis
  out: per-node phase movement, compile-census program-set diff, cache
  hit-set diff with the moved fingerprint input named, env-knob diff,
  queue-wait separated from body movement.  ``tools/perf_doctor.py`` is
  the CLI.

Recording is always on at negligible cost; trace-file export is gated by
``ANOVOS_TPU_TRACE=<path|1>``, attribution by ``ANOVOS_TPU_DEVPROF``,
the flight recorder by ``ANOVOS_TPU_FLIGHTREC``.
"""

from anovos_tpu.obs import compile_census, devprof, diffing, flight, telemetry
from anovos_tpu.obs.manifest import (
    MANIFEST_VERSION,
    STABLE_TOP_FIELDS,
    build_manifest,
    config_hash,
    load_manifest,
    process_section,
    stable_view,
    write_manifest,
)
from anovos_tpu.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_metrics,
    memory_by_device,
    record_cache_stats,
    record_device_memory,
)
from anovos_tpu.obs.timed import timed
from anovos_tpu.obs.tracing import (
    Span,
    TraceRotator,
    Tracer,
    get_tracer,
    maybe_rotator,
    rotation_spec,
    trace_destination,
    write_chrome_trace,
)

__all__ = [
    "compile_census",
    "devprof",
    "diffing",
    "flight",
    "telemetry",
    "memory_by_device",
    "MANIFEST_VERSION",
    "STABLE_TOP_FIELDS",
    "build_manifest",
    "config_hash",
    "load_manifest",
    "process_section",
    "stable_view",
    "write_manifest",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "get_metrics",
    "record_cache_stats",
    "record_device_memory",
    "timed",
    "Span",
    "TraceRotator",
    "Tracer",
    "get_tracer",
    "maybe_rotator",
    "rotation_spec",
    "trace_destination",
    "write_chrome_trace",
]
