"""Dependency-aware DAG executor for the workflow runner.

The reference pipeline inherits overlap for free from Spark's scheduler;
this framework's runner used to walk the YAML blocks one at a time on a
single host thread, so the pipeline ran as slow as the SUM of its blocks
instead of its critical path.  Here each config block registers as a node
declaring the resources it ``reads`` and ``writes`` (the current ``df``
version, stats CSVs, report subtrees), and nodes whose inputs are ready run
concurrently on a bounded worker pool.  Heavy work lives in XLA/NumPy/
pyarrow, which release the GIL, so device compute from one block overlaps
host-side CSV/plotting work from another.

Design properties:

* **Edges are derived, not declared.**  ``add()`` wires read-after-write,
  write-after-write and write-after-read dependencies from the declared
  resource sets, always pointing at ALREADY-registered nodes — so the graph
  is acyclic by construction and registration order is a valid topological
  order.  Sequential mode simply executes that order, which is exactly the
  YAML walk the runner performed before.
* **Failure semantics match the sequential runner.**  A node registered
  with ``on_error="raise"`` aborts the run: no new nodes start, in-flight
  nodes finish, and the ORIGINAL exception is re-raised.  ``"continue"``
  nodes log and are treated as done.  NOTE: the workflow registers every
  node as ``"raise"`` and keeps the reference's best-effort try/except
  INSIDE the geo/ts node bodies (so both executors share one isolation
  path); ``"continue"`` is the generic policy for other graph authors.
* **Hang watchdog with escalation.**  ``node_timeout`` bounds any single
  node.  A node's FIRST expiry no longer aborts the run: the attempt is
  interrupted (cooperatively, via the per-attempt ``interrupt`` event
  that chaos hangs and library checkpoints can observe) and re-allowed
  under a raised bound (``policy.timeout_factor`` — spine nodes get more
  patience than read-only fan-out nodes).  Only when the ESCALATED bound
  also expires does the node's error policy apply: ``NodeTimeout`` naming
  the block (the legacy behavior), or degradation for retry+degrade
  policies — the stuck worker thread is abandoned (daemon) and a
  replacement spawned so the pool keeps its width.  Workers are daemon
  threads so a wedged node cannot block interpreter exit either.
* **Retry / failover / degradation** (``anovos_tpu.resilience``).
  ``on_error="retry:N[:degrade|:continue]"`` re-executes a failed node up
  to N times with exponential backoff + deterministic jitter; between
  attempts the capture recorder's partial artifacts are discarded (append
  -mode files excepted) and the WAL journal logs ``node_retry``.  Retry
  soundness rides the same GC006-verified effect contracts the cache
  keys ride: a node's writes are exactly its declared, capturable
  artifacts, so re-execution overwrites rather than corrupts.  A failure
  that looks backend-shaped (or an escalated timeout) triggers a bounded
  in-run health probe; a wedged accelerator flips the runtime to CPU
  ONCE (``resilience.failover``) and the in-flight frontier re-executes
  from the last WAL-committed state — a mid-run wedge costs seconds, not
  the run.  Re-execution of ANY kind (policy, timeout, failover) applies
  only to retry-mode nodes: ``raise``/``continue`` registrations opted
  out, and a failover still flips the backend for the rest of the run
  while their own error follows the declared policy.  Exhausted
  ``retry:N:degrade`` nodes mark themselves
  ``degraded`` (registry + manifest + report placeholder) and the run
  continues.  Every path is exercised by the seeded chaos harness
  (``ANOVOS_TPU_CHAOS`` → ``resilience.chaos``), whose injection sites
  the executor visits before each node body.
* **Observability.**  Per-node start/end/thread spans are recorded and
  ``run()`` returns a summary with the measured critical path (longest
  dependency chain by wall time) and the parallel speedup — surfaced in the
  run log and in the run manifest's ``scheduler`` section.  Every node additionally emits
  a tracer span (``anovos_tpu.obs``: worker lane, queue wait, deps waited
  on) for the Chrome-trace export, and books wall/queue-wait time into the
  process metrics registry (``node_wall_seconds``,
  ``node_queue_wait_seconds``) that feeds the run manifest.
* **Incremental recompute.**  A node registered with a
  :class:`~anovos_tpu.cache.NodeCachePolicy` gets a fingerprint — its
  policy's key material folded with the fingerprints of the nodes it reads
  through RAW edges (registration order is topological, so dep
  fingerprints always exist when ``add()`` runs).  With a
  :class:`~anovos_tpu.cache.CacheStore` attached, ``_execute`` consults
  the store first: on a hit the node's committed artifacts are restored
  (copy from the content-addressed store, a ``cache:restore`` span on the
  worker lane) and the node is marked done WITHOUT executing; on a miss
  the body runs inside an artifact-capture recorder and its created files
  are committed atomically afterwards.  Cache failures never fail the
  run — a broken restore falls back to executing, a broken commit logs
  and continues.  A node whose RAW dep has no fingerprint is uncacheable
  (its inputs are unidentifiable), as is any node without a policy.

* **Collective-aware lanes (multi-device meshes).**  Concurrency used to
  be single-device-only: two concurrently dispatched programs that both
  carry cross-device collectives can enqueue onto the per-device streams
  in different orders and deadlock at their AllReduce rendezvous, so
  ``workflow.main`` degraded to sequential whenever >1 device was
  present.  Now every registration declares a
  :class:`~anovos_tpu.parallel.placement.Placement` (``mesh`` /
  ``submesh:N`` / ``device`` / ``host`` — audited against the body's
  actual dispatches by graftcheck GC011) and the executor derives lane
  discipline from it: collective nodes claim the **rendezvous lane**
  through the runtime's :class:`~anovos_tpu.shared.runtime.
  DeviceLeaseRegistry` (at most one collective claim covering any chip,
  so the rendezvous order stays total — sub-mesh nodes with disjoint
  carves may overlap), while ``device``-placed nodes lease one chip
  each, run under a :func:`~anovos_tpu.shared.runtime.placement_scope`
  (their tables re-placed onto the leased chip, uncommitted dispatch
  pinned via ``jax.default_device``) and fan out freely — single-device
  programs carry no rendezvous, so any number may overlap each other
  and the collective in flight.  ``mesh`` nodes registered under one
  ``lane_group`` (the workflow's ``stats_generator`` readers of one table
  version) hold the lane as one claim and are in flight together; their
  registrant orders their device work.  ``host`` nodes never touch a device
  and need no lease.  On single-device runtimes (or without a runtime)
  the lane machinery is inert and behavior is exactly the PR 1
  scheduler.  Leases are released when a node finishes, degrades, or is
  abandoned — a hang escalation interrupts the collective attempt
  without wedging the rendezvous lane (the chaos ``hang-collective``
  scenario gates this).
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
import time
from typing import Callable, Dict, Iterable, List, Optional, Union

from anovos_tpu.parallel.placement import Placement, parse_placement
from anovos_tpu.resilience.policy import ErrorPolicy, parse_policy

logger = logging.getLogger("anovos_tpu.parallel.scheduler")

__all__ = ["DagScheduler", "Node", "NodeTimeout", "default_workers"]


class NodeTimeout(RuntimeError):
    """A node exceeded the scheduler's per-node timeout (names the block)."""


def default_workers() -> int:
    """Worker-pool width: env override, else sized to the host AND mesh.

    On a single-core host a wide pool only timeshares compute and inflates
    per-block walls; two workers still overlap device compute with host
    file I/O (both release the GIL) without distorting block timings.

    On a multi-device runtime the pool must cover the rendezvous lane plus
    one worker per leasable chip — device-placed fan-out nodes are chip-
    bound, not host-core-bound (XLA releases the GIL), so sizing the pool
    to host CPUs alone would leave leased chips idle behind the queue.
    """
    env = os.environ.get("ANOVOS_TPU_EXECUTOR_WORKERS", "")
    if env:
        return max(1, int(env))
    base = max(2, min(8, available_cpus()))
    try:
        from anovos_tpu.shared.runtime import peek_runtime

        rt = peek_runtime()  # never init a backend just to size a pool
        n_dev = rt.n_devices if rt is not None else 0
    except Exception:  # pragma: no cover - runtime import failure
        n_dev = 0
    if n_dev > 1:
        return max(base, min(n_dev + 1, 16))
    return base


def available_cpus() -> int:
    """CPUs this process may actually run on — cgroup/cpuset-aware where the
    platform supports it (os.cpu_count() reports the host's cores even in a
    container pinned to one)."""
    try:
        return len(os.sched_getaffinity(0)) or 1
    except AttributeError:  # pragma: no cover - non-Linux
        return os.cpu_count() or 1


class Node:
    __slots__ = (
        "name", "fn", "reads", "writes", "on_error", "deps", "dependents",
        "pending", "state", "start", "end", "ready", "thread", "error",
        "cache", "fingerprint", "cached",
        # lane state (collective-aware multi-device execution)
        "placement", "lane_group", "lease", "devices",
        # resilience state (anovos_tpu.resilience)
        "policy", "attempts", "attempt_start", "interrupt",
        "timeout_retried", "failover_retried", "failover_granted",
        "escalated", "degraded", "abandoned", "rec",
    )

    def __init__(self, name: str, fn: Callable[[], None], reads, writes,
                 on_error: Union[str, ErrorPolicy],
                 placement: Union[None, str, Placement] = None,
                 lane_group: Optional[str] = None):
        self.name = name
        self.fn = fn
        self.reads = tuple(reads)
        self.writes = tuple(writes)
        self.placement = parse_placement(placement)  # raises on unknown kind
        self.lane_group = lane_group  # shared mesh claim (DeviceLeaseRegistry)
        self.lease = None           # DeviceLease while claimed/running
        self.devices: List[str] = []  # leased device labels (telemetry)
        self.policy = parse_policy(on_error)   # raises on an unknown mode
        self.on_error = self.policy.describe()
        self.deps: List["Node"] = []
        self.dependents: List["Node"] = []
        self.pending = 0            # unfinished deps (concurrent mode)
        self.state = "pending"      # pending|running|done|failed|failed-continued|degraded|skipped
        self.start = self.end = 0.0
        self.ready = 0.0            # when the last dep finished (queue-wait origin)
        self.thread = ""
        self.error: Optional[BaseException] = None
        self.cache = None           # NodeCachePolicy (or None: always execute)
        self.fingerprint: Optional[str] = None
        self.cached = False         # True when this run restored instead of ran
        self.attempts = 0           # executions of the body this run
        self.attempt_start = 0.0    # monotonic start of the CURRENT attempt
        self.interrupt = threading.Event()  # per-attempt cooperative interrupt
        self.timeout_retried = False   # the one escalated-bound re-execution
        self.failover_retried = False  # the one post-failover re-execution
        self.failover_granted = False  # watchdog flipped while this node ran
        self.escalated = False      # watchdog raised this node's bound once
        self.degraded = False       # retries exhausted; section marked degraded
        self.abandoned = False      # watchdog gave up on a stuck attempt
        self.rec = None             # the CURRENT attempt's capture recorder

    @property
    def queue_wait(self) -> float:
        """Seconds spent ready-but-unstarted (worker-pool contention)."""
        if self.start and self.ready:
            return max(self.start - self.ready, 0.0)
        return 0.0


class DagScheduler:
    """Register nodes with resource reads/writes, then ``run()`` them."""

    def __init__(self, name: str = "dag", cache_store=None, journal=None):
        self.name = name
        self._nodes: List[Node] = []
        self._by_name: Dict[str, Node] = {}
        self._last_writer: Dict[str, Node] = {}
        self._readers_since_write: Dict[str, List[Node]] = {}
        self.cache_store = cache_store   # anovos_tpu.cache.CacheStore | None
        self.journal = journal           # anovos_tpu.cache.RunJournal | None
        self._cache_lock = threading.Lock()
        self._cache_stats = {"hits": 0, "misses": 0, "restore_s": 0.0}
        self._res_lock = threading.Lock()
        self._res_stats = {"retries": 0, "timeout_retries": 0,
                           "failover_retries": 0, "timeout_escalations": 0}
        # live views for the flight recorder's postmortem dumps: the nodes
        # currently executing and the ready queue (depth only).  Maintained
        # by both executors; read (racily, by design) at dump time.
        self._running: Dict[str, Node] = {}
        self._ready_view = None
        # chip-lease registry for lane-aware execution (multi-device
        # runtimes only; None keeps the lane machinery inert) + the
        # runtime generation it was built against — a mid-run failover
        # rebuilds the runtime, after which lease devices are resolved
        # by stable id into the new device set (see _lease_devices)
        self._lanes = None
        self._lanes_gen = -1
        # set by run(): the node spans' parent, and what the summary's
        # start_s/end_s count from (time.monotonic() of the first node)
        self._span_parent: dict = {}
        self.origin_monotonic: Optional[float] = None

    # -- registration ----------------------------------------------------
    def add(
        self,
        name: str,
        fn: Callable[[], None],
        reads: Iterable[str] = (),
        writes: Iterable[str] = (),
        on_error: Union[str, ErrorPolicy] = "raise",
        cache=None,
        placement: Union[None, str, Placement] = None,
        lane_group: Optional[str] = None,
    ) -> Node:
        """Register ``fn`` as node ``name``.

        A read of a resource nobody has written yet is treated as an
        external input (immediately available) — mirroring the sequential
        runner, where a consumer registered before its producer would also
        find only whatever pre-exists on disk.

        ``on_error`` is ``"raise"``, ``"continue"``,
        ``"retry:N[:degrade|:continue]"`` or an
        :class:`~anovos_tpu.resilience.ErrorPolicy` (see
        ``resilience.policy``).  Retry is only SOUND for nodes whose
        effect contract is exact — declared ``writes`` matching the
        body's real artifacts (graftcheck GC006 verifies this for the
        workflow's registrations); re-execution then overwrites the
        discarded partial outputs instead of corrupting shared state.

        ``cache`` (a :class:`~anovos_tpu.cache.NodeCachePolicy`) makes the
        node cacheable: its fingerprint is the policy's key material folded
        with the fingerprints of its RAW-edge producers.

        ``placement`` (:class:`~anovos_tpu.parallel.placement.Placement`
        or ``"mesh"``/``"submesh:N"``/``"device"``/``"host"``) declares
        where the body's device work runs; on multi-device runtimes the
        executor derives its lane discipline from it.  ``None`` defaults
        to ``host`` — a node that dispatches device programs on a multi-
        device mesh MUST declare itself (graftcheck GC011 audits the
        workflow's declarations).

        ``lane_group``: ``mesh``-placed nodes of one group hold the
        rendezvous lane as ONE claim and may be in flight together; the
        registrant answers for their device work being ordered among
        themselves (``_PipelineRun.fanout(share_lane=True)`` runs each
        body under the table version's lock).
        """
        if name in self._by_name:
            raise ValueError(f"duplicate node name {name!r}")
        node = Node(name, fn, reads, writes, on_error, placement=placement,
                    lane_group=lane_group)
        node.cache = cache
        deps: "dict[int, Node]" = {}  # id -> Node, insertion-ordered, deduped
        raw_deps: "dict[int, Node]" = {}  # the content-carrying subset
        for r in node.reads:
            w = self._last_writer.get(r)
            if w is not None:
                deps[id(w)] = w  # read-after-write
                raw_deps[id(w)] = w
        for w in node.writes:
            prev = self._last_writer.get(w)
            if prev is not None:
                deps[id(prev)] = prev  # write-after-write
            for rd in self._readers_since_write.get(w, ()):
                deps[id(rd)] = rd  # write-after-read
        deps.pop(id(node), None)
        node.deps = list(deps.values())
        for d in node.deps:
            d.dependents.append(node)
        # update resource maps AFTER wiring so a node never depends on itself
        for r in node.reads:
            self._readers_since_write.setdefault(r, []).append(node)
        for w in node.writes:
            self._last_writer[w] = node
            self._readers_since_write[w] = []
        raw_deps.pop(id(node), None)
        if cache is not None:
            # fingerprint = key material ⊕ RAW-producer fingerprints; a
            # producer without one makes this node's inputs unidentifiable
            dep_fps = [d.fingerprint for d in raw_deps.values()]
            if all(fp is not None for fp in dep_fps):
                from anovos_tpu.cache import digest

                node.fingerprint = digest(cache.key_material, *sorted(dep_fps))
        self._nodes.append(node)
        self._by_name[name] = node
        return node

    def __len__(self) -> int:
        return len(self._nodes)

    # -- execution -------------------------------------------------------
    def run(
        self,
        mode: Optional[str] = None,
        max_workers: Optional[int] = None,
        node_timeout: Optional[float] = None,
    ) -> dict:
        """Execute all nodes; returns the run summary (see ``_summary``).

        ``mode`` defaults to ``ANOVOS_TPU_EXECUTOR`` (``concurrent`` unless
        set to ``sequential``).  ``node_timeout`` defaults to
        ``ANOVOS_TPU_NODE_TIMEOUT`` seconds (0 disables the watchdog).
        """
        mode = mode or os.environ.get("ANOVOS_TPU_EXECUTOR", "concurrent")
        if mode not in ("concurrent", "sequential"):
            raise ValueError(f"unknown executor mode {mode!r} (concurrent|sequential)")
        if node_timeout is None:
            node_timeout = float(os.environ.get("ANOVOS_TPU_NODE_TIMEOUT", "900"))
        t0 = time.monotonic()
        # the span this run happens under (``dag`` in a workflow pass): the
        # node spans' parent, which a worker thread's own stack cannot give
        from anovos_tpu.obs import get_tracer

        caller = get_tracer().current()
        self._span_parent = {"parent": caller.name} if caller is not None else {}
        # devprof boundary drain probes are device syncs: fine when nodes
        # run one at a time, but with concurrent nodes sharing a device
        # queue they would serialize the async overlap — so concurrent
        # runs skip them unless ANOVOS_TPU_DEVPROF=full opts in
        self._devprof_drain = (
            mode == "sequential"
            or os.environ.get("ANOVOS_TPU_DEVPROF", "") == "full")
        # live telemetry plane (obs.telemetry): /statusz and the executor
        # depth gauges read this scheduler's racy live view for the run's
        # duration.  Registration is one dict insert — free with the
        # telemetry server off, and never touches the scheduler cv on.
        from anovos_tpu.obs import telemetry

        telemetry.register_provider("scheduler", statusz=self.live_state,
                                    metrics=self._telemetry_gauges)
        try:
            if mode == "sequential":
                workers = 1
                self._run_sequential()
            else:
                workers = min(max_workers or default_workers(),
                              max(len(self._nodes), 1))
                self._run_concurrent(workers, node_timeout)
        finally:
            telemetry.unregister_provider("scheduler")
            # drop the depth gauges with the provider: a finished run's
            # last scraped values must not expose as live forever
            from anovos_tpu.obs.metrics import get_metrics

            for fam in ("scheduler_inflight_nodes",
                        "scheduler_ready_queue_depth"):
                inst = get_metrics().peek(fam)
                if inst is not None:
                    inst.remove()
        return self._summary(time.monotonic() - t0, mode, workers)

    # -- lanes (collective-aware multi-device execution) -------------------
    def _lane_registry(self):
        """The runtime's chip-lease registry, or None when the lane
        machinery is inert (no runtime yet, or a single-device one).
        Never initializes a backend."""
        try:
            from anovos_tpu.shared.runtime import peek_runtime, runtime_generation
        except ImportError:  # pragma: no cover - no jax at all
            return None
        rt = peek_runtime()
        if rt is None or rt.n_devices <= 1:
            return None
        self._lanes = rt.lease_registry()
        self._lanes_gen = runtime_generation()
        return self._lanes

    def _lease_devices(self, lease) -> tuple:
        """The lease's devices, re-resolved by stable device id when a
        mid-run failover rebuilt the runtime underneath the registry (the
        lease stays valid as a lane token; the actual chips must come
        from the live device set).  The remap dedupes — a flip onto a
        narrower device set shrinks a multi-chip carve rather than build
        a mesh with repeated devices."""
        from anovos_tpu.shared.runtime import peek_runtime, runtime_generation

        if runtime_generation() == self._lanes_gen or not lease.devices:
            return lease.devices
        rt = peek_runtime()
        if rt is None:
            return lease.devices
        devs = list(rt.mesh.devices.flat)
        return tuple(dict.fromkeys(devs[d.id % len(devs)]
                                   for d in lease.devices))

    def _node_scope(self, node: Node):
        """The execution context a node's lease implies: device/submesh
        leases enter a placement scope over a runtime derived from the
        leased chips (tables built inside land there) and pin uncommitted
        single-device dispatch via ``jax.default_device``; mesh/host
        leases (and unlaned runs) need no scope."""
        lease = node.lease
        if lease is None or lease.kind in ("host", "mesh") or not lease.devices:
            return contextlib.nullcontext()
        import jax

        from anovos_tpu.shared.runtime import derive_runtime, placement_scope

        devices = self._lease_devices(lease)
        stack = contextlib.ExitStack()
        stack.enter_context(placement_scope(derive_runtime(devices)))
        if lease.kind == "device":
            stack.enter_context(jax.default_device(devices[0]))
        return stack

    def _execute(self, node: Node) -> None:
        from anovos_tpu.obs import devprof, get_metrics, get_tracer

        node.state = "running"
        node.thread = threading.current_thread().name
        node.devices = node.lease.device_labels() if node.lease else []
        node.start = time.monotonic()
        try:
            with get_tracer().span(
                node.name, cat="node",
                deps=[d.name for d in node.deps],
                queue_wait_s=round(node.queue_wait, 4),
                lane=node.placement.describe(),
                scheduler=self.name,
                **self._span_parent,
            ), devprof.node_bracket(node.name,
                                    drain=getattr(self, "_devprof_drain", True),
                                    lane=node.placement.describe(),
                                    devices=node.devices):
                if not self._try_restore(node):
                    self._run_attempts(node)
            if not node.abandoned:
                node.state = "degraded" if node.degraded else "done"
        except BaseException as e:
            node.error = e
            if node.policy.mode == "continue" or (
                node.policy.mode == "retry"
                and node.policy.on_exhausted == "continue"
            ):
                node.state = "failed-continued"
                logger.exception("node %r failed; continuing (on_error=%s)",
                                 node.name, node.on_error)
            else:
                node.state = "failed"
                # the run is about to abort: capture the postmortem NOW,
                # while the in-flight state still exists
                self._flight_dump("fatal_error", node,
                                  extra={"error": repr(e)[:300]})
                raise
        finally:
            node.end = time.monotonic()
            reg = get_metrics()
            reg.histogram("node_wall_seconds",
                          "scheduler node execution wall time"
                          ).observe(node.end - node.start, node=node.name)
            reg.histogram("node_queue_wait_seconds",
                          "ready-to-start wait behind the worker pool"
                          ).observe(node.queue_wait, node=node.name)

    # -- resilience --------------------------------------------------------
    def _run_attempts(self, node: Node) -> None:
        """Execute the node body under its error policy: chaos injection
        site, bounded retries with backoff, the one escalated-timeout
        re-execution, the one post-failover re-execution, and terminal
        degradation — in that precedence order."""
        from anovos_tpu.resilience import chaos
        from anovos_tpu.resilience import policy as rpolicy

        pol = node.policy
        # re-execution of ANY kind (policy retry, interrupted-timeout retry,
        # post-failover retry) is only sound for retry-mode nodes: a node
        # registered "raise"/"continue" opted out — e.g. the stability node,
        # whose cross-run metric-file appends a re-execution could double-book
        retryable = pol.mode == "retry"
        retries_left = pol.retries if retryable else 0
        while True:
            node.attempts += 1
            node.attempt_start = time.monotonic()
            if node.interrupt.is_set():
                node.interrupt = threading.Event()  # fresh event per attempt
            try:
                # the placement scope is entered PER ATTEMPT, not per node:
                # a post-failover retry must re-derive its devices from the
                # rebuilt runtime (a scope held across the flip would pin
                # the retry to the dead backend's devices)
                with self._node_scope(node):
                    chaos.chaos_point(f"node:{node.name}",
                                      interrupt=node.interrupt)
                    self._run_body(node)
                return
            except KeyboardInterrupt:
                raise
            except BaseException as e:
                # 1) watchdog-interrupted attempt: one re-execution at the
                #    escalated bound before the error policy applies at all
                if (retryable and node.interrupt.is_set()
                        and not node.timeout_retried):
                    node.timeout_retried = True
                    self._note_retry(node, e, kind="timeout_retry")
                    self._discard_partial(node)
                    continue
                # 2) backend failover: when the failure is a wedge (chaos
                #    flag, backend-shaped error, failed health probe, or the
                #    watchdog flipped while this node ran — failover_granted)
                #    the flip earns ONE re-execution outside the budget —
                #    the node was never given a healthy backend to run on
                pre_flip = self._backend_state()
                flipped = self._maybe_failover(node, e)
                if flipped:
                    # the wedge evidence (which node, which op, what the
                    # device looked like) dies with the flip — the dump
                    # runs post-flip, so the pre-flip backend/HBM/wedge
                    # snapshot rides along explicitly
                    self._flight_dump("backend_failover", node,
                                      extra={"error": repr(e)[:300],
                                             "pre_flip": pre_flip})
                flipped = flipped or node.failover_granted
                node.failover_granted = False
                if retryable and flipped and not node.failover_retried:
                    node.failover_retried = True
                    self._note_retry(node, e, kind="failover_retry")
                    self._discard_partial(node)
                    continue
                # 3) policy retries with exponential backoff + jitter
                if retries_left > 0:
                    retries_left -= 1
                    self._note_retry(node, e, kind="retry")
                    self._discard_partial(node)
                    time.sleep(rpolicy.backoff_delay(node.name, node.attempts, pol))
                    continue
                # 4) exhausted: degrade (the run continues, the section is
                #    marked) or propagate to _execute's raise/continue
                if pol.mode == "retry" and pol.on_exhausted == "degrade":
                    node.degraded = True
                    node.error = e
                    rpolicy.record_degraded(node.name, f"{type(e).__name__}: {e}")
                    if self.journal is not None:
                        self.journal.append("node_degraded", node=node.name,
                                            attempts=node.attempts,
                                            error=repr(e)[:300])
                    logger.warning(
                        "node %r exhausted %d attempt(s) (%r); marking its "
                        "section DEGRADED and continuing — the report renders "
                        "a placeholder", node.name, node.attempts, e)
                    return
                raise

    def _note_retry(self, node: Node, exc: BaseException, kind: str) -> None:
        from anovos_tpu.obs import get_metrics

        with self._res_lock:
            self._res_stats["retries"] += 1
            if kind == "timeout_retry":
                self._res_stats["timeout_retries"] += 1
            elif kind == "failover_retry":
                self._res_stats["failover_retries"] += 1
        get_metrics().counter(
            "node_retries_total", "scheduler node re-executions after failure",
        ).inc(node=node.name, kind=kind)
        if self.journal is not None:
            self.journal.append("node_retry", node=node.name, kind=kind,
                                attempt=node.attempts, error=repr(exc)[:300])
        else:
            # journal-less runs still feed the flight-recorder ring, in the
            # SAME shape the journal path produces (journal.append records
            # as ev="journal", event=<name>) so postmortem consumers match
            # one schema regardless of whether a journal was armed
            from anovos_tpu.obs import flight

            flight.record("journal", event="node_retry", node=node.name,
                          kind=kind, attempt=node.attempts,
                          error=repr(exc)[:300])
        logger.warning("node %r attempt %d failed (%r); re-executing (%s)",
                       node.name, node.attempts, exc, kind)

    def _discard_partial(self, node: Node) -> None:
        """Between attempts, drop the failed attempt's partial artifacts:
        wait out its in-flight async writes (so a stale queued write can
        never land AFTER the retry's fresh one), then unlink the files the
        capture recorder booked — except append-mode files, whose
        pre-existing content must survive.  Best-effort: a retry that
        re-overwrites is already safe for exact-contract nodes."""
        rec, node.rec = node.rec, None
        if rec is None:
            return
        try:
            if node.cache is not None and node.cache.flush is not None and rec.keys:
                node.cache.flush(sorted(rec.keys))
        except Exception:
            logger.debug("retry of node %r: async flush of partial writes "
                         "failed (likely the original error)", node.name,
                         exc_info=True)
        for p in sorted(rec.discardable_paths()):
            try:
                if os.path.isfile(p):
                    os.remove(p)
            except OSError:
                pass

    def _maybe_failover(self, node: Node, exc: BaseException) -> bool:
        """True when THIS failure triggered the run's backend failover."""
        try:
            from anovos_tpu.resilience import failover

            return failover.maybe_failover(exc, journal=self.journal)
        except Exception:
            logger.exception("backend failover check for node %r failed", node.name)
            return False

    # -- flight recorder ---------------------------------------------------
    def _backend_state(self) -> dict:
        """Backend name + per-device HBM + simulated-wedge flag, sampled
        BEFORE a potential failover flips the runtime — the postmortem
        must show the wedged accelerator, not the CPU it flipped to.
        Cheap, and only called on node failures / escalated timeouts."""
        try:
            import sys

            from anovos_tpu.obs.metrics import memory_by_device
            from anovos_tpu.resilience import chaos

            jax = sys.modules.get("jax")
            backend = None
            if jax is not None:
                try:
                    backend = jax.default_backend()
                except Exception:
                    backend = None
            return {
                "backend": backend,
                "hbm": {dev: stats.get("bytes_in_use")
                        for dev, stats in memory_by_device().items()},
                "wedged": chaos.backend_wedged(),
            }
        except Exception:
            return {}

    def live_state(self) -> dict:
        """The racy live view of the executor — in-flight nodes (state,
        attempts, elapsed wall, lane, leased devices), ready-queue depth
        and rendezvous holders.  ONE assembly shared by the crash-time
        flight dump and the live ``/statusz`` telemetry provider; it
        reads the running/ready views without the scheduler cv by design
        (a snapshot races the pool, and must never stall it)."""
        now = time.monotonic()
        inflight = []
        for n in list(self._running.values()):
            lease = n.lease  # racy read by design
            inflight.append({
                "node": n.name,
                "state": n.state,
                "attempts": n.attempts,
                "escalated": n.escalated,
                "elapsed_s": round(now - n.attempt_start, 3)
                if n.attempt_start else None,
                "thread": n.thread,
                # which lane this node occupies and which chips it
                # holds — a rendezvous deadlock postmortem must show
                # WHICH collective was in flight on which devices
                "lane": (lease.kind if lease is not None
                         else n.placement.describe()),
                "devices": (lease.device_labels() if lease is not None
                            else list(n.devices)),
                "deps": [d.name for d in n.deps],
            })
        try:
            queue_depth = len(self._ready_view) if self._ready_view is not None else 0
        except Exception:
            queue_depth = None
        lanes = self._lanes
        return {
            "inflight": inflight,
            "queue_depth": queue_depth,
            "rendezvous_holders": (lanes.collective_holders()
                                   if lanes is not None else []),
        }

    def _telemetry_gauges(self, reg) -> None:
        """Scrape-time executor depth gauges (the ``/metrics`` live
        families): how stuffed is the pool, how deep is the ready queue."""
        state = self.live_state()
        reg.gauge("scheduler_inflight_nodes",
                  "nodes currently executing in the DAG scheduler"
                  ).set(float(len(state["inflight"])))
        reg.gauge("scheduler_ready_queue_depth",
                  "nodes ready to run but not yet claimed by a worker"
                  ).set(float(state["queue_depth"] or 0))

    def _flight_dump(self, trigger: str, node: Optional[Node] = None,
                     extra: Optional[dict] = None) -> None:
        """Postmortem hook (obs.flight): no-op unless workflow.main armed
        the recorder for this run.  Reads the live running/ready views
        racily — a dump races the pool by construction."""
        try:
            from anovos_tpu.obs import flight

            if not flight.enabled():
                return
            state = self.live_state()
            flight.dump(trigger, node=node.name if node is not None else "",
                        inflight=state["inflight"],
                        queue_depth=state["queue_depth"],
                        rendezvous_holders=state["rendezvous_holders"],
                        extra=extra)
        except Exception:
            logger.exception("flight-recorder dump (%s) failed", trigger)

    # -- cache ------------------------------------------------------------
    def _try_restore(self, node: Node) -> bool:
        """Cache hit: restore the node's committed artifacts and report
        True (the body is skipped).  Any restore failure logs and reports
        False — executing is always a safe fallback."""
        if self.cache_store is None or node.fingerprint is None:
            return False
        manifest = self.cache_store.lookup(node.fingerprint)
        if manifest is None:
            return False
        from anovos_tpu.obs import get_metrics, get_tracer

        t0 = time.monotonic()
        try:
            with get_tracer().span(f"cache:restore:{node.name}", cat="cache",
                                   fingerprint=node.fingerprint[:12],
                                   files=len(manifest.get("files", ()))):
                n_files = self.cache_store.restore(manifest)
                if node.cache.on_hit is not None:
                    pdir = (self.cache_store.payload_dir(node.fingerprint)
                            if manifest.get("payload") else None)
                    node.cache.on_hit(pdir)
        except Exception:
            logger.exception("cache restore for node %r failed; executing", node.name)
            return False
        restore_s = time.monotonic() - t0
        node.cached = True
        reg = get_metrics()
        reg.counter("cache_hits_total", "scheduler nodes restored from cache"
                    ).inc(node=node.name)
        reg.histogram("cache_restore_seconds", "one node's artifact restore wall"
                      ).observe(restore_s, node=node.name)
        with self._cache_lock:
            self._cache_stats["hits"] += 1
            self._cache_stats["restore_s"] += restore_s
        if self.journal is not None:
            self.journal.append("node_restored", node=node.name,
                                fp=node.fingerprint, files=n_files)
        return True

    def _run_body(self, node: Node) -> None:
        """Execute the body; on a cacheable miss, capture created artifacts
        and commit them (commit failure logs — the run's own outputs are
        already on disk and must not be sacrificed to a cache error)."""
        if self.cache_store is None or node.fingerprint is None:
            node.fn()
            return
        from anovos_tpu.cache import capture
        from anovos_tpu.obs import get_metrics

        get_metrics().counter("cache_misses_total",
                              "scheduler nodes executed (no cache entry)"
                              ).inc(node=node.name)
        with self._cache_lock:
            self._cache_stats["misses"] += 1
        if self.journal is not None:
            self.journal.append("node_begin", node=node.name, fp=node.fingerprint)
        rec = capture.Recorder()
        node.rec = rec  # the retry path discards this attempt's partials
        try:
            with capture.recording(rec):
                node.fn()
        except BaseException:
            if self.journal is not None:
                self.journal.append("node_failed", node=node.name, fp=node.fingerprint)
            raise
        if node.abandoned:
            # a zombie attempt the watchdog already gave up on (the node is
            # booked DEGRADED, dependents ran, the manifest/report say so):
            # its late result must NOT become a committed cache entry a
            # future run would restore as if the node had succeeded.  Its
            # direct file writes cannot be unwound at thread level — that
            # is the documented cost of abandoning — but the durable record
            # stays consistent.
            logger.warning(
                "abandoned node %r finished late; its result is NOT "
                "committed (section already degraded)", node.name)
            return
        try:
            if node.cache.flush is not None and rec.keys:
                # the node's queued async writes must land before commit
                node.cache.flush(sorted(rec.keys))
            manifest = self.cache_store.commit(
                node.fingerprint, node.name, rec.paths,
                payload_write=node.cache.payload_write,
            )
            if self.journal is not None:
                self.journal.append("node_commit", node=node.name,
                                    fp=node.fingerprint,
                                    files=len(manifest.get("files", ())))
        except Exception:
            logger.exception("cache commit for node %r failed; run continues uncached",
                             node.name)

    def _run_sequential(self) -> None:
        # leases are uncontended one-at-a-time, but still taken: placement
        # (which chip a device-placed node computes on) must be identical
        # between the executors or their artifacts could diverge
        lanes = self._lane_registry()
        for node in self._nodes:
            node.ready = time.monotonic()  # no pool: ready == start
            if lanes is not None:
                node.lease = lanes.try_lease(node.name, node.placement.kind,
                                             node.placement.n_devices,
                                             node.lane_group)
            self._running[node.name] = node
            try:
                self._execute(node)
            finally:
                self._running.pop(node.name, None)
                if lanes is not None:
                    lanes.release(node.lease)
                node.lease = None

    def _run_concurrent(self, max_workers: int, node_timeout: float) -> None:
        cv = threading.Condition()
        ready: List[Node] = []
        self._running.clear()
        running: Dict[str, Node] = self._running  # flight-dump live view
        self._ready_view = ready
        lanes = self._lane_registry()
        state = {"stop": False, "fatal": None, "done": 0, "spawned": 0}
        total = len(self._nodes)
        t_ready0 = time.monotonic()
        for n in self._nodes:
            n.pending = len(n.deps)
            if n.pending == 0:
                n.ready = t_ready0
                ready.append(n)

        def claim_next() -> Optional[Node]:
            """The first ready node whose lane is available (caller holds
            ``cv``).  A collective node blocked behind the rendezvous lane
            does not starve the queue — later single-device/host nodes are
            still claimable around it."""
            for i, n in enumerate(ready):
                if lanes is None:
                    del ready[i]
                    return n
                lease = lanes.try_lease(n.name, n.placement.kind,
                                        n.placement.n_devices, n.lane_group)
                if lease is not None:
                    n.lease = lease
                    del ready[i]
                    return n
            return None

        def release_lease(node: Node) -> None:
            """Caller holds ``cv`` (claim and release both run under it,
            so the lane bookkeeping has one lock order: cv -> registry)."""
            lease, node.lease = node.lease, None
            if lanes is not None and lease is not None:
                lanes.release(lease)

        def finish(node: Node) -> None:
            with cv:
                if node.abandoned:
                    # the watchdog already booked this node (degraded),
                    # released its lease and unblocked its dependents;
                    # this is the zombie attempt finally waking — its
                    # result is discarded (node.lease is already None)
                    cv.notify_all()
                    return
                release_lease(node)
                running.pop(node.name, None)
                state["done"] += 1
                if node.state == "failed" and state["fatal"] is None:
                    state["fatal"] = node.error
                    state["stop"] = True
                elif node.state in ("done", "failed-continued", "degraded"):
                    for dep in node.dependents:
                        dep.pending -= 1
                        if dep.pending == 0 and not state["stop"]:
                            dep.ready = time.monotonic()
                            ready.append(dep)
                cv.notify_all()

        def worker() -> None:
            while True:
                with cv:
                    node = None
                    while not state["stop"] and state["done"] < total:
                        node = claim_next()
                        if node is not None:
                            break
                        cv.wait(0.05)
                    if node is None:
                        return
                    node.state = "claimed"
                    # attempt_start is the watchdog's clock origin; set it
                    # BEFORE dispatch so a node is never observed at 0.0
                    node.attempt_start = time.monotonic()
                    running[node.name] = node
                try:
                    self._execute(node)
                except BaseException:
                    pass  # recorded on the node; surfaced via state["fatal"]
                finish(node)
                if node.abandoned:
                    # this thread is the zombie the watchdog replaced: a
                    # substitute worker already holds its pool slot, so
                    # rejoining would widen the pool by one per abandonment
                    return

        def spawn_worker() -> None:
            state["spawned"] += 1
            threading.Thread(
                target=worker, name=f"{self.name}-w{state['spawned'] - 1}",
                daemon=True,
            ).start()

        def abandon(node: Node, reason: str) -> None:
            """Watchdog verdict on a truly stuck retry+degrade node: book it
            degraded WITHOUT its (zombie) thread, release its lane lease
            (a stuck collective must not wedge the rendezvous lane — the
            zombie's possible late dispatches are the documented cost of
            abandoning, recorded in the postmortem), unblock dependents,
            and replace the lost worker.  Caller holds ``cv``."""
            from anovos_tpu.resilience import policy as rpolicy

            release_lease(node)
            node.abandoned = True
            node.degraded = True
            node.error = NodeTimeout(reason)
            node.state = "degraded"
            node.end = time.monotonic()
            rpolicy.record_degraded(node.name, reason)
            if self.journal is not None:
                self.journal.append("node_degraded", node=node.name,
                                    attempts=node.attempts, error=reason[:300])
            logger.warning("%s — abandoning the stuck attempt (thread leaked, "
                           "worker replaced) and DEGRADING the section", reason)
            # the postmortem dump happens at the call site AFTER cv is
            # released — file I/O under the scheduler lock stalls the pool
            running.pop(node.name, None)
            state["done"] += 1
            for dep in node.dependents:
                dep.pending -= 1
                if dep.pending == 0 and not state["stop"]:
                    dep.ready = time.monotonic()
                    ready.append(dep)
            spawn_worker()

        for _ in range(min(max_workers, max(total, 1))):
            spawn_worker()
        cv.acquire()
        try:
            while state["done"] < total:
                if state["stop"] and not running:
                    break
                cv.wait(0.1)
                if not (node_timeout and node_timeout > 0):
                    continue
                now = time.monotonic()
                expired: Optional[Node] = None
                # non-fatal postmortem dumps (escalation, abandonment) do
                # file I/O + fsync — collected here and written OUTSIDE cv
                # so a slow disk never stalls the whole worker pool
                pending_dumps: List[tuple] = []
                for node in list(running.values()):
                    factor = node.policy.timeout_factor if node.escalated else 1.0
                    if now - node.attempt_start <= node_timeout * factor:
                        continue
                    if not node.escalated:
                        # first expiry: escalate, don't abort — interrupt the
                        # attempt (cooperative: chaos hangs and library
                        # checkpoints observe the event and unwind into the
                        # timeout-retry path) and grant the raised bound
                        node.escalated = True
                        node.attempt_start = now
                        node.interrupt.set()
                        with self._res_lock:
                            self._res_stats["timeout_escalations"] += 1
                        from anovos_tpu.obs import get_metrics

                        get_metrics().counter(
                            "node_timeout_escalations_total",
                            "watchdog timeouts escalated instead of fatal",
                        ).inc(node=node.name)
                        if self.journal is not None:
                            self.journal.append("node_timeout_escalated",
                                                node=node.name,
                                                bound_s=round(node_timeout, 3),
                                                factor=node.policy.timeout_factor)
                        logger.warning(
                            "node %r exceeded its %.1fs bound; interrupting the "
                            "attempt and escalating once to %.1fs before the "
                            "error policy applies", node.name, node_timeout,
                            node_timeout * node.policy.timeout_factor)
                        # first sign of a hang: dump the postmortem NOW —
                        # if the escalated bound also blows, the evidence
                        # of what the node was doing is already on disk
                        pending_dumps.append(
                            ("timeout_escalation", node,
                             {"bound_s": round(node_timeout, 3),
                              "factor": node.policy.timeout_factor}))
                        continue
                    expired = node
                    break
                if pending_dumps:
                    cv.release()
                    try:
                        for trig, dnode, extra in pending_dumps:
                            self._flight_dump(trig, dnode, extra=extra)
                    finally:
                        cv.acquire()
                    continue  # re-scan: state may have moved while unlocked
                if expired is None:
                    continue
                # escalated bound ALSO blown: probe the backend OUTSIDE the
                # lock (bounded, but seconds) — a wedge flips to CPU and the
                # interrupt gets one more bound to unwind into re-execution
                cv.release()
                try:
                    pre_flip = self._backend_state()
                    flipped = self._watchdog_failover(expired)
                    if flipped:
                        self._flight_dump("backend_failover", expired,
                                          extra={"via": "watchdog",
                                                 "pre_flip": pre_flip})
                finally:
                    cv.acquire()
                if expired.name not in running:
                    continue  # the attempt finished while we probed
                if flipped and not expired.failover_retried:
                    # the grant must not consume the node's retry budget:
                    # _run_attempts sees failover_granted and books the
                    # re-execution as the one budget-free failover retry
                    expired.failover_granted = True
                    expired.attempt_start = time.monotonic()
                    expired.interrupt.set()
                    continue
                name = expired.name
                reason = (
                    f"scheduler node {name!r} still running after its escalated "
                    f"bound ({node_timeout:.0f}s x{expired.policy.timeout_factor:g}) "
                    f"— likely hung; (raise ANOVOS_TPU_NODE_TIMEOUT if the block "
                    f"is legitimately slow)"
                )
                if (expired.policy.mode == "retry"
                        and expired.policy.on_exhausted == "degrade"):
                    abandon(expired, reason)
                    cv.notify_all()
                    cv.release()  # the run survives: dump without stalling it
                    try:
                        self._flight_dump("node_abandoned", expired,
                                          extra={"reason": reason})
                    finally:
                        cv.acquire()
                    continue
                state["stop"] = True
                state["fatal"] = NodeTimeout(reason)
                cv.notify_all()
                # dump OUTSIDE cv even on the fatal path: a stalled disk
                # (the very pathology being recorded) must not turn the
                # abort into a scheduler hang — stop is already signalled
                cv.release()
                try:
                    self._flight_dump("fatal_timeout", expired,
                                      extra={"reason": reason})
                finally:
                    cv.acquire()
                break
        finally:
            cv.release()
        for n in self._nodes:
            if n.state in ("pending", "claimed"):
                n.state = "skipped"
        if state["fatal"] is not None:
            raise state["fatal"]
        # workers exit on their own once done == total (daemon threads)

    def _watchdog_failover(self, node: Node) -> bool:
        """Escalated-timeout health verdict: a node stuck past its raised
        bound is exactly the mid-run-wedge signature, so ALWAYS probe here
        (unlike the failure path, which probes only suspicious errors)."""
        try:
            from anovos_tpu.resilience import failover

            return failover.maybe_failover(node.error, journal=self.journal,
                                           force_probe=True)
        except Exception:
            logger.exception("watchdog failover probe for node %r failed", node.name)
            return False

    # -- observability ---------------------------------------------------
    def _summary(self, wall_s: float, mode: str, workers: int) -> dict:
        executed = [n for n in self._nodes if n.end > 0.0]
        origin = min((n.start for n in executed), default=0.0)
        # what the nodes' start_s/end_s count from, on time.monotonic():
        # the manifest's ``clock`` places it on the tracer's timeline
        self.origin_monotonic = origin if executed else None
        durs = {n.name: n.end - n.start for n in executed}
        serial = sum(durs.values())
        # longest dependency chain by measured duration; registration order
        # is a topological order so one forward pass suffices
        best: Dict[str, float] = {}
        prev: Dict[str, Optional[str]] = {}
        for n in self._nodes:
            d = durs.get(n.name, 0.0)
            pick, plen = None, 0.0
            for dep in n.deps:
                if best.get(dep.name, 0.0) > plen:
                    pick, plen = dep.name, best[dep.name]
            best[n.name] = d + plen
            prev[n.name] = pick
        chain: List[str] = []
        if best:
            cur: Optional[str] = max(best, key=lambda k: best[k])
            cp_len = best[cur]
            while cur is not None:
                chain.append(cur)
                cur = prev[cur]
            chain.reverse()
        else:
            cp_len = 0.0
        with self._cache_lock:
            cache_stats = dict(self._cache_stats)
        with self._res_lock:
            res_stats = dict(self._res_stats)
        from anovos_tpu.resilience import failover as _failover

        # max concurrently in-flight nodes, from the measured spans: the
        # multi-device acceptance metric (>1 proves the executor really
        # overlapped nodes; ``__graft_entry__.executor_pass`` gates on it)
        events = sorted(
            ev for n in executed for ev in ((n.start, 1), (n.end, -1)))
        in_flight = overlap = 0
        for _, delta in events:
            in_flight += delta
            overlap = max(overlap, in_flight)
        try:
            from anovos_tpu.shared.runtime import peek_runtime

            rt = peek_runtime()
            n_devices = rt.n_devices if rt is not None else 1
        except Exception:  # pragma: no cover - no runtime at all
            n_devices = 1

        return {
            "mode": mode,
            "workers": workers,  # the pool width this run actually used
            "n_devices": n_devices,
            "multidev_overlap": overlap,
            "wall_s": round(wall_s, 4),
            "serial_s": round(serial, 4),
            "critical_path_s": round(cp_len, 4),
            "parallel_speedup": round(serial / wall_s, 3) if wall_s > 0 else 0.0,
            "critical_path": chain,
            "cache": {
                "enabled": self.cache_store is not None,
                "hits": cache_stats["hits"],
                "misses": cache_stats["misses"],
                "restore_s": round(cache_stats["restore_s"], 4),
                "uncacheable": sum(1 for n in self._nodes if n.fingerprint is None),
            },
            "resilience": {
                **res_stats,
                "failovers": _failover.failover_count(),
                "degraded": sorted(n.name for n in self._nodes if n.degraded),
            },
            "nodes": {
                n.name: {
                    "start_s": round(n.start - origin, 4) if n.end else None,
                    "end_s": round(n.end - origin, 4) if n.end else None,
                    "dur_s": round(n.end - n.start, 4) if n.end else None,
                    "queue_wait_s": round(n.queue_wait, 4) if n.end else None,
                    "thread": n.thread,
                    "lane": n.placement.describe(),
                    "devices": list(n.devices),
                    "state": n.state,
                    "cached": n.cached,
                    "attempts": n.attempts,
                    "escalated": n.escalated,
                    "degraded": n.degraded,
                    "deps": [d.name for d in n.deps],
                }
                for n in self._nodes
            },
        }

    @staticmethod
    def format_summary(summary: dict) -> str:
        """One-paragraph critical-path report for the run log."""
        chain = summary.get("critical_path", [])
        nodes = summary.get("nodes", {})
        hops = " -> ".join(
            f"{name} ({nodes.get(name, {}).get('dur_s') or 0.0:.2f}s)" for name in chain
        )
        return (
            f"scheduler[{summary.get('mode')}]: wall={summary.get('wall_s'):.2f}s "
            f"serial={summary.get('serial_s'):.2f}s "
            f"critical_path={summary.get('critical_path_s'):.2f}s "
            f"parallel_speedup={summary.get('parallel_speedup'):.2f}x "
            f"longest chain: {hops}"
        )
