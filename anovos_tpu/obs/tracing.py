"""Thread-safe span tracing with Chrome-trace-format export.

The concurrent executor (PR 1) made "what did this run actually do" a
genuinely parallel question — a per-block INFO line cannot show which nodes
overlapped, which worker lane ran what, or how long a node sat queued
behind its dependencies.  This module records nestable spans from any
thread at negligible cost (one ``perf_counter_ns`` pair + a deque append
under a lock) and exports them as Chrome-trace JSON loadable in
``chrome://tracing`` or Perfetto (https://ui.perfetto.dev).

Always-on recording, gated export: spans accumulate in a bounded ring
buffer regardless of configuration; a trace FILE is only written when
``ANOVOS_TPU_TRACE`` is set (``1`` → ``<run output>/obs/trace.json``, any
other value → that path).  Everything here is stdlib-only.

Span events use the Trace Event Format "complete" phase (``ph: "X"``) with
microsecond ``ts``/``dur``; worker threads appear as separate lanes via
``tid`` plus ``thread_name`` metadata events, so per-lane span sums can be
checked against the scheduler's reported wall time.

One pass of ``workflow.run`` is one ``run_pass()``: a fresh timeline, a
``run_id`` that every span of the pass carries, and the root span ``run``.
``phase()`` spans opened under it (``config``, ``ingest``, ``dag``, ...) form
the pass's phase tree; the scheduler's node spans of the pass are rows of it
too (under ``dag``, each on its worker's thread), and so is a ``phase()``
opened inside a node (``place/d2d``) and a span reported once it was over
(``finished()``: a compile stage).  The tree has two sinks besides the
Chrome trace: the
run manifest's ``phases`` (``Tracer.phases()``), and, while a profiler
session is on (``annotate_with``), a ``jax.profiler.TraceAnnotation`` per
phase and per scheduler node, which puts the program's spans into the
``.xplane.pb`` on the clock of the device's operations.
"""

from __future__ import annotations

import json
import logging
import os
import resource
import threading
import time
import uuid
from collections import deque
from contextlib import contextmanager
from typing import Dict, Iterable, List, Optional, Tuple

logger = logging.getLogger("anovos_tpu.obs.tracing")

__all__ = [
    "Span",
    "TraceRotator",
    "Tracer",
    "annotate_with",
    "get_tracer",
    "maybe_rotator",
    "rotation_spec",
    "trace_destination",
    "write_chrome_trace",
]

# ring-buffer bound: ~200k spans ≈ tens of MB of export, far beyond a
# configs_full run (~hundreds of spans) but a hard cap for pathological
# loops (a long-lived service calling traced ops forever)
_DEFAULT_BUFFER = 200_000

# the root span of a pass, and the categories that are also written into a
# profiler session as TraceAnnotations
ROOT_PHASE = "run"
_ANNOTATED_CATS = ("phase", "node")
# what a row of the tree consumed between its two ends (``phases()`` keeps
# them apart from the counts of the work, under ``usage``): ``cpu_s`` on
# every row, the process's four on the root and its direct children
_USAGE = ("cpu_s", "proc_cpu_s", "minflt", "majflt", "nivcsw")

# ``jax.profiler.TraceAnnotation`` while a profiler session is on, else None:
# the one check ``span()`` makes.  ``workflow.run`` sets it around the
# session; this module never imports jax.
_ANNOTATION = None


def annotate_with(factory) -> None:
    """Open ``factory(name)`` around every phase and node span from now on
    (``None`` stops it).  ``workflow.run`` passes
    ``jax.profiler.TraceAnnotation`` for the length of its profiler session."""
    global _ANNOTATION
    _ANNOTATION = factory


class Span:
    """One finished span: wall-clock interval + attributes, immutable."""

    __slots__ = ("name", "cat", "start_ns", "dur_ns", "thread", "tid", "args",
                 "run_id")

    def __init__(self, name: str, cat: str, start_ns: int, dur_ns: int,
                 thread: str, tid: int, args: Optional[dict] = None,
                 run_id: Optional[str] = None):
        self.name = name
        self.cat = cat
        self.start_ns = start_ns
        self.dur_ns = dur_ns
        self.thread = thread
        self.tid = tid
        self.args = args or {}
        self.run_id = run_id

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return (f"Span({self.name!r}, cat={self.cat!r}, "
                f"dur={self.dur_ns / 1e6:.3f}ms, thread={self.thread!r})")


class OpenSpan:
    """A span that has not ended: what ``span()`` yields and
    ``Tracer.current()`` returns, so that counts can be put on the span at
    the boundary where the work happens."""

    __slots__ = ("name", "cat", "attrs", "tree", "start_ns")

    def __init__(self, name: str, cat: str, attrs: dict, tree: bool = False):
        self.name = name
        self.cat = cat
        self.attrs = attrs
        self.tree = tree  # a row of the pass's phase tree (Tracer.phases)
        self.start_ns = 0  # perf_counter_ns at its start, once ``span()`` has taken it

    def add(self, **counts) -> None:
        """Add each count to the span's attribute of that name."""
        for k, v in counts.items():
            self.attrs[k] = self.attrs.get(k, 0) + v


class Tracer:
    """Collects spans from any thread; nesting is tracked per thread.

    ``span()`` is a context manager; the parent span's name is recorded in
    the child's ``args["parent"]`` via a thread-local stack, so exported
    traces keep their logical nesting even across identically-timed events.
    """

    def __init__(self, buffer: Optional[int] = None):
        if buffer is None:
            raw = os.environ.get("ANOVOS_TPU_TRACE_BUFFER", "")
            try:
                buffer = int(raw) if raw else _DEFAULT_BUFFER
            except ValueError:
                # a module-level Tracer() is built at import: a malformed
                # env value must degrade to the default, not kill the
                # whole package import with an opaque traceback
                import warnings

                warnings.warn(
                    f"ANOVOS_TPU_TRACE_BUFFER={raw!r} is not an integer; "
                    f"using the default {_DEFAULT_BUFFER}")
                buffer = _DEFAULT_BUFFER
        self._spans: "deque[Span]" = deque(maxlen=max(buffer, 1))
        self._dropped = 0
        self._warned_wrap = False
        self._lock = threading.Lock()
        self._local = threading.local()
        # one epoch per tracer: chrome ts fields are offsets from it, so a
        # clear() between runs re-bases the timeline at ~0
        self._epoch_ns = time.perf_counter_ns()
        # the same instant on time.monotonic(), the scheduler's clock, so
        # that its stamps convert to this timeline (seconds_at)
        self._epoch_monotonic = time.monotonic()
        # the pass in progress: its id, its finished phase spans (kept apart
        # from the ring, which rotation drains and a long service wraps) and
        # its root once that has ended
        self.run_id: Optional[str] = None
        self._pass_open = False
        self._phases: List[Span] = []
        self._root: Optional[Span] = None

    # -- recording -------------------------------------------------------
    def _stack(self) -> List[OpenSpan]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def current(self) -> Optional[OpenSpan]:
        """The innermost span open on THIS thread, or None."""
        stack = self._stack()
        return stack[-1] if stack else None

    def enclosing(self, cat: str) -> Optional[OpenSpan]:
        """The innermost span of category ``cat`` open on THIS thread (the
        scheduler node a library call runs under), or None."""
        return next((sp for sp in reversed(self._stack()) if sp.cat == cat), None)

    @contextmanager
    def span(self, name: str, cat: str = "anovos", **attrs):
        """Record ``name`` spanning the ``with`` body; yields the open span.
        Exceptions propagate (the span still lands, flagged ``error``)."""
        stack = self._stack()
        if stack:
            attrs.setdefault("parent", stack[-1].name)
        # of the pass's tree: a phase, or a scheduler node of an open pass
        # (its worker's stack starts at the node; the scheduler names the
        # phase it ran under as the node's parent)
        tree = cat == "phase" or (cat == "node" and self._pass_open)
        stack.append(OpenSpan(name, cat, attrs, tree))
        note = None
        if _ANNOTATION is not None and cat in _ANNOTATED_CATS:
            note = _ANNOTATION(name)
            note.__enter__()
        # what a row of the tree consumed besides wall: this thread's seconds
        # on a CPU, and on the root and its direct children (the main
        # thread's ten to twelve phases) the whole process's (native threads
        # too), its page faults and involuntary context switches
        usage0 = (resource.getrusage(resource.RUSAGE_SELF)
                  if cat == "phase" and attrs.get("parent") in (None, ROOT_PHASE) else None)
        cpu0 = time.thread_time_ns() if tree else 0
        t0 = stack[-1].start_ns = time.perf_counter_ns()
        try:
            yield stack[-1]
        except BaseException as e:
            attrs["error"] = type(e).__name__
            raise
        finally:
            dur = time.perf_counter_ns() - t0
            if tree:
                attrs["cpu_s"] = (time.thread_time_ns() - cpu0) / 1e9
            if usage0 is not None:
                u = resource.getrusage(resource.RUSAGE_SELF)
                attrs.update(
                    proc_cpu_s=u.ru_utime + u.ru_stime - usage0.ru_utime - usage0.ru_stime,
                    minflt=u.ru_minflt - usage0.ru_minflt, majflt=u.ru_majflt - usage0.ru_majflt,
                    nivcsw=u.ru_nivcsw - usage0.ru_nivcsw)
            if note is not None:
                note.__exit__(None, None, None)
            stack.pop()
            th = threading.current_thread()
            self._record(Span(name, cat, t0 - self._epoch_ns, dur,
                              th.name, th.ident or 0, attrs, self.run_id), tree)

    def phase(self, name: str, cat: str = "anovos", **attrs):
        """A span of the pass's phase tree: ``cat="phase"`` where a span open
        on this thread is itself of the tree (a phase, the root being
        ``run_pass()``'s, or a scheduler node of the pass), and its parent is
        the innermost such span, so every row's parent is a row: an op span
        in between (``obs.timed`` around a library call) is none and is
        passed over.  Anywhere else (on a writer thread, outside any pass)
        the same work is an ordinary span of category ``cat``."""
        row = self.tree_row()
        if row is not None:
            cat = "phase"
            if row is not self._stack()[-1]:
                attrs["parent"] = row.name
        return self.span(name, cat=cat, **attrs)

    def finished(self, name: str, seconds: float, cat: str = "anovos", **attrs) -> None:
        """Record a span that ended just now on THIS thread and took
        ``seconds``: what is learned only after the fact (a listener told how
        long a compile stage took).  Filed as ``phase()`` would have filed it:
        a row of the pass's tree under the innermost row open here (never
        starting before that row), an ordinary span anywhere else.  It
        carries no usage and no ``TraceAnnotation``."""
        end = time.perf_counter_ns()
        start = end - max(int(seconds * 1e9), 0)
        row = self.tree_row()
        if row is not None:
            cat, attrs["parent"], start = "phase", row.name, max(start, row.start_ns)
        elif self._stack():
            attrs.setdefault("parent", self._stack()[-1].name)
        th = threading.current_thread()
        self._record(Span(name, cat, start - self._epoch_ns, end - start,
                          th.name, th.ident or 0, attrs, self.run_id), row is not None)

    def tree_row(self) -> Optional[OpenSpan]:
        """The innermost span open on THIS thread that is a row of the pass's
        tree (what a ``phase()`` opened here would name as its parent), or
        None."""
        return next((sp for sp in reversed(self._stack()) if sp.tree), None)

    def open_spans(self) -> List[OpenSpan]:
        """The spans open on THIS thread, outermost first: what a thread
        that hands work to another passes to :meth:`under`."""
        return list(self._stack())

    @contextmanager
    def under(self, spans: List[OpenSpan]):
        """Run the body as work handed over by the thread that had ``spans``
        open (its :meth:`open_spans`): on a thread with no span of its own
        open (a pool thread) a span opened inside has the parent, and a
        ``phase()`` the row of the tree, that it would have had on the
        submitting thread; without this a pool thread's phases would be
        filed outside the tree.  The hand-over itself records no span."""
        stack = self._stack()
        if stack or not spans:
            yield
            return
        stack.extend(spans)
        try:
            yield
        finally:
            del stack[:]

    @contextmanager
    def holding(self, lock, wait_name: str, cat: str = "anovos"):
        """Hold ``lock`` for the body.  Where another thread has it, the wait
        is a ``phase(wait_name)``: a row under the waiting node and not its
        self time; uncontended, no span."""
        if not lock.acquire(blocking=False):
            with self.phase(wait_name, cat=cat):
                lock.acquire()
        try:
            yield
        finally:
            lock.release()

    def in_pass(self) -> bool:
        """Whether THIS thread is inside a ``run_pass()``."""
        stack = self._stack()
        return bool(stack) and stack[0].cat == "phase"

    @contextmanager
    def run_pass(self):
        """One pass: a fresh timeline, a new ``run_id`` and the root span
        (``workflow.run`` opens one per call)."""
        self.clear()
        self.run_id = uuid.uuid4().hex[:12]
        self._pass_open = True
        try:
            with self.span(ROOT_PHASE, cat="phase") as root:
                yield root
        finally:
            self._pass_open = False

    def instant(self, name: str, cat: str = "anovos", **attrs) -> None:
        """A zero-duration marker event."""
        th = threading.current_thread()
        self._record(Span(name, cat, time.perf_counter_ns() - self._epoch_ns,
                          0, th.name, th.ident or 0, attrs, self.run_id))

    def _record(self, sp: Span, tree: bool = False) -> None:
        dropped = warn = False
        with self._lock:
            if tree:
                self._phases.append(sp)
                if sp.cat == "phase" and "parent" not in sp.args:
                    self._root = sp
            if len(self._spans) == self._spans.maxlen:
                self._dropped += 1
                dropped = True
                if not self._warned_wrap:
                    self._warned_wrap = warn = True
            self._spans.append(sp)
        if dropped:
            # ring overflow is no longer silent: a long-running service
            # that outgrows the buffer books every evicted span (and warns
            # ONCE) so /metrics shows the loss instead of the trace simply
            # missing its first hours.  Only the overflow regime pays the
            # counter; the steady-state record path is unchanged.
            from anovos_tpu.obs.metrics import get_metrics

            get_metrics().counter(
                "trace_spans_dropped_total",
                "spans evicted from the tracer ring at maxlen (raise "
                "ANOVOS_TPU_TRACE_BUFFER or enable ANOVOS_TPU_TRACE_ROTATE)",
            ).inc()
            if warn:
                logger.warning(
                    "tracer ring wrapped at maxlen=%d — older spans are being "
                    "dropped; raise ANOVOS_TPU_TRACE_BUFFER or set "
                    "ANOVOS_TPU_TRACE_ROTATE to export-and-clear segments",
                    self._spans.maxlen)

    # -- reading / lifecycle --------------------------------------------
    def snapshot(self) -> List[Span]:
        with self._lock:
            return list(self._spans)

    def span_count(self) -> int:
        with self._lock:
            return len(self._spans)

    @property
    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    def clear(self) -> None:
        """Start a fresh timeline (``run_pass`` calls this per pass)."""
        with self._lock:
            self._spans.clear()
            self._dropped = 0
            self._warned_wrap = False
            self._epoch_ns = time.perf_counter_ns()
            self._epoch_monotonic = time.monotonic()
            self.run_id = None
            self._phases = []
            self._root = None

    def phases(self) -> List[dict]:
        """The finished spans of the pass's tree (phases and scheduler nodes)
        as the manifest holds them: ``{name, parent, start_s, end_s, thread,
        counts, usage}``, seconds from the root span's start, in order of
        start.  ``counts`` are the span's numeric attributes, ``usage`` what
        it consumed (``_USAGE``).  Empty until the root has ended."""
        with self._lock:
            spans, root = list(self._phases), self._root
        if root is None:
            return []
        rows = []
        for sp in spans:
            start = sp.start_ns - root.start_ns
            rows.append({
                "name": sp.name,
                "parent": sp.args.get("parent"),
                "start_s": round(start / 1e9, 6),
                "end_s": round((start + sp.dur_ns) / 1e9, 6),
                "thread": sp.thread,
                "counts": {k: round(v, 6) if isinstance(v, float) else v
                           for k, v in sp.args.items()
                           if isinstance(v, (int, float)) and not isinstance(v, bool)
                           and k not in _USAGE},
                "usage": {k: round(sp.args[k], 6) for k in _USAGE if k in sp.args},
            })
        rows.sort(key=lambda r: (r["start_s"], -r["end_s"]))
        return rows

    def seconds_at(self, reading: float, perf_counter: bool = False) -> Optional[float]:
        """A ``time.monotonic()`` reading (the scheduler's stamps), or with
        ``perf_counter`` one of ``time.perf_counter()`` (the spans' own clock:
        what a process noted before its first pass), in seconds from the root
        span's start, as ``phases()`` counts them."""
        with self._lock:
            root = self._root
            if root is None:
                return None
            epoch = self._epoch_ns / 1e9 if perf_counter else self._epoch_monotonic
            return round(reading - epoch - root.start_ns / 1e9, 6)

    def drain(self) -> List[Span]:
        """Atomically copy-and-clear the ring WITHOUT re-basing the epoch
        — rotation's primitive: successive drains partition one
        uninterrupted timeline, so the union of exported segments equals
        what a single unbounded export would have held."""
        with self._lock:
            out = list(self._spans)
            self._spans.clear()
        return out

    def requeue(self, spans: List[Span]) -> None:
        """Put drained spans back at the FRONT of the ring (a failed
        segment export must not lose them).  If front + current exceed
        the bound, the oldest spans fall off — the same eviction the
        ring would have applied anyway."""
        with self._lock:
            merged = list(spans) + list(self._spans)
            self._spans.clear()
            overflow = len(merged) - (self._spans.maxlen or len(merged))
            if overflow > 0:
                self._dropped += overflow
            self._spans.extend(merged[-(self._spans.maxlen or len(merged)):])
        if overflow > 0:
            # same visibility contract as _record: span loss — here from
            # persistently-failing segment exports — must show on /metrics
            from anovos_tpu.obs.metrics import get_metrics

            get_metrics().counter(
                "trace_spans_dropped_total",
                "spans evicted from the tracer ring at maxlen (raise "
                "ANOVOS_TPU_TRACE_BUFFER or enable ANOVOS_TPU_TRACE_ROTATE)",
            ).inc(overflow)

    # -- export ----------------------------------------------------------
    def to_chrome(self, spans: Optional[Iterable[Span]] = None) -> dict:
        """Trace Event Format document (the ``chrome://tracing`` schema)."""
        if spans is None:
            spans = self.snapshot()
        pid = os.getpid()
        events: List[dict] = []
        seen_tids: Dict[int, str] = {}
        for sp in spans:
            if sp.tid not in seen_tids:
                seen_tids[sp.tid] = sp.thread
            ev = {
                "name": sp.name,
                "cat": sp.cat,
                "ph": "X" if sp.dur_ns else "i",
                "ts": sp.start_ns / 1e3,   # microseconds
                "pid": pid,
                "tid": sp.tid,
            }
            if sp.cat == "cache":
                # cache-restore spans on worker lanes render in a fixed
                # distinct color, so a warm run's restored-vs-executed mix
                # is visible at a glance in Perfetto
                ev["cname"] = "thread_state_runnable"
            if sp.dur_ns:
                ev["dur"] = sp.dur_ns / 1e3
            else:
                ev["s"] = "t"  # instant scope: thread
            if sp.args:
                ev["args"] = {k: _jsonable(v) for k, v in sp.args.items()}
            if sp.run_id is not None:
                ev.setdefault("args", {})["run_id"] = sp.run_id
            events.append(ev)
        meta = [
            {"name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
             "args": {"name": tname}}
            for tid, tname in sorted(seen_tids.items())
        ]
        return {"traceEvents": meta + events, "displayTimeUnit": "ms"}

    def export(self, path: str, spans: Optional[Iterable[Span]] = None) -> str:
        """Write the Chrome-trace JSON; returns the path written."""
        doc = self.to_chrome(spans)
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(doc, f)
        return path


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    return str(v)


_TRACER = Tracer()


def get_tracer() -> Tracer:
    """The process-wide tracer (scheduler, writer, and ops all share it)."""
    return _TRACER


def trace_destination(default_dir: str = ".") -> Optional[str]:
    """Resolve ``ANOVOS_TPU_TRACE`` to an export path, or None when unset.

    ``1``/``true`` → ``<default_dir>/obs/trace.json``; any other non-empty
    value is used verbatim as the path.
    """
    val = os.environ.get("ANOVOS_TPU_TRACE", "")
    if not val or val.lower() in ("0", "false"):
        return None
    if val.lower() in ("1", "true"):
        return os.path.join(default_dir, "obs", "trace.json")
    return val


def write_chrome_trace(path: str) -> str:
    """Export the process-wide tracer's buffer to ``path``."""
    return _TRACER.export(path)


# ---------------------------------------------------------------------------
# trace segment rotation (ANOVOS_TPU_TRACE_ROTATE)
# ---------------------------------------------------------------------------

def rotation_spec() -> Optional[Tuple[str, float]]:
    """``ANOVOS_TPU_TRACE_ROTATE`` parsed to ``("secs", s)`` /
    ``("spans", n)``, or None when off.

    A value with an ``s`` suffix rotates on wall time (``"30s"``,
    ``"1.5s"``); a bare integer rotates when the ring holds that many
    spans (``"100000"``).  ``0``/unset/garbage → off (garbage warns)."""
    raw = os.environ.get("ANOVOS_TPU_TRACE_ROTATE", "").strip().lower()
    if not raw or raw in ("0", "false", "off"):
        return None
    try:
        if raw.endswith("s") and raw[:-1]:
            secs = float(raw[:-1])
            return ("secs", secs) if secs > 0 else None
        n = int(raw)
        return ("spans", float(n)) if n > 0 else None
    except ValueError:
        logger.warning("ANOVOS_TPU_TRACE_ROTATE=%r is neither '<secs>s' nor "
                       "a span count; rotation off", raw)
        return None


class TraceRotator:
    """Periodic export-and-clear of the tracer ring into numbered Chrome-
    trace segments — a week-long service run keeps a COMPLETE,
    bounded-on-disk trace instead of only the ring's last ~200k spans.

    Segments land next to the configured export path (``trace.json`` →
    ``trace_0001.json``, ``trace_0002.json``, …); the drain preserves the
    tracer epoch, so segments share one timeline and their union equals
    an uninterrupted export.  When ``submit`` is provided (the run's
    :class:`AsyncArtifactWriter`), segment writes ride the async queue;
    otherwise they are written on the rotator's own daemon thread —
    either way the traced threads never block on a segment write."""

    def __init__(self, dest: str, tracer: Optional[Tracer] = None,
                 spec: Optional[Tuple[str, float]] = None,
                 submit=None):
        self.dest = dest
        self.tracer = tracer or get_tracer()
        self.spec = spec if spec is not None else rotation_spec()
        self.submit = submit
        self.segments: List[str] = []
        self._n = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._last = time.monotonic()
        self._lock = threading.Lock()

    @property
    def active(self) -> bool:
        return self.spec is not None

    def segment_path(self, n: int) -> str:
        base = self.dest[:-5] if self.dest.endswith(".json") else self.dest
        return f"{base}_{n:04d}.json"

    def start(self) -> "TraceRotator":
        if not self.active or self._thread is not None:
            return self
        kind, val = self.spec
        poll = min(1.0, val / 4.0) if kind == "secs" else 0.25
        self._thread = threading.Thread(
            target=self._loop, args=(max(poll, 0.05),),
            name="anovos-trace-rotator", daemon=True)
        self._thread.start()
        return self

    def _loop(self, poll: float) -> None:
        while not self._stop.wait(poll):
            try:
                self.maybe_rotate()
            except Exception:
                logger.exception("trace segment export failed; spans "
                                 "requeued into the ring, retrying next period")

    def _due(self) -> bool:
        kind, val = self.spec
        if kind == "secs":
            return time.monotonic() - self._last >= val
        return self.tracer.span_count() >= val

    def maybe_rotate(self, force: bool = False) -> Optional[str]:
        """Export-and-clear one segment when due (or ``force``); returns
        the segment path, or None when nothing rotated.  A failed direct
        export requeues the drained spans and records no segment — spans
        are never lost and ``segments`` never names a phantom file."""
        if not self.active:
            return None
        with self._lock:
            if not force and not self._due():
                return None
            self._last = time.monotonic()
            spans = self.tracer.drain()
            if not spans:
                return None
            self._n += 1
            n = self._n
            path = self.segment_path(n)
        if self.submit is not None:
            # ONE constant writer key for every segment: a per-segment key
            # would mint a fresh artifact_writes_total series per rotation
            # — the unbounded-label-cardinality leak GC016 polices — and
            # the writer's pending list handles repeated keys fine.  A
            # queued write's failure surfaces at the writer's drain.
            self.submit("obs:trace_seg", self.tracer.export, path, spans)
        else:
            try:
                self.tracer.export(path, spans)
            except Exception:
                with self._lock:
                    self._n -= 1
                self.tracer.requeue(spans)
                raise
        with self._lock:
            self.segments.append(path)
        return path

    def close(self) -> List[str]:
        """Stop the timer thread and flush the final segment; returns all
        segment paths written.  Idempotent."""
        if not self.active:
            return []
        self._stop.set()
        thread = self._thread
        self._thread = None
        if thread is not None:
            thread.join(timeout=10)
        self.maybe_rotate(force=True)
        return list(self.segments)


def maybe_rotator(default_dir: str, submit=None,
                  tracer: Optional[Tracer] = None) -> Optional[TraceRotator]:
    """A started :class:`TraceRotator` when ``ANOVOS_TPU_TRACE_ROTATE``
    is set, else None (zero threads).  Rotation implies export: with
    ``ANOVOS_TPU_TRACE`` also set its path anchors the segment names,
    otherwise segments default under ``<default_dir>/obs/``."""
    spec = rotation_spec()
    if spec is None:
        return None
    dest = trace_destination(default_dir) or os.path.join(
        default_dir, "obs", "trace.json")
    return TraceRotator(dest, tracer=tracer, spec=spec, submit=submit).start()
