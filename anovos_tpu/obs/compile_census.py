"""Process-wide XLA compile census.

The cold-run wall of the pipeline is compile-bound, not compute-bound
(PERF.md: 20.8 s of a 32.4 s cold configs_full spent in XLA compiles), and
the ``timed()`` first-call probes only see the ops they decorate.  This
module listens to JAX's own monitoring stream and follows every program on
its way to the device, stage by stage:

* ``/jax/core/compile/jaxpr_trace_duration`` — **trace**: the Python function
  run on tracers.  Only the outermost trace of a thread counts (what a
  program calls is traced inside it and is part of its seconds).
* ``/jax/core/compile/jaxpr_to_mlir_module_duration`` — **lower**.
* ``/jax/core/compile/backend_compile_duration`` — one event per program
  that reaches the backend, whether the persistent cache held it or not.
  The cache's own events on the same thread say which: after
  ``/jax/compilation_cache/cache_hits`` the event is a **load** (its stage
  seconds are ``/jax/compilation_cache/cache_retrieval_time_sec``: the
  read and the deserialization), else it is a **build** (the whole event:
  a real compile, and the write to the cache where there is one).
* ``/jax/compilation_cache/compile_requests_use_cache`` and
  ``/jax/compilation_cache/cache_misses`` — the cache was asked; an entry
  was handed to it for writing (a write that fails raises a Python warning
  and no event, so this counts attempts).

Each backend event is attributed to its program:

* **name**: the event's ``fun_name`` (``jit(_masked_quantiles)``).  Two
  compiles of the same kernel at different shapes share a name — the
  column-count shape variants the census exists to expose.
* **fingerprint**: sha1 of the lowered MLIR module text, read from the
  ``_cached_compilation`` frame on the listener's stack — the true program
  signature.  ``distinct_programs`` counts unique fingerprints, so a
  recompile of an identical program (cache eviction, donation variants)
  does not inflate it.
* **node**: the scheduler node open on the dispatching thread (the tracer's
  innermost ``node`` span), or none.

Inside a pass every stage is also a finished span of the pass's tree
(``compile/trace``, ``compile/lower``, ``compile/load``, ``compile/build``;
``Tracer.finished``) under the row that waited for it.

Never raises: if the JAX internals move, attribution degrades to
``<unknown>`` names and per-event fingerprints (every compile counts as
distinct — the safe error direction for a regression gate).  What the
listeners themselves cost is counted (``self_seconds_total``).

Wire-up: :func:`install` is idempotent and called from
``runtime.init_runtime`` (so any entry point that touches the device mesh
is covered) and again from ``workflow.main``.  ``workflow.main`` stamps
:func:`mark` at run start and embeds :func:`census` (the delta) in the run
manifest; ``tools/compile_census.py`` renders it and gates CI.
"""

from __future__ import annotations

import hashlib
import sys
import threading
import time
from typing import List, Optional

from anovos_tpu.obs.metrics import get_metrics
from anovos_tpu.obs.tracing import get_tracer

__all__ = ["install", "mark", "census", "COMPILE_EVENT"]

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_WRITE_EVENT = "/jax/compilation_cache/cache_misses"

_STAGES = ("trace", "lower", "load", "build")

_LOCK = threading.Lock()
_INSTALLED = False
# per dispatching thread: how deep in nested traces it is, whether the cache
# answered the request in flight and how long the retrieval took, and the
# listeners' seconds not yet booked on a record
_TL = threading.local()


class _Heard:
    """One thing heard.  ``kind`` is a stage of one program (``_STAGES``, with
    the stage's ``seconds``) or "request" / "write" (the cache asked, written
    to); ``fingerprint``, ``node`` and ``backend_s`` (the whole
    backend_compile_duration event) are those of a "load" or "build";
    ``own_s`` is what the listeners took since the thread's last record."""

    __slots__ = ("kind", "program", "seconds", "fingerprint", "node", "backend_s", "own_s")

    def __init__(self, kind, program="", seconds=0.0, fingerprint=None, node=None, backend_s=0.0):
        self.kind, self.program, self.seconds = kind, program, float(seconds)
        self.fingerprint, self.node, self.backend_s, self.own_s = fingerprint, node, float(backend_s), 0.0


_EVENTS: List[_Heard] = []  # in the order heard; ``mark()`` is a position in it


def _sniff_program() -> Optional[str]:
    """Module-text fingerprint from the compile call stack."""
    try:
        f = sys._getframe(1)
        while f is not None:
            if f.f_code.co_name == "_cached_compilation":
                comp = f.f_locals.get("computation")
                if comp is not None:
                    return hashlib.sha1(str(comp).encode()).hexdigest()[:16]
                break
            f = f.f_back
    except Exception:
        pass
    return None


def _book(rec: _Heard) -> _Heard:
    """A record of the census and, for a stage, a finished span of the tracer."""
    with _LOCK:
        if rec.kind in ("load", "build") and rec.fingerprint is None:
            rec.fingerprint = f"<event-{len(_EVENTS)}>"  # degrade: every compile distinct
        _EVENTS.append(rec)
    if rec.kind in _STAGES:
        get_tracer().finished("compile/" + rec.kind, rec.seconds, cat="compile")
    return rec


def _hear_duration(event: str, seconds: float, fun_name=None) -> Optional[_Heard]:
    program = "<unknown>" if fun_name is None else str(fun_name)
    if event == _TRACE_EVENT:
        _TL.depth = depth = max(getattr(_TL, "depth", 0) - 1, 0)
        if depth:
            return None  # traced inside another program's trace: part of its seconds
        # the trace is told the function's name, lowering and the backend the module's
        return _book(_Heard("trace", f"jit({program})", seconds))
    if event == _LOWER_EVENT:
        return _book(_Heard("lower", program, seconds))
    if event == _RETRIEVAL_EVENT:
        _TL.load_s = float(seconds)
        return None
    if event != COMPILE_EVENT:
        return None
    hit, load_s = getattr(_TL, "hit", False), getattr(_TL, "load_s", 0.0)
    _TL.hit, _TL.load_s = False, 0.0
    # the scheduler node of the DISPATCHING thread (compiles happen
    # synchronously inside the node body's dispatch) — None outside any node
    node = get_tracer().enclosing("node")
    rec = _book(_Heard("load" if hit else "build", program, load_s if hit else seconds,
                       _sniff_program(), node.name if node is not None else None, seconds))
    reg = get_metrics()
    reg.counter("xla_compiles_total",
                "XLA backend compiles observed this process").inc()
    reg.counter("xla_compile_seconds_total",
                "wall seconds spent in XLA backend compiles").inc(rec.backend_s)
    return rec


def _hear_event(event: str) -> Optional[_Heard]:
    if event == _HIT_EVENT:
        _TL.hit = True
    elif event == _REQUEST_EVENT:
        _TL.hit, _TL.load_s = False, 0.0  # a new request: whatever the last one left is stale
        return _book(_Heard("request"))
    elif event == _WRITE_EVENT:
        return _book(_Heard("write"))
    return None


def _timed(hear, *args) -> None:
    """Run one listener body; its own seconds go on the record it made, or wait
    on the thread for the next one.  A census must never break a compile."""
    t0 = time.perf_counter()
    rec = None
    try:
        rec = hear(*args)
    except Exception:
        pass
    own = getattr(_TL, "own_s", 0.0) + time.perf_counter() - t0
    if rec is not None:
        rec.own_s, own = own, 0.0
    _TL.own_s = own


def _listener(event: str, duration_secs: float, fun_name=None, **_kw) -> None:
    _timed(_hear_duration, event, duration_secs, fun_name)


def _event_listener(event: str, **_kw) -> None:
    _timed(_hear_event, event)


def _scalar_listener(event: str, _value=None, **_kw) -> None:
    # JAX reports the start of a timed stage as a scalar: how traces nest
    if event == _TRACE_EVENT:
        _TL.depth = getattr(_TL, "depth", 0) + 1


def install() -> None:
    """Register the jax.monitoring listeners (idempotent, never raises)."""
    global _INSTALLED
    with _LOCK:
        if _INSTALLED:
            return
        _INSTALLED = True
    try:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_listener)
        jax.monitoring.register_event_listener(_event_listener)
        jax.monitoring.register_scalar_listener(_scalar_listener)
    except Exception:
        pass


def mark() -> int:
    """Current event position — pass to :func:`census` for a per-run delta."""
    with _LOCK:
        return len(_EVENTS)


def census(since: int = 0, top: int = 20) -> dict:
    """Aggregate view of what was heard after ``since``.

    ``compiles_total`` counts backend events (loads and builds),
    ``distinct_programs`` their unique fingerprints, ``distinct_kernels``
    their unique names, ``compile_seconds_total`` their seconds.  Of those
    events ``cache_hits`` were loads and ``built_programs`` builds (the cache
    asked or not: a program that compiles in under
    ``jax_persistent_cache_min_compile_time_secs`` is never stored and is
    built by every process); ``cache_requests`` and ``cache_writes`` count the
    cache's own events, the four ``*_seconds_total`` sum the stages and
    ``self_seconds_total`` is what the listeners took.  ``programs`` is the
    per-name table (count = backend events, seconds = theirs, ``trace_s`` /
    ``lower_s`` / ``load_s`` / ``build_s`` the stages, ``hits`` the loads)
    sorted by the program's whole way to the device (stages traced and
    lowered, and the backend), truncated to ``top`` (0 = all); a program that
    was traced or lowered and never reached the backend has ``count`` 0.
    """
    with _LOCK:
        events = _EVENTS[since:]
    by_name: dict = {}
    fps = set()
    totals = dict.fromkeys(_STAGES, 0.0)
    counts = {"request": 0, "write": 0, "load": 0, "build": 0}
    for e in events:
        if e.kind in counts:
            counts[e.kind] += 1
        if e.kind not in totals:
            continue
        totals[e.kind] += e.seconds
        row = by_name.setdefault(e.program, {"program": e.program, "count": 0, "seconds": 0.0, "hits": 0,
                                             "nodes": set(), **dict.fromkeys(_STAGES, 0.0)})
        row[e.kind] += e.seconds
        if e.kind in ("load", "build"):
            fps.add(e.fingerprint)
            row["count"] += 1
            row["seconds"] += e.backend_s
            row["hits"] += e.kind == "load"
            if e.node:
                row["nodes"].add(e.node)
    programs = sorted(by_name.values(),
                      key=lambda r: (-(r["seconds"] + r["trace"] + r["lower"]), r["program"]))
    if top:
        programs = programs[:top]
    return {
        "compiles_total": counts["load"] + counts["build"],
        "distinct_programs": len(fps),
        "distinct_kernels": sum(1 for r in by_name.values() if r["count"]),
        "compile_seconds_total": round(sum(e.backend_s for e in events), 3),
        "cache_requests": counts["request"],
        "cache_hits": counts["load"],
        "cache_writes": counts["write"],
        "built_programs": counts["build"],
        **{f"{k}_seconds_total": round(totals[k], 3) for k in _STAGES},
        "self_seconds_total": round(sum(e.own_s for e in events), 6),
        "programs": [
            {"program": r["program"], "count": r["count"], "seconds": round(r["seconds"], 3),
             **{f"{k}_s": round(r[k], 3) for k in _STAGES}, "hits": r["hits"],
             "nodes": sorted(r["nodes"])}
            for r in programs
        ],
    }
