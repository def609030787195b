"""``timed()`` — op-level wall-time wrapper that separates XLA trace/compile
time from steady-state execute time.

JAX compiles one program per (shape, dtype, static-arg) signature; the
first call through a jitted function at a new signature pays tracing +
XLA compilation, every later call replays the cached executable.  Timing
them as one bucket makes cold runs look like slow kernels and warm runs
look like fast compiles.  ``timed`` keeps a per-wrapper set of abstract
signatures it has already seen (the same first-call probe a compile cache
performs) and books the wall time under ``op_compile_seconds`` or
``op_execute_seconds`` accordingly, with a ``op_cache_hit_total`` counter
for the compile-cache hit rate the manifest reports.

The signature key is *abstract*: arrays contribute (shape, dtype), scalars
and strings their value, other objects their type — so a second call at
the same shapes counts as a cache hit even with different data, exactly
like XLA's own cache.  Key derivation never raises; an unkeyable argument
degrades to its type name.

CAVEAT — async dispatch: a purely-jitted op returns its device arrays
asynchronously, so on accelerators the ``execute``-phase wall measures
DISPATCH time, not device time; the device tail lands in whichever
downstream host fetch blocks.  ``timed`` deliberately does NOT insert a
``block_until_ready`` barrier — that would serialize the async overlap
the concurrent executor exists to exploit.  The numbers that
ARE representative: first-call ``compile`` walls (tracing+compilation is
synchronous), host-orchestrating ops that fetch internally
(``kmeans_elbow``, ``dbscan_fit``, ``describe_streaming``), and
everything on the CPU test mesh.  For device-true kernel time, wrap the
run in ``ANOVOS_PROFILE=<dir>`` (jax.profiler) instead.
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Optional

from anovos_tpu.obs import devprof
from anovos_tpu.obs.metrics import get_metrics
from anovos_tpu.obs.tracing import get_tracer

__all__ = ["timed"]


def _abstract(v, depth: int = 0):
    """Abstract signature of one argument (cheap, total)."""
    try:
        shape = getattr(v, "shape", None)
        dtype = getattr(v, "dtype", None)
        if shape is not None and dtype is not None:
            return ("arr", tuple(shape), str(dtype))
        if isinstance(v, (bool, int)):
            # ints/bools are overwhelmingly STATIC jit args in these ops
            # (k, iters, nbins, chunk, flags) — one compiled program per
            # VALUE — so they key by value.  A dynamic int scalar then
            # over-reports compiles (new value → "compile" despite a shared
            # program), which is the safe error direction for a first-call
            # probe; keying by type would misbook real static-arg compiles
            # as cache hits, inverting the split the manifest reports.
            return ("static", type(v).__name__, v)
        if isinstance(v, float):
            # float scalars trace as 0-d weak-typed arrays: one program per
            # dtype, not per value — 1.0 and 2.0 share a signature
            return ("scalar", "float")
        if isinstance(v, (str, bytes)) or v is None:
            return v  # strings are static args: the value IS the signature
        if isinstance(v, (tuple, list)) and depth < 3:
            return ("seq", tuple(_abstract(x, depth + 1) for x in v[:16]), len(v))
        if isinstance(v, dict) and depth < 3:
            return ("map", tuple(sorted(
                (str(k), _abstract(x, depth + 1)) for k, x in list(v.items())[:16])))
        return type(v).__name__
    except Exception:
        return type(v).__name__


def timed(name: Optional[str] = None):
    """Decorator: trace + meter calls to a (typically jitted) op.

    Emits a span per call (cat ``op``, ``args.phase`` ∈ {``compile``,
    ``execute``}) and books wall time into the process metrics registry.
    ``name`` defaults to ``module.qualname`` minus the package prefix.
    """

    def deco(fn):
        label = name or f"{fn.__module__.rsplit('.', 1)[-1]}.{fn.__qualname__}"
        seen: set = set()
        lock = threading.Lock()

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            try:
                key = (tuple(_abstract(a) for a in args),
                       tuple(sorted((k, _abstract(v)) for k, v in kwargs.items())))
                hash(key)
            except TypeError:
                key = None  # unhashable exotic args: treat every call as first
            with lock:
                first = key is None or key not in seen
                if key is not None:
                    seen.add(key)
            phase = "compile" if first else "execute"
            reg = get_metrics()
            t0 = time.perf_counter()
            # the devprof bracket books execute-phase wall as this node's
            # dispatch time (outermost bracket only — nested timed ops
            # would double-count) and stamps last_op for flight dumps
            with get_tracer().span(label, cat="op", phase=phase), \
                    devprof.dispatch_bracket(label, phase=phase):
                out = fn(*args, **kwargs)
            dt = time.perf_counter() - t0
            if first:
                reg.histogram("op_compile_seconds",
                              "first-call (trace+compile+execute) wall time"
                              ).observe(dt, op=label)
            else:
                reg.counter("op_cache_hit_total",
                            "op calls that replayed a cached executable").inc(op=label)
                reg.histogram("op_execute_seconds",
                              "steady-state op wall time").observe(dt, op=label)
            return out

        wrapper.__wrapped__ = fn
        return wrapper

    return deco
