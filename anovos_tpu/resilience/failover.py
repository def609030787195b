"""Mid-run backend failover: detect a wedged accelerator, flip to CPU.

An accelerator can stop answering dispatches mid-run.  Without this
module that costs the whole run (watchdog → ``NodeTimeout`` → abort).
Here the scheduler recovers IN-RUN:

* :func:`backend_healthy` — a bounded in-process dispatch check
  (:func:`probe_in_process`): one tiny jitted program with a
  hard deadline on a helper thread.  The chaos harness's simulated wedge
  (``chaos.backend_wedged()``) short-circuits it, so the failover path is
  tier-1-testable without real broken hardware.
* :func:`maybe_failover` — the scheduler's hook on node failure /
  escalated timeout.  Cheap by default: it only pays the probe when the
  wedge flag is set or the exception LOOKS backend-shaped (XLA runtime
  errors, dead-connection RPC noise) — an ordinary config error never costs
  a probe.  On an unhealthy verdict it flips once.
* :func:`failover_to_cpu` — the flip: pin ``jax_default_device`` to a
  CPU device (honored mid-process, unlike ``jax_platforms``), rebuild
  the runtime mesh over the CPU device set, clear the simulated wedge,
  and journal ``backend_failover``.  Programs recompile for CPU on next
  dispatch; nodes committed before the wedge keep their results (the
  WAL/cache frontier), so a wedge costs seconds of re-execution of the
  in-flight frontier instead of the run.

One flip per run: CPU cannot wedge, so a second unhealthy verdict means
the failure is not the backend and the error policy proceeds normally.
"""

from __future__ import annotations

import functools
import logging
import os
import threading
from typing import Optional

logger = logging.getLogger("anovos_tpu.resilience.failover")

__all__ = [
    "backend_healthy",
    "failover_to_cpu",
    "maybe_failover",
    "failover_count",
    "reset",
]

_LOCK = threading.Lock()
_STATE = {"flipped": False, "count": 0}

# exception text that earns a (bounded) health probe: the classes an
# unresponsive backend produces, plus XLA's runtime-error surface
_BACKEND_ERROR_MARKERS = (
    "XlaRuntimeError", "DEADLINE_EXCEEDED", "UNAVAILABLE", "INTERNAL",
    "failed to connect", "socket closed", "Unable to initialize backend",
    "BackendWedge",
)


def _looks_backend_shaped(exc: BaseException) -> bool:
    text = f"{type(exc).__name__}: {exc}"
    return any(m in text for m in _BACKEND_ERROR_MARKERS)


@functools.lru_cache(maxsize=1)
def _inproc_probe_fn():
    """One tiny jitted program for the in-process health check — built
    once ever, so repeated probes hit the compile cache instead of
    re-tracing (graftcheck GC003 discipline)."""
    import jax

    return jax.jit(lambda a: a + 1.0)


def probe_in_process(timeout_s: float) -> bool:
    """Bounded IN-PROCESS dispatch check: "is THIS process's backend still
    dispatching", asked between scheduler nodes.  One tiny jitted program
    must round-trip (compute + device→host fetch) within ``timeout_s`` on a
    helper thread; a wedged dispatch leaves the daemon thread behind —
    unavoidable at thread level, bounded to one probe at a time by the
    caller (the flip to CPU follows the first failed probe, and CPU probes
    cannot wedge)."""
    done = threading.Event()
    result = {"ok": False}

    def _dispatch():
        try:
            result["ok"] = float(_inproc_probe_fn()(1.0)) == 2.0
        except Exception:
            result["ok"] = False
        finally:
            done.set()

    t = threading.Thread(target=_dispatch, name="backend-health-probe", daemon=True)
    t.start()
    if not done.wait(timeout_s):
        return False  # the probe thread is wedged with the backend
    return result["ok"]


def backend_healthy(timeout_s: Optional[float] = None) -> bool:
    """Bounded answer to "is the current backend dispatching?".

    A chaos-simulated wedge reports unhealthy immediately; otherwise one
    tiny jitted program must round-trip within the deadline
    (``ANOVOS_TPU_HEALTH_TIMEOUT`` seconds, default 5)."""
    from anovos_tpu.resilience import chaos

    if chaos.backend_wedged():
        return False
    if timeout_s is None:
        timeout_s = float(os.environ.get("ANOVOS_TPU_HEALTH_TIMEOUT", "5"))
    return probe_in_process(timeout_s)


def failover_to_cpu(reason: str, journal=None) -> bool:
    """Flip the runtime to CPU (idempotent; False when already flipped).

    ``jax_default_device`` takes effect for every dispatch after the
    update — unlike ``jax_platforms``, which latches at backend init —
    and the runtime mesh is rebuilt over the CPU device set so sharded
    table programs re-place correctly."""
    with _LOCK:
        if _STATE["flipped"]:
            return False
        _STATE["flipped"] = True
        _STATE["count"] += 1
    from anovos_tpu.resilience import chaos

    try:
        import jax

        cpu_devices = jax.devices("cpu")
        jax.config.update("jax_default_device", cpu_devices[0])
        from anovos_tpu.shared.runtime import init_runtime

        init_runtime(devices=cpu_devices)
    except Exception:
        logger.exception("backend failover: CPU re-init failed; the run "
                         "continues on the configured backend")
        clear = False
    else:
        clear = True
    if clear:
        chaos.clear_wedge()
        logger.warning(
            "backend failover: accelerator unresponsive (%s); runtime "
            "flipped to CPU mid-run — committed node results are kept, the "
            "in-flight frontier re-executes", reason)
        from anovos_tpu.obs import get_metrics

        get_metrics().counter(
            "backend_failovers_total",
            "mid-run backend failovers (accelerator -> cpu)",
        ).inc()
        if journal is not None:
            try:
                journal.append("backend_failover", reason=str(reason)[:300])
            except Exception:
                logger.exception("backend_failover journal append failed")
    return clear


def maybe_failover(exc: Optional[BaseException] = None, journal=None,
                   force_probe: bool = False) -> bool:
    """The scheduler's failure hook: probe-if-suspicious, flip-if-wedged.

    Returns True when this call FLIPPED the backend (the caller then
    grants the failed node a failover re-execution that does not consume
    its retry budget).  Ordinary errors return False without paying a
    probe."""
    from anovos_tpu.resilience import chaos

    suspicious = force_probe or chaos.backend_wedged() or (
        exc is not None and _looks_backend_shaped(exc))
    if not suspicious:
        return False
    with _LOCK:
        if _STATE["flipped"]:
            return False
    if backend_healthy():
        return False
    return failover_to_cpu(
        reason=repr(exc) if exc is not None else "health probe timeout",
        journal=journal)


def failover_count() -> int:
    with _LOCK:
        return _STATE["count"]


def reset() -> None:
    """Per-run reset (workflow.main): a new run may probe/flip again."""
    with _LOCK:
        _STATE["flipped"] = False
        _STATE["count"] = 0
