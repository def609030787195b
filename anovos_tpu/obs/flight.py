"""Black-box flight recorder: a crash-safe postmortem for wedged runs.

The resilience layer (PR 6) *recovers* from hangs, wedges and timeouts
but leaves no record of what the run was doing when things went wrong:
every escalation throws away its evidence.  This module keeps a bounded in-memory ring of recent
lifecycle events (journal appends, chaos injections, retries — recorded
explicitly by their producers) and, when the scheduler or failover layer
hits one of the four postmortem triggers —

* **timeout escalation** (a node blew its watchdog bound),
* **abandonment** (a stuck attempt's thread was given up on),
* **backend failover** (the runtime flipped to CPU mid-run),
* **fatal error** (a raise-mode node is about to abort the run),

— it dumps everything it knows SYNCHRONOUSLY (tmp + rename, never
through the async writer: the process may be about to die) to
``obs/flightrec_<node>.json``:

* the trigger, the triggering node, and the in-flight node set (state,
  attempts, elapsed wall, and each node's last device op + live
  dispatch/transfer tallies from ``obs.devprof``);
* the scheduler's ready-queue depth (is the pool starved or stuffed?);
* per-device HBM state (``obs.metrics.memory_by_device``);
* the ring of recent lifecycle events plus the tail of the tracer's
  span buffer (the last ~200 spans: which ops ran, in what order, on
  which worker lanes);
* a full metrics snapshot.

Dumps land under ``obs/`` — the same telemetry subtree every golden
tree-hash already excludes — so a dump never perturbs artifact parity,
and a CLEAN run produces no dump at all (asserted by
``tools/chaos_run.py``).  ``ANOVOS_TPU_FLIGHTREC=0`` disables recording;
any other integer sets the event-ring bound (default 256).
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from collections import deque
from typing import List, Optional

logger = logging.getLogger("anovos_tpu.obs.flight")

__all__ = ["build_snapshot", "configure", "enabled", "record", "dump",
           "dump_paths", "reset", "snapshot_events"]

FLIGHTREC_VERSION = 1
_DEFAULT_EVENTS = 256
_SPAN_TAIL = 200  # tracer spans included in a dump

_LOCK = threading.Lock()
_RING: Optional[deque] = None   # None until configure(); disabled when env=0
_DIR: Optional[str] = None      # dump destination (the run's obs/ dir)
_DUMPS: List[str] = []          # paths written this run
# the LAST completed run's manifest at this obs dir, captured at arm time
# (before this run overwrites it): the perf doctor's live baseline — so
# /statusz and postmortems can say "slow vs the last clean run", not just
# "slow"
_BASELINE_MANIFEST: Optional[dict] = None


def _ring_bound() -> int:
    raw = os.environ.get("ANOVOS_TPU_FLIGHTREC", "")
    if raw == "0":
        return 0
    try:
        n = int(raw) if raw else _DEFAULT_EVENTS
    except ValueError:
        n = _DEFAULT_EVENTS
    return max(n, 16) if n else 0


def enabled() -> bool:
    with _LOCK:
        return _RING is not None and _DIR is not None


def configure(obs_dir: Optional[str]) -> None:
    """Arm the recorder for one run: fresh ring, dumps go to ``obs_dir``.

    ``workflow.main`` calls this with its resolved ``<run>/obs`` path
    before scheduling; a falsy ``obs_dir`` or ``ANOVOS_TPU_FLIGHTREC=0``
    disarms (library users of DagScheduler outside a workflow run see a
    no-op recorder)."""
    global _RING, _DIR, _BASELINE_MANIFEST
    bound = _ring_bound()
    baseline = None
    if obs_dir and bound != 0:
        # parse the previous completed run's manifest NOW — the file is
        # overwritten at this run's end, and a mid-run /statusz or crash
        # dump must compare against the run BEFORE this one
        try:
            path = os.path.join(os.path.abspath(obs_dir), "run_manifest.json")
            if os.path.isfile(path):
                with open(path) as f:
                    baseline = json.load(f)
        except Exception:
            baseline = None  # a torn/foreign file is no baseline
    with _LOCK:
        _DUMPS.clear()
        _BASELINE_MANIFEST = baseline
        if not obs_dir or bound == 0:
            _RING, _DIR = None, None
            return
        _RING = deque(maxlen=bound)
        _DIR = os.path.abspath(obs_dir)


def reset() -> None:
    """Disarm and drop state (tests)."""
    configure(None)


def record(kind: str, /, **fields) -> None:
    """Append one lifecycle event to the ring (no-op when disarmed).

    Producers: ``cache.journal`` (every WAL event), ``resilience.chaos``
    (injections), plus the scheduler's retry bookkeeping.  Cheap: one
    lock + deque append.  The event type lands under ``ev`` so payload
    fields named ``kind`` (journal retry records) never collide."""
    with _LOCK:
        if _RING is None:
            return
        _RING.append({"t_unix": round(time.time(), 3), "ev": kind, **fields})


def dump_paths() -> List[str]:
    """Dump files written since the last :func:`configure`."""
    with _LOCK:
        return list(_DUMPS)


def snapshot_events() -> List[dict]:
    """The current event ring, oldest first (empty when disarmed).  The
    read-only accessor consumers that ATTACH context — the continuum
    alert stream — use instead of triggering a full postmortem dump."""
    with _LOCK:
        return list(_RING) if _RING is not None else []


def _doctor_summary() -> Optional[dict]:
    """``diffing.live_node_summary`` over the captured baseline manifest
    and the current devprof state (guarded — never raises)."""
    with _LOCK:
        baseline = _BASELINE_MANIFEST
    if baseline is None:
        return None
    try:
        from anovos_tpu.obs import devprof
        from anovos_tpu.obs.diffing import live_node_summary

        return live_node_summary(baseline, devprof.results(),
                                 devprof.active_frames())
    except Exception:
        logger.exception("perf-doctor live summary failed")
        return None


def _safe_name(node: str) -> str:
    out = "".join(c if (c.isalnum() or c in "._-") else "_" for c in node)
    return out or "run"


def _span_tail() -> List[dict]:
    try:
        from anovos_tpu.obs.tracing import get_tracer

        spans = get_tracer().snapshot()[-_SPAN_TAIL:]
        return [
            {
                "name": sp.name, "cat": sp.cat, "thread": sp.thread,
                "start_ms": round(sp.start_ns / 1e6, 3),
                "dur_ms": round(sp.dur_ns / 1e6, 3),
                "args": {k: v for k, v in sp.args.items()
                         if isinstance(v, (str, int, float, bool))},
            }
            for sp in spans
        ]
    except Exception:
        return []


def build_snapshot(trigger: str, node: str = "",
                   inflight: Optional[List[dict]] = None,
                   queue_depth: Optional[int] = None,
                   rendezvous_holders: Optional[List[str]] = None,
                   extra: Optional[dict] = None,
                   events: Optional[List[dict]] = None) -> dict:
    """Assemble the flight-recorder snapshot document — ONE code path
    shared by the crash-time :func:`dump` and the live ``/statusz``
    endpoint (``obs.telemetry``), so the view an operator scrapes on
    demand is byte-for-byte the view a postmortem would have frozen:
    in-flight nodes with live devprof tallies and last device op, the
    ready-queue depth, per-device HBM, the event-ring tail, the span
    tail, and a full metrics snapshot.

    ``inflight`` entries carry each node's executor ``lane`` and leased
    ``devices`` (multi-device DAG execution), and ``rendezvous_holders``
    names the node(s) holding the collective rendezvous lane — together
    they are the evidence a rendezvous-deadlock postmortem needs: WHICH
    collective was in flight, on which chips.  Works with the recorder
    disarmed (the event ring is simply empty)."""
    from anovos_tpu.obs import devprof
    from anovos_tpu.obs.metrics import get_metrics, memory_by_device

    if events is None:
        events = snapshot_events()
    active = devprof.active_frames()
    inflight_out = []
    for entry in (inflight or []):
        name = entry.get("node", "")
        live = active.get(name)
        if live:
            entry = {**entry, "devprof": live}
        inflight_out.append(entry)
    backend = None
    try:
        import sys

        jax = sys.modules.get("jax")
        if jax is not None:
            backend = jax.default_backend()
    except Exception:
        pass
    doc = {
        "flightrec_version": FLIGHTREC_VERSION,
        "trigger": trigger,
        "node": node,
        "t_unix": round(time.time(), 3),
        "pid": os.getpid(),
        "backend": backend,
        "inflight": inflight_out,
        "queue_depth": queue_depth,
        "rendezvous_holders": list(rendezvous_holders or []),
        "hbm": {
            dev: {k: stats.get(k) for k in
                  ("bytes_in_use", "peak_bytes_in_use") if k in stats}
            for dev, stats in memory_by_device().items()
        },
        "events": events,
        "spans_tail": _span_tail(),
        "devprof_finished": devprof.results(),
        # perf-doctor live summary: THIS run's per-node walls vs the last
        # completed run at the same obs dir (captured at configure time) —
        # "what is slow right now vs the last clean run".  None when no
        # prior manifest exists; a summary must never sink a snapshot.
        "doctor": _doctor_summary(),
        "metrics": get_metrics().snapshot(),
    }
    if extra:
        doc["extra"] = extra
    return doc


def dump(trigger: str, node: str = "", inflight: Optional[List[dict]] = None,
         queue_depth: Optional[int] = None,
         rendezvous_holders: Optional[List[str]] = None,
         extra: Optional[dict] = None) -> Optional[str]:
    """Write the postmortem for ``trigger`` (one of the four classes in
    the module doc).  Returns the path, or None when disarmed/failed —
    a flight recorder must never take the run down with it."""
    with _LOCK:
        ring, out_dir = _RING, _DIR
        events = list(ring) if ring is not None else []
    if ring is None or out_dir is None:
        return None
    try:
        doc = build_snapshot(trigger, node=node, inflight=inflight,
                             queue_depth=queue_depth,
                             rendezvous_holders=rendezvous_holders,
                             extra=extra, events=events)
        os.makedirs(out_dir, exist_ok=True)
        # never overwrite an earlier dump for the same node THIS run: an
        # escalation-time snapshot must survive the later fatal/abandon
        # dump (the scheduler promises the escalation evidence is already
        # on disk when the escalated bound also blows).  The path is
        # claimed under the lock so concurrent triggers never collide.
        base = f"flightrec_{_safe_name(node)}"
        with _LOCK:
            taken = set(_DUMPS)
            path = os.path.join(out_dir, base + ".json")
            n = 1
            # a file from a PREVIOUS (crashed) run in the same obs dir is
            # evidence too — os.path.exists keeps a resumed run from
            # destroying the original crash postmortem
            while path in taken or os.path.exists(path):
                n += 1
                path = os.path.join(out_dir, f"{base}_{n}.json")
            _DUMPS.append(path)
        tmp = path + f".tmp.{os.getpid()}.{threading.get_ident()}"
        try:
            with open(tmp, "w") as f:
                json.dump(doc, f, sort_keys=True, indent=1, separators=(",", ": "))
                f.write("\n")
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except BaseException:
            with _LOCK:
                if path in _DUMPS:
                    _DUMPS.remove(path)
            raise
        logger.warning("flight recorder: %s on node %r — postmortem written "
                       "to %s", trigger, node, path)
        return path
    except Exception:
        logger.exception("flight-recorder dump for %r (%s) failed", node, trigger)
        return None
