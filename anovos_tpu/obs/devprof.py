"""Per-node device-time attribution: where a scheduler node's wall went.

The obs subsystem's host-side spans (PR 2) time nodes as opaque wall
intervals, which conflates four very different costs on an accelerator:

* **device time** — the chip actually computing;
* **dispatch** — host wall spent inside jitted-op calls (on an async
  backend this is enqueue time, not compute time — ``obs.timed``'s
  documented caveat);
* **transfer** — host↔device movement at ``Table`` materialization
  boundaries (h2d on ``Runtime.shard_rows``, d2h on
  ``Table.to_pandas`` / ``Column.exact_host``), with exact byte counts;
* **host** — everything else (pandas/pyarrow work, CSV writes, tracing,
  Python orchestration), computed as the remainder.

None of the ROADMAP scale items (multi-device node placement, out-of-core
overlap, serving latency) can be steered without this split — a node that
is 95% host time gains nothing from a faster chip, and a node that is 90%
queue-drain gains nothing from more workers.

Mechanism (stdlib + already-loaded jax only, never imports the backend):

* ``node_bracket(name)`` wraps one scheduler node.  On entry it samples
  per-device HBM (``memory_stats``, where the backend exposes it); on
  exit it runs a **drain probe** — dispatch one trivial jitted program
  and ``block_until_ready`` it.  Device streams execute in enqueue
  order, so the probe's blocking wall ≈ the device work still in flight
  at the node boundary; the probe's own unloaded floor (measured once at
  ``reset``) is subtracted.  That drain wall is the node's attributed
  ``device_time_s``.  (d2h transfers are themselves completion barriers,
  so device tail consumed by a materializing fetch lands in
  ``transfer_s`` — the attribution is "what the host was waiting ON",
  not a hardware counter.)
* ``dispatch_bracket(label)`` is entered by every ``timed()`` op; only
  the OUTERMOST bracket on a thread books its wall (nested timed ops —
  ``kmeans_elbow`` calling ``kmeans_fit`` — would double-count), and
  only ``execute``-phase walls count as dispatch (first-call walls are
  trace+compile, i.e. host work, left in the remainder).
* ``transfer_bracket(direction, nbytes)`` wraps the materialization
  choke points and books wall + bytes into both the active frame and
  the process-wide ``transfer_{h2d,d2h}_bytes_total`` counters.

Attribution is clamped so ``device_time_s + dispatch_s + transfer_s +
host_s ≤ wall`` ALWAYS holds: if the measured components exceed the wall
(overlap between categories), they are scaled down proportionally and
the frame is marked ``clamped``.

Everything lands in (a) the run manifest's ``devprof`` section (stripped
by ``stable_view`` — pure telemetry), (b) ``devprof_*`` metric families,
and (c) a ``devprof:<node>`` tracer instant next to the node span.
``ANOVOS_TPU_DEVPROF=0`` disables the brackets (one dict lookup per
site remains).  The node names in a profiler trace are not this module's:
the tracer annotates node and phase spans (``obs.tracing.annotate_with``),
whatever this switch says.
"""

from __future__ import annotations

import functools
import logging
import os
import sys
import threading
import time
from contextlib import contextmanager
from typing import Dict, Optional

from anovos_tpu.obs.metrics import get_metrics, memory_by_device

logger = logging.getLogger("anovos_tpu.obs.devprof")

__all__ = [
    "enabled",
    "reset",
    "current_frame",
    "under",
    "node_bracket",
    "dispatch_bracket",
    "transfer_bracket",
    "record_transfer",
    "record_decode",
    "results",
    "active_frames",
]

_LOCK = threading.Lock()
_RESULTS: Dict[str, dict] = {}     # node name -> finished attribution
_ACTIVE: Dict[str, "_Frame"] = {}  # node name -> in-flight frame
_TL = threading.local()            # .frame (current _Frame), .dispatch_depth

# unloaded wall of one drain probe (measured at reset); subtracted from
# boundary drains so an idle device attributes ~0 device time
_PROBE_FLOOR = 0.0


@functools.lru_cache(maxsize=1)
def _probe_fn():
    """The drain-probe program, compiled once per process."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda: jnp.zeros((), "float32") + 1.0)


def enabled() -> bool:
    """Brackets active unless ``ANOVOS_TPU_DEVPROF=0``."""
    return os.environ.get("ANOVOS_TPU_DEVPROF", "1") != "0"


class _Frame:
    __slots__ = ("name", "t0", "dispatch_s", "transfer_s", "device_s",
                 "h2d_bytes", "d2h_bytes", "dispatches", "transfers",
                 "decode_s", "decode_bytes", "decodes",
                 "last_op", "hbm0", "lane", "devices", "_lock")

    def __init__(self, name: str, lane: Optional[str] = None,
                 devices: Optional[list] = None):
        self.name = name
        # executor-lane attribution (PR 8): which lane the scheduler ran
        # this node on (mesh | submesh | device | host) and the leased
        # device labels — rides every result/snapshot so postmortems and
        # the manifest can tell rendezvous-lane time from fan-out time
        self.lane = lane
        self.devices = list(devices or [])
        self.t0 = time.perf_counter()
        self.dispatch_s = 0.0
        self.transfer_s = 0.0
        self.device_s = 0.0
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.dispatches = 0
        self.transfers = 0
        # streaming-ingest decode attribution (round 12): host wall spent
        # DECODING part files (pyarrow/pandas) vs merely consuming them.
        # Both were lumped into the host_s remainder before; the split is
        # what the AUTOTUNE window controller steers on.  Decode booked
        # from prefetch-pool worker threads can OVERLAP the node wall, so
        # decode_s is reported as an informational sub-attribution and
        # deliberately stays OUT of the clamped device+dispatch+transfer
        # +host ≤ wall invariant.
        self.decode_s = 0.0
        self.decode_bytes = 0
        self.decodes = 0
        self.last_op: Optional[str] = None
        self.hbm0 = _hbm_in_use()
        # transfer/dispatch hooks fire from the node's worker thread, but
        # async-writer threads can also materialize (queued write_dataset):
        # the frame is shared through _ACTIVE, so accumulate under a lock
        self._lock = threading.Lock()

    def add_dispatch(self, seconds: float, label: str) -> None:
        with self._lock:
            self.dispatch_s += seconds
            self.dispatches += 1
            self.last_op = label

    def add_transfer(self, direction: str, nbytes: int, seconds: float,
                     label: str) -> None:
        with self._lock:
            self.transfer_s += seconds
            self.transfers += 1
            if direction == "h2d":
                self.h2d_bytes += nbytes
            elif direction == "d2h":
                self.d2h_bytes += nbytes
            # d2d (placement re-lays) books wall only; bytes live in the
            # process-wide transfer_d2d_bytes_total counter
            self.last_op = label

    def add_decode(self, seconds: float, nbytes: int, label: str) -> None:
        with self._lock:
            self.decode_s += seconds
            self.decode_bytes += nbytes
            self.decodes += 1
            self.last_op = label

    def snapshot(self) -> dict:
        """In-flight view (flight-recorder dumps read this mid-node)."""
        with self._lock:
            return {
                "lane": self.lane,
                "devices": list(self.devices),
                "elapsed_s": round(time.perf_counter() - self.t0, 4),
                "dispatch_s": round(self.dispatch_s, 4),
                "transfer_s": round(self.transfer_s, 4),
                "h2d_bytes": self.h2d_bytes,
                "d2h_bytes": self.d2h_bytes,
                "decode_s": round(self.decode_s, 4),
                "decode_bytes": self.decode_bytes,
                "last_op": self.last_op,
            }

    def finish(self, drain: bool = True) -> dict:
        wall = time.perf_counter() - self.t0
        self.device_s = max(_drain_wall() - _PROBE_FLOOR, 0.0) if drain else 0.0
        hbm1 = _hbm_in_use()
        hbm_delta = {
            dev: int(hbm1.get(dev, 0) - b0)
            for dev, b0 in self.hbm0.items()
        }
        clamped = False
        attributed = self.device_s + self.dispatch_s + self.transfer_s
        if attributed > wall > 0.0:
            scale = wall / attributed
            self.device_s *= scale
            self.dispatch_s *= scale
            self.transfer_s *= scale
            clamped = True
        # round the wall and the three attributed components FIRST, then
        # derive host from the rounded values: rounding each independently
        # can push the sum a few 1e-6 past the rounded wall, violating the
        # documented invariant.  Any post-rounding excess is shaved off the
        # largest component so everything stays on the 1e-6 grid.
        wall_r = round(wall, 6)
        dev_r = round(self.device_s, 6)
        disp_r = round(self.dispatch_s, 6)
        xfer_r = round(self.transfer_s, 6)
        excess = round(dev_r + disp_r + xfer_r - wall_r, 6)
        if excess > 0:
            if dev_r >= disp_r and dev_r >= xfer_r:
                dev_r = round(max(dev_r - excess, 0.0), 6)
            elif disp_r >= xfer_r:
                disp_r = round(max(disp_r - excess, 0.0), 6)
            else:
                xfer_r = round(max(xfer_r - excess, 0.0), 6)
        host_r = round(max(wall_r - dev_r - disp_r - xfer_r, 0.0), 6)
        out = {
            "wall_s": wall_r,
            "device_time_s": dev_r,
            "dispatch_s": disp_r,
            "transfer_s": xfer_r,
            "host_s": host_r,
            "h2d_bytes": self.h2d_bytes,
            "d2h_bytes": self.d2h_bytes,
            "dispatches": self.dispatches,
            "transfers": self.transfers,
            "last_op": self.last_op,
            "clamped": clamped,
        }
        if self.decodes:
            # informational sub-attribution (see __init__): under a prefetch
            # pool the decode wall runs on background threads and may exceed
            # the host_s remainder — it measures decode WORK, not node wall
            out["decode_s"] = round(self.decode_s, 6)
            out["decode_bytes"] = self.decode_bytes
            out["decodes"] = self.decodes
        if self.lane is not None:
            out["lane"] = self.lane
            out["devices"] = list(self.devices)
        if any(hbm_delta.values()):
            out["hbm_delta_bytes"] = hbm_delta
        return out


def _hbm_in_use() -> Dict[str, int]:
    """{device label: bytes_in_use} across ALL local devices (empty on
    backends without memory_stats — the CPU test mesh)."""
    out: Dict[str, int] = {}
    for dev, stats in memory_by_device().items():
        in_use = stats.get("bytes_in_use")
        if in_use is not None:
            out[dev] = int(in_use)
    return out


def _drain_wall() -> float:
    """Dispatch a trivial program and block: the wall is the device-queue
    drain at this boundary.  0.0 when jax is not loaded or anything fails
    (a probe must never take a node down)."""
    if sys.modules.get("jax") is None or not enabled():
        return 0.0
    try:
        fn = _probe_fn()
        t0 = time.perf_counter()
        fn().block_until_ready()
        return time.perf_counter() - t0
    except Exception:
        return 0.0


def reset() -> None:
    """Per-run reset (workflow.main): drop prior results and warm + floor
    the drain probe so the first node doesn't book the probe's own compile
    as device time."""
    global _PROBE_FLOOR
    with _LOCK:
        _RESULTS.clear()
        _ACTIVE.clear()
    if sys.modules.get("jax") is not None and enabled():
        _drain_wall()  # compile once, outside any node
        floors = [_drain_wall() for _ in range(3)]
        _PROBE_FLOOR = min([f for f in floors if f > 0.0] or [0.0])


@contextmanager
def node_bracket(name: str, drain: Optional[bool] = None,
                 lane: Optional[str] = None,
                 devices: Optional[list] = None):
    """Attribute one scheduler node; results land in :func:`results`.

    ``drain`` controls the exit boundary probe.  The probe is a device
    SYNC: with concurrently executing nodes sharing one device queue it
    would wait out OTHER nodes' in-flight programs too — serializing the
    async overlap the concurrent executor exists to exploit and
    attributing foreign device time to whichever node finishes first.
    So the scheduler passes ``drain=False`` on concurrent runs unless
    ``ANOVOS_TPU_DEVPROF=full`` opts into boundary syncs;
    ``device_time_s`` is then 0 and the device share lands in whichever
    category actually waited on it (transfer for materializing fetches,
    host otherwise).  ``None`` (direct callers) means drain.

    Re-entrant per thread only in the degenerate sense that an inner
    bracket shadows the outer for its duration (scheduler nodes never
    nest in practice)."""
    if not enabled():
        yield None
        return
    if drain is None:
        drain = True
    frame = _Frame(name, lane=lane, devices=devices)
    prev = getattr(_TL, "frame", None)
    _TL.frame = frame
    with _LOCK:
        _ACTIVE[name] = frame
    try:
        yield frame
    finally:
        _TL.frame = prev
        try:
            out = frame.finish(drain=drain)
        except Exception:  # attribution must never fail the node
            logger.exception("devprof finish for node %r failed", name)
            out = None
        with _LOCK:
            _ACTIVE.pop(name, None)
            if out is not None:
                _RESULTS[name] = out
        if out is not None:
            _emit(name, out)


def _emit(name: str, out: dict) -> None:
    try:
        reg = get_metrics()
        for key, fam, help_ in (
            ("device_time_s", "devprof_device_seconds",
             "attributed device-queue drain per node"),
            ("dispatch_s", "devprof_dispatch_seconds",
             "host wall inside jitted-op calls per node"),
            ("transfer_s", "devprof_transfer_seconds",
             "host<->device materialization wall per node"),
            ("host_s", "devprof_host_seconds",
             "unattributed host wall per node"),
        ):
            reg.histogram(fam, help_).observe(out[key], node=name)
        from anovos_tpu.obs.tracing import get_tracer

        get_tracer().instant(
            f"devprof:{name}", cat="devprof",
            device_time_s=out["device_time_s"], dispatch_s=out["dispatch_s"],
            transfer_s=out["transfer_s"], host_s=out["host_s"],
            h2d_bytes=out["h2d_bytes"], d2h_bytes=out["d2h_bytes"],
        )
    except Exception:
        logger.exception("devprof emit for node %r failed", name)


@contextmanager
def dispatch_bracket(label: str, phase: str = "execute"):
    """Wrap one (typically jitted) op call — entered by ``obs.timed``.

    Only the outermost bracket on a thread books dispatch wall, and only
    for ``execute``-phase calls (first-call walls are host-side
    trace+compile); every bracket still stamps ``last_op`` so postmortem
    dumps name the op a node died in."""
    depth = getattr(_TL, "dispatch_depth", 0)
    _TL.dispatch_depth = depth + 1
    t0 = time.perf_counter()
    try:
        yield
    finally:
        dt = time.perf_counter() - t0
        _TL.dispatch_depth = depth
        frame = getattr(_TL, "frame", None)
        if frame is not None:
            if depth == 0 and phase == "execute":
                frame.add_dispatch(dt, label)
            else:
                with frame._lock:
                    frame.last_op = label


def record_transfer(direction: str, nbytes: int, seconds: float,
                    label: str = "", shards: int = 0) -> None:
    """Book one host↔device movement (``direction`` ∈ {"h2d", "d2h"}).
    ``shards``: into how many device shards the bytes were split (the open
    ``ingest/h2d`` span counts them; the frames and counters do not).

    Honors the off switch like every bracket — direct callers
    (``data_ingest._concat_columns``) must go quiet under
    ``ANOVOS_TPU_DEVPROF=0`` too, or a disabled run reports a partial,
    inconsistent transfer tally."""
    if direction not in ("h2d", "d2h", "d2d"):
        raise ValueError(f"direction must be h2d|d2h|d2d, got {direction!r}")
    if not enabled():
        return
    get_metrics().counter(
        f"transfer_{direction}_bytes_total",
        "bytes moved %s at Table materialization/placement boundaries"
        % {"h2d": "host->device", "d2h": "device->host",
           "d2d": "device->device (placement re-lays)"}[direction],
    ).inc(nbytes)
    frame = getattr(_TL, "frame", None)
    if frame is None:
        # outside every node: ingest books its bytes and its enqueue seconds
        # (device_put is async) on the ``ingest/h2d`` span open on this
        # thread.  A writer-pool thread materializing a queued artifact has
        # neither frame nor span: without plumbing the submitting node
        # through the queue it feeds the global counters only (the per-node
        # split stays a lower bound)
        from anovos_tpu.obs.tracing import get_tracer

        sp = get_tracer().current()
        if sp is not None and sp.name == "ingest/h2d":
            sp.add(bytes=nbytes, enqueue_s=seconds, shards=shards)
        return
    frame.add_transfer(direction, nbytes, seconds, label or direction)


@contextmanager
def transfer_bracket(direction: str, nbytes: int, label: str = "", shards: int = 0):
    """Time + book one materialization boundary."""
    if not enabled():
        yield
        return
    t0 = time.perf_counter()
    try:
        yield
    finally:
        try:
            record_transfer(direction, int(nbytes),
                            time.perf_counter() - t0, label, shards)
        except Exception:
            logger.exception("devprof transfer record failed")


def record_decode(seconds: float, nbytes: int, label: str = "decode",
                  frame=None) -> None:
    """Book one part-file decode (wall + input bytes).

    ``frame`` lets prefetch-pool WORKER threads attribute their decode to
    the CONSUMING node's frame (captured via :func:`current_frame` when
    the pool was created — the pool threads themselves carry no
    thread-local frame, the async-writer situation all over again).
    Honors the ``ANOVOS_TPU_DEVPROF=0`` off switch like every bracket."""
    if not enabled():
        return
    try:
        reg = get_metrics()
        reg.counter(
            "stream_decode_seconds_total",
            "host wall spent decoding part files in streaming passes",
        ).inc(seconds)
        reg.counter(
            "stream_decode_bytes_total",
            "part-file bytes decoded in streaming passes",
        ).inc(nbytes)
    except Exception:
        logger.exception("devprof decode record failed")
    fr = frame if frame is not None else getattr(_TL, "frame", None)
    if fr is not None:
        fr.add_decode(seconds, int(nbytes), label)


def current_frame():
    """The in-flight devprof frame of THIS thread (None outside a node
    bracket or with devprof disabled).  Prefetch pools capture it at
    construction so worker-thread decode books to the consuming node."""
    return getattr(_TL, "frame", None)


@contextmanager
def under(frame):
    """Run the body as work handed over by the thread whose
    :func:`current_frame` was ``frame``: on a thread with no frame of its own
    (a host-pool thread) what the body books (a fetch's ``d2h`` record) lands
    on the node that handed the work over, as it would have on that node's
    thread.  ``Tracer.under`` does the same for spans."""
    if frame is None or getattr(_TL, "frame", None) is not None:
        yield
        return
    _TL.frame = frame
    try:
        yield
    finally:
        _TL.frame = None


def results() -> Dict[str, dict]:
    """Finished per-node attributions of the current run (name → dict)."""
    with _LOCK:
        return {k: dict(v) for k, v in sorted(_RESULTS.items())}


def active_frames() -> Dict[str, dict]:
    """In-flight nodes' live attribution (flight-recorder postmortems)."""
    with _LOCK:
        frames = dict(_ACTIVE)
    out = {}
    for name, fr in frames.items():
        try:
            out[name] = fr.snapshot()
        except Exception:
            out[name] = {}
    return out
