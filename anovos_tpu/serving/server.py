"""The online feature server: micro-batching request loop over a bundle.

Request lifecycle:

1. **validate + coerce** (client thread, before anything is enqueued):
   the payload must carry exactly the bundle's required input columns
   with equal-length value lists; numeric columns accept numbers/null,
   categorical columns strings/null, timestamps ISO strings.  Schema
   drift (unknown/missing columns), wrong dtypes, and hostile values
   (±inf and finite floats beyond the f32 range — the PR 10 sanitize
   policy's overflow class, applied at the request boundary) all return
   a STRUCTURED per-request error ("quarantine response") immediately:
   a hostile request can neither poison a shared micro-batch nor crash
   the server, and every rejection books
   ``serve_requests_quarantined_total{reason}``.
2. **micro-batch**: accepted requests queue; the batcher thread drains
   up to ``ANOVOS_SERVE_MAX_BATCH`` rows or ``ANOVOS_SERVE_BATCH_WINDOW_MS``
   of accumulation, concatenates the frames, and pads the batch onto the
   serving row buckets (``ApplyProgram.pad_frame``) so every width hits
   a pre-compiled executable.
3. **apply**: one fused pass through the bundle's transformer chain,
   wrapped in a tracer span and a ``devprof.node_bracket`` (dispatch
   attribution on the apply path; the chaos site ``serve:apply`` sits
   inside the bracket for the ``serve-fault`` scenario).  A failed apply
   retries once — an injected transient must not fail real requests —
   and a second failure is FATAL for the batch: a flight-recorder
   postmortem (trigger ``serve_fatal``) is dumped synchronously, every
   request in the batch gets a structured error, and the loop keeps
   serving subsequent batches.
4. **respond**: per-request row slices serialize back to JSON-able
   columnar payloads; per-request wall books into
   ``serve_request_seconds`` and the bounded latency ring that ``stats()``
   summarizes as p50/p99/QPS.
"""

from __future__ import annotations

import logging
import os
import queue
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

import numpy as np
import pandas as pd

from anovos_tpu.obs.telemetry import RollingWindow
from anovos_tpu.serving.program import ApplyProgram

logger = logging.getLogger("anovos_tpu.serving.server")

__all__ = ["FeatureServer", "coerce_payload", "frame_to_payload"]

# the device numeric plane is f32 (data_ingest.guard's sanitize contract):
# any finite float beyond this becomes ±inf on upload
_F32_MAX = float(np.finfo(np.float32).max)
_LATENCY_RING = 8192


def _error(code: str, detail: str, **extra) -> dict:
    return {"error": {"code": code, "detail": detail, **extra}}


def coerce_payload(input_columns: List[dict], payload: dict,
                   max_rows: int) -> Tuple[Optional[pd.DataFrame], Optional[dict]]:
    """Validate one request payload against the bundle schema and coerce
    it to the canonical frame dtypes (numeric→float64, cat→object str,
    ts→datetime64).  Returns ``(frame, None)`` or ``(None, error)`` —
    the error dict IS the response (a per-request quarantine, mirroring
    the PR 10 ingest policy at this boundary)."""
    if not isinstance(payload, dict) or not isinstance(payload.get("columns"), dict):
        return None, _error("bad_request",
                            'payload must be {"columns": {name: [values...]}}')
    cols = payload["columns"]
    schema = {c["name"]: c for c in input_columns}
    unknown = sorted(set(cols) - set(schema))
    missing = sorted(set(schema) - set(cols))
    if unknown or missing:
        return None, _error(
            "schema_drift",
            "request columns do not match the bundle schema",
            unknown_columns=unknown, missing_columns=missing)
    lengths = {len(v) for v in cols.values() if isinstance(v, (list, tuple))}
    if any(not isinstance(v, (list, tuple)) for v in cols.values()):
        return None, _error("bad_request", "column values must be lists")
    if len(lengths) != 1:
        return None, _error("bad_shape",
                            f"column lengths disagree: {sorted(lengths)}")
    n = lengths.pop()
    if not (1 <= n <= max_rows):
        return None, _error("bad_shape",
                            f"rows must be 1..{max_rows}, got {n}")
    data: Dict[str, object] = {}
    hostile: Dict[str, dict] = {}
    for name in (c["name"] for c in input_columns):
        spec = schema[name]
        vals = cols[name]
        if spec["kind"] == "cat":
            bad = [v for v in vals if v is not None and not isinstance(v, str)]
            if bad:
                return None, _error(
                    "wrong_dtype",
                    f"column {name!r} is categorical: values must be "
                    f"strings or null (got e.g. {bad[0]!r})", column=name)
            data[name] = np.array(
                [v if v is not None else None for v in vals], dtype=object)
        elif spec["kind"] == "ts":
            # ISO strings or null ONLY — pd.to_datetime would otherwise
            # silently read bare numbers as epoch-nanosecond instants
            bad = [v for v in vals if v is not None and not isinstance(v, str)]
            if bad:
                return None, _error(
                    "wrong_dtype",
                    f"column {name!r} is a timestamp: values must be ISO "
                    f"strings or null (got e.g. {bad[0]!r})", column=name)
            try:
                data[name] = pd.to_datetime(pd.Series(vals), errors="raise",
                                            utc=False).to_numpy()
            except Exception as e:
                return None, _error(
                    "wrong_dtype",
                    f"column {name!r} is a timestamp: {e}", column=name)
        else:
            bad = [v for v in vals
                   if v is not None
                   and not (isinstance(v, (int, float)) and not isinstance(v, bool))]
            if bad:
                return None, _error(
                    "wrong_dtype",
                    f"column {name!r} is numeric: values must be numbers "
                    f"or null (got e.g. {bad[0]!r})", column=name)
            arr = np.array([np.nan if v is None else float(v) for v in vals],
                           dtype=np.float64)
            pos = int((arr == np.inf).sum())
            neg = int((arr == -np.inf).sum())
            over = int((np.isfinite(arr) & (np.abs(arr) > _F32_MAX)).sum())
            if pos or neg or over:
                hostile[name] = {"posinf": pos, "neginf": neg, "overflow": over}
            data[name] = arr
    if hostile:
        # the sanitize policy at the request boundary: a value the decode
        # guard would null/clip in batch ingest is a per-request refusal
        # here — the caller is told exactly what was hostile, the batch
        # queue never sees the rows
        return None, _error(
            "hostile_values",
            "±inf / f32-overflow values refused at the request boundary "
            "(data_ingest.guard sanitize policy)", columns=hostile)
    return pd.DataFrame(data), None


def frame_to_payload(df: pd.DataFrame) -> Dict[str, list]:
    """Feature frame → JSON-able columnar payload (NaN/NaT → null)."""
    out: Dict[str, list] = {}
    for name in df.columns:
        s = df[name]
        # pandas' own predicates: pandas 3 string columns carry StringDtype,
        # which numpy's issubdtype cannot interpret
        if pd.api.types.is_datetime64_any_dtype(s.dtype):
            out[name] = [None if pd.isna(v) else pd.Timestamp(v).isoformat()
                         for v in s]
        elif s.dtype == object or pd.api.types.is_string_dtype(s.dtype):
            out[name] = [None if pd.isna(v) else str(v) for v in s]
        elif pd.api.types.is_integer_dtype(s.dtype):
            out[name] = [int(v) for v in s]
        else:
            out[name] = [None if not np.isfinite(v) else float(v) for v in s]
    return out


class _Pending:
    __slots__ = ("frame", "rows", "event", "response", "t0", "booked")

    def __init__(self, frame: pd.DataFrame, t0: float):
        self.frame = frame
        self.rows = len(frame)
        self.event = threading.Event()
        self.response: Optional[dict] = None
        self.t0 = t0
        # one-request-one-SLO-sample: whichever side (client timeout or
        # batcher completion) claims this flag FIRST — under the server
        # lock — books the request; the other side must not
        self.booked = False


class FeatureServer:
    """Threaded micro-batching server over one :class:`ApplyProgram`.

    In-process transport: clients call :meth:`serve` from their own
    threads (the CLI's concurrent-client smoke load and the chaos gate
    both drive it this way); the batching/apply loop runs on
    one background thread so device dispatch stays single-lane and
    devprof's drain attribution is meaningful."""

    def __init__(self, program: ApplyProgram,
                 window_ms: Optional[float] = None,
                 max_batch: Optional[int] = None,
                 obs_dir: Optional[str] = None):
        self.program = program
        self.window_s = float(
            window_ms if window_ms is not None
            else os.environ.get("ANOVOS_SERVE_BATCH_WINDOW_MS", "5")) / 1000.0
        self.max_batch = int(
            max_batch if max_batch is not None
            else os.environ.get("ANOVOS_SERVE_MAX_BATCH", "256"))
        self.obs_dir = obs_dir
        self._queue: "queue.Queue[_Pending]" = queue.Queue()
        self._carry: Optional[_Pending] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._latencies = deque(maxlen=_LATENCY_RING)
        self._lock = threading.Lock()
        self._served = 0
        self._quarantined = 0
        self._failed = 0
        self._t_started: Optional[float] = None
        self.cold_start_s: Optional[float] = None
        # live telemetry plane: rolling SLO windows over the request
        # stream (p50/p99/QPS/error-budget burn at scrape time, not
        # end-of-run aggregates), the in-flight batch view /statusz
        # reads, and the last fatal batch /healthz names
        self.rolling = RollingWindow()
        self._inflight_batch: Optional[dict] = None
        self._last_fatal: Optional[dict] = None
        self._telemetry = None
        self._rotator = None

    # -- lifecycle ----------------------------------------------------------
    def start(self, warm: bool = True) -> "FeatureServer":
        """Arm obs, AOT-compile the apply path per bucket, start the loop.

        ``cold_start_s`` is the measured server-start wall: warm-up
        (bounded by the persistent XLA compile cache) through the first
        live response."""
        t0 = time.perf_counter()
        if self.obs_dir:
            from anovos_tpu.obs import flight

            if not flight.enabled():
                flight.configure(os.path.join(self.obs_dir, "obs"))
        # live telemetry plane: join/start the embedded HTTP listener
        # (ANOVOS_TPU_TELEMETRY; off = None, zero threads) and register
        # the serving provider either way — a workflow-owned listener can
        # then scrape this server too
        from anovos_tpu.obs import telemetry
        from anovos_tpu.obs.tracing import maybe_rotator

        self._telemetry = telemetry.acquire(context="serving")
        telemetry.register_provider(
            "serving", statusz=self._statusz_fragment,
            metrics=self._telemetry_gauges, health=self._health_fragment)
        # trace segment rotation (off by default): a long-lived server's
        # apply spans rotate to disk instead of silently aging out of the
        # tracer ring
        if self.obs_dir:
            self._rotator = maybe_rotator(self.obs_dir)
        if warm:
            self.program.warm(self.max_batch)
        self._stop.clear()
        self._t_started = time.monotonic()
        self._thread = threading.Thread(
            target=self._loop, name="anovos-serve-batcher", daemon=True)
        self._thread.start()
        if warm:
            # the cold-start contract is start → FIRST RESPONSE: drive one
            # live request through the whole queue/batch/apply/serialize path
            first = self.serve({"columns": frame_to_payload(
                self.program.synthetic_frame(1))})
            if "error" in first:
                raise RuntimeError(f"serving warm probe failed: {first['error']}")
        self.cold_start_s = round(time.perf_counter() - t0, 3)
        return self

    def close(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None
        from anovos_tpu.obs import telemetry

        telemetry.unregister_provider("serving")
        telemetry.release(self._telemetry)
        self._telemetry = None
        if self._rotator is not None:
            self._rotator.close()
            self._rotator = None

    # -- client API ---------------------------------------------------------
    def serve(self, payload: dict, timeout_s: float = 120.0) -> dict:
        """One blocking request: validate, enqueue, await the batch."""
        from anovos_tpu.obs import get_metrics

        t0 = time.perf_counter()
        frame, err = coerce_payload(self.program.input_columns, payload,
                                    self.max_batch)
        if err is not None:
            with self._lock:
                self._quarantined += 1
            get_metrics().counter(
                "serve_requests_quarantined_total",
                "requests refused at the serving boundary with a structured "
                "per-request error",
            ).inc(reason=err["error"]["code"])
            return err
        pending = _Pending(frame, t0)
        self._queue.put(pending)
        if not pending.event.wait(timeout_s):
            # a timeout is a client-visible FAILURE: it must burn error
            # budget in the rolling windows, or a wedged apply that times
            # every request out would scrape as a perfectly healthy
            # server.  The booking claim is decided UNDER the lock so the
            # batcher completing at the same instant cannot also book
            # this request — one request, one SLO sample.
            with self._lock:
                claimed = not pending.booked
                pending.booked = True
            if claimed:
                elapsed = time.perf_counter() - t0
                # timeouts COUNT toward the latency tail: a wedged apply
                # that strands every client at timeout_s IS the p99, and
                # the serve-fault bounded-p99 gate must see it
                with self._lock:
                    self._latencies.append(elapsed)
                self.rolling.observe(elapsed, ok=False)
                get_metrics().histogram(
                    "serve_request_seconds",
                    "request wall from validation to response"
                ).observe(elapsed)
                get_metrics().counter(
                    "serve_requests_timeout_total",
                    "requests that timed out awaiting their batch").inc()
                return _error("timeout", f"no response within {timeout_s}s")
            # the batch finished in the same instant: its response is valid
            pending.event.wait(5.0)
            if pending.response is not None:
                return pending.response
            return _error("timeout", f"no response within {timeout_s}s")
        return pending.response  # type: ignore[return-value]

    # -- batching loop ------------------------------------------------------
    def _next_batch(self) -> List[_Pending]:
        batch: List[_Pending] = []
        rows = 0
        if self._carry is not None:
            batch.append(self._carry)
            rows = self._carry.rows
            self._carry = None
        while not batch:
            try:
                first = self._queue.get(timeout=0.05)
            except queue.Empty:
                if self._stop.is_set():
                    return []
                continue
            batch.append(first)
            rows = first.rows
        deadline = time.monotonic() + self.window_s
        while rows < self.max_batch:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            try:
                nxt = self._queue.get(timeout=remaining)
            except queue.Empty:
                break
            if rows + nxt.rows > self.max_batch:
                self._carry = nxt  # heads the next batch — never dropped
                break
            batch.append(nxt)
            rows += nxt.rows
        return batch

    def _loop(self) -> None:
        while not (self._stop.is_set() and self._queue.empty()
                   and self._carry is None):
            batch = self._next_batch()
            if not batch:
                if self._stop.is_set():
                    return
                continue
            try:
                self._process(batch)
            except Exception:  # the loop must outlive any batch
                logger.exception("serving batch processing failed")
                for p in batch:
                    if p.response is None:
                        p.response = _error("internal", "batch processing failed")
                        p.event.set()

    def _process(self, batch: List[_Pending]) -> None:
        from anovos_tpu.obs import devprof, flight, get_metrics, get_tracer
        from anovos_tpu.resilience.chaos import chaos_point

        reg = get_metrics()
        frames = [p.frame for p in batch]
        n = sum(p.rows for p in batch)
        big = pd.concat(frames, ignore_index=True) if len(frames) > 1 else frames[0]
        bucket = self.program.bucket_rows(n, self.max_batch)
        padded = self.program.pad_frame(big, bucket)
        with self._lock:
            self._inflight_batch = {"rows": n, "requests": len(batch),
                                    "bucket": bucket,
                                    "since_unix": round(time.time(), 3)}
        out: Optional[pd.DataFrame] = None
        last: Optional[BaseException] = None
        try:
            for attempt in (1, 2):
                try:
                    with get_tracer().span("serving/apply", cat="serve",
                                           rows=n, bucket=bucket,
                                           requests=len(batch), attempt=attempt), \
                            devprof.node_bracket("serving/apply"):
                        chaos_point("serve:apply")
                        out = self.program.apply_frame(padded)
                    break
                except Exception as e:
                    last = e
                    logger.warning(
                        "serving apply attempt %d failed (%s: %s) — %s",
                        attempt, type(e).__name__, e,
                        "retrying" if attempt == 1 else "batch is fatal")
        finally:
            with self._lock:
                self._inflight_batch = None
        reg.counter("serve_batches_total",
                    "micro-batches dispatched through the apply program"
                    ).inc()
        reg.histogram("serve_batch_rows",
                      "rows per dispatched micro-batch",
                      buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024),
                      ).observe(n)
        if out is None:
            # FATAL for this batch: postmortem first (synchronous, crash-
            # safe), then structured errors — the server keeps serving
            with self._lock:
                self._failed += 1
                # /healthz names the failed batch until the server dies:
                # a fatal apply is a degraded serving plane even after
                # the loop moves on
                self._last_fatal = {
                    "rows": n, "requests": len(batch),
                    "error": f"{type(last).__name__}: {str(last)[:300]}",
                    "t_unix": round(time.time(), 3),
                }
            reg.counter(
                "serve_batches_failed_total",
                "micro-batches whose apply failed after retry (every request "
                "got a structured error; a flight postmortem was dumped)",
            ).inc()
            flight.dump(
                "serve_fatal", node="serving/apply",
                extra={"error": f"{type(last).__name__}: {last}",
                       "batch_rows": n, "requests": len(batch)})
            now = time.perf_counter()
            for p in batch:
                p.response = _error(
                    "apply_failed",
                    f"feature apply failed after retry: "
                    f"{type(last).__name__}: {str(last)[:300]}")
                # failed requests COUNT toward the latency tail: a wedged
                # apply that burns 60s before erroring is p99, and the
                # serve-fault chaos gate's bounded-p99 check reads it
                # here.  A request whose client already timed out (and
                # claimed the booking) is not sampled twice.
                with self._lock:
                    claimed = not p.booked
                    p.booked = True
                    if claimed:
                        self._latencies.append(now - p.t0)
                if claimed:
                    self.rolling.observe(now - p.t0, ok=False)
                    reg.histogram("serve_request_seconds",
                                  "request wall from validation to response"
                                  ).observe(now - p.t0)
                p.event.set()
            return
        offset = 0
        now = time.perf_counter()
        for p in batch:
            part = out.iloc[offset:offset + p.rows].reset_index(drop=True)
            offset += p.rows
            p.response = {"rows": p.rows, "columns": frame_to_payload(part)}
            latency = now - p.t0
            with self._lock:
                claimed = not p.booked
                p.booked = True
                if claimed:
                    self._served += 1
                    self._latencies.append(latency)
            if claimed:
                self.rolling.observe(latency, ok=True)
                reg.histogram("serve_request_seconds",
                              "request wall from validation to response"
                              ).observe(latency)
            p.event.set()

    # -- telemetry provider callbacks (obs.telemetry; scrape thread) --------
    def _statusz_fragment(self) -> dict:
        """The serving section of ``/statusz``: end-of-run stats plus the
        live rolling windows, the in-flight batch and the last fatal."""
        with self._lock:
            inflight = dict(self._inflight_batch) if self._inflight_batch else None
            last_fatal = dict(self._last_fatal) if self._last_fatal else None
        return {
            **self.stats(),
            "rolling": self.rolling.summary(),
            "inflight_batch": inflight,
            "last_fatal": last_fatal,
            "queue_depth": self._queue.qsize(),
        }

    def _telemetry_gauges(self, reg) -> None:
        """The ``/metrics`` live serving families: rolling-window
        p50/p99/QPS/error-budget burn (sliding over the latency ring, not
        end-of-run aggregates) + queue depth, set at scrape time."""
        for window, s in self.rolling.summary().items():
            # an EMPTY window removes its latency series rather than
            # leaving the last burst's p99 scraping as frozen-fresh for
            # hours (qps/burn honestly read 0 and stay)
            if s["p50_ms"] is not None:
                reg.gauge("serve_rolling_p50_ms",
                          "rolling-window p50 request latency"
                          ).set(s["p50_ms"], window=window)
                reg.gauge("serve_rolling_p99_ms",
                          "rolling-window p99 request latency"
                          ).set(s["p99_ms"], window=window)
            else:
                for fam in ("serve_rolling_p50_ms", "serve_rolling_p99_ms"):
                    inst = reg.peek(fam)  # never MINT a family on cleanup
                    if inst is not None:
                        inst.remove(window=window)
            reg.gauge("serve_rolling_qps",
                      "rolling-window sustained requests per second"
                      ).set(s["qps"], window=window)
            reg.gauge("serve_rolling_error_budget_burn",
                      "rolling-window error rate over the SLO error budget "
                      "(1.0 = burning exactly at budget)"
                      ).set(s["error_budget_burn"], window=window)
        reg.gauge("serve_queue_depth",
                  "requests accepted but not yet batched"
                  ).set(float(self._queue.qsize()))

    def _health_fragment(self):
        """``/healthz`` fold: a fatal micro-batch degrades the serving
        plane, with the batch named in the reason."""
        with self._lock:
            lf = dict(self._last_fatal) if self._last_fatal else None
        if lf is None:
            return ("ok", [])
        return ("degraded", [
            f"serving: micro-batch of {lf['rows']} row(s) "
            f"({lf['requests']} request(s)) failed after retry: {lf['error']}"
        ])

    # -- stats --------------------------------------------------------------
    def stats(self) -> dict:
        with self._lock:
            lat = sorted(self._latencies)
            served, quarantined, failed = self._served, self._quarantined, self._failed
        elapsed = (time.monotonic() - self._t_started) if self._t_started else 0.0

        def pct(p: float) -> Optional[float]:
            if not lat:
                return None
            return round(lat[min(int(p * (len(lat) - 1)), len(lat) - 1)] * 1000, 3)

        return {
            "served": served,
            "quarantined": quarantined,
            "failed_batches": failed,
            "p50_ms": pct(0.50),
            "p99_ms": pct(0.99),
            "qps": round(served / elapsed, 2) if elapsed > 0 else None,
            "cold_start_s": self.cold_start_s,
            "window_ms": self.window_s * 1000,
            "max_batch": self.max_batch,
        }
