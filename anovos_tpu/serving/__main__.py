"""CLI for the serving subsystem.

``python -m anovos_tpu.serving export --data <dataset> --chain <yaml> \
      --cache <dir>``
    Fit the chain on the dataset, commit the versioned bundle into the
    CAS store at ``--cache``, print ``{"bundle_version": ...}``.

``python -m anovos_tpu.serving smoke [--rows N] [--clients C] \
      [--requests R] [--json]``
    The end-to-end proof: fit + export the demo bundle, reload it from
    the store, boot a :class:`FeatureServer` (AOT warm-up against the
    persistent XLA compile cache under ``--cache``), run a concurrent-
    client mixed-width load, verify a parity sample byte-identically
    against the batch apply, and print one JSON line with
    ``serve_qps`` / ``serve_p50_ms`` / ``serve_p99_ms`` /
    ``serve_cold_start_s``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

_T0 = time.perf_counter()  # process-start anchor for the cold-start record

# mixed request widths for the smoke load: single rows through half-batches
_SMOKE_WIDTHS = (1, 3, 8, 17, 32)


def _emit(doc: dict) -> None:
    sys.stdout.write(json.dumps(doc, sort_keys=True) + "\n")
    sys.stdout.flush()


def _cmd_export(ns) -> int:
    import yaml

    os.environ.setdefault("ANOVOS_TPU_CACHE", ns.cache)
    from anovos_tpu.data_ingest import data_ingest
    from anovos_tpu.serving.bundle import fit_bundle, save_bundle
    from anovos_tpu.shared.runtime import init_runtime

    init_runtime()
    with open(ns.chain) as f:
        chain_spec = yaml.safe_load(f)
    idf = data_ingest.read_dataset(ns.data, ns.file_type)
    bundle = fit_bundle(idf, chain_spec, source=os.path.abspath(ns.data))
    version = save_bundle(bundle, ns.cache)
    _emit({"bundle_version": version, "cache_root": os.path.abspath(ns.cache),
           "stages": [s["family"] for s in bundle.chain],
           "input_columns": bundle.input_names})
    return 0


def _cmd_smoke(ns) -> int:
    workdir = ns.workdir or tempfile.mkdtemp(prefix="anovos_serve_smoke_")
    cache = ns.cache or os.path.join(workdir, "cache")
    os.environ.setdefault("ANOVOS_TPU_CACHE", cache)

    from anovos_tpu.serving.bundle import load_bundle
    from anovos_tpu.serving.demo import build_demo_bundle, demo_frame
    from anovos_tpu.serving.program import ApplyProgram
    from anovos_tpu.serving.server import (
        FeatureServer, coerce_payload, frame_to_payload)
    from anovos_tpu.shared.runtime import init_runtime

    init_runtime()
    import jax

    version = build_demo_bundle(cache, rows=ns.rows)

    # ---- server boot: the measured cold start -----------------------------
    t_boot = time.perf_counter()
    bundle = load_bundle(cache, version)
    program = ApplyProgram(bundle)
    server = FeatureServer(program, obs_dir=workdir)
    server.start(warm=True)
    cold_start_s = round(time.perf_counter() - t_boot, 3)

    # ---- concurrent-client mixed-width load -------------------------------
    src = demo_frame(ns.rows, seed=11)[bundle.input_names]
    payloads = []
    off = 0
    for i in range(ns.clients * ns.requests):
        w = _SMOKE_WIDTHS[i % len(_SMOKE_WIDTHS)]
        if off + w > len(src):
            off = 0
        payloads.append({"columns": frame_to_payload(src.iloc[off:off + w])})
        off += w
    results: list = [None] * len(payloads)

    def client(cid: int) -> None:
        for r in range(ns.requests):
            i = cid * ns.requests + r
            results[i] = server.serve(payloads[i])

    t_load = time.perf_counter()
    threads = [threading.Thread(target=client, args=(c,))
               for c in range(ns.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    load_wall = time.perf_counter() - t_load

    errors = [r for r in results if r is None or "error" in r]
    # parity sample: one request per width, byte-compared against the
    # batch apply of exactly those rows
    parity_ok = True
    for i in range(min(len(payloads), len(_SMOKE_WIDTHS))):
        frame, err = coerce_payload(program.input_columns, payloads[i],
                                    server.max_batch)
        assert err is None
        ref = frame_to_payload(program.apply_frame(frame))
        if results[i] is None or results[i].get("columns") != ref:
            parity_ok = False

    # the emitted serve_* stats describe THE ORIGINAL LOAD LEG only —
    # captured before the telemetry legs push their extra contended load
    # through the same server
    stats = server.stats()

    # ---- telemetry overhead leg (--telemetry) -----------------------------
    # Leg A above ran WARM with the telemetry plane off; leg B repeats the
    # exact same load with the embedded HTTP server up and two scraper
    # threads hammering /metrics + /healthz throughout — the A/B delta in
    # one process is the telemetry overhead (no process-boot or compile
    # variance), and the scrape latencies give scrape_p99_ms under
    # genuine concurrent-client load.
    telemetry_fields: dict = {}
    if getattr(ns, "telemetry", False):
        telemetry_fields = _telemetry_leg(server, payloads, ns)
    server.close()

    _emit({
        **telemetry_fields,
        "serve_qps": round(len(payloads) / load_wall, 2),
        "serve_p50_ms": stats["p50_ms"],
        "serve_p99_ms": stats["p99_ms"],
        "serve_cold_start_s": cold_start_s,
        "serve_requests": len(payloads),
        "serve_clients": ns.clients,
        "serve_errors": len(errors),
        "serve_parity_ok": parity_ok,
        "serve_warm": program.warm_stats,
        "bundle_version": version,
        "backend": jax.default_backend(),
        "proc_wall_s": round(time.perf_counter() - _T0, 3),
    })
    return 0 if (parity_ok and not errors) else 1


# leg-B scrape cadence: one scrape per client per interval.  0.25s is
# 20-60× FASTER than a production Prometheus cadence (5-15s) — the
# overhead number is measured under deliberately aggressive polling, and
# the real-world figure is proportionally smaller still.
_SCRAPE_INTERVAL_S = 0.25


# repeats of the payload list per overhead leg: a sub-1% wall delta needs
# multi-second legs, or box noise swamps the measurement
_OVERHEAD_REPS = 4


def _telemetry_leg(server, payloads, ns) -> dict:
    """The telemetry-overhead legs of the smoke, measured back to back:

    * leg A′ — the warm load ×``_OVERHEAD_REPS`` with the telemetry
      plane OFF (no listener thread exists);
    * leg B — the identical load with the listener live and two scrape
      clients polling ``/metrics`` + ``/healthz`` every
      ``_SCRAPE_INTERVAL_S`` over keep-alive connections; the A′/B wall
      delta is ``telemetry_overhead_pct``;
    * leg C — the load once more with SATURATING back-to-back scrapers,
      purely to measure the scrape latency tail under concurrent serving
      load (``scrape_p99_ms``); its serve wall is deliberately not part
      of the overhead figure.

    Never raises — a telemetry failure lands as a field, not a dead
    smoke."""
    import http.client
    import json as _json

    from anovos_tpu.obs import telemetry

    scrape_lat: list = []
    scrape_failures = [0]
    srv = None

    def scrape_loop(stop: threading.Event, interval: float, offset: int) -> None:
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=10)
        i = offset
        while not stop.is_set():
            path = "/metrics" if i % 2 == 0 else "/healthz"
            t0 = time.perf_counter()
            try:
                conn.request("GET", path)
                conn.getresponse().read()
                scrape_lat.append(time.perf_counter() - t0)
            except Exception:
                scrape_failures[0] += 1
                conn.close()
                conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                                  timeout=10)
            i += 1
            if interval and stop.wait(interval):
                break
        conn.close()

    def load_leg(n_scrapers: int, interval: float, reps: int = 1):
        results_b: list = [None] * (len(payloads) * reps)

        def client_b(cid: int) -> None:
            for rep in range(reps):
                for r in range(ns.requests):
                    i = cid * ns.requests + r
                    results_b[rep * len(payloads) + i] = server.serve(payloads[i])

        stop = threading.Event()
        scrapers = [threading.Thread(target=scrape_loop,
                                     args=(stop, interval, k), daemon=True)
                    for k in range(n_scrapers)]
        for t in scrapers:
            t.start()
        t_on = time.perf_counter()
        clients = [threading.Thread(target=client_b, args=(c,))
                   for c in range(ns.clients)]
        for t in clients:
            t.start()
        for t in clients:
            t.join()
        wall = time.perf_counter() - t_on
        stop.set()
        for t in scrapers:
            t.join(timeout=10)
        errs = sum(1 for r in results_b if r is None or "error" in r)
        return wall, errs

    # leg A': the telemetry-off baseline, long enough to resolve <1%.
    # When ANOVOS_TPU_TELEMETRY is set in the environment the server's
    # own start() already acquired a listener, so the baseline is
    # "listener idle" rather than "off" — labeled honestly instead of
    # silently mis-claiming what the overhead figure compares.
    baseline_mode = "off" if telemetry.current() is None else "listener-idle"
    wall_off, errors_a = load_leg(n_scrapers=0, interval=0.0,
                                  reps=_OVERHEAD_REPS)
    srv = telemetry.acquire(context="serve-smoke", port=0)
    if srv is None:
        return {"telemetry_error": "telemetry listener failed to bind"}
    # leg B: the identical load, listener live, scrapes at the stated cadence
    wall_on, errors_b = load_leg(n_scrapers=2, interval=_SCRAPE_INTERVAL_S,
                                 reps=_OVERHEAD_REPS)
    cadence_scrapes = len(scrape_lat)
    # leg C: the scrape tail under saturating polling + full serve load
    scrape_lat.clear()
    _wall_c, errors_c = load_leg(n_scrapers=2, interval=0.0)

    healthz_status = None
    try:
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=10)
        conn.request("GET", "/healthz")
        healthz_status = _json.loads(
            conn.getresponse().read().decode()).get("status")
        conn.close()
    except Exception as e:
        scrape_failures[0] += 1
        healthz_status = f"scrape failed: {type(e).__name__}"
    telemetry.release(srv)

    lat = sorted(scrape_lat)
    pct = telemetry.RollingWindow._pct  # one percentile semantic repo-wide
    overhead_pct = max(0.0, (wall_on - wall_off) / wall_off * 100.0) \
        if wall_off > 0 else None
    return {
        "telemetry_overhead_pct": None if overhead_pct is None
        else round(overhead_pct, 3),
        "telemetry_baseline": baseline_mode,
        "serve_wall_off_s": round(wall_off, 4),
        "serve_wall_on_s": round(wall_on, 4),
        "scrape_interval_s": _SCRAPE_INTERVAL_S,
        "scrape_cadence_count": cadence_scrapes,
        "scrape_count": len(lat),
        "scrape_failures": scrape_failures[0],
        "scrape_p50_ms": pct(lat, 0.50),
        "scrape_p99_ms": pct(lat, 0.99),
        "healthz_status": healthz_status,
        "serve_errors_baseline_leg": errors_a,
        "serve_errors_with_telemetry": errors_b + errors_c,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m anovos_tpu.serving",
        description="versioned feature bundles + online feature serving")
    sub = ap.add_subparsers(dest="cmd", required=True)

    exp = sub.add_parser("export", help="fit a chain, commit a versioned bundle")
    exp.add_argument("--data", required=True, help="input dataset path")
    exp.add_argument("--file-type", default="parquet", dest="file_type")
    exp.add_argument("--chain", required=True,
                     help="YAML list of {name, config} transformer stages")
    exp.add_argument("--cache", required=True, help="CAS store root")
    exp.set_defaults(fn=_cmd_export)

    smk = sub.add_parser("smoke", help="end-to-end serve smoke load")
    smk.add_argument("--rows", type=int, default=2000)
    smk.add_argument("--clients", type=int, default=4)
    smk.add_argument("--requests", type=int, default=25,
                     help="requests per client")
    smk.add_argument("--cache", help="CAS/compile-cache root (default: fresh)")
    smk.add_argument("--workdir", help="obs/flight destination (default: tmp)")
    smk.add_argument("--json", action="store_true",
                     help="(always JSON; kept for symmetry)")
    smk.add_argument("--telemetry", action="store_true",
                     help="second warm load leg with the telemetry plane "
                          "live + scrapers attached; emits "
                          "telemetry_overhead_pct / scrape_p99_ms")
    smk.set_defaults(fn=_cmd_smoke)

    ns = ap.parse_args(argv)
    return ns.fn(ns)


if __name__ == "__main__":
    sys.exit(main())
