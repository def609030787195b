"""Chaos scenario gate: run a config under fault injection, verify recovery.

One command answers "do the resilience paths actually work on this
checkout": it runs a pipeline config twice — once clean (the golden
tree), once under a named ``ANOVOS_TPU_CHAOS`` scenario — and exits
nonzero unless

* the chaos run COMPLETES (no injected fault escaped recovery),
* its artifact tree is BYTE-IDENTICAL to the clean run's (``obs/``
  telemetry excluded — same exclusion as the cache golden tests), and
* the run manifest's ``resilience`` section records the expected
  recovery events (retries for ``exc``, a timeout escalation for
  ``hang``, a backend failover for ``wedge``).

Scenarios (sites target the default synthetic config's nodes; use
``--spec`` to inject into an arbitrary ``--config``):

* ``exc``   — one injected exception on a stats node → absorbed by the
  per-node retry policy.
* ``hang``  — one injected hang on a quality node → watchdog escalation
  interrupts the attempt, which re-executes under the raised bound
  (needs the concurrent executor; this scenario forces it and a small
  ``ANOVOS_TPU_NODE_TIMEOUT``).
* ``wedge`` — one simulated backend wedge on the drift node → in-run
  health probe + failover to CPU, node re-executes.
* ``full``  — all three in one run.
* ``hang-collective`` — a mesh-placed (collective) node hangs on EVERY
  attempt on a multi-device mesh (``--devices 8``): escalation interrupts
  the collective, exhausted retries end in abandonment that releases the
  rendezvous-lane lease, and the run completes DEGRADED within a bounded
  wall — no AllReduce deadlock, no wedged lane.  Parity is waived (the
  degraded section's artifacts are absent by design); instead the gate
  pins the exact degraded set, the bounded wall, and lane attribution in
  the flight dumps.
* ``serve-fault`` — the ONLINE-SERVING scenario (no workflow run): a
  feature server boots from a demo bundle, then a chaos-injected hang +
  double exception fire on the ``serve:apply`` site while clean and
  hostile requests interleave.  Gates: bounded p99, zero corrupted
  responses (every clean request's payload byte-identical to the batch
  apply of the same rows), structured per-request errors for the
  hostile payloads, a ``serve_fatal`` flight dump for the injected
  fatal, the server still serving afterwards — and a clean leg with
  byte parity and ZERO flight dumps.

Usage::

    python -m tools.chaos_run --scenario full [--workdir DIR] [--json]
    python -m tools.chaos_run --config cfg.yaml --spec 'exc@node:my_node'

Tier-1 wires the fast ``exc`` scenario (``tests/test_resilience.py``).
"""

from __future__ import annotations

import argparse
import copy
import fnmatch
import hashlib
import json
import os
import pathlib
import sys
import tempfile
import time

SCENARIOS = {
    "exc": "seed=7;exc@node:stats_generator/*",
    "hang": "seed=7;hang@node:quality_checker/*:secs=600",
    "wedge": "seed=7;wedge@node:drift_detector/*",
    "full": ("seed=7;exc@node:stats_generator/*;"
             "hang@node:quality_checker/*:secs=600;"
             "wedge@node:drift_detector/*"),
    # a COLLECTIVE (mesh-placed) node hangs on EVERY attempt on the multi-
    # device mesh: escalation must interrupt the collective, the exhausted
    # retries must end in abandonment that RELEASES the rendezvous-lane
    # lease, and the run must complete degraded within the watchdog bound
    # — no AllReduce deadlock, no wedged lane (run with --devices 8)
    "hang-collective": "seed=7;hang@node:drift_detector/*:secs=600:n=99",
    # the DATA-PLANE scenario: two of the four input part files fail to
    # decode on every attempt (one 'corrupt', one 'truncate' — distinct
    # error classes in the quarantine manifest) plus a slow read on a
    # third.  The ingest guard must retry, quarantine EXACTLY those two
    # parts with exact row counts, and the run must complete degraded
    # over the surviving rows; the clean leg must quarantine nothing.
    "corrupt-ingest": ("seed=7;corrupt@io:*part-00001.parquet:n=99;"
                       "truncate@io:*part-00002.parquet:n=99;"
                       "slowread@io:*part-00003.parquet:secs=0.2"),
    # the online-serving scenario: hang listed FIRST so the first batch
    # attempt sleeps 0.5s then hits the exception; the retry hits the
    # second exception → the batch is fatal (flight dump + structured
    # errors) while every later batch serves normally.
    "serve-fault": ("seed=7;hang@serve:apply:secs=0.5:n=1;"
                    "exc@serve:apply:n=2"),
    # the STREAMING-INGEST scenario (no workflow run): six of eight part
    # files become slow reads (0.6s each, both describe passes → 7.2s of
    # serial decode penalty).  The prefetch pool must ABSORB the slow
    # parts — workers sleep concurrently while the device crunches
    # already-staged chunks — so the chaos wall stays well under the
    # synchronous penalty, with byte-identical results.
    "slowread-stream": "seed=7;slowread@io:*part-0000[0-5].parquet:secs=0.6:n=99",
    # the CONTINUUM scenario (no chaos spec — the faults are PHYSICAL,
    # baked into the 30-day feed by tools/continuum_bench.build_feed_30d:
    # schema drift at day 15, garbage bytes at day 20, a distribution
    # shift at day 25).  Gates: the incremental day-by-day leg and a
    # from-scratch batch leg over the union produce byte-identical
    # artifact trees (obs/ excluded), the corrupt day is quarantined on
    # BOTH legs, and the shift day fires a drift alert carrying
    # flight-recorder context.
    "feed-30d": "",
}

# how many synthetic input part files a scenario's dataset is split into
SCENARIO_PARTS = {"corrupt-ingest": 4}

# exact quarantine manifest contents (basename -> rows_lost) a scenario
# must produce; the clean leg must always quarantine nothing (asserted
# for every scenario)
EXPECT_QUARANTINE = {
    "corrupt-ingest": {"part-00001.parquet": 375, "part-00002.parquet": 375},
}

# which manifest resilience counters must be > 0 per scenario
EXPECT = {
    "exc": ("retries",),
    "hang": ("timeout_escalations", "timeout_retries"),
    "wedge": ("failovers",),
    "full": ("retries", "timeout_escalations", "timeout_retries", "failovers"),
    "hang-collective": ("timeout_escalations", "timeout_retries"),
    "corrupt-ingest": (),  # recovery happens below the scheduler: the
                           # quarantine gate (EXPECT_QUARANTINE) is the check
}

# scenarios whose faults are DESIGNED to exhaust recovery: the named
# sections must degrade (and exactly these), artifact parity with the
# clean run is waived (the degraded section's artifacts are absent by
# construction), and the run must still finish within a bounded multiple
# of the clean wall — the "no wedged rendezvous lane" assertion
EXPECT_DEGRADED = {
    "hang-collective": ("drift_detector/drift_statistics",),
    # data-plane degradation: the two quarantined parts, named exactly
    "corrupt-ingest": ("ingest/part-00001.parquet", "ingest/part-00002.parquet"),
}

# scenarios that only make sense on a multi-device mesh (the lane
# machinery is inert on one device)
REQUIRE_MULTIDEV = {"hang-collective"}

# flight-recorder postmortems the chaos run must produce: (trigger, node
# glob) pairs per scenario.  A CLEAN run must produce none — asserted for
# every scenario (obs/ is excluded from the artifact tree hash, so the
# dumps never perturb byte parity; their ABSENCE on clean runs is the
# contract being gated here).
EXPECT_FLIGHT = {
    "exc": (),  # an absorbed retry is not a postmortem trigger
    "hang": (("timeout_escalation", "quality_checker/*"),),
    "wedge": (("backend_failover", "drift_detector/*"),),
    "full": (("timeout_escalation", "quality_checker/*"),
             ("backend_failover", "drift_detector/*")),
    "hang-collective": (("timeout_escalation", "drift_detector/*"),
                        ("node_abandoned", "drift_detector/*")),
    "corrupt-ingest": (),  # a quarantined part is degradation, not a postmortem
}


def flight_dumps(root) -> list:
    """(path, trigger, node) of every flight-recorder dump under ``root``."""
    import glob as _glob

    out = []
    for p in sorted(_glob.glob(os.path.join(root, "**", "flightrec_*.json"),
                               recursive=True)):
        try:
            with open(p) as f:
                doc = json.load(f)
            out.append((p, doc.get("trigger", ""), doc.get("node", "")))
        except (OSError, ValueError):
            out.append((p, "<unreadable>", ""))
    return out


def tree_hash(root) -> str:
    """sha256 over (relpath, bytes) of every artifact; obs/ telemetry is
    run-varying by design and excluded (same rule as tests/test_cache.py)."""
    h = hashlib.sha256()
    root = pathlib.Path(root)
    for p in sorted(root.rglob("*")):
        if p.is_file() and "obs" not in p.parts:
            h.update(str(p.relative_to(root)).encode())
            h.update(p.read_bytes())
    return h.hexdigest()


def synthetic_config(workdir: str, parts: int = 1) -> dict:
    """A small self-contained config whose node set covers every scenario
    site (stats fan-out, quality spine, drift).  ``parts`` splits the
    same 1500 rows into N part files (the corrupt-ingest scenario needs
    real part-file granularity to quarantine)."""
    import numpy as np
    import pandas as pd

    data = os.path.join(workdir, "data" if parts == 1 else f"data{parts}")
    if not os.path.isdir(data):
        os.makedirs(data)
        rng = np.random.default_rng(7)
        df = pd.DataFrame({
            "age": rng.normal(40, 9, 1500).round(1),
            "fnlwgt": rng.normal(2e5, 4e4, 1500).round(0),
            "workclass": rng.choice(["private", "gov", "self"], 1500),
            "income": rng.choice(["<=50K", ">50K"], 1500),
        })
        if parts == 1:
            df.to_parquet(os.path.join(data, "part-0.parquet"), index=False)
        else:
            for i, idx in enumerate(np.array_split(np.arange(len(df)), parts)):
                df.iloc[idx].to_parquet(
                    os.path.join(data, f"part-{i:05d}.parquet"), index=False)
    return {
        "input_dataset": {"read_dataset": {"file_path": data,
                                           "file_type": "parquet"}},
        "stats_generator": {
            "metric": ["global_summary", "measures_of_counts",
                       "measures_of_cardinality"],
            "metric_args": {"list_of_cols": "all", "drop_cols": []},
        },
        "quality_checker": {
            "duplicate_detection": {"list_of_cols": "all", "drop_cols": [],
                                    "treatment": True},
            "IDness_detection": {"list_of_cols": "all", "drop_cols": [],
                                 "treatment": True, "treatment_threshold": 0.9},
        },
        "drift_detector": {"drift_statistics": {
            "configs": {"list_of_cols": "all", "drop_cols": [],
                        "method_type": "PSI", "threshold": 0.1},
            "source_dataset": {"read_dataset": {"file_path": data,
                                                "file_type": "parquet"}},
        }},
        "report_preprocessing": {"master_path": "report_stats"},
        "write_main": {"file_path": "output", "file_type": "parquet",
                       "file_configs": {"mode": "overwrite"}},
    }


def _run_once(cfg: dict, rundir: str, chaos_spec: str, node_timeout: str) -> dict:
    """One workflow.main run in ``rundir``; returns the manifest."""
    from anovos_tpu import workflow
    from anovos_tpu.obs import load_manifest

    os.makedirs(rundir, exist_ok=True)
    prev_cwd = os.getcwd()
    prev_env = {k: os.environ.get(k) for k in
                ("ANOVOS_TPU_CHAOS", "ANOVOS_TPU_EXECUTOR",
                 "ANOVOS_TPU_NODE_TIMEOUT", "ANOVOS_TPU_CACHE",
                 "ANOVOS_TPU_FLIGHTREC")}
    try:
        os.environ.pop("ANOVOS_TPU_CACHE", None)  # parity gate runs uncached
        # the flightrec gate asserts dumps appear (and that clean runs have
        # none) — an ambient ANOVOS_TPU_FLIGHTREC=0 would fail it spuriously
        os.environ.pop("ANOVOS_TPU_FLIGHTREC", None)
        os.environ["ANOVOS_TPU_EXECUTOR"] = "concurrent"
        os.environ["ANOVOS_TPU_NODE_TIMEOUT"] = node_timeout
        if chaos_spec:
            os.environ["ANOVOS_TPU_CHAOS"] = chaos_spec
        else:
            os.environ.pop("ANOVOS_TPU_CHAOS", None)
        os.chdir(rundir)
        workflow.main(copy.deepcopy(cfg), "local")
        return load_manifest(workflow.LAST_MANIFEST_PATH)
    finally:
        os.chdir(prev_cwd)
        for k, v in prev_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def run_scenario(scenario: str, workdir: str, config: dict = None,
                 spec: str = None, node_timeout: str = "5") -> dict:
    """Clean + chaos run, parity + counter checks.  Returns the result
    record (``ok`` plus per-check fields) without exiting."""
    cfg = config if config is not None else synthetic_config(
        workdir, parts=SCENARIO_PARTS.get(scenario, 1))
    chaos_spec = spec if spec is not None else SCENARIOS[scenario]
    result = {"scenario": scenario, "spec": chaos_spec}
    if scenario in REQUIRE_MULTIDEV:
        import jax

        n_dev = len(jax.devices())
        result["n_devices"] = n_dev
        if n_dev < 2:
            result["ok"] = False
            result["error"] = (
                f"scenario {scenario!r} needs a multi-device mesh, got "
                f"{n_dev} device(s) — run with --devices 8 in a fresh process")
            return result

    t0 = time.monotonic()
    # the small node_timeout exists so the CHAOS run's injected hang
    # escalates quickly; the clean run gets a generous bound — otherwise a
    # legitimately slow node on a loaded box escalates, writes a flight
    # dump, and fails the clean_flightrec==0 assertion spuriously
    clean_timeout = str(max(float(node_timeout), 600.0))
    clean_manifest = _run_once(cfg, os.path.join(workdir, "clean"), "", clean_timeout)
    result["clean_wall_s"] = round(time.monotonic() - t0, 3)
    result["clean_quarantined_parts"] = (
        ((clean_manifest.get("resilience") or {}).get("quarantine") or {})
        .get("parts", 0))
    golden = tree_hash(os.path.join(workdir, "clean"))
    clean_dumps = flight_dumps(os.path.join(workdir, "clean"))
    result["clean_flightrec"] = len(clean_dumps)

    t0 = time.monotonic()
    try:
        manifest = _run_once(cfg, os.path.join(workdir, "chaos"),
                             chaos_spec, node_timeout)
    except Exception as e:
        result["ok"] = False
        result["error"] = f"chaos run DIED (recovery failed): {type(e).__name__}: {e}"
        return result
    result["chaos_wall_s"] = round(time.monotonic() - t0, 3)

    res = manifest.get("resilience") or {}
    result["resilience"] = {k: v for k, v in res.items() if k != "chaos"}
    result["injections"] = (res.get("chaos") or {}).get("injections", 0)
    expected_degraded = sorted(EXPECT_DEGRADED.get(scenario, ()))
    chaos_hash = tree_hash(os.path.join(workdir, "chaos"))
    # degradation scenarios waive byte parity: the degraded section's
    # artifacts are absent from the chaos tree by construction
    result["parity"] = True if expected_degraded else chaos_hash == golden
    missing = [k for k in EXPECT.get(scenario, ()) if not res.get(k)]
    result["missing_counters"] = missing
    # scheduler-degraded nodes UNION data-plane degradations (quarantined
    # parts, best-effort fallbacks) — the registry names both
    result["degraded"] = sorted(
        set(res.get("degraded") or [])
        | set((res.get("degraded_sections") or {}).keys()))
    degraded_ok = (result["degraded"] == expected_degraded)
    # the data-plane gate: exact quarantine manifest contents (both the
    # manifest's resilience section and the crash-safe on-disk copy), and
    # zero quarantines on the clean leg
    quar = res.get("quarantine") or {}
    result["quarantined_parts"] = quar.get("parts", 0)
    result["quarantine_rows"] = quar.get("rows_lost", 0)
    quarantine_ok = result["clean_quarantined_parts"] == 0
    expected_q = EXPECT_QUARANTINE.get(scenario)
    if expected_q is not None:
        got = {os.path.basename(r["file"]): r["rows_lost"]
               for r in quar.get("records", [])}
        result["quarantine_records"] = got
        if got != expected_q:
            quarantine_ok = False
        import glob as _glob

        on_disk = _glob.glob(os.path.join(
            workdir, "chaos", "**", "quarantine_manifest.json"), recursive=True)
        if not on_disk:
            quarantine_ok = False
            result["quarantine_manifest_missing"] = True
        else:
            with open(on_disk[0]) as f:
                disk_doc = json.load(f)
            disk_got = {os.path.basename(r["file"]): r["rows_lost"]
                        for r in disk_doc.get("records", [])}
            if disk_got != expected_q:
                quarantine_ok = False
                result["quarantine_disk_records"] = disk_got
    # the "no wedged rendezvous lane" assertion: an abandoned collective
    # must not stall the rest of the run — the chaos wall stays within a
    # bounded multiple of the clean wall, nowhere near the 600s hang
    bounded_ok = True
    if expected_degraded:
        bound = result["clean_wall_s"] * 2 + 90
        result["chaos_wall_bound_s"] = round(bound, 1)
        bounded_ok = result["chaos_wall_s"] <= bound
    # flight-recorder postmortems: each expected (trigger, node glob) must
    # have a dump naming a matching node; the clean run must have produced
    # none at all
    dumps = flight_dumps(os.path.join(workdir, "chaos"))
    result["flightrec"] = [
        {"file": os.path.basename(p), "trigger": trig, "node": node}
        for p, trig, node in dumps
    ]
    flight_missing = [
        f"{trig}@{pat}"
        for trig, pat in EXPECT_FLIGHT.get(scenario, ())
        if not any(t == trig and fnmatch.fnmatchcase(n, pat)
                   for _, t, n in dumps)
    ]
    result["flightrec_missing"] = flight_missing
    # postmortems must name each in-flight node's lane (and leased
    # devices) — the evidence a rendezvous postmortem runs on
    lanes_ok = True
    if EXPECT_FLIGHT.get(scenario, ()):
        lanes_ok = False
        for p, trig, node in dumps:
            try:
                with open(p) as f:
                    doc = json.load(f)
            except (OSError, ValueError):
                continue
            for entry in doc.get("inflight", []):
                if entry.get("node") == node and entry.get("lane"):
                    lanes_ok = True
        result["flightrec_lanes_ok"] = lanes_ok
    result["ok"] = bool(
        result["parity"] and not missing and degraded_ok and bounded_ok
        and quarantine_ok
        and result["injections"] > 0 and not flight_missing and lanes_ok
        and result["clean_flightrec"] == 0)
    if not result["ok"] and "error" not in result:
        reasons = []
        if not result["parity"]:
            reasons.append("artifact tree differs from the clean golden run")
        if not quarantine_ok:
            reasons.append(
                "quarantine gate failed: expected "
                f"{EXPECT_QUARANTINE.get(scenario)} got "
                f"{result.get('quarantine_records')} (clean leg quarantined "
                f"{result['clean_quarantined_parts']} part(s))")
        if missing:
            reasons.append(f"expected recovery counters missing: {missing}")
        if not degraded_ok:
            reasons.append(
                f"degraded sections {result['degraded']} != expected "
                f"{expected_degraded}")
        if not bounded_ok:
            reasons.append(
                f"chaos wall {result['chaos_wall_s']}s exceeded the bound "
                f"{result['chaos_wall_bound_s']}s — the abandoned collective "
                "wedged the run")
        if result["injections"] == 0:
            reasons.append("chaos plan fired nothing (site names drifted?)")
        if flight_missing:
            reasons.append("expected flight-recorder dump(s) missing: "
                           f"{flight_missing} (got {result['flightrec']})")
        if not lanes_ok:
            reasons.append("flight dumps carry no lane attribution for the "
                           "triggering node")
        if result["clean_flightrec"]:
            reasons.append(
                f"{result['clean_flightrec']} flight-recorder dump(s) on the "
                "CLEAN run — postmortems must only fire on real trouble")
        result["error"] = "; ".join(reasons)
    return result


def run_serve_fault(workdir: str) -> dict:
    """The online-serving fault gate (no workflow run involved).

    Clean leg: boot a server from the demo bundle, serve mixed-width
    requests, every response byte-identical to the batch apply, zero
    flight dumps.  Chaos leg: install the ``serve-fault`` plan, lead
    with a victim request (hang + exc, retry exc → fatal batch), then
    interleave clean and hostile requests.  Gates: the victim got a
    structured ``apply_failed`` error, a ``serve_fatal`` flight dump
    exists, hostile payloads got structured quarantine responses, every
    clean response stayed byte-identical (zero corrupted responses),
    p99 stayed bounded, and the server was still serving at the end."""
    import numpy as np

    from anovos_tpu.obs import flight
    from anovos_tpu.resilience import chaos
    from anovos_tpu.serving.bundle import load_bundle
    from anovos_tpu.serving.demo import build_demo_bundle, demo_frame
    from anovos_tpu.serving.program import ApplyProgram
    from anovos_tpu.serving.server import (
        FeatureServer, coerce_payload, frame_to_payload)
    from anovos_tpu.shared.runtime import init_runtime

    init_runtime()
    spec = SCENARIOS["serve-fault"]
    result = {"scenario": "serve-fault", "spec": spec}
    cache = os.path.join(workdir, "cache")
    version = build_demo_bundle(cache, rows=1500)
    bundle = load_bundle(cache, version)
    src = demo_frame(1500, seed=11)[bundle.input_names]
    widths = (1, 3, 8, 17)
    payloads, off = [], 0
    for i in range(16):
        w = widths[i % len(widths)]
        payloads.append({"columns": frame_to_payload(src.iloc[off:off + w])})
        off += w
    hostile = [
        {"columns": {**payloads[0]["columns"],
                     "age": [float("inf")]}},
        {"columns": {**payloads[0]["columns"], "age": [1e39]}},
        {"columns": {**{k: v for k, v in payloads[0]["columns"].items()
                        if k != "age"}, "bogus_col": [1.0]}},
        {"columns": {**payloads[0]["columns"], "age": ["not-a-number"]}},
    ]

    def reference(program, payload):
        frame, err = coerce_payload(program.input_columns, payload, 256)
        assert err is None
        return frame_to_payload(program.apply_frame(frame))

    def run_leg(leg: str, chaos_spec: str) -> dict:
        import threading
        import urllib.error
        import urllib.request

        from anovos_tpu.obs import telemetry

        obs_dir = os.path.join(workdir, leg)
        os.makedirs(obs_dir, exist_ok=True)
        flight.configure(os.path.join(obs_dir, "obs"))
        # the live telemetry plane rides the leg on an ephemeral port:
        # the gate scrapes /metrics + /healthz WHILE the fault is in
        # flight (a wedged apply must never wedge a scrape)
        tele = telemetry.acquire(context=f"chaos-{leg}", port=0)
        scrape_failures = [0]

        def scrape(path: str):
            """(status_code, body) — a 503 (unhealthy) is still a SERVED
            scrape; only a dead/deaf listener counts as a failure."""
            if tele is None:
                scrape_failures[0] += 1
                return None, "telemetry listener failed to bind"
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{tele.port}{path}", timeout=10) as r:
                    return r.status, r.read().decode()
            except urllib.error.HTTPError as e:
                return e.code, e.read().decode()
            except Exception as e:
                scrape_failures[0] += 1
                return None, f"{type(e).__name__}: {e}"

        try:
            program = ApplyProgram(load_bundle(cache, version))
            server = FeatureServer(program, obs_dir=obs_dir)
            t0 = time.monotonic()
            server.start(warm=True)
            # faults target STEADY-STATE serving: the plan lands after
            # boot so the warm probe is not the victim
            chaos.install(chaos_spec or None)
            out: dict = {"cold_start_s": round(time.monotonic() - t0, 3)}
            victim = None
            midfault_ok = 0
            if chaos_spec:
                # drive the victim from a side thread and scrape
                # MID-FAULT: the injected 0.5s hang is in flight while
                # /metrics and /healthz must keep answering
                box: list = []
                vt = threading.Thread(
                    target=lambda: box.append(server.serve(payloads[-1])))
                vt.start()
                time.sleep(0.15)
                for path in ("/metrics", "/healthz"):
                    code, _body = scrape(path)
                    if code is not None:
                        midfault_ok += 1
                vt.join()
                victim = box[0] if box else None
            clean_bad = []
            hostile_bad = []
            for i, p in enumerate(payloads[:12]):
                resp = server.serve(p)
                if "error" in resp or resp.get("columns") != reference(program, p):
                    clean_bad.append(i)
                if chaos_spec and i % 3 == 0:
                    h = server.serve(hostile[(i // 3) % len(hostile)])
                    if "error" not in h:
                        hostile_bad.append(i)
            # post-load health + exposition sanity, still mid-leg
            _code, health_body = scrape("/healthz")
            try:
                health_doc = json.loads(health_body) if health_body else {}
            except ValueError:
                health_doc = {}
            _mcode, metrics_body = scrape("/metrics")
            stats = server.stats()
            server.close()
            dumps = flight_dumps(obs_dir)
            chaos_plan = chaos.plan()
            out.update({
                "victim": victim,
                "clean_corrupted": clean_bad,
                "hostile_unrefused": hostile_bad,
                "stats": stats,
                "flightrec": [{"file": os.path.basename(p), "trigger": t,
                               "node": n} for p, t, n in dumps],
                "injections": chaos_plan.injection_count() if chaos_plan else 0,
                "midfault_scrapes_ok": midfault_ok,
                "scrape_failures": scrape_failures[0],
                "healthz_status": health_doc.get("status"),
                "healthz_reasons": health_doc.get("reasons", []),
                "metrics_has_serve_families": bool(
                    metrics_body and "serve_batches_total" in metrics_body
                    and "serve_rolling_qps" in metrics_body),
            })
            return out
        finally:
            # a leg that dies mid-body must not leak the listener (the
            # next leg's acquire would join the leaked refcount and its
            # release would never stop the socket) nor the chaos plan
            telemetry.release(tele)
            chaos.reset()
            flight.reset()

    clean = run_leg("clean", "")
    result["clean_flightrec"] = len(clean["flightrec"])
    result["clean_corrupted"] = clean["clean_corrupted"]
    result["clean_p99_ms"] = clean["stats"]["p99_ms"]
    result["clean_wall_s"] = clean["cold_start_s"]
    result["clean_healthz"] = clean["healthz_status"]
    result["clean_scrape_failures"] = clean["scrape_failures"]

    chaos_leg = run_leg("chaos", spec)
    result["injections"] = chaos_leg["injections"]
    result["chaos_p99_ms"] = chaos_leg["stats"]["p99_ms"]
    result["chaos_corrupted"] = chaos_leg["clean_corrupted"]
    result["hostile_unrefused"] = chaos_leg["hostile_unrefused"]
    result["flightrec"] = chaos_leg["flightrec"]
    result["quarantined"] = chaos_leg["stats"]["quarantined"]
    result["served_after_fatal"] = chaos_leg["stats"]["served"]
    result["midfault_scrapes_ok"] = chaos_leg["midfault_scrapes_ok"]
    result["chaos_scrape_failures"] = chaos_leg["scrape_failures"]
    result["chaos_healthz"] = chaos_leg["healthz_status"]
    result["chaos_healthz_reasons"] = chaos_leg["healthz_reasons"]

    victim = chaos_leg["victim"] or {}
    victim_ok = (victim.get("error") or {}).get("code") == "apply_failed"
    fatal_dumped = any(d["trigger"] == "serve_fatal"
                      for d in chaos_leg["flightrec"])
    # telemetry-plane gates: the clean leg reports ok with zero dropped
    # scrapes; the chaos leg's /healthz flips to degraded NAMING the
    # failed batch, and every scrape during the fault was served
    health_flipped = (
        chaos_leg["healthz_status"] == "degraded"
        and any("serving" in r and "failed after retry" in r
                for r in chaos_leg["healthz_reasons"]))
    telemetry_ok = (
        clean["healthz_status"] == "ok"
        and clean["scrape_failures"] == 0
        and clean["metrics_has_serve_families"]
        and chaos_leg["scrape_failures"] == 0
        and chaos_leg["midfault_scrapes_ok"] >= 2
        and health_flipped)
    result["telemetry_ok"] = telemetry_ok
    # bounded p99: the injected 0.5s hang + one retry must not push the
    # tail anywhere near a hung-server cliff
    p99_bound_ms = 10_000.0
    result["p99_bound_ms"] = p99_bound_ms
    bounded = (chaos_leg["stats"]["p99_ms"] or np.inf) <= p99_bound_ms
    result["parity"] = not (clean["clean_corrupted"]
                            or chaos_leg["clean_corrupted"])
    result["ok"] = bool(
        result["parity"] and victim_ok and fatal_dumped and bounded
        and telemetry_ok
        and not chaos_leg["hostile_unrefused"]
        and chaos_leg["stats"]["served"] >= len(payloads[:12])
        and result["injections"] >= 3
        and result["clean_flightrec"] == 0)
    if not result["ok"]:
        reasons = []
        if clean["healthz_status"] != "ok":
            reasons.append(
                f"clean-leg /healthz reported {clean['healthz_status']!r} "
                f"({clean.get('healthz_reasons')}) instead of ok")
        if clean["scrape_failures"] or chaos_leg["scrape_failures"]:
            reasons.append(
                f"dropped scrapes (clean {clean['scrape_failures']}, "
                f"chaos {chaos_leg['scrape_failures']}) — every scrape "
                "must be served, fault or not")
        if chaos_leg["midfault_scrapes_ok"] < 2:
            reasons.append(
                f"only {chaos_leg['midfault_scrapes_ok']}/2 mid-fault "
                "scrapes answered while the apply hang was in flight")
        if not health_flipped:
            reasons.append(
                f"/healthz did not flip to degraded naming the failed batch "
                f"(status={chaos_leg['healthz_status']!r}, "
                f"reasons={chaos_leg['healthz_reasons']})")
        if not clean["metrics_has_serve_families"]:
            reasons.append("/metrics exposition is missing the live serve "
                           "families (serve_batches_total / serve_rolling_qps)")
        if clean["clean_corrupted"] or chaos_leg["clean_corrupted"]:
            reasons.append(
                f"corrupted clean responses (clean leg {clean['clean_corrupted']}, "
                f"chaos leg {chaos_leg['clean_corrupted']})")
        if not victim_ok:
            reasons.append(f"victim request did not fail structurally: {victim}")
        if not fatal_dumped:
            reasons.append(
                f"no serve_fatal flight dump (got {chaos_leg['flightrec']})")
        if not bounded:
            reasons.append(
                f"chaos p99 {chaos_leg['stats']['p99_ms']}ms exceeded the "
                f"{p99_bound_ms}ms bound")
        if chaos_leg["hostile_unrefused"]:
            reasons.append("hostile payload(s) served instead of refused: "
                           f"{chaos_leg['hostile_unrefused']}")
        if chaos_leg["stats"]["served"] < len(payloads[:12]):
            reasons.append("server stopped serving after the fatal batch")
        if result["injections"] < 3:
            reasons.append(
                f"chaos plan fired {result['injections']} (< 3 — site drifted?)")
        if result["clean_flightrec"]:
            reasons.append(f"{result['clean_flightrec']} flight dump(s) on the "
                           "CLEAN serving leg")
        result["error"] = "; ".join(reasons)
    return result


def run_slowread_stream(workdir: str) -> dict:
    """The streaming-ingest fault gate (no workflow run).

    Clean leg: ``describe_streaming`` over an 8-part dataset with the
    prefetch pool on.  Chaos leg: the ``slowread-stream`` plan delays six
    of the eight parts by 0.6s per read (both passes → 7.2s of serial
    decode penalty).  Gates: byte-identical stats frames, zero
    quarantines on both legs, and a BOUNDED chaos wall — the pool must
    absorb the slow parts concurrently, so the overhead stays under 60%
    of the serial penalty (a synchronous pipeline pays all of it), plus
    measurable decode/compute overlap on the chaos leg."""
    import numpy as np
    import pandas as pd

    from anovos_tpu.data_ingest import guard
    from anovos_tpu.ops.streaming import describe_streaming, last_stream_summary
    from anovos_tpu.resilience import chaos

    spec = SCENARIOS["slowread-stream"]
    result = {"scenario": "slowread-stream", "spec": spec}
    data = os.path.join(workdir, "stream_data")
    if not os.path.isdir(data):
        os.makedirs(data)
        rng = np.random.default_rng(7)
        for i in range(8):
            pd.DataFrame({
                "a": rng.normal(i, 2.0, 2048),
                "b": rng.exponential(5.0, 2048),
            }).to_parquet(os.path.join(data, f"part-{i:05d}.parquet"),
                          index=False)
    prev = {k: os.environ.get(k) for k in
            ("ANOVOS_STREAM_INFLIGHT", "ANOVOS_STREAM_DECODE_WORKERS")}
    try:
        # pin a real pool: the gate measures pool absorption, not the
        # box's cpu count
        os.environ["ANOVOS_STREAM_INFLIGHT"] = "auto"
        os.environ["ANOVOS_STREAM_DECODE_WORKERS"] = "4"
        guard.reset()
        chaos.reset()
        t0 = time.monotonic()
        clean = describe_streaming(data, "parquet", chunk_rows=2048)
        result["clean_wall_s"] = round(time.monotonic() - t0, 3)
        result["clean_quarantined_parts"] = len(guard.records())

        chaos.install(spec)
        t0 = time.monotonic()
        slow = describe_streaming(data, "parquet", chunk_rows=2048)
        result["chaos_wall_s"] = round(time.monotonic() - t0, 3)
        plan = chaos.plan()
        result["injections"] = plan.injection_count() if plan else 0
        result["quarantined_parts"] = len(guard.records())
        ss = last_stream_summary()
        result["stream_overlap_pct"] = ss.get("overlap_pct")
        result["stream_workers"] = ss.get("workers")
    finally:
        chaos.reset()
        guard.reset()
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    serial_penalty = 6 * 0.6 * 2  # parts × secs × passes
    bound = result["clean_wall_s"] + 0.6 * serial_penalty
    result["serial_penalty_s"] = serial_penalty
    result["chaos_wall_bound_s"] = round(bound, 2)
    parity = bool(clean.equals(slow))
    result["parity"] = parity
    bounded = result["chaos_wall_s"] <= bound
    overlapped = (result["stream_overlap_pct"] or 0) >= 0.3
    result["ok"] = bool(
        parity and bounded and overlapped
        and result["injections"] >= 12
        and result["quarantined_parts"] == 0
        and result["clean_quarantined_parts"] == 0)
    if not result["ok"]:
        reasons = []
        if not parity:
            reasons.append("slow-read stats frame differs from the clean run")
        if not bounded:
            reasons.append(
                f"chaos wall {result['chaos_wall_s']}s exceeded the bound "
                f"{result['chaos_wall_bound_s']}s — the pool serialized the "
                "slow parts instead of absorbing them")
        if not overlapped:
            reasons.append(
                f"overlap {result['stream_overlap_pct']} < 0.3 — device "
                "compute stalled for the decode wall")
        if result["injections"] < 12:
            reasons.append(
                f"chaos plan fired {result['injections']} (< 12 — io site "
                "names drifted?)")
        if result["quarantined_parts"] or result["clean_quarantined_parts"]:
            reasons.append("slowread must delay, never quarantine")
        result["error"] = "; ".join(reasons)
    return result


def run_feed_30d(workdir: str) -> dict:
    """The continuum byte-parity gate (no workflow run, no chaos spec —
    the 30-day feed's faults are physical).  Incremental leg: one
    ``continuum.step`` per arriving day; batch leg: one step over the
    whole union from empty state.  See ``tools/continuum_bench`` for the
    feed layout; this gate reuses its builder and its legs so the bench
    and the gate cannot drift apart."""
    import json as _json

    from tools import continuum_bench

    result = {"scenario": "feed-30d", "spec": ""}
    try:
        r = continuum_bench.run(days=30, rows_per_day=500, workdir=workdir)
    except Exception as e:
        result["ok"] = False
        result["error"] = f"{type(e).__name__}: {e}"
        return result
    result.update({k: v for k, v in r.items() if k != "workdir"})
    result["parity"] = r["continuum_parity"]
    # the shift-day alert must carry flight-recorder context: re-read the
    # emitted stream (the incremental leg's obs/ subtree)
    alerts_path = os.path.join(workdir, "inc", "out", "obs",
                               "continuum_alerts.jsonl")
    shift_alerts = []
    if os.path.exists(alerts_path):
        with open(alerts_path) as f:
            for line in f:
                try:
                    rec = _json.loads(line)
                except ValueError:
                    continue
                if rec.get("kind") == "drift":
                    shift_alerts.append(rec)
    with_context = [a for a in shift_alerts if a.get("flight")]
    result["drift_alerts"] = len(shift_alerts)
    result["drift_alerts_with_flight_context"] = len(with_context)
    quarantine_ok = (r["continuum_quarantined"] == ["day-20.parquet"]
                     and r["continuum_batch_quarantined"] == ["day-20.parquet"])
    history_flat = r["continuum_day30_vs_day2"] <= 2.0
    result["ok"] = bool(
        r["continuum_parity"] and quarantine_ok and history_flat
        and r["continuum_shift_alert_day"] is not None
        and with_context)
    if not result["ok"]:
        reasons = []
        if not r["continuum_parity"]:
            reasons.append("incremental artifacts differ from the "
                           "from-scratch batch run over the union")
        if not quarantine_ok:
            reasons.append(
                f"quarantine mismatch: inc={r['continuum_quarantined']} "
                f"batch={r['continuum_batch_quarantined']} (want day-20 on both)")
        if not history_flat:
            reasons.append(
                f"day-30 fold {r['continuum_day30_fold_s']}s is "
                f"{r['continuum_day30_vs_day2']}x day-2 — fold wall grew "
                "with history length")
        if r["continuum_shift_alert_day"] is None:
            reasons.append("no drift alert fired on/after the shift day")
        elif not with_context:
            reasons.append("drift alerts carry no flight-recorder context")
        result["error"] = "; ".join(reasons)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="run a config under a chaos scenario; exit nonzero "
                    "unless recovery and artifact parity hold")
    ap.add_argument("--scenario", choices=sorted(SCENARIOS), default="full")
    ap.add_argument("--config", help="YAML config (default: built-in synthetic)")
    ap.add_argument("--spec", help="explicit ANOVOS_TPU_CHAOS spec override")
    ap.add_argument("--workdir", help="run directory (default: a fresh tempdir)")
    ap.add_argument("--node-timeout", default="5",
                    help="ANOVOS_TPU_NODE_TIMEOUT for both runs (seconds; "
                         "small so the hang scenario escalates quickly)")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N virtual CPU devices (fresh process only; "
                         "the hang-collective scenario needs a multi-device "
                         "mesh)")
    ap.add_argument("--json", action="store_true", help="machine-readable result")
    ns = ap.parse_args(argv)

    if ns.devices:
        # must land before the first jax device query in this process; the
        # fragile forcing sequence lives in ONE place (__graft_entry__)
        import __graft_entry__ as _entry

        _entry.force_virtual_devices(ns.devices)

    cfg = None
    if ns.config:
        import yaml

        with open(ns.config) as f:
            cfg = yaml.load(f, yaml.SafeLoader)
    workdir = ns.workdir or tempfile.mkdtemp(prefix="anovos_chaos_")
    if ns.scenario == "serve-fault":
        # --node-timeout is a workflow-scenario knob (ANOVOS_TPU_NODE_TIMEOUT);
        # the serving scenario's tail bound is the p99 gate instead
        result = run_serve_fault(workdir)
    elif ns.scenario == "slowread-stream":
        # streaming-ingest scenario: the bound is the pool-absorption gate
        result = run_slowread_stream(workdir)
    elif ns.scenario == "feed-30d":
        # continuum scenario: incremental-vs-batch byte parity over the
        # 30-day feed with the corrupt day quarantined on both legs
        result = run_feed_30d(workdir)
    else:
        result = run_scenario(ns.scenario, workdir, config=cfg, spec=ns.spec,
                              node_timeout=ns.node_timeout)
    if ns.json:
        print(json.dumps(result, sort_keys=True))
    else:
        status = "OK" if result["ok"] else "FAIL"
        print(f"chaos_run[{ns.scenario}]: {status} — "
              f"injections={result.get('injections')} "
              f"parity={result.get('parity')} "
              f"resilience={result.get('resilience')}")
        if not result["ok"]:
            print("chaos_run: " + result.get("error", "unknown failure"),
                  file=sys.stderr)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
