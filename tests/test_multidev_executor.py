"""Collective-aware multi-device DAG execution (ISSUE 8).

The contract under test:

* placement is declarative data (``parallel.placement``), chips are handed
  out by the runtime's ``DeviceLeaseRegistry`` under the rendezvous-lane
  invariant (at most one collective claim covering any device), and the
  executor derives its lane discipline from both;
* ``device``-placed nodes run under a placement scope: tables re-placed
  onto the leased chip, layout gates resolving against the derived
  runtime — and produce the same numbers as the mesh layout;
* a hung collective node is escalated, abandoned, and its lease RELEASED,
  so the rendezvous lane never wedges;
* ``workflow.main`` no longer degrades to sequential on the 8-virtual-
  device mesh: the fresh-process gates below run the real pipeline
  concurrent-vs-sequential (byte parity + measured overlap > 1) and the
  chaos ``hang-collective`` scenario end to end.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from anovos_tpu.parallel.placement import Placement, parse_placement
from anovos_tpu.parallel.scheduler import DagScheduler, default_workers

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------- placement --

def test_parse_placement_forms():
    assert parse_placement(None).kind == "host"
    assert parse_placement("mesh").collective
    assert parse_placement("submesh:3") == Placement("submesh", 3)
    assert parse_placement("submesh:3").collective
    assert not parse_placement("device").collective
    assert parse_placement(Placement("device")).kind == "device"
    with pytest.raises(ValueError):
        parse_placement("warp")
    with pytest.raises(ValueError):
        parse_placement("submesh:0")


# ------------------------------------------------------------ lease registry --

def _registry():
    from anovos_tpu.shared.runtime import DeviceLeaseRegistry, get_runtime

    rt = get_runtime()
    return DeviceLeaseRegistry(list(rt.mesh.devices.flat)), rt


def test_mesh_lease_is_exclusive_against_collectives():
    reg, _ = _registry()
    mesh = reg.try_lease("a", "mesh")
    assert mesh is not None and len(mesh.devices) == reg.n_devices
    assert reg.try_lease("b", "mesh") is None
    assert reg.try_lease("c", "submesh", 2) is None
    # device leases never block — single-device programs carry no rendezvous
    dev = reg.try_lease("d", "device")
    assert dev is not None and len(dev.devices) == 1
    assert reg.collective_holders() == ["a"]
    reg.release(mesh)
    assert reg.try_lease("b", "mesh") is not None
    reg.release(dev)


def test_mesh_claims_of_one_group_are_one_claim():
    """Readers of one table version share the rendezvous lane (they order
    their own device work); every other collective claim waits for all of
    them, and they for it."""
    reg, _ = _registry()
    a = reg.try_lease("a", "mesh", group="df:1")
    b = reg.try_lease("b", "mesh", group="df:1")
    assert a is not None and b is not None and len(b.devices) == reg.n_devices
    assert reg.try_lease("c", "mesh") is None
    assert reg.try_lease("d", "mesh", group="df:2") is None
    assert reg.try_lease("e", "submesh", 2) is None
    reg.release(a)
    assert reg.try_lease("c", "mesh") is None and reg.collective_holders() == ["b"]
    reg.release(b)
    c = reg.try_lease("c", "mesh")
    assert c is not None
    assert reg.try_lease("a", "mesh", group="df:1") is None  # an ungrouped claim admits nobody
    reg.release(c)


def test_submesh_carves_are_disjoint():
    reg, _ = _registry()
    a = reg.try_lease("a", "submesh", 4)
    b = reg.try_lease("b", "submesh", 4)
    assert a is not None and b is not None
    assert not (set(d.id for d in a.devices) & set(d.id for d in b.devices))
    assert reg.try_lease("c", "submesh", 1) is None  # no free chip left
    reg.release(a)
    assert reg.try_lease("c", "submesh", 1) is not None


def test_device_lease_is_sticky_by_holder_name():
    """XLA executables are keyed on the device assignment: a node hopping
    chips between runs/executors would recompile per chip."""
    reg, _ = _registry()
    first = reg.try_lease("stats_generator/global_summary", "device")
    reg.release(first)
    again = reg.try_lease("stats_generator/global_summary", "device")
    reg.release(again)
    assert [d.id for d in first.devices] == [d.id for d in again.devices]


def test_default_workers_covers_lane_plus_chips(monkeypatch):
    monkeypatch.delenv("ANOVOS_TPU_EXECUTOR_WORKERS", raising=False)
    from anovos_tpu.shared.runtime import get_runtime

    n = get_runtime().n_devices
    assert n == 8
    assert default_workers() >= n + 1  # rendezvous lane + one per chip


# ------------------------------------------------------- placement scoping --

def test_table_to_active_placement_matches_mesh_numbers():
    import pandas as pd

    from anovos_tpu.ops.reductions import masked_moments
    from anovos_tpu.shared.runtime import (
        derive_runtime, get_runtime, placement_scope, wants_column_parallel,
    )
    from anovos_tpu.shared.table import Table

    g = np.random.default_rng(3)
    df = pd.DataFrame({"a": g.normal(size=500), "b": g.normal(size=500)})
    df.iloc[::9, 1] = np.nan
    t = Table.from_pandas(df)
    X, M = t.numeric_block(["a", "b"])
    mesh_mom = {k: np.asarray(v) for k, v in masked_moments(X, M).items()}

    rt = get_runtime()
    one = derive_runtime(list(rt.mesh.devices.flat)[:1])
    with placement_scope(one):
        assert get_runtime() is one  # the scope overrides resolution
        t1 = t.to_active_placement()
        devs = {d.id for d in t1.columns["a"].data.sharding.device_set}
        assert len(devs) == 1
        X1, M1 = t1.numeric_block(["a", "b"])
        assert not wants_column_parallel(X1, M1)  # 1-device: gate off
        one_mom = {k: np.asarray(v) for k, v in masked_moments(X1, M1).items()}
    assert get_runtime() is rt  # scope restored
    for k in mesh_mom:
        # 1-device and 8-shard reductions legitimately differ in the last
        # ulp (different partial-sum trees); the executors compare byte-
        # identical because BOTH run the node under the same placement
        np.testing.assert_allclose(one_mom[k], mesh_mom[k], rtol=1e-5,
                                   atol=1e-6, err_msg=k)
    # outside any scope the table is returned untouched
    assert t.to_active_placement() is t


# ------------------------------------------------------------- lane executor --

def test_collective_nodes_serialize_device_nodes_overlap():
    """At most one collective node in flight (the rendezvous-lane
    invariant) while device/host nodes overlap it and each other."""
    lock = threading.Lock()
    live = {"coll": 0, "max_coll": 0, "any": 0, "max_any": 0}

    def body(kind, dur=0.15):
        def f():
            with lock:
                live["any"] += 1
                live["max_any"] = max(live["max_any"], live["any"])
                if kind == "mesh":
                    live["coll"] += 1
                    live["max_coll"] = max(live["max_coll"], live["coll"])
            time.sleep(dur)
            with lock:
                live["any"] -= 1
                if kind == "mesh":
                    live["coll"] -= 1
        return f

    s = DagScheduler()
    for i in range(3):
        s.add(f"coll{i}", body("mesh"), placement="mesh")
    for i in range(3):
        s.add(f"dev{i}", body("device"), placement="device")
    summary = s.run(mode="concurrent", max_workers=8, node_timeout=30)
    assert live["max_coll"] == 1, "two collective nodes overlapped"
    assert live["max_any"] >= 2, "nothing overlapped at all"
    assert summary["multidev_overlap"] >= 2
    assert summary["n_devices"] == 8
    lanes = {k: v["lane"] for k, v in summary["nodes"].items()}
    assert lanes["coll0"] == "mesh" and lanes["dev0"] == "device"
    # device nodes record which chip they leased; mesh nodes the full set
    assert len(summary["nodes"]["dev0"]["devices"]) == 1
    assert len(summary["nodes"]["coll0"]["devices"]) == 8


def test_mesh_nodes_of_one_lane_group_overlap_each_other_and_no_other_collective():
    lock = threading.Lock()
    live, seen = set(), []

    def body(name):
        def f():
            with lock:
                live.add(name)
                seen.append(frozenset(live))
            time.sleep(0.15)
            with lock:
                live.discard(name)
        return f

    s = DagScheduler()
    for i in range(3):
        s.add(f"reader{i}", body(f"reader{i}"), placement="mesh", lane_group="df:0")
    s.add("spine", body("spine"), placement="mesh")
    summary = s.run(mode="concurrent", max_workers=8, node_timeout=30)
    assert all(n["state"] == "done" for n in summary["nodes"].values())
    assert max(len(x) for x in seen) == 3, "the group's members never overlapped"
    assert not [x for x in seen if "spine" in x and len(x) > 1], "a collective overlapped the group"


def test_submesh_nodes_with_disjoint_carves_overlap():
    ev_a, ev_b = threading.Event(), threading.Event()

    def a():
        ev_a.set()
        assert ev_b.wait(10), "b never overlapped a despite disjoint carves"

    def b():
        ev_b.set()
        assert ev_a.wait(10), "a never overlapped b despite disjoint carves"

    s = DagScheduler()
    s.add("a", a, placement="submesh:4")
    s.add("b", b, placement="submesh:4")
    summary = s.run(mode="concurrent", max_workers=4, node_timeout=30)
    assert all(n["state"] == "done" for n in summary["nodes"].values())


def test_hung_collective_releases_rendezvous_lane(monkeypatch):
    """Escalation -> abandonment of a stuck collective must release its
    lease so later collective nodes still run: the run completes DEGRADED,
    never wedged.  (The fresh-process chaos scenario gates the same path
    through workflow.main; this pins the scheduler mechanics.)"""
    monkeypatch.setenv("ANOVOS_TPU_HEALTH_TIMEOUT", "1")
    hang = threading.Event()
    ran = []

    s = DagScheduler()
    s.add("stuck", lambda: hang.wait(30), placement="mesh",
          on_error="retry:0:degrade")
    s.add("next_coll", lambda: ran.append("next_coll"), placement="mesh")
    t0 = time.monotonic()
    summary = s.run(mode="concurrent", max_workers=4, node_timeout=0.4)
    took = time.monotonic() - t0
    hang.set()  # unblock the abandoned daemon thread
    assert summary["nodes"]["stuck"]["state"] == "degraded"
    assert ran == ["next_coll"], "rendezvous lane stayed wedged"
    assert summary["nodes"]["next_coll"]["state"] == "done"
    assert took < 15, f"abandonment took {took:.1f}s — not bounded"
    # the lane registry holds no collective claim once the run is over
    assert s._lanes is not None and s._lanes.collective_holders() == []


def test_stable_view_keeps_lane_drops_devices():
    from anovos_tpu.obs import build_manifest, get_metrics, stable_view

    s = DagScheduler()
    s.add("n", lambda: None, placement="device")
    summary = s.run(mode="sequential")
    man = build_manifest({}, summary, get_metrics().snapshot())
    sv = stable_view(man)
    node = sv["scheduler"]["nodes"]["n"]
    assert node["lane"] == "device"
    assert "devices" not in node
    assert "multidev_overlap" not in sv["scheduler"]


# ------------------------------------------------- fresh-process acceptance --

def _fresh_env():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    for k in ("ANOVOS_TPU_CHAOS", "ANOVOS_TPU_CACHE", "ANOVOS_TPU_EXECUTOR",
              "ANOVOS_TPU_PLACEMENT", "XLA_FLAGS"):
        env.pop(k, None)
    return env


def test_workflow_concurrent_on_8dev_mesh_parity_and_overlap():
    """THE acceptance gate: on the 8-virtual-device mesh, workflow.main
    no longer degrades to sequential — the concurrent executor completes
    the pipeline with artifacts byte-identical to sequential, >= 2 nodes
    concurrently in flight, and a warm wall that holds the sequential
    wall."""
    p = subprocess.run(
        [sys.executable, "-m", "tools.dryrun_multichip", "--executor-only",
         "--devices", "8"],
        capture_output=True, text=True, timeout=560, env=_fresh_env(), cwd=REPO,
    )
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-3000:]
    line = [ln for ln in p.stdout.splitlines()
            if ln.startswith("executor_pass:")][-1]
    rec = json.loads(line.split(":", 1)[1])
    assert rec["e2e_multidev_overlap"] > 1
    assert rec["e2e_multidev_devices"] == 8


def test_chaos_hang_collective_fresh_process(tmp_path):
    """Chaos hang injected into a collective node on the multi-device
    mesh: escalation interrupts the collective, the lease is released,
    and the run finishes degraded within the bound — no AllReduce
    deadlock, no wedged rendezvous lane."""
    p = subprocess.run(
        [sys.executable, "-m", "tools.chaos_run", "--scenario",
         "hang-collective", "--devices", "8", "--workdir", str(tmp_path),
         "--json"],
        capture_output=True, text=True, timeout=560, env=_fresh_env(), cwd=REPO,
    )
    assert p.returncode == 0, p.stdout + p.stderr
    rec = json.loads(p.stdout.strip().splitlines()[-1])
    assert rec["ok"], rec
    assert rec["n_devices"] == 8
    assert rec["degraded"] == ["drift_detector/drift_statistics"]
    assert rec["resilience"]["timeout_escalations"] >= 1
    assert rec["flightrec_lanes_ok"] is True
    assert rec["chaos_wall_s"] <= rec["chaos_wall_bound_s"]
