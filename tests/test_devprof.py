"""Device-time attribution (anovos_tpu.obs.devprof):

* unit semantics — dispatch nesting (outermost wins), transfer byte/wall
  booking, the drain probe, attribution clamping;
* the acceptance invariant — every executed node of a workflow run
  carries a manifest ``devprof`` entry with ``device + dispatch +
  transfer + host ≤ wall ≤ node dur``;
* multi-device memory sampling — ``record_device_memory`` labels every
  local device and keeps a mesh-wide high-water (the PR's satellite fix
  for the 7-invisible-chips bug);
* stability — the ``devprof`` section and its metric families are
  stripped by ``stable_view`` so manifest byte-parity goldens hold.
"""

import copy
import threading

import pytest

from anovos_tpu import obs
from anovos_tpu.obs import devprof
from anovos_tpu.obs.metrics import MetricsRegistry, record_device_memory


# ---------------------------------------------------------------------------
# unit: brackets
# ---------------------------------------------------------------------------

def test_node_bracket_produces_invariant_result():
    devprof.reset()
    with devprof.node_bracket("n1"):
        with devprof.dispatch_bracket("ops.fake"):
            pass
        devprof.record_transfer("h2d", 1024, 0.001, label="test")
    out = devprof.results()["n1"]
    total = (out["device_time_s"] + out["dispatch_s"]
             + out["transfer_s"] + out["host_s"])
    assert total <= out["wall_s"] + 1e-9
    assert out["h2d_bytes"] == 1024
    assert out["d2h_bytes"] == 0
    assert out["transfers"] == 1
    assert out["last_op"] in ("test", "ops.fake")


def test_dispatch_bracket_outermost_only():
    devprof.reset()
    with devprof.node_bracket("nested"):
        with devprof.dispatch_bracket("outer"):
            with devprof.dispatch_bracket("inner"):
                pass
    out = devprof.results()["nested"]
    # one booked dispatch despite two brackets: the inner one is nested
    assert out["dispatches"] == 1


def test_dispatch_compile_phase_not_booked_as_dispatch():
    devprof.reset()
    with devprof.node_bracket("cold"):
        with devprof.dispatch_bracket("ops.x", phase="compile"):
            pass
    out = devprof.results()["cold"]
    assert out["dispatches"] == 0     # compile wall stays in the remainder
    assert out["last_op"] == "ops.x"  # but the op is still named


def test_transfer_bracket_books_bytes_and_direction():
    devprof.reset()
    reg_before_h2d = obs.get_metrics().counter(
        "transfer_h2d_bytes_total").value()
    with devprof.node_bracket("t"):
        with devprof.transfer_bracket("h2d", 100, label="up"):
            pass
        with devprof.transfer_bracket("d2h", 200, label="down"):
            pass
    out = devprof.results()["t"]
    assert out["h2d_bytes"] == 100 and out["d2h_bytes"] == 200
    assert obs.get_metrics().counter(
        "transfer_h2d_bytes_total").value() == reg_before_h2d + 100


@pytest.mark.parametrize("rows,threads", [(1_000, 4), (200_000, 4), (200_000, 1)])
def test_to_pandas_books_one_d2h_record_a_column_and_one_a_wide_pair_on_the_callers_node(
        monkeypatch, rows, threads):
    """The fetch of a table inside a node: a ``table.to_pandas`` record of
    ``data`` + ``mask`` bytes a column and a ``column.exact_host`` record a
    wide pair, on the node's frame whichever thread waited for the copy
    (the units of a long table run on the host pool's threads)."""
    import numpy as np

    from anovos_tpu.shared import host_pool
    from anovos_tpu.shared.table import Table

    made = host_pool.HostPool(threads)
    monkeypatch.setattr(host_pool, "_POOL", made)
    tbl = Table.from_numpy({**{f"c{i}": np.arange(rows, dtype="float32") for i in range(8)},
                            "wide": np.arange(rows, dtype="int64") + (1 << 40)})
    padded = tbl.padded_rows
    booked, lock = [], threading.Lock()
    real = devprof.record_transfer

    def noting(direction, nbytes, seconds, label="", shards=0):
        with lock:
            booked.append((direction, nbytes, label, threading.current_thread().name))
        return real(direction, nbytes, seconds, label, shards)

    monkeypatch.setattr(devprof, "record_transfer", noting)
    devprof.reset()
    before = sum(v for _, v in obs.get_metrics().counter("transfer_d2h_bytes_total").items())
    with devprof.node_bracket("fetching"):
        df = tbl.to_pandas()
    if made._executor is not None:
        made._executor.shutdown(wait=True)
    assert df.shape == (rows, 9)
    assert sorted(b[:3] for b in booked) == sorted(
        [("d2h", 5 * padded, "table.to_pandas")] * 9 + [("d2h", 8 * padded, "column.exact_host")])
    on_pool = {b[3] for b in booked} - {threading.current_thread().name}
    assert bool(on_pool) == (rows >= 131_072 and threads > 1)
    out = devprof.results()["fetching"]
    assert out["transfers"] == 10 and out["d2h_bytes"] == (9 * 5 + 8) * padded and out["h2d_bytes"] == 0
    assert out["device_time_s"] + out["dispatch_s"] + out["transfer_s"] + out["host_s"] <= out["wall_s"] + 1e-6
    after = sum(v for _, v in obs.get_metrics().counter("transfer_d2h_bytes_total").items())
    assert after - before == (9 * 5 + 8) * padded


def test_record_transfer_rejects_bad_direction():
    with pytest.raises(ValueError):
        devprof.record_transfer("sideways", 1, 0.0)


def test_transfer_outside_node_counts_globally_only():
    devprof.reset()
    before = obs.get_metrics().counter("transfer_d2h_bytes_total").value()
    devprof.record_transfer("d2h", 64, 0.0, label="orphan")
    assert obs.get_metrics().counter(
        "transfer_d2h_bytes_total").value() == before + 64
    assert devprof.results() == {}  # no frame — no per-node booking


def test_clamp_when_components_exceed_wall(monkeypatch):
    """A drain probe slower than the node wall itself (possible on a
    contended box) must be scaled down, never break the invariant."""
    devprof.reset()
    monkeypatch.setattr(devprof, "_drain_wall", lambda: 3600.0)
    monkeypatch.setattr(devprof, "_PROBE_FLOOR", 0.0)
    with devprof.node_bracket("clamped"):
        pass
    out = devprof.results()["clamped"]
    assert out["clamped"] is True
    total = (out["device_time_s"] + out["dispatch_s"]
             + out["transfer_s"] + out["host_s"])
    assert total <= out["wall_s"] + 1e-9


def test_disabled_via_env(monkeypatch):
    monkeypatch.setenv("ANOVOS_TPU_DEVPROF", "0")
    devprof.reset()
    with devprof.node_bracket("off") as frame:
        assert frame is None
    assert devprof.results() == {}


def test_active_frames_visible_mid_node():
    devprof.reset()
    seen = {}
    with devprof.node_bracket("live"):
        with devprof.dispatch_bracket("ops.mid"):
            pass
        seen = devprof.active_frames()
    assert "live" in seen
    assert seen["live"]["last_op"] == "ops.mid"
    assert devprof.active_frames() == {}  # frame retired at exit


def test_timed_ops_feed_the_active_frame():
    """The obs.timed wrapper enters a dispatch bracket: a timed op called
    under a node bracket books dispatch wall there on its SECOND call
    (first call is compile-phase = host remainder)."""
    from anovos_tpu.obs.timed import timed

    calls = []

    @timed("ops.probe_op")
    def op(x):
        calls.append(x)
        return x

    devprof.reset()
    op(1)  # compile-phase call OUTSIDE the node: seeds the signature set
    with devprof.node_bracket("with_op"):
        op(1)  # same signature: execute phase
    out = devprof.results()["with_op"]
    assert out["dispatches"] == 1
    assert out["last_op"] == "ops.probe_op"


def test_timed_above_jit_fires_on_warm_calls():
    """Regression: @timed must sit ABOVE @jax.jit — underneath, jit traces
    the wrapper once and warm calls bypass it entirely, so dispatch never
    books and last_op never stamps for exactly the kernels GC010 exists
    to cover."""
    import jax.numpy as jnp

    from anovos_tpu import obs
    from anovos_tpu.ops.datetime_kernels import extract_unit
    from anovos_tpu.ops.drift_kernels import drift_side_full  # noqa: F401

    secs = jnp.arange(8, dtype=jnp.int32)
    before = obs.get_metrics().counter("op_cache_hit_total").value(
        op="ops.extract_unit")
    extract_unit(secs, "day")
    extract_unit(secs, "day")
    after = obs.get_metrics().counter("op_cache_hit_total").value(
        op="ops.extract_unit")
    assert after >= before + 1, "warm call bypassed the timed wrapper"


def test_record_transfer_quiet_when_disabled(monkeypatch):
    """Regression: the off switch must silence DIRECT record_transfer
    callers too, not just the brackets."""
    monkeypatch.setenv("ANOVOS_TPU_DEVPROF", "0")
    before = obs.get_metrics().counter("transfer_d2h_bytes_total").value()
    devprof.record_transfer("d2h", 4096, 0.0, label="disabled")
    assert obs.get_metrics().counter(
        "transfer_d2h_bytes_total").value() == before


def test_node_bracket_drain_false_attributes_zero_device():
    devprof.reset()
    with devprof.node_bracket("nodrain", drain=False):
        pass
    assert devprof.results()["nodrain"]["device_time_s"] == 0.0


# ---------------------------------------------------------------------------
# drain probe
# ---------------------------------------------------------------------------

def test_drain_probe_returns_small_wall_on_idle_device():
    devprof.reset()  # warms the probe + measures the floor
    wall = devprof._drain_wall()
    assert 0.0 <= wall < 1.0  # idle CPU mesh: the probe is ~instant


# ---------------------------------------------------------------------------
# workflow integration: the acceptance invariant
# ---------------------------------------------------------------------------

@pytest.fixture()
def small_run(tmp_path, monkeypatch):
    from tools.chaos_run import synthetic_config

    from anovos_tpu import workflow

    cfg = synthetic_config(str(tmp_path))
    rundir = tmp_path / "run"
    rundir.mkdir()
    monkeypatch.chdir(rundir)
    monkeypatch.delenv("ANOVOS_TPU_CACHE", raising=False)
    monkeypatch.delenv("ANOVOS_TPU_CHAOS", raising=False)
    monkeypatch.setenv("ANOVOS_TPU_EXECUTOR", "sequential")
    workflow.main(copy.deepcopy(cfg), "local")
    return obs.load_manifest(workflow.LAST_MANIFEST_PATH)


def test_every_executed_node_has_devprof_entry(small_run):
    """Acceptance: every executed node carries a devprof manifest entry
    whose components sum to ≤ its wall, and whose wall ≤ the scheduler's
    measured node duration."""
    man = small_run
    dev = man.get("devprof") or {}
    nodes = man["scheduler"]["nodes"]
    executed = [n for n, nd in nodes.items()
                if nd.get("state") == "done" and nd.get("dur_s") is not None]
    assert executed, "nothing executed?"
    for name in executed:
        entry = dev.get(name)
        assert entry, f"executed node {name!r} has no devprof entry"
        total = (entry["device_time_s"] + entry["dispatch_s"]
                 + entry["transfer_s"] + entry["host_s"])
        assert total <= entry["wall_s"] + 1e-6, (name, entry)
        # the bracket lives inside the scheduler's node span
        assert entry["wall_s"] <= nodes[name]["dur_s"] + 0.1, (name, entry)


def test_run_books_transfer_bytes(small_run):
    """The synthetic run ingests parquet (h2d) and writes CSV stats
    (d2h via to_pandas): both directions must be nonzero in metrics."""
    metrics = small_run["metrics"]
    h2d = metrics.get("transfer_h2d_bytes_total", {}).get("series", {})
    d2h = metrics.get("transfer_d2h_bytes_total", {}).get("series", {})
    assert sum(h2d.values()) > 0, "no h2d bytes booked"
    assert sum(d2h.values()) > 0, "no d2h bytes booked"


def test_devprof_stripped_from_stable_view(small_run):
    sv = obs.stable_view(small_run)
    assert "devprof" not in sv
    assert not any(k.startswith("devprof_") or k.startswith("transfer_")
                   for k in sv["metrics"])


# ---------------------------------------------------------------------------
# satellite: multi-device memory sampling
# ---------------------------------------------------------------------------

class _FakeDevice:
    def __init__(self, i, in_use, peak):
        self.platform = "faketpu"
        self.id = i
        self._stats = {"bytes_in_use": in_use, "peak_bytes_in_use": peak}

    def memory_stats(self):
        return self._stats


def test_record_device_memory_covers_all_local_devices(monkeypatch):
    import jax

    devices = [_FakeDevice(i, (i + 1) * 1000, (i + 1) * 2000) for i in range(8)]
    monkeypatch.setattr(jax, "local_devices", lambda: devices)
    reg = MetricsRegistry()
    record_device_memory(reg)
    series = reg.gauge("device_bytes_in_use").series()
    assert len(series) == 8, "one gauge series per local device"
    assert reg.gauge("device_bytes_in_use").value(device="faketpu:7") == 8000.0
    # mesh-wide sum + high-water
    assert reg.gauge("device_mesh_bytes_in_use").value() == sum(
        (i + 1) * 1000 for i in range(8))
    hw = reg.gauge("device_mesh_bytes_high_water").value()
    assert hw == reg.gauge("device_mesh_bytes_in_use").value()
    # high-water survives a later, smaller sample
    devices[7]._stats["bytes_in_use"] = 1
    record_device_memory(reg)
    assert reg.gauge("device_mesh_bytes_high_water").value() == hw


def test_record_device_memory_noop_without_stats(monkeypatch):
    import jax

    class _NoStats:
        platform, id = "cpu", 0

        def memory_stats(self):
            return None

    monkeypatch.setattr(jax, "local_devices", lambda: [_NoStats()])
    reg = MetricsRegistry()
    record_device_memory(reg)
    assert reg.snapshot() == {}


# ---------------------------------------------------------------------------
# thread-safety: transfers landing from a second thread
# ---------------------------------------------------------------------------

def test_frame_accumulation_is_thread_safe():
    devprof.reset()
    with devprof.node_bracket("threads"):
        frame = devprof._ACTIVE["threads"]

        def hammer():
            for _ in range(500):
                frame.add_transfer("h2d", 2, 0.0, "t")

        ts = [threading.Thread(target=hammer) for _ in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    out = devprof.results()["threads"]
    assert out["h2d_bytes"] == 4 * 500 * 2
    assert out["transfers"] == 2000
