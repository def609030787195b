"""Device-mesh runtime singleton.

The reference builds a process-wide SparkSession at import time
(shared/spark.py:84-97) and every public function takes it as the first
argument.  Here the analogue is a :class:`Runtime` holding a
``jax.sharding.Mesh`` over the local (or distributed) device set, created
lazily on first use.  Row-sharding of Tables rides the ``"data"`` axis;
the optional ``"model"`` axis exists so very wide tables / model weights can
be column-sharded (tensor-parallel analogue — SURVEY.md §2.10).

Unlike Spark there is no RPC control plane: all cross-device communication is
compiler-scheduled XLA collectives over ICI (psum/all_gather/reduce_scatter),
and multi-host process groups come from ``jax.distributed.initialize`` over
DCN.

Placement (PR 8): a node's execution context is no longer implicitly "the
global mesh".  The DAG executor runs each node under a declarative
:class:`~anovos_tpu.parallel.placement.Placement` — the global mesh, a
carved sub-mesh, or one pinned chip — by entering :func:`placement_scope`
with a :func:`derive_runtime`-built Runtime; ``get_runtime()`` and the
layout-constraint gates resolve through the scope, so every Table and
kernel built inside the node lands on the node's leased devices.  The
chips themselves are handed out by :class:`DeviceLeaseRegistry`
(``Runtime.lease_registry()``), which enforces the rendezvous-lane
invariant: at most one collective claim covers any device.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from contextlib import contextmanager
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"

_RUNTIME: Optional["Runtime"] = None
# bumped by every init_runtime (incl. mid-run failover rebuilds): lease
# registries and derived-runtime caches key their validity on it
_RUNTIME_GEN = 0

# thread-local placement override: a scheduler worker executing a
# device-/submesh-placed node sees a derived Runtime instead of the
# global mesh, so every Table/kernel built inside the node lands on the
# node's leased devices (see parallel/placement.py)
_TL_PLACEMENT = threading.local()


@dataclasses.dataclass
class Runtime:
    """Process-wide execution context (the SparkSession analogue)."""

    mesh: Mesh
    data_axis: str = DATA_AXIS
    model_axis: str = MODEL_AXIS

    @property
    def n_data(self) -> int:
        return self.mesh.shape[self.data_axis]

    @property
    def n_model(self) -> int:
        return self.mesh.shape.get(self.model_axis, 1)

    @property
    def n_devices(self) -> int:
        return self.mesh.size

    def lease_registry(self) -> "DeviceLeaseRegistry":
        """This runtime's chip-lease registry (created on first use) —
        the scheduler's lane arbiter on multi-device meshes."""
        with _DERIVED_LOCK:
            reg = getattr(self, "_leases", None)
            if reg is None:
                reg = DeviceLeaseRegistry(list(self.mesh.devices.flat))
                self._leases = reg
        return reg

    # -- sharding helpers -------------------------------------------------
    def row_sharding(self) -> NamedSharding:
        """Sharding for (rows,) or (rows, cols) arrays: rows over 'data'."""
        return NamedSharding(self.mesh, P(self.data_axis))

    def column_parallel_sharding(self) -> NamedSharding:
        """(rows, k) re-laid column-parallel: each device holds whole
        columns (columns spread over the data axis)."""
        return NamedSharding(self.mesh, P(None, self.data_axis))

    def row_col_sharding(self, shard_cols: bool = False) -> NamedSharding:
        spec = P(self.data_axis, self.model_axis if shard_cols else None)
        return NamedSharding(self.mesh, spec)

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def shard_rows(self, arr) -> jax.Array:
        """Place a host array on device, row-sharded over the data axis.

        This is THE h2d choke point for Table construction, so it carries
        the devprof transfer bracket: exact byte counts, dispatch-side wall
        (``device_put`` is async — the wall is enqueue time, the bytes are
        exact; see ``obs.devprof``)."""
        from anovos_tpu.obs import devprof

        spec = P(*((self.data_axis,) + (None,) * (arr.ndim - 1)))
        with devprof.transfer_bracket("h2d", getattr(arr, "nbytes", 0),
                                      label="runtime.shard_rows", shards=self.n_data):
            return jax.device_put(arr, NamedSharding(self.mesh, spec))

    def shard_rows_of_many(self, arrs: Sequence[np.ndarray]) -> List[jax.Array]:
        """:meth:`shard_rows` of every one of ``arrs`` (each ``(rows,)``) in
        one ``device_put`` call and under one transfer bracket: the same
        arrays on the same sharding, without a Python call an array."""
        from anovos_tpu.obs import devprof

        with devprof.transfer_bracket("h2d", sum(a.nbytes for a in arrs),
                                      label="runtime.shard_rows_of_many", shards=self.n_data):
            return jax.device_put(list(arrs), self.row_sharding())

    def shard_rows_block(self, block: np.ndarray) -> jax.Array:
        """Place a host block of ``(k, rows)``, one row-sharded array a row
        of it, on device with its second axis over the data axis: every
        device gets the shard of each of the ``k`` arrays that
        :meth:`shard_rows` would have given it, in one transfer."""
        from anovos_tpu.obs import devprof

        with devprof.transfer_bracket("h2d", block.nbytes,
                                      label="runtime.shard_rows_block", shards=self.n_data):
            return jax.device_put(block, NamedSharding(self.mesh, P(None, self.data_axis)))

    def pad_rows(self, n: int) -> int:
        """Rows are padded to a multiple of the data-axis size so every
        shard has identical (static) shape — XLA requires static shapes.

        On top of that, row counts are bucketed into geometric size classes
        (2^k and 1.5·2^k — ≤33% padding waste) so tables with nearby row
        counts share compiled program shapes: every jit is keyed on the
        padded shape, and on a remote-compile backend each novel shape costs
        seconds of XLA compile.  Padding rows carry mask=False, so kernels
        are unaffected.  ANOVOS_SHAPE_BUCKETS=0 disables the bucketing."""
        m = self.n_data
        if os.environ.get("ANOVOS_SHAPE_BUCKETS", "1") != "0" and n > 256:
            b = 256
            while b < n:
                if (c := b + b // 2) >= n:  # 1.5·2^k class between doublings
                    b = c
                    break
                b *= 2
            n = b
        return ((n + m - 1) // m) * m

    # column-axis floor: blocks this narrow are left exact — the compile
    # saving cannot repay padding a 1-2 column kernel to 4+ lanes, and the
    # per-column transformer paths routinely stack single columns
    PAD_COLS_FLOOR = 4

    def pad_cols(self, k: int) -> int:
        """Column-axis size class for a stacked (rows, k) block.

        Same static-shape discipline as :meth:`pad_rows`, applied to the
        column axis of ``Table.numeric_block``: per-block column subsets of
        nearby widths are padded up to geometric 2^j / 1.5·2^j classes
        (≤33% padding waste) so they reuse compiled program shapes instead
        of each paying a fresh XLA compile — the round-5 census measured
        the ×3-×11 repeat compiles on the cold path to be exactly these
        column-count shape variants (PERF.md).  Padding lanes carry
        mask=False, so masked kernels never see them; consumers slice
        per-column outputs back to the live ``k``.

        ``ANOVOS_SHAPE_BUCKETS=0`` disables bucketing on BOTH axes; widths
        at or below the floor (4) stay exact either way."""
        if k <= self.PAD_COLS_FLOOR or os.environ.get("ANOVOS_SHAPE_BUCKETS", "1") == "0":
            return k
        b = self.PAD_COLS_FLOOR
        while b < k:
            if (c := b + b // 2) >= k:  # 1.5·2^j class between doublings
                b = c
                break
            b *= 2
        return b


# JAX's persistent compilation cache, when ``JAX_COMPILATION_CACHE_DIR`` does
# not place it: one fixed directory of the checkout, resolved from this
# file and never from cwd, a temp name, a pid or the time — the directory is
# part of the cache key, so a cache that moves never hits.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def _stamp_unstamped_cache_entries() -> int:
    """Where the compile cache has a size limit (``jax_compilation_cache_max_size``;
    the chip machine sets ``JAX_COMPILATION_CACHE_MAX_SIZE``) JAX keeps an
    ``-atime`` file beside every ``-cache`` entry and reads them all before
    each write.  An entry without one, left by a process that used the
    directory without the limit, makes every write fail, and every later
    process compiles everything again (PERF.md, PR 24 and PR 25).  Such
    entries get the stamp of now and age out like the rest.  Returns the
    ``-cache`` entries it listed (0 where it lists none)."""
    cache_dir = jax.config.jax_compilation_cache_dir
    if not cache_dir or "://" in cache_dir or jax.config.jax_compilation_cache_max_size == -1:
        return 0
    try:
        names = set(os.listdir(cache_dir))
    except OSError:
        return 0  # not there yet: JAX makes it
    stamp = time.time_ns().to_bytes(8, "little")
    entries = [name for name in names if name.endswith("-cache")]
    for name in entries:
        if name[: -len("cache")] + "atime" not in names:
            try:
                with open(os.path.join(cache_dir, name[: -len("cache")] + "atime"), "wb") as f:
                    f.write(stamp)
            except OSError:
                pass
    return len(entries)


@contextmanager
def placement_scope(rt: Optional["Runtime"]):
    """Thread-local runtime override for one scheduler node's execution.

    Inside the scope, :func:`get_runtime` (and the sharding-constraint
    gates) resolve to ``rt`` — typically a 1-device or carved sub-mesh
    runtime derived by :func:`derive_runtime` — so tables and kernels
    built by the node body place onto the node's leased devices instead
    of the global mesh.  ``None`` is a no-op scope."""
    prev = getattr(_TL_PLACEMENT, "runtime", None)
    _TL_PLACEMENT.runtime = rt
    try:
        yield rt
    finally:
        _TL_PLACEMENT.runtime = prev


def active_placement_runtime() -> Optional["Runtime"]:
    """The thread's placement-override runtime, or None outside a scope."""
    return getattr(_TL_PLACEMENT, "runtime", None)


def _current_runtime() -> Optional["Runtime"]:
    """Placement override if active on this thread, else the global
    runtime (or None before init) — the layout-gate resolution rule."""
    return getattr(_TL_PLACEMENT, "runtime", None) or _RUNTIME


def peek_runtime() -> Optional["Runtime"]:
    """The global runtime WITHOUT initializing one (scheduler lane setup
    must never be the thing that drags a jax backend up)."""
    return _RUNTIME


def runtime_generation() -> int:
    """Monotonic counter bumped by every :func:`init_runtime` (including
    mid-run failover rebuilds) — consumers holding derived state (lease
    registries, sub-mesh runtimes) use it to notice a stale device set."""
    return _RUNTIME_GEN


_DERIVED: Dict[Tuple[int, Tuple[int, ...]], "Runtime"] = {}
_DERIVED_LOCK = threading.Lock()


def derive_runtime(devices: Sequence[jax.Device]) -> Runtime:
    """A Runtime over a subset of the global mesh's devices (all on the
    data axis) — the execution context of a ``device``/``submesh``-placed
    node.  Cached per (runtime generation, device-id tuple) so repeated
    node executions reuse one Mesh object (and therefore one jit cache
    key) instead of recompiling per call."""
    devs = tuple(devices)
    key = (_RUNTIME_GEN, tuple(d.id for d in devs))
    with _DERIVED_LOCK:
        rt = _DERIVED.get(key)
        if rt is None:
            mesh = Mesh(np.array(devs).reshape(len(devs), 1),
                        (DATA_AXIS, MODEL_AXIS))
            rt = Runtime(mesh=mesh)
            _DERIVED[key] = rt
    return rt


@dataclasses.dataclass
class DeviceLease:
    """One node's claim on chips.  ``kind`` mirrors the placement kind;
    ``devices`` is empty for host leases."""

    holder: str
    kind: str
    devices: Tuple[jax.Device, ...] = ()

    def device_labels(self) -> List[str]:
        return [f"{d.platform}:{d.id}" for d in self.devices]


class DeviceLeaseRegistry:
    """Hands out chips to scheduler nodes under the lane discipline.

    Invariants enforced:

    * at most ONE collective claim may cover any given device — the
      rendezvous lane.  A ``mesh`` claim covers every device, so it is
      exclusive against all collective claims; two ``submesh`` claims
      may coexist only on disjoint device sets.  ``mesh`` claims of one
      ``group`` count as one claim and may coexist: the group's members
      order their own device work (the workflow's readers of one table
      version run it under that version's lock), so the lane still sees
      one program at a time.
    * ``device`` claims never block (single-device programs carry no
      rendezvous, so sharing a chip with anything merely timeshares it).
      Chip choice is STICKY by holder name — XLA executables are keyed on
      their device assignment, so a node that hopped chips between runs
      (or between the sequential and concurrent executors) would recompile
      its programs per chip; the name-hashed preference keeps every node's
      programs on one chip across runs and executors, falling back to the
      least-claimed free chip only under a live collision.
    * ``host`` claims are bookkeeping only.

    Thread-safe; ``try_*`` never blocks (the scheduler polls under its
    own condition variable and retries when a release notifies it).
    """

    def __init__(self, devices: Sequence[jax.Device]):
        self._devices = tuple(devices)
        self._lock = threading.Lock()
        self._collective: Dict[str, Tuple[jax.Device, ...]] = {}
        self._group: Dict[str, Optional[str]] = {}  # holder -> group of its mesh claim
        self._single_load: Dict[int, int] = {d.id: 0 for d in self._devices}

    @property
    def n_devices(self) -> int:
        return len(self._devices)

    def _collective_covered(self) -> set:
        out = set()
        for devs in self._collective.values():
            out.update(d.id for d in devs)
        return out

    def try_lease(self, holder: str, kind: str, n_devices: int = 0,
                  group: Optional[str] = None) -> Optional[DeviceLease]:
        """A lease for ``holder`` under placement ``kind``, or None when
        the lane is busy (collective kinds only — device/host always
        succeed).  ``group`` (``mesh`` only): the shared claim to join."""
        with self._lock:
            if kind == "host":
                return DeviceLease(holder, "host")
            if kind == "device":
                import hashlib

                pref = self._devices[
                    int.from_bytes(
                        hashlib.sha256(holder.encode()).digest()[:4], "big")
                    % len(self._devices)]
                if self._single_load[pref.id] == 0:
                    dev = pref
                else:
                    covered = self._collective_covered()
                    dev = min(
                        self._devices,
                        key=lambda d: (self._single_load[d.id],
                                       d.id in covered, d.id),
                    )
                self._single_load[dev.id] += 1
                return DeviceLease(holder, "device", (dev,))
            if kind == "mesh":
                if any(group is None or self._group.get(h) != group
                       for h in self._collective):
                    return None
                self._collective[holder] = self._devices
                self._group[holder] = group
                return DeviceLease(holder, "mesh", self._devices)
            if kind == "submesh":
                covered = self._collective_covered()
                free = [d for d in self._devices if d.id not in covered]
                if len(free) < n_devices:
                    return None
                devs = tuple(free[:n_devices])
                self._collective[holder] = devs
                return DeviceLease(holder, "submesh", devs)
            raise ValueError(f"unknown lease kind {kind!r}")

    def release(self, lease: Optional[DeviceLease]) -> None:
        if lease is None:
            return
        with self._lock:
            if lease.kind in ("mesh", "submesh"):
                self._collective.pop(lease.holder, None)
                self._group.pop(lease.holder, None)
            elif lease.kind == "device":
                for d in lease.devices:
                    if self._single_load.get(d.id, 0) > 0:
                        self._single_load[d.id] -= 1

    def collective_holders(self) -> List[str]:
        """Nodes currently holding the rendezvous lane (postmortems)."""
        with self._lock:
            return sorted(self._collective)


def init_runtime(
    devices: Optional[Sequence[jax.Device]] = None,
    mesh_shape: Optional[tuple] = None,
    distributed: bool = False,
) -> Runtime:
    """Build (or rebuild) the global Runtime.

    ``mesh_shape=(n_data, n_model)``; defaults to all devices on the data
    axis.  ``distributed=True`` calls ``jax.distributed.initialize()`` first
    (multi-host over DCN; env-driven coordinator discovery).
    """
    global _RUNTIME, _RUNTIME_GEN
    from anovos_tpu.obs.tracing import get_tracer

    # a phase of the pass that first needs the runtime (an ordinary span
    # outside any): the backend's start where nothing touched it before, the
    # listing of the cache directory, the mesh
    with get_tracer().phase("runtime/init", cat="runtime") as span:
        # compile census from the first device touch: every XLA backend compile
        # in this process is counted with per-program attribution (obs
        # subsystem; the run manifest embeds the per-run delta)
        try:
            from anovos_tpu.obs.compile_census import install as _install_census

            _install_census()
        except Exception:
            pass
        # TPU MXU's default f32 matmul precision is bf16 inputs — catastrophic
        # for the quadratic-expansion distance/covariance kernels (squared lat/lon
        # magnitudes produced within-eps errors ~800x eps^2).  A stats framework
        # needs true-f32 matmuls; ANOVOS_MATMUL_PRECISION overrides (e.g. to
        # "default" for throughput-over-accuracy experiments).
        jax.config.update(
            "jax_default_matmul_precision", os.environ.get("ANOVOS_MATMUL_PRECISION", "highest")
        )
        # persistent XLA compilation cache, on by default: pipeline stages
        # produce many distinct table shapes, and compilation dominates
        # cold-run wall time.  Where JAX_COMPILATION_CACHE_DIR is set JAX has
        # already read it and no directory is set in code.
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
        # The pipeline is ~200 SMALL programs, so the threshold must sit well
        # below jax's 1s default.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.02)
        span.add(cache_entries=_stamp_unstamped_cache_entries())
        if distributed and jax.process_count() == 1 and "JAX_COORDINATOR_ADDRESS" in os.environ:
            jax.distributed.initialize()
        devs = list(devices if devices is not None else jax.devices())
        span.add(devices=len(devs))
        if mesh_shape is None:
            mesh_shape = (len(devs), 1)
        n_data, n_model = mesh_shape
        if n_data * n_model != len(devs):
            raise ValueError(f"mesh_shape {mesh_shape} != device count {len(devs)}")
        dev_grid = np.array(devs).reshape(n_data, n_model)
        mesh = Mesh(dev_grid, (DATA_AXIS, MODEL_AXIS))
        _RUNTIME_GEN += 1
        _RUNTIME = Runtime(mesh=mesh)
    return _RUNTIME


def get_runtime() -> Runtime:
    override = getattr(_TL_PLACEMENT, "runtime", None)
    if override is not None:
        return override
    global _RUNTIME
    if _RUNTIME is None:
        _RUNTIME = init_runtime()
    return _RUNTIME


def column_parallel(a: jax.Array, cp: bool = True) -> jax.Array:
    """Order-statistics layout constraint for a (rows, k) block.

    A sort along the row-sharded axis is the worst collective pattern
    GSPMD can emit — O(log n) cross-device partition exchanges per sort
    (measured: describe_numeric 6.5 s vs 0.07 s on the 8-virtual-device
    mesh at 32k x 9).  Re-laying the block column-parallel costs ONE small
    all-to-all, after which every downstream sort / take_along_axis /
    cummax is device-local; column-wise reductions of the result come back
    over the same axis.  Moments and other row-reductions should stay on
    the row sharding (partial-sum + psum is optimal there) — apply this
    only to the input of sort-based statistics.

    Apply INSIDE a jit, passing the kernel's static ``cp`` argument —
    computed by :func:`wants_column_parallel` on the jit's CONCRETE inputs
    (a committed single-device array constrained onto a multi-device mesh
    is an incompatible-devices error).  No-op when ``cp`` is false, on a
    1-device mesh, or before the runtime exists.
    """
    rt = _current_runtime()
    if not cp or rt is None or rt.mesh.size == 1:
        return a
    return jax.lax.with_sharding_constraint(
        a, rt.column_parallel_sharding()
    )


def replicated(a: jax.Array, cp: bool = True) -> jax.Array:
    """Replicate a small array across the mesh (companion to
    :func:`column_parallel` for the (rows,) id/validity vectors that every
    column-parallel lane needs in full).  Same gating contract."""
    rt = _current_runtime()
    if not cp or rt is None or rt.mesh.size == 1:
        return a
    return jax.lax.with_sharding_constraint(
        a, NamedSharding(rt.mesh, P(*([None] * a.ndim)))
    )


def row_sharded(a: jax.Array, cp: bool = True) -> jax.Array:
    """Constrain a (rows, ...) result back onto the row sharding.  Kernels
    that replicate their inputs for device-local sorts must NOT return
    row-length outputs replicated — a persisted replicated column occupies
    every device for the table's lifetime, unbounded by the transient
    replication guard.  Same gating contract as :func:`column_parallel`."""
    rt = _current_runtime()
    if not cp or rt is None or rt.mesh.size == 1:
        return a
    return jax.lax.with_sharding_constraint(a, rt.row_sharding())


def replicate_gate(*arrays) -> bool:
    """Gate for kernels whose whole input set replicates for device-local
    sorts (1-D ts/window programs): drops Nones and applies the size guard
    to everything."""
    arrs = tuple(a for a in arrays if a is not None)
    return wants_column_parallel(*arrs, replicate=arrs)


def wants_column_parallel(*arrays, replicate=()) -> bool:
    """Gate for :func:`column_parallel`, evaluated on CONCRETE jit inputs.

    True iff the runtime mesh is multi-device and every given array
    verifiably lives on exactly that mesh's devices.  Tracers (nested-jit
    callers) and committed single-device arrays return False — the
    constraint would either be unverifiable or an incompatible-devices
    error; the kernel then runs unconstrained, which is merely the old
    layout, never wrong.

    ``replicate``: the arrays the kernel will feed to :func:`replicated`
    under the re-lay (1-D id/value vectors).  The gate sums their sizes
    itself — callers name the arrays, not a hand-computed byte count —
    and refuses above ``ANOVOS_REPLICATE_MAX_BYTES`` (default 256 MB):
    a row-sharded sort is slow but memory-bounded, while an unbounded
    per-device replica of a billion-row id column is an OOM.  The
    (rows, k) column-parallel re-lay itself does not change total
    footprint and needs no guard.
    """
    rt = _current_runtime()
    if rt is None or rt.mesh.size == 1:
        return False
    rep_bytes = sum(int(a.size) * a.dtype.itemsize for a in replicate)
    if rep_bytes > int(os.environ.get("ANOVOS_REPLICATE_MAX_BYTES", 1 << 28)):
        return False
    mesh_devs = set(rt.mesh.devices.flat)
    for a in arrays:
        try:
            ds = a.sharding.device_set
        except Exception:
            return False
        if set(ds) != mesh_devs:
            return False
    return True
