"""The partition-arrival loop: poll → decode new parts → fold → re-
finalize → re-render → alert → snapshot.

One :func:`step` is the unit of work (the CLI's ``step`` command, the
``continuous_analysis`` workflow node, and each iteration of ``run``):

1. **scan** — the dataset directory's part files classified against the
   folded state by PR 10 stat signature (new / changed / retracted /
   unchanged / still-quarantined);
2. **decode** — only the new/changed parts, through the PR 12
   :class:`~anovos_tpu.data_ingest.prefetch.DecodePool` (quarantine /
   reconcile / sanitize semantics intact: a corrupt day quarantines,
   lands in the Degraded Sections banner via the guard's
   ``record_degraded`` wiring, and is remembered by signature so it is
   not re-attempted every poll);
3. **fold** — each decoded partition's sufficient-stat partials commit
   individually (WAL ``fold_commit`` — the mid-fold crash window is one
   partition, never the arrival batch);
4. **finalize** — artifacts re-derive from the keyed partial maps
   (O(partitions · k), never O(history rows)) and only the report
   sections whose inputs changed re-render
   (``data_report.continuum_report``);
5. **alert** — per-arrival drift/quality threshold crossings emit
   structured JSON with flight-recorder context
   (``anovos_tpu.continuum.alerts``);
6. **snapshot** — the new fold frontier commits content-addressed into
   the PR 5 CacheStore (WAL ``snapshot_commit``).

Drift rides the persisted model (``DriftSpec.model_dir`` — the PR 12
streaming drift model layout).  With no model on disk yet, the watcher
fits one from the configured ``baseline`` partitions the moment they are
all folded: cutoffs from the baseline's merged moments
(``cutoffs_from_bounds`` — the exact streaming-fit tail), categorical
source frequencies from the baseline's counters (no decode), numeric
source frequencies from ONE re-decode of the baseline partitions
(journaled ``model_fitted``).  Partitions folded before the model
existed re-fold once it lands, so arrival order never changes the final
state.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import time
from typing import Dict, List, Optional, Tuple

import numpy as np
import pandas as pd

from anovos_tpu.cache.fingerprint import canonical, digest
from anovos_tpu.continuum import alerts as alerts_mod
from anovos_tpu.continuum.state import ContinuumState, part_signature
from anovos_tpu.continuum.sufficient import (
    ACCUMULATORS,
    DriftSpec,
    FoldContext,
    MomentsAccumulator,
)
from anovos_tpu.obs import timed

logger = logging.getLogger("anovos_tpu.continuum.watcher")

__all__ = ["ContinuumConfig", "step", "run", "status", "poll_seconds"]


def poll_seconds(default: float = 30.0) -> float:
    """``ANOVOS_CONTINUUM_POLL_S`` (audited knob) overrides the config's
    poll interval."""
    raw = os.environ.get("ANOVOS_CONTINUUM_POLL_S", "")
    if raw:
        try:
            return max(0.0, float(raw))
        except ValueError:
            pass
    return default


@dataclasses.dataclass
class ContinuumConfig:
    """The ``continuous_analysis`` config section, resolved."""

    dataset_path: str
    file_type: str = "parquet"
    state_dir: str = "continuum_state"
    output_path: str = "continuum_artifacts"
    file_configs: Optional[dict] = None
    list_of_cols: object = "all"
    drop_cols: Tuple[str, ...] = ()
    hll_rsd: float = 0.05
    outlier_model_path: str = ""
    drift: Optional[dict] = None          # model_path/bin_size/method_type/threshold/baseline
    stability: Optional[dict] = None      # metric_weightages/threshold/binary_cols
    alert_thresholds: Optional[dict] = None
    poll_s: float = 30.0
    cache_dir: str = ""                   # snapshot store root ("" = <state_dir>/cache)

    @classmethod
    def from_dict(cls, d: dict, base_dir: str = ".") -> "ContinuumConfig":
        d = dict(d or {})
        path = d.get("dataset_path") or d.get("file_path")
        if not path:
            raise TypeError("continuous_analysis requires dataset_path")

        def _resolve(p, default):
            p = p or default
            return p if os.path.isabs(p) else os.path.join(base_dir, p)

        return cls(
            dataset_path=path if os.path.isabs(path) else os.path.join(base_dir, path),
            file_type=d.get("file_type", "parquet") or "parquet",
            state_dir=_resolve(d.get("state_dir"), "continuum_state"),
            output_path=_resolve(d.get("output_path"), "continuum_artifacts"),
            file_configs=d.get("file_configs"),
            list_of_cols=d.get("list_of_cols", "all") or "all",
            drop_cols=tuple(d.get("drop_cols") or ()),
            hll_rsd=float(d.get("hll_rsd", 0.05) or 0.05),
            outlier_model_path=d.get("outlier_model_path", "") or "",
            drift=d.get("drift"),
            stability=d.get("stability"),
            alert_thresholds=d.get("alerts"),
            poll_s=float(d.get("poll_s", 30.0) or 30.0),
            cache_dir=d.get("cache_dir", "") or "",
        )

    # -- derived -----------------------------------------------------------
    def config_sig(self) -> str:
        """Feed identity: everything that changes partials or artifacts.
        Paths stay OUT (the same feed config in a moved directory must
        reuse its state); the drift model content is covered by the
        fitted-cutoff persistence, not the key."""
        return digest(canonical({
            "file_type": self.file_type,
            "list_of_cols": self.list_of_cols,
            "drop_cols": list(self.drop_cols),
            "hll_rsd": self.hll_rsd,
            "outlier": bool(self.outlier_model_path),
            "drift": {k: (self.drift or {}).get(k)
                      for k in ("bin_size", "method_type", "threshold", "baseline")}
            if self.drift else None,
            "stability": self.stability,
        }))

    def drift_spec(self) -> Optional[DriftSpec]:
        if not self.drift:
            return None
        d = dict(self.drift)
        model_dir = d.get("model_path") or os.path.join(self.state_dir, "drift_model")
        if not os.path.isabs(model_dir):
            model_dir = os.path.join(os.path.dirname(self.state_dir) or ".", model_dir)
        return DriftSpec(
            model_dir=model_dir,
            bin_size=int(d.get("bin_size", 10) or 10),
            method_type=d.get("method_type", "PSI") or "PSI",
            threshold=float(d.get("threshold", 0.1) or 0.1),
            baseline=d.get("baseline", "") or "",
        )

    def fold_context(self) -> FoldContext:
        from anovos_tpu.ops.hll import precision_for_rsd

        bounds = None
        if self.outlier_model_path:
            from anovos_tpu.data_analyzer.quality_checker import _load_outlier_model

            bounds = {c: tuple(b) for c, b in
                      _load_outlier_model(self.outlier_model_path)[0].items()}
        spec = self.drift_spec()
        ctx = FoldContext(
            list_of_cols=self.list_of_cols,
            drop_cols=tuple(self.drop_cols),
            hll_p=precision_for_rsd(self.hll_rsd),
            outlier_bounds=bounds,
            drift=spec,
            drift_cutoffs=_load_cutoffs(spec) if spec else None,
        )
        return ctx


def _load_cutoffs(spec: DriftSpec) -> Optional[Dict[str, np.ndarray]]:
    """The persisted binning model's interior cutoffs, or None when no
    model exists yet (the watcher may fit one from the baseline)."""
    from anovos_tpu.data_transformer.model_io import load_model_df

    path = os.path.join(spec.model_dir, "attribute_binning")
    if not os.path.isdir(path) and not os.path.isfile(path):
        return None
    try:
        dfm = load_model_df(spec.model_dir, "attribute_binning")
    except Exception as e:
        logger.exception("drift model at %s unreadable; drift inactive",
                         spec.model_dir)
        # not a silent fallback: the feed keeps running without drift,
        # and the degraded-section registry names the reason
        from anovos_tpu.resilience.policy import record_degraded

        record_degraded("continuum/drift_model",
                        f"unreadable drift model: {type(e).__name__}: {e}")
        return None
    return {str(r["attribute"]): np.asarray(list(r["parameters"]), np.float64)
            for _, r in dfm.iterrows()}


def _open_state(cfg: ContinuumConfig, ctx: FoldContext):
    """(state, snapshot store).  A missing/foreign state dir restores
    from the newest committed snapshot in the store when one exists."""
    from anovos_tpu.cache.store import CacheStore

    cache_dir = cfg.cache_dir or os.path.join(cfg.state_dir, "cache")
    store = CacheStore(cache_dir)
    sig = cfg.config_sig()
    manifest = os.path.join(cfg.state_dir, "state_manifest.json")
    if not os.path.exists(manifest):
        restored = ContinuumState.restore_from_store(store, cfg.state_dir, sig, ctx)
        if restored is not None:
            logger.info("continuum state restored from snapshot store (%d parts)",
                        len(restored.parts))
            return restored, store
    return ContinuumState(cfg.state_dir, sig, ctx), store


def _decode_parts(cfg: ContinuumConfig, state: ContinuumState,
                  keys: List[str]) -> Tuple[Dict[str, pd.DataFrame], List[str]]:
    """Decode ``keys`` (canonical part keys) through the prefetch pool.
    Returns (decoded frames by key, quarantined keys).  Quarantine /
    reconcile / sanitize semantics are the guarded reader's — the pool
    only moves where the decode runs."""
    from anovos_tpu.data_ingest.guard import IngestError, policy_from_env
    from anovos_tpu.data_ingest.prefetch import (
        DecodePool,
        StreamController,
        StreamStats,
    )

    frames: Dict[str, pd.DataFrame] = {}
    bad: List[str] = []
    if not keys:
        return frames, bad
    root = os.path.abspath(cfg.dataset_path)
    files = [os.path.join(root, k) for k in keys]
    ctl, stats = StreamController(), StreamStats()
    pool = (DecodePool(files, cfg.file_type, dict(cfg.file_configs or {}),
                       ctl, stats=stats, journal=state.journal)
            if ctl.workers > 0 else None)
    from anovos_tpu.obs import telemetry

    try:
        for fi, (key, f) in enumerate(zip(keys, files)):
            sig = part_signature(f)
            # decode is the longest phase of a catch-up step: keep the
            # service loop's heartbeat (if one is registered) fresh per
            # part so /healthz never pages a watcher that is busy decoding
            telemetry.refresh_heartbeat("continuum_watcher")
            try:
                if pool is not None:
                    frames[key] = pool.fetch(fi, f)
                else:
                    from anovos_tpu.data_ingest.data_ingest import read_host_frame

                    frames[key] = read_host_frame([f], cfg.file_type,
                                                  dict(cfg.file_configs or {}))
            except IngestError as e:
                if policy_from_env().on_corrupt == "raise":
                    raise
                # the guard already quarantined + record_degraded'd the
                # part; the state remembers the bad SIGNATURE so an
                # unchanged corrupt day is not re-attempted every poll
                state.mark_quarantined(key, f, sig or "gone",
                                       f"{type(e).__name__}: {e}")
                bad.append(key)
    finally:
        if pool is not None:
            pool.close()
    return frames, bad


def _fit_drift_model(cfg: ContinuumConfig, state: ContinuumState,
                     ctx: FoldContext) -> bool:
    """Fit + persist the drift source model from the folded baseline
    partitions (no model on disk yet).  Cutoffs come from the baseline's
    merged moments — zero decode; numeric source frequencies need the
    baseline binned over those fresh cutoffs — ONE re-decode of the
    baseline partitions, journaled.  Returns True when a model landed."""
    import jax.numpy as jnp

    from anovos_tpu.data_transformer.model_io import save_model_df
    from anovos_tpu.drift_stability.drift_detector import _drop_allnan_cutoffs
    from anovos_tpu.ops.drift_kernels import binned_histograms, cutoffs_from_bounds, device_cutoffs

    spec = ctx.drift
    if spec is None or not spec.baseline:
        return False
    base_keys = [k for k in state.folded_keys() if spec.is_baseline(k)]
    if not base_keys:
        return False
    mom = ACCUMULATORS["moments"].reduce(state.family_state("moments", base_keys))
    if mom is None:
        return False
    from anovos_tpu.continuum.sufficient import _cols_of

    num_cols = _cols_of(mom)
    cut_rows: List[Tuple[str, np.ndarray]] = []
    if num_cols:
        cuts = cutoffs_from_bounds(np.asarray(mom["min"], np.float32), np.asarray(mom["max"], np.float32),
                                   mom["n"], spec.bin_size)
        cuts64, kept_cols, _ = _drop_allnan_cutoffs(cuts[: len(num_cols)], num_cols)
        cut_rows = list(zip(kept_cols, cuts64))
    cut_map = {c: np.asarray(v, np.float64) for c, v in cut_rows}
    src_rows = sum(int(state.parts[k]["rows"]) for k in base_keys)

    # numeric source histograms: the one re-decode (baseline only, once)
    num_counts: Dict[str, np.ndarray] = {c: np.zeros(spec.bin_size, np.int64)
                                         for c in cut_map}
    redecoded = 0
    if cut_map:
        frames, _bad = _decode_parts(cfg, state, base_keys)
        for key in sorted(frames):
            from anovos_tpu.continuum.sufficient import PartFrame

            part = PartFrame(frames[key], ctx)
            cols = [c for c in part.num_cols if c in cut_map]
            if not cols:
                continue
            v, m = part.device_block()
            k_pad = int(v.shape[1])
            cuts_pad = np.full((k_pad, spec.bin_size - 1), np.nan, np.float32)
            for j, c in enumerate(part.num_cols):
                if c in cut_map:
                    cuts_pad[j] = device_cutoffs(cut_map[c])
            hist = np.asarray(binned_histograms(
                v, m, jnp.asarray(cuts_pad), spec.bin_size))
            for c in cols:
                num_counts[c] += hist[part.num_cols.index(c)].astype(np.int64)
            redecoded += 1

    os.makedirs(spec.model_dir, exist_ok=True)
    if cut_map:
        save_model_df(
            pd.DataFrame({"attribute": [c for c, _ in cut_rows],
                          "parameters": [list(map(float, v)) for _, v in cut_rows]}),
            spec.model_dir, "attribute_binning")
    # categorical source frequencies: straight from the baseline counters
    cat_state = state.family_state("categorical", base_keys)
    cat_agg = ACCUMULATORS["categorical"].reduce(cat_state)
    from anovos_tpu.continuum.sufficient import CategoricalAccumulator

    cat_counts = CategoricalAccumulator.counters(cat_agg) if cat_agg else {}
    denom = max(src_rows, 1)
    from anovos_tpu.drift_stability.drift_detector import save_frequency_map

    for c in sorted(set(cut_map) | set(cat_counts)):
        if c in cut_map:
            keys = list(range(1, spec.bin_size + 1))
            p = (num_counts[c] / denom).tolist()
        else:
            keys = sorted(cat_counts[c])
            p = [cat_counts[c][k] / denom for k in keys]
        save_frequency_map(spec.model_dir, c, keys, p)
    state.journal.append("model_fitted", baseline_parts=len(base_keys),
                         source_rows=src_rows, redecoded_parts=redecoded,
                         num_cols=len(cut_map), cat_cols=len(cat_counts))
    ctx.drift_cutoffs = cut_map
    return True


def _finalize_artifacts(cfg: ContinuumConfig, state: ContinuumState,
                        ctx: FoldContext) -> Dict[str, pd.DataFrame]:
    """Every artifact frame re-derived from the current partial maps."""
    arts: Dict[str, pd.DataFrame] = {}
    stats = ACCUMULATORS["moments"].finalize(state.family_state("moments"), ctx)
    hll = ACCUMULATORS["hll"].finalize(state.family_state("hll"), ctx)
    if len(stats) and len(hll):
        stats = stats.merge(hll, on="attribute", how="left")
    arts["stats"] = stats
    arts["missing"] = ACCUMULATORS["missing"].finalize(
        state.family_state("missing"), ctx)
    arts["categorical"] = ACCUMULATORS["categorical"].finalize(
        state.family_state("categorical"), ctx)
    if ctx.outlier_bounds:
        arts["outlier"] = ACCUMULATORS["outlier"].finalize(
            state.family_state("outlier"), ctx)
    if ctx.drift is not None and ctx.drift_cutoffs is not None:
        arts["drift"] = ACCUMULATORS["drift_target"].finalize(
            state.family_state("drift_target"), ctx)
    hist = _stability_history(state)
    if len(hist):
        arts["stability_history"] = hist
        from anovos_tpu.drift_stability.stability import stability_frame_from_history

        stab_cfg = dict(cfg.stability or {})
        arts["stability"] = stability_frame_from_history(
            hist,
            metric_weightages=stab_cfg.get(
                "metric_weightages", {"mean": 0.5, "stddev": 0.3, "kurtosis": 0.2}),
            threshold=float(stab_cfg.get("threshold", 1)),
            binary_cols=stab_cfg.get("binary_cols", []),
        )
    return arts


def _stability_history(state: ContinuumState) -> pd.DataFrame:
    """Per-partition metric history: each folded partition is one run
    index, numbered in CANONICAL (sorted part key) order — a new arrival
    appends a new index, and arrival order never renumbers history."""
    rows = []
    mom_state = state.family_state("moments")
    for idx, key in enumerate(sorted(mom_state), start=1):
        pm = MomentsAccumulator.part_metrics(mom_state[key])
        pm.insert(0, "idx", idx)
        pm.insert(1, "partition", key)
        rows.append(pm)
    if not rows:
        return pd.DataFrame(columns=["idx", "partition", "attribute",
                                     "mean", "stddev", "kurtosis"])
    return pd.concat(rows, ignore_index=True)


_ARTIFACT_FILES = {
    "stats": "continuum_stats.csv",
    "missing": "continuum_missing.csv",
    "categorical": "continuum_categorical.csv",
    "outlier": "continuum_outlier.csv",
    "drift": "continuum_drift.csv",
    "stability": "continuum_stability.csv",
    "stability_history": "continuum_stability_history.csv",
}


def _write_artifacts(out_dir: str, arts: Dict[str, pd.DataFrame]) -> Dict[str, str]:
    os.makedirs(out_dir, exist_ok=True)
    paths = {}
    for name, df in arts.items():
        path = os.path.join(out_dir, _ARTIFACT_FILES[name])
        tmp = path + ".tmp"
        df.to_csv(tmp, index=False)
        os.replace(tmp, path)
        paths[name] = path
    return paths


@timed("continuum.step")
def step(cfg: ContinuumConfig) -> dict:
    """One arrival-loop iteration; returns the step summary (also
    journaled as ``step_end``)."""
    from anovos_tpu.data_ingest.data_ingest import _resolve_files
    from anovos_tpu.obs import get_metrics

    t0 = time.monotonic()
    ctx = cfg.fold_context()
    # arm the flight recorder when nothing else did (the standalone CLI /
    # service path; a workflow run already pointed it at the run's obs/):
    # every WAL event then lands in the ring, and alerts carry the real
    # lead-up context instead of an empty list
    from anovos_tpu.obs import flight
    from anovos_tpu.resilience import chaos

    obs_dir = os.path.join(cfg.output_path, "obs")
    if not flight.enabled():
        flight.configure(obs_dir)
    # standalone service path: honor ANOVOS_TPU_CHAOS when no plan is
    # installed yet (inside a workflow run, main() already installed it)
    if chaos.plan() is None:
        chaos.install_from_env()
    state, store = _open_state(cfg, ctx)
    state.journal.append("step_begin", dataset=os.path.abspath(cfg.dataset_path))

    try:
        files = _resolve_files(cfg.dataset_path, cfg.file_type)
    except (OSError, ValueError):
        files = []
    scan = state.scan(files, cfg.dataset_path)
    for key in scan.new:
        state.journal.append("partition_seen", part=key, status="new")
    for key in scan.changed:
        state.journal.append("partition_seen", part=key, status="changed")
    for key in scan.retracted:
        state.retract(key)

    # decode + fold the arrivals (changed parts re-fold under their new
    # signature — fold_part replaces the keyed partial wholesale).  With
    # a drift baseline configured and no model on disk yet, baseline
    # partitions fold FIRST and the model fits before the rest fold, so
    # a batch catch-up (all 30 days landing at once) bins every target
    # partition on its one and only decode.
    to_fold = sorted(scan.new) + sorted(scan.changed)
    folded: List[str] = []
    quarantined: List[str] = []
    model_fitted = False
    root = os.path.abspath(cfg.dataset_path)
    # live telemetry: the fold backlog is scrapeable the moment the scan
    # lands (mid-fold reads see the arrivals still pending)
    backlog_gauge = get_metrics().gauge(
        "continuum_fold_backlog",
        "arrived partitions not yet folded into the continuum state")
    backlog_gauge.set(float(len(to_fold)))

    def _fold_batch(keys: List[str]) -> None:
        from anovos_tpu.obs import telemetry

        frames, bad = _decode_parts(cfg, state, keys)
        quarantined.extend(bad)
        for key in sorted(frames):
            path = os.path.join(root, key)
            state.fold_part(key, path, frames[key], part_signature(path) or "gone")
            folded.append(key)
            # keepalive through a long catch-up fold: refresh the SERVICE
            # loop's heartbeat (if one is registered — one-shot steps
            # never register) per committed partition, so a 30-partition
            # burst does not page /healthz stale mid-fold
            telemetry.refresh_heartbeat("continuum_watcher")

    t_fold0 = time.monotonic()
    if (ctx.drift is not None and ctx.drift_cutoffs is None
            and ctx.drift.baseline):
        _fold_batch([k for k in to_fold if ctx.drift.is_baseline(k)])
        model_fitted = _fit_drift_model(cfg, state, ctx)
        _fold_batch([k for k in to_fold if not ctx.drift.is_baseline(k)])
    else:
        _fold_batch(to_fold)

    # basis guard (the StreamCheckpoint.check_bounds analogue): drift
    # histograms are only mergeable under ONE cutoff matrix and outlier
    # counts under ONE bounds vector — a swapped persisted model strips
    # the family from every folded partition (family_invalidated WAL)
    # and the catch-up below re-folds them under the new basis
    invalidated = 0
    if ctx.drift is not None and ctx.drift_cutoffs is not None:
        invalidated += state.check_family_basis(
            "drift_target", digest(canonical(
                {c: [float(v) for v in ctx.drift_cutoffs[c]]
                 for c in sorted(ctx.drift_cutoffs)}), str(ctx.drift.bin_size)))
    if ctx.outlier_bounds:
        invalidated += state.check_family_basis(
            "outlier", digest(canonical(
                {c: [None if v is None else float(v) for v in b]
                 for c, b in sorted(ctx.outlier_bounds.items())})))

    # re-fold any partition missing a family it should carry — a part
    # that predates the drift model, or whose family basis was just
    # invalidated (one-time catch-up: arrival order and model swaps must
    # not change the final state)
    refolded: List[str] = []
    from anovos_tpu.continuum.sufficient import active_families

    pending = sorted(
        k for k in state.folded_keys()
        if not set(active_families(ctx, k)) <= set(
            state.parts[k].get("families", [])))
    if pending:
        from anovos_tpu.obs import telemetry

        re_frames, _bad = _decode_parts(cfg, state, pending)
        for key in sorted(re_frames):
            path = os.path.join(root, key)
            state.fold_part(key, path, re_frames[key],
                            part_signature(path) or "gone")
            refolded.append(key)
            # a basis swap refolds the WHOLE history — same keepalive as
            # the arrival fold loop
            telemetry.refresh_heartbeat("continuum_watcher")

    fold_wall_s = round(time.monotonic() - t_fold0, 4)
    backlog_gauge.set(float(max(
        len(to_fold) - len(folded) - len(quarantined), 0)))

    # re-finalize + re-render only when something moved
    arts: Dict[str, pd.DataFrame] = {}
    render = {"rendered": [], "reused": [], "path": None}
    changed_state = bool(folded or refolded or quarantined or scan.retracted
                         or model_fitted or invalidated)
    if changed_state or not os.path.exists(
            os.path.join(cfg.output_path, "continuum_report.html")):
        arts = _finalize_artifacts(cfg, state, ctx)
        _write_artifacts(cfg.output_path, arts)
        if folded or refolded:
            # arrival→artifact lag: scan detection of this step's
            # arrivals through the re-finalized artifacts on disk
            get_metrics().gauge(
                "continuum_arrival_artifact_lag_seconds",
                "wall from arrival detection to re-finalized artifacts "
                "for the last folding step"
            ).set(round(time.monotonic() - t0, 4))
        from anovos_tpu.data_report.continuum_report import render_report

        render = render_report(
            cfg.output_path, arts,
            quarantined=state.quarantined_parts(),
            # deliberately path-free: the report must hash identically
            # between an incremental leg and a from-scratch leg run in
            # different directories (dataset location lives in `status`)
            feed={"partitions": len(state.folded_keys()),
                  "rows": state.total_rows()},
            cache_dir=os.path.join(cfg.state_dir, "sections"))

    # per-arrival alerts (the shift DAY fires, not the diluted cumulative)
    emitted = []
    for key in folded + refolded:
        emitted.extend(alerts_mod.evaluate_part(
            key, state.partials(key), ctx,
            thresholds=cfg.alert_thresholds))
    for key in quarantined:
        emitted.append(alerts_mod.quarantine_alert(
            key, state.parts.get(key, {}).get("reason", "")))
    emitted = alerts_mod.emit(emitted, obs_dir, state.journal)

    snapshot_fp = None
    if changed_state:
        snapshot_fp = state.snapshot(store)

    summary = {
        "scan": scan.to_json(),
        "folded": folded,
        "refolded": refolded,
        "quarantined": quarantined,
        "model_fitted": model_fitted,
        "alerts": len(emitted),
        "fold_wall_s": fold_wall_s,
        "wall_s": round(time.monotonic() - t0, 4),
        "snapshot_fp": snapshot_fp,
        "partitions": len(state.folded_keys()),
        "rows": state.total_rows(),
        "sections_rendered": render["rendered"],
        "sections_reused": render["reused"],
    }
    state.journal.append("step_end", folded=len(folded), refolded=len(refolded),
                         quarantined=len(quarantined), alerts=len(emitted),
                         fold_wall_s=fold_wall_s,
                         wall_s=summary["wall_s"])
    get_metrics().counter(
        "continuum_partitions_folded_total",
        "partitions folded by the continuum arrival loop").inc(len(folded) + len(refolded))
    return summary


def run(cfg: ContinuumConfig, max_iterations: Optional[int] = None,
        stop_file: Optional[str] = None) -> List[dict]:
    """The long-running service loop: a :func:`step` every poll interval
    (``ANOVOS_CONTINUUM_POLL_S`` overrides the config) until
    ``max_iterations`` or the ``stop_file`` appears."""
    from anovos_tpu.obs import telemetry
    from anovos_tpu.obs.tracing import maybe_rotator

    interval = poll_seconds(cfg.poll_s)
    # the long-running surface owns the telemetry plane + trace rotation
    # for its lifetime (both off by default: zero threads, no files)
    tele = telemetry.acquire(context="continuum")
    rotator = maybe_rotator(cfg.output_path)
    out = []
    i = 0
    clean_exit = False
    try:
        while True:
            # the heartbeat belongs to THIS loop, not step(): a one-shot
            # `step` CLI call or the workflow's continuous_analysis node
            # must not register a beat nothing will ever refresh (it
            # would flip /healthz stale on a perfectly healthy batch
            # run).  Beat BEFORE the step (so the first long catch-up is
            # already covered — the fold loop refreshes it per committed
            # partition) and again after; /healthz goes stale (then
            # unhealthy) when the loop stops beating — a killed watcher
            # is visible without anyone instrumenting the death path.
            telemetry.beat("continuum_watcher", interval_s=interval)
            out.append(step(cfg))
            telemetry.beat("continuum_watcher", interval_s=interval)
            i += 1
            if max_iterations is not None and i >= max_iterations:
                clean_exit = True
                break
            if stop_file and os.path.exists(stop_file):
                logger.info("stop file %s present — continuum loop exiting",
                            stop_file)
                clean_exit = True
                break
            time.sleep(interval)
    finally:
        if rotator is not None:
            rotator.close()
        if clean_exit:
            # an INTENTIONALLY-stopped loop must not page anyone: without
            # this a process that outlives the loop would flip /healthz
            # stale ⇒ degraded ⇒ unhealthy for a watcher that exited
            # cleanly.  A loop that DIES (exception) deliberately keeps
            # its beat so it goes stale and /healthz pages — that is the
            # whole point of the heartbeat.
            telemetry.clear_heartbeat("continuum_watcher")
        telemetry.release(tele)
    return out


def status(cfg: ContinuumConfig) -> dict:
    """Feed status from the on-disk state: partitions, rows, quarantine,
    the journal frontier, and the last step summary."""
    from anovos_tpu.cache.journal import read_journal

    manifest_path = os.path.join(cfg.state_dir, "state_manifest.json")
    parts: Dict[str, dict] = {}
    if os.path.exists(manifest_path):
        try:
            with open(manifest_path) as f:
                parts = (json.load(f) or {}).get("parts", {})
        except (OSError, ValueError):
            parts = {}
    records = read_journal(os.path.join(cfg.state_dir, "continuum_journal.jsonl"))
    last_step = next((r for r in reversed(records) if r.get("event") == "step_end"), None)
    last_snap = next((r for r in reversed(records) if r.get("event") == "snapshot_commit"), None)
    return {
        "state_dir": os.path.abspath(cfg.state_dir),
        "partitions": sum(1 for e in parts.values() if not e.get("quarantined")),
        "quarantined": sorted(k for k, e in parts.items() if e.get("quarantined")),
        "rows": sum(int(e.get("rows", 0)) for e in parts.values()
                    if not e.get("quarantined")),
        "journal_events": len(records),
        "alerts_emitted": sum(1 for r in records if r.get("event") == "alert_emitted"),
        "last_step": last_step,
        "last_snapshot": (last_snap or {}).get("fp"),
    }
