"""Hardened data plane (round 10): the fault-injection matrix.

Every entry completes DEGRADED — never crashes the run — with the
quarantine manifest naming each lost part and row count exactly:

* truncated parquet footer            → quarantined
* bad (footer) magic bytes            → quarantined
* undecodable-UTF-8 CSV part          → quarantined (exact byte offset)
* schema-drifted part                 → reconciled (missing null-filled,
                                        extra dropped, numeric widened)
* inf/NaN storm                       → sanitized at the decode boundary
* mid-stream kill + resume            → only undone chunks re-read,
                                        result identical

plus the guarantees around them: clean-input byte parity (the guard is a
no-op on undamaged data), retry-absorbs-transient-faults, fail-fast
knobs, and the streaming backpressure window's device-residency bound.
"""

import json
import os

import numpy as np
import pandas as pd
import pytest

from anovos_tpu.data_ingest import data_ingest, guard
from anovos_tpu.obs import get_metrics
from anovos_tpu.resilience import chaos


@pytest.fixture(autouse=True)
def _fresh_guard(monkeypatch):
    """Each test gets an empty quarantine registry, no chaos plan, fresh
    metrics, and a no-retry policy (retries are exercised explicitly)."""
    monkeypatch.setenv("ANOVOS_INGEST_RETRIES", "0")
    guard.reset()
    chaos.reset()
    get_metrics().reset()
    yield
    guard.reset()
    chaos.reset()


def _write_parts(d, nparts=4, rows=50, cols=None):
    os.makedirs(d, exist_ok=True)
    rng = np.random.default_rng(11)
    paths = []
    for i in range(nparts):
        df = pd.DataFrame(cols(i, rows, rng) if cols else {
            "a": rng.normal(size=rows),
            "b": rng.integers(0, 9, rows).astype("int64"),
            "c": rng.choice(["x", "y"], rows),
        })
        p = os.path.join(d, f"part-{i:05d}.parquet")
        df.to_parquet(p, index=False)
        paths.append(p)
    return paths


# ----------------------------------------------------------------------
# corruption classes
# ----------------------------------------------------------------------
def test_truncated_parquet_footer_quarantined(tmp_path):
    paths = _write_parts(tmp_path / "d")
    raw = open(paths[1], "rb").read()
    open(paths[1], "wb").write(raw[: len(raw) - 100])  # footer gone
    t = data_ingest.read_dataset(str(tmp_path / "d"), "parquet")
    assert t.nrows == 3 * 50
    recs = guard.records()
    assert len(recs) == 1
    assert recs[0].file == os.path.abspath(paths[1])
    assert recs[0].error_class == "ArrowInvalid"
    assert recs[0].rows_lost is None  # footer gone: genuinely unknowable


def test_bad_magic_bytes_quarantined(tmp_path):
    paths = _write_parts(tmp_path / "d")
    raw = bytearray(open(paths[2], "rb").read())
    raw[-4:] = b"XXXX"  # pyarrow validates the FOOTER magic
    open(paths[2], "wb").write(bytes(raw))
    t = data_ingest.read_dataset(str(tmp_path / "d"), "parquet")
    assert t.nrows == 3 * 50
    recs = guard.records()
    assert [os.path.basename(r.file) for r in recs] == ["part-00002.parquet"]


def test_undecodable_utf8_csv_quarantined(tmp_path):
    d = tmp_path / "csvs"
    d.mkdir()
    pd.DataFrame({"a": [1.0, 2.0], "s": ["ok", "fine"]}).to_csv(
        d / "part-00000.csv", index=False)
    with open(d / "part-00001.csv", "wb") as f:
        f.write(b"a,s\n3.0,\xff\xfe\x00garbage\n4.0,ok\n")
    pd.DataFrame({"a": [5.0], "s": ["last"]}).to_csv(
        d / "part-00002.csv", index=False)
    t = data_ingest.read_dataset(str(d), "csv")
    assert t.nrows == 3
    recs = guard.records()
    assert len(recs) == 1
    assert recs[0].error_class == "UnicodeDecodeError"
    assert recs[0].byte_offset == 0  # first byte of the value is the bad one
    assert recs[0].rows_lost == 2 and recs[0].rows_estimated  # line count


def test_quarantine_manifest_on_disk_exact(tmp_path):
    paths = _write_parts(tmp_path / "d")
    open(paths[0], "wb").write(b"not parquet at all")
    guard.configure(str(tmp_path / "obs"))
    data_ingest.read_dataset(str(tmp_path / "d"), "parquet")
    mp = guard.manifest_path()
    assert mp and os.path.exists(mp)
    doc = json.load(open(mp))
    assert doc["parts"] == 1
    assert [os.path.basename(r["file"]) for r in doc["records"]] == ["part-00000.parquet"]
    # the degradation registry names the part too (report banner feed)
    from anovos_tpu.resilience import degraded_sections

    assert "ingest/part-00000.parquet" in degraded_sections()


def test_all_parts_quarantined_raises(tmp_path):
    paths = _write_parts(tmp_path / "d", nparts=2)
    for p in paths:
        open(p, "wb").write(b"garbage")
    with pytest.raises(guard.IngestError, match="quarantined"):
        data_ingest.read_dataset(str(tmp_path / "d"), "parquet")


def test_on_corrupt_raise_restores_fail_fast(tmp_path, monkeypatch):
    monkeypatch.setenv("ANOVOS_INGEST_ON_CORRUPT", "raise")
    paths = _write_parts(tmp_path / "d")
    open(paths[1], "wb").write(b"garbage")
    with pytest.raises(guard.IngestError, match="part read failed"):
        data_ingest.read_dataset(str(tmp_path / "d"), "parquet")
    assert guard.records() == []  # fail-fast mode quarantines nothing


# ----------------------------------------------------------------------
# chaos I/O faults + retry
# ----------------------------------------------------------------------
def test_chaos_corrupt_absorbed_by_retry(tmp_path, monkeypatch):
    monkeypatch.setenv("ANOVOS_INGEST_RETRIES", "1")
    _write_parts(tmp_path / "d")
    chaos.install("corrupt@io:*part-00001.parquet")  # n defaults to 1: one failure
    t = data_ingest.read_dataset(str(tmp_path / "d"), "parquet")
    assert t.nrows == 4 * 50  # the retry re-read it successfully
    assert guard.records() == []
    assert get_metrics().counter("ingest_retries_total").value() == 1


def test_chaos_truncate_exhausts_to_quarantine(tmp_path, monkeypatch):
    monkeypatch.setenv("ANOVOS_INGEST_RETRIES", "1")
    _write_parts(tmp_path / "d")
    chaos.install("truncate@io:*part-00001.parquet:n=99")
    t = data_ingest.read_dataset(str(tmp_path / "d"), "parquet")
    assert t.nrows == 3 * 50
    recs = guard.records()
    assert len(recs) == 1
    assert recs[0].error_class == "ChaosTruncate"
    # the file itself is intact, so the row count is EXACT, not estimated
    assert recs[0].rows_lost == 50 and not recs[0].rows_estimated


def test_chaos_slowread_only_delays(tmp_path):
    _write_parts(tmp_path / "d", nparts=2)
    chaos.install("slowread@io:*part-00000.parquet:secs=0.05")
    t = data_ingest.read_dataset(str(tmp_path / "d"), "parquet")
    assert t.nrows == 2 * 50
    assert guard.records() == []
    assert chaos.plan().injection_count() == 1


# ----------------------------------------------------------------------
# schema drift
# ----------------------------------------------------------------------
def _drifted_dir(tmp_path):
    d = tmp_path / "drift"
    d.mkdir()
    pd.DataFrame({
        "a": np.array([1, 2, 3], dtype="int64"),
        "b": [1.5, 2.5, 3.5],
        "c": ["x", "y", "z"],
    }).to_parquet(d / "part-00000.parquet", index=False)
    pd.DataFrame({  # a widened to float, b missing, d extra
        "a": [4.25, 5.25],
        "c": ["w", "v"],
        "d": ["extra", "extra"],
    }).to_parquet(d / "part-00001.parquet", index=False)
    return d


def test_schema_drift_reconciled(tmp_path):
    t = data_ingest.read_dataset(str(_drifted_dir(tmp_path)), "parquet")
    assert t.nrows == 5
    assert t.col_names == ["a", "b", "c"]  # extra column 'd' dropped
    df = t.to_pandas()
    # widened numeric promotion: int part + float part → float values exact
    assert df["a"].tolist() == [1.0, 2.0, 3.0, 4.25, 5.25]
    # missing column null-filled for the drifted part's rows (mask=False)
    assert df["b"].notna().tolist() == [True, True, True, False, False]
    assert df["c"].tolist() == ["x", "y", "z", "w", "v"]
    drift = get_metrics().counter("ingest_schema_drift_total")
    assert drift.value(kind="missing_col") == 1
    assert drift.value(kind="extra_col") == 1
    assert drift.value(kind="widened") == 1
    assert guard.records() == []  # drift is repaired, not quarantined


def test_schema_drift_strict_raises(tmp_path, monkeypatch):
    monkeypatch.setenv("ANOVOS_INGEST_SCHEMA_DRIFT", "strict")
    with pytest.raises(guard.IngestError, match="schema drift"):
        data_ingest.read_dataset(str(_drifted_dir(tmp_path)), "parquet")


def test_numeric_vs_string_drift_coerces(tmp_path):
    d = tmp_path / "mix"
    d.mkdir()
    pd.DataFrame({"v": [1.0, 2.0]}).to_parquet(d / "part-00000.parquet", index=False)
    pd.DataFrame({"v": ["3.5", "junk"]}).to_parquet(d / "part-00001.parquet", index=False)
    t = data_ingest.read_dataset(str(d), "parquet")
    df = t.to_pandas()
    assert df["v"].tolist()[:3] == [1.0, 2.0, 3.5]
    assert pd.isna(df["v"].iloc[3])  # 'junk' nulled, counted
    assert get_metrics().counter("ingest_schema_drift_total").value(kind="unparseable") == 1


def test_string_vs_numeric_drift_stringifies():
    # the OTHER retype direction: string-typed reference, numeric part —
    # the part column stringifies toward the reference schema (the
    # zero-padding is gone — values drifted, not just dtype — but the
    # column stays uniformly string-typed) and the repair is counted
    ref = pd.DataFrame({"code": ["00501", "00502"]})
    drifted = pd.DataFrame({"code": np.array([501, 502], dtype="int64")})
    out = guard.reconcile_frames([("p0", ref), ("p1", drifted)])
    merged = pd.concat(out, ignore_index=True)
    assert merged["code"].tolist() == ["00501", "00502", "501", "502"]
    assert pd.api.types.is_string_dtype(merged["code"])  # object, or pandas 3's str
    assert get_metrics().counter("ingest_schema_drift_total").value(kind="retyped") == 1


# ----------------------------------------------------------------------
# hostile values (inf/NaN storm)
# ----------------------------------------------------------------------
def _storm_dir(tmp_path):
    d = tmp_path / "storm"
    d.mkdir()
    pd.DataFrame({
        "v": [1.0, np.inf, -np.inf, np.nan, 1e39, -1e39, 2.0],
        "clean": np.arange(7.0),
    }).to_parquet(d / "part-00000.parquet", index=False)
    return d


def test_inf_overflow_masked_by_default(tmp_path):
    t = data_ingest.read_dataset(str(_storm_dir(tmp_path)), "parquet")
    from anovos_tpu.ops.describe import table_describe

    stats, _ = table_describe(t, ["v", "clean"], [])
    # 7 values - 2 inf - 1 NaN - 2 overflow = 2 survivors, all finite
    assert int(np.asarray(stats["count"])[0]) == 2
    c = get_metrics().counter("ingest_sanitized_values_total")
    assert c.value(column="v", kind="posinf") == 1
    assert c.value(column="v", kind="neginf") == 1
    assert c.value(column="v", kind="overflow") == 2
    assert c.value(column="clean", kind="posinf") in (None, 0)  # untouched
    df = t.to_pandas()
    assert df["v"].notna().sum() == 2  # only 1.0 and 2.0 survive
    assert np.isfinite(df["v"].dropna()).all()


def test_inf_overflow_clip_mode(tmp_path, monkeypatch):
    monkeypatch.setenv("ANOVOS_INGEST_SANITIZE", "clip")
    t = data_ingest.read_dataset(str(_storm_dir(tmp_path)), "parquet")
    df = t.to_pandas()
    f32max = float(np.finfo(np.float32).max)
    vals = df["v"].dropna().to_numpy()
    assert len(vals) == 6  # only the NaN is null
    assert vals.max() <= f32max * 1.001 and vals.min() >= -f32max * 1.001
    assert np.isfinite(vals).all()


def test_sanitize_keep_passthrough(tmp_path, monkeypatch):
    monkeypatch.setenv("ANOVOS_INGEST_SANITIZE", "keep")
    t = data_ingest.read_dataset(str(_storm_dir(tmp_path)), "parquet")
    df = t.to_pandas()
    assert np.isinf(df["v"].dropna()).sum() >= 2  # legacy passthrough


# ----------------------------------------------------------------------
# clean-input parity: the guard is a no-op on undamaged data
# ----------------------------------------------------------------------
def test_clean_input_parity_guard_vs_legacy(tmp_path, monkeypatch):
    d = tmp_path / "clean"
    _write_parts(d, nparts=3)
    t_guarded = data_ingest.read_dataset(str(d), "parquet").to_pandas()
    # legacy-equivalent policy: fail-fast, strict schemas, no sanitization
    monkeypatch.setenv("ANOVOS_INGEST_ON_CORRUPT", "raise")
    monkeypatch.setenv("ANOVOS_INGEST_SCHEMA_DRIFT", "strict")
    monkeypatch.setenv("ANOVOS_INGEST_SANITIZE", "keep")
    t_legacy = data_ingest.read_dataset(str(d), "parquet").to_pandas()
    pd.testing.assert_frame_equal(t_guarded, t_legacy)
    assert guard.records() == []


# ----------------------------------------------------------------------
# streaming: backpressure knob + resumable checkpoint
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def stream_parts(tmp_path_factory):
    d = tmp_path_factory.mktemp("stream_parts")
    rng = np.random.default_rng(3)
    for i in range(5):
        pd.DataFrame({
            "a": rng.normal(i, 2.0, 2048),
            "b": rng.exponential(5.0, 2048),
        }).to_parquet(d / f"part-{i:05d}.parquet", index=False)
    return d


def test_stream_inflight_window_bounds_residency(stream_parts, monkeypatch):
    from anovos_tpu.ops.streaming import describe_streaming

    results = {}
    for window in (1, 8):
        get_metrics().reset()
        monkeypatch.setenv("ANOVOS_STREAM_INFLIGHT", str(window))
        results[window] = describe_streaming(
            str(stream_parts), "parquet", chunk_rows=1024).set_index("attribute")
        hw = get_metrics().gauge("stream_inflight_high_water").value(
            window=str(window))
        assert hw is not None and hw <= window, (window, hw)
        if window == 1:
            assert hw == 1  # fully synchronous at the smallest window
    # the window is pure backpressure: results identical at 1 and 8
    pd.testing.assert_frame_equal(results[1], results[8])


def test_stream_mid_kill_resume_rereads_only_undone(stream_parts, tmp_path, monkeypatch):
    from anovos_tpu.ops import streaming

    ref = streaming.describe_streaming(str(stream_parts), "parquet", chunk_rows=2048)
    ck = str(tmp_path / "ckpt")
    # kill the stream after two pass-1 chunk commits
    orig_commit = streaming.StreamCheckpoint.commit
    state = {"n": 0}

    def bomb(self, pass_no, idx, arrays):
        orig_commit(self, pass_no, idx, arrays)
        state["n"] += 1
        if state["n"] == 2:
            raise RuntimeError("simulated mid-stream kill")

    monkeypatch.setattr(streaming.StreamCheckpoint, "commit", bomb)
    with pytest.raises(RuntimeError, match="simulated"):
        streaming.describe_streaming(str(stream_parts), "parquet",
                                     chunk_rows=2048, checkpoint_dir=ck)
    monkeypatch.setattr(streaming.StreamCheckpoint, "commit", orig_commit)

    # resume: count which files get re-read
    reads = []
    orig_rhf = data_ingest.read_host_frame

    def counting(files, *a, **k):
        reads.extend(files)
        return orig_rhf(files, *a, **k)

    monkeypatch.setattr(data_ingest, "read_host_frame", counting)
    res = streaming.describe_streaming(str(stream_parts), "parquet",
                                       chunk_rows=2048, checkpoint_dir=ck,
                                       resume=True)
    # identical result, fewer reads than the 10 (5 files x 2 passes) a
    # fresh run pays — the committed prefix was skipped
    pd.testing.assert_frame_equal(res, ref)
    assert len(reads) < 10, reads

    # the WAL journal recorded begin/commit per chunk
    events = [json.loads(l) for l in open(os.path.join(ck, "stream_journal.jsonl"))]
    kinds = {e["event"] for e in events}
    assert {"run_begin", "chunk_begin", "chunk_commit"} <= kinds
    commits = [e for e in events if e["event"] == "chunk_commit" and e["phase"] == 1]
    assert len(commits) == 5  # 2 pre-kill + 3 on resume


def test_stream_checkpoint_invalidated_on_data_change(stream_parts, tmp_path):
    from anovos_tpu.ops import streaming

    ck = str(tmp_path / "ck2")
    a = streaming.describe_streaming(str(stream_parts), "parquet",
                                     chunk_rows=2048, checkpoint_dir=ck)
    # different chunking → different stream signature → fresh start (the
    # stale progress must not be resumed against)
    b = streaming.describe_streaming(str(stream_parts), "parquet",
                                     chunk_rows=1024, checkpoint_dir=ck,
                                     resume=True)
    for c in ("a", "b"):
        ra = a.set_index("attribute").loc[c]
        rb = b.set_index("attribute").loc[c]
        assert ra["count"] == rb["count"]
        assert abs(ra["mean"] - rb["mean"]) < 1e-3


def test_resume_invalidates_chunks_after_readability_change(
        stream_parts, tmp_path, monkeypatch):
    """A part that was quarantined in run 1 (transient fault, same file
    bytes) reads fine on the resumed run 2: every chunk index downstream
    of it shifted, so run 1's committed partials there must be dropped
    and recomputed — trusting them would silently double-count/drop
    rows while claiming the uninterrupted result."""
    from anovos_tpu.ops import streaming

    ref = streaming.describe_streaming(str(stream_parts), "parquet",
                                       chunk_rows=2048)
    ck = str(tmp_path / "ck3")
    # run 1: the MIDDLE part fails on every attempt → quarantined, the
    # stream completes (and checkpoints every chunk) over the 4 survivors
    chaos.install("corrupt@io:*part-00002.parquet:n=99")
    degraded = streaming.describe_streaming(
        str(stream_parts), "parquet", chunk_rows=2048, checkpoint_dir=ck)
    assert int(degraded.set_index("attribute").loc["a", "count"]) == 4 * 2048
    chaos.reset()
    guard.reset()

    # run 2, resume, no chaos: the part reads fine now
    res = streaming.describe_streaming(
        str(stream_parts), "parquet", chunk_rows=2048, checkpoint_dir=ck,
        resume=True)
    pd.testing.assert_frame_equal(res, ref)
    events = [json.loads(l) for l in open(os.path.join(ck, "stream_journal.jsonl"))]
    assert any(e["event"] == "chunks_invalidated" and e["from_chunk"] == 2
               for e in events)


def test_streaming_raise_mode_propagates(stream_parts, tmp_path, monkeypatch):
    # fail-fast policy: a corrupt part must KILL the stream (nothing is
    # quarantined/recorded in raise mode — silently skipping the file
    # would be unaccounted data loss)
    import shutil

    from anovos_tpu.ops.streaming import describe_streaming

    d = tmp_path / "sp_raise"
    d.mkdir()
    for i in range(3):
        shutil.copy(stream_parts / f"part-{i:05d}.parquet", d)
    raw = open(d / "part-00001.parquet", "rb").read()
    open(d / "part-00001.parquet", "wb").write(raw[:-64])
    monkeypatch.setenv("ANOVOS_INGEST_ON_CORRUPT", "raise")
    with pytest.raises(guard.IngestError):
        describe_streaming(str(d), "parquet", chunk_rows=1024)
    assert guard.records() == []


def test_distributed_raise_mode_propagates(tmp_path, monkeypatch):
    # same contract one layer up: read_dataset_distributed must not
    # degrade a host's slice to empty (dropping its READABLE parts) when
    # the policy asked for fail-fast
    from anovos_tpu.data_ingest.distributed_ingest import read_dataset_distributed

    paths = _write_parts(tmp_path / "d", nparts=3)
    open(paths[1], "wb").write(b"garbage")
    monkeypatch.setenv("ANOVOS_INGEST_ON_CORRUPT", "raise")
    with pytest.raises(guard.IngestError):
        read_dataset_distributed(str(tmp_path / "d"), "parquet")
    assert guard.records() == []


def test_streaming_quarantines_corrupt_part(stream_parts, tmp_path):
    from anovos_tpu.ops.streaming import describe_streaming

    d = tmp_path / "sp"
    d.mkdir()
    import shutil

    for i in range(5):
        shutil.copy(stream_parts / f"part-{i:05d}.parquet", d)
    raw = open(d / "part-00002.parquet", "rb").read()
    open(d / "part-00002.parquet", "wb").write(raw[:-64])
    got = describe_streaming(str(d), "parquet", chunk_rows=1024).set_index("attribute")
    assert int(got.loc["a", "count"]) == 4 * 2048  # stream survives minus the part
    assert [os.path.basename(r.file) for r in guard.records()] == ["part-00002.parquet"]


# ----------------------------------------------------------------------
# distributed fallback schema helper (fast path of the satellite tests)
# ----------------------------------------------------------------------
def test_empty_with_schema_skips_corrupt_head(tmp_path):
    from anovos_tpu.data_ingest.distributed_ingest import _empty_with_schema

    paths = _write_parts(tmp_path / "d", nparts=3)
    open(paths[0], "wb").write(b"garbage")  # head part unreadable
    df = _empty_with_schema(paths, "parquet", {})
    assert len(df) == 0
    assert list(df.columns) == ["a", "b", "c"]
    assert [os.path.basename(r.file) for r in guard.records()] == ["part-00000.parquet"]


def test_empty_with_schema_all_dead_raises(tmp_path):
    from anovos_tpu.data_ingest.distributed_ingest import _empty_with_schema

    paths = _write_parts(tmp_path / "d", nparts=2)
    for p in paths:
        open(p, "wb").write(b"garbage")
    with pytest.raises(guard.IngestError, match="schema"):
        _empty_with_schema(paths, "parquet", {})
