"""A pandas frame that is only being written never touches the device
(``shared.table.host_table_frame``, ``write_dataset`` of a ``DataFrame``,
``workflow.save``), and the files it leaves have the bytes they had when the
frame went through ``Table.from_pandas`` + ``Table.to_pandas``: this file
keeps that device round trip as its reference.  On the suite's 8-device mesh
and on one device."""

import os

import jax
import numpy as np
import pandas as pd
import pytest
import yaml

from anovos_tpu import obs, workflow
from anovos_tpu.data_ingest import data_ingest, synthetic
from anovos_tpu.shared.artifact_store import AsyncArtifactWriter
from anovos_tpu.shared.runtime import init_runtime
from anovos_tpu.shared.table import Table, host_table_frame

ROWS = 2000
STATS = ["global_summary", "measures_of_counts", "measures_of_centralTendency",
         "measures_of_cardinality", "measures_of_percentiles", "measures_of_dispersion",
         "measures_of_shape"]
EXT = {"parquet": ".parquet", "csv": ".csv"}

# one frame for every dtype the round trip tells apart
FRAMES = {
    "float64_not_exact_in_f32": lambda: pd.DataFrame(
        {"v": [0.1234, 1e-300, 3.141592653589793, np.nan, 1e300, -2.5]}),
    "float64_exact_in_f32": lambda: pd.DataFrame({"v": [0.5, -1.25, 1024.0, np.nan, 3.0]}),
    "float64_whole_numbers": lambda: pd.DataFrame({"v": [1.0, 2.0, -7.0]}),
    "float32": lambda: pd.DataFrame({"v": np.array([0.1, 2.5, np.nan, -3e38], np.float32)}),
    "negative_zero": lambda: pd.DataFrame({"v": [-0.0, 0.0, 1.5], "w": [-0.0, 0.1, np.nan]}),
    "all_nan": lambda: pd.DataFrame({"v": [np.nan, np.nan, np.nan]}),
    "int64_fits_int32": lambda: pd.DataFrame(
        {"v": np.array([-2**31, 0, 7, 2**31 - 1], np.int64)}),
    "int64_beyond_int32": lambda: pd.DataFrame(
        {"v": np.array([-2**62, -1, 2**31, 2**53 + 1], np.int64)}),
    "int32_and_uint8": lambda: pd.DataFrame(
        {"v": np.array([1, -2, 3], np.int32), "u": np.array([0, 200, 255], np.uint8)}),
    "nullable_Int64_with_a_null": lambda: pd.DataFrame(
        {"v": pd.array([1, None, 2**40], dtype="Int64")}),
    "nullable_Int64_full": lambda: pd.DataFrame({"v": pd.array([1, 5, 2**40], dtype="Int64")}),
    "bool": lambda: pd.DataFrame({"v": [True, False, True]}),
    "object_strings_with_None": lambda: pd.DataFrame(
        {"v": np.array(["b", None, "a", "", "b", "é"], dtype=object)}),
    "object_mixed": lambda: pd.DataFrame({"v": np.array(["b", 1, 1.0, None], dtype=object)}),
    "str_dtype": lambda: pd.DataFrame({"v": pd.Series(["x", None, "w", "x"], dtype="str")}),
    "string_dtype": lambda: pd.DataFrame({"v": pd.Series(["x", None, "w", "x"], dtype="string")}),
    "category": lambda: pd.DataFrame(
        {"v": pd.Categorical(["m", None, "k", "m"], categories=["z", "m", "k"])}),
    "datetime64_with_NaT": lambda: pd.DataFrame(
        {"v": pd.to_datetime(["2020-01-01 00:00:01.75", None, "1969-12-31 23:59:59.0"],
                             format="%Y-%m-%d %H:%M:%S.%f")}),
    "zero_rows": lambda: pd.DataFrame(
        {"f": np.array([], np.float64), "i": np.array([], np.int64),
         "s": np.array([], dtype=object)}),
    "no_columns": lambda: pd.DataFrame(index=range(3)),
    "a_stats_table_by_hand": lambda: pd.DataFrame(
        {"attribute": ["age", "fnlwgt", "sex"], "mean": [38.5816, 189778.3665, np.nan],
         "fill_count": np.array([2000, 1990, 2000], np.int64), "fill_pct": [1.0, 0.995, 1.0],
         "mode": ["36", "203488", "Male"], "flagged": [False, True, False]}),
}


@pytest.fixture(scope="module", params=["mesh8", "one_device"])
def mesh(request):
    """The suite's 8-device mesh, then a runtime on one of its devices."""
    if request.param == "mesh8":
        yield request.param
        return
    init_runtime(devices=jax.devices()[:1])
    try:
        yield request.param
    finally:
        init_runtime()  # the suite's 8-device mesh again


def _part_bytes(folder, file_type, parts=1):
    out = []
    for i in range(parts):
        with open(os.path.join(folder, f"part-{i:05d}{EXT[file_type]}"), "rb") as f:
            out.append(f.read())
    assert os.path.exists(os.path.join(folder, "_SUCCESS"))
    return out


def _both_ways(df, tmp_path, file_type, **kw):
    """The part files of ``df`` written from the host and through the device."""
    cfg = {"mode": "overwrite", **kw.pop("file_configs", {})}
    parts = int(cfg.get("repartition", 1))
    data_ingest.write_dataset(df, str(tmp_path / "host"), file_type, cfg, **kw)
    data_ingest.write_dataset(Table.from_pandas(df), str(tmp_path / "device"), file_type, cfg, **kw)
    return (_part_bytes(tmp_path / "host", file_type, parts),
            _part_bytes(tmp_path / "device", file_type, parts))


@pytest.mark.parametrize("file_type", ["parquet", "csv"])
@pytest.mark.parametrize("case", sorted(FRAMES))
def test_a_host_frames_file_has_the_round_trips_bytes(mesh, case, file_type, tmp_path):
    df = FRAMES[case]()
    host, device = _both_ways(df, tmp_path, file_type)
    assert host == device


@pytest.mark.parametrize("case", sorted(FRAMES))
def test_the_host_frame_is_the_round_trips_frame(mesh, case):
    df = FRAMES[case]()
    want = Table.from_pandas(df).to_pandas()
    got = host_table_frame(df)
    assert list(got.dtypes) == list(want.dtypes) and list(got.columns) == list(want.columns)
    pd.testing.assert_frame_equal(got, want, check_exact=True)
    for c in want.columns:  # -0.0 == 0.0 and nan != nan: compare the bits of the floats
        if want[c].dtype.kind == "f":
            assert got[c].to_numpy().tobytes() == want[c].to_numpy().tobytes()


@pytest.mark.parametrize("file_type", ["parquet", "csv"])
def test_part_files_and_column_order_as_through_the_device(mesh, file_type, tmp_path):
    df = pd.concat([FRAMES["a_stats_table_by_hand"]()] * 3, ignore_index=True)
    host, device = _both_ways(df, tmp_path, file_type, file_configs={"repartition": 2},
                              column_order=["mode", "attribute", "mean"])
    assert len(host) == 2 and host == device


# ------------------------------------------------ a `stats` run's tables ----
def _run(work, cfg):
    path = work / "pipeline.yaml"
    path.write_text(yaml.safe_dump(cfg, sort_keys=False))
    cwd = os.getcwd()
    os.chdir(work)
    try:
        workflow.run(str(path), "local")
    finally:
        os.chdir(cwd)
    return obs.load_manifest(workflow.LAST_MANIFEST_PATH)


def _input_dataset(work):
    data = synthetic.generate(ROWS, 7, dest=work / "income_dataset")
    return {
        "read_dataset": {"file_path": os.path.join(data, "parquet"), "file_type": "parquet"},
        "delete_column": ["logfnl", "empty", "dt_2"],
    }


@pytest.fixture(scope="module")
def stats_run(mesh, tmp_path_factory):
    """One ``workflow.run`` of the benchmark's ``stats`` mix at 2,000 rows:
    its manifest, the frames its seven nodes handed to ``write_dataset``, and
    the transfers and ``ingest/*`` spans booked from the writer's threads."""
    work = tmp_path_factory.mktemp(f"host_frame_stats_{mesh}")
    cfg = {
        "input_dataset": _input_dataset(work),
        "stats_generator": {"metric": STATS,
                            "metric_args": {"list_of_cols": "all", "drop_cols": ["ifa"]}},
        "write_stats": {"file_path": str(work / "stats"), "file_type": "parquet",
                        "file_configs": {"mode": "overwrite"}},
    }
    frames = {}
    real_write = data_ingest.write_dataset

    def write_and_note(idf, file_path, *a, **k):
        frames[os.path.basename(file_path)] = idf
        return real_write(idf, file_path, *a, **k)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(data_ingest, "write_dataset", write_and_note)
        manifest = _run(work, cfg)
    return {"work": work, "manifest": manifest, "frames": frames,
            "spans": obs.get_tracer().snapshot()}


@pytest.mark.parametrize("file_type", ["parquet", "csv"])
@pytest.mark.parametrize("table", STATS)
def test_a_stats_runs_table_has_the_round_trips_bytes(stats_run, table, file_type, tmp_path):
    df = stats_run["frames"][table]
    assert isinstance(df, pd.DataFrame) and len(df)
    host, device = _both_ways(df, tmp_path, file_type)
    assert host == device
    if file_type == "parquet":  # and they are the bytes the run itself left
        folder = stats_run["work"] / "stats" / "data_analyzer" / "stats_generator" / table
        assert _part_bytes(folder, "parquet") == host


def _drains(manifest):
    return {r["parent"]: r["counts"] for r in manifest["phases"] if r["name"] == "artifact:drain"}


def test_a_stats_pass_counts_seven_host_frames_of_seven_pending(stats_run):
    drains = _drains(stats_run["manifest"])
    assert drains["run"]["pending"] == 7 and drains["run"]["host_frames"] == 7
    assert drains["close"]["pending"] == 0 and drains["close"]["host_frames"] == 0
    writes = [sp for sp in stats_run["spans"] if sp.name.startswith("write:stats:")]
    assert len(writes) == 7 and all(sp.args["host_frame"] == 1 for sp in writes)


def test_a_stats_passes_writer_threads_book_no_transfer_and_no_ingest_span(stats_run):
    """Every ``ingest/*`` span of the pass is ingest's own, on the main
    thread under the ``ingest`` phase; the writer's threads open ``write:*``
    spans and nothing else."""
    from_writers = [sp for sp in stats_run["spans"] if sp.thread.startswith("artifact-writer")]
    assert from_writers and {sp.name.split(":")[0] for sp in from_writers} == {"write"}
    ingest = [sp for sp in stats_run["spans"] if sp.name.startswith("ingest/")]
    assert ingest and {sp.thread for sp in ingest} == {"MainThread"}


def test_a_queued_table_counts_no_host_frame(mesh, tmp_path_factory):
    """A pass whose one queued write is of a device ``Table`` (a treated
    dataset under ``write_intermediate``; ``write_main`` is written after the
    drain, synchronously): ``pending`` 1, ``host_frames`` 0."""
    work = tmp_path_factory.mktemp(f"host_frame_table_{mesh}")
    out = {"file_type": "parquet", "file_configs": {"mode": "overwrite"}}
    manifest = _run(work, {
        "input_dataset": _input_dataset(work),
        "quality_checker": {"nullRows_detection": {
            "list_of_cols": "all", "drop_cols": [], "treatment": True, "treatment_threshold": 0.75}},
        "write_intermediate": {"file_path": str(work / "intermediate"), **out},
        "write_main": {"file_path": str(work / "output"), **out},
    })
    assert _drains(manifest)["run"] == {"pending": 1, "host_frames": 0}
    (write,) = [sp for sp in obs.get_tracer().snapshot() if sp.name.startswith("write:ckpt:")]
    assert "host_frame" not in write.args
    assert os.path.exists(work / "output" / "final_dataset" / "part-00000.parquet")


# ------------------------------------------------------- workflow.save ----
def _transfer_bytes():
    reg = obs.get_metrics()
    return {d: sum(v for _, v in reg.counter(f"transfer_{d}_bytes_total").items())
            for d in ("h2d", "d2h")}


@pytest.mark.parametrize("queued", [True, False], ids=["queued", "synchronous"])
def test_save_of_a_frame_books_no_transfer_and_no_ingest_phase(mesh, queued, tmp_path):
    df = FRAMES["a_stats_table_by_hand"]()
    write = {"file_path": str(tmp_path), "file_type": "parquet", "file_configs": {"mode": "overwrite"}}
    tracer = obs.get_tracer()
    writer = AsyncArtifactWriter(workers=2) if queued else None
    before = _transfer_bytes()
    with tracer.run_pass():
        assert workflow.save(df, write, "host", writer=writer, key="stats:by_hand") is df
        if writer is not None:
            writer.close()
    assert _transfer_bytes() == before
    rows = tracer.phases()
    names = {sp.name for sp in tracer.snapshot()} | {r["name"] for r in rows}
    assert not {n for n in names if n.startswith("ingest/")}
    if queued:
        (drain,) = [r for r in rows if r["name"] == "artifact:drain" and r["counts"]["pending"]]
        assert drain["counts"] == {"pending": 1, "host_frames": 1}
    # the reference does book them: the counters and the spans do measure the round trip
    with tracer.run_pass():
        data_ingest.write_dataset(Table.from_pandas(df), str(tmp_path / "device"), "parquet",
                                  {"mode": "overwrite"})
    after = _transfer_bytes()
    assert after["h2d"] > before["h2d"] and after["d2h"] > before["d2h"]
    assert {"ingest/encode", "ingest/h2d"} <= {r["name"] for r in tracer.phases()}
    assert _part_bytes(tmp_path / "host", "parquet") == _part_bytes(tmp_path / "device", "parquet")


def test_save_reread_from_disk_returns_a_frame(mesh, tmp_path, monkeypatch):
    monkeypatch.setenv("ANOVOS_REREAD_FROM_DISK", "1")
    df = FRAMES["a_stats_table_by_hand"]()
    write = {"file_path": str(tmp_path), "file_type": "parquet", "file_configs": {"mode": "overwrite"}}
    back = workflow.save(df, write, "t", reread=True, writer=AsyncArtifactWriter(workers=2))
    assert isinstance(back, pd.DataFrame) and list(back.columns) == list(df.columns)
    assert back["fill_count"].tolist() == [2000, 1990, 2000]


def test_save_no_longer_builds_a_table():
    assert not {"from_pandas", "Table", "_T"} & set(workflow.save.__code__.co_names)
