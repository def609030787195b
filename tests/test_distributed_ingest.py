"""Multi-host ingest: 2 simulated processes (jax.distributed + Gloo CPU
collectives) read disjoint file shards, assemble ONE global Table, and the
stats kernels must agree with a single-process run over the same data
(round-1 verdict #6; SURVEY.md §2.10 DP story)."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pandas as pd
import pytest

_WORKER = textwrap.dedent(
    """
    import json, os, sys
    pid = int(sys.argv[1]); port = sys.argv[2]; data_dir = sys.argv[3]; out = sys.argv[4]
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}", num_processes=2, process_id=pid
    )
    sys.path.insert(0, "/root/repo")
    from anovos_tpu.shared.runtime import init_runtime
    rt = init_runtime()  # global mesh over both processes' devices
    assert rt.n_devices == jax.device_count() == 2

    from anovos_tpu.data_ingest.distributed_ingest import read_dataset_distributed
    t = read_dataset_distributed(data_dir, "parquet")

    from anovos_tpu.ops.describe import table_describe
    import jax.numpy as jnp
    import numpy as np
    num_cols = [c for c in t.col_names if t.columns[c].kind == "num"]
    stats, _ = table_describe(t, num_cols, [])

    cat_cols = [c for c in t.col_names if t.columns[c].kind == "cat"]
    from anovos_tpu.ops.segment import code_counts
    cat_counts = {
        c: np.asarray(code_counts(t.columns[c].data, t.columns[c].mask,
                                  max(len(t.columns[c].vocab), 1))).tolist()
        for c in cat_cols
    }
    vocabs = {c: [str(v) for v in t.columns[c].vocab] for c in cat_cols}
    if pid == 0:
        json.dump(
            {
                "nrows": t.nrows,
                "num_cols": num_cols,
                "count": stats["count"].tolist(),
                "mean": stats["mean"].round(4).tolist(),
                "nunique": stats["nunique"].tolist(),
                "cat_counts": cat_counts,
                "vocabs": vocabs,
                "dtype_names": {c: t.columns[c].dtype_name for c in t.col_names},
            },
            open(out, "w"),
        )
    """
)


@pytest.mark.slow
def test_two_process_stats_parity(tmp_path):
    rng = np.random.default_rng(5)
    n = 4000
    df = pd.DataFrame(
        {
            "a": rng.normal(size=n),
            "b": rng.integers(0, 50, n).astype("int64"),
            "wide_id": 10**15 + rng.integers(0, 1000, n).astype("int64"),
            "cat": rng.choice(["x", "y", "z", "w"], n),
        }
    )
    df.loc[rng.choice(n, 200, replace=False), "a"] = np.nan
    # an integer with nulls in the FIRST part only: host 0 reads pandas' nullable
    # integer, host 1 a plain int64, and both must take the masked integer branch
    clicks = pd.array(rng.integers(0, 1000, n), "Int64")
    clicks[rng.choice(n // 2, 300, replace=False)] = pd.NA
    clicks[5], clicks[n - 5] = 2**24 + 1, 2**24 + 3
    df["b"] = clicks
    data_dir = tmp_path / "data"
    data_dir.mkdir()
    # two part files with DIFFERENT category mixes so the vocab union matters
    df.iloc[: n // 2].to_parquet(data_dir / "part-00000.parquet", index=False)
    half2 = df.iloc[n // 2 :].copy()
    half2.loc[half2.index[:50], "cat"] = "only_in_part2"
    half2.to_parquet(data_dir / "part-00001.parquet", index=False)
    df_full = pd.concat([df.iloc[: n // 2], half2])

    worker = tmp_path / "worker.py"
    worker.write_text(_WORKER)
    out = tmp_path / "stats.json"
    port = "29517"
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), str(i), port, str(data_dir), str(out)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for i in range(2)
    ]
    logs = [p.communicate(timeout=300)[0] for p in procs]
    for p, log in zip(procs, logs):
        assert p.returncode == 0, f"worker failed:\n{log[-3000:]}"
    got = json.loads(out.read_text())

    assert got["nrows"] == n
    exp = df_full
    for i, c in enumerate(got["num_cols"]):
        assert got["count"][i] == int(exp[c].notna().sum()), c
        assert abs(got["mean"][i] - float(exp[c].mean())) < 1e-2 * max(1, abs(exp[c].mean())), c
        if c == "wide_id":  # exactness through the distributed wide pair
            assert got["nunique"][i] == exp[c].nunique(), c
    assert got["dtype_names"] == {"a": "double", "b": "bigint", "wide_id": "bigint", "cat": "string"}
    vocab = got["vocabs"]["cat"]
    assert "only_in_part2" in vocab  # union across hosts
    exp_counts = exp["cat"].value_counts()
    for v, cnt in zip(vocab, got["cat_counts"]["cat"]):
        assert int(cnt) == int(exp_counts.get(v, 0)), v


_DRIFT_WORKER = textwrap.dedent(
    """
    import os, sys
    pid = int(sys.argv[1]); port = sys.argv[2]; src_dir = sys.argv[3]; tgt_dir = sys.argv[4]; out = sys.argv[5]
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}", num_processes=2, process_id=pid
    )
    sys.path.insert(0, "/root/repo")
    from anovos_tpu.shared.runtime import init_runtime
    init_runtime()

    from anovos_tpu.data_ingest.distributed_ingest import read_dataset_distributed
    src = read_dataset_distributed(src_dir, "parquet")
    tgt = read_dataset_distributed(tgt_dir, "parquet")

    from anovos_tpu.drift_stability.drift_detector import statistics
    res = statistics(
        tgt, src, method_type="PSI|JSD", use_sampling=False,
        source_path=out + f"_model_p{pid}",
    )
    if pid == 0:
        res.to_json(out, orient="records")
    """
)


@pytest.mark.slow
def test_two_process_drift_parity(tmp_path):
    """The full drift pipeline (cutoff fit on device, fused per-side
    histograms, vocab-union categoricals) over two 2-process distributed
    tables must match the single-process computation to 1e-3 in PSI (f32
    reduction order differs across process shardings, so not bit-exact)."""
    rng = np.random.default_rng(7)
    n = 3000
    src_df = pd.DataFrame(
        {
            "x": rng.normal(0, 1, n),
            "y": rng.exponential(2, n),
            "cat": rng.choice(["a", "b", "c"], n, p=[0.5, 0.3, 0.2]),
        }
    )
    tgt_df = pd.DataFrame(
        {
            "x": rng.normal(0.4, 1.2, n),  # drifted
            "y": rng.exponential(2, n),
            "cat": rng.choice(["a", "b", "c"], n, p=[0.2, 0.3, 0.5]),
        }
    )
    src_dir, tgt_dir = tmp_path / "src", tmp_path / "tgt"
    for d, df in ((src_dir, src_df), (tgt_dir, tgt_df)):
        d.mkdir()
        df.iloc[: n // 2].to_parquet(d / "part-00000.parquet", index=False)
        df.iloc[n // 2 :].to_parquet(d / "part-00001.parquet", index=False)

    worker = tmp_path / "drift_worker.py"
    worker.write_text(_DRIFT_WORKER)
    out = tmp_path / "drift.json"
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), str(i), "29519", str(src_dir), str(tgt_dir), str(out)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for i in range(2)
    ]
    logs = [p.communicate(timeout=300)[0] for p in procs]
    for p, log in zip(procs, logs):
        assert p.returncode == 0, f"drift worker failed:\n{log[-3000:]}"
    got = pd.read_json(out).set_index("attribute")

    # single-process oracle over the identical data
    from anovos_tpu.drift_stability.drift_detector import statistics
    from anovos_tpu.shared.table import Table

    exp = statistics(
        Table.from_pandas(tgt_df), Table.from_pandas(src_df),
        method_type="PSI|JSD", use_sampling=False, source_path=str(tmp_path / "solo_model"),
    ).set_index("attribute")
    for c in ("x", "y", "cat"):
        assert abs(float(got.loc[c, "PSI"]) - float(exp.loc[c, "PSI"])) < 1e-3, c
        assert int(got.loc[c, "flagged"]) == int(exp.loc[c, "flagged"]), c
    assert int(exp.loc["x", "flagged"]) == 1  # the drift is real


_FAILURE_WORKER = textwrap.dedent(
    """
    import json, os, sys
    pid = int(sys.argv[1]); port = sys.argv[2]
    corrupt_dir = sys.argv[3]; single_dir = sys.argv[4]; out = sys.argv[5]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["ANOVOS_INGEST_RETRIES"] = "0"
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}", num_processes=2, process_id=pid
    )
    sys.path.insert(0, "/root/repo")
    from anovos_tpu.shared.runtime import init_runtime
    init_runtime()

    from anovos_tpu.data_ingest import guard
    from anovos_tpu.data_ingest.distributed_ingest import read_dataset_distributed
    import numpy as np

    # case 1: process 1's entire file slice (part-00001) is corrupt — its
    # frame degrades to empty-with-schema, the schema allgather still
    # converges, and the other shards' rows survive
    t = read_dataset_distributed(corrupt_dir, "parquet")
    from anovos_tpu.ops.describe import table_describe
    num_cols = [c for c in t.col_names if t.columns[c].kind == "num"]
    stats, _ = table_describe(t, num_cols, [])
    # each host quarantines ITS slice's parts: gather the union so the
    # asserting host sees the record made on the holder host
    from anovos_tpu.data_ingest.distributed_ingest import _allgather_obj
    local_q = [r.file.rsplit("/", 1)[-1] for r in guard.records()]
    quarantined = sorted({f for host in _allgather_obj(local_q) for f in host})

    # case 2: more processes than files — process 1 holds ZERO files and
    # must still converge through the schema allgather
    t2 = read_dataset_distributed(single_dir, "parquet")

    # case 3: host materialization of a multi-process table must raise
    # (non-addressable shards), not silently return a partial frame
    to_pandas_raised = ""
    try:
        t2.to_pandas()
    except Exception as e:
        to_pandas_raised = type(e).__name__
    if pid == 0:
        json.dump(
            {
                "nrows": t.nrows,
                "count": np.asarray(stats["count"]).tolist(),
                "quarantined": quarantined,
                "nrows_single": t2.nrows,
                "to_pandas_raised": to_pandas_raised,
            },
            open(out, "w"),
        )
    else:
        assert to_pandas_raised, "to_pandas must raise on process 1 too"
    """
)


@pytest.mark.slow
def test_two_process_failure_paths(tmp_path):
    """The hardened-ingest satellite matrix for read_dataset_distributed:
    a process whose whole slice is quarantined, a process holding zero
    files, and the multi-process to_pandas raise — every case must
    CONVERGE (the schema allgather runs on all hosts) instead of hanging
    the cluster or dying."""
    rng = np.random.default_rng(9)
    n_part = 400
    corrupt_dir = tmp_path / "corrupt"
    corrupt_dir.mkdir()
    for i in range(3):
        pd.DataFrame({
            "a": rng.normal(size=n_part),
            "cat": rng.choice(["u", "v"], n_part),
        }).to_parquet(corrupt_dir / f"part-{i:05d}.parquet", index=False)
    # files[1::2] == [part-00001] is process 1's whole slice: corrupt it
    bad = corrupt_dir / "part-00001.parquet"
    raw = bad.read_bytes()
    bad.write_bytes(raw[: len(raw) - 96])

    single_dir = tmp_path / "single"
    single_dir.mkdir()
    pd.DataFrame({"a": rng.normal(size=n_part)}).to_parquet(
        single_dir / "part-00000.parquet", index=False)

    worker = tmp_path / "failure_worker.py"
    worker.write_text(_FAILURE_WORKER)
    out = tmp_path / "failure.json"
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), str(i), "29523", str(corrupt_dir),
             str(single_dir), str(out)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for i in range(2)
    ]
    logs = [p.communicate(timeout=300)[0] for p in procs]
    for p, log in zip(procs, logs):
        assert p.returncode == 0, f"failure-path worker died:\n{log[-3000:]}"
    got = json.loads(out.read_text())

    assert got["nrows"] == 2 * n_part          # part-00001's rows are gone
    assert got["count"] == [2 * n_part]        # stats converge over survivors
    assert got["quarantined"] == ["part-00001.parquet"]  # on the holder host
    assert got["nrows_single"] == n_part       # zero-file host converged
    assert got["to_pandas_raised"]             # multi-process materialization raises
