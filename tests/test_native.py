"""Native C++ decode library tests (parity vs the pure-Python path)."""

import numpy as np
import pandas as pd
import pytest

from anovos_tpu.data_ingest import avro_io
from anovos_tpu.shared import native as nat
from anovos_tpu.shared.table import Table

from anovos_tpu.data_ingest.synthetic import DEFAULT_DIR

REF_AVRO = str(DEFAULT_DIR / "join" / "part-00000.avro")


@pytest.fixture(scope="module")
def lib():
    lib = nat.get_native()
    if lib is None:
        pytest.skip("native library unavailable (no toolchain)")
    return lib


def _python_decode(path):
    saved_lib, saved_tried = nat._LIB, nat._TRIED
    nat._LIB, nat._TRIED = None, True
    try:
        return avro_io.read_avro(path)
    finally:
        nat._LIB, nat._TRIED = saved_lib, saved_tried


def test_native_avro_parity_income_join(lib):
    out_n = avro_io.read_avro(REF_AVRO)
    out_p = _python_decode(REF_AVRO)
    assert set(out_n) == set(out_p)
    for k in out_p:
        a, b = out_n[k], out_p[k]
        if isinstance(a, nat.NativeEncodedStrings):
            a = a.to_object_array()
        if getattr(b, "dtype", None) == object:
            assert all((x == y) or (x is None and y is None) for x, y in zip(a, b)), k
        else:
            np.testing.assert_allclose(
                np.nan_to_num(np.asarray(a, float), nan=-9e9),
                np.nan_to_num(np.asarray(b, float), nan=-9e9),
            )


def test_native_avro_parity_deflate(lib, tmp_path):
    df = pd.DataFrame(
        {
            "s": ["alpha", None, "gamma", "alpha"] * 50,
            "x": [1.5, 2.5, np.nan, 4.0] * 50,
            "n": list(range(200)),
        }
    )
    path = str(tmp_path / "t.avro")
    avro_io.write_avro(df, path, codec="deflate")
    out = avro_io.read_avro(path)
    s = out["s"]
    if isinstance(s, nat.NativeEncodedStrings):
        s = s.to_object_array()
    assert s[0] == "alpha" and s[1] is None
    np.testing.assert_allclose(np.nan_to_num(np.asarray(out["x"], float), nan=-1), np.nan_to_num(df["x"].to_numpy(), nan=-1))


def test_native_encoded_strings_into_table(lib, income_df):
    out = avro_io.read_avro(REF_AVRO)
    t = Table.from_numpy(out, nrows=len(out["ifa"]))
    assert t["workclass"].kind == "cat"
    df = t.to_pandas()
    assert df["workclass"].iloc[0] == income_df["workclass"].iloc[0]
    # vocab is sorted (canonical convention shared with np.unique encoding)
    vocab = t["workclass"].vocab
    assert list(vocab) == sorted(vocab)


def test_native_avro_encode_roundtrip(tmp_path):
    """Write half of the native IO layer: C++ block encoder produces a
    container the (native) reader round-trips exactly; falls back cleanly."""
    import numpy as np
    import pandas as pd

    from anovos_tpu.data_ingest import avro_io
    from anovos_tpu.shared.native import NativeEncodedStrings

    rng = np.random.default_rng(1)
    n = 3000
    df = pd.DataFrame(
        {
            "f": rng.normal(size=n),
            "i": rng.integers(-(10**12), 10**12, n),
            "b": rng.random(n) > 0.5,
            "s": rng.choice(["alpha", "beta", "γamma"], n).astype(object),
        }
    )
    df.loc[rng.choice(n, 100, replace=False), "f"] = np.nan
    df.loc[rng.choice(n, 80, replace=False), "s"] = None
    p = tmp_path / "x.avro"
    avro_io.write_avro(df, str(p))
    dec = avro_io.read_avro(str(p))
    got_s = dec["s"].to_object_array() if isinstance(dec["s"], NativeEncodedStrings) else dec["s"]
    np.testing.assert_allclose(
        np.nan_to_num(np.asarray(dec["f"], float), nan=-9),
        np.nan_to_num(df["f"].to_numpy(), nan=-9), rtol=1e-6,
    )
    np.testing.assert_array_equal(np.asarray(dec["i"]).astype(np.int64), df["i"].to_numpy())
    np.testing.assert_array_equal(np.asarray(dec["b"]).astype(bool), df["b"].to_numpy())
    assert all((a == b) or (a is None and pd.isna(b)) for a, b in zip(got_s, df["s"]))


def test_edge_components_matches_scipy():
    """The native union-find (plain and min-count-thresholded) must label
    components exactly as scipy's weak connectivity on the same
    upper-triangular edge set — it replaces scipy in the DBSCAN
    hyperparameter grid (ops/cluster.dbscan_host_grid_multi)."""
    import numpy as np
    import pytest
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components

    from anovos_tpu.shared.native import (
        native_edge_components, native_edge_components_minc)

    if native_edge_components(np.array([0]), np.array([1]), 2) is None:
        pytest.skip("native library unavailable")

    rng = np.random.default_rng(7)
    n = 400
    for trial in range(5):
        m = rng.integers(0, 1200)
        ei = rng.integers(0, n, m)
        ej = rng.integers(0, n, m)
        keep = ei < ej  # upper-triangular, self-loops dropped (grid contract)
        ei, ej = ei[keep], ej[keep]
        nc, lab = native_edge_components(ei, ej, n)
        g = coo_matrix((np.ones(len(ei)), (ei, ej)), shape=(n, n))
        nc_ref, lab_ref = connected_components(g, directed=True, connection="weak")
        assert nc == nc_ref
        np.testing.assert_array_equal(lab, lab_ref)

        # thresholded variant == filter-then-plain on the kept edges
        minc = rng.integers(0, 10, len(ei))
        for thresh in (0, 3, 7, 11):
            nct, labt = native_edge_components_minc(ei, ej, minc, thresh, n)
            k = minc >= thresh
            ncp, labp = native_edge_components(ei[k], ej[k], n)
            assert nct == ncp
            np.testing.assert_array_equal(labt, labp)


def test_dbscan_grid_native_equals_scipy_fallback():
    """End-to-end grid parity: the native path and the scipy fallback must
    produce identical label grids (core labeling AND border adoption)."""
    import numpy as np
    import jax.numpy as jnp

    import anovos_tpu.shared.native as nat
    from anovos_tpu.ops.cluster import dbscan_host_grid_multi, pairwise_d2

    rng = np.random.default_rng(5)
    X = np.concatenate([
        rng.normal([0, 0], 0.2, (300, 2)), rng.normal([2, 2], 0.2, (300, 2)),
        rng.uniform(-1, 3, (100, 2)),
    ]).astype(np.float32)
    D2 = np.asarray(pairwise_d2(jnp.asarray(X)))
    eps, ms = [0.2, 0.3, 0.4], [3, 6, 9, 12]
    native = dbscan_host_grid_multi(D2, eps, ms)
    orig = nat.native_edge_components_minc
    nat.native_edge_components_minc = lambda *a, **k: None
    try:
        fallback = dbscan_host_grid_multi(D2, eps, ms)
    finally:
        nat.native_edge_components_minc = orig
    np.testing.assert_array_equal(native, fallback)


def test_stale_so_rebuilds_instead_of_disabling(tmp_path, monkeypatch):
    """A prebuilt .so missing a newer export (mtimes equal — rsync -a/tar
    deployment defeats the staleness check) must trigger a rebuild from
    the adjacent source and load, not silently disable the whole native
    layer."""
    import os
    import shutil
    import subprocess

    import anovos_tpu.shared.native as nat

    if nat.get_native() is None:
        import pytest

        pytest.skip("no toolchain")
    src = os.path.join(tmp_path, "anovos_native.cpp")
    shutil.copy(os.path.join(os.path.dirname(__file__), "..", "native",
                             "anovos_native.cpp"), src)
    stale_src = tmp_path / "old.cpp"
    stale_src.write_text('extern "C" { long long avro_decode() { return -9; } }\n')
    so = os.path.join(tmp_path, "libanovos_native.so")
    subprocess.run(["g++", "-O3", "-shared", "-fPIC", str(stale_src), "-o", so],
                   check=True)
    # equal mtimes: the src-newer check must NOT fire; only the missing
    # edge_components_minc symbol reveals the staleness
    t = os.path.getmtime(src)
    os.utime(so, (t, t))
    monkeypatch.setattr(nat, "_NATIVE_DIR", str(tmp_path))
    monkeypatch.setattr(nat, "_SO_PATH", so)
    monkeypatch.setattr(nat, "_LIB", None)
    monkeypatch.setattr(nat, "_TRIED", False)
    lib = nat.get_native()
    assert lib is not None and hasattr(lib, "edge_components_minc")
    # restore the module-level cache for other tests in this process
    monkeypatch.setattr(nat, "_LIB", None)
    monkeypatch.setattr(nat, "_TRIED", False)
