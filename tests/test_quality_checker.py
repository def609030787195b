"""quality_checker tests (reference style: test_quality_checker.py, 11 tests)."""

import numpy as np
import pandas as pd
import pytest

from anovos_tpu.data_analyzer import quality_checker as qc
from anovos_tpu.shared.table import Table


@pytest.fixture()
def qdf():
    return Table.from_pandas(
        pd.DataFrame(
            {
                "a": [1.0, 2.0, 2.0, np.nan, 5.0, 2.0],
                "b": ["x", "y", "y", None, "z", "y"],
                "c": [10, 20, 20, 30, 40, 20],
            }
        )
    )


def test_duplicate_detection(qdf):
    odf, stats = qc.duplicate_detection(qdf, treatment=True)
    d = dict(zip(stats["metric"], stats["value"]))
    assert d["rows_count"] == 6.0
    assert d["unique_rows_count"] == 4.0  # rows 1,2,5 identical (2.0,y,20)
    assert d["duplicate_rows"] == 2.0
    assert odf.nrows == 4


def test_nullrows_detection(qdf):
    odf, stats = qc.nullRows_detection(qdf, treatment=True, treatment_threshold=0.5)
    # row 3 has 2/3 nulls > 0.5 → removed
    assert odf.nrows == 5
    assert "treated" in stats.columns


def test_nullcolumns_row_removal(qdf):
    odf, stats = qc.nullColumns_detection(qdf, treatment=True, treatment_method="row_removal")
    assert odf.nrows == 5
    assert set(stats["attribute"]) == {"a", "b"}


def test_nullcolumns_MMM(qdf):
    odf, stats = qc.nullColumns_detection(
        qdf, treatment=True, treatment_method="MMM", treatment_configs={"method_type": "median"}
    )
    df = odf.to_pandas()
    assert not df["a"].isna().any()
    assert df["a"][3] == 2.0
    assert df["b"][3] == "y"


def test_nullcolumns_column_removal(qdf):
    odf, _ = qc.nullColumns_detection(
        qdf,
        treatment=True,
        treatment_method="column_removal",
        treatment_configs={"treatment_threshold": 0.1},
    )
    assert "a" not in odf.col_names and "b" not in odf.col_names and "c" in odf.col_names


def test_outlier_detection_upper():
    rng = np.random.default_rng(0)
    vals = np.concatenate([rng.normal(50, 5, 500), [500.0, 600.0]])
    t = Table.from_pandas(pd.DataFrame({"v": vals}))
    odf, stats = qc.outlier_detection(
        t, ["v"], detection_side="upper", treatment=True, treatment_method="value_replacement"
    )
    assert stats.set_index("attribute").loc["v", "upper_outliers"] >= 2
    assert odf.to_pandas()["v"].max() < 500


def test_outlier_model_roundtrip(tmp_path):
    rng = np.random.default_rng(1)
    t = Table.from_pandas(pd.DataFrame({"v": rng.normal(0, 1, 400)}))
    mp = str(tmp_path / "m")
    _, s1 = qc.outlier_detection(t, ["v"], detection_side="both", model_path=mp, treatment=False)
    _, s2 = qc.outlier_detection(
        t, ["v"], detection_side="both", pre_existing_model=True, model_path=mp, treatment=False
    )
    pd.testing.assert_frame_equal(s1, s2)


def test_idness_detection(qdf):
    df = pd.DataFrame({"id": [f"u{i}" for i in range(10)], "g": ["a", "b"] * 5})
    t = Table.from_pandas(df)
    odf, stats = qc.IDness_detection(t, treatment=True, treatment_threshold=0.9)
    assert "id" not in odf.col_names and "g" in odf.col_names
    assert stats.set_index("attribute").loc["id", "treated"] == 1


def test_biasedness_detection():
    df = pd.DataFrame({"biased": ["m"] * 97 + ["f"] * 3, "ok": ["a", "b"] * 50})
    t = Table.from_pandas(df)
    odf, stats = qc.biasedness_detection(t, treatment=True, treatment_threshold=0.9)
    assert "biased" not in odf.col_names and "ok" in odf.col_names


def test_invalid_entries_detection():
    df = pd.DataFrame(
        {
            "s": ["hello", "n/a", "aaa", "abcd", "fine", ":"],
            "n": [1.0, 2.0, 9999.0, 3.0, 4.0, 5.0],
        }
    )
    t = Table.from_pandas(df)
    odf, stats = qc.invalidEntries_detection(t, treatment=True, treatment_method="null_replacement")
    st = stats.set_index("attribute")
    # n/a (null vocab), aaa (repeated), abcd (ordinal run), : (special char)
    assert st.loc["s", "invalid_count"] == 4
    assert st.loc["n", "invalid_count"] == 1  # 9999.0 → repeated chars
    out = odf.to_pandas()
    assert pd.isna(out["s"][1]) and pd.isna(out["s"][2]) and pd.isna(out["s"][3])
    assert out["s"][0] == "hello"
    assert np.isnan(out["n"][2])


def test_invalid_entries_manual():
    df = pd.DataFrame({"s": ["apple", "banana", "forbidden"]})
    t = Table.from_pandas(df)
    _, stats = qc.invalidEntries_detection(
        t, detection_type="manual", invalid_entries=["forbidden"], treatment=False
    )
    assert stats["invalid_count"][0] == 1


def test_unique_compact_buffer_is_exactly_rows_long():
    """The compaction buffer keeps the padded row count as its length (a
    length every mesh divides — as rows+1 it crashed the TPU's SPMD
    partitioner on four chips), and still holds every distinct value."""
    import jax.numpy as jnp

    from anovos_tpu.data_analyzer.quality_checker import _unique_compact

    data = jnp.asarray([3.0, 1.0, 3.0, 2.0, 9.0, 1.0, 7.0, 7.0])
    for mask in ([True] * 8, [True, True, True, True, False, True, False, False]):
        m = jnp.asarray(mask)
        buf, nu = _unique_compact(data, m)
        assert buf.shape == data.shape
        want = sorted(set(float(v) for v, k in zip(data, mask) if k))
        assert [float(v) for v in buf[: int(nu)]] == want
