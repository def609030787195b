"""The supervised encoder and the mode imputation on a vocabulary beyond the
coarse segment classes (a click log's hashed ids): 200,000 distinct values in
300,000 rows with nulls, against float64 pandas; the padded segment classes
(16^k up to 65,536, 2^k above); the model frame built only where it is
written, with the bytes it always had; the imputation's exact integer fill."""

import os

import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from anovos_tpu.data_transformer import transformers as T
from anovos_tpu.obs import get_tracer
from anovos_tpu.ops import segment
from anovos_tpu.shared.table import Table

ROWS, DISTINCT = 300_000, 200_000


@pytest.mark.parametrize("n,lanes", [
    (0, 16), (1, 16), (16, 16), (17, 256), (41, 256), (256, 256), (257, 4096), (4096, 4096), (4097, 65536),
    (32_561, 65536), (65_536, 65536),  # the four coarse classes, as every accepted cell compiles them
    (65_537, 131_072), (131_072, 131_072), (131_073, 262_144), (200_000, 262_144), (364_858, 524_288),
    (1_048_576, 1_048_576), (1_048_577, 2_097_152), (10_131_227, 16_777_216)])
def test_segment_classes_are_sixteen_to_the_k_up_to_65536_and_powers_of_two_above(n, lanes):
    assert segment._bucket_segments(n) == lanes
    assert lanes >= max(n, 1) and (n <= 65_536 or lanes < 2 * n)


@pytest.fixture(scope="module")
def click_frame():
    """300,000 rows: an id column of 200,000 distinct 8-hex values (every one
    present, a tenth of the rows null), a small column with a tie for its
    mode, a click."""
    rng = np.random.default_rng(34)
    ids = np.concatenate([np.arange(DISTINCT), rng.integers(0, DISTINCT, ROWS - DISTINCT)])
    null = np.concatenate([np.zeros(DISTINCT, bool), rng.random(ROWS - DISTINCT) < 0.3])  # no value's only row
    order = rng.permutation(ROWS)
    big = np.array([f"{v:08x}" for v in (ids[order].astype(np.uint64) * 2654435761 % 2**32)], dtype=object)
    big[null[order]] = None
    small = np.array(["b", "a", "c"], dtype=object)[np.arange(ROWS) % 3]
    small[:3] = None  # one of each goes: a, b and c stay tied
    return pd.DataFrame({"big": big, "small": small, "label": (rng.random(ROWS) < 0.26).astype(np.int32)})


@pytest.fixture(scope="module")
def click_table(click_frame):
    return Table.from_pandas(click_frame)


def _spans(fn, *args, **kw):
    """``fn``'s result and the ``transform/*`` phases it opened, as a pass would record them."""
    tracer = get_tracer()
    with tracer.run_pass():
        out = fn(*args, **kw)
    return out, {r["name"]: r["counts"] for r in tracer.phases() if r["name"].startswith("transform/")}


def test_cat_to_num_supervised_on_200000_categories_against_float64_pandas(click_frame, click_table):
    out, spans = _spans(T.cat_to_num_supervised, click_table, ["big", "small"], label_col="label", event_label=1)
    got = out.to_pandas()
    assert list(got.columns) == list(click_frame.columns) and len(got) == ROWS
    for c in ("big", "small"):
        grouped = click_frame.groupby(c)["label"].agg(["sum", "count"])
        want = click_frame[c].map(grouped["sum"] / grouped["count"]).to_numpy("float64")
        have = got[c].to_numpy("float64")
        assert np.array_equal(np.isnan(have), click_frame[c].isna().to_numpy())  # a null stays a null
        valid = ~np.isnan(have)
        assert np.abs(have[valid] - want[valid]).max() <= 0.5e-4 + 1e-7  # the share, to 4 decimals
    padded = click_table.padded_rows
    assert spans["transform/fit"] == {
        "cols": 2, "rows": padded, "vocab_max": DISTINCT, "segments_max": 262_144,  # a power of two above 65,536
        "count_rows": 2 * padded, "label_rows": 2 * padded, "seg_lanes": 2 * (262_144 + 16),
        "dense_counts": 0, "scatter_counts": 4}  # a count and a label count a column; on the suite's mesh none by contraction
    assert spans["transform/apply"]["lut_bytes"] == 5 * (262_144 + 16)  # not 5 x 1,048,576: the class is 2^18
    assert spans["transform/apply"]["gather_rows"] == 4 * padded
    assert (spans["transform/apply"]["dense_gathers"], spans["transform/apply"]["index_gathers"]) == (0, 4)


def test_group_counts_are_exact_at_the_padded_class(click_frame, click_table):
    col = click_table.columns["big"]
    counts = np.asarray(segment.code_counts(col.data, col.mask, len(col.vocab)))
    assert counts.shape == (262_144,) and not counts[len(col.vocab):].any()
    want = click_frame["big"].value_counts().reindex(col.vocab).to_numpy()
    assert np.array_equal(counts[:len(col.vocab)], want) and counts.sum() == click_frame["big"].notna().sum()
    y = jnp.asarray(np.pad(click_frame["label"].to_numpy("float32"), (0, click_table.padded_rows - ROWS)))
    events = np.asarray(segment.code_label_counts(col.data, col.mask, y, len(col.vocab)))
    want = click_frame.groupby("big")["label"].sum().reindex(col.vocab).to_numpy()
    assert np.array_equal(events[:len(col.vocab)], want)


def test_imputation_mode_on_200000_categories_and_a_tie(click_frame, click_table):
    out, spans = _spans(T.imputation_MMM, click_table, list_of_cols="missing", method_type="median")
    got = out.to_pandas()
    counts = click_frame["big"].value_counts()
    mode = min(counts.index[counts == counts.iloc[0]])  # a tie: the first in code-point order
    assert not got["big"].isna().any() and not got["small"].isna().any()
    was_null = click_frame["big"].isna().to_numpy()
    assert (got["big"][was_null] == mode).all() and (got["big"][~was_null] == click_frame["big"][~was_null]).all()
    assert (got["small"][:3] == "a").all() and (got["small"][3:] == click_frame["small"][3:]).all()
    assert np.array_equal(got["label"], click_frame["label"])
    assert spans["transform/fit"]["cols"] == 2 and spans["transform/apply"]["cols"] == 2
    assert spans["transform/fit"]["vocab_max"] == DISTINCT and spans["transform/fit"]["segments_max"] == 262_144
    assert spans["transform/fit"]["seg_lanes"] == 262_144 + 16 and "label_rows" not in spans["transform/fit"]
    assert (spans["transform/fit"]["dense_counts"], spans["transform/fit"]["scatter_counts"]) == (0, 2)
    assert "dense_counts" not in spans["transform/apply"]  # the fills are no call of ops/segment.py


def test_the_model_frame_is_built_only_where_it_is_written_and_has_the_bytes_it_had(tmp_path, monkeypatch):
    df = pd.DataFrame({"g": ["a", "a", "b", "b", "b", None, "c"], "label": [1, 0, 1, 1, 0, 1, 0]})
    t = Table.from_pandas(df)
    frames = []
    real = pd.DataFrame
    monkeypatch.setattr(T.pd, "DataFrame", lambda *a, **k: frames.append(1) or real(*a, **k))
    plain = T.cat_to_num_supervised(t, ["g"], label_col="label", event_label=1)
    assert not frames  # no model frame, so no str() a distinct value
    saved = T.cat_to_num_supervised(t, ["g"], label_col="label", event_label=1, model_path=str(tmp_path / "m"))
    assert frames == [1]
    monkeypatch.undo()
    assert plain.to_pandas().equals(saved.to_pandas())
    path = tmp_path / "m" / "cat_to_num_supervised" / "g" / "part-00000.csv"
    by_hand = tmp_path / "by_hand.csv"
    pd.DataFrame({"g": ["a", "b", "c"], "g_encoded": np.array([0.5, 0.6667, 0.0], np.float32).astype(np.float64)}
                 ).to_csv(by_hand, index=False)
    assert path.read_bytes() == by_hand.read_bytes()
    again = T.cat_to_num_supervised(t, ["g"], pre_existing_model=True, model_path=str(tmp_path / "m"))
    assert again.to_pandas()["g"].round(4).tolist()[:5] == [0.5, 0.5, 0.6667, 0.6667, 0.6667]
    assert os.listdir(tmp_path / "m" / "cat_to_num_supervised") == ["g"]


def test_an_integer_fill_keeps_values_beyond_2_to_the_24():
    big = 2**24 + 1
    t = Table.from_numpy({"n": np.ma.MaskedArray(np.array([big, 7, 3, 30_000_001, 5, 9, 11], np.int64),
                                                 mask=[False, True, False, False, True, False, False])})
    col = t.columns["n"]
    assert col.data.dtype == jnp.int32 and col.dtype_name == "bigint" and not col.is_wide
    assert np.asarray(col.mask)[:7].tolist() == [True, False, True, True, False, True, True]
    out = T.imputation_MMM(t, list_of_cols="missing", method_type="median").to_pandas()["n"]
    # the lower median of 3 9 11 big 30000001 fills; what was there stays to the unit (f32 would round both)
    assert out.dtype == np.int32 and out.tolist() == [big, 11, 3, 30_000_001, 11, 9, 11]
