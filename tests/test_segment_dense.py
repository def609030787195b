"""The group counts of a small class by one-hot contraction (PR 44):
``code_counts`` and ``code_label_counts`` against ``np.bincount`` to the bit on
the edges a contraction has, and the route a call takes.  (The lookups and
the layouts are in ``test_segment_dense_lookup.py`` and
``test_segment_dense_layout.py``; ``segment_cases.py`` says why three files.)"""

import jax.numpy as jnp
import numpy as np
import pytest

from anovos_tpu.ops import segment as sg
from segment_cases import CLASSES, ROWS, column, routes_seen, want_counts


@pytest.mark.parametrize("rows", ROWS.values(), ids=ROWS.keys())
@pytest.mark.parametrize("p", CLASSES)
def test_code_counts_is_bincount_to_the_bit(p, rows):
    codes, M, _ = column(p, rows, seed=p + rows)
    got = np.asarray(sg.code_counts(jnp.asarray(codes), jnp.asarray(M), p))
    assert got.shape == (p,) and got.dtype == np.float32
    assert np.array_equal(got, want_counts(codes, M, p)) and got[p - 1] >= 1


@pytest.mark.parametrize("rows", ROWS.values(), ids=ROWS.keys())
@pytest.mark.parametrize("p", CLASSES)
def test_code_label_counts_is_weighted_bincount_to_the_bit(p, rows):
    codes, M, y = column(p, rows, seed=2 * p + rows)
    got = np.asarray(sg.code_label_counts(jnp.asarray(codes), jnp.asarray(M), jnp.asarray(y), p))
    assert np.array_equal(got, want_counts(codes, M, p, y))
    ones = np.asarray(sg.code_label_counts(jnp.asarray(codes), jnp.asarray(M), jnp.ones(rows, jnp.float32), p))
    assert np.array_equal(ones, want_counts(codes, M, p))  # report_preprocessing's totals


@pytest.mark.parametrize("p", CLASSES)
def test_the_padded_lanes_are_zero_and_an_all_null_column_counts_nothing(p):
    vocab = p // 2 + 1  # the smallest vocabulary's neighbourhood of the class: half the lanes are padding
    codes, M, y = column(p, 5003, seed=p, vocab=vocab)
    for got in (sg.code_counts(jnp.asarray(codes), jnp.asarray(M), vocab),
                sg.code_label_counts(jnp.asarray(codes), jnp.asarray(M), jnp.asarray(y), vocab)):
        got = np.asarray(got)
        assert got.shape == (sg._bucket_segments(vocab),) and not got[vocab:].any() and got[:vocab].any()
    codes, M, y = column(p, 5003, seed=p, all_null=True)
    assert not np.asarray(sg.code_counts(jnp.asarray(codes), jnp.asarray(M), p)).any()
    assert not np.asarray(sg.code_label_counts(jnp.asarray(codes), jnp.asarray(M), jnp.asarray(y), p)).any()


def test_the_routes_follow_from_the_class_the_dtype_and_the_layout(monkeypatch):
    """What the public functions hand their programs: seen on the static ``dense`` they pass."""
    seen = routes_seen(monkeypatch)
    c, m = jnp.zeros(4096, jnp.int32), jnp.ones(4096, bool)
    for p in CLASSES:
        sg.code_counts(c, m, p), sg.code_label_counts(c, m, m.astype(jnp.float32), p)
        sg.vocab_lookup(np.zeros(p, np.float32), c), sg.vocab_lookup(np.zeros(p, bool), c)
    assert seen == [True] * 4 * 4 + [False] * 4  # to 65,536 lanes by contraction, 131,072 as before
    assert sg._DENSE_COUNT_LANES_MAX == sg._DENSE_GATHER_LANES_MAX == 65_536
    assert not sg._dense_class(48, 65_536)  # no power of two: no two levels
    assert [sg._levels(p) for p in (1, 8, 16, 256, 4096, 65_536)] == [(1, 1), (2, 4), (4, 4), (16, 16), (64, 64), (256, 256)]
    assert sg.dense_chunks(5003, 8192) == 5003 and sg.dense_chunks(5 * 8192, 32_768) == 8192
    assert sg.dense_chunks(1_572_864, sg._DENSE_CHUNK_ROWS) == sg._DENSE_CHUNK_ROWS == 8192


# the padded classes of the Criteo cut's 26 vocabularies (benchmark/configs/criteo_display.json, distinct_at_rows)
_CRITEO_VOCABS = [364_858, 356_085, 348_506, 335_089, 285_498, 154_114, 85_973, 81_118, 14_992, 12_517, 5_683, 5_652,
                  3_194, 2_173, 1_460, 633, 583, 305, 105, 27, 24, 18, 15, 10, 4, 3]


def test_segment_routes_on_the_click_logs_26_classes():
    classes = [sg._bucket_segments(v) for v in _CRITEO_VOCABS]
    assert sg.segment_routes(classes, "counts") == {"dense_counts": 18, "scatter_counts": 8}
    assert sg.segment_routes(classes, "gathers") == {"dense_gathers": 18, "index_gathers": 8}
    # cat_to_num_supervised makes two calls a column
    assert sg.segment_routes(2 * classes, "counts") == {"dense_counts": 36, "scatter_counts": 16}
    assert sg.segment_routes(2 * classes, "gathers") == {"dense_gathers": 36, "index_gathers": 16}
    assert sg.segment_routes(classes, "counts", sharded=True) == {"dense_counts": 0, "scatter_counts": 26}
    assert sg.segment_routes([], "counts") == {"dense_counts": 0, "scatter_counts": 0}
    with pytest.raises(KeyError):
        sg.segment_routes(classes, "sorts")
