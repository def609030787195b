"""The LUT gather of a small class by one-hot contraction with the LUT's
bytes (PR 44): ``vocab_lookup`` against ``np.take`` to the bit, and both routes
of each of the three programs lane for lane."""

import jax.numpy as jnp
import numpy as np
import pytest

from anovos_tpu.ops import segment as sg
from segment_cases import CLASSES, ROWS, column, luts


@pytest.mark.parametrize("kind", ["bool", "f32", "f32_inf_nan", "int32", "f16"])
@pytest.mark.parametrize("p", CLASSES)
def test_vocab_lookup_is_take_to_the_bit(p, kind):
    """Nulls read lane 0 and a code past the class its last lane, as the index
    gather's clip has it; the LUT's bytes come back whatever they spell; in one
    chunk of a length no chunk divides and in a scan of five."""
    lut = luts(p, seed=p)[kind]
    for rows in ROWS.values():
        codes, _, _ = column(p, rows, seed=3 * p + rows)
        codes[7:16] = np.arange(9)  # the lanes of -0.0, the denormals, inf and NaN are read
        codes[16] = p + 5
        got = np.asarray(sg.vocab_lookup(lut, jnp.asarray(codes)))
        want = lut[np.clip(codes, 0, p - 1)]
        assert got.dtype == want.dtype and got.tobytes() == want.tobytes(), rows


@pytest.mark.parametrize("p", [16, 256, 4096, 65_536])
def test_both_routes_of_one_class_are_equal_lane_for_lane(p):
    rows = 5 * 8192
    codes, M, y = column(p, rows, seed=5 * p)
    c, m, w = jnp.asarray(codes), jnp.asarray(M), jnp.asarray(y)
    assert np.array_equal(np.asarray(sg._code_counts_p(c, m, p, dense=True)), np.asarray(sg._code_counts_p(c, m, p)))
    assert np.array_equal(np.asarray(sg._code_label_counts_p(c, m, w, p, dense=True)),
                          np.asarray(sg._code_label_counts_p(c, m, w, p)))
    for lut in luts(p, seed=p).values():
        dense, index = (np.asarray(sg._lut_gather(jnp.asarray(lut), c, dense=d)) for d in (True, False))
        assert dense.dtype == index.dtype and dense.tobytes() == index.tobytes()
