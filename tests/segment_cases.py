"""Columns and LUTs on the edges a one-hot contraction has, for the
``test_segment_dense*.py`` files (three files of under 30 tests each: xdist's
``loadfile`` hands files out by their number of tests, and a longer file
moves ``tests/benchmark/test_benchmark_harness.py`` behind a worker that has
warmed its programs; PERF.md section 7 item 19)."""

import numpy as np

from anovos_tpu.ops import segment as sg

CLASSES = [16, 256, 4096, 65_536, 131_072]
# one chunk of a length no chunk divides, and a scan of five chunks
ROWS = {"one_odd_chunk": 5003, "the_scan": 5 * 8192}


def column(p, rows, seed, vocab=None, all_null=False):
    """Codes of a vocabulary that fills its class to the last lane (or of
    ``vocab`` values), a tenth null (-1), and a mask that drops a tenth more."""
    g = np.random.default_rng(seed)
    vocab = vocab or p
    codes = g.integers(0, vocab, rows).astype(np.int32)
    codes[: min(rows, 7)] = vocab - 1  # the last lane is counted
    codes[g.random(rows) < 0.1] = -1
    if all_null:
        codes[:] = -1
    return codes, g.random(rows) > 0.1, (g.random(rows) < 0.3).astype(np.float32)


def want_counts(codes, M, p, weights=None):
    valid = M & (codes >= 0)
    return np.bincount(codes[valid], weights=None if weights is None else weights[valid], minlength=p).astype(np.float32)


def luts(p, seed):
    g = np.random.default_rng(seed)
    f32 = g.normal(0.0, 50.0, p).astype(np.float32)
    f32[:6] = [-0.0, 1e-45, -1e-42, np.finfo(np.float32).max, -np.finfo(np.float32).tiny, 0.0]
    odd = f32.copy()
    odd[6:9] = [np.inf, -np.inf, np.nan]
    return {"bool": g.random(p) < 0.5, "f32": f32, "f32_inf_nan": odd,
            "int32": g.integers(-2**31, 2**31 - 1, p, dtype=np.int64).astype(np.int32),
            "f16": g.normal(0.0, 5.0, p).astype(np.float16)}


def routes_seen(monkeypatch):
    """The static ``dense`` every later call of the three programs is handed, in order."""
    seen = []
    for name in ("_code_counts_p", "_code_label_counts_p", "_lut_gather"):
        real = getattr(sg, name)
        monkeypatch.setattr(sg, name, lambda *a, _real=real, dense, **k: seen.append(dense) or _real(*a, dense=dense, **k))
    return seen
