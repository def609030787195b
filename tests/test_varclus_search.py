"""The search phase of the variable clustering values a move by the largest
eigenvalue of each of the two clusters it would leave.  Since PR 46 it takes
that eigenvalue from ``np.linalg.eigvalsh`` (no eigenvectors: a third of the
time, and the host VarClus was a quarter of a ``home_credit.association``
pass and the part of it that changed from seed to seed).  This file holds the
loop it replaced, which took it from ``_correig``'s full ``eigh``, and shows
the same clusters and the same R-square table on correlation matrices with
block structure, with pure noise, with duplicated columns and on a credit-like
table with flags."""

import numpy as np
import pandas as pd
import pytest

from anovos_tpu.data_analyzer.association_eval_varclus import VarClusJax


class EighSearch(VarClusJax):
    """The search phase as it was: both eigenvalues of a cluster from ``_correig``."""

    def _first_eig(self, feats):
        vals, _, props = self._correig(feats)
        return float(vals[0]), float(props[0])


def _corr(X):
    names = [f"c{i:02d}" for i in range(X.shape[1])]
    return pd.DataFrame(np.corrcoef(X.T), columns=names, index=names)


def blocks(rng, k):
    factors = rng.standard_normal((600, 4))
    return factors @ rng.standard_normal((4, k)) + 0.8 * rng.standard_normal((600, k))


def noise(rng, k):
    return rng.standard_normal((600, k))


def duplicates(rng, k):
    X = rng.standard_normal((600, k))
    X[:, 1] = X[:, 0]
    X[:, 3] = -X[:, 2]
    return X


def credit_like(rng, k):
    z = rng.standard_normal(600)
    cols = [(rng.random(600) < 0.5 / (1 + i)).astype(float) + 0.05 * z * (i % 3 == 0) for i in range(k // 2)]
    cols += [np.exp(0.3 * z + rng.standard_normal(600)) for _ in range(k - k // 2)]
    return np.column_stack(cols)


@pytest.mark.parametrize("make", [blocks, noise, duplicates, credit_like])
@pytest.mark.parametrize("k", [9, 30])
def test_eigvalsh_search_gives_the_clusters_of_the_eigh_search(make, k):
    for seed in range(3):
        C = _corr(make(np.random.default_rng([seed, k]), k))
        was, now = EighSearch(C).fit(), VarClusJax(C).fit()
        assert [c["clus"] for c in was.clusters.values()] == [c["clus"] for c in now.clusters.values()]
        pd.testing.assert_frame_equal(was.rsquare_table().round(9), now.rsquare_table().round(9))


def test_first_eig_of_small_clusters_and_of_a_block():
    C = _corr(blocks(np.random.default_rng(3), 6))
    vc = VarClusJax(C)
    assert vc._first_eig(["c00"]) == (1.0, 1.0)
    r = C.loc["c00", "c01"]
    first, share = vc._first_eig(["c00", "c01"])
    assert first == pytest.approx(1 + abs(r)) and share == pytest.approx((1 + abs(r)) / 2)
    vals, _, props = vc._correig(list(C.columns))
    first, share = vc._first_eig(list(C.columns))
    assert first == pytest.approx(vals[0], rel=1e-12) and share == pytest.approx(props[0], rel=1e-12)
    assert vc._tot_var(["c00", "c01"], [], ["c02"])[0] == pytest.approx(1 + abs(r) + 1.0)
