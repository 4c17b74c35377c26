"""``fit_distributed``'s per-node cover: the hessian sum of the rows
each node holds, inner nodes from their level's histogram and leaves
from the leaf sums, as XGBoost reports it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import boosting, distributed


def _data(n=600, f=4, seed=5):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, f))
    y = (x[:, 0] + 0.5 * x[:, 1] > 0).astype(jnp.float32)
    return np.asarray(x), np.asarray(y)


def _routed_hess_sums(x, feature, threshold, h, depth):
    """float64 hessian sum of each node, rows descended on raw values."""
    n = x.shape[0]
    sums = np.zeros(2 ** (depth + 1) - 1)
    node = np.zeros(n, np.int64)
    for d in range(depth + 1):
        heap = 2 ** d - 1 + node
        sums += np.bincount(heap, h, len(sums))
        if d == depth:
            break
        f = feature[heap]
        left = (f < 0) | (x[np.arange(n), f.clip(0)] <= threshold[heap])
        node = 2 * node + np.where(left, 0, 1)
    return sums


@pytest.mark.parametrize("subtract", [False, True])
def test_first_trees_cover_is_the_routed_hessian_sum(subtract):
    x, y = _data()
    cfg = boosting.GBDTConfig(n_trees=2, max_depth=3, n_candidates=8,
                              subtract=subtract)
    mesh = jax.make_mesh((1,), ("data",))
    model = distributed.fit_distributed(x, y, cfg, mesh,
                                        jax.random.PRNGKey(3))
    cover = np.asarray(model.cover)
    assert cover.shape == (2, 2 ** 4 - 1)
    # round 0 starts every row at the base score: one hessian for all
    p = 1 / (1 + np.exp(-model.base_score))
    h = np.full(len(y), p * (1 - p))
    want = _routed_hess_sums(x, np.asarray(model.forest.feature[0]),
                             np.asarray(model.forest.threshold[0]), h, 3)
    np.testing.assert_allclose(cover[0], want, rtol=1e-5)
    # every tree: a split node's cover is its children's
    for t in range(2):
        inner = np.arange(7)
        split = np.asarray(model.forest.feature[t]) >= 0
        np.testing.assert_allclose(
            cover[t, inner[split]],
            cover[t, 2 * inner[split] + 1] + cover[t, 2 * inner[split] + 2],
            rtol=1e-5)


def test_cover_matches_the_unrolled_oracle():
    x, y = _data(n=512)
    cfg = boosting.GBDTConfig(n_trees=3, max_depth=3, n_candidates=8)
    mesh = jax.make_mesh((1,), ("data",))
    key = jax.random.PRNGKey(4)
    scan = distributed.fit_distributed(x, y, cfg, mesh, key)
    oracle = distributed.fit_distributed(x, y, cfg, mesh, key,
                                         reference=True)
    np.testing.assert_array_equal(np.asarray(scan.cover),
                                  np.asarray(oracle.cover))


def test_single_host_fit_leaves_cover_unset():
    x, y = _data(n=256)
    cfg = boosting.GBDTConfig(n_trees=1, max_depth=2, n_candidates=8)
    model = boosting.fit(x, y, cfg, jax.random.PRNGKey(0))
    assert model.cover is None
