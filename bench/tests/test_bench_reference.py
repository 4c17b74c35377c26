"""The plain references against the program at a tiny size on the CPU."""

import dataclasses
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

import repro  # noqa: E402

import datagen  # noqa: E402
import reference  # noqa: E402
import traffic  # noqa: E402

SEED = 2 ** 31 + 12345      # wider than 32 signed bits


@pytest.fixture(scope="module")
def trained():
    x, y = datagen.mixture(datagen.seed_key(SEED), n=6000, f=7,
                           positive_share=0.458)
    cfg = repro.GBDTConfig(n_trees=1, max_depth=4, n_candidates=8)
    key = traffic.call_key(SEED, 3)
    model = repro.fit(x, y, cfg, key)
    p = reference.TreeParams(max_depth=4, n_candidates=8, l2=cfg.l2,
                             gamma=cfg.gamma,
                             min_child_weight=cfg.min_child_weight)
    tree = reference.HostTree(*(np.asarray(a)[0] for a in model.forest))
    return (np.asarray(x), np.asarray(y), np.asarray(key), p, tree,
            np.asarray(model.candidates)[0])


def test_check_round_agrees_with_fit(trained):
    x, y, key, p, tree, cands = trained
    got = reference.check_round(x, y, key, p, tree, cands)
    assert got["candidates_differ"] == 0
    assert got["bad_nodes"] == 0
    assert got["split_shortfall"] < 1e-6
    # the program sums each leaf's grad/hess in float32: at round 0 every
    # row's grad is one of two values, so the rounding of ~2,000 adds
    # leans one way (about 4e-5 here)
    assert got["leaf_gap"] < 2e-4


def test_grow_round_grows_the_programs_tree(trained):
    x, y, key, p, tree, _ = trained
    mine = reference.grow_round(x, y, key, p)
    np.testing.assert_array_equal(mine.feature, tree.feature)
    np.testing.assert_array_equal(mine.split_bin, tree.split_bin)
    np.testing.assert_array_equal(mine.threshold, tree.threshold)
    np.testing.assert_allclose(mine.leaf_value, tree.leaf_value, rtol=2e-4,
                               atol=1e-7)


def test_check_round_catches_faults(trained):
    x, y, key, p, tree, cands = trained
    empty = reference.HostTree(np.full_like(tree.feature, -1),
                               np.full_like(tree.split_bin, p.n_candidates),
                               np.full_like(tree.threshold, np.inf),
                               np.zeros_like(tree.leaf_value))
    got = reference.check_round(x, y, key, p, empty, cands)
    assert got["leaf_gap"] > 0.5 and got["split_shortfall"] > 0.5
    moved = dataclasses.replace(tree, split_bin=tree.split_bin.copy(),
                                threshold=tree.threshold.copy())
    moved.split_bin[0] = (moved.split_bin[0] + 4) % p.n_candidates
    moved.threshold[0] = cands[moved.feature[0], moved.split_bin[0]]
    assert reference.check_round(x, y, key, p, moved,
                                 cands)["split_shortfall"] > 0.01
    assert reference.check_round(x, y, key, p, tree, cands + 1.0)[
        "candidates_differ"] == cands.size
    half = reference.grow_round(x, y, key, p, rows=np.arange(0, len(y), 2))
    assert reference.check_round(x, y, key, p, half, cands)["leaf_gap"] > 1e-3


def test_bf16_control_reads_wider_than_fit(trained):
    x, y, key, p, tree, cands = trained
    sound = reference.check_round(x, y, key, p, tree, cands)["leaf_gap"]
    control = reference.grow_round(x, y, key, p, bf16=True)
    assert reference.check_round(x, y, key, p, control, cands)[
        "leaf_gap"] > 10 * sound


@pytest.fixture(scope="module")
def served():
    x, _ = datagen.mixture(datagen.seed_key(SEED), n=3000, f=11)
    arrays = datagen.forest(datagen.seed_key(SEED, 4), x, n_trees=30,
                            max_depth=5, k=16)
    cands, feature, split_bin, threshold, leaf = arrays
    cfg = repro.GBDTConfig(n_trees=30, max_depth=5, n_candidates=16,
                           repropose_each_round=False)
    model = repro.GBDTModel(cfg, repro.Forest(feature, split_bin, threshold,
                                              leaf), 0.25, cands[None])
    rows = np.asarray(x)[:700]
    got = np.asarray(model.predict(rows, output="margin"))
    host = jax.device_get((feature, threshold, leaf))
    return rows, got, host, cfg


def test_forest_margins_agree_with_predict(served):
    rows, got, (feature, threshold, leaf), cfg = served
    want = reference.forest_margins(rows, feature, threshold, leaf, 0.25,
                                    cfg.learning_rate, cfg.max_depth,
                                    block=256)
    assert np.max(np.abs(got - want)) < 1e-5


def test_forest_margins_bf16_control_reads_wider(served):
    rows, got, (feature, threshold, leaf), cfg = served
    want = reference.forest_margins(rows, feature, threshold, leaf, 0.25,
                                    cfg.learning_rate, cfg.max_depth)
    control = reference.forest_margins(rows, feature, threshold, leaf, 0.25,
                                       cfg.learning_rate, cfg.max_depth,
                                       bf16=True)
    assert np.max(np.abs(control - want)) > 100 * np.max(np.abs(got - want))


def test_forest_keeps_a_trained_forests_invariants():
    x, _ = datagen.mixture(datagen.seed_key(7), n=500, f=4)
    cands, feature, split_bin, threshold, _ = (
        np.asarray(a) for a in datagen.forest(
            datagen.seed_key(7, 4), x, n_trees=10, max_depth=3, k=5))
    assert np.all(np.diff(cands, axis=1) >= 0)
    inner = feature >= 0
    np.testing.assert_array_equal(
        threshold[inner], cands[feature[inner], split_bin[inner]])
    assert np.all(split_bin[~inner] == 5) and np.all(np.isinf(
        threshold[~inner]))
    assert set(np.unique(cands)) <= set(np.unique(np.asarray(x)))


def test_seed_key_takes_wide_seeds_and_separates_streams():
    a = np.asarray(datagen.seed_key(2 ** 40 + 1))
    assert a.dtype == np.uint32 and a.shape == (2,)
    assert not np.array_equal(a, np.asarray(datagen.seed_key(2 ** 40 + 1, 1)))
    assert np.array_equal(a, np.asarray(datagen.seed_key(2 ** 40 + 1)))


@pytest.mark.parametrize("n,block", [(1000, 256), (1024, 256), (100, 256)])
def test_host_pool_holds_the_devices_rows(n, block):
    key = datagen.seed_key(2 ** 35 + 3)
    x, y = datagen.mixture(key, n=n, f=9, block=block)
    hx, hy = datagen.mixture_host(key, n=n, f=9, block=block)
    np.testing.assert_array_equal(np.asarray(x), hx)
    np.testing.assert_array_equal(np.asarray(y), hy)
    assert len(np.unique(hx[:, 0])) == n        # no row left unwritten
    assert set(np.unique(hy)) == {0.0, 1.0}
