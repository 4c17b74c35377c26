"""Plain reference of a row-sharded training round (the paper's
Algorithm 1), for the cells that train over several chips.

Written from the algorithm's description and importing nothing of the
program.  The candidates of a call keyed by ``key`` on ``W`` workers,
each holding one equal block of rows in order (the row count padded to
a multiple of ``W`` with repeats of the leading rows):

1. worker ``w`` draws its local pool from its own rows under
   ``fold_in(key, w)``: per feature, ``k`` row indices drawn uniformly
   under ``split(fold_in(key, w), f)``, the values sorted;
2. the pools are joined in worker order, ``W * k`` values a feature;
3. ``k`` of them are drawn uniformly per feature under the round key
   ``fold_in(key, 10_000 + round)`` split over the features, the values
   sorted.

The rest of the round (binning, base grad/hess, the per-level
histograms and the tree walk, in float64) is :mod:`reference`'s, on all
the rows, which is what the workers' summed statistics stand for.
Besides :func:`reference.check_round`'s numbers, :func:`check_round`
reads two that grow with the rows every worker adds to a sum, so that
a worker's rows left out of a cross-chip sum cannot pass for rounding:
the base score (the label sum over the row count) and each node's cover
(the hessian sum of the rows it holds: inner nodes from the level
histograms, leaves from the leaf sums).
"""

from __future__ import annotations

import jax
import numpy as np

import reference

ROUND_KEY_OFFSET = 10_000


def _draws(key, f: int, k: int, n: int) -> np.ndarray:
    """``(f, k)`` indices below ``n``, feature ``j``'s drawn under the
    ``j``-th key of ``split(key, f)``."""
    keys = jax.random.split(key, f)
    return np.asarray(jax.vmap(
        lambda kk: jax.random.randint(kk, (k,), 0, n))(keys))


def sharded_candidates(key, x: np.ndarray, k: int, workers: int,
                       round_index: int = 0) -> np.ndarray:
    """Algorithm 1's ``(f, k)`` candidates for round ``round_index`` of a
    call keyed by ``key`` with the rows of ``x`` over ``workers``."""
    n, f = x.shape
    pad = -n % workers
    if pad:
        x = np.concatenate([x, x[:pad]])
    per = x.shape[0] // workers
    cols = np.arange(f)[:, None]
    with jax.default_device(jax.devices("cpu")[0]):
        pools = []
        for w in range(workers):
            rows = _draws(jax.random.fold_in(key, w), f, k, per)
            pools.append(np.sort(x[w * per + rows, cols], axis=1))
        joined = np.concatenate(pools, axis=1)                  # (f, W k)
        picks = _draws(jax.random.fold_in(
            key, ROUND_KEY_OFFSET + round_index), f, k, workers * k)
    return np.sort(np.take_along_axis(joined, picks, axis=1), axis=1)


def base_score(y: np.ndarray, keep: np.ndarray | None = None,
               bf16: bool = False) -> float:
    """The logistic base score: the logit of the label sum (of the
    ``keep`` rows only, where given) over the row count, clipped as the
    program clips it; with ``bf16`` the sum accumulates in bfloat16."""
    w = np.asarray(y, np.float64) if keep is None else np.where(keep, y, 0.0)
    s = reference._sums(np.zeros(len(y), np.int64), w, 1, bf16)[0]
    p = float(np.clip(s / len(y), 1e-6, 1 - 1e-6))
    return float(np.log(p / (1 - p)))


def grad_hess(y: np.ndarray, base: float, bf16: bool = False):
    """Logistic grad/hess of every row at the margin ``base``."""
    prob = 1.0 / (1.0 + np.exp(-base))
    g = prob - np.asarray(y, np.float64)
    h = np.full(len(y), prob * (1 - prob))
    if bf16:
        g, h = reference._bf16(g), reference._bf16(h)
    return g.astype(np.float64), h.astype(np.float64)


def _routes(bins: np.ndarray, tree: reference.HostTree,
            max_depth: int) -> list:
    """Each row's node at every depth ``0..max_depth`` of ``tree``, rows
    routed by its split bins (a passthrough sends every row left)."""
    rows = np.arange(bins.shape[0])
    node = np.zeros(bins.shape[0], np.int64)
    out = [node]
    for d in range(max_depth):
        heap = 2 ** d - 1 + node
        feat = tree.feature[heap]
        left = (feat < 0) | (bins[rows, feat.clip(0)]
                             <= tree.split_bin[heap])
        node = 2 * node + np.where(left, 0, 1)
        out.append(node)
    return out


def node_sums(routes: list, w: np.ndarray, depths: range,
              bf16: bool = False) -> np.ndarray:
    """Sums of ``w`` over the rows each node at ``depths`` holds, by
    depth then node: all depths give the heap order of the nodes."""
    return np.concatenate([reference._sums(routes[d], w, 2 ** d, bf16)
                           for d in depths])


def grow_round(x, y, key, p: reference.TreeParams, workers: int, *,
               bf16: bool = False, bf16_inputs: bool = False,
               cands: np.ndarray | None = None,
               hist_rows: np.ndarray | None = None,
               leaf_rows: np.ndarray | None = None,
               rows: np.ndarray | None = None):
    """The reference's own first tree of a call keyed by ``key``, with
    the base score and cover a program that computed so would return:
    ``(tree, base, cover)``.

    ``cands`` puts other candidates in place of Algorithm 1's.  Boolean
    row masks leave rows out of one sum, as a worker left out of a
    cross-chip sum would: ``hist_rows`` keeps those rows only in the
    level histograms (the splits and the inner nodes' cover),
    ``leaf_rows`` only in the leaf sums (the leaf values and covers),
    ``rows`` only, everywhere, the base score's label sum included, over
    the whole row count.  ``bf16`` and ``bf16_inputs`` are
    :func:`reference.grow_round`'s.
    """
    if cands is None:
        cands = sharded_candidates(key, x, p.n_candidates, workers)
    bins = reference.bin_features(x, cands)
    base = base_score(y, rows, bf16)
    g, h = grad_hess(y, base, bf16 or bf16_inputs)
    if rows is not None:
        g, h = np.where(rows, g, 0.0), np.where(rows, h, 0.0)
    hist_g, hist_h = ((g, h) if hist_rows is None else
                      (np.where(hist_rows, g, 0.0),
                       np.where(hist_rows, h, 0.0)))
    tree = reference._walk(bins, hist_g, hist_h, cands, p, None, bf16)[0]
    if leaf_rows is not None:
        g, h = np.where(leaf_rows, g, 0.0), np.where(leaf_rows, h, 0.0)
    d = p.max_depth
    routes = _routes(bins, tree, d)
    leaves = range(d, d + 1)
    cover = np.concatenate([node_sums(routes, hist_h, range(d), bf16),
                            node_sums(routes, h, leaves, bf16)])
    leaf_g = node_sums(routes, g, leaves, bf16)
    tree.leaf_value = (-leaf_g / (cover[2 ** d - 1:] + p.l2)).astype(
        np.float32)
    return tree, base, cover.astype(np.float32)


def check_round(x, y, key, p: reference.TreeParams,
                tree: reference.HostTree, candidates: np.ndarray,
                workers: int, base: float, cover: np.ndarray) -> dict:
    """How far the first tree a row-sharded call keyed by ``key`` grew,
    with its base score and cover, lies from the reference:
    ``leaf_gap``, ``split_shortfall``, ``candidates_differ`` and
    ``bad_nodes`` as :func:`reference.check_round` defines them, and

      base_gap: the base score's distance from the float64 logit of the
        label mean;
      cover_gap: the largest gap between a node's cover and the float64
        hessian sum of the rows the tree routes there, over the larger
        of that node's sum and the median filled node's.
    """
    cands = sharded_candidates(key, x, p.n_candidates, workers)
    bins = reference.bin_features(x, cands)
    g, h = reference.base_grad_hess(y)
    ref, H, best, chosen, bad = reference._walk(bins, g, h, cands, p, tree)
    best, chosen = np.concatenate(best), np.concatenate(chosen)
    pos = best[best > 0]
    scale = np.maximum(best, np.median(pos) if pos.size else 1.0)
    short = np.max(np.maximum(best - chosen, 0.0) / scale)
    filled = H > 0
    mid = np.median(np.abs(ref.leaf_value[filled])) if filled.any() else 1.0
    gap = np.abs(np.asarray(tree.leaf_value, np.float64) - ref.leaf_value)
    leaf_gap = np.max(gap / np.maximum(np.abs(ref.leaf_value),
                                       max(mid, 1e-30)))
    differ = int(np.sum(np.asarray(candidates, np.float32) != cands))
    want = node_sums(_routes(bins, tree, p.max_depth), h,
                     range(p.max_depth + 1))
    mid = np.median(want[want > 0])
    cover_gap = np.max(np.abs(np.asarray(cover, np.float64) - want)
                       / np.maximum(want, mid))
    return {"leaf_gap": float(leaf_gap), "split_shortfall": float(short),
            "candidates_differ": differ, "bad_nodes": bad,
            "base_gap": abs(float(base) - base_score(y)),
            "cover_gap": float(cover_gap)}
