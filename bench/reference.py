"""Plain references that decide ``correct``.

Straightforward numpy, accumulating in float64, written from the
algorithm's description and importing nothing of the program:

- a boosting round: the paper's random proposal (``k`` values of each
  feature drawn uniformly, with ``jax.random`` under the call's key, and
  sorted), binning ``#{c < x}``, per-level grad/hess histograms by
  ``np.bincount``, XGBoost's split gain, row routing and leaf values
  ``-G / (H + lambda)``.  :func:`check_round` follows the tree the
  program grew and measures how far it lies from the reference;
  :func:`grow_round` grows the reference's own tree, which is how a
  control or a planted fault is put in the program's place;
- a forest's margins by descending each tree on its own
  (:func:`forest_margins`).

``bf16=True`` gives the control: the same computation carried out in
bfloat16, the precision below the configurations' float32.  Its inputs
(grad and hess; features, thresholds and leaf values) are rounded to
bfloat16 and its sums accumulate in bfloat16, row after row, as a
scatter-add into a bfloat16 histogram would.
"""

from __future__ import annotations

import dataclasses

import jax
import ml_dtypes
import numpy as np


def _bf16(a: np.ndarray) -> np.ndarray:
    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


# ---------------------------------------------------------------------------
# Training: one boosting round from the base score.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TreeParams:
    max_depth: int
    n_candidates: int
    l2: float
    gamma: float
    min_child_weight: float


@dataclasses.dataclass
class HostTree:
    feature: np.ndarray      # (2^d - 1,) int, -1 = passthrough
    split_bin: np.ndarray    # (2^d - 1,) int, nbins - 1 for passthrough
    threshold: np.ndarray    # (2^d - 1,) float32, +inf for passthrough
    leaf_value: np.ndarray   # (2^d,) float32


def random_candidates(key, x: np.ndarray, k: int, round_index: int = 0):
    """The paper's proposal for round ``round_index`` of a call keyed by
    ``key``: per feature, ``k`` row indices drawn uniformly under
    ``split(fold_in(key, round), f)``, the values sorted."""
    n, f = x.shape
    with jax.default_device(jax.devices("cpu")[0]):
        keys = jax.random.split(jax.random.fold_in(key, round_index), f)
        rows = np.asarray(jax.vmap(
            lambda kk: jax.random.randint(kk, (k,), 0, n))(keys))
    return np.sort(x[rows, np.arange(f)[:, None]], axis=1)


def bin_features(x: np.ndarray, cands: np.ndarray) -> np.ndarray:
    """``#{c < x}`` per feature, as uint8 (nbins = k + 1 <= 256)."""
    bins = np.empty(x.shape, np.uint8)
    for j in range(x.shape[1]):
        bins[:, j] = np.searchsorted(cands[j], x[:, j], side="left")
    return bins


def base_grad_hess(y: np.ndarray, bf16: bool = False):
    """Logistic grad/hess of every row at the base score (round 0)."""
    p = float(np.clip(np.mean(y, dtype=np.float64), 1e-6, 1 - 1e-6))
    prob = 1.0 / (1.0 + np.exp(-np.log(p / (1 - p))))
    g = prob - y.astype(np.float64)
    h = np.full(y.shape, prob * (1 - prob))
    if bf16:
        g, h = _bf16(g), _bf16(h)
    return g.astype(np.float64), h.astype(np.float64)


def _sums(idx, w, size: int, bf16: bool) -> np.ndarray:
    """Per-bucket sums of ``w``: float64, or accumulated in bfloat16."""
    if not bf16:
        return np.bincount(idx, w, size)
    out = np.zeros(size, ml_dtypes.bfloat16)
    np.add.at(out, idx, w.astype(ml_dtypes.bfloat16))
    return out.astype(np.float64)


def _level_hist(node, width, bins, g, h, nbins, bf16):
    n, f = bins.shape
    base = node.astype(np.int64) * nbins
    gh = np.empty((2, width, f, nbins))
    for j in range(f):
        idx = base + bins[:, j]
        for i, w in enumerate((g, h)):
            gh[i, :, j] = _sums(idx, w, width * nbins, bf16).reshape(
                width, nbins)
    return gh


def _gains(gh, p: TreeParams):
    """XGBoost's gain of splitting after bin s, (width, f, nbins - 1),
    and whether each split keeps ``min_child_weight`` on both sides."""
    G, H = gh
    gl = np.cumsum(G, -1)[..., :-1]
    hl = np.cumsum(H, -1)[..., :-1]
    gt, ht = G.sum(-1, keepdims=True), H.sum(-1, keepdims=True)
    gr, hr = gt - gl, ht - hl

    def score(a, b):
        return a * a / (b + p.l2)

    gain = 0.5 * (score(gl, hl) + score(gr, hr) - score(gt, ht)) - p.gamma
    ok = (hl >= p.min_child_weight) & (hr >= p.min_child_weight)
    return gain, ok


def _walk(bins, g, h, cands, p: TreeParams, tree: HostTree | None,
          bf16: bool = False):
    """Grow level by level.  With ``tree`` given, route rows by its
    splits and record, per node, the best gain there and the gain of the
    tree's choice; without, choose the best split as the program does
    (first best bin per feature, first best feature, split if gain > 0)."""
    n, f = bins.shape
    nbins = p.n_candidates + 1
    n_inner = 2 ** p.max_depth - 1
    out = HostTree(np.full(n_inner, -1), np.full(n_inner, nbins - 1),
                   np.full(n_inner, np.inf, np.float32), None)
    best_all, chosen_all, bad = [], [], 0
    node = np.zeros(n, np.int64)
    rows = np.arange(n)
    for d in range(p.max_depth):
        width = 2 ** d
        heap = width - 1 + np.arange(width)
        gain, ok = _gains(_level_hist(node, width, bins, g, h, nbins, bf16),
                          p)
        valid = np.where(ok, gain, -np.inf)
        best = valid.reshape(width, -1).max(1)
        m = np.arange(width)
        if tree is None:
            per_f = valid.argmax(-1)                          # (width, f)
            bf = np.take_along_axis(valid, per_f[..., None], -1)[..., 0] \
                .argmax(-1)
            split = best > 0
            feat = np.where(split, bf, -1)
            sbin = np.where(split, per_f[m, bf], nbins - 1)
        else:
            feat, sbin = tree.feature[heap], tree.split_bin[heap]
            thr = tree.threshold[heap]
            inner = feat >= 0
            fc, sc = feat.clip(0, f - 1), sbin.clip(0, nbins - 2)
            in_range = (feat < f) & (sbin >= 0) & (sbin < nbins - 1)
            chosen = np.where(inner, gain[m, fc, sc], 0.0)
            bad += int(np.sum(inner & ~(in_range & ok[m, fc, sc])))
            bad += int(np.sum(inner & (thr != cands[fc, sc])))
            bad += int(np.sum(~inner & ((sbin != nbins - 1)
                                        | (thr != np.inf))))
            best_all.append(np.maximum(best, 0.0))
            chosen_all.append(chosen)
        out.feature[heap] = feat
        out.split_bin[heap] = sbin
        out.threshold[heap] = np.where(
            feat >= 0, cands[feat.clip(0, f - 1), sbin.clip(0, nbins - 2)],
            np.inf)
        j = feat.clip(0, f - 1)[node]
        go_left = (feat[node] < 0) | (bins[rows, j] <= sbin[node])
        node = 2 * node + np.where(go_left, 0, 1)
    n_leaves = 2 ** p.max_depth
    G = _sums(node, g, n_leaves, bf16)
    H = _sums(node, h, n_leaves, bf16)
    out.leaf_value = -G / (H + p.l2)
    return out, H, best_all, chosen_all, bad


def grow_round(x, y, key, p: TreeParams, *, bf16: bool = False,
               bf16_inputs: bool = False,
               rows: np.ndarray | None = None) -> HostTree:
    """The reference's own first tree of a call keyed by ``key``, grown
    on ``rows`` of ``x`` (all of them by default).  ``bf16_inputs``
    rounds only grad and hess to bfloat16 and keeps the exact sums."""
    cands = random_candidates(key, x, p.n_candidates)
    if rows is not None:
        x, y = x[rows], y[rows]
    g, h = base_grad_hess(y, bf16 or bf16_inputs)
    tree = _walk(bin_features(x, cands), g, h, cands, p, None, bf16)[0]
    tree.leaf_value = tree.leaf_value.astype(np.float32)
    return tree


def check_round(x, y, key, p: TreeParams, tree: HostTree,
                candidates: np.ndarray) -> dict:
    """How far the first tree the program grew in a call keyed by
    ``key`` lies from the reference.

    Returns:
      leaf_gap: the largest gap between a leaf value and the reference's
        for the rows the tree routes there, over the larger of that
        leaf's reference value and the median one's;
      split_shortfall: the largest amount by which a node's chosen split
        (0 for no split) falls short of the best gain the reference
        finds there, over the larger of that best gain and the median
        node's;
      candidates_differ: candidate values unequal to the reference's
        proposal;
      bad_nodes: nodes whose split is out of range or breaks
        ``min_child_weight``, whose threshold is not its candidate, or
        whose passthrough sentinels are wrong.
    """
    cands = random_candidates(key, x, p.n_candidates)
    bins = bin_features(x, cands)
    g, h = base_grad_hess(y)
    ref, H, best, chosen, bad = _walk(bins, g, h, cands, p, tree)
    best, chosen = np.concatenate(best), np.concatenate(chosen)
    pos = best[best > 0]
    scale = np.maximum(best, np.median(pos) if pos.size else 1.0)
    short = np.max(np.maximum(best - chosen, 0.0) / scale)
    filled = H > 0
    mid = np.median(np.abs(ref.leaf_value[filled])) if filled.any() else 1.0
    gap = np.abs(np.asarray(tree.leaf_value, np.float64) - ref.leaf_value)
    leaf_gap = np.max(gap / np.maximum(np.abs(ref.leaf_value), max(mid, 1e-30)))
    differ = int(np.sum(np.asarray(candidates, np.float32) != cands))
    return {"leaf_gap": float(leaf_gap), "split_shortfall": float(short),
            "candidates_differ": differ, "bad_nodes": bad}


# ---------------------------------------------------------------------------
# Serving: a forest's margins.
# ---------------------------------------------------------------------------

def forest_margins(x, feature, threshold, leaf, base_score, learning_rate,
                   max_depth: int, *, bf16: bool = False,
                   block: int = 4096) -> np.ndarray:
    """``base + lr * sum_t leaf_t(x)``, each tree descended on its own
    (``x <= threshold`` goes left), summed in float64; with ``bf16`` the
    features, thresholds and leaves are rounded to bfloat16 and the sum
    accumulates in bfloat16."""
    x = np.asarray(x, np.float32)
    threshold = np.asarray(threshold, np.float32)
    leaf = np.asarray(leaf, np.float32)
    if bf16:
        x, threshold, leaf = _bf16(x), _bf16(threshold), _bf16(leaf)
    feature = np.asarray(feature)
    n_trees = feature.shape[0]
    trees = np.arange(n_trees)[None, :]
    out = np.empty(x.shape[0], np.float64)
    for s in range(0, x.shape[0], block):
        xb = x[s:s + block]
        r = np.arange(xb.shape[0])[:, None]
        node = np.zeros((xb.shape[0], n_trees), np.int64)
        for d in range(max_depth):
            heap = 2 ** d - 1 + node
            fidx = feature[trees, heap].clip(0)
            left = xb[r, fidx] <= threshold[trees, heap]
            node = 2 * node + np.where(left, 0, 1)
        vals = leaf[trees, node]
        if bf16:
            total = np.zeros(len(xb), ml_dtypes.bfloat16)
            for t in range(n_trees):
                total += vals[:, t].astype(ml_dtypes.bfloat16)
        else:
            total = vals.sum(1, dtype=np.float64)
        out[s:s + block] = base_score + learning_rate * total.astype(
            np.float64)
    return out
