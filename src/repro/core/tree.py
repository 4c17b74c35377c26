"""Level-wise decision-tree growth on binned features.

TPU-native adaptation of XGBoost's approximate tree builder: instead of a
host-side node queue we grow a *complete* binary tree of static depth.
Level d has 2^d frontier nodes; every row carries a level-local node id.
Nodes that should not split (gain <= 0, min_child_weight violated) become
"passthrough" nodes: every row goes LEFT, the right child is empty
(G = H = 0 -> weight 0).  This wastes a bounded amount of compute in
exchange for fully static shapes — the standard TPU trade.

Heap layout (0-based): inner node i has children 2i+1 / 2i+2; level d
occupies indices [2^d - 1, 2^(d+1) - 2]; leaves are the 2^max_depth
level-(max_depth) nodes.

Split semantics (consistent with binning.py):
  row goes left  <=>  bin_id <= split_bin  <=>  x <= threshold
where threshold = candidates[feature, split_bin].
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from ..kernels import ops
from ..kernels.ops import HistSpec


class Tree(NamedTuple):
    """A single fitted tree (all arrays static-shaped)."""
    feature: jax.Array     # (2^depth - 1,) int32; -1 = passthrough
    split_bin: jax.Array   # (2^depth - 1,) int32; nbins-1 for passthrough
    threshold: jax.Array   # (2^depth - 1,) float32; +inf for passthrough
    leaf_value: jax.Array  # (2^depth,) float32


class TreeStats(NamedTuple):
    """Per-tree growth telemetry (all 0-d arrays, scan-stackable).

    Derived from the same (psum'd, in the distributed trainer) gain
    panel the splits themselves come from — plus the local row panel for
    the update count — so it is replicated across workers (the trainers
    psum ``hist_updates`` to its cluster-wide value) and adding it
    cannot change the grown tree.
    """
    n_splits: jax.Array     # () int32 — realized (gain > 0) splits
    gain_sum: jax.Array     # () float32 — sum of realized split gains
    gain_max: jax.Array     # () float32 — largest realized gain (0 if none)
    hist_updates: jax.Array  # () float32 — scatter updates issued for the
    #                          tree's histograms: sum over levels of
    #                          (rows actually scattered) * n_features.
    #                          Direct growth scatters every row at every
    #                          level; subtraction growth only the rows
    #                          routed LEFT.  float32 (telemetry — exact
    #                          below 2^24 updates per tree)


class Forest(NamedTuple):
    """A boosted ensemble as a struct-of-arrays: every field of Tree
    stacked along a leading round axis.  Static-shaped in (n_trees,
    max_depth), so it can be the per-round output of a ``lax.scan`` and
    the input of a single-compile vectorized predictor."""
    feature: jax.Array     # (T, 2^depth - 1) int32
    split_bin: jax.Array   # (T, 2^depth - 1) int32
    threshold: jax.Array   # (T, 2^depth - 1) float32
    leaf_value: jax.Array  # (T, 2^depth) float32

    @property
    def n_trees(self) -> int:
        return self.feature.shape[0]


def forest_from_trees(trees: list[Tree]) -> Forest:
    """Stack a Python list of trees (the reference-loop output)."""
    return Forest(*(jnp.stack(a) for a in zip(*trees)))


def forest_trees(forest: Forest) -> list[Tree]:
    """Per-tree views of a forest (host-side convenience/back-compat)."""
    return [Tree(*(a[i] for a in forest)) for i in range(forest.n_trees)]


def collective(op: Callable, a: jax.Array, axis_name: str) -> jax.Array:
    """``op(a, axis_name)`` (``lax.psum``, ``lax.all_gather``, ...) under
    the ``repro.collective`` scope, so a profile puts the distributed
    trainer's collectives in one layer."""
    with jax.named_scope("repro.collective"):
        return op(a, axis_name)


def _level_slice(depth: int) -> slice:
    return slice(2 ** depth - 1, 2 ** (depth + 1) - 1)


@functools.partial(jax.jit, static_argnames=(
    "max_depth", "nbins", "l2", "gamma", "min_child_weight", "backend",
    "spec", "axis_name", "return_leaf_nodes", "return_cover",
    "return_stats"))
def build_tree(bins: jax.Array, gh: jax.Array, candidates: jax.Array, *,
               max_depth: int, nbins: int | None = None, l2: float = 1.0,
               gamma: float = 0.0, min_child_weight: float = 1e-6,
               backend: str = "auto",
               spec: HistSpec | None = None,
               axis_name: str | None = None,
               return_leaf_nodes: bool = False,
               return_cover: bool = False,
               return_stats: bool = False):
    """Grow one tree on binned data.

    The level loop is a ``lax.scan`` over a *uniform* frontier of
    ``F = 2^(max_depth-1)`` nodes: every level's histogram has the same
    static shape, so ONE compiled scatter (or Pallas launch) serves all
    levels instead of one program per depth.  At depth ``d < max_depth-1``
    node ids only occupy ``[0, 2^d)``; the unpopulated tail has an
    all-zero histogram, fails ``min_child_weight`` at every bin, and
    falls out as a passthrough — exactly the semantics the complete-tree
    layout already gives empty nodes, so the widened frontier is
    bit-exact vs the per-depth loop (same rows hit the same buckets in
    the same order).

    With ``spec.subtract`` set the scan instead runs histogram-
    subtraction growth (the classic trick of XGBoost/LightGBM, adapted
    to the uniform frontier): each level scatters only the rows routed
    LEFT, keyed by the parent id, into a HALF-width panel of
    ``F/2`` parent buckets; the right-child histograms are reconstructed
    as ``parent - left`` from the previous level's composed panel, which
    rides the scan carry.  Level 0 falls out of the same program — every
    row has child id 0 (even), so the "left" scatter is the full root
    histogram.  Unpopulated odd nodes are re-zeroed from a static
    populated-width mask (otherwise ``0 - left`` would leak the root's
    negation down the all-right spine of the carry).  In the distributed
    trainer only the half panel enters the per-level ``lax.psum`` —
    the collective payload of tree growth halves.  Float subtraction
    re-associates the right-child sums, so subtraction trees are only
    *tree-for-tree* pinned against the ``subtract=False`` oracles on
    fixed workloads rather than histogram-bit-exact (see README
    "Architecture").

    Args:
      bins: (n, f) int32 bin ids in [0, nbins).
      gh: (n, 2) grad/hess panel for the current boosting round.
      candidates: (f, k) candidate values (k = nbins - 1); used only to
        record raw thresholds for inference on unbinned data.
      nbins, backend: legacy kwargs; superseded by ``spec``.  Exactly
        one of ``spec`` / ``nbins`` must be provided.
      spec: :class:`HistSpec` describing the histogram workload.  Its
        ``n_nodes`` must cover the frontier (``>= 2^(max_depth-1)``).
        Its backend picks the histogram kernel only; split gain runs
        ``ops.split_gain``'s own 'auto' choice.
      axis_name: if set, every histogram is lax.psum'd over this mesh
        axis (distributed-XGBoost histogram AllReduce inside shard_map);
        None = single host.
      return_leaf_nodes: also return each row's final leaf id.  Growth
        already routes every row to its leaf, so the scanned boosting
        trainers read the margin update as ``leaf_value[node]`` instead
        of re-descending the tree with predict_binned.
      return_cover: also return each node's cover, the sum of the
        hessians of the rows it holds (XGBoost's ``cover``): a
        ``(2^(max_depth+1) - 1,)`` float32 array, the inner nodes in
        heap order read from their level's (psum'd) histogram, then the
        leaves from the (psum'd) leaf sums.
      return_stats: also return a :class:`TreeStats` (realized split
        count + gain summary) computed from the per-level gain panels.
        Static flag: the telemetry-off graph is unchanged.

    Returns:
      A :class:`Tree`, extended to ``(Tree, node)`` when
      ``return_leaf_nodes`` is set, then by ``cover`` when
      ``return_cover`` is set and by ``stats`` when ``return_stats`` is
      set (``node`` is the (n,) int32 leaf assignment, ``stats`` the
      :class:`TreeStats`).
    """
    frontier = 2 ** max(max_depth - 1, 0)
    if spec is None:
        if nbins is None:
            raise TypeError("build_tree needs either spec= or nbins=")
        spec = HistSpec(n_nodes=frontier, nbins=nbins, n_levels=1,
                        backend=backend)
    else:
        if nbins is not None and nbins != spec.nbins:
            raise ValueError(
                f"nbins={nbins} conflicts with spec.nbins={spec.nbins}")
        if spec.n_nodes < frontier:
            raise ValueError(
                f"spec.n_nodes={spec.n_nodes} < frontier {frontier} "
                f"for max_depth={max_depth}")
    nbins = spec.nbins
    lspec = spec.with_levels(1)        # one scan step = one level

    psum = (None if axis_name is None
            else functools.partial(collective, jax.lax.psum,
                                   axis_name=axis_name))
    n, f = bins.shape
    n_inner = 2 ** max_depth - 1
    n_leaves = 2 ** max_depth

    def split_and_route(hist, node, upd):
        """Shared tail of a level step: pick splits from the (already
        psum'd / composed) frontier panel and route rows one level down.
        ``upd`` is the level's scatter-update count (stats only)."""
        # split gain resolves its own backend: the spec's picks only the
        # histogram kernel
        gains, sbins = ops.split_gain(hist, l2=l2, gamma=gamma,
                                      min_child_weight=min_child_weight)
        with jax.named_scope("repro.route"):
            # a node's hessians sum to the same total over any feature
            cover = (jnp.sum(hist[:frontier, 0, :, 1], axis=-1)
                     if return_cover else None)
            return _route(gains[:frontier], sbins[:frontier], node, upd,
                          cover)

    def _route(gains, sbins, node, upd, cover):
        """Each node's best split, then every row one level down."""
        best_f = jnp.argmax(gains, axis=1).astype(jnp.int32)  # (nodes,)
        best_gain = jnp.take_along_axis(gains, best_f[:, None], 1)[:, 0]
        best_s = jnp.take_along_axis(sbins, best_f[:, None], 1)[:, 0]

        do_split = best_gain > 0.0
        lvl_feature = jnp.where(do_split, best_f, -1)
        lvl_sbin = jnp.where(do_split, best_s, nbins - 1)
        lvl_thresh = jnp.where(
            do_split,
            candidates[lvl_feature.clip(0),
                       lvl_sbin.clip(0, candidates.shape[1] - 1)],
            jnp.inf)

        # route rows: left (2*node) if bin <= s else right (2*node + 1)
        row_bin = jnp.take_along_axis(
            bins, lvl_feature.clip(0)[node][:, None], axis=1)[:, 0]
        go_left = row_bin <= lvl_sbin[node]
        node = node * 2 + jnp.where(go_left, 0, 1)
        ys = (lvl_feature, lvl_sbin, lvl_thresh)
        if return_cover:
            ys += (cover,)
        if return_stats:
            # unpopulated frontier tail nodes have all-zero histograms
            # and never split, so summing the full frontier is exact
            realized = jnp.where(do_split, best_gain, 0.0)
            ys += ((jnp.sum(do_split.astype(jnp.int32)),
                    jnp.sum(realized), jnp.max(realized), upd),)
        return node, ys

    def level_step(node, _):
        # (n_nodes, f, nbins, 2); same shape every level — one program
        hist = ops.hist_levels(bins, node[None], gh, lspec)[0]
        if psum is not None:
            hist = psum(hist)
        # direct growth scatters every row at every level
        return split_and_route(hist, node, jnp.float32(n * f))

    half = max(frontier // 2, 1)
    sspec = dataclasses.replace(lspec, n_nodes=half)  # parent-keyed panel

    def level_step_subtract(carry, populated):
        node, prev = carry
        # half-width panel: LEFT-routed (even child id) rows only, keyed
        # by parent id — in the distributed trainer this halved panel is
        # all that crosses the mesh
        left = ops.hist_levels(bins, node[None], gh, sspec)[0]
        if psum is not None:
            left = psum(left)
        if frontier == 1:
            hist = left                     # single-node level: root hist
        else:
            # interleave [left[p], prev[p] - left[p]] -> child 2p, 2p+1;
            # re-zero unpopulated nodes so the carried panel stays the
            # true level histogram (prev=0 minus a stale left would leak
            # garbage down the all-right spine)
            hist = jnp.stack([left, prev[:half] - left], axis=1)
            hist = hist.reshape(frontier, f, nbins, 2)
            hist = jnp.where(populated[:, None, None, None], hist, 0.0)
        upd = jnp.sum((node % 2 == 0).astype(jnp.float32)) * f
        node, ys = split_and_route(hist, node, upd)
        return (node, hist), ys

    stats = TreeStats(jnp.int32(0), jnp.float32(0.0), jnp.float32(0.0),
                      jnp.float32(0.0))
    node = jnp.zeros((n,), jnp.int32)          # level-local node id
    if max_depth > 0:
        if spec.subtract:
            # populated[d, m] <=> node id m exists at depth d
            populated = (jnp.arange(frontier)[None, :]
                         < (2 ** jnp.arange(max_depth))[:, None])
            prev0 = jnp.zeros((frontier, f, nbins, 2), jnp.float32)
            (node, _), ys = jax.lax.scan(level_step_subtract,
                                         (node, prev0), populated)
        else:
            node, ys = jax.lax.scan(level_step, node, None,
                                    length=max_depth)
        feats, sbins_l, threshs = ys[:3]
        if return_cover:
            covers = ys[3]
        if return_stats:
            ns_l, gs_l, gm_l, up_l = ys[-1]
            stats = TreeStats(jnp.sum(ns_l).astype(jnp.int32),
                              jnp.sum(gs_l).astype(jnp.float32),
                              jnp.max(gm_l).astype(jnp.float32),
                              jnp.sum(up_l).astype(jnp.float32))

    feature = jnp.full((n_inner,), -1, jnp.int32)
    split_bin = jnp.full((n_inner,), nbins - 1, jnp.int32)
    threshold = jnp.full((n_inner,), jnp.inf, jnp.float32)
    inner_cover = jnp.zeros((n_inner,), jnp.float32)
    for depth in range(max_depth):
        sl = _level_slice(depth)
        w = 2 ** depth                 # populated prefix of the frontier
        feature = feature.at[sl].set(feats[depth, :w])
        split_bin = split_bin.at[sl].set(sbins_l[depth, :w])
        threshold = threshold.at[sl].set(threshs[depth, :w])
        if return_cover:
            inner_cover = inner_cover.at[sl].set(covers[depth, :w])

    # leaf values from final-level grad/hess totals; grad/hess packed
    # into one complex64 scatter (bit-exact: lanes add independently,
    # same row order) — ~1.3x faster than the 2-wide segment_sum on CPU
    with jax.named_scope("repro.leaf_update"):
        z = jax.lax.complex(gh[:, 0].astype(jnp.float32),
                            gh[:, 1].astype(jnp.float32))
        seg_z = jnp.zeros((n_leaves,), jnp.complex64).at[node].add(z)
        seg = jnp.stack([seg_z.real, seg_z.imag], -1)
    if psum is not None:
        seg = psum(seg)
    with jax.named_scope("repro.leaf_update"):
        leaf_value = -seg[:, 0] / (seg[:, 1] + l2)
    tree = Tree(feature, split_bin, threshold,
                leaf_value.astype(jnp.float32))
    out = (tree,)
    if return_leaf_nodes:
        out += (node,)
    if return_cover:
        out += (jnp.concatenate([inner_cover, seg[:, 1]]),)
    if return_stats:
        out += (stats,)
    return out if len(out) > 1 else tree


def _descend_binned(tree: Tree, bins: jax.Array, max_depth: int) -> jax.Array:
    n = bins.shape[0]
    node = jnp.zeros((n,), jnp.int32)          # level-local id
    for depth in range(max_depth):
        heap = (2 ** depth - 1) + node
        fidx = tree.feature[heap]
        sbin = tree.split_bin[heap]
        row_bin = jnp.take_along_axis(bins, fidx.clip(0)[:, None], 1)[:, 0]
        go_left = row_bin <= sbin
        node = node * 2 + jnp.where(go_left, 0, 1)
    return tree.leaf_value[node]


def _descend_raw(tree: Tree, x: jax.Array, max_depth: int) -> jax.Array:
    n = x.shape[0]
    node = jnp.zeros((n,), jnp.int32)
    for depth in range(max_depth):
        heap = (2 ** depth - 1) + node
        fidx = tree.feature[heap]
        thr = tree.threshold[heap]
        xv = jnp.take_along_axis(x, fidx.clip(0)[:, None], 1)[:, 0]
        go_left = xv <= thr
        node = node * 2 + jnp.where(go_left, 0, 1)
    return tree.leaf_value[node]


@functools.partial(jax.jit, static_argnames=("max_depth",))
def predict_binned(tree: Tree, bins: jax.Array, *, max_depth: int) -> jax.Array:
    """Evaluate one tree on binned features; returns (n,) leaf values."""
    return _descend_binned(tree, bins, max_depth)


@functools.partial(jax.jit, static_argnames=("max_depth",))
def predict_raw(tree: Tree, x: jax.Array, *, max_depth: int) -> jax.Array:
    """Evaluate one tree on raw features (x <= threshold goes left)."""
    return _descend_raw(tree, x, max_depth)


@functools.partial(jax.jit, static_argnames=("max_depth",))
def _forest_predict_scan(forest: Forest, x: jax.Array, *,
                         max_depth: int) -> jax.Array:
    """Sequential per-tree scan ensemble sum — the ORIGINAL predictor,
    kept as the semantic oracle and bench baseline for the batched
    level-synchronous engine (:func:`repro.core.predict.forest_predict`,
    bit-identical output).  One compile for any n_trees, O(n) working
    memory, but n_trees dependent dispatch chains — not the fast path.

    Returns the *unscaled* ensemble sum; the caller applies learning
    rate and base score.
    """
    def body(acc, t):
        return acc + _descend_raw(Tree(*t), x, max_depth), None

    acc0 = jnp.zeros((x.shape[0],), jnp.float32)
    acc, _ = jax.lax.scan(body, acc0, forest)
    return acc


def forest_predict_raw(forest: Forest, x: jax.Array, *,
                       max_depth: int) -> jax.Array:
    """Deprecated: use :func:`repro.core.predict.forest_predict`, the
    batched level-synchronous engine (bit-identical, much faster)."""
    warnings.warn(
        "forest_predict_raw (per-tree scan) is deprecated; use "
        "repro.core.predict.forest_predict (batched level-synchronous "
        "traversal, bit-identical output)",
        DeprecationWarning, stacklevel=2)
    return _forest_predict_scan(forest, x, max_depth=max_depth)


@functools.partial(jax.jit, static_argnames=("max_depth",))
def forest_predict_binned(forest: Forest, bins: jax.Array, *,
                          max_depth: int) -> jax.Array:
    """As :func:`forest_predict_raw` but on pre-binned features."""
    def body(acc, t):
        return acc + _descend_binned(Tree(*t), bins, max_depth), None

    acc0 = jnp.zeros((bins.shape[0],), jnp.float32)
    acc, _ = jax.lax.scan(body, acc0, forest)
    return acc
