"""Pure-jnp oracles for every kernel in this package.

These are the correctness ground truth for the Pallas kernels
(tests assert allclose against them across shape/dtype sweeps) and the
fallback implementation on backends without Pallas support.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("n_nodes", "nbins"))
def hist_ref(bins: jax.Array, node: jax.Array, gh: jax.Array, *,
             n_nodes: int, nbins: int) -> jax.Array:
    """(n_nodes, f, nbins, 2) grad/hess histogram via scatter-add."""
    n, f = bins.shape
    valid = node >= 0
    node_c = jnp.where(valid, node, 0)
    # flat index: ((node * f) + feat) * nbins + bin
    flat = (node_c[:, None] * f + jnp.arange(f)[None, :]) * nbins + bins
    w = jnp.where(valid, 1.0, 0.0)
    vals = jnp.broadcast_to((gh * w[:, None])[:, None, :],
                            (n, f, 2)).astype(jnp.float32)
    out = jnp.zeros((n_nodes * f * nbins, 2), jnp.float32)
    out = out.at[flat.ravel()].add(vals.reshape(n * f, 2))
    return out.reshape(n_nodes, f, nbins, 2)


@functools.partial(jax.jit, static_argnames=("n_nodes", "nbins"))
def hist_levels_ref(bins: jax.Array, node_per_level: jax.Array,
                    gh: jax.Array, *, n_nodes: int, nbins: int) -> jax.Array:
    """Oracle for the level-batched histogram: a naive per-level loop of
    :func:`hist_ref`, stacked along a leading level axis.

    Args:
      node_per_level: (n_levels, n) int32 node ids per level in
        [0, n_nodes); negative = row masked out at that level.

    Returns:
      (n_levels, n_nodes, f, nbins, 2) float32.
    """
    return jnp.stack([
        hist_ref(bins, node_per_level[lvl], gh, n_nodes=n_nodes, nbins=nbins)
        for lvl in range(node_per_level.shape[0])])


@functools.partial(jax.jit, static_argnames=("n_nodes", "nbins"))
def hist_levels_packed(bins: jax.Array, node_per_level: jax.Array,
                       gh: jax.Array, *, n_nodes: int,
                       nbins: int) -> jax.Array:
    """Level-batched CPU histogram: ONE complex64 scatter keyed by
    (level, node, feature, bin).

    Bit-exact vs :func:`hist_levels_ref`: buckets are disjoint across
    levels and features, the real/imag lanes add independently, and
    within each bucket the updates arrive in the same row order as the
    per-level scatter.  The feature-bin offset ``fb`` and the packed
    grad/hess panel are level-invariant, so batching L levels amortises
    the index arithmetic that a per-level loop would recompute (and lets
    XLA hoist both out of a level-step ``lax.scan``).
    """
    L, n = node_per_level.shape
    f = bins.shape[1]
    valid = node_per_level >= 0                            # (L, n)
    node_c = jnp.where(valid, node_per_level, 0)
    fb = jnp.arange(f, dtype=jnp.int32)[None, :] * nbins + bins   # (n, f)
    z = jax.lax.complex(gh[:, 0].astype(jnp.float32),
                        gh[:, 1].astype(jnp.float32)).astype(jnp.complex64)
    zl = jnp.where(valid, z[None, :], 0)                   # (L, n)
    lvl_node = (jnp.arange(L, dtype=jnp.int32)[:, None] * n_nodes + node_c)
    flat = lvl_node[:, :, None] * (f * nbins) + fb[None]   # (L, n, f)
    vals = jnp.broadcast_to(zl[:, :, None], (L, n, f))
    out = jnp.zeros((L * n_nodes * f * nbins,), jnp.complex64)
    out = out.at[flat.ravel()].add(vals.ravel())
    return jnp.stack([out.real, out.imag], -1).reshape(
        L, n_nodes, f, nbins, 2).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("n_nodes", "nbins"))
def hist_levels_left_ref(bins: jax.Array, node_per_level: jax.Array,
                         gh: jax.Array, *, n_nodes: int,
                         nbins: int) -> jax.Array:
    """Oracle for the histogram-subtraction child mode.

    ``node_per_level`` holds CHILD frontier ids in ``[0, 2 * n_nodes)``
    (level-local heap ids: left child of parent ``p`` is ``2p``, right is
    ``2p + 1``).  Only rows routed LEFT (even id) contribute, keyed by
    the parent id ``child >> 1``; odd and negative ids drop out.  The
    sibling histogram is NOT computed here — subtraction growers derive
    it as ``parent - left`` from the cached previous-level panel.

    Returns:
      (n_levels, n_nodes, f, nbins, 2) float32 — ``n_nodes`` PARENT
      buckets, i.e. half the child frontier.
    """
    left = (node_per_level >= 0) & (node_per_level % 2 == 0)
    parent = jnp.where(left, node_per_level // 2, -1)
    return hist_levels_ref(bins, parent, gh, n_nodes=n_nodes, nbins=nbins)


@functools.partial(jax.jit, static_argnames=("n_nodes", "nbins"))
def hist_levels_left_packed(bins: jax.Array, node_per_level: jax.Array,
                            gh: jax.Array, *, n_nodes: int,
                            nbins: int) -> jax.Array:
    """Packed CPU scatter for the subtraction child mode (see
    :func:`hist_levels_left_ref` for the indexing contract).

    One complex64 scatter into the HALF-width parent-keyed panel: rows
    routed RIGHT (odd child id) and masked rows (negative id) get an
    out-of-range flat index, which XLA's default scatter mode DROPS —
    they never reach the read-modify-write, so the logical update count
    is ``n_left * f`` instead of ``n * f`` and the output working set is
    half the full-frontier panel's.  Matches the oracle exactly: within
    each parent bucket the surviving updates arrive in the same row
    order as the per-level scatter.
    """
    L, n = node_per_level.shape
    f = bins.shape[1]
    left = (node_per_level >= 0) & (node_per_level % 2 == 0)   # (L, n)
    parent = jnp.where(left, node_per_level // 2, 0)
    fb = jnp.arange(f, dtype=jnp.int32)[None, :] * nbins + bins   # (n, f)
    z = jax.lax.complex(gh[:, 0].astype(jnp.float32),
                        gh[:, 1].astype(jnp.float32)).astype(jnp.complex64)
    size = L * n_nodes * f * nbins
    lvl_node = (jnp.arange(L, dtype=jnp.int32)[:, None] * n_nodes + parent)
    flat = lvl_node[:, :, None] * (f * nbins) + fb[None]       # (L, n, f)
    # dropped rows point one-past-the-end (NOT -1: negative indices wrap
    # under NumPy semantics; >= size is unambiguously out of bounds)
    flat = jnp.where(left[:, :, None], flat, size)
    vals = jnp.broadcast_to(z[None, :, None], (L, n, f))
    out = jnp.zeros((size,), jnp.complex64)
    out = out.at[flat.ravel()].add(vals.ravel())
    return jnp.stack([out.real, out.imag], -1).reshape(
        L, n_nodes, f, nbins, 2).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("n_nodes", "nbins"))
def hist_packed(bins: jax.Array, node: jax.Array, gh: jax.Array, *,
                n_nodes: int, nbins: int) -> jax.Array:
    """CPU-fast histogram: grad/hess packed into one complex64 scatter.

    Bit-exact vs :func:`hist_ref` (the real/imag lanes add independently,
    in the same row order), but issues ONE scalar scatter-add per (row,
    feature) instead of a 2-wide slice update — ~1.6x faster through
    XLA:CPU's scatter path.  Single-level view of
    :func:`hist_levels_packed`; ``hist_ref`` stays the correctness
    oracle.
    """
    return hist_levels_packed(bins, node[None], gh,
                              n_nodes=n_nodes, nbins=nbins)[0]


# ---------------------------------------------------------------------------
# Batched level-synchronous forest traversal (inference hot path).
#
# A "chunk" is C stacked trees in heap SoA layout: feature (C, 2^d - 1),
# cmp (C, 2^d - 1) — raw thresholds (float32) or split bins (int32) —
# and leaf (C, 2^d).  All C trees advance one depth level per step; the
# contract is PER-TREE leaf values (n, C), so the caller controls the
# ensemble summation order (the engine accumulates in tree order, which
# makes it bit-identical to the sequential per-tree scan it replaces).
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("max_depth",))
def traverse_chunk_ref(values: jax.Array, feature: jax.Array,
                       cmp: jax.Array, leaf: jax.Array, *,
                       max_depth: int) -> jax.Array:
    """Oracle for the level-synchronous chunk traversal: a vmap over the
    per-tree descent, field-for-field the same indexing as
    ``tree._descend_raw`` / ``tree._descend_binned`` (so per-tree leaf
    values are bit-identical to the single-tree predictors).

    Args:
      values: (n, f) raw float32 features or int32 bin ids — the dtype
        carries the mode; the comparison ``value <= cmp`` is the split
        rule either way (NaN compares False, so NaN rows route RIGHT on
        the raw path).
      feature: (C, 2^max_depth - 1) int32 split features; -1 =
        passthrough (clipped to 0 for the gather, exactly like the
        single-tree descent).
      cmp: (C, 2^max_depth - 1) thresholds (float32, +inf passthrough)
        or split bins (int32, nbins-1 passthrough).
      leaf: (C, 2^max_depth) float32 leaf values.

    Returns:
      (n, C) float32 per-tree leaf values.
    """
    n = values.shape[0]

    def one_tree(fe, cm, lf):
        node = jnp.zeros((n,), jnp.int32)
        for depth in range(max_depth):
            heap = (2 ** depth - 1) + node
            fidx = fe[heap]
            cv = cm[heap]
            xv = jnp.take_along_axis(values, fidx.clip(0)[:, None], 1)[:, 0]
            node = node * 2 + jnp.where(xv <= cv, 0, 1)
        return lf[node]

    return jax.vmap(one_tree, in_axes=0, out_axes=1)(feature, cmp, leaf)


def _g(src: jax.Array, idx: jax.Array) -> jax.Array:
    """In-bounds flat gather.  ``promise_in_bounds`` skips XLA:CPU's
    per-element clamp — measurably faster at this kernel's gather
    volume (12 gathers per row-tree) — and is safe here because every
    index is in range by construction: level-local node ids live in
    [0, 2^depth), heap offsets stay below 2^max_depth - 1, and feature
    ids from build_tree are in [-1, f) and clipped to 0 before use."""
    return src.at[idx].get(mode="promise_in_bounds", unique_indices=False)


@functools.partial(jax.jit, static_argnames=("max_depth",))
def traverse_chunk_packed(values: jax.Array, feature: jax.Array,
                          cmp: jax.Array, leaf: jax.Array, *,
                          max_depth: int) -> jax.Array:
    """CPU-fast chunk traversal: the (feature, cmp) node record is packed
    into one complex64 array, so every level costs ONE fused gather over
    the whole flattened (tree, node) heap for both fields — plus one
    row-wise feature-value gather — instead of the per-tree loop's 2C
    small gathers.  Three extra CPU tweaks, each worth real wall-clock
    at the 500x6 bench: level 0 reads the root record with a slice
    instead of a gather (every row is at node 0), all gathers are flat
    1-D with precomputed row/tree offsets (XLA lowers these leaner than
    the fancy-indexing dimension_numbers), and bounds clamping is
    skipped via promise_in_bounds (see :func:`_g`).

    Bit-exact vs :func:`traverse_chunk_ref`: the comparison runs in
    float32 on both paths (bin ids and split bins are small ints, exact
    in f32; feature ids < 2^24 survive the imag lane round-trip), and
    the -1 passthrough feature is clipped to 0 before the value gather,
    so even NaN rows take identical routes.

    Same signature/returns as :func:`traverse_chunk_ref`.
    """
    n, f = values.shape
    C, n_inner = feature.shape
    n_leaves = leaf.shape[1]
    if max_depth == 0 or n_inner == 0:
        return jnp.broadcast_to(leaf[:, 0][None, :], (n, C))
    rec = jax.lax.complex(cmp.astype(jnp.float32),
                          feature.astype(jnp.float32))
    rec = rec.astype(jnp.complex64).ravel()          # (C * n_inner,)
    tree_off = (jnp.arange(C, dtype=jnp.int32) * n_inner)[None, :]
    row_off = (jnp.arange(n, dtype=jnp.int32) * f)[:, None]
    vflat = values.astype(jnp.float32).ravel()
    # level 0: every row sits at the root — slice the record, no gather
    f0 = feature[:, 0].clip(0)                       # (C,)
    c0 = cmp[:, 0].astype(jnp.float32)
    xv = _g(vflat, row_off + f0[None, :])
    node = jnp.where(xv <= c0[None, :], 0, 1).astype(jnp.int32)
    for depth in range(1, max_depth):
        r = _g(rec, tree_off + (2 ** depth - 1) + node)   # both fields
        fidx = r.imag.astype(jnp.int32)
        xv = _g(vflat, row_off + fidx.clip(0))
        node = node * 2 + jnp.where(xv <= r.real, 0, 1)
    leaf_off = (jnp.arange(C, dtype=jnp.int32) * n_leaves)[None, :]
    return _g(leaf.ravel(), leaf_off + node)


@functools.partial(jax.jit, static_argnames=())
def _score(g, h, l2):
    return (g * g) / (h + l2)


def _prefix_sum(a: jax.Array) -> jax.Array:
    """Left-to-right prefix sum over the last axis, ``((a0 + a1) + a2)
    ...``.  One fixed association on every backend, the same one the
    Pallas split-gain kernel unrolls: ``jnp.cumsum`` regroups long axes
    differently per backend, which moves gains by a few ulps."""
    out = [a[..., :1]]
    for s in range(1, a.shape[-1]):
        out.append(out[-1] + a[..., s:s + 1])
    return jnp.concatenate(out, axis=-1)


@functools.partial(jax.jit, static_argnames=())
def split_gain_ref(hist: jax.Array, *, l2: float = 1.0, gamma: float = 0.0,
                   min_child_weight: float = 1e-6):
    """Best gain / split-bin per (node, feature) — oracle for split_gain."""
    g = hist[..., 0]
    h = hist[..., 1]
    gl = _prefix_sum(g)
    hl = _prefix_sum(h)
    gt = gl[..., -1:]
    ht = hl[..., -1:]
    gr = gt - gl
    hr = ht - hl
    gain = 0.5 * (_score(gl, hl, l2) + _score(gr, hr, l2)
                  - _score(gt, ht, l2)) - gamma
    nbins = gain.shape[2]
    pos = jnp.arange(nbins)
    ok = (hl >= min_child_weight) & (hr >= min_child_weight) \
        & (pos < nbins - 1)
    gain = jnp.where(ok, gain, -jnp.inf)
    return jnp.max(gain, axis=2), jnp.argmax(gain, axis=2).astype(jnp.int32)


def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, window: int = 0) -> jax.Array:
    """Naive fp32 attention with GQA + sliding window (oracle)."""
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    g = hq // hkv
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / (d ** 0.5)
    qpos = jnp.arange(sq)[:, None] + (sk - sq)  # right-aligned (cache case)
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
