"""Pallas TPU kernel: batched level-synchronous forest traversal.

Tree-ensemble inference is memory-gather bound: every depth level of
every tree wants ``x[row, feature[node]]`` for a different (row, node)
pair.  GPUs take the gathers; TPUs have neither scalar gathers in the
vector unit nor atomics, so — like the histogram kernel's
histogram-as-matmul trick — the TPU-native formulation replaces every
gather with a **masked-select reduction** over a static axis:

  field[r, t]  =  sum_j  where(node[r, t] == j, field_level[t, j], 0)

The select axis is tiny (the level's frontier width ``2^d``, then the
feature count ``f``), the compares and sums run on the VPU over fully
static shapes, and exactly one mask lane is hot per (row, tree) — so
the select is also *value-exact* (one non-zero term; adding zeros never
re-associates anything), which keeps the kernel bit-identical to the
jnp reference path.

One launch descends a whole tree chunk: grid over row tiles only, the
chunk's SoA arrays (feature / cmp / leaf) stay resident in VMEM while
row tiles stream through, and the depth loop is unrolled inside the
kernel (static ``max_depth``).  Output is the per-tree leaf-value
matrix ``(rows, trees)``; the caller owns the ensemble summation order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


# The (rows, chunk, width) masked-select temporaries must fit the 16 MB
# of scoped VMEM: 256 rows overran it at depth 8 with 25 trees, 64 fit.
DEFAULT_ROW_TILE = 64


def _traverse_kernel(vals_ref, feat_ref, cmp_ref, leaf_ref, out_ref, *,
                     max_depth: int):
    vals = vals_ref[...]                    # (rt, f) float32 or int32
    feat = feat_ref[...]                    # (C, 2^d - 1) int32
    cmp = cmp_ref[...]                      # (C, 2^d - 1) f32 or int32
    leaf = leaf_ref[...]                    # (C, 2^d) float32
    rt, f = vals.shape
    C = feat.shape[0]

    node = jnp.zeros((rt, C), jnp.int32)    # level-local node ids
    for depth in range(max_depth):
        base = 2 ** depth - 1
        width = 2 ** depth                  # node ids live in [0, width)
        lvl_feat = feat[:, base:base + width]       # static level slice
        lvl_cmp = cmp[:, base:base + width]
        # masked-select the (feature, cmp) node record: one hot lane per
        # (row, tree), so the sum is exact (never re-associates)
        sel = node[:, :, None] == jax.lax.broadcasted_iota(
            jnp.int32, (rt, C, width), 2)
        fidx = jnp.sum(jnp.where(sel, lvl_feat[None], 0), axis=2)
        cval = jnp.sum(jnp.where(sel, lvl_cmp[None], 0), axis=2)
        # masked-select the row's feature value (clip -1 passthrough to
        # feature 0, same as the jnp descent — keeps NaN routing aligned)
        fsel = fidx.clip(0)[:, :, None] == jax.lax.broadcasted_iota(
            jnp.int32, (rt, C, f), 2)
        xv = jnp.sum(jnp.where(fsel, vals[:, None, :], 0), axis=2)
        node = node * 2 + jnp.where(xv <= cval, 0, 1)

    lsel = node[:, :, None] == jax.lax.broadcasted_iota(
        jnp.int32, (rt, C, leaf.shape[1]), 2)
    out_ref[...] = jnp.sum(jnp.where(lsel, leaf[None], 0.0), axis=2)


@functools.partial(jax.jit, static_argnames=("max_depth", "row_tile",
                                             "interpret"))
def traverse_chunk_pallas(values: jax.Array, feature: jax.Array,
                          cmp: jax.Array, leaf: jax.Array, *,
                          max_depth: int,
                          row_tile: int = DEFAULT_ROW_TILE,
                          interpret: bool = False) -> jax.Array:
    """Per-tree leaf values of a stacked tree chunk in one launch.

    Args:
      values: (n, f) raw float32 features or int32 bin ids.
      feature: (C, 2^max_depth - 1) int32; -1 = passthrough.
      cmp: (C, 2^max_depth - 1) float32 thresholds or int32 split bins
        (must match the dtype/mode of ``values``).
      leaf: (C, 2^max_depth) float32 leaf values.
      row_tile: rows per grid step (VMEM knob).

    Returns:
      (n, C) float32 — bit-identical to
      :func:`repro.kernels.ref.traverse_chunk_ref` (the masked-select
      sums have exactly one hot lane, so nothing re-associates).
    """
    n, f = values.shape
    C, n_inner = feature.shape
    n_leaves = leaf.shape[1]
    if max_depth == 0 or n_inner == 0:
        # depth-0 forest: every row lands in the single leaf
        return jnp.broadcast_to(leaf[:, 0][None, :], (n, C))

    n_pad = -n % row_tile
    if n_pad:
        values = jnp.pad(values, ((0, n_pad), (0, 0)))
    nt = (n + n_pad) // row_tile

    out = pl.pallas_call(
        functools.partial(_traverse_kernel, max_depth=max_depth),
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((row_tile, f), lambda t: (t, 0)),
            pl.BlockSpec((C, n_inner), lambda t: (0, 0)),
            pl.BlockSpec((C, n_inner), lambda t: (0, 0)),
            pl.BlockSpec((C, n_leaves), lambda t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((row_tile, C), lambda t: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((n + n_pad, C), jnp.float32),
        interpret=interpret,
    )(values, feature, cmp, leaf)
    return out[:n]
