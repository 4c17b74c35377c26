"""Pallas TPU kernel: gradient/hessian histogram accumulation.

This is the hot loop of distributed GBDT (the paper's Table 2 timing is
dominated by it once proposal is cheap).  GPU implementations use atomic
scatter-adds into shared-memory histograms; TPUs have no atomics, so the
TPU-native formulation is **histogram-as-matmul**:

  for a tile of rows, build the one-hot matrix  O[r, (node,bin)]  and
  contract it with the (rows, 2) grad/hess panel on the MXU:

      hist[f, :, node*nbins+bin] += [g h].T @ O

The one-hot never leaves VMEM; the contraction dimension (rows tile) is a
multiple of 128 so the MXU is fully utilised.

The level-batched entry point :func:`hist_levels_pallas` accumulates the
histograms of L node-id assignments ("levels") of the same rows in one
launch: the grid's middle axis enumerates (level, node_chunk) pairs, so
every frontier (level, node) block lives in VMEM while its row tiles
stream through — one kernel for the whole frontier instead of one launch
per level.  Grid is (features, level*node_chunks, row_tiles) with the
row_tiles axis innermost and accumulating into the same output block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_ROW_TILE = 512


def _hist_levels_kernel(bins_ref, node_ref, gh_ref, out_ref, *,
                        nbins: int, node_chunk: int, n_chunks: int):
    fi, c, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # the bins and node blocks span every feature / level column (the
    # TPU tiling wants a block's minor dim to be the array's whole
    # width); pick this grid step's column with a one-hot lane select,
    # which is value-exact (one hot lane, the rest add integer zeros)
    def column(block, j):
        lanes = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
        return jnp.sum(jnp.where(lanes == j, block, 0), axis=1,
                       keepdims=True)                 # (rt, 1) int32

    bins = column(bins_ref[...], fi)
    # middle grid axis c enumerates (level, chunk)
    node = column(node_ref[...], c // n_chunks)       # -1 = padding
    gh = gh_ref[...].astype(jnp.float32)              # (rt, 2)

    base = (c % n_chunks) * node_chunk
    local = node - base
    valid = (local >= 0) & (local < node_chunk)
    idx = jnp.where(valid, local * nbins + bins, -1)

    width = node_chunk * nbins
    cols = jax.lax.broadcasted_iota(jnp.int32, (idx.shape[0], width), 1)
    onehot = (idx == cols).astype(jnp.float32)        # (rt, width)
    # {g, h} on sublanes, (node, bin) on lanes: gh.T @ onehot, with f32
    # operands kept f32 on the MXU (the default would round gh to bf16)
    out_ref[0, 0] += jax.lax.dot_general(
        gh, onehot, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=(
    "n_nodes", "nbins", "row_tile", "node_chunk", "interpret"))
def hist_levels_pallas(bins: jax.Array, node_per_level: jax.Array,
                       gh: jax.Array, *, n_nodes: int, nbins: int,
                       row_tile: int = DEFAULT_ROW_TILE,
                       node_chunk: int = 0,
                       interpret: bool = False) -> jax.Array:
    """Per-(level, node, feature, bin) grad/hess sums in one launch.

    Args:
      bins: (n, f) int32 bin ids in [0, nbins).
      node_per_level: (L, n) int32 node assignment per level in
        [0, n_nodes); negative = row masked out at that level.
      gh: (n, 2) float grad/hess panel.
      n_nodes: frontier nodes per level.
      nbins: bins per feature.
      node_chunk: nodes per output block (VMEM knob); 0 = auto.

    Returns:
      (L, n_nodes, f, nbins, 2) float32 histogram.
    """
    L, _ = node_per_level.shape
    n, f = bins.shape
    if node_chunk <= 0:
        # keep the one-hot tile under ~8 MB of VMEM: rt * chunk*nbins * 4B
        node_chunk = max(1, min(n_nodes, (8 * 2 ** 20) // (row_tile * nbins * 4)))
    n_chunks = -(-n_nodes // node_chunk)
    nodes_padded = n_chunks * node_chunk

    # pad rows to a tile multiple; padding rows get node=-1 (masked out)
    node_t = node_per_level.T                   # (n, L): row-tiled blocks
    n_pad = -n % row_tile
    if n_pad:
        bins = jnp.pad(bins, ((0, n_pad), (0, 0)))
        node_t = jnp.pad(node_t, ((0, n_pad), (0, 0)), constant_values=-1)
        gh = jnp.pad(gh, ((0, n_pad), (0, 0)))
    nt = (n + n_pad) // row_tile

    out = pl.pallas_call(
        functools.partial(_hist_levels_kernel, nbins=nbins,
                          node_chunk=node_chunk, n_chunks=n_chunks),
        grid=(f, L * n_chunks, nt),
        in_specs=[
            pl.BlockSpec((row_tile, f), lambda fi, c, t: (t, 0)),
            pl.BlockSpec((row_tile, L), lambda fi, c, t: (t, 0)),
            pl.BlockSpec((row_tile, 2), lambda fi, c, t: (t, 0)),
        ],
        # one (2, node_chunk * nbins) panel per (feature, level, chunk):
        # its last two dims are the array's whole, as the TPU tiling wants
        out_specs=pl.BlockSpec((1, 1, 2, node_chunk * nbins),
                               lambda fi, c, t: (fi, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (f, L * n_chunks, 2, node_chunk * nbins), jnp.float32),
        interpret=interpret,
    )(bins, node_t, gh)

    out = out.reshape(f, L, n_chunks, 2, node_chunk, nbins)
    out = jnp.transpose(out, (1, 2, 4, 0, 5, 3))
    return out.reshape(L, nodes_padded, f, nbins, 2)[:, :n_nodes]


@functools.partial(jax.jit, static_argnames=(
    "n_nodes", "nbins", "row_tile", "node_chunk", "interpret"))
def hist_levels_left_pallas(bins: jax.Array, node_per_level: jax.Array,
                            gh: jax.Array, *, n_nodes: int, nbins: int,
                            row_tile: int = DEFAULT_ROW_TILE,
                            node_chunk: int = 0,
                            interpret: bool = False) -> jax.Array:
    """Subtraction child mode: left-routed rows only, parent-keyed panel.

    ``node_per_level`` holds CHILD frontier ids in ``[0, 2 * n_nodes)``;
    rows routed RIGHT (odd id) are masked to -1 and contribute a zero
    one-hot row, so the launch accumulates only the left children into
    ``n_nodes`` PARENT buckets.  The MXU contraction cost per tile is
    unchanged (the one-hot is half as wide but still dense), but the
    output panel — and therefore the HBM writes and any downstream
    ``lax.psum`` — is half the full-frontier panel.

    Returns:
      (n_levels, n_nodes, f, nbins, 2) float32.
    """
    left = (node_per_level >= 0) & (node_per_level % 2 == 0)
    parent = jnp.where(left, node_per_level // 2, -1)
    return hist_levels_pallas(bins, parent, gh, n_nodes=n_nodes,
                              nbins=nbins, row_tile=row_tile,
                              node_chunk=node_chunk, interpret=interpret)
