"""Pallas TPU kernel: gradient/hessian histogram accumulation.

This is the hot loop of GBDT training (the paper's Table 2 timing is
dominated by it once proposal is cheap).  GPU implementations use atomic
scatter-adds into shared-memory histograms; TPUs have no atomics, and
XLA's TPU scatter serialises its updates, so the TPU-native formulation
is **histogram-as-matmul** on the MXU.  For a tile of rows:

  A[(f, bin), r]             = 1 if row r of feature f is in bin
  B[(stat, piece, node), r]  = that piece of row r's grad or hess if r
                               sits at node, else 0
  hist[(f, bin), (stat, piece, node)] += A @ B.T

Every row is read once per tile for all features, nodes and both
statistics; the contraction runs over the rows.  Rows lie on the lane
axis of every block, so the (n, f) bins reach the kernel transposed.

Precision: A is exact in bfloat16.  Each float32 grad/hess value is
split into three bfloat16 pieces, ``hi + mid + lo``, by truncating its
mantissa 8 bits at a time, which gives back every bit of the value (for
normal float32 values).  A one-hot times a piece is exact, so a bf16 MXU
contraction accumulating in float32 sums exactly the float32 inputs, at
three bf16 passes instead of the six of ``Precision.HIGHEST``.  The
pieces are added after the last tile.  The row sum is re-associated
against the scatter's row order, so results match ``hist_ref`` to
float32 rounding, not bit for bit.

``A`` is built in VMEM, one group of features at a time: a tiny exact
matmul against a constant 0/1 expander copies each row's bin id of
feature ``f`` to that feature's ``nbins`` sublanes, and a compare with
each sublane's bin number makes the one-hot.  The grid is (levels,
feature-group blocks, node blocks, row tiles), row tiles innermost,
accumulating into one resident output block.  :func:`plan` sizes the
blocks from (f, nbins, n_nodes) so that a step fits VMEM: SUSY's 18
features x 33 bins x 32 nodes run in one group block and one node block
of 2048-row tiles; 256 bins or deep frontiers split, each node block
rebuilding the one-hot and each group block the panel.

``ops.HistSpec.resolved`` picks this kernel for ``backend='auto'`` on a
TPU; on the CPU, without an MXU, the ``packed`` scatter stays.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# the largest rows per grid step: as fast as 4096 at f=18 on a v5e,
# faster at f=115, of the 512-4096 tried (PERF.md)
DEFAULT_ROW_TILE = 2048
# one-hot sublanes built per matmul: features are grouped to about this
_GROUP_ROWS = 512
# feature groups one grid step may unroll: bounds the kernel's size
_MAX_GROUPS_PER_STEP = 8
# bf16 pieces per float32 value (8 + 8 + 8 significant bits)
_PIECES = 3
# bin ids ride the expander matmul in bf16, exact up to 256
MAX_NBINS = 256
# what a grid step may hold in VMEM by :func:`vmem_bytes`, which reads
# 1.3-9x above what Mosaic allocates for a v5e, under the limit the
# kernel asks for (a v5e has 128 MiB; Mosaic's default limit is 16)
VMEM_BUDGET = 24 * 2 ** 20
_VMEM_LIMIT = 32 * 2 ** 20


class Tiling(NamedTuple):
    """The blocks of one grid step."""
    row_tile: int           # rows (lanes), a multiple of 128
    node_block: int         # frontier nodes: all, or a multiple of 64
    groups_per_step: int    # feature groups whose one-hot is built


def _truncate_bf16(v: jax.Array) -> jax.Array:
    """``v`` with its low 16 bits cleared: a float32 that bf16 holds
    exactly.  Bit arithmetic, so no rounding mode or excess-precision
    rewrite of a convert pair can change it."""
    bits = jax.lax.bitcast_convert_type(v, jnp.int32)
    return jax.lax.bitcast_convert_type(bits & jnp.int32(-0x10000),
                                        jnp.float32)


def split3(v: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``hi, mid, lo`` float32 pieces of ``v``, each exact in bf16, with
    ``hi + mid + lo == v`` for normal float32 ``v``."""
    hi = _truncate_bf16(v)
    rest = v - hi                   # exact: the low 16 mantissa bits
    mid = _truncate_bf16(rest)
    return hi, mid, rest - mid      # the last 8 bits: exact in bf16


def _layout(f: int, nbins: int) -> tuple[int, int, int]:
    """(n_groups, features per group, one-hot sublanes per group)."""
    n_groups = -(-(f * nbins) // _GROUP_ROWS)
    per_group = -(-f // n_groups)
    rows = -(-(per_group * nbins) // 16) * 16       # bf16 sublane tile
    return n_groups, per_group, rows


def _ceil(x: int, m: int) -> int:
    return -(-x // m) * m


def vmem_bytes(f: int, nbins: int, tiling: Tiling) -> int:
    """VMEM a grid step holds: the pipelined blocks twice, the panel
    scratch, and the one-hot and panel values of the kernel body, each
    padded to the chip's (8, 128) / (16, 128) tiles."""
    rt, nb, gps = tiling
    _, _, rows = _layout(f, nbins)
    lanes = _ceil(2 * _PIECES * nb, 128)
    blocks = (gps * rows * lanes * 4                    # out
              + _ceil(f, 16) * rt * 2 + 2 * 8 * rt * 4  # bins, node, gh
              + gps * rows * _ceil(f, 128) * 2          # spread
              + rows * 128 * 4)                         # bin_of
    panel = 2 * _PIECES * nb * rt * 2
    body = (rows * rt * (4 + 2 + 4)          # ids, one-hot, the compare
            + rows * lanes * 4               # one group's product
            + nb * rt * (4 + 4))             # the node mask, a masked piece
    return 2 * blocks + panel + body


def plan(f: int, nbins: int, n_nodes: int,
         max_row_tile: int = DEFAULT_ROW_TILE) -> Tiling:
    """The tiling a launch uses, the first whose step fits
    ``VMEM_BUDGET``: row tiles of at least 512 before smaller ones (a
    grid step has a fixed cost), then the fewest node blocks (each
    rebuilds the one-hot), the fewest feature-group blocks (each
    rebuilds the panel) and the largest row tile."""
    n_groups, _, _ = _layout(f, nbins)
    node_pad = _ceil(n_nodes, 16)
    tiles = [max_row_tile >> k for k in range(5)
             if (max_row_tile >> k) % 128 == 0 and max_row_tile >> k]
    for floor in (min(512, max_row_tile), 0):
        for n_node_blocks in range(1, node_pad // 16 + 1):
            # the output block's lanes, 6 * nb, are a multiple of 128
            # or the whole panel
            nb = (node_pad if n_node_blocks == 1
                  else _ceil(-(-node_pad // n_node_blocks), 64))
            for n_group_blocks in range(1, n_groups + 1):
                gps = -(-n_groups // n_group_blocks)
                if gps > _MAX_GROUPS_PER_STEP:
                    continue
                for rt in tiles:
                    tiling = Tiling(rt, nb, gps)
                    if rt >= floor and (vmem_bytes(f, nbins, tiling)
                                        <= VMEM_BUDGET):
                        return tiling
    raise ValueError(f"no tiling of f={f}, nbins={nbins}, "
                     f"n_nodes={n_nodes} fits {VMEM_BUDGET} bytes of VMEM")


def _expander(f: int, f_pad: int, nbins: int, n_groups_pad: int):
    """The constant operands that turn bin ids into the one-hot.

    Returns ``spread`` (n_groups_pad, rows, f_pad) bf16, whose row ``s``
    of group ``g`` picks feature ``g * per_group + s // nbins`` (no
    feature past ``f``), and ``bin_of`` (rows, 1) float32, row ``s``'s
    bin number in every group (-1 on padding rows, which no bin id
    equals).  Rows of features past ``f`` count bin 0 in slots that are
    cut off afterwards."""
    _, per_group, rows = _layout(f, nbins)
    spread = np.zeros((n_groups_pad, rows, f_pad), np.float32)
    for feat in range(f):
        g, j = divmod(feat, per_group)
        spread[g, j * nbins:(j + 1) * nbins, feat] = 1.0
    bin_of = np.full((rows, 1), -1.0, np.float32)
    bin_of[:per_group * nbins, 0] = np.tile(np.arange(nbins), per_group)
    return jnp.asarray(spread, jnp.bfloat16), jnp.asarray(bin_of)


def _hist_levels_kernel(bins_ref, node_ref, gh_ref, spread_ref,
                        bin_of_ref, out_ref, panel_ref, *,
                        groups_per_step: int, node_block: int):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # B: sublane (stat, piece, node) holds that piece of each row's grad
    # or hess where the row sits at the node of this step's block;
    # masked rows (id < 0) and padding rows match no node
    node = node_ref[0] - pl.program_id(2) * node_block  # (1, rt) int32
    at = node == jax.lax.broadcasted_iota(
        jnp.int32, (node_block, node.shape[1]), 0)      # (node_block, rt)
    pieces = split3(gh_ref[...])                        # 3 x (2, rt)
    for s in range(2):
        for c, piece in enumerate(pieces):
            row = (s * _PIECES + c) * node_block
            panel_ref[row:row + node_block, :] = jnp.where(
                at, piece[s:s + 1, :], 0.0).astype(jnp.bfloat16)
    panel = panel_ref[...]                              # (6*nb, rt)

    bins = bins_ref[...]                                # (f_pad, rt) bf16
    for g in range(groups_per_step):
        # each sublane gets its feature's bin id (one 0/1 term: exact),
        # then the compare with its own bin number makes A
        ids = jnp.dot(spread_ref[g], bins,
                      preferred_element_type=jnp.float32)   # (rows, rt)
        onehot = (ids == bin_of_ref[...]).astype(jnp.bfloat16)
        out_ref[0, g] += jax.lax.dot_general(
            onehot, panel, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=(
    "n_nodes", "nbins", "row_tile", "interpret"))
def hist_levels_pallas(bins: jax.Array, node_per_level: jax.Array,
                       gh: jax.Array, *, n_nodes: int, nbins: int,
                       row_tile: int = DEFAULT_ROW_TILE,
                       interpret: bool = False) -> jax.Array:
    """Per-(level, node, feature, bin) grad/hess sums in one launch.

    Args:
      bins: (n, f) integer bin ids in [0, nbins), nbins <= 256.
      node_per_level: (L, n) int32 node assignment per level in
        [0, n_nodes); negative = row masked out at that level.
      gh: (n, 2) float grad/hess panel (finite).
      n_nodes: frontier nodes per level.
      nbins: bins per feature.
      row_tile: the largest rows per grid step (a multiple of 128);
        :func:`plan` takes a smaller one where a step would overrun
        VMEM.

    Returns:
      (L, n_nodes, f, nbins, 2) float32 histogram.
    """
    if nbins > MAX_NBINS:
        raise ValueError(f"nbins={nbins} > {MAX_NBINS}: bin ids would not "
                         "be exact in bf16")
    n, f = bins.shape
    tiling = plan(f, nbins, n_nodes, min(row_tile, _ceil(n, 128)))
    return _hist_tiled(bins, node_per_level, gh, n_nodes=n_nodes,
                       nbins=nbins, tiling=tiling, interpret=interpret)


def _hist_tiled(bins, node_per_level, gh, *, n_nodes: int, nbins: int,
                tiling: Tiling, interpret: bool) -> jax.Array:
    """:func:`hist_levels_pallas` over the grid ``tiling`` gives: (levels,
    feature-group blocks, node blocks, row tiles), row tiles innermost,
    accumulating into one resident output block."""
    rt, nb, gps = tiling
    L, _ = node_per_level.shape
    n, f = bins.shape
    n_pad = _ceil(n, rt)
    f_pad = _ceil(f, 16)
    n_groups, per_group, rows = _layout(f, nbins)
    n_group_blocks = -(-n_groups // gps)
    n_node_blocks = -(-n_nodes // nb)
    spread, bin_of = _expander(f, f_pad, nbins, n_group_blocks * gps)
    n_panel = 2 * _PIECES * nb                           # B's sublanes

    # rows on lanes; padding rows sit at node -1 with zero grad/hess
    bins_t = jnp.pad(bins.T.astype(jnp.bfloat16),
                     ((0, f_pad - f), (0, n_pad - n)))
    node_t = jnp.pad(node_per_level.astype(jnp.int32),
                     ((0, 0), (0, n_pad - n)),
                     constant_values=-1)[:, None, :]     # (L, 1, n_pad)
    gh_t = jnp.pad(gh.astype(jnp.float32).T, ((0, 0), (0, n_pad - n)))

    out = pl.pallas_call(
        functools.partial(_hist_levels_kernel, groups_per_step=gps,
                          node_block=nb),
        grid=(L, n_group_blocks, n_node_blocks, n_pad // rt),
        in_specs=[
            pl.BlockSpec((f_pad, rt), lambda l, g, k, t: (0, t)),
            pl.BlockSpec((1, 1, rt), lambda l, g, k, t: (l, 0, t)),
            pl.BlockSpec((2, rt), lambda l, g, k, t: (0, t)),
            pl.BlockSpec((gps, rows, f_pad), lambda l, g, k, t: (g, 0, 0)),
            pl.BlockSpec((rows, 1), lambda l, g, k, t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, gps, rows, n_panel),
                               lambda l, g, k, t: (l, g, 0, k)),
        out_shape=jax.ShapeDtypeStruct(
            (L, n_group_blocks * gps, rows, n_node_blocks * n_panel),
            jnp.float32),
        scratch_shapes=[pltpu.VMEM((n_panel, rt), jnp.bfloat16)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(bins_t, node_t, gh_t, spread, bin_of)

    # (L, group, feature-in-group, bin, node block, stat, piece, node)
    # -> add pieces -> (L, node, feature, bin, stat)
    out = out[:, :, :per_group * nbins].reshape(
        L, n_group_blocks * gps * per_group, nbins, n_node_blocks, 2,
        _PIECES, nb)
    out = (out[..., 0, :] + out[..., 1, :]) + out[..., 2, :]
    out = jnp.transpose(out[:, :f], (0, 3, 5, 1, 2, 4))
    return out.reshape(L, n_node_blocks * nb, f, nbins, 2)[:, :n_nodes]


@functools.partial(jax.jit, static_argnames=(
    "n_nodes", "nbins", "row_tile", "interpret"))
def hist_levels_left_pallas(bins: jax.Array, node_per_level: jax.Array,
                            gh: jax.Array, *, n_nodes: int, nbins: int,
                            row_tile: int = DEFAULT_ROW_TILE,
                            interpret: bool = False) -> jax.Array:
    """Subtraction child mode: left-routed rows only, parent-keyed panel.

    ``node_per_level`` holds CHILD frontier ids in ``[0, 2 * n_nodes)``;
    rows routed RIGHT (odd id) are masked to -1 and match no node, so
    the launch accumulates only the left children into ``n_nodes``
    PARENT buckets.  The MXU work per tile is that of a direct launch
    over ``n_nodes`` nodes; the output panel — and therefore any
    downstream ``lax.psum`` — is half the full-frontier panel.

    Returns:
      (n_levels, n_nodes, f, nbins, 2) float32.
    """
    left = (node_per_level >= 0) & (node_per_level % 2 == 0)
    parent = jnp.where(left, node_per_level // 2, -1)
    return hist_levels_pallas(bins, parent, gh, n_nodes=n_nodes,
                              nbins=nbins, row_tile=row_tile,
                              interpret=interpret)
