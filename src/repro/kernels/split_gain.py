"""Pallas TPU kernel: best-split scan over histogram bins.

Given the (node, feature, bin, {g,h}) histogram, compute for every
(node, feature) the split position maximising the XGBoost gain

  gain(s) = 1/2 [ GL(s)^2/(HL(s)+l2) + GR(s)^2/(HR(s)+l2) - G^2/(H+l2) ] - gamma

subject to min_child_weight on both sides.  One grid step per node; the
whole (features, nbins) panel for a node lives in VMEM (f*nbins*2 floats —
a few hundred KB for realistic f<=512, nbins<=256).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _split_gain_kernel(hist_ref, gain_ref, idx_ref, *,
                       l2: float, gamma: float, min_child_weight: float):
    g = hist_ref[0, 0]                       # (nbins, f) f32
    h = hist_ref[0, 1]
    nbins = g.shape[0]

    def score(gg, hh):
        return (gg * gg) / (hh + l2)

    # left sums incl. bin s, as (1, f) rows, added left to right like
    # ref.split_gain_ref: Pallas TPU has no cumsum, and the static bin
    # axis is short enough to unroll
    gl, hl = [g[0:1]], [h[0:1]]
    for s in range(1, nbins):
        gl.append(gl[-1] + g[s:s + 1])
        hl.append(hl[-1] + h[s:s + 1])
    gt, ht = gl[-1], hl[-1]

    best = jnp.full(gt.shape, -jnp.inf, jnp.float32)
    best_s = jnp.zeros(gt.shape, jnp.int32)
    # splitting at the last bin puts everything left — never useful
    for s in range(nbins - 1):
        gr = gt - gl[s]
        hr = ht - hl[s]
        gain = 0.5 * (score(gl[s], hl[s]) + score(gr, hr)
                      - score(gt, ht)) - gamma
        ok = (hl[s] >= min_child_weight) & (hr >= min_child_weight)
        gain = jnp.where(ok, gain, -jnp.inf)
        better = gain > best                 # first maximum wins (argmax)
        best = jnp.where(better, gain, best)
        best_s = jnp.where(better, s, best_s)

    gain_ref[0] = best
    idx_ref[0] = best_s


@functools.partial(jax.jit, static_argnames=(
    "l2", "gamma", "min_child_weight", "interpret"))
def split_gain_pallas(hist: jax.Array, *, l2: float = 1.0, gamma: float = 0.0,
                      min_child_weight: float = 1e-6,
                      interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Best gain and split-bin per (node, feature).

    Args:
      hist: (n_nodes, f, nbins, 2) float32 histogram.

    Returns:
      gains: (n_nodes, f) float32 (-inf where no legal split).
      idx:   (n_nodes, f) int32 best bin index s (split: bin <= s goes left).
    """
    n_nodes, f, nbins, _ = hist.shape
    kern = functools.partial(_split_gain_kernel, l2=float(l2),
                             gamma=float(gamma),
                             min_child_weight=float(min_child_weight))
    gains, idx = pl.pallas_call(
        kern,
        grid=(n_nodes,),
        in_specs=[pl.BlockSpec((1, 2, nbins, f), lambda i: (i, 0, 0, 0))],
        # a (1, f) row per node: the unit axis makes the block's last
        # two dims the array's whole (1, f), as the TPU tiling requires
        out_specs=[
            pl.BlockSpec((1, 1, f), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, f), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_nodes, 1, f), jnp.float32),
            jax.ShapeDtypeStruct((n_nodes, 1, f), jnp.int32),
        ],
        interpret=interpret,
    )(jnp.transpose(hist, (0, 3, 2, 1)))    # {g,h} x bins x features
    return gains[:, 0], idx[:, 0]
