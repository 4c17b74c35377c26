"""Inputs and weights of every cell, made on the device from ``--seed``.

Copied from the program's generators so that no later change to the
program can move the yardstick:

- :func:`mixture` is ``repro.data.tabular.gaussian_classification``
  (two anisotropic Gaussian classes, a quarter of the columns turned
  into products of two columns, 5% of labels flipped), written with
  ``jax.random`` so that millions of rows are made on the chip in one
  jitted call instead of in numpy and then copied over;
  :func:`mixture_host` makes the same rows block by block into a host
  pool.
- :func:`forest` is ``repro.launch.serve_gbdt.synthetic_gbdt``: a
  random forest that keeps the invariants of a trained one (sorted
  candidate grid, each threshold is ``candidates[feature, split_bin]``,
  passthrough nodes carry ``(-1, k, +inf)``).  Here the candidates are
  the paper's random proposal, values sampled from the data, so a row
  takes the paths it would take through a forest trained on that data.

Each returns plain arrays.  The harness hands them to the program, and
host copies of the same arrays to the plain reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A raw threefry key from any whole number (wider than 32 bits too).

    ``stream`` separates the draws of one run (data, forest, per-call
    keys) so that none of them shares bits with another.
    """
    words = np.random.SeedSequence([seed % (1 << 64), stream]).generate_state(
        2, dtype=np.uint32)
    return jnp.asarray(words, dtype=jnp.uint32)


def _params(key: jax.Array, f: int, sep: float):
    ky, km, ks, kx, kf = jax.random.split(key, 5)
    means = sep * jax.random.normal(km, (f, 2), jnp.float32)
    scales = jax.random.uniform(ks, (f, 2), jnp.float32, 0.5, 2.0)
    return (ky, kx, kf), means, scales


def _block(keys, means, scales, i, *, block: int, f: int, flip: float,
           positive_share: float):
    """Rows of block ``i``: made feature-major, so that a few features do
    not pad out to the chip's 128 lanes, then turned row-major."""
    ky, kx, kf = keys
    k = max(2, f // 4)
    cls = jax.random.bernoulli(jax.random.fold_in(ky, i), positive_share,
                               (block,)).astype(jnp.int32)
    x = means[:, cls] + scales[:, cls] * jax.random.normal(
        jax.random.fold_in(kx, i), (f, block), jnp.float32)
    if 2 * k <= f:
        # non-linear interaction columns (physics-derived features)
        x = x.at[:k].set(x[:k] * x[k:2 * k])
    noise = jax.random.bernoulli(jax.random.fold_in(kf, i), flip, (block,))
    return x.T, jnp.where(noise, 1 - cls, cls).astype(jnp.float32)


def _starts(n: int, block: int) -> list[int]:
    """First row of each block; the last block ends at row ``n``."""
    return [min(i * block, n - block) for i in range(-(-n // block))]


@functools.partial(jax.jit, static_argnames=("n", "f", "block"))
def mixture(key: jax.Array, *, n: int, f: int, sep: float = 1.2,
            flip: float = 0.05, positive_share: float = 0.5,
            block: int = 1 << 17):
    """``(x, y)`` on the device: ``(n, f)`` float32 features and ``(n,)``
    0/1 labels.

    Blocks of rows are written in place into ``x``, so the generator
    needs little beyond ``x`` itself: its peak stays under the peak of
    the work that follows.  The last block ends at row ``n`` and
    overwrites the tail of the one before it.
    """
    block = min(block, n)
    keys, means, scales = _params(key, f, sep)

    def body(i, xy):
        xb, yb = _block(keys, means, scales, i, block=block, f=f, flip=flip,
                        positive_share=positive_share)
        start = jnp.minimum(i * block, n - block)
        return (jax.lax.dynamic_update_slice(xy[0], xb, (start, 0)),
                jax.lax.dynamic_update_slice(xy[1], yb, (start,)))

    init = (jnp.zeros((n, f), jnp.float32), jnp.zeros((n,), jnp.float32))
    return jax.lax.fori_loop(0, -(-n // block), body, init)


@functools.partial(jax.jit, static_argnames=("f", "block"))
def _host_block(key, i, *, f, sep, flip, positive_share, block):
    keys, means, scales = _params(key, f, sep)
    return _block(keys, means, scales, i, block=block, f=f, flip=flip,
                  positive_share=positive_share)


def mixture_host(key: jax.Array, *, n: int, f: int, sep: float = 1.2,
                 flip: float = 0.05, positive_share: float = 0.5,
                 block: int = 1 << 14):
    """:func:`mixture`'s rows for the same ``block``, made on the device
    one block at a time and gathered on the host as numpy arrays: a
    client's pool of requests, which leaves no copy on the device."""
    block = min(block, n)
    x = np.empty((n, f), np.float32)
    y = np.empty((n,), np.float32)
    for i, start in enumerate(_starts(n, block)):
        xb, yb = jax.device_get(_host_block(
            key, i, f=f, sep=sep, flip=flip, positive_share=positive_share,
            block=block))
        x[start:start + block], y[start:start + block] = xb, yb
    return x, y


def forest(key: jax.Array, x, *, n_trees: int, max_depth: int, k: int,
           passthrough_frac: float = 0.1, leaf_scale: float = 0.1):
    """A forest of ``n_trees`` complete trees of depth ``max_depth`` on
    the rows ``x`` (host or device).

    Returns ``(candidates (f, k), feature (T, 2^d - 1) int32, split_bin
    (T, 2^d - 1) int32, threshold (T, 2^d - 1) float32, leaf_value
    (T, 2^d) float32)``, the candidates on the host, the rest on the
    device.
    """
    x = np.asarray(x)
    n, f = x.shape
    kc, kt = jax.random.split(key)
    rows = np.asarray(jax.random.randint(kc, (f, k), 0, n))
    cands = np.sort(x[rows, np.arange(f)[:, None]], axis=1)
    return (cands,) + _trees(kt, jnp.asarray(cands), n_trees=n_trees,
                             max_depth=max_depth,
                             passthrough_frac=passthrough_frac,
                             leaf_scale=leaf_scale)


@functools.partial(jax.jit, static_argnames=("n_trees", "max_depth"))
def _trees(key, cands, *, n_trees, max_depth, passthrough_frac, leaf_scale):
    f, k = cands.shape
    n_inner, n_leaves = 2 ** max_depth - 1, 2 ** max_depth
    kf, ks, kp, kl = jax.random.split(key, 4)
    feature = jax.random.randint(kf, (n_trees, n_inner), 0, f)
    split_bin = jax.random.randint(ks, (n_trees, n_inner), 0, k)
    passthrough = jax.random.uniform(kp, (n_trees, n_inner)) < passthrough_frac
    feature = jnp.where(passthrough, -1, feature).astype(jnp.int32)
    split_bin = jnp.where(passthrough, k, split_bin).astype(jnp.int32)
    threshold = cands[feature.clip(0), split_bin.clip(max=k - 1)]
    threshold = jnp.where(passthrough, jnp.inf, threshold).astype(jnp.float32)
    leaf = leaf_scale * jax.random.normal(kl, (n_trees, n_leaves), jnp.float32)
    return feature, split_bin, threshold, leaf
