"""Candidate split-point proposal strategies.

The paper's contribution is the ``random`` strategy (uniform sampling of
feature values) plus its distributed form (Algorithm 1: local sample →
AllReduce/all-gather → shared resample).  The baselines it is measured
against are the "data faithful" strategies: GK quantile summary
(XGBoost's unweighted limit), the weighted quantile sketch (XGBoost
proper), and fixed uniform-range bins (CatBoost-style).

All strategies return a dense ``(n_features, k)`` float32 array of sorted
candidate values; a feature with fewer distinct values than k simply
repeats values (binning collapses duplicates into empty bins, which is
harmless for split finding).
"""

from __future__ import annotations

import warnings
from functools import partial
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from . import sketch

Strategy = Literal["random", "gk_quantile", "weighted_quantile",
                   "uniform_range", "exact"]

# Strategies that lower to pure jax ops, so the boosting trainers can
# re-propose *inside* a lax.scan round step.  The host-side strategies
# ('gk_quantile', 'exact') are x-only — their candidates are identical
# every round — so the trainers compute them once outside the scan.
TRACEABLE: tuple[str, ...] = ("random", "weighted_quantile",
                              "uniform_range")


# ---------------------------------------------------------------------------
# The paper's method: uniform random sampling (jit-able, O(n) per feature).
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("k",))
def random_candidates(key: jax.Array, x: jax.Array, k: int) -> jax.Array:
    """Uniform random candidates for every feature.

    Args:
      key: PRNG key.
      x: (n, f) feature matrix.
      k: candidates per feature.

    Returns:
      (f, k) sorted candidates.
    """
    n, f = x.shape

    def per_feature(key, col):
        idx = jax.random.randint(key, (k,), 0, n)
        return jnp.sort(col[idx])

    keys = jax.random.split(key, f)
    return jax.vmap(per_feature)(keys, x.T)


@partial(jax.jit, static_argnames=("k",))
def random_candidates_local(key: jax.Array, x_local: jax.Array, k: int) -> jax.Array:
    """Per-worker local sampling done 'during data read' (Appendix 6.1)."""
    return random_candidates(key, x_local, k)


def resample_gathered(key: jax.Array, gathered: jax.Array, k: int) -> jax.Array:
    """Algorithm 1's post-AllReduce step: combine then resample to size k.

    Args:
      gathered: (workers, f, k) candidates from every worker
        (the all-gather result — identical on every worker).
      k: target candidates per feature.

    Returns:
      (f, k) sorted candidates — deterministic in ``key`` so every worker
      computes the *same* set without a second broadcast.
    """
    w, f, kk = gathered.shape
    pool = jnp.transpose(gathered, (1, 0, 2)).reshape(f, w * kk)

    def per_feature(key, row):
        idx = jax.random.randint(key, (k,), 0, row.shape[0])
        return jnp.sort(row[idx])

    keys = jax.random.split(key, f)
    return jax.vmap(per_feature)(keys, pool)


# ---------------------------------------------------------------------------
# Baselines ("data faithful").
# ---------------------------------------------------------------------------

def _pad_candidates(c: np.ndarray, k: int) -> np.ndarray:
    """Right-pad a (possibly empty) candidate row to length k.

    Degenerate features — constant columns, empty inputs — can yield
    zero candidates, where ``np.pad(..., mode='edge')`` raises; an
    all-zero row is harmless (binning collapses duplicate candidates
    into empty bins, so the feature is simply never split on).
    """
    c = np.asarray(c, dtype=np.float32)
    if len(c) >= k:
        return c[:k]
    if len(c) == 0:
        return np.zeros(k, dtype=np.float32)
    return np.pad(c, (0, k - len(c)), mode="edge")


def gk_quantile_candidates(x: np.ndarray, k: int) -> np.ndarray:
    """GK-summary candidates per feature (host-side; deliberately costly)."""
    x = np.asarray(x)
    out = np.empty((x.shape[1], k), dtype=np.float32)
    for j in range(x.shape[1]):
        out[j] = _pad_candidates(sketch.gk_candidates(x[:, j], k), k)
    return out


@partial(jax.jit, static_argnames=("k",))
def weighted_quantile_candidates(x: jax.Array, hess: jax.Array, k: int) -> jax.Array:
    """XGBoost weighted-quantile candidates; hessian-weighted."""
    return jax.vmap(lambda col: sketch.weighted_quantiles(col, hess, k))(x.T)


@partial(jax.jit, static_argnames=("k",))
def uniform_range_candidates(x: jax.Array, k: int) -> jax.Array:
    """CatBoost-style fixed bins: k evenly spaced points in [min, max]."""
    lo = jnp.min(x, axis=0)
    hi = jnp.max(x, axis=0)
    t = jnp.arange(1, k + 1) / (k + 1)
    return lo[:, None] + (hi - lo)[:, None] * t[None, :]


def exact_candidates(x: np.ndarray, k: int) -> np.ndarray:
    """All unique values, capped at k per feature (greedy exact baseline).

    With k >= number of unique values this reproduces the exact greedy
    algorithm; used for correctness tests on small data.
    """
    x = np.asarray(x)
    out = np.empty((x.shape[1], k), dtype=np.float32)
    for j in range(x.shape[1]):
        u = np.unique(x[:, j]).astype(np.float32)
        if len(u) >= k:
            idx = np.linspace(0, len(u) - 1, k).round().astype(int)
            out[j] = u[idx]
        else:
            out[j] = _pad_candidates(u, k)
    return out


# ---------------------------------------------------------------------------
# Unified front end.
# ---------------------------------------------------------------------------

def propose(strategy: Strategy, x, k: int, *, key: jax.Array | None = None,
            hess: jax.Array | None = None,
            traced: bool = False) -> jnp.ndarray:
    """Unified proposal dispatch (distributed version in distributed.py).

    One entry point for both host code and jit-traced code.  The
    :data:`TRACEABLE` strategies are pure jax ops and run either way.
    The host-only strategies ('gk_quantile', 'exact') are x-only numpy
    and raise ``ValueError`` when ``x`` is a ``jax.core.Tracer`` (the
    trainers propose them once, outside the trace) or when
    ``traced=True`` asks for the jit-safe path.  Traced, the proposal's
    device work carries the ``repro.proposal`` scope.

    Args:
      x: (n, f) feature matrix.
      k: candidates per feature.
      key: PRNG key (required for 'random').
      hess: (n,) hessian weights for 'weighted_quantile'; defaults to
        ones (the unweighted quantile sketch).

    Returns:
      (f, k) sorted float32 candidates.
    """
    with jax.named_scope("repro.proposal"):
        if strategy == "random":
            if key is None:
                raise ValueError("random proposal needs a PRNG key")
            return random_candidates(key, jnp.asarray(x), k)
        if strategy == "weighted_quantile":
            if hess is None:
                hess = jnp.ones(x.shape[0], dtype=jnp.float32)
            return weighted_quantile_candidates(jnp.asarray(x), hess, k)
        if strategy == "uniform_range":
            return uniform_range_candidates(jnp.asarray(x), k)
        if strategy not in ("gk_quantile", "exact"):
            raise ValueError(f"unknown strategy {strategy!r}")
        if traced or isinstance(x, jax.core.Tracer):
            raise ValueError(
                f"strategy {strategy!r} is host-only (numpy) and cannot "
                f"run under jit; propose outside the trace "
                f"(TRACEABLE={TRACEABLE})")
        if strategy == "gk_quantile":
            return jnp.asarray(gk_quantile_candidates(np.asarray(x), k))
        return jnp.asarray(exact_candidates(np.asarray(x), k))


def propose_traced(strategy: Strategy, x: jax.Array, k: int,
                   key: jax.Array, hess: jax.Array) -> jax.Array:
    """Deprecated: use ``propose(strategy, x, k, key=key, hess=hess)`` —
    the unified dispatcher serves traced and host code alike."""
    warnings.warn(
        "propose_traced is deprecated; use propose(strategy, x, k, "
        "key=key, hess=hess)", DeprecationWarning, stacklevel=2)
    return propose(strategy, x, k, key=key, hess=hess, traced=True)
