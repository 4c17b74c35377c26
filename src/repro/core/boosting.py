"""Gradient-boosted decision trees over the binned tree builder.

Single-host trainer (the distributed shard_map trainer lives in
distributed.py and reuses the same scanned round step).  Mirrors the
paper's experimental setup: proposal strategy is pluggable per-round
('random' = the paper; 'gk_quantile' / 'weighted_quantile' /
'uniform_range' = the data-faithful baselines; 'exact' = greedy).

The hot loop is a single-compile ``lax.scan`` over boosting rounds: one
round step (grad/hess -> propose -> bin -> build_tree -> margin update)
is traced ONCE and scanned over pre-split per-round PRNG keys, with the
margin buffer donated into the jit so XLA updates it in place.  Trees
accumulate as a static-shaped struct-of-arrays :class:`tree.Forest`
(the scan's stacked per-round output), so trace+compile cost is O(1) in
``n_trees`` and no host round-trip happens between rounds.  The
jit-able proposal strategies (random / weighted_quantile /
uniform_range) re-propose natively inside the scan; the host-side
strategies (gk_quantile / exact) are x-only — identical candidates
every round — and are proposed once outside it.

:func:`fit_reference` keeps the original per-round Python loop as the
semantic oracle; tests assert the scanned trainer reproduces it
tree-for-tree on a fixed seed.
"""

from __future__ import annotations

import dataclasses
import functools
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from . import binning, predict as predict_lib, proposal, tree as tree_lib
from ..kernels.ops import HistSpec, TraverseSpec
from ..obs import TrainReport, round_report


@dataclasses.dataclass(frozen=True)
class GBDTConfig:
    n_trees: int = 20
    max_depth: int = 6
    learning_rate: float = 0.3
    l2: float = 1.0
    gamma: float = 0.0
    min_child_weight: float = 1.0
    n_candidates: int = 32              # k; nbins = k + 1
    strategy: proposal.Strategy = "random"
    objective: str = "logistic"         # 'logistic' | 'mse'
    repropose_each_round: bool = True   # paper re-proposes per iteration
    backend: str = "auto"               # kernel backend
    telemetry: bool = False             # per-round TrainReport (repro.obs)
    subtract: bool = False              # histogram-subtraction growth:
    #                                     scatter LEFT children only,
    #                                     right = parent - left (halves
    #                                     scatter updates + psum bytes;
    #                                     trees pinned tree-for-tree vs
    #                                     the subtract=False oracles)

    @property
    def nbins(self) -> int:
        return self.n_candidates + 1

    def hist_spec(self) -> HistSpec:
        """The fit-wide histogram workload this config implies: frontier
        width 2^(max_depth-1) nodes, one batched level per tree depth."""
        return HistSpec(n_nodes=2 ** max(self.max_depth - 1, 0),
                        nbins=self.nbins,
                        n_levels=max(self.max_depth, 1),
                        backend=self.backend,
                        subtract=self.subtract)


@dataclasses.dataclass
class GBDTModel:
    config: GBDTConfig
    forest: tree_lib.Forest             # stacked (n_trees, ...) ensemble
    base_score: float
    candidates: jax.Array               # (rounds_proposed, f, k): n_trees
    #                                     when re-proposing a traceable
    #                                     strategy each round, else 1
    #                                     (fixed grid — host-side
    #                                     strategies are x-only).  Both
    #                                     trainers follow this convention.
    proposal_seconds: float = 0.0       # host-side strategies only; the
    #                                     scanned strategies propose
    #                                     inside the compiled loop
    fit_seconds: float = 0.0
    report: TrainReport | None = None   # per-round telemetry when
    #                                     config.telemetry is on
    cover: jax.Array | None = None      # (n_trees, 2^(d+1) - 1) hessian
    #                                     sum of every node, inner nodes
    #                                     in heap order then the leaves
    #                                     (XGBoost's cover); filled by
    #                                     fit_distributed, whose checks
    #                                     read the cross-chip sums

    @property
    def trees(self) -> list[tree_lib.Tree]:
        """Per-tree views (back-compat with the list-of-trees API)."""
        return tree_lib.forest_trees(self.forest)

    @property
    def bin_edges(self) -> jax.Array | None:
        """The (f, k) training candidate grid when it is shared by every
        tree (host-side strategies, or ``repropose_each_round=False``);
        None when the trainer re-proposed a fresh grid per round — the
        binned fast path needs one grid that reproduces every recorded
        threshold, and per-tree grids have no such thing."""
        if self.candidates.shape[0] == 1:
            return self.candidates[0]
        return None

    def bin_features(self, x: jax.Array) -> jax.Array:
        """Bin raw rows against the training grid for binned predict.

        Returns (n, f) uint8 bin ids in [0, k] (int32 when nbins > 256);
        NaN lands in the last bin.  Feed the result to
        ``predict(..., binned=True)`` — binning once and serving many
        batches skips the per-call float threshold gathers.
        """
        edges = self.bin_edges
        if edges is None:
            raise ValueError(
                "binned predict needs a fixed candidate grid; this model "
                "re-proposed candidates per round (strategy="
                f"{self.config.strategy!r}, repropose_each_round=True). "
                "Train with repropose_each_round=False or a host-side "
                "strategy to serve binned.")
        bins = binning.bin_features(jnp.asarray(x, jnp.float32), edges)
        if self.config.nbins <= 256:
            return bins.astype(jnp.uint8)
        return bins

    def predict(self, x: jax.Array, *, output: str = "label",
                binned: bool = False, backend: str | None = None,
                tree_chunk: int | None = None) -> jax.Array:
        """Evaluate the ensemble (batched level-synchronous engine).

        Args:
          output: 'label' — hard 0/1 for logistic, the predicted value
            for mse (the default, and what metrics consume); 'margin' —
            the raw additive score; 'proba' — sigmoid of the margin
            (logistic only).
          binned: traverse on integer bin ids instead of float
            thresholds (exact vs raw on finite rows, NaN goes last-bin
            instead of right).  ``x`` may be raw floats (binned here
            against :attr:`bin_edges`) or already-binned ids from
            :meth:`bin_features`.
          backend: traversal backend override ('auto'/'pallas'/
            'interpret'/'ref'/'packed'); default auto-selects.
          tree_chunk: trees per traversal chunk (compile-time constant
            of the engine's scan step).

        All output modes route through ONE jitted ensemble-sum
        executable per (shapes, spec) — picking 'proba' after 'label'
        does not recompile or re-traverse differently.

        Under ``jax.profiler`` the call is the host span
        ``repro.predict``, with children ``repro.predict.input``
        (conversion, transfer, binning), ``repro.predict.spec``, and
        :func:`repro.core.predict.margin`'s ``repro.predict.dispatch``
        and ``repro.predict.affine``.
        """
        with jax.profiler.TraceAnnotation("repro.predict"):
            return self._predict(x, output, binned, backend, tree_chunk)

    def _predict(self, x, output, binned, backend, tree_chunk):
        with jax.profiler.TraceAnnotation("repro.predict.input"):
            x = jnp.asarray(x)
            if binned and not jnp.issubdtype(x.dtype, jnp.integer):
                x = self.bin_features(x)
            elif binned:
                if self.bin_edges is None:
                    raise ValueError(
                        "binned predict needs a fixed candidate grid "
                        "(see GBDTModel.bin_features)")
            else:
                x = x.astype(jnp.float32)
        with jax.profiler.TraceAnnotation("repro.predict.spec"):
            spec = TraverseSpec(
                tree_chunk=tree_chunk or predict_lib.DEFAULT_TREE_CHUNK,
                binned=binned,
                backend=backend or self.config.backend).resolved()
        m = predict_lib.margin(
            self.forest, x, self.base_score, self.config.learning_rate,
            max_depth=self.config.max_depth, spec=spec)
        if output == "margin":
            return m
        if self.config.objective != "logistic":
            if output == "proba":
                raise ValueError(
                    f"output='proba' needs a logistic objective, got "
                    f"{self.config.objective!r}")
            return m                       # 'label' for regression = value
        p = jax.nn.sigmoid(m)
        if output == "proba":
            return p
        if output == "label":
            return (p > 0.5).astype(jnp.float32)
        raise ValueError(f"unknown output {output!r}")

    def predict_margin(self, x: jax.Array) -> jax.Array:
        """Deprecated: use ``predict(x, output='margin')``."""
        warnings.warn(
            "GBDTModel.predict_margin is deprecated; use "
            "predict(x, output='margin')", DeprecationWarning, stacklevel=2)
        return self.predict(x, output="margin")


def grad_hess(margin: jax.Array, y: jax.Array, objective: str):
    """First/second order stats of the loss wrt the margin."""
    if objective == "logistic":
        p = jax.nn.sigmoid(margin)
        return (p - y).astype(jnp.float32), (p * (1 - p)).astype(jnp.float32)
    if objective == "mse":
        return (margin - y).astype(jnp.float32), jnp.ones_like(margin)
    raise ValueError(f"unknown objective {objective!r}")


def _base_score(y: jax.Array, objective: str) -> float:
    if objective == "logistic":
        p = float(jnp.clip(jnp.mean(y), 1e-6, 1 - 1e-6))
        return float(np.log(p / (1 - p)))
    return float(jnp.mean(y))


def round_keys(key: jax.Array, n_trees: int, offset: int = 0) -> jax.Array:
    """Pre-split per-round keys, identical to fold_in(key, offset + r)."""
    return jax.vmap(lambda r: jax.random.fold_in(key, r))(
        offset + jnp.arange(n_trees))


# ---------------------------------------------------------------------------
# Round-step trace accounting.
#
# The Python body of the scanned round step runs exactly once per trace
# of the surrounding jit, so a module-level counter bumped there IS the
# lowering count of the hot loop.  tests/test_retrace.py asserts it does
# not grow with n_trees.
# ---------------------------------------------------------------------------

_round_traces = 0


def _bump_round_traces() -> None:
    global _round_traces
    _round_traces += 1


def round_trace_count() -> int:
    """How many times a boosting round step has been traced (all trainers)."""
    return _round_traces


@functools.partial(jax.jit,
                   static_argnames=("cfg", "spec"),
                   donate_argnums=(3,))
def _fit_scanned(x, y, keys, margin0, fixed_c, *, cfg: GBDTConfig,
                 spec: HistSpec):
    """Single-compile boosting: lax.scan of one round step over rounds.

    margin0 is donated — the round runner's carry buffer is updated in
    place rather than double-buffered at the jit boundary.  ``spec`` is
    the fit-wide :class:`HistSpec` (already resolved), the one static
    handle the tree builder needs instead of loose kernel kwargs.

    Returns (forest, candidates, margin, report); candidates has a
    leading axis of n_trees when re-proposing inside the scan, else 1.
    ``report`` is a stacked :class:`repro.obs.TrainReport` when
    ``cfg.telemetry`` is on, else None — the per-round rows ride the
    scan as extra outputs, so the telemetry-off graph (and the one
    round-step trace) is unchanged.
    """
    def grow(margin, bins, cands):
        with jax.named_scope("repro.leaf_update"):
            g, h = grad_hess(margin, y, cfg.objective)
            gh = jnp.stack([g, h], 1)
        built = tree_lib.build_tree(
            bins, gh, cands,
            max_depth=cfg.max_depth, l2=cfg.l2,
            gamma=cfg.gamma, min_child_weight=cfg.min_child_weight,
            spec=spec, return_leaf_nodes=True,
            return_stats=cfg.telemetry)
        t, node = built[0], built[1]
        # growth already routed every row to its leaf — gather the leaf
        # values directly instead of re-descending with predict_binned
        with jax.named_scope("repro.leaf_update"):
            margin = margin + cfg.learning_rate * t.leaf_value[node]
        rep = None
        if cfg.telemetry:
            rep = round_report(margin=margin, y=y, g=g, h=h,
                               objective=cfg.objective, stats=built[2])
        return margin, t, rep

    in_scan = cfg.repropose_each_round and fixed_c is None
    if in_scan:
        def round_step(margin, key_r):
            _bump_round_traces()
            with jax.named_scope("repro.leaf_update"):
                _, h = grad_hess(margin, y, cfg.objective)
            c = proposal.propose(cfg.strategy, x, cfg.n_candidates,
                                 key=key_r, hess=h)
            bins = binning.bin_features(x, c)
            margin, t, rep = grow(margin, bins, c)
            return margin, (t, c, rep)

        margin, (trees, cands, report) = jax.lax.scan(
            round_step, margin0, keys)
        return tree_lib.Forest(*trees), cands, margin, report

    # fixed candidate grid: host-side strategies (candidates passed in)
    # or repropose_each_round=False (proposed once from round-0 stats)
    if fixed_c is None:
        with jax.named_scope("repro.leaf_update"):
            _, h0 = grad_hess(margin0, y, cfg.objective)
        fixed_c = proposal.propose(cfg.strategy, x, cfg.n_candidates,
                                   key=keys[0], hess=h0)
    bins = binning.bin_features(x, fixed_c)

    def round_step(margin, _key_r):
        _bump_round_traces()
        margin, t, rep = grow(margin, bins, fixed_c)
        return margin, (t, rep)

    margin, (trees, report) = jax.lax.scan(round_step, margin0, keys)
    return tree_lib.Forest(*trees), fixed_c[None], margin, report


def _platform(x: jax.Array) -> str:
    """The platform a fit on ``x`` runs on: that of x's device."""
    return next(iter(x.devices())).platform


def fit(x: jax.Array, y: jax.Array, cfg: GBDTConfig,
        key: jax.Array | None = None) -> GBDTModel:
    """Train a GBDT model on a single host (single-compile scan trainer).

    Args:
      x: (n, f) float32 features.
      y: (n,) labels ({0,1} for logistic, real for mse).

    Reproduces :func:`fit_reference` tree-for-tree on the same key.
    Under ``jax.profiler`` the call is the host span ``repro.fit``, its
    set-up before the jitted scan ``repro.fit.prepare``.
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    with jax.profiler.TraceAnnotation("repro.fit"):
        with jax.profiler.TraceAnnotation("repro.fit.prepare"):
            x = jnp.asarray(x, jnp.float32)
            y = jnp.asarray(y, jnp.float32)
            t_fit0 = time.perf_counter()
            base = _base_score(y, cfg.objective)
            margin0 = jnp.full((x.shape[0],), base, jnp.float32)
            keys = round_keys(key, cfg.n_trees)
            # pin 'auto' outside jit, for the device x lives on
            spec = cfg.hist_spec().resolved(_platform(x))

            fixed_c = None
            proposal_s = 0.0
            if cfg.strategy not in proposal.TRACEABLE:
                # host-side strategies are x-only: one proposal serves
                # all rounds
                t0 = time.perf_counter()
                fixed_c = jax.block_until_ready(jnp.asarray(
                    proposal.propose(cfg.strategy, x, cfg.n_candidates,
                                     key=jax.random.fold_in(key, 0))))
                proposal_s = time.perf_counter() - t0

        forest, cands, margin, report = _fit_scanned(
            x, y, keys, margin0, fixed_c, cfg=cfg, spec=spec)
        jax.block_until_ready(margin)
    return GBDTModel(cfg, forest, base, cands,
                     proposal_seconds=proposal_s,
                     fit_seconds=time.perf_counter() - t_fit0,
                     report=report)


def fit_reference(x: jax.Array, y: jax.Array, cfg: GBDTConfig,
                  key: jax.Array | None = None) -> GBDTModel:
    """The original per-round Python loop (one dispatch + host sync per
    round, O(n_trees) trace/compile).  Kept as the semantic oracle for
    the scanned trainer and as the bench baseline — not the fast path.
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    t_fit0 = time.perf_counter()

    base = _base_score(y, cfg.objective)
    margin = jnp.full((x.shape[0],), base, jnp.float32)
    spec = cfg.hist_spec().resolved(_platform(x))

    trees: list[tree_lib.Tree] = []
    cands: list[jax.Array] = []
    proposal_s = 0.0
    bins = None
    # host-side strategies are x-only (identical candidates every round),
    # so propose once: model.candidates is (1, f, k), matching fit()
    repropose = (cfg.repropose_each_round
                 and cfg.strategy in proposal.TRACEABLE)

    for r in range(cfg.n_trees):
        g, h = grad_hess(margin, y, cfg.objective)
        if repropose or r == 0:
            t0 = time.perf_counter()
            c = proposal.propose(cfg.strategy, x, cfg.n_candidates,
                                 key=jax.random.fold_in(key, r), hess=h)
            c = jax.block_until_ready(c)
            proposal_s += time.perf_counter() - t0
            bins = binning.bin_features(x, c)
            cands.append(c)
        t = tree_lib.build_tree(
            bins, jnp.stack([g, h], 1), cands[-1],
            max_depth=cfg.max_depth, l2=cfg.l2,
            gamma=cfg.gamma, min_child_weight=cfg.min_child_weight,
            spec=spec)
        trees.append(t)
        margin = margin + cfg.learning_rate * tree_lib.predict_binned(
            t, bins, max_depth=cfg.max_depth)

    margin = jax.block_until_ready(margin)
    return GBDTModel(cfg, tree_lib.forest_from_trees(trees), base,
                     jnp.stack(cands),
                     proposal_seconds=proposal_s,
                     fit_seconds=time.perf_counter() - t_fit0)


def accuracy(model: GBDTModel, x, y) -> float:
    if model.config.objective != "logistic":
        raise ValueError("accuracy is for classification")
    lbl = model.predict(x, output="label")
    return float(jnp.mean((lbl > 0.5) == (jnp.asarray(y) > 0.5)))


def mape(model: GBDTModel, x, y) -> float:
    p = model.predict(x, output="label")   # regression 'label' = value
    y = jnp.asarray(y, jnp.float32)
    return float(jnp.mean(jnp.abs((p - y) / jnp.where(y == 0, 1.0, y)))) * 100
