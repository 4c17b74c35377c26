"""Distributed GBDT training — the paper's Algorithm 1 on a JAX mesh.

Mapping from the paper's Rabit/AllReduce world to JAX:

  * worker        -> one slice of the ``data`` mesh axis (shard_map)
  * local sample at data read  -> random_candidates_local on the local shard
  * AllReduce(combine + resample) -> lax.all_gather over 'data' followed by
    a *shared-key* resample: every worker folds the same round key, so all
    workers compute the identical candidate set without a broadcast step.
  * histogram AllReduce -> lax.psum of the (node, feature, bin) panels
    inside the tree builder (the classic distributed-XGBoost pattern).
    With ``cfg.subtract`` on, only the HALF-width left-child panels are
    psum'd — each worker reconstructs the right children as
    ``parent - left`` from its (replicated) previous-level panel, so the
    per-level collective payload of tree growth halves (XGBoost's
    histogram-subtraction trick applied to the communication schedule).

The per-worker boosting loop is the same single-compile ``lax.scan``
round step as :func:`boosting.fit`: the round body (grad/hess ->
propose -> bin -> build_tree -> margin update, with its collectives)
is traced once and scanned over pre-split round keys, so the whole
n_trees-round training job is ONE compiled program per worker instead
of an unrolled O(n_trees) graph.  ``_worker_fit_reference`` keeps the
unrolled loop as the semantic oracle.

When ``n % n_workers != 0`` the driver pads the data with repeats of
the leading rows so every shard is equal-sized (static shapes), and
each worker weighs its rows by their validity (a row is valid when its
global index is under the true row count): pad rows have their
grad/hess zeroed every round and drop out of the base-score and loss
reductions (``n_global`` is the TRUE row count), so the padded fit
computes exactly the statistics of the unpadded data — no duplicated
rows ever enter a psum.

The quantile baseline is also provided in distributed form (local sketch ->
all_gather -> merge), so Table-2-style comparisons run under the same
collective schedule.  With ``cfg.telemetry`` on, the scanned worker also
emits a per-round :class:`repro.obs.TrainReport` (loss / norms psum'd to
their global values, so the report is replicated across workers) and the
driver fills in the estimated per-round collective payload.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import binning, boosting, proposal, sketch, tree as tree_lib
from .. import obs
from ..kernels import ops


def merge_quantile_gathered(gathered: jax.Array, k: int) -> jax.Array:
    """Distributed sketch merge: sort the union, take k evenly spaced.

    This is the classic quantile-summary merge (what XGBoost's AllReduce
    reducer does to per-worker GK summaries), specialised to equal-weight
    summaries.
    """
    w, f, kk = gathered.shape
    pool = jnp.sort(jnp.transpose(gathered, (1, 0, 2)).reshape(f, w * kk), axis=1)
    idx = jnp.floor((jnp.arange(1, k + 1) / (k + 1)) * (w * kk)).astype(jnp.int32)
    return pool[:, idx]


def _worker_propose(cfg: boosting.GBDTConfig, key_r, x_local, hess, w_local,
                    local_pool, axis: str):
    """One round's distributed proposal — traceable for every supported
    strategy, so it can live inside the scanned round step.  ``hess`` is
    already masked for pad rows; ``w_local`` is the validity weight (the
    unweighted-quantile limit uses it so pad rows carry no rank mass).
    The local work carries the ``repro.proposal`` scope, the gathers
    ``repro.collective``."""
    if cfg.strategy == "random":
        gathered = tree_lib.collective(lax.all_gather, local_pool,
                                       axis)                    # (W, f, b)
        with jax.named_scope("repro.proposal"):
            return proposal.resample_gathered(key_r, gathered,
                                              cfg.n_candidates)
    if cfg.strategy in ("weighted_quantile", "gk_quantile"):
        with jax.named_scope("repro.proposal"):
            local_c = proposal.weighted_quantile_candidates(
                x_local,
                hess if cfg.strategy == "weighted_quantile" else w_local,
                cfg.n_candidates)
        gathered = tree_lib.collective(lax.all_gather, local_c, axis)
        with jax.named_scope("repro.proposal"):
            return merge_quantile_gathered(gathered, cfg.n_candidates)
    if cfg.strategy == "uniform_range":
        with jax.named_scope("repro.proposal"):
            lo, hi = jnp.min(x_local, axis=0), jnp.max(x_local, axis=0)
        lo = tree_lib.collective(lax.pmin, lo, axis)
        hi = tree_lib.collective(lax.pmax, hi, axis)
        with jax.named_scope("repro.proposal"):
            t = jnp.arange(1, cfg.n_candidates + 1) / (cfg.n_candidates + 1)
            return lo[:, None] + (hi - lo)[:, None] * t[None, :]
    raise ValueError(f"strategy {cfg.strategy!r} has no distributed form")


def _masked_grad_hess(margin, y_local, w_local, objective: str):
    """Per-row loss stats with pad rows zeroed: a weight-0 row contributes
    nothing to histograms, leaf values, or any psum downstream."""
    with jax.named_scope("repro.leaf_update"):
        g, h = boosting.grad_hess(margin, y_local, objective)
        return g * w_local, h * w_local


def _valid_rows(x_local, axis: str, n_global: int) -> jax.Array:
    """This worker's per-row validity weight: 1 for the rows whose
    global index lies under the true row count ``n_global``, 0 for the
    pad rows after them."""
    per = x_local.shape[0]
    index = lax.axis_index(axis) * per + jnp.arange(per)
    return (index < n_global).astype(jnp.float32)


def _worker_base_and_pool(x_local, y_local, w_local, key, *, cfg, axis,
                          n_global):
    """Shared preamble: global base score + 'data read' candidate pool.

    ``n_global`` is the TRUE global row count; pad rows are excluded
    from the label sum by ``w_local``, so the base score is exactly the
    unpadded one.
    """
    ysum = tree_lib.collective(lax.psum, jnp.sum(y_local * w_local), axis)
    if cfg.objective == "logistic":
        p = jnp.clip(ysum / n_global, 1e-6, 1 - 1e-6)
        base = jnp.log(p / (1 - p))
    else:
        base = ysum / n_global

    # 'data read' stage: local candidate pool (Appendix 6.1).  Pad rows
    # may be sampled — they duplicate real leading rows, so the pool
    # still only contains observed feature values.
    widx = lax.axis_index(axis)
    with jax.named_scope("repro.proposal"):
        local_pool = proposal.random_candidates_local(
            jax.random.fold_in(key, widx), x_local, cfg.n_candidates)
    return base, local_pool


def _worker_fit(x_local, y_local, key, *,
                cfg: boosting.GBDTConfig, axis: str, n_global: int,
                spec: ops.HistSpec):
    """Traced per-worker trainer; runs identically on every 'data' slice.

    One lax.scan over rounds — the round step (with its all_gather /
    psum collectives) compiles once regardless of cfg.n_trees.  Returns
    ``(forest, candidates, base, margin, cover)`` plus a stacked
    :class:`repro.obs.TrainReport` when ``cfg.telemetry`` is on.
    """
    w_local = _valid_rows(x_local, axis, n_global)
    base, local_pool = _worker_base_and_pool(
        x_local, y_local, w_local, key, cfg=cfg, axis=axis,
        n_global=n_global)
    margin0 = jnp.full((x_local.shape[0],), base, jnp.float32)
    keys = boosting.round_keys(key, cfg.n_trees, offset=10_000)
    psum = functools.partial(tree_lib.collective, lax.psum, axis_name=axis)

    def grow(margin, bins, cands):
        g, h = _masked_grad_hess(margin, y_local, w_local, cfg.objective)
        with jax.named_scope("repro.leaf_update"):
            gh = jnp.stack([g, h], 1)
        built = tree_lib.build_tree(
            bins, gh, cands,
            max_depth=cfg.max_depth, l2=cfg.l2,
            gamma=cfg.gamma, min_child_weight=cfg.min_child_weight,
            spec=spec, axis_name=axis, return_leaf_nodes=True,
            return_cover=True, return_stats=cfg.telemetry)
        t, node, cover = built[:3]
        # growth already routed every local row to its leaf — gather the
        # leaf values directly instead of re-descending the tree
        with jax.named_scope("repro.leaf_update"):
            margin = margin + cfg.learning_rate * t.leaf_value[node]
        rep = None
        if cfg.telemetry:
            # loss / norms psum to their global (pad-free) values, so
            # the report rows are replicated across workers
            rep = obs.round_report(margin=margin, y=y_local, g=g, h=h,
                                   objective=cfg.objective, stats=built[3],
                                   n_global=n_global, weight=w_local,
                                   psum=psum)
        return margin, t, cover, rep

    if cfg.repropose_each_round:
        def round_step(margin, key_r):
            boosting._bump_round_traces()
            _, h = _masked_grad_hess(margin, y_local, w_local,
                                     cfg.objective)
            c = _worker_propose(cfg, key_r, x_local, h, w_local,
                                local_pool, axis)
            bins = binning.bin_features(x_local, c)
            margin, t, cover, rep = grow(margin, bins, c)
            return margin, (t, c, cover, rep)

        margin, (trees, cands, cover, report) = lax.scan(round_step,
                                                         margin0, keys)
        out = (tree_lib.Forest(*trees), cands, base, margin, cover)
        return out + ((report,) if cfg.telemetry else ())

    _, h0 = _masked_grad_hess(margin0, y_local, w_local, cfg.objective)
    c0 = _worker_propose(cfg, keys[0], x_local, h0, w_local, local_pool,
                         axis)
    bins0 = binning.bin_features(x_local, c0)

    def round_step(margin, _key_r):
        boosting._bump_round_traces()
        margin, t, cover, rep = grow(margin, bins0, c0)
        return margin, (t, cover, rep)

    margin, (trees, cover, report) = lax.scan(round_step, margin0, keys)
    out = (tree_lib.Forest(*trees), c0[None], base, margin, cover)
    return out + ((report,) if cfg.telemetry else ())


def _worker_fit_reference(x_local, y_local, key, *,
                          cfg: boosting.GBDTConfig, axis: str,
                          n_global: int, spec: ops.HistSpec):
    """The original unrolled per-worker loop (O(n_trees) traced graph).
    Kept as the semantic oracle for the scanned worker (no telemetry)."""
    w_local = _valid_rows(x_local, axis, n_global)
    base, local_pool = _worker_base_and_pool(
        x_local, y_local, w_local, key, cfg=cfg, axis=axis,
        n_global=n_global)
    margin = jnp.full((x_local.shape[0],), base, jnp.float32)
    trees, cands, covers = [], [], []
    bins = None

    for r in range(cfg.n_trees):
        g, h = _masked_grad_hess(margin, y_local, w_local, cfg.objective)
        if cfg.repropose_each_round or r == 0:
            c = _worker_propose(cfg, jax.random.fold_in(key, 10_000 + r),
                                x_local, h, w_local, local_pool, axis)
            bins = binning.bin_features(x_local, c)
            cands.append(c)
        t, cover = tree_lib.build_tree(
            bins, jnp.stack([g, h], 1), cands[-1],
            max_depth=cfg.max_depth, l2=cfg.l2,
            gamma=cfg.gamma, min_child_weight=cfg.min_child_weight,
            spec=spec, axis_name=axis, return_cover=True)
        trees.append(t)
        covers.append(cover)
        margin = margin + cfg.learning_rate * tree_lib.predict_binned(
            t, bins, max_depth=cfg.max_depth)

    return (tree_lib.forest_from_trees(trees), jnp.stack(cands), base,
            margin, jnp.stack(covers))


# Sharded programs built so far (all meshes and configs): a repeat
# ``fit_distributed`` call with the same config, mesh, axis and row
# count finds its program in ``sharded_fit``'s cache and adds none.
_programs_built = 0


def sharded_program_count() -> int:
    """How many row-sharded training programs have been built."""
    return _programs_built


@functools.lru_cache(maxsize=64)
def sharded_fit(cfg: boosting.GBDTConfig, mesh: Mesh, *, axis: str,
                n_global: int, reference: bool = False):
    """The jitted row-sharded training program of :func:`fit_distributed`.

    Takes ``(x, y, key)`` with rows sharded over ``axis`` (the row
    count divisible by the worker count; the rows from ``n_global`` on
    are pad) and returns ``(forest, candidates, base, margin, cover)``,
    plus the stacked report when ``cfg.telemetry`` is on and
    ``reference`` is off.

    Cached on its arguments (the mesh fixes the platform), so a repeat
    call returns the same jitted program and JAX's own cache then
    serves it without tracing or compiling again.
    """
    global _programs_built
    _programs_built += 1
    worker = _worker_fit_reference if reference else _worker_fit
    telemetry = cfg.telemetry and not reference
    spec = cfg.hist_spec().resolved(mesh.devices.flat[0].platform)
    fn = functools.partial(worker, cfg=cfg, axis=axis, n_global=n_global,
                           spec=spec)
    return jax.jit(jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(axis, None), P(axis), P()),
        out_specs=(P(), P(), P(), P(axis), P())
        + ((P(),) if telemetry else ()),
        check_vma=False,
    ))


def _stage(a, sharding: NamedSharding, pad: int) -> jax.Array:
    """``a`` as f32 with its first ``pad`` rows repeated at the end, laid
    out by ``sharding``.  A ``jax.Array`` already laid out so is used as
    it is; anything else is padded on the host and only each device's
    shard is transferred, so the full array never lands on one device."""
    if (not pad and isinstance(a, jax.Array) and a.dtype == jnp.float32
            and a.sharding.is_equivalent_to(sharding, a.ndim)):
        return a
    a = np.asarray(a, np.float32)
    if pad:
        a = np.concatenate([a, a[:pad]], 0)
    return jax.device_put(a, sharding)


def fit_distributed(x, y, cfg: boosting.GBDTConfig, mesh: Mesh,
                    key: jax.Array | None = None,
                    axis: str = "data",
                    reference: bool = False) -> boosting.GBDTModel:
    """Train a GBDT with rows sharded over ``axis`` of ``mesh``.

    Semantics match :func:`boosting.fit` up to the candidate sets (each
    worker samples locally, then the union is resampled — Algorithm 1).
    When ``n`` does not divide the worker count the data is padded with
    repeats of the leading rows for static shard shapes, but a per-row
    validity weight zeroes the pad rows' grad/hess and label mass, so
    base score, histograms, and leaf values are exactly those of the
    unpadded data.  ``x`` and ``y`` already row-sharded over ``axis`` (and
    needing no pad) are used in place; anything else goes through host
    memory.  ``reference=True`` runs the unrolled oracle loop instead of
    the scanned trainer (tests only).  The model's ``cover`` holds each
    node's hessian sum over every worker's rows.

    Under ``jax.profiler`` the call is the host span ``repro.fit``; its
    set-up ``repro.fit.prepare`` holds ``repro.fit.stage`` (padding and
    the inputs' layout) and ``repro.fit.program``
    (finding or building the program).  On the device the collectives
    carry the ``repro.collective`` scope.
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    with jax.profiler.TraceAnnotation("repro.fit"):
        with jax.profiler.TraceAnnotation("repro.fit.prepare"):
            with jax.profiler.TraceAnnotation("repro.fit.stage"):
                n_true = x.shape[0]
                nw = mesh.shape[axis]
                # repeat leading rows so shard shapes stay static; their
                # weight is zero, so they never reach a psum'd statistic
                pad = -n_true % nw
                xs = _stage(x, NamedSharding(mesh, P(axis, None)), pad)
                ys = _stage(y, NamedSharding(mesh, P(axis)), pad)
            with jax.profiler.TraceAnnotation("repro.fit.program"):
                program = sharded_fit(cfg, mesh, axis=axis,
                                      n_global=n_true, reference=reference)

        out = program(xs, ys, key)
        forest, cands, base, _margin, cover = out[:5]

        report = None
        if cfg.telemetry and not reference:
            report = out[5]
            ag, ps = obs.collective_bytes_per_round(cfg, xs.shape[1], nw)
            report = report._replace(all_gather_bytes=jnp.asarray(ag),
                                     psum_bytes=jnp.asarray(ps))
        base = float(base)                  # waits for the program
    return boosting.GBDTModel(cfg, forest, base, cands, report=report,
                              cover=cover)
