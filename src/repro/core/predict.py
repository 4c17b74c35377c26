"""Batched forest inference engine: level-synchronous traversal.

Training got its single-compile scan trainer and level-batched
histograms; this module gives *inference* the same treatment.  The old
predictor (``tree.forest_predict_raw``, now a deprecated shim) was a
sequential ``lax.scan`` over trees — ``n_trees`` dependent dispatch
chains of ``max_depth`` tiny gathers each, the opposite of how a
serving path should use the hardware.

Here the stacked :class:`repro.core.tree.Forest` — already a
struct-of-arrays ``(n_trees, 2^d - 1)`` heap — is traversed
**level-synchronously**: a chunk of ``C`` trees advances one depth
level per step, carrying an ``(n_rows, C)`` node-index matrix and doing
ONE fused gather + compare across all trees of the chunk
(:func:`repro.kernels.ops.traverse_chunk`; the `ref` backend is a vmap
over the per-tree descent, `packed` a complex64 record gather, `pallas`
a masked-select kernel).  A ``lax.scan`` over tree chunks keeps working
memory at O(rows x chunk) and the traversal compile count O(1) in
``n_trees`` — the chunk step's Python body traces once per compiled
predict regardless of forest size (``traverse_trace_count``, pinned by
tests/test_retrace.py), mirroring the trainer's round-step contract.

Exactness: within each chunk the per-tree leaf values are accumulated
onto the carry in tree order, so the ensemble sum is **bit-identical**
to the sequential per-tree scan it replaces (padding trees are
passthrough with leaf 0 — adding exact zeros).

The binned fast path (``binned=True``) traverses on int bin ids
(``bin <= split_bin``) instead of float thresholds.  Because recorded
thresholds ARE candidate-grid boundaries (``threshold =
candidates[feature, split_bin]``), binned routing is exact vs the raw
path on finite rows binned against the training grid.  NaN contract:
raw NaN compares False at every node and routes RIGHT; binned NaN sits
in the LAST bin (``bin_features``) and follows that bin's routing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops
from ..kernels.ops import TraverseSpec
from . import tree as tree_lib


# ---------------------------------------------------------------------------
# Traversal trace accounting — same convention as boosting.round_trace_count:
# the chunk step's Python body runs exactly once per trace of the
# surrounding jit, so this counter IS the lowering count of the
# traversal hot loop.  tests/test_retrace.py asserts it does not grow
# with n_trees.
# ---------------------------------------------------------------------------

# 25 won the 500x6 CPU chunk sweep (benchmarks/bench_predict.py): big
# enough to amortize the per-chunk scan step, small enough that the
# (rows, chunk) traversal temporaries stay cache-resident.
DEFAULT_TREE_CHUNK = 25

_traverse_traces = 0


def _bump_traverse_traces() -> None:
    global _traverse_traces
    _traverse_traces += 1


def traverse_trace_count() -> int:
    """How many times the traversal chunk step has been traced."""
    return _traverse_traces


def _forest_sum_impl(forest: tree_lib.Forest, values: jax.Array,
                     acc0: jax.Array, max_depth: int,
                     spec: TraverseSpec) -> jax.Array:
    """Chunk-scanned ensemble leaf-value sum (traced body, see module doc)."""
    t = forest.n_trees
    c = spec.tree_chunk
    pad = -t % c
    cmp = forest.split_bin if spec.binned else forest.threshold
    feat, leafv = forest.feature, forest.leaf_value
    if pad:
        # passthrough zero-leaf padding trees: every row descends the
        # all-left spine into leaf 0 and contributes an exact 0.0
        feat = jnp.pad(feat, ((0, pad), (0, 0)), constant_values=-1)
        cmp = jnp.pad(cmp, ((0, pad), (0, 0)),
                      constant_values=(2 ** 20 if spec.binned
                                       else np.inf))
        leafv = jnp.pad(leafv, ((0, pad), (0, 0)))
    n_chunks = (t + pad) // c
    chunks = (feat.reshape(n_chunks, c, -1),
              cmp.reshape(n_chunks, c, -1),
              leafv.reshape(n_chunks, c, -1))

    def chunk_step(acc, chunk):
        _bump_traverse_traces()
        fe, cm, lf = chunk
        vals = ops.traverse_chunk(values, fe, cm, lf, spec,
                                  max_depth=max_depth)   # (n, C)
        # accumulate in tree order: bit-identical to the per-tree scan
        for i in range(c):
            acc = acc + vals[:, i]
        return acc, None

    acc, _ = jax.lax.scan(chunk_step, acc0, chunks)
    return acc


@functools.partial(jax.jit, static_argnames=("max_depth", "spec"),
                   donate_argnums=(2,))
def _forest_sum(forest, values, acc0, *, max_depth: int,
                spec: TraverseSpec):
    return _forest_sum_impl(forest, values, acc0, max_depth, spec)


def margin(forest, values, base_score, learning_rate, *,
           max_depth: int, spec: TraverseSpec):
    """The single margin path for :meth:`GBDTModel.predict`: ``base +
    lr * ensemble_sum``, with the traversal jitted ONCE per (shapes,
    spec) — 'label' and 'proba' outputs route through this instead of
    rebuilding the ensemble sum per output mode.  The freshly-zeroed
    accumulator is donated into the chunk scan, which updates the carry
    buffer in place rather than double-buffering at the jit boundary.
    An empty ``(0, f)`` batch short-circuits to ``(0,)`` without
    tracing anything.

    The closing affine transform deliberately stays OUTSIDE the jit:
    fused, XLA contracts ``base + lr * sum`` into an FMA (1-ulp drift
    on CPU — ``optimization_barrier`` does not stop the LLVM-level
    contraction), whereas op-by-op it reproduces the historical eager
    ``base + lr * total`` bit-for-bit.  The two O(n) elementwise
    dispatches are noise next to the traversal.

    Under ``jax.profiler`` the traversal's dispatch is the host span
    ``repro.predict.dispatch`` and the affine ``repro.predict.affine``.
    """
    with jax.profiler.TraceAnnotation("repro.predict.dispatch"):
        values = jnp.asarray(values,
                             jnp.int32 if spec.binned else jnp.float32)
        n = values.shape[0]
        if n == 0:
            total = jnp.zeros((0,), jnp.float32)
        else:
            total = _forest_sum(forest, values,
                                jnp.zeros((n,), jnp.float32),
                                max_depth=max_depth, spec=spec)
    with jax.profiler.TraceAnnotation("repro.predict.affine"):
        return base_score + learning_rate * total


def forest_predict(forest: tree_lib.Forest, values: jax.Array, *,
                   max_depth: int, spec: TraverseSpec | None = None,
                   binned: bool = False, tree_chunk: int | None = None,
                   backend: str = "auto") -> jax.Array:
    """Unscaled ensemble sum over a stacked forest, batched across trees.

    Drop-in replacement for the deprecated per-tree-scan
    ``tree.forest_predict_raw`` (bit-identical output), with a binned
    mode the scan never had.  The caller applies learning rate and base
    score — or uses :func:`margin` / ``GBDTModel.predict`` which do.

    Args:
      values: (n, f) raw float32 features, or int bin ids (uint8/int32)
        when ``binned`` — e.g. from ``GBDTModel.bin_features``.
      spec: full :class:`TraverseSpec`; overrides the ``binned`` /
        ``tree_chunk`` / ``backend`` conveniences when given.

    Returns:
      (n,) float32 sum of per-tree leaf values; ``(0,)`` for an empty
      batch without tracing anything.
    """
    if spec is None:
        spec = TraverseSpec(tree_chunk=tree_chunk or DEFAULT_TREE_CHUNK,
                            binned=binned, backend=backend)
    spec = spec.resolved()            # pin 'auto' outside the trace
    values = jnp.asarray(values, jnp.int32 if spec.binned else jnp.float32)
    n = values.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.float32)
    acc0 = jnp.zeros((n,), jnp.float32)
    return _forest_sum(forest, values, acc0, max_depth=max_depth,
                       spec=spec)
