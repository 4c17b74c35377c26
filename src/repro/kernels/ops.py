"""Public jit'd wrappers for the Pallas kernels.

Each op picks between the Pallas kernel (TPU, or interpret=True for CPU
validation), the XLA 'packed' path and the pure-jnp oracle in ref.py.
Call sites in the library go through these wrappers only — never
through the kernels directly — so backend selection is a single switch.

The histogram hot path is fronted by a small kernel API: a
:class:`HistSpec` (static shape/backend/dtype policy, hashable so it can
ride through ``jax.jit`` static args) plus :func:`hist_levels`, the
level-batched entry point.  Library code builds one spec per fit and
passes it down instead of hand-threading ``n_nodes``/``nbins``/
``backend`` kwargs through every layer.
"""

from __future__ import annotations

import dataclasses
import warnings

import jax

from . import ref
from .hist import hist_levels_left_pallas, hist_levels_pallas
from .split_gain import split_gain_pallas
from .traverse import traverse_chunk_pallas
from .flash_attention import flash_attention_pallas


_BACKENDS = ("auto", "pallas", "interpret", "ref", "packed")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve(backend: str) -> str:
    """Resolve 'auto' to a concrete backend name.

    The scanned trainers call this once per fit, outside traced code, so
    the choice is a static constant of the compiled program.  'auto'
    picks 'packed' — the XLA path (complex64-scatter histogram, packed
    record gathers), bit-exact vs the 'ref' oracle — on every platform,
    TPU included: whether a Pallas kernel beats it on the chip is not
    measured yet.  ``backend='pallas'`` still asks for the kernels.
    """
    if backend not in _BACKENDS:
        raise ValueError(f"unknown backend {backend!r}")
    return "packed" if backend == "auto" else backend


@dataclasses.dataclass(frozen=True)
class HistSpec:
    """Static description of a histogram workload.

    Frozen + hashable so a spec is a valid ``jax.jit`` static argument:
    one spec per fit rides through the trainers and the tree builder
    instead of loose ``n_nodes``/``nbins``/``backend`` kwargs.

    Attributes:
      n_nodes: frontier nodes per level (the widest level this spec
        serves; shallower levels just leave high node ids empty).
      nbins: bins per feature (``n_candidates + 1``).
      n_levels: node-id assignments batched per :func:`hist_levels`
        call.  A tree builder growing ``max_depth`` levels uses
        ``n_levels = max_depth`` as its fit-wide spec and derives the
        per-call view with :meth:`with_levels`.
      backend: 'auto' | 'pallas' | 'interpret' | 'ref' | 'packed'.
      acc_dtype: accumulator dtype policy.  Only 'float32' is
        supported — it is the bit-exactness contract with ``hist_ref``
        — but it is part of the spec so a future bf16/f64 policy is an
        API no-op.
      subtract: histogram-subtraction policy.  ``False`` (the oracle
        path) scatters every row into the full frontier panel.  ``True``
        switches :func:`hist_levels` to CHILD MODE: ``node_per_level``
        carries child frontier ids in ``[0, 2 * n_nodes)``, only rows
        routed LEFT (even id) are scattered, keyed by the parent id
        ``child >> 1``, and the panel has ``n_nodes`` PARENT buckets —
        the grower reconstructs each right child as ``parent - left``
        from its cached previous-level panel.  Halves the logical
        scatter-update count and the panel entering any distributed
        ``lax.psum``; raw histogram values are no longer bit-exact vs
        direct accumulation (float subtraction re-associates), so the
        exactness contract moves up a level: trees must match the
        ``subtract=False`` oracles tree-for-tree on pinned workloads
        while raw histograms are tolerance-checked.
    """
    n_nodes: int
    nbins: int
    n_levels: int = 1
    backend: str = "auto"
    acc_dtype: str = "float32"
    subtract: bool = False

    def __post_init__(self):
        if self.n_nodes < 1:
            raise ValueError(f"n_nodes must be >= 1, got {self.n_nodes}")
        if self.nbins < 1:
            raise ValueError(f"nbins must be >= 1, got {self.nbins}")
        if self.n_levels < 1:
            raise ValueError(f"n_levels must be >= 1, got {self.n_levels}")
        if self.backend not in _BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}")
        if self.acc_dtype != "float32":
            raise ValueError(
                f"acc_dtype {self.acc_dtype!r} unsupported: 'float32' is "
                "the bit-exactness contract with hist_ref")

    def resolved(self) -> "HistSpec":
        """Spec with 'auto' pinned to a concrete backend (call once per
        fit, outside traced code)."""
        return dataclasses.replace(self, backend=resolve(self.backend))

    def with_levels(self, n_levels: int) -> "HistSpec":
        """Same spec serving a different number of batched levels."""
        return dataclasses.replace(self, n_levels=n_levels)

    def child_view(self) -> "HistSpec":
        """The half-width parent-keyed panel a subtraction grower
        scatters into: ``n_nodes`` halved (full frontier -> parent
        count), subtract mode pinned on."""
        return dataclasses.replace(self, n_nodes=max(self.n_nodes // 2, 1),
                                   subtract=True)


def hist_levels(bins, node_per_level, gh, spec: HistSpec):
    """Level-batched gradient/hessian histogram.

    One call accumulates the histograms of ``spec.n_levels`` node-id
    assignments of the same rows, keyed by (level, node, feature, bin):
    the packed CPU backend issues a single complex64 scatter across all
    levels, the Pallas backend a single launch whose grid covers the
    whole (level, node) frontier.

    Args:
      bins: (n, f) int32 bin ids in [0, spec.nbins).
      node_per_level: (spec.n_levels, n) int32 node ids per level;
        negative = row masked out at that level.  Direct mode
        (``spec.subtract=False``): ids in [0, spec.n_nodes).  Child mode
        (``spec.subtract=True``): CHILD frontier ids in
        [0, 2 * spec.n_nodes) — only even (LEFT-routed) ids contribute,
        keyed by the parent id ``child >> 1``.
      gh: (n, 2) float grad/hess panel.
      spec: static workload description (resolve 'auto' outside traced
        code via ``spec.resolved()`` when tracing matters).

    Returns:
      (spec.n_levels, spec.n_nodes, f, nbins, 2) float32 — bit-exact vs
      a per-level :func:`repro.kernels.ref.hist_ref` loop on the 'ref'
      and 'packed' backends (in child mode, vs
      :func:`repro.kernels.ref.hist_levels_left_ref`).
    """
    if node_per_level.ndim != 2 or node_per_level.shape[0] != spec.n_levels:
        raise ValueError(
            f"node_per_level must be (n_levels={spec.n_levels}, n), got "
            f"shape {node_per_level.shape}")
    backend = resolve(spec.backend)
    # named_scope: the hot-loop kernels show up as one annotated region
    # per op in profiler traces (jax.profiler / perfetto), keyed by
    # backend so packed-vs-pallas time is separable
    if spec.subtract:
        with jax.named_scope(f"repro.hist_levels_left[{backend}]"):
            if backend == "packed":
                return ref.hist_levels_left_packed(bins, node_per_level,
                                                   gh, n_nodes=spec.n_nodes,
                                                   nbins=spec.nbins)
            if backend == "ref":
                return ref.hist_levels_left_ref(bins, node_per_level, gh,
                                                n_nodes=spec.n_nodes,
                                                nbins=spec.nbins)
            return hist_levels_left_pallas(
                bins, node_per_level, gh, n_nodes=spec.n_nodes,
                nbins=spec.nbins, interpret=(backend == "interpret"))
    with jax.named_scope(f"repro.hist_levels[{backend}]"):
        if backend == "packed":
            return ref.hist_levels_packed(bins, node_per_level, gh,
                                          n_nodes=spec.n_nodes,
                                          nbins=spec.nbins)
        if backend == "ref":
            return ref.hist_levels_ref(bins, node_per_level, gh,
                                       n_nodes=spec.n_nodes,
                                       nbins=spec.nbins)
        return hist_levels_pallas(bins, node_per_level, gh,
                                  n_nodes=spec.n_nodes, nbins=spec.nbins,
                                  interpret=(backend == "interpret"))


@dataclasses.dataclass(frozen=True)
class TraverseSpec:
    """Static description of a batched forest-traversal workload.

    The inference-side sibling of :class:`HistSpec`: frozen + hashable,
    so one spec rides through ``jax.jit`` static args instead of loose
    chunk/backend kwargs.  ``repro.core.predict`` builds one per predict
    call and the backends underneath are swapped by this single switch.

    Attributes:
      tree_chunk: trees advanced together per level-synchronous chunk.
        Working memory of the engine is O(rows * tree_chunk); the chunk
        scan keeps the compile count O(1) in ``n_trees`` (forests are
        padded with passthrough zero-leaf trees up to a chunk multiple).
        Default 25 won the 500x6 CPU sweep in
        ``benchmarks/bench_predict.py``.
      binned: traverse on int bin ids (``bin <= split_bin``) instead of
        raw float thresholds (``x <= threshold``).  Exact vs the raw
        path on finite rows when the bin ids come from the training
        candidate grid — thresholds ARE bin boundaries; NaN rows bin to
        the LAST bin (so they follow the binned routing) while raw NaN
        compares False and routes RIGHT.
      backend: 'auto' | 'pallas' | 'interpret' | 'ref' | 'packed'; same
        resolution rule as histograms ('auto' -> packed).
    """
    tree_chunk: int = 25
    binned: bool = False
    backend: str = "auto"

    def __post_init__(self):
        if self.tree_chunk < 1:
            raise ValueError(
                f"tree_chunk must be >= 1, got {self.tree_chunk}")
        if self.backend not in _BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}")

    def resolved(self) -> "TraverseSpec":
        """Spec with 'auto' pinned to a concrete backend (call once per
        predict, outside traced code)."""
        return dataclasses.replace(self, backend=resolve(self.backend))


def traverse_chunk(values, feature, cmp, leaf, spec: TraverseSpec, *,
                   max_depth: int):
    """Level-synchronous descent of one chunk of stacked trees.

    All ``C = feature.shape[0]`` trees advance one depth level per step:
    a single fused gather (or masked-select on the Pallas path) fetches
    every (row, tree) node record, one comparison routes the whole
    (rows, trees) matrix a level down.

    Args:
      values: (n, f) raw float32 features, or int32 bin ids when
        ``spec.binned``.
      feature: (C, 2^max_depth - 1) int32 split features; -1 =
        passthrough.
      cmp: (C, 2^max_depth - 1) float32 thresholds (raw) or int32 split
        bins (binned).
      leaf: (C, 2^max_depth) float32 leaf values.
      spec: static workload description (resolve 'auto' outside traced
        code via ``spec.resolved()`` when tracing matters).

    Returns:
      (n, C) float32 PER-TREE leaf values — summation is left to the
      caller so the engine can accumulate in tree order, keeping the
      ensemble sum bit-identical to the sequential per-tree scan.  All
      backends agree bitwise (`ref` is the vmapped per-tree oracle).
    """
    backend = resolve(spec.backend)
    with jax.named_scope(f"repro.traverse[{backend}]"):
        if backend == "packed":
            return ref.traverse_chunk_packed(values, feature, cmp, leaf,
                                             max_depth=max_depth)
        if backend == "ref":
            return ref.traverse_chunk_ref(values, feature, cmp, leaf,
                                          max_depth=max_depth)
        return traverse_chunk_pallas(values, feature, cmp, leaf,
                                     max_depth=max_depth,
                                     interpret=(backend == "interpret"))


def hist(bins, node, gh, *, n_nodes: int, nbins: int,
         backend: str = "auto"):
    """Deprecated: single-level histogram shim.

    Build a :class:`HistSpec` and call
    ``hist_levels(bins, node[None], gh, spec)[0]`` instead (see README
    "Architecture" for the timeline).
    """
    warnings.warn(
        "ops.hist is deprecated; build a HistSpec and call "
        "hist_levels(bins, node[None], gh, spec)[0]",
        DeprecationWarning, stacklevel=2)
    spec = HistSpec(n_nodes=n_nodes, nbins=nbins, n_levels=1,
                    backend=backend)
    return hist_levels(bins, node[None], gh, spec)[0]


def split_gain(hist_arr, *, l2: float = 1.0, gamma: float = 0.0,
               min_child_weight: float = 1e-6, backend: str = "auto"):
    """Best (gain, bin) per (node, feature) from a histogram."""
    backend = resolve(backend)
    with jax.named_scope(f"repro.split_gain[{backend}]"):
        if backend in ("ref", "packed"):  # 'packed' only specialises hist
            return ref.split_gain_ref(hist_arr, l2=l2, gamma=gamma,
                                      min_child_weight=min_child_weight)
        return split_gain_pallas(hist_arr, l2=l2, gamma=gamma,
                                 min_child_weight=min_child_weight,
                                 interpret=(backend == "interpret"))


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    backend: str = "auto"):
    """Blockwise attention with GQA + optional sliding window."""
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "ref"
    if backend == "ref":
        return ref.attention_ref(q, k, v, causal=causal, window=window)
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  interpret=(backend == "interpret"))
