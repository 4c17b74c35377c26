"""Bit-exactness of the level-batched histogram behind the HistSpec API.

`ops.hist_levels` must reproduce a naive per-level `hist_ref` loop
EXACTLY (same f32 bits) on the 'ref' and 'packed' backends — the packed
complex64 scatter adds each bucket's rows in the same order, so no
re-association happens — and to tight tolerance on the Pallas interpret
path (one-hot matmul re-associates the row sum).  Shapes deliberately
include non-power-of-2 node counts, nbins=1, single-sample leaves, and
masked (-1) rows.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tree as tree_lib
from repro.kernels import ops, ref
from repro.kernels.ops import HistSpec


# (n, f, nbins, n_nodes, n_levels)
SHAPES = [
    (257, 3, 8, 3, 2),      # non-power-of-2 nodes, odd n
    (64, 2, 1, 4, 3),       # nbins=1: every row in bin 0
    (33, 5, 17, 32, 6),     # n_nodes ~ n: single-sample/empty leaves
    (1024, 7, 33, 16, 1),   # single level through the batched path
    (500, 4, 16, 5, 4),
]


def _case(n, f, nbins, n_nodes, L, seed=0, masked=True):
    rng = np.random.default_rng(seed)
    bins = jnp.asarray(rng.integers(0, nbins, (n, f)), jnp.int32)
    lo = -1 if masked else 0            # -1 rows must drop out entirely
    node = jnp.asarray(rng.integers(lo, n_nodes, (L, n)), jnp.int32)
    gh = jnp.asarray(rng.normal(size=(n, 2)), jnp.float32)
    return bins, node, gh


def _oracle(bins, node, gh, n_nodes, nbins):
    return jnp.stack([
        ref.hist_ref(bins, node[l], gh, n_nodes=n_nodes, nbins=nbins)
        for l in range(node.shape[0])])


@pytest.mark.parametrize("n,f,nbins,n_nodes,L", SHAPES)
@pytest.mark.parametrize("backend", ["ref", "packed"])
def test_hist_levels_bit_exact(n, f, nbins, n_nodes, L, backend):
    bins, node, gh = _case(n, f, nbins, n_nodes, L)
    spec = HistSpec(n_nodes=n_nodes, nbins=nbins, n_levels=L,
                    backend=backend)
    out = ops.hist_levels(bins, node, gh, spec)
    want = _oracle(bins, node, gh, n_nodes, nbins)
    assert out.shape == (L, n_nodes, f, nbins, 2)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("n,f,nbins,n_nodes,L", SHAPES)
def test_hist_levels_pallas_interpret(n, f, nbins, n_nodes, L):
    bins, node, gh = _case(n, f, nbins, n_nodes, L, seed=1)
    spec = HistSpec(n_nodes=n_nodes, nbins=nbins, n_levels=L,
                    backend="interpret")
    out = ops.hist_levels(bins, node, gh, spec)
    want = _oracle(bins, node, gh, n_nodes, nbins)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-6, atol=1e-5)


def test_hist_single_level_delegates():
    """ops.hist is the L=1 view of hist_levels (deprecated shim kept
    working, but it must warn)."""
    bins, node, gh = _case(300, 4, 9, 6, 1, seed=2)
    with pytest.warns(DeprecationWarning, match="ops.hist is deprecated"):
        one = ops.hist(bins, node[0], gh, n_nodes=6, nbins=9,
                       backend="packed")
    spec = HistSpec(n_nodes=6, nbins=9, n_levels=1, backend="packed")
    batched = ops.hist_levels(bins, node, gh, spec)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(batched[0]))


def test_masked_rows_drop_out():
    """A -1 node id contributes nothing at that level, but the same row
    still counts at levels where it has a valid id."""
    bins, _, gh = _case(100, 2, 4, 3, 1, seed=3)
    rng = np.random.default_rng(3)
    node_ok = jnp.asarray(rng.integers(0, 3, (100,)), jnp.int32)
    node = jnp.stack([node_ok, node_ok.at[:50].set(-1)])
    spec = HistSpec(n_nodes=3, nbins=4, n_levels=2, backend="packed")
    out = ops.hist_levels(bins, node, gh, spec)
    np.testing.assert_array_equal(
        np.asarray(out[0]),
        np.asarray(ref.hist_ref(bins, node_ok, gh, n_nodes=3, nbins=4)))
    np.testing.assert_array_equal(
        np.asarray(out[1]),
        np.asarray(ref.hist_ref(bins, node[1], gh, n_nodes=3, nbins=4)))
    # level 1 lost exactly the first 50 rows' mass
    tot0 = float(out[0].sum())
    tot1 = float(out[1].sum())
    assert tot0 != tot1


# ---------------------------------------------------------------------------
# Child mode (subtraction growth): spec.subtract=True scatters only the
# LEFT-routed rows, keyed by parent id, into a half-width panel.  The
# grower reconstructs right children as parent - left; the invariant
# that makes that sound is parent == left + right per (feature, bin).
# ---------------------------------------------------------------------------

def _child_case(n, f, nbins, n_parents, L, seed=0, p_left=0.5):
    """Rows routed through L levels over n_parents parents: child id =
    2*parent + route per level (route 0 = LEFT), -1 = masked out."""
    rng = np.random.default_rng(seed)
    bins = jnp.asarray(rng.integers(0, nbins, (n, f)), jnp.int32)
    parent = rng.integers(-1, n_parents, (L, n))
    route = (rng.random((L, n)) >= p_left).astype(np.int64)
    child = np.where(parent >= 0, 2 * parent + route, -1)
    gh = jnp.asarray(rng.normal(size=(n, 2)), jnp.float32)
    return bins, jnp.asarray(child, jnp.int32), gh


@pytest.mark.parametrize("backend", ["ref", "packed"])
def test_child_mode_backends_bit_exact(backend):
    bins, child, gh = _child_case(300, 4, 9, 5, 3, seed=11)
    spec = HistSpec(n_nodes=5, nbins=9, n_levels=3, backend=backend,
                    subtract=True)
    out = ops.hist_levels(bins, child, gh, spec)
    want = ref.hist_levels_left_ref(bins, child, gh, n_nodes=5, nbins=9)
    assert out.shape == (3, 5, 4, 9, 2)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_child_mode_pallas_interpret():
    bins, child, gh = _child_case(257, 3, 8, 4, 2, seed=12)
    spec = HistSpec(n_nodes=4, nbins=8, n_levels=2, backend="interpret",
                    subtract=True)
    out = ops.hist_levels(bins, child, gh, spec)
    want = ref.hist_levels_left_ref(bins, child, gh, n_nodes=4, nbins=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("p_left", [0.5, 0.97, 1.0])
def test_parent_equals_left_plus_right(p_left):
    """The subtraction invariant, including passthrough-heavy routing
    (p_left -> 1: nodes route everything LEFT, right children empty)."""
    n, f, nbins, P, L = 800, 3, 9, 4, 3
    bins, child, gh = _child_case(n, f, nbins, P, L, seed=7, p_left=p_left)
    left = ops.hist_levels(bins, child, gh,
                           HistSpec(n_nodes=P, nbins=nbins, n_levels=L,
                                    backend="packed", subtract=True))
    # direct child-frontier panel, split into (left, right) pairs
    full = ops.hist_levels(bins, child, gh,
                           HistSpec(n_nodes=2 * P, nbins=nbins, n_levels=L,
                                    backend="packed"))
    lr = full.reshape(L, P, 2, f, nbins, 2)
    parent_ids = jnp.where(child >= 0, child // 2, -1)
    parent = ops.hist_levels(bins, parent_ids, gh,
                             HistSpec(n_nodes=P, nbins=nbins, n_levels=L,
                                      backend="packed"))
    # the left panel is the direct left-child histogram, bit-for-bit
    np.testing.assert_array_equal(np.asarray(left), np.asarray(lr[:, :, 0]))
    # parent == left + right (tolerance: addition order differs)
    np.testing.assert_allclose(np.asarray(parent),
                               np.asarray(lr[:, :, 0] + lr[:, :, 1]),
                               rtol=1e-5, atol=1e-4)
    # the grower's reconstruction: parent - left == direct right child
    np.testing.assert_allclose(np.asarray(parent - left),
                               np.asarray(lr[:, :, 1]),
                               rtol=1e-5, atol=1e-4)


def test_build_tree_subtract_matches_direct():
    """Same tree out of subtraction growth and direct growth (the
    exactness contract at the tree level; raw hists differ in low bits)."""
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(600, 4)), jnp.float32)
    cand = jnp.sort(jnp.asarray(rng.normal(size=(4, 8)), jnp.float32), 1)
    from repro.core import binning
    bins = binning.bin_features(x, cand)
    gh = jnp.asarray(rng.normal(size=(600, 2)), jnp.float32)
    gh = gh.at[:, 1].set(jnp.abs(gh[:, 1]) + 0.1)
    for depth in (1, 2, 4):
        spec = HistSpec(n_nodes=2 ** max(depth - 1, 0), nbins=9,
                        n_levels=depth, backend="packed")
        direct = tree_lib.build_tree(bins, gh, cand, max_depth=depth,
                                     spec=spec)
        sub = tree_lib.build_tree(
            bins, gh, cand, max_depth=depth,
            spec=dataclasses.replace(spec, subtract=True))
        np.testing.assert_array_equal(np.asarray(direct.feature),
                                      np.asarray(sub.feature))
        np.testing.assert_array_equal(np.asarray(direct.split_bin),
                                      np.asarray(sub.split_bin))
        np.testing.assert_allclose(np.asarray(direct.threshold),
                                   np.asarray(sub.threshold), atol=1e-6)
        np.testing.assert_allclose(np.asarray(direct.leaf_value),
                                   np.asarray(sub.leaf_value), atol=1e-5)


def test_histspec_validation_and_views():
    with pytest.raises(ValueError):
        HistSpec(n_nodes=0, nbins=4)
    with pytest.raises(ValueError):
        HistSpec(n_nodes=2, nbins=0)
    with pytest.raises(ValueError):
        HistSpec(n_nodes=2, nbins=4, n_levels=0)
    with pytest.raises(ValueError):
        HistSpec(n_nodes=2, nbins=4, backend="cuda")
    with pytest.raises(ValueError):
        HistSpec(n_nodes=2, nbins=4, acc_dtype="bfloat16")
    spec = HistSpec(n_nodes=2, nbins=4, n_levels=3)
    assert spec.with_levels(1).n_levels == 1
    assert spec.with_levels(1).n_nodes == spec.n_nodes
    assert spec.resolved().backend in ("packed", "pallas")
    assert hash(spec) == hash(HistSpec(n_nodes=2, nbins=4, n_levels=3))
    cv = HistSpec(n_nodes=8, nbins=4).child_view()
    assert cv.n_nodes == 4 and cv.subtract is True
    assert HistSpec(n_nodes=1, nbins=4).child_view().n_nodes == 1


def test_hist_levels_shape_mismatch_raises():
    bins, node, gh = _case(50, 2, 4, 3, 2, seed=4)
    spec = HistSpec(n_nodes=3, nbins=4, n_levels=3, backend="packed")
    with pytest.raises(ValueError):
        ops.hist_levels(bins, node, gh, spec)      # node has 2 levels


def test_build_tree_spec_equals_kwargs():
    """build_tree(spec=...) is the same tree as the legacy kwargs path."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(400, 5)), jnp.float32)
    cand = jnp.sort(jnp.asarray(rng.normal(size=(5, 8)), jnp.float32), 1)
    from repro.core import binning
    bins = binning.bin_features(x, cand)
    gh = jnp.asarray(rng.normal(size=(400, 2)), jnp.float32)
    gh = gh.at[:, 1].set(jnp.abs(gh[:, 1]) + 0.1)

    legacy = tree_lib.build_tree(bins, gh, cand, max_depth=4, nbins=9,
                                 backend="packed")
    spec = HistSpec(n_nodes=8, nbins=9, n_levels=4, backend="packed")
    new = tree_lib.build_tree(bins, gh, cand, max_depth=4, spec=spec)
    for a, b in zip(legacy, new):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    with pytest.raises(ValueError):     # conflicting nbins
        tree_lib.build_tree(bins, gh, cand, max_depth=4, nbins=5, spec=spec)
    with pytest.raises(ValueError):     # frontier wider than spec
        tree_lib.build_tree(bins, gh, cand, max_depth=5, spec=spec)
    with pytest.raises(TypeError):      # neither spec nor nbins
        tree_lib.build_tree(bins, gh, cand, max_depth=4)


# ---------------------------------------------------------------------------
# The MXU kernel (interpret mode): a bf16 one-hot contraction against a
# grad/hess panel split into three bf16 pieces, summed in float32.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,f,nbins,n_nodes,L", SHAPES)
def test_child_mode_pallas_every_shape(n, f, nbins, n_nodes, L):
    """Child mode through the same kernel with parent ids, on every
    SHAPES case, masked rows included."""
    bins, child, gh = _child_case(n, f, nbins, n_nodes, L, seed=22)
    assert int(jnp.sum(child < 0)) > 0
    spec = HistSpec(n_nodes=n_nodes, nbins=nbins, n_levels=L,
                    backend="interpret", subtract=True)
    out = ops.hist_levels(bins, child, gh, spec)
    want = ref.hist_levels_left_ref(bins, child, gh, n_nodes=n_nodes,
                                    nbins=nbins)
    assert out.shape == (L, n_nodes, f, nbins, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-6, atol=1e-5)


def _wide_gh(n, seed):
    """grad and hess whose magnitudes span 1e-6 to 1e3, and logistic
    hessians p (1 - p)."""
    rng = np.random.default_rng(seed)
    mag = 10.0 ** rng.uniform(-6, 3, (n, 2))
    gh = mag * np.where(rng.random((n, 2)) < 0.5, -1.0, 1.0)
    p = 1.0 / (1.0 + np.exp(-rng.normal(scale=4.0, size=n // 2)))
    gh[: n // 2, 1] = p * (1 - p)
    return gh.astype(np.float32)


def test_split3_gives_back_every_float32_bit():
    from repro.kernels.hist import split3
    import ml_dtypes
    v = jnp.asarray(_wide_gh(20_000, seed=23).ravel())
    pieces = split3(v)
    for p in pieces:                    # each piece is exact in bf16
        p = np.asarray(p)
        np.testing.assert_array_equal(
            p.astype(ml_dtypes.bfloat16).astype(np.float32), p)
    hi, mid, lo = (np.asarray(p) for p in pieces)
    np.testing.assert_array_equal((hi + mid) + lo, np.asarray(v))
    np.testing.assert_array_equal(hi.astype(np.float64) + mid + lo,
                                  np.asarray(v, np.float64))


def test_pallas_wide_range_as_exact_as_packed():
    """grad/hess from 1e-6 to 1e3: the three-piece kernel lies no
    farther from float64 sums than the float32 scatter does, so the
    split loses no float32 bit (one bf16 piece would lose 16)."""
    n, f, nbins, n_nodes = 4096, 3, 9, 4
    rng = np.random.default_rng(24)
    bins = jnp.asarray(rng.integers(0, nbins, (n, f)), jnp.int32)
    node = jnp.asarray(rng.integers(-1, n_nodes, (1, n)), jnp.int32)
    gh = _wide_gh(n, seed=25)
    b, nd = np.asarray(bins), np.asarray(node)[0]
    want = np.zeros((n_nodes, f, nbins, 2))
    ok = nd >= 0
    for j in range(f):
        idx = nd[ok] * nbins + b[ok, j]
        for s in range(2):
            want[:, j, :, s] = np.bincount(
                idx, gh[ok, s].astype(np.float64),
                n_nodes * nbins).reshape(n_nodes, nbins)

    def err(backend):
        spec = HistSpec(n_nodes=n_nodes, nbins=nbins, backend=backend)
        got = np.asarray(ops.hist_levels(bins, node, jnp.asarray(gh),
                                         spec))[0]
        e = np.abs(got - want)
        return e.max(), e.mean()

    (packed_max, packed_mean), (pallas_max, pallas_mean) = (
        err("packed"), err("interpret"))
    assert pallas_max <= packed_max and pallas_mean <= packed_mean, (
        pallas_max, packed_max, pallas_mean, packed_mean)
    # one bf16 piece would be off by ~2^-9 of the largest values: ~10
    assert pallas_max < 1e-6 * np.abs(want).max()


@pytest.mark.parametrize("f,nbins", [(1, 1), (18, 33), (28, 256),
                                     (115, 33), (115, 256), (300, 33)])
def test_plan_fits_vmem_at_every_depth(f, nbins):
    """The kernel's tiling keeps a grid step inside its VMEM budget for
    frontiers of depth 1 to 12, with blocks the chip's tiles accept."""
    from repro.kernels import hist
    for depth in range(1, 13):
        n_nodes = 2 ** (depth - 1)
        t = hist.plan(f, nbins, n_nodes)
        assert hist.vmem_bytes(f, nbins, t) <= hist.VMEM_BUDGET
        assert t.row_tile % 128 == 0
        # the output block's lanes, 6 * node_block, tile by 128 or are
        # the whole panel
        assert (t.node_block == -(-n_nodes // 16) * 16
                or t.node_block % 64 == 0)
        assert 1 <= t.groups_per_step <= hist._MAX_GROUPS_PER_STEP


def test_plan_keeps_the_tiles_timed_on_the_chip():
    """SUSY's frontier (direct: 32 nodes, child: 16) and f=115 at 33
    bins run in one node block and one group block with 2048-row tiles,
    the tiling PERF.md times; deep or 256-bin frontiers split."""
    from repro.kernels.hist import Tiling, plan
    assert plan(18, 33, 32) == Tiling(2048, 32, 2)
    assert plan(18, 33, 16) == Tiling(2048, 16, 2)
    assert plan(115, 33, 32) == Tiling(2048, 32, 8)
    assert plan(115, 256, 32).groups_per_step < 58       # 58 groups
    assert plan(28, 33, 512).node_block < 512
    assert plan(18, 33, 32, max_row_tile=384) == Tiling(384, 32, 2)


@pytest.mark.parametrize("tiling", [(128, 80, 3), (256, 64, 2),
                                    (128, 64, 1), (512, 80, 2)])
def test_every_tiling_sums_alike(tiling):
    """Row tiles, node blocks and feature-group blocks (three groups,
    so blocks of two pad one) split the same sums: the kernel matches
    hist_ref under each, masked rows included."""
    from repro.kernels import hist
    n, f, nbins, n_nodes, L = 700, 70, 17, 70, 2
    assert hist._layout(f, nbins)[0] == 3
    bins, node, gh = _case(n, f, nbins, n_nodes, L, seed=28)
    out = hist._hist_tiled(bins, node, gh, n_nodes=n_nodes, nbins=nbins,
                           tiling=hist.Tiling(*tiling), interpret=True)
    want = _oracle(bins, node, gh, n_nodes, nbins)
    assert out.shape == (L, n_nodes, f, nbins, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("subtract", [False, True])
def test_build_tree_pallas_matches_packed(subtract):
    """The MXU kernel grows the packed trees exactly, on the pinned
    workload of the subtraction tests."""
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(600, 4)), jnp.float32)
    cand = jnp.sort(jnp.asarray(rng.normal(size=(4, 8)), jnp.float32), 1)
    from repro.core import binning
    bins = binning.bin_features(x, cand)
    gh = jnp.asarray(rng.normal(size=(600, 2)), jnp.float32)
    gh = gh.at[:, 1].set(jnp.abs(gh[:, 1]) + 0.1)
    for depth in (1, 2, 4):
        spec = HistSpec(n_nodes=2 ** max(depth - 1, 0), nbins=9,
                        n_levels=depth, backend="packed",
                        subtract=subtract)
        want = tree_lib.build_tree(bins, gh, cand, max_depth=depth,
                                   spec=spec)
        got = tree_lib.build_tree(
            bins, gh, cand, max_depth=depth,
            spec=dataclasses.replace(spec, backend="interpret"))
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Resolution: the platform picks the histogram's backend, and only its.
# ---------------------------------------------------------------------------

def _fake_platform(monkeypatch, platform):
    monkeypatch.setattr(ops.jax, "default_backend", lambda: platform)


@pytest.mark.parametrize("platform,hist_backend", [("tpu", "pallas"),
                                                   ("cpu", "packed")])
def test_auto_resolution_by_platform(monkeypatch, platform, hist_backend):
    _fake_platform(monkeypatch, platform)
    assert HistSpec(n_nodes=32, nbins=33).resolved().backend == hist_backend
    assert HistSpec(n_nodes=32, nbins=33).resolved(platform).backend \
        == hist_backend
    # more bins than bf16 holds exactly: the scatter, on any platform
    assert HistSpec(n_nodes=4, nbins=300).resolved().backend == "packed"
    # an explicit backend is kept
    assert HistSpec(n_nodes=4, nbins=9, backend="ref").resolved().backend \
        == "ref"
    # traversal and split gain keep their own rule
    assert ops.TraverseSpec().resolved().backend == "packed"
    assert ops.resolve("auto") == "packed"


@pytest.mark.parametrize("platform,hist_backend", [("tpu", "pallas"),
                                                   ("cpu", "packed")])
def test_fit_resolves_histogram_by_platform(monkeypatch, platform,
                                            hist_backend):
    """``fit`` hands its scanned program the histogram of the platform
    its data lives on."""
    from repro.core import boosting
    monkeypatch.setattr(boosting, "_platform", lambda x: platform)
    seen = {}

    def program(*args, cfg, spec):
        seen["spec"] = spec
        raise RuntimeError("stop")

    monkeypatch.setattr(boosting, "_fit_scanned", program)
    x = jnp.zeros((64, 3), jnp.float32)
    with pytest.raises(RuntimeError, match="stop"):
        boosting.fit(x, jnp.zeros((64,), jnp.float32),
                     boosting.GBDTConfig(n_trees=1, max_depth=2))
    assert seen["spec"].backend == hist_backend


def test_cpu_fit_beside_a_tpu_stays_packed(monkeypatch):
    """Where the default backend is a TPU but the fit runs on the host
    CPU (``jax.default_device``), 'auto' resolves to the CPU's scatter,
    and ``fit`` and ``fit_reference`` grow the same forest there."""
    from repro.core import boosting
    _fake_platform(monkeypatch, "tpu")
    rng = np.random.default_rng(27)
    x = rng.normal(size=(256, 3)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    cfg = boosting.GBDTConfig(n_trees=2, max_depth=3, n_candidates=8)
    with jax.default_device(jax.devices("cpu")[0]):
        assert HistSpec(n_nodes=4, nbins=9).resolved().backend == "packed"
        scan = boosting.fit(x, y, cfg, jax.random.PRNGKey(0))
        oracle = boosting.fit_reference(x, y, cfg, jax.random.PRNGKey(0))
    for a, b in zip(scan.forest, oracle.forest):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sharded_fit_resolves_by_mesh_platform(monkeypatch):
    """fit_distributed asks its mesh's devices, not the default
    backend: a CPU mesh stays packed though the default says TPU."""
    from jax.sharding import Mesh
    from repro.core import boosting, distributed
    _fake_platform(monkeypatch, "tpu")
    mesh = Mesh(np.array(jax.devices("cpu")[:1]), ("data",))
    program = distributed.sharded_fit(
        boosting.GBDTConfig(n_trees=1, max_depth=2), mesh, axis="data",
        n_global=64)
    rows = jax.ShapeDtypeStruct((64,), jnp.float32)
    text = program.lower(jax.ShapeDtypeStruct((64, 3), jnp.float32), rows,
                         jax.ShapeDtypeStruct((2,), jnp.uint32)
                         ).as_text(debug_info=True)
    assert "repro.hist_levels[packed]" in text
    assert "repro.hist_levels[pallas]" not in text


def test_split_gain_ignores_the_histogram_backend():
    """build_tree's split gain resolves 'auto' on its own: a Pallas
    histogram does not pull in the split-gain kernel."""
    rng = np.random.default_rng(26)
    bins = jnp.asarray(rng.integers(0, 9, (128, 3)), jnp.int32)
    gh = jnp.asarray(rng.normal(size=(128, 2)), jnp.float32)
    cand = jnp.zeros((3, 8), jnp.float32)
    spec = HistSpec(n_nodes=2, nbins=9, n_levels=2, backend="interpret")
    text = tree_lib.build_tree.lower(bins, gh, cand, max_depth=2,
                                     spec=spec).as_text(debug_info=True)
    assert "repro.hist_levels[interpret]" in text
    assert "repro.split_gain[packed]" in text
    assert "repro.split_gain[interpret]" not in text
