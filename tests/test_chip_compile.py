"""Compile the main path for a described TPU v5e, no chip attached.

The TPU compiler is installed with JAX, so it can compile for a chip
that is only described (``topologies.get_topology_desc``).  That
refuses what interpret mode accepts — blocks that break the (8, 128)
tiling, kernels that overrun VMEM, programs that overrun HBM — at no
chip time.  Nothing here runs; it says nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports every test file.  All such compiles stay in this one file, so
they land on one worker.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import boosting, distributed
from repro.kernels.hist import hist_levels_left_pallas, hist_levels_pallas
from repro.kernels.split_gain import split_gain_pallas
from repro.kernels.traverse import traverse_chunk_pallas


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("f", [28, 115])
@pytest.mark.parametrize("subtract", [False, True])
def test_hist_levels_pallas_compiles(one_chip, f, subtract):
    """A frontier of 32 nodes (depth 6) over 33 bins."""
    n, n_nodes, nbins = 65536, 32, 33
    kernel = hist_levels_left_pallas if subtract else hist_levels_pallas
    panel = n_nodes // 2 if subtract else n_nodes     # parent-keyed: half
    _compile(lambda b, nd, g: kernel(b, nd, g, n_nodes=panel, nbins=nbins),
             _shape(one_chip, (n, f), jnp.int32),
             _shape(one_chip, (1, n), jnp.int32),
             _shape(one_chip, (n, 2), jnp.float32))


@pytest.mark.parametrize("f", [28, 115])
def test_split_gain_pallas_compiles(one_chip, f):
    _compile(split_gain_pallas, _shape(one_chip, (32, f, 33, 2), jnp.float32))


@pytest.mark.parametrize("f,depth,dtype", [(28, 6, jnp.float32),
                                           (115, 8, jnp.int32)])
def test_traverse_chunk_pallas_compiles(one_chip, f, depth, dtype):
    """A chunk of 25 trees over 4096 rows, raw (float) and binned (int)."""
    chunk = 25
    _compile(lambda v, fe, cm, lf: traverse_chunk_pallas(
                 v, fe, cm, lf, max_depth=depth),
             _shape(one_chip, (4096, f), dtype),
             _shape(one_chip, (chunk, 2 ** depth - 1), jnp.int32),
             _shape(one_chip, (chunk, 2 ** depth - 1), dtype),
             _shape(one_chip, (chunk, 2 ** depth), jnp.float32))


def test_packed_fit_compiles_at_susy_scale(one_chip):
    """The default one-chip fit at the published SUSY size, 5M x 18."""
    n, f, rounds = 5_000_000, 18, 10
    cfg = boosting.GBDTConfig(n_trees=rounds, max_depth=6, n_candidates=32)
    spec = cfg.hist_spec().resolved()
    assert spec.backend == "packed"
    compiled = boosting._fit_scanned.lower(
        _shape(one_chip, (n, f), jnp.float32),
        _shape(one_chip, (n,), jnp.float32),
        _shape(one_chip, (rounds, 2), jnp.uint32),
        _shape(one_chip, (n,), jnp.float32),
        None, cfg=cfg, spec=spec).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9


def test_row_sharded_fit_compiles_at_higgs_scale(topo):
    """fit_distributed's program over four chips at the published HIGGS
    size, 11M x 28: each device holds a quarter of the rows."""
    n, f = 11_000_000, 28
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    cfg = boosting.GBDTConfig(n_trees=10, max_depth=6, n_candidates=32)
    rows = NamedSharding(mesh, P("data", None))
    vec = NamedSharding(mesh, P("data"))
    args = (jax.ShapeDtypeStruct((n, f), jnp.float32, sharding=rows),
            jax.ShapeDtypeStruct((n,), jnp.float32, sharding=vec),
            jax.ShapeDtypeStruct((n,), jnp.float32, sharding=vec),
            jax.ShapeDtypeStruct((2,), jnp.uint32,
                                 sharding=NamedSharding(mesh, P())))
    compiled = distributed.sharded_fit(
        cfg, mesh, axis="data", n_global=n).lower(*args).compile()
    mem = compiled.memory_analysis()
    # x, y and the validity weight, a quarter of the rows each; the
    # chip's (8, 128) tiles pad the 28 features to 32
    quarter = (n * f * 4 + 2 * n * 4) / 4
    assert quarter <= mem.argument_size_in_bytes <= 1.2 * quarter
    assert mem.temp_size_in_bytes < 16e9
