"""Distributed GBDT (Algorithm 1) — runs in a subprocess with 8 forced
host devices so the main test process keeps its single-device view."""

import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow        # subprocess retrain, >60s

_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np, json
from jax.sharding import Mesh
from repro.core import boosting, distributed

key = jax.random.PRNGKey(7)
n, f = 8192, 6
X = jax.random.normal(key, (n, f))
w = jax.random.normal(jax.random.fold_in(key, 1), (f,))
y = (X @ w > 0).astype(jnp.float32)
mesh = Mesh(np.array(jax.devices()).reshape(8,), ("data",))

out = {"n_devices": len(jax.devices())}
for strat in ("random", "weighted_quantile"):
    cfg = boosting.GBDTConfig(n_trees=4, max_depth=4, n_candidates=16,
                              strategy=strat)
    m = distributed.fit_distributed(X, y, cfg, mesh, key)
    out[strat] = boosting.accuracy(m, X, y)

# inputs already row-sharded are used in place and give the same forest
from jax.sharding import NamedSharding, PartitionSpec as P
cfg = boosting.GBDTConfig(n_trees=4, max_depth=4, n_candidates=16)
ma = distributed.fit_distributed(np.asarray(X), np.asarray(y), cfg, mesh, key)
mb = distributed.fit_distributed(
    jax.device_put(X, NamedSharding(mesh, P("data", None))),
    jax.device_put(y, NamedSharding(mesh, P("data"))), cfg, mesh, key)
out["presharded_same"] = all(
    bool(np.array_equal(np.asarray(a), np.asarray(b)))
    for a, b in zip(ma.forest, mb.forest))

# single-host reference with identical config
cfg = boosting.GBDTConfig(n_trees=4, max_depth=4, n_candidates=16)
m1 = boosting.fit(X, y, cfg, key)
out["single"] = boosting.accuracy(m1, X, y)
print("RESULT" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def dist_result():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][0]
    return json.loads(line[len("RESULT"):])


def test_runs_on_8_workers(dist_result):
    assert dist_result["n_devices"] == 8


def test_distributed_random_learns(dist_result):
    assert dist_result["random"] > 0.85


def test_presharded_inputs_match_host_inputs(dist_result):
    """x, y already sharded over the mesh skip host staging, same trees."""
    assert dist_result["presharded_same"], dist_result


def test_distributed_random_matches_quantile(dist_result):
    """Paper claim, distributed: S ~= Q accuracy."""
    assert abs(dist_result["random"] - dist_result["weighted_quantile"]) \
        < 0.03, dist_result


def test_distributed_matches_single_host(dist_result):
    """Algorithm 1 with psum'd histograms ~= single-host training."""
    assert abs(dist_result["random"] - dist_result["single"]) < 0.03, \
        dist_result


# ---------------------------------------------------------------------------
# Padding correctness: n % n_workers != 0.
#
# The driver pads shards with repeats of the leading rows; those rows
# must carry zero weight so they never bias the base score, the psum'd
# histograms, or the leaf values.  With 'uniform_range' the distributed
# candidate grid is IDENTICAL to the single-host one (pmin/pmax of
# duplicated rows == global min/max), so the padded distributed fit must
# agree with the single-host fit oracle tree-for-tree — the strongest
# possible regression check for the padding bias.
# ---------------------------------------------------------------------------

_PAD_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np, json
from jax.sharding import Mesh
from repro.core import boosting, distributed

key = jax.random.PRNGKey(7)
n, f = 1003, 4                       # 1003 % 8 = 3 -> 5 pad rows
X = jax.random.normal(key, (n, f))
w = jax.random.normal(jax.random.fold_in(key, 1), (f,))
y = (X @ w > 0).astype(jnp.float32)
mesh = Mesh(np.array(jax.devices()).reshape(8,), ("data",))

cfg = boosting.GBDTConfig(n_trees=4, max_depth=4, n_candidates=8,
                          strategy="uniform_range")
md = distributed.fit_distributed(X, y, cfg, mesh, key)
mr = distributed.fit_distributed(X, y, cfg, mesh, key, reference=True)
ms = boosting.fit(X, y, cfg, key)

def forest_cmp(a, b):
    return {
        "feature_equal": bool(np.array_equal(np.asarray(a.feature),
                                             np.asarray(b.feature))),
        "split_bin_equal": bool(np.array_equal(np.asarray(a.split_bin),
                                               np.asarray(b.split_bin))),
        "threshold_close": bool(np.allclose(np.asarray(a.threshold),
                                            np.asarray(b.threshold),
                                            atol=1e-6)),
        "leaf_close": bool(np.allclose(np.asarray(a.leaf_value),
                                       np.asarray(b.leaf_value),
                                       atol=1e-4)),
    }

# weighted_quantile on padded data must also train fine (no crash, sane
# accuracy) even though its merged candidate grid is not the single-host one
cfg_wq = boosting.GBDTConfig(n_trees=4, max_depth=4, n_candidates=8,
                             strategy="weighted_quantile")
m_wq = distributed.fit_distributed(X, y, cfg_wq, mesh, key)

out = {
    "n_devices": len(jax.devices()),
    "vs_single": forest_cmp(md.forest, ms.forest),
    "scan_vs_ref": forest_cmp(md.forest, mr.forest),
    "base_gap": abs(md.base_score - ms.base_score),
    "acc_dist": boosting.accuracy(md, X, y),
    "acc_single": boosting.accuracy(ms, X, y),
    "acc_wq": boosting.accuracy(m_wq, X, y),
}
print("RESULT" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def pad_result():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    proc = subprocess.run([sys.executable, "-c", _PAD_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][0]
    return json.loads(line[len("RESULT"):])


def test_padded_fit_matches_single_host_oracle(pad_result):
    """n % nw != 0: pad rows carry zero weight, so the distributed fit
    reproduces the single-host trees exactly (uniform_range grid)."""
    assert pad_result["n_devices"] == 8
    assert all(pad_result["vs_single"].values()), pad_result
    assert pad_result["base_gap"] < 1e-5, pad_result
    assert pad_result["acc_dist"] == pytest.approx(
        pad_result["acc_single"], abs=1e-6)


def test_padded_scan_matches_reference_worker(pad_result):
    """The scanned worker and the unrolled oracle agree under padding."""
    assert all(pad_result["scan_vs_ref"].values()), pad_result


def test_padded_weighted_quantile_trains(pad_result):
    assert pad_result["acc_wq"] > 0.85, pad_result
