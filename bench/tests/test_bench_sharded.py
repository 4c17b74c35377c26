"""The row-sharded cell ``higgs.train4`` end to end on four CPU host
devices, its Algorithm 1 reference, and the faults it must catch.

Everything runs in one subprocess with four forced host devices, so
that this process keeps its one-device view.  There the look for a chip
and the compile cache are stubbed, as in ``test_bench_harness.py``, and
the configuration is cut to a few thousand rows.  A CPU trace has no
device plane, so for the traced run the stub lays one out per device
from the trace's own ``bench.fit`` spans: operations under the
``repro.collective``, ``repro.hist_levels``, ``repro.split_gain`` and
``repro.bin_features`` scopes, each a fixed share of every call.  That
checks that the cell's per-layer metrics are read and are in range, not
a chip's times.  The planted faults leave a chip's rows out of one
cross-chip sum each; they have to fail the checks that grow with the
rows (``cover_gap``, ``base_gap``), not only the tree's ratios.
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

_SCRIPT = r"""
import contextlib, dataclasses, io, json, sys
sys.path[:0] = [BENCH, SRC]

import jax, jax.numpy as jnp, numpy as np
from jax import lax

import repro
from repro.core import distributed, proposal, tree as tree_lib
import datagen, harness, reference_sharded, tracing, traffic, work

SEED = 2 ** 33 + 97
FAULTS = ["shard_left_out", "leaf_sums_left_out", "base_sum_left_out",
          "half_rows"]
ROWS = 8192
KIND = jax.devices()[0].device_kind
harness.require_accelerator = lambda chips: jax.devices()[:chips]
harness.enable_cache = lambda: None
work.PEAKS[KIND] = work.PEAKS["TPU v5 lite"]
load_config = harness.load_config


def tiny(spec, name):
    cfg = load_config(spec, name)
    cfg["rows"] = ROWS
    return cfg


harness.load_config = tiny
load_trace = tracing.load


def with_device_planes(path):
    # per device and bench.fit span, from 0.35 of it on: a tenth under
    # repro.bin_features, a tenth under repro.collective, a fifth under
    # repro.hist_levels, a twentieth under repro.split_gain
    trace = load_trace(path)
    ops = []
    layers = [(0.1, "fusion.1", "jit(f)/repro.bin_features"),
              (0.1, "all-reduce.1", "jit(f)/repro.collective"),
              (0.2, "fusion.2", "jit(f)/repro.hist_levels[x]"),
              (0.05, "reduce.3", "jit(f)/repro.split_gain")]
    for s in trace.spans:
        if s.name == "bench.fit":
            d, t = s.end - s.start, s.start + 0.35 * (s.end - s.start)
            for share, name, stack in layers:
                ops.append(tracing.Op(t, t + share * d, name, stack))
                t += share * d
    trace.devices = {f"/device:TPU:{i}": tracing.DeviceOps.of(ops)
                     for i in range(4)}
    return trace


tracing.load = with_device_planes


def run(trace):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert harness.main(["--workload", "higgs.train4", "--seed",
                             str(SEED), "--seconds", "0.5", "--trace",
                             str(trace)]) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


result = {"devices": len(jax.devices()), "runs": [run(0), run(1)]}

# Algorithm 1's reference against the program's candidates, two rounds,
# and against the program's own proposal functions; 8190 rows pad by 2
cfg = repro.GBDTConfig(n_trees=2, max_depth=3, n_candidates=8)
mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
key = traffic.call_key(SEED, 7)
x, y = datagen.mixture(datagen.seed_key(SEED), n=8190, f=5)
x, y = np.asarray(x), np.asarray(y)
model = distributed.fit_distributed(x, y, cfg, mesh, key)
got = np.asarray(model.candidates)
xp = np.concatenate([x, x[:2]])
pools = jnp.stack([proposal.random_candidates_local(
    jax.random.fold_in(key, w), xp[w * 2048:(w + 1) * 2048], 8)
    for w in range(4)])
differ = []
for r in range(2):
    ref = reference_sharded.sharded_candidates(np.asarray(key), x, 8, 4, r)
    prog = np.asarray(proposal.resample_gathered(
        jax.random.fold_in(key, 10_000 + r), pools, 8))
    differ.append([int(np.sum(ref != got[r])), int(np.sum(ref != prog))])
result["candidates_differ"] = differ

# planted faults: the single-host proposal in place of Algorithm 1's
# (it reports no cover: the single-host fit's tree with the sharded
# program's cover of it stands in), then faults in the program's sums
real_fit = repro.fit_distributed
repro.fit_distributed = lambda x, y, cfg, mesh, key: dataclasses.replace(
    repro.fit(np.asarray(x), np.asarray(y), cfg, key),
    cover=real_fit(x, y, cfg, mesh, key).cover)
result["single_host_proposal"] = run(0)
repro.fit_distributed = real_fit
real_collective = tree_lib.collective
real_valid_rows = distributed._valid_rows


def planted(name):
    def collective(op, a, axis_name):
        if op is lax.psum and a.ndim == 4 and name == "shard_left_out":
            # a level's panel without the last shard's rows
            last = lax.axis_index(axis_name) == lax.psum(1, axis_name) - 1
            a = jnp.where(last, jnp.zeros_like(a), a)
        if op is lax.psum and a.ndim == 2 and name == "leaf_sums_left_out":
            return a                            # each chip's own leaves
        if op is lax.psum and a.ndim == 0 and name == "base_sum_left_out":
            return a                            # each chip's label sum
        return real_collective(op, a, axis_name)

    def valid_rows(x_local, axis, n_global):
        w = real_valid_rows(x_local, axis, n_global)
        if name == "half_rows":                 # each shard's back half
            per = x_local.shape[0]
            w = w * (jnp.arange(per) < per // 2)
        return w

    tree_lib.collective = collective
    distributed._valid_rows = valid_rows
    jax.clear_caches()
    distributed.sharded_fit.cache_clear()
    return run(0)


for fault in FAULTS:
    result[fault] = planted(fault)
print("RESULT" + json.dumps(result))
"""


@pytest.fixture(scope="module")
def result():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    script = _SCRIPT.replace("BENCH, SRC", f"{str(BENCH)!r}, "
                             f"{str(ROOT / 'src')!r}")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=str(ROOT))
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT")][0]
    return json.loads(line[len("RESULT"):])


def _limits():
    return json.loads((BENCH / "limits" / "higgs.train4.json").read_text())


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_over_four_devices_and_is_correct(result, trace):
    assert result["devices"] == 4
    line = result["runs"][trace]
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert line["compiles_in_window"] == 0
    assert line["device"]["count"] == 4
    assert line["checks"]["candidates_differ"]["value"] == 0
    assert line["checks"]["bad_nodes"]["value"] == 0
    if not trace:
        assert set(line["metrics"]) == {"train_s_per_round", "setup_s"}


def test_the_algorithm_1_reference_gives_the_programs_candidates(result):
    # per round: against fit_distributed's candidates, and against
    # proposal.random_candidates_local / resample_gathered
    assert result["candidates_differ"] == [[0, 0], [0, 0]]


def test_the_traced_line_carries_the_cells_per_layer_metrics(result):
    metrics = {k: v["value"] for k, v in result["runs"][1]["metrics"].items()}
    assert set(metrics) == {"hist_ms_per_round", "hist_roofline",
                            "split_gain_ms_per_round",
                            "binning_ms_per_round", "round_mfu_pct",
                            "device_idle_pct.train",
                            "unscoped_ms_per_round"}
    for name in ("hist_roofline", "round_mfu_pct"):
        assert 0 < metrics[name] <= 100
    # every stub operation is under a repro. scope, the collective too
    assert metrics["unscoped_ms_per_round"] == 0.0
    for name in ("hist_ms_per_round", "split_gain_ms_per_round",
                 "binning_ms_per_round"):
        assert metrics[name] > 0


@pytest.mark.parametrize("fault", ["single_host_proposal",
                                   "shard_left_out", "leaf_sums_left_out",
                                   "base_sum_left_out", "half_rows"])
def test_planted_faults_read_above_their_limits(result, fault):
    line = result[fault]
    assert line["correct"] is False, line["checks"]
    over = [k for k, v in line["checks"].items()
            if v["value"] is not None and v["value"] > _limits()[k]]
    assert over, line["checks"]
    # a worker's rows left out of a cross-chip sum show in the numbers
    # that grow with the rows, not only as noise in the tree
    want = {"shard_left_out": "cover_gap", "leaf_sums_left_out": "cover_gap",
            "base_sum_left_out": "base_gap", "half_rows": "cover_gap"}
    if fault in want:
        assert want[fault] in over, line["checks"]
