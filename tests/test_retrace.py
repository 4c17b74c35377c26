"""Retrace behavior of the scanned boosting trainer and the batched
inference engine.

The whole point of the lax.scan round runner is that trace/compile cost
is O(1) in n_trees: the round step's Python body executes once per
trace of the surrounding jit, so ``boosting.round_trace_count()`` is a
direct lowering count of the hot loop.  Doubling n_trees must not
increase it, and refitting with unchanged (config, shapes) must hit the
jit cache and add zero traces.

Where the installed JAX exposes ``jax.monitoring`` event listeners, the
same invariant is cross-checked against XLA compile events.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import boosting, predict as predict_lib
from repro.launch.serve_gbdt import synthetic_gbdt


def _toy(n=1000, f=4, seed=0):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (n, f))
    w = jax.random.normal(jax.random.fold_in(key, 1), (f,))
    y = (x @ w > 0).astype(jnp.float32)
    return x, y


def _fit_traces(x, y, cfg):
    before = boosting.round_trace_count()
    boosting.fit(x, y, cfg, jax.random.PRNGKey(0))
    return boosting.round_trace_count() - before


def test_doubling_n_trees_does_not_retrace_more():
    x, y = _toy()
    base = dict(max_depth=4, n_candidates=16)
    t_small = _fit_traces(x, y, boosting.GBDTConfig(n_trees=4, **base))
    t_double = _fit_traces(x, y, boosting.GBDTConfig(n_trees=8, **base))
    t_quad = _fit_traces(x, y, boosting.GBDTConfig(n_trees=16, **base))
    assert t_small == 1, t_small          # one trace of the round step
    assert t_double == t_small            # O(1) in n_trees, not O(n_trees)
    assert t_quad == t_small


def test_telemetry_round_step_traces_o1():
    """The ROADMAP rule for new jitted entry points: the telemetry-
    enabled round step (TrainReport rows as extra scan outputs) must
    keep the O(1)-in-n_trees compile property of the plain one."""
    x, y = _toy(seed=3)
    base = dict(max_depth=4, n_candidates=16, telemetry=True)
    t_small = _fit_traces(x, y, boosting.GBDTConfig(n_trees=4, **base))
    t_double = _fit_traces(x, y, boosting.GBDTConfig(n_trees=8, **base))
    t_quad = _fit_traces(x, y, boosting.GBDTConfig(n_trees=16, **base))
    assert t_small == 1, t_small
    assert t_double == t_small
    assert t_quad == t_small
    # refit with unchanged config: jit cache hit, zero new traces
    assert _fit_traces(x, y, boosting.GBDTConfig(n_trees=4, **base)) == 0


def test_subtract_round_step_traces_o1():
    """Subtraction growth swaps the level scan's body (child-mode
    scatter + panel carry) — still one round-step trace regardless of
    n_trees, and a refit hits the jit cache."""
    x, y = _toy(seed=5)
    base = dict(max_depth=4, n_candidates=16, subtract=True,
                telemetry=True)
    t_small = _fit_traces(x, y, boosting.GBDTConfig(n_trees=4, **base))
    t_double = _fit_traces(x, y, boosting.GBDTConfig(n_trees=8, **base))
    assert t_small == 1, t_small
    assert t_double == t_small
    assert _fit_traces(x, y, boosting.GBDTConfig(n_trees=4, **base)) == 0


def test_refit_same_config_hits_jit_cache():
    x, y = _toy(seed=1)
    cfg = boosting.GBDTConfig(n_trees=4, max_depth=4, n_candidates=16)
    _fit_traces(x, y, cfg)                # warm (may or may not be cached)
    assert _fit_traces(x, y, cfg) == 0    # second fit: zero new traces
    # a different key is NOT a retrace either (keys are traced values)
    before = boosting.round_trace_count()
    boosting.fit(x, y, cfg, jax.random.PRNGKey(99))
    assert boosting.round_trace_count() - before == 0


_SHARDED_REFIT = r"""
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.core import boosting, distributed

compiles = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, duration, **_: compiles.append(event)
    if event == "/jax/core/compile/backend_compile_duration" else None)
mesh = Mesh(np.array(jax.devices()), ("data",))
x = jax.random.normal(jax.random.PRNGKey(0), (2048, 5))
y = (x[:, 0] > 0).astype(jnp.float32)
inputs = {"host": (np.asarray(x), np.asarray(y)),
          "sharded": (jax.device_put(x, NamedSharding(mesh, P("data", None))),
                      jax.device_put(y, NamedSharding(mesh, P("data"))))}
cfg = boosting.GBDTConfig(n_trees=3, max_depth=3, n_candidates=8)
out = {"devices": len(jax.devices())}
for name, (xs, ys) in inputs.items():
    distributed.fit_distributed(xs, ys, cfg, mesh, jax.random.PRNGKey(1))
    before = (boosting.round_trace_count(),
              distributed.sharded_program_count(), len(compiles))
    m = distributed.fit_distributed(xs, ys, cfg, mesh, jax.random.PRNGKey(2))
    jax.block_until_ready(m.forest)
    after = (boosting.round_trace_count(),
             distributed.sharded_program_count(), len(compiles))
    out[name] = [a - b for a, b in zip(after, before)]
print("RESULT" + json.dumps(out))
"""


def test_sharded_refit_same_config_traces_and_builds_nothing():
    """A second ``fit_distributed`` call with the same config, mesh and
    row count and a new key finds its program: no round-step trace, no
    program built, no XLA compile, for host and row-sharded inputs.
    Four host devices, in a subprocess, as in test_distributed.py."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    proc = subprocess.run([sys.executable, "-c", _SHARDED_REFIT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT")][0]
    out = json.loads(line[len("RESULT"):])
    assert out["devices"] == 4
    # (round-step traces, programs built, XLA compiles) of the repeat
    assert out["host"] == [0, 0, 0], out
    assert out["sharded"] == [0, 0, 0], out


def test_traversal_traces_o1_in_n_trees():
    """Inference mirrors the trainer's contract: the batched traversal's
    chunk step traces at most once per fresh compiled predict no matter
    how many trees the forest holds (the chunk axis is a lax.scan), and
    a repeat call with unchanged (shapes, spec) adds zero traces."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(300, 5)).astype(np.float32))

    def fresh_traces(n_trees):
        model = synthetic_gbdt(n_trees=n_trees, max_depth=3, n_features=5,
                               n_candidates=8, seed=n_trees)
        before = predict_lib.traverse_trace_count()
        predict_lib.forest_predict(model.forest, x, max_depth=3,
                                   tree_chunk=4)
        fresh = predict_lib.traverse_trace_count() - before
        before = predict_lib.traverse_trace_count()
        predict_lib.forest_predict(model.forest, x, max_depth=3,
                                   tree_chunk=4)
        repeat = predict_lib.traverse_trace_count() - before
        return fresh, repeat

    f8, r8 = fresh_traces(8)
    f32, r32 = fresh_traces(32)
    assert f8 <= 1 and f32 <= 1, (f8, f32)   # O(1) in n_trees
    assert r8 == 0 and r32 == 0, (r8, r32)   # jit cache hit on repeat


def test_compile_events_constant_in_n_trees():
    """Cross-check via jax.monitoring where available: the number of XLA
    backend compiles triggered by a fit does not grow with n_trees."""
    if not hasattr(jax, "monitoring") or \
            not hasattr(jax.monitoring, "register_event_listener"):
        pytest.skip("jax.monitoring event listeners unavailable")
    events = []
    jax.monitoring.register_event_listener(
        lambda name, **kw: events.append(name))

    def compiles_for(n_trees):
        x, y = _toy(n=512, f=3, seed=2 + n_trees)   # fresh shapes per call
        cfg = boosting.GBDTConfig(n_trees=n_trees, max_depth=3,
                                  n_candidates=8)
        start = len(events)
        boosting.fit(x, y, cfg, jax.random.PRNGKey(0))
        return sum("compile" in e for e in events[start:])

    c4 = compiles_for(4)
    c8 = compiles_for(8)
    assert c8 <= c4, (c4, c8)             # doubling rounds: no extra compiles
