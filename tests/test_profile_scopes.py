"""The layers' ``jax.named_scope``s in the trainers' lowered programs,
and the host spans of ``fit``, ``fit_distributed`` and ``predict`` in a
profile.

A profile reads a layer's device time from the scope in each
operation's name stack, so every layer has to carry its scope and no
layer's scope may enclose another's (its time would be counted twice).
"""

import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import boosting, distributed

OLD = ("repro.hist_levels", "repro.hist_levels_left", "repro.split_gain",
       "repro.bin_features")
NEW = ("repro.proposal", "repro.route", "repro.leaf_update")
COLLECTIVES = ("psum", "all_gather", "pmin", "pmax")


def _scope_stacks(lowered) -> list[tuple[str, ...]]:
    """Each location's ``repro.*`` scopes, outermost first, without the
    backend bracket."""
    names = re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True))
    return [tuple(p.split("[")[0] for p in n.split("/")
                  if p.startswith("repro.")) for n in names]


def _data(n=256, f=4):
    x = jax.random.normal(jax.random.PRNGKey(0), (n, f))
    return x, (x[:, 0] > 0).astype(jnp.float32)


def _fit_lowered(cfg):
    x, y = _data()
    return boosting._fit_scanned.lower(
        x, y, boosting.round_keys(jax.random.PRNGKey(1), cfg.n_trees),
        jnp.zeros((x.shape[0],)), None, cfg=cfg,
        spec=cfg.hist_spec().resolved())


def _distributed(cfg):
    x, y = _data()
    mesh = jax.make_mesh((1,), ("data",))
    fn = distributed.sharded_fit(cfg, mesh, axis="data", n_global=256)
    return fn, (x, y, jax.random.PRNGKey(1))


STRATEGIES = ["random", "weighted_quantile", "uniform_range"]


@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("subtract", [False, True])
def test_fit_carries_every_layer_scope_and_none_nests(strategy, subtract):
    cfg = boosting.GBDTConfig(n_trees=2, max_depth=3, n_candidates=8,
                              strategy=strategy, subtract=subtract)
    stacks = _scope_stacks(_fit_lowered(cfg))
    found = {s for st in stacks for s in st}
    hist = "repro.hist_levels_left" if subtract else "repro.hist_levels"
    assert set(NEW) | {hist, "repro.split_gain",
                       "repro.bin_features"} <= found
    assert "repro.collective" not in found
    assert all(len(set(st)) <= 1 for st in stacks), \
        {st for st in stacks if len(set(st)) > 1}


@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("subtract", [False, True])
def test_fit_distributed_carries_every_layer_scope_and_none_nests(
        strategy, subtract):
    cfg = boosting.GBDTConfig(n_trees=2, max_depth=3, n_candidates=8,
                              strategy=strategy, subtract=subtract,
                              telemetry=True)
    fn, args = _distributed(cfg)
    stacks = _scope_stacks(fn.lower(*args))
    found = {s for st in stacks for s in st}
    assert set(NEW) | {"repro.collective", "repro.split_gain",
                       "repro.bin_features"} <= found
    assert all(len(set(st)) <= 1 for st in stacks), \
        {st for st in stacks if len(set(st)) > 1}


def _collectives(jaxpr, outer=""):
    """``(primitive, name stack)`` of every collective in ``jaxpr`` and
    the jaxprs inside it, each stack prefixed by its callers'."""
    for eqn in jaxpr.eqns:
        stack = outer + "/" + str(eqn.source_info.name_stack)
        if eqn.primitive.name in COLLECTIVES:
            yield eqn.primitive.name, stack
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _collectives(sub, stack)


@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("subtract", [False, True])
def test_every_collective_of_fit_distributed_is_scoped(strategy, subtract):
    cfg = boosting.GBDTConfig(n_trees=2, max_depth=3, n_candidates=8,
                              strategy=strategy, subtract=subtract,
                              telemetry=True)
    fn, args = _distributed(cfg)
    found = list(_collectives(jax.make_jaxpr(fn)(*args).jaxpr))
    prims = {p for p, _ in found}
    assert {"psum", "all_gather" if strategy != "uniform_range"
            else "pmin"} <= prims
    assert all("repro.collective" in s for _, s in found), \
        [(p, s) for p, s in found if "repro.collective" not in s]


def test_scopes_leave_the_forest_unchanged():
    # scopes are metadata: the scanned trainer still matches its oracle
    x, y = _data(512, 5)
    cfg = boosting.GBDTConfig(n_trees=3, max_depth=3, n_candidates=8)
    a = boosting.fit(x, y, cfg, jax.random.PRNGKey(3))
    b = boosting.fit_reference(x, y, cfg, jax.random.PRNGKey(3))
    for u, v in zip(a.forest, b.forest):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))


def _host_spans(directory, prefix):
    """``(start, end, name)`` of the host events named ``prefix...`` in
    the profile written under ``directory``, by start."""
    (path,) = glob.glob(f"{directory}/**/*.xplane.pb", recursive=True)
    profile = jax.profiler.ProfileData.from_file(path)
    return sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                  for plane in profile.planes for line in plane.lines
                  for e in line.events if e.name.startswith(prefix))


def test_fit_distributed_stage_and_program_spans_nest_in_prepare(tmp_path):
    x, y = _data()
    mesh = jax.make_mesh((1,), ("data",))
    cfg = boosting.GBDTConfig(n_trees=2, max_depth=3, n_candidates=8)
    with jax.profiler.trace(str(tmp_path)):
        distributed.fit_distributed(x, y, cfg, mesh)
    spans = _host_spans(tmp_path, "repro.fit")
    assert [s[2] for s in spans] == ["repro.fit", "repro.fit.prepare",
                                     "repro.fit.stage", "repro.fit.program"]
    fit, prepare, stage, program = spans
    assert fit[0] <= prepare[0] and prepare[1] <= fit[1]
    for child in (stage, program):
        assert prepare[0] <= child[0] and child[1] <= prepare[1]
    assert stage[1] <= program[0]


def test_sharded_program_count_counts_one_per_distinct_config():
    x, y = _data(200, 3)            # a row count no other test here uses
    mesh = jax.make_mesh((1,), ("data",))
    a = boosting.GBDTConfig(n_trees=1, max_depth=2, n_candidates=5)
    b = boosting.GBDTConfig(n_trees=1, max_depth=3, n_candidates=5)
    before = distributed.sharded_program_count()
    built = []
    for cfg in (a, b, a, b):
        distributed.fit_distributed(x, y, cfg, mesh)
        built.append(distributed.sharded_program_count() - before)
    assert built == [1, 2, 2, 2]
