"""Each cell's run end to end on the CPU at a tiny size.

The look for a chip and the compile cache are stubbed here, in the
test; the configurations are cut to a few thousand rows.  The rest of a
run is the one the chip runs: set-up, window, trace reduction, metric
readers, the reference's check and the result line.
"""

import json
import os
import pathlib
import subprocess
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

import repro  # noqa: E402

import harness  # noqa: E402
import work  # noqa: E402

SEED = 2 ** 31 + 777
LINE_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture
def cpu_run(monkeypatch, capsys):
    """``run(workload, trace) -> last stdout line`` at a tiny size."""
    monkeypatch.setattr(harness, "require_accelerator",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "enable_cache", lambda: None)
    monkeypatch.setitem(work.PEAKS, jax.devices()[0].device_kind,
                        work.PEAKS["TPU v5 lite"])
    load = harness.load_config

    def tiny(spec, name):
        cfg = load(spec, name)
        cfg["rows"] = 12000
        if "forest" in cfg:
            cfg["forest"]["n_trees"] = 40
        return cfg

    monkeypatch.setattr(harness, "load_config", tiny)

    def run(workload, trace=0, seconds=0.5):
        assert harness.main(["--workload", workload, "--seed", str(SEED),
                             "--seconds", str(seconds), "--trace",
                             str(trace)]) == 0
        out = capsys.readouterr()
        return json.loads(out.out.strip().splitlines()[-1]), out.err

    return run


CELLS = [c["name"] for c in harness.load_benchmark()["workloads"]]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_end_to_end(cpu_run, workload, trace):
    line, err = cpu_run(workload, trace)
    assert list(line)[:5] == LINE_KEYS and list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert line["compiles_in_window"] == 0
    dev = line["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    spec = harness.load_benchmark()
    if trace:
        want = {m["name"] for m in harness.cell_metrics(
            spec, workload, "per_layer")}
        # on the CPU no device plane exists: only host-clock readers read
        assert set(line["metrics"]) <= want and line["metrics"]
        assert {"busy_s", "window_s"} <= set(dev)
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        want = {m["name"] for m in harness.cell_metrics(
            spec, workload, "end_to_end")}
        assert set(line["metrics"]) == want and "setup_s" in want
        for m in line["metrics"].values():
            assert m["value"] > 0 and m["unit"]
    # the numbers compared are the last lines of standard error
    tail = err.strip().splitlines()[-len(line["checks"]):]
    assert all(t.startswith("check ") for t in tail)


def _zero_tree(model):
    f = model.forest
    empty = repro.Forest(jnp.full_like(f.feature, -1),
                         jnp.full_like(f.split_bin, f.split_bin.max()),
                         jnp.full_like(f.threshold, jnp.inf),
                         jnp.zeros_like(f.leaf_value))
    model.forest = empty
    return model


def _fit_unchanged(fit):
    return lambda x, y, cfg, key: _zero_tree(fit(x, y, cfg, key))


def _fit_half(fit):
    return lambda x, y, cfg, key: fit(x[::2], y[::2], cfg, key)


def _fit_altered(fit):
    def altered(x, y, cfg, key):
        model = fit(x, y, cfg, key)
        leaf = model.forest.leaf_value
        model.forest = model.forest._replace(
            leaf_value=leaf.at[0, jnp.argmax(jnp.abs(leaf[0]))].multiply(-1.0))
        return model
    return altered


def _predict_half(predict):
    def half(self, x, **kw):
        m = predict(self, x[: len(x) // 2], **kw)
        return jnp.concatenate([m, jnp.full((len(x) - len(m),), m.mean())])
    return half


def _predict_altered(predict):
    def altered(self, x, **kw):
        return predict(self, x, **kw).at[3].add(0.01)
    return altered


@pytest.mark.parametrize("workload,target,fault", [
    ("susy.train", "fit", _fit_unchanged),
    ("susy.train", "fit", _fit_half),
    ("susy.train", "fit", _fit_altered),
    ("mirai.serve", "predict", _predict_half),
    ("mirai.serve", "predict", _predict_altered),
], ids=["train-unchanged", "train-half-batch", "train-altered-leaf",
        "serve-half-batch", "serve-altered-margin"])
def test_broken_timed_path_is_not_correct(cpu_run, monkeypatch, workload,
                                          target, fault):
    if target == "fit":
        monkeypatch.setattr(repro, "fit", fault(repro.fit))
    else:
        monkeypatch.setattr(repro.GBDTModel, "predict",
                            fault(repro.GBDTModel.predict))
    line, _ = cpu_run(workload)
    assert line["correct"] is False, line["checks"]


def test_run_exits_nonzero_without_an_accelerator():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no accelerator" in proc.stderr
