"""chip_smoke.py's phases on the CPU at tiny sizes, Pallas in interpret
mode, and its refusal to run without a TPU.

The phases are plain functions of their sizes; only ``main()`` asks for
the chip.  Here the "chip" and the CPU reference are the same device, so
these tests pin control flow and checks, not chip numerics.
"""

import importlib.util
import pathlib

import jax
import pytest

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
_spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(smoke)

TINY = dict(rounds=3, depth=3, k=8)


@pytest.fixture(autouse=True)
def no_failed_checks():
    """Every check a phase makes must pass."""
    smoke.FAILURES.clear()
    yield
    assert smoke.FAILURES == []


@pytest.fixture(scope="module")
def cpu():
    return jax.devices("cpu")[0]


@pytest.fixture(scope="module")
def trained():
    smoke.FAILURES.clear()
    out = smoke.phase_train(4096, 1024, 2048, **TINY)
    assert smoke.FAILURES == []       # set up before no_failed_checks runs
    return out


def test_phase_train(trained):
    assert trained["backend"] == "packed"
    assert trained["model"].forest.n_trees == TINY["rounds"]
    assert 0.8 < trained["accuracy"] <= 1.0


def test_phase_oracle(trained, cpu):
    out = smoke.phase_oracle(trained["data"], trained["cfg"], 2048, cpu)
    assert out["accuracy_chip"] == out["accuracy_cpu"]
    assert out["identical_split_share"] == 1.0


def test_phase_serve(trained, cpu, monkeypatch, capsys):
    # serve_gbdt.main turns the compile cache on; tests never do
    monkeypatch.setattr(smoke.serve_gbdt, "enable_compile_cache",
                        lambda: None)
    out = smoke.phase_serve(trained["model"], cpu, rows=64, requests=2,
                            trees=7, depth=3, n_features=5)
    assert set(out) == {"checkpoint", "synthetic raw", "synthetic binned"}
    assert all(v["max_abs_margin_diff"] <= 1e-5 for v in out.values())
    assert "[serve_gbdt]" in capsys.readouterr().out


def test_phase_pallas_interpret(cpu):
    out = smoke.phase_pallas("interpret", cpu, n=256, f=4, nbins=9,
                             n_nodes=4, chunk=3, depth=3, n_features=5)
    assert set(out) == {"hist_levels", "hist_levels_left", "split_gain",
                        "traverse_chunk raw", "traverse_chunk binned"}


def test_phase_distributed_one_device(cpu):
    out = smoke.phase_distributed([cpu], n_train=4096, n_test=1024,
                                  n_slice=2048, **TINY)
    assert out["subtract=False"]["accuracy"] > 0.8
    assert out["subtract=True"]["accuracy"] > 0.8


def test_failed_check_is_recorded(capsys):
    smoke.check(True, "passes")
    smoke.check(False, "broken")
    assert smoke.FAILURES == ["broken"]
    assert "FAILED: broken" in capsys.readouterr().out
    smoke.FAILURES.clear()


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_without_tpu(argv, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main(argv) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
