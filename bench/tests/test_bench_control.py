"""The control fails each cell's check; the program passes it.

The control is the reference put in the program's place with the
values a later change would be tempted to keep in bfloat16; the
training cell is also held against planted faults.  Here at a size a
test run holds, with the limits the cells use on the chip.
"""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import pytest  # noqa: E402

import drive_serve  # noqa: E402
import drive_train  # noqa: E402
import harness  # noqa: E402
import readings  # noqa: E402

SEEDS = [2 ** 31 + 1, 2 ** 32 + 2, 3]


def _cell(name, rows, **forest):
    spec = harness.load_benchmark()
    cell = harness.find_cell(spec, name)
    config = harness.load_config(spec, cell["config"])
    config["rows"] = rows
    config.get("forest", {}).update(forest)
    return config, harness.load_mix(cell["traffic"]), harness.load_limits(name)


def _fails(numbers, limits):
    return any(numbers[k] > limits[k] for k in limits if k in numbers)


@pytest.mark.parametrize("seed", SEEDS)
def test_train_control_and_faults_fail_the_program_passes(seed):
    config, mix, limits = _cell("susy.train", 20000)
    out = readings.train_readings(drive_train.Driver(config, mix, seed),
                                  control=True)
    assert not _fails(out["program"], limits), out["program"]
    assert _fails(out["control"], limits), out["control"]
    assert _fails(out["half_batch"], limits), out["half_batch"]
    if "altered_split" in out:
        assert _fails(out["altered_split"], limits), out["altered_split"]


@pytest.mark.parametrize("seed", SEEDS)
def test_serve_control_fails_the_program_passes(seed):
    config, mix, limits = _cell("mirai.serve", 8192, n_trees=60)
    out = readings.serve_readings(drive_serve.Driver(config, mix, seed),
                                  control=True, seconds=0.2)
    assert not _fails(out["program"], limits), out["program"]
    assert _fails(out["control"], limits), out["control"]
