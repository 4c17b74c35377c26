#!/usr/bin/env python3
"""Readings that a cell's limits are set from, in one process.

    python3 bench/readings.py --workload <name> --seeds 1 2 ... \\
        [--control-seeds 1 2 3] [--seconds 2]

For each seed the cell's inputs are made from the seed, the timed entry
runs as in a run's window (one ``fit`` call; requests for ``--seconds``)
and the reference reads what it produced.  On the control seeds the
reference is also put in the program's place computed with bfloat16
values (the control) and, for training, planted faults are read: the
tree grown on half of the rows, and the program's tree with its root
split moved by one bin.  One JSON line a seed.  The benchmark's own runs
never run this; ``PERF.md`` keeps what it read.
"""

import argparse
import dataclasses
import json
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)
sys.path.insert(1, os.path.join(os.path.dirname(_HERE), "src"))

import numpy as np  # noqa: E402

import harness  # noqa: E402
import reference  # noqa: E402


def train_readings(driver, control: bool) -> dict:
    driver.make_inputs()
    driver.window(0.0)
    key, model = driver.calls[0]
    key = np.asarray(key)
    tree = reference.HostTree(*(np.asarray(a)[0] for a in model.forest))
    x, y = np.asarray(driver.x), np.asarray(driver.y)
    out = {"program": driver.check()}
    if control:
        p = driver.params
        cands = reference.random_candidates(key, x, p.n_candidates)

        def read(t):
            return reference.check_round(x, y, key, p, t, cands)

        out["control"] = read(reference.grow_round(x, y, key, p, bf16=True))
        out["control_inputs"] = read(reference.grow_round(
            x, y, key, p, bf16_inputs=True))
        out["half_batch"] = read(reference.grow_round(
            x, y, key, p, rows=np.arange(0, len(y), 2)))
        moved = dataclasses.replace(tree, split_bin=tree.split_bin.copy(),
                                    threshold=tree.threshold.copy())
        if moved.feature[0] >= 0:
            s = moved.split_bin[0]
            moved.split_bin[0] = s + 1 if s + 1 < p.n_candidates else s - 1
            moved.threshold[0] = cands[moved.feature[0], moved.split_bin[0]]
            out["altered_split"] = read(moved)
    return out


def serve_readings(driver, control: bool, seconds: float) -> dict:
    driver.setup()
    driver.window(seconds)
    got, rows = driver.sample()
    ref = driver.reference_margins(rows)
    out = {"program": {"margin_gap": float(np.max(np.abs(got - ref)))},
           "rows": len(rows)}
    if control:
        ctrl = driver.reference_margins(rows, bf16=True)
        out["control"] = {"margin_gap": float(np.max(np.abs(ctrl - ref)))}
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--control-seeds", type=int, nargs="*", default=[])
    p.add_argument("--seconds", type=float, default=2.0)
    args = p.parse_args(argv)
    spec = harness.load_benchmark()
    cell = harness.find_cell(spec, args.workload)
    devices = harness.require_accelerator(int(cell["chips"]))
    harness.enable_cache()
    config = harness.load_config(spec, cell["config"])
    mix = harness.load_mix(cell["traffic"])
    driver_cls = __import__(f"drive_{mix['kind']}").Driver
    for seed in args.seeds:
        driver = driver_cls(config, mix, seed)
        control = seed in args.control_seeds
        if mix["kind"] == "train":
            out = train_readings(driver, control)
        else:
            out = serve_readings(driver, control, args.seconds)
        out.update(workload=cell["name"], seed=seed,
                   device=devices[0].device_kind)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
