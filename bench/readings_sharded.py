#!/usr/bin/env python3
"""Readings of the row-sharded cells' reference that their limits are
set from, one process, no chip needed.

    python3 bench/readings_sharded.py --workload higgs.train4 --seeds 1 2 \
        [--readings control shard_left_out]

For each seed the cell's data is made from the seed as a run makes it,
and for the key of the window's first call the reference of Algorithm 1
(:mod:`reference_sharded`) reads what it would read of trees, base
scores and covers put in the program's place:

- ``control``: the reference computed in bfloat16 (inputs rounded,
  sums accumulated in bfloat16); ``control_inputs``: grad and hess
  rounded, exact sums;
- ``single_host_proposal``: the tree and candidates of the single-host
  proposal (:func:`reference.random_candidates`) in place of
  Algorithm 1's;
- ``shard_left_out``: the last worker's rows left out of every level's
  histogram sum, the leaves still summing every row;
- ``leaf_sums_left_out``: the leaf sums not summed over the workers:
  the leaves (values and covers) of the first worker's rows alone, as
  the first chip would return them;
- ``base_sum_left_out``: the label sum of the base score not summed
  over the workers: the first worker's alone, over every row (the tree
  and its covers left sound);
- ``half_rows``: the back half of each worker's rows left out of every
  sum, the base score's included.

``--readings`` picks some of them (all by default), so that several
processes can share a seed's readings.  One JSON line a seed.  The
program's own readings are the ``checks`` of the cell's runs on the
chip; ``PERF.md`` keeps both.
"""

import argparse
import json
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)

import numpy as np  # noqa: E402

import datagen  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
import reference_sharded  # noqa: E402
import traffic  # noqa: E402


READINGS = ("control", "control_inputs", "single_host_proposal",
            "shard_left_out", "leaf_sums_left_out", "base_sum_left_out",
            "half_rows")


def readings(config: dict, workers: int, seed: int,
             names=READINGS) -> dict:
    g = config["gbdt"]
    p = reference.TreeParams(
        max_depth=g["max_depth"], n_candidates=g["n_candidates"],
        l2=g["l2"], gamma=g["gamma"],
        min_child_weight=g["min_child_weight"])
    n, f = int(config["rows"]), int(config["features"])
    x, y = (np.asarray(a) for a in datagen.mixture(
        datagen.seed_key(seed, 0), n=n, f=f, **config["data"]))
    key = np.asarray(traffic.call_key(seed, 0))
    ours = reference_sharded.sharded_candidates(key, x, p.n_candidates,
                                                workers)

    def grow(**kw):
        return reference_sharded.grow_round(x, y, key, p, workers,
                                            cands=ours, **kw) + (ours,)

    def single_host():
        cands = reference.random_candidates(key, x, p.n_candidates)
        return reference_sharded.grow_round(x, y, key, p, workers,
                                            cands=cands) + (cands,)

    def base_left_out():
        tree, _, cover, cands = grow()
        return tree, reference_sharded.base_score(y, worker == 0), cover, \
            cands

    per = -(-n // workers)
    worker = np.arange(n) // per
    planted = {
        "control": lambda: grow(bf16=True),
        "control_inputs": lambda: grow(bf16_inputs=True),
        "single_host_proposal": single_host,
        "shard_left_out": lambda: grow(hist_rows=worker < workers - 1),
        "leaf_sums_left_out": lambda: grow(leaf_rows=worker == 0),
        "base_sum_left_out": base_left_out,
        "half_rows": lambda: grow(rows=np.arange(n) % per < per // 2),
    }
    out = {}
    for name in names:
        tree, base, cover, cands = planted[name]()
        out[name] = reference_sharded.check_round(
            x, y, key, p, tree, cands, workers, base, cover)
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--readings", nargs="+", choices=READINGS,
                   default=list(READINGS))
    args = p.parse_args(argv)
    spec = harness.load_benchmark()
    cell = harness.find_cell(spec, args.workload)
    config = harness.load_config(spec, cell["config"])
    workers = int(harness.load_mix(cell["traffic"])["workers"])
    for seed in args.seeds:
        out = readings(config, workers, seed, args.readings)
        out.update(workload=cell["name"], seed=seed, workers=workers)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
