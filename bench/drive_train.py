"""Driver of ``train`` mixes: ``repro.fit`` calls back to back.

Set-up makes the configuration's data on the device from the seed and
warms up with one whole ``fit`` call on those shapes.  The window then
runs ``fit`` with a fresh key per call and closes at the first call
boundary at or after ``--seconds``.  After it, the reference checks
the first tree of one call drawn from the seed.
"""

from __future__ import annotations

import time

import jax
import numpy as np

import datagen
import reference
import traffic
import work


class Driver:
    def __init__(self, config: dict, mix: dict, seed: int):
        import repro
        self._repro = repro
        self.config, self.mix, self.seed = config, mix, seed
        self.rounds_per_call = int(mix["rounds_per_call"])
        self.cfg = repro.GBDTConfig(n_trees=self.rounds_per_call,
                                    **config["gbdt"])
        self.n, self.f = int(config["rows"]), int(config["features"])
        self.params = reference.TreeParams(
            max_depth=self.cfg.max_depth, n_candidates=self.cfg.n_candidates,
            l2=self.cfg.l2, gamma=self.cfg.gamma,
            min_child_weight=self.cfg.min_child_weight)
        self.calls: list = []

    def setup(self) -> None:
        self.make_inputs()
        model = self._repro.fit(self.x, self.y, self.cfg,
                                traffic.call_key(self.seed, 0, stream=3))
        jax.block_until_ready(model.forest)

    def make_inputs(self) -> None:
        self.x, self.y = datagen.mixture(
            datagen.seed_key(self.seed, 0), n=self.n, f=self.f,
            **self.config["data"])

    def window(self, seconds: float) -> None:
        fit = self._repro.fit
        self.calls, self.failed = [], 0
        t_open = time.perf_counter()
        for i in range(1 << 62):
            key = traffic.call_key(self.seed, i)
            with jax.profiler.TraceAnnotation("bench.fit"):
                try:
                    model = fit(self.x, self.y, self.cfg, key)
                    jax.block_until_ready(model.forest)
                    self.calls.append((key, model))
                except Exception as e:           # a failed call counts
                    self.failed += 1
                    print(f"fit call {i} failed: {e!r}", flush=True)
            t = time.perf_counter()
            if t - t_open >= seconds:
                break
        self.wall_s = t - t_open
        self.attempted = i + 1

    @property
    def units(self) -> int:
        return len(self.calls) * self.rounds_per_call

    def end_to_end(self) -> dict:
        return {"train_s_per_round": self.wall_s / max(self.units, 1)}

    def work(self) -> dict:
        """Least work of one round, per layer."""
        k, d = self.cfg.n_candidates, self.cfg.max_depth
        return {
            "histogram": work.histogram(self.n, self.f, max_depth=d,
                                        n_candidates=k),
            "binning": work.binning(self.n, self.f, n_candidates=k),
            "split_gain": work.split_gain(self.f, max_depth=d,
                                          n_candidates=k),
            "round": work.boosting_round(self.n, self.f, max_depth=d,
                                         n_candidates=k),
        }

    def check(self) -> dict:
        """The reference's numbers for one call of the window."""
        if not self.calls:
            return {}
        rng = np.random.default_rng(np.random.SeedSequence(
            [self.seed % (1 << 64), 5]))
        key, model = self.calls[int(rng.integers(len(self.calls)))]
        forest = jax.device_get(model.forest)
        tree = reference.HostTree(*(np.asarray(a)[0] for a in forest))
        cands = np.asarray(model.candidates)[0]
        x, y = np.asarray(self.x), np.asarray(self.y)
        del self.x, self.y, model
        self.calls = []
        return reference.check_round(x, y, np.asarray(key), self.params,
                                     tree, cands)
