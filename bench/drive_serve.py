"""Driver of ``serve`` mixes: scoring requests through ``GBDTModel.predict``.

Set-up makes the configuration's rows from the seed, a block at a time
on the device, into a pool on the host (requests come from a client),
makes the forest on the device, and warms up every request size of the
mix.  In the window one client sends each request as soon as the
previous one's margins are ready, and each is timed from its start to
``block_until_ready`` on its margins; the window closes at the first
request boundary at or after ``--seconds``.  After it, the
reference scores a sample of the served requests drawn from the seed,
the largest among them.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
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
        self.n, self.f = int(config["rows"]), int(config["features"])
        self.forest_cfg = config["forest"]
        self.served: list = []

    def setup(self) -> None:
        self.make_inputs()
        for rows in traffic.request_sizes(self.mix):
            for _ in range(2):
                self.model.predict(self.pool[:rows],
                                   output="margin").block_until_ready()

    def make_inputs(self) -> None:
        repro = self._repro
        fc = self.forest_cfg
        x, y = datagen.mixture_host(datagen.seed_key(self.seed, 0),
                                    n=self.n, f=self.f, **self.config["data"])
        arrays = datagen.forest(
            datagen.seed_key(self.seed, 4), x, n_trees=fc["n_trees"],
            max_depth=fc["max_depth"], k=fc["n_candidates"],
            passthrough_frac=fc["passthrough_frac"],
            leaf_scale=fc["leaf_scale"])
        cands, feature, split_bin, threshold, leaf = arrays
        p = float(np.clip(np.mean(y, dtype=np.float64),
                          1e-6, 1 - 1e-6))
        self.base_score = float(np.log(p / (1 - p)))
        self.cfg = repro.GBDTConfig(
            n_trees=fc["n_trees"], max_depth=fc["max_depth"],
            n_candidates=fc["n_candidates"],
            learning_rate=fc["learning_rate"], repropose_each_round=False)
        self.model = repro.GBDTModel(
            config=self.cfg,
            forest=repro.Forest(feature=feature, split_bin=split_bin,
                                threshold=threshold, leaf_value=leaf),
            base_score=self.base_score, candidates=jnp.asarray(cands)[None])
        self.host_forest = jax.device_get((feature, threshold, leaf))
        self.pool = x

    def window(self, seconds: float) -> None:
        predict = self.model.predict
        pool = self.pool
        self.served, self.latencies, self.failed = [], [], 0
        self.attempted = 0
        t_open = time.perf_counter()
        for req in traffic.requests(self.mix, self.seed, self.n):
            with jax.profiler.TraceAnnotation("bench.prepare"):
                xb = pool[req.start:req.start + req.rows]
            self.attempted += 1
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.request"):
                try:
                    m = predict(xb, output="margin")
                    m.block_until_ready()
                    self.served.append((req, m))
                except Exception as e:           # a failed request counts
                    self.failed += 1
                    print(f"request {req.index} failed: {e!r}", flush=True)
            t1 = time.perf_counter()
            self.latencies.append(t1 - t0)
            if t1 - t_open >= seconds:
                break
        self.wall_s = t1 - t_open

    @property
    def units(self) -> int:
        return len(self.served)

    def end_to_end(self) -> dict:
        rows = sum(req.rows for req, _ in self.served)
        lat_ms = 1e3 * np.asarray(self.latencies)
        return {"serve_rows_per_s": rows / self.wall_s,
                "serve_p95_ms": float(np.percentile(lat_ms, 95))}

    def work(self) -> dict:
        """Least work of one request (the window's mean), per layer."""
        fc = self.forest_cfg
        n = max(len(self.served), 1)
        kw = dict(n_trees=fc["n_trees"], max_depth=fc["max_depth"])
        trav = sum((work.traversal(req.rows, self.f, **kw)
                    for req, _ in self.served), work.Work(0.0, 0.0))
        full = sum((work.request(req.rows, self.f, **kw)
                    for req, _ in self.served), work.Work(0.0, 0.0))
        return {"traversal": trav * (1.0 / n), "request": full * (1.0 / n)}

    def sample(self):
        """Margins and rows of the requests the reference compares: a
        sample of the served ones drawn from the seed, with the largest.
        Frees the program's state."""
        want = int(self.mix["check_requests"])
        rng = np.random.default_rng(np.random.SeedSequence(
            [self.seed % (1 << 64), 6]))
        largest = max(range(len(self.served)),
                      key=lambda i: self.served[i][0].rows)
        others = [i for i in range(len(self.served)) if i != largest]
        k = min(want - 1, len(others))
        pick = [largest] + (rng.choice(others, k, replace=False).tolist()
                            if k > 0 else [])
        got = np.concatenate([np.asarray(self.served[i][1]) for i in pick])
        rows = np.concatenate([
            self.pool[r.start:r.start + r.rows]
            for r in (self.served[i][0] for i in pick)])
        self.served, self.model = [], None
        return got, rows

    def reference_margins(self, rows, *, bf16: bool = False):
        feature, threshold, leaf = self.host_forest
        return reference.forest_margins(
            rows, feature, threshold, leaf, self.base_score,
            self.cfg.learning_rate, self.cfg.max_depth, bf16=bf16)

    def check(self) -> dict:
        """The reference's widest margin gap over a sample of requests."""
        if not self.served:
            return {}
        got, rows = self.sample()
        ref = self.reference_margins(rows)
        gap = np.abs(got.astype(np.float64) - ref)
        return {"margin_gap": float(np.max(gap)) if np.all(np.isfinite(got))
                else float("inf")}
