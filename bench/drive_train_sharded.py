"""Driver of ``train_sharded`` mixes: ``repro.fit_distributed`` calls
back to back, rows sharded over a ``("data",)`` mesh.

The mesh holds the first ``workers`` of the machine's devices (fewer
where fewer are there: one on a CPU, which then runs the cell as a
one-worker mesh).  Set-up makes the configuration's data from the seed
and lays it out row-sharded over the mesh once, so no call copies it
through the host, and warms up with one whole call.  The window is
``drive_train``'s, each call a ``fit_distributed`` on the mesh with a
fresh key.  After it, the reference of Algorithm 1
(:mod:`reference_sharded`) checks the first tree of one call drawn from
the seed, with the call's base score and node covers.
"""

from __future__ import annotations

import dataclasses
import types

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import datagen
import drive_train
import reference
import reference_sharded
import work


class Driver(drive_train.Driver):
    def __init__(self, config: dict, mix: dict, seed: int):
        super().__init__(config, mix, seed)
        repro = self._repro
        if "cover" not in {f.name for f in
                           dataclasses.fields(repro.GBDTModel)}:
            # fail at once: the check reads every node's cover
            raise SystemExit("fit_distributed of this program reports no "
                             "node cover; the cell cannot be checked")
        self.mesh = Mesh(np.array(jax.devices()[:int(mix["workers"])]),
                         ("data",))
        self.workers = self.mesh.size
        # drive_train's window and set-up call ``self._repro.fit``: here
        # that is a fit_distributed on the mesh
        self._repro = types.SimpleNamespace(
            fit=lambda x, y, cfg, key: repro.fit_distributed(
                x, y, cfg, self.mesh, key))

    def make_inputs(self) -> None:
        x, y = datagen.mixture(datagen.seed_key(self.seed, 0), n=self.n,
                               f=self.f, **self.config["data"])
        self.x = jax.device_put(x, NamedSharding(self.mesh, P("data", None)))
        self.y = jax.device_put(y, NamedSharding(self.mesh, P("data")))
        del x, y
        jax.block_until_ready((self.x, self.y))

    def work(self) -> dict:
        """Least work of one round on each chip, which holds a worker's
        share of the rows."""
        n = -(-self.n // self.workers)
        k, d = self.cfg.n_candidates, self.cfg.max_depth
        return {
            "histogram": work.histogram(n, self.f, max_depth=d,
                                        n_candidates=k),
            "binning": work.binning(n, self.f, n_candidates=k),
            "split_gain": work.split_gain(self.f, max_depth=d,
                                          n_candidates=k),
            "round": work.boosting_round(n, self.f, max_depth=d,
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
        cover = np.asarray(model.cover)[0]
        x, y = np.asarray(self.x), np.asarray(self.y)
        del self.x, self.y
        self.calls = []
        return reference_sharded.check_round(
            x, y, np.asarray(key), self.params, tree, cands, self.workers,
            model.base_score, cover)
