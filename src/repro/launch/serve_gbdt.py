"""GBDT serving entry point: microbatched batched-forest inference.

Drives the level-synchronous inference engine
(:mod:`repro.core.predict`) the way a serving process would: a stream
of fixed-size microbatches through ONE warmed-up compiled traversal,
per-request wall-clock latencies, p50/p99 + rows/s summarized as a
:class:`repro.obs.PredictReport`.

Usage:
  PYTHONPATH=src python -m repro.launch.serve_gbdt \
      --trees 500 --depth 6 --features 32 --microbatch 4096 \
      --requests 32 --backend auto [--binned] [--ckpt model.npz] \
      [--data-shards N] [--json predict_report.json]

With ``--ckpt`` the model comes from :func:`repro.checkpoint.load_gbdt`
(the full serving round-trip); otherwise a synthetic forest of the
requested shape is built — serving performance depends on tree count /
depth / row count, not on the leaf values being meaningful.

``--data-shards`` lays each microbatch out row-sharded across a
``(data, model)`` debug mesh (:func:`repro.launch.mesh.make_debug_mesh`)
before predicting — the engine is elementwise in rows, so jit
partitions the traversal without any annotation in the model code.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..core import boosting, tree as tree_lib
from ..core.predict import DEFAULT_TREE_CHUNK
from ..obs import PredictReport
from . import mesh as mesh_lib
from .compile_cache import enable_compile_cache


def synthetic_gbdt(*, n_trees: int, max_depth: int, n_features: int,
                   n_candidates: int = 32, seed: int = 0,
                   passthrough_frac: float = 0.1,
                   **config_overrides) -> boosting.GBDTModel:
    """A random-but-valid GBDTModel of the requested shape.

    Valid means the trained-model invariants hold, so every predict
    path (raw, binned, oracle scan) agrees on it: candidates are a
    fixed sorted grid, each internal node's threshold IS
    ``candidates[feature, split_bin]``, and passthrough nodes carry the
    (-1, +inf, last-bin) sentinel triple.  Used by the serving
    entry point and ``benchmarks/bench_predict.py`` — inference cost
    depends on the forest's shape, not on how it was fit.
    """
    rng = np.random.default_rng(seed)
    f, k = n_features, n_candidates
    n_inner, n_leaves = 2 ** max_depth - 1, 2 ** max_depth
    cands = np.sort(rng.normal(size=(f, k)).astype(np.float32), axis=1)

    feature = rng.integers(0, f, size=(n_trees, n_inner)).astype(np.int32)
    split_bin = rng.integers(0, k, size=(n_trees, n_inner)).astype(np.int32)
    passthrough = rng.random(size=(n_trees, n_inner)) < passthrough_frac
    feature = np.where(passthrough, -1, feature).astype(np.int32)
    split_bin = np.where(passthrough, k, split_bin).astype(np.int32)
    threshold = cands[feature.clip(0), split_bin.clip(max=k - 1)]
    threshold = np.where(passthrough, np.inf, threshold).astype(np.float32)
    leaf_value = (0.1 * rng.normal(size=(n_trees, n_leaves))
                  ).astype(np.float32)

    cfg = boosting.GBDTConfig(
        n_trees=n_trees, max_depth=max_depth, n_candidates=k,
        repropose_each_round=False, **config_overrides)
    forest = tree_lib.Forest(
        feature=jnp.asarray(feature), split_bin=jnp.asarray(split_bin),
        threshold=jnp.asarray(threshold), leaf_value=jnp.asarray(leaf_value))
    return boosting.GBDTModel(config=cfg, forest=forest, base_score=0.0,
                              candidates=jnp.asarray(cands)[None])


def serve(model: boosting.GBDTModel, *, microbatch: int = 4096,
          n_requests: int = 32, binned: bool = False,
          backend: str | None = None, tree_chunk: int | None = None,
          data_shards: int = 0, seed: int = 0,
          output: str = "margin") -> PredictReport:
    """Run the microbatched serving loop and return its telemetry.

    Warmup: the first microbatch is predicted twice before timing
    starts — that traces + compiles the traversal (and, binned, the
    binning) so every measured request hits the executable cache.
    """
    cfg = model.config
    n_features = model.candidates.shape[1]      # (rounds, f, k)
    rng = np.random.default_rng(seed)
    batches = [rng.normal(size=(microbatch, n_features)).astype(np.float32)
               for _ in range(n_requests)]

    sharding = None
    if data_shards:
        m = mesh_lib.make_debug_mesh(n_data=data_shards, n_model=1)
        sharding = jax.sharding.NamedSharding(
            m, jax.sharding.PartitionSpec("data"))

    def request(xb: np.ndarray) -> jax.Array:
        if sharding is not None:
            xb = jax.device_put(xb, sharding)
        return model.predict(xb, output=output, binned=binned,
                             backend=backend, tree_chunk=tree_chunk)

    # warmup: compile the whole request path outside the timed loop
    for _ in range(2):
        request(batches[0]).block_until_ready()

    lat = np.empty((n_requests,), np.float64)
    for i, xb in enumerate(batches):
        t0 = time.perf_counter()
        request(xb).block_until_ready()
        lat[i] = time.perf_counter() - t0

    return PredictReport(
        latencies_s=lat, rows_per_request=microbatch,
        engine={
            "n_trees": cfg.n_trees, "max_depth": cfg.max_depth,
            "n_features": int(n_features),
            "tree_chunk": tree_chunk or DEFAULT_TREE_CHUNK,
            "backend": backend or cfg.backend, "binned": bool(binned),
            "data_shards": int(data_shards),
        })


def main(argv=None) -> PredictReport:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--ckpt", default=None,
                   help="serve a checkpointed model (repro.checkpoint)")
    p.add_argument("--trees", type=int, default=500)
    p.add_argument("--depth", type=int, default=6)
    p.add_argument("--features", type=int, default=32)
    p.add_argument("--candidates", type=int, default=32)
    p.add_argument("--microbatch", type=int, default=4096)
    p.add_argument("--requests", type=int, default=32)
    p.add_argument("--backend", default=None,
                   help="auto|pallas|interpret|ref|packed")
    p.add_argument("--tree-chunk", type=int, default=None)
    p.add_argument("--binned", action="store_true",
                   help="traverse on bin ids (binning timed per request)")
    p.add_argument("--data-shards", type=int, default=0,
                   help="row-shard each microbatch over a debug mesh")
    p.add_argument("--output", default="margin",
                   choices=["margin", "proba", "label"])
    p.add_argument("--json", default=None,
                   help="write the PredictReport JSON here")
    args = p.parse_args(argv)
    enable_compile_cache()

    if args.ckpt:
        from ..checkpoint import load_gbdt
        model = load_gbdt(args.ckpt)
    else:
        model = synthetic_gbdt(n_trees=args.trees, max_depth=args.depth,
                               n_features=args.features,
                               n_candidates=args.candidates)

    report = serve(model, microbatch=args.microbatch,
                   n_requests=args.requests, binned=args.binned,
                   backend=args.backend, tree_chunk=args.tree_chunk,
                   data_shards=args.data_shards, output=args.output)
    s = report.summarize()
    print(f"[serve_gbdt] {report.engine['n_trees']} trees x depth "
          f"{report.engine['max_depth']} | {s['rows_per_request']} rows/req "
          f"x {s['n_requests']} req | backend={report.engine['backend']}"
          f"{' binned' if report.engine['binned'] else ''}", flush=True)
    print(f"[serve_gbdt] {s['rows_per_s']:,.0f} rows/s | p50 "
          f"{s['latency_ms']['p50']:.2f} ms | p99 "
          f"{s['latency_ms']['p99']:.2f} ms", flush=True)
    if args.json:
        report.to_json(args.json)
        print(f"[serve_gbdt] wrote {args.json}", flush=True)
    return report


if __name__ == "__main__":
    main()
