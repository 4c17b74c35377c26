"""Algorithm 1 end-to-end: distributed GBDT over 8 (forced) host devices.

Each worker samples candidates from its local shard at data-read time;
per boosting round the candidate pools are all-gathered and resampled
with a shared key (the paper's AllReduce-combine-resample); gradient
histograms are psum'd inside the tree builder.  The per-worker loop is
the same single-compile ``lax.scan`` round runner as the single-host
trainer, so each worker traces its round step exactly once.

Run:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python examples/distributed_gbdt.py
"""

import os

if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

import jax                                                      # noqa: E402
import numpy as np                                              # noqa: E402
from jax.sharding import Mesh                                   # noqa: E402

import repro                                                    # noqa: E402
from repro.data import make_dataset                             # noqa: E402
from repro.launch.compile_cache import enable_compile_cache     # noqa: E402


def main() -> None:
    enable_compile_cache()
    print(f"devices: {len(jax.devices())}")
    xtr, ytr, xte, yte, _ = make_dataset("higgs-like", 32_768, 8_192)
    mesh = Mesh(np.array(jax.devices()).reshape(-1), ("data",))

    for strat in ("random", "weighted_quantile"):
        # telemetry=True: per-round TrainReport with psum'd global loss
        # stats and the estimated per-round collective payload
        cfg = repro.GBDTConfig(n_trees=10, max_depth=5,
                               n_candidates=32, strategy=strat,
                               telemetry=True)
        m = repro.fit_distributed(xtr, ytr, cfg, mesh,
                                  jax.random.PRNGKey(0))
        acc = repro.accuracy(m, xte, yte)
        coll = m.report.summarize()["collective_bytes"]
        print(f"  {strat:18s} acc={acc:.4f}  "
              f"({mesh.shape['data']} workers, Algorithm 1)")
        print(f"  {'':18s} loss {float(m.report.train_loss[0]):.4f} -> "
              f"{float(m.report.train_loss[-1]):.4f}, "
              f"~{coll['per_round'] / 1024:.1f} KiB collectives/round "
              f"(all_gather {coll['all_gather_total'] / 1024:.1f} KiB + "
              f"psum {coll['psum_total'] / 1024:.1f} KiB total)")

    # single-host reference
    cfg = repro.GBDTConfig(n_trees=10, max_depth=5, n_candidates=32)
    m1 = repro.fit(xtr, ytr, cfg, jax.random.PRNGKey(0))
    print(f"  {'single-host':18s} acc={repro.accuracy(m1, xte, yte):.4f}")


if __name__ == "__main__":
    main()
