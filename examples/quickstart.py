"""Quickstart: the paper in 60 seconds.

Trains a GBDT with the paper's random split-point proposal and with the
XGBoost-style weighted-quantile sketch on a synthetic SUSY-like dataset,
then prints the accuracy parity + proposal speedup (Table 2's claim) and
the Theorem-1 rank-error curve (Fig. 2's claim).

The trainer is the single-compile ``lax.scan`` round runner: the whole
n_trees-round fit is one compiled program (watch the reported round-step
trace count stay at one per config), and the fitted ensemble comes back
as a stacked :class:`repro.core.tree.Forest`.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import jax

import repro
from repro.core import rank_error                  # research experiment,
from repro.core.boosting import round_trace_count  # trace diagnostic —
from repro.data import make_dataset       # all outside the stable surface
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    print("=== 1. GBDT: random sampling (S) vs quantile sketch (Q) ===")
    xtr, ytr, xte, yte, _ = make_dataset("susy-like", 20_000, 5_000)
    results = {}
    for strat in ("random", "weighted_quantile"):
        cfg = repro.GBDTConfig(n_trees=20, max_depth=6,
                               n_candidates=32, strategy=strat)
        m = repro.fit(xtr, ytr, cfg, jax.random.PRNGKey(0))
        results[strat] = dict(
            acc=repro.accuracy(m, xte, yte),
            fit_s=m.fit_seconds,
            trees=m.forest.n_trees)
    for k, v in results.items():
        print(f"  {k:18s} acc={v['acc']:.4f} "
              f"fit={v['fit_s']:.1f}s forest={v['trees']} trees")
    gap = abs(results['random']['acc']
              - results['weighted_quantile']['acc'])
    print(f"  accuracy gap = {gap:.4f}  (paper: ~0, Table 2)")
    print(f"  round-step traces = {round_trace_count()} "
          f"(one compile per config — O(1) in n_trees)")

    print("\n=== 2. Telemetry: per-round TrainReport ===")
    # telemetry rows ride the same compiled scan (still one round-step
    # trace); the report is a struct-of-arrays of per-round scalars
    cfg = repro.GBDTConfig(n_trees=10, max_depth=5, n_candidates=32,
                           telemetry=True)
    m = repro.fit(xtr, ytr, cfg, jax.random.PRNGKey(0))
    rep = m.report
    s = rep.summarize()
    print(f"  round  loss    grad_norm  splits  best_gain")
    for r in (0, rep.n_rounds // 2, rep.n_rounds - 1):
        print(f"  {r:5d}  {float(rep.train_loss[r]):.4f}  "
              f"{float(rep.grad_norm[r]):9.2f}  "
              f"{int(rep.n_splits[r]):6d}  "
              f"{float(rep.best_gain_max[r]):9.2f}")
    print(f"  loss {s['train_loss']['first']:.4f} -> "
          f"{s['train_loss']['final']:.4f} over {s['n_rounds']} rounds, "
          f"{s['splits']['total']} splits realized")

    print("\n=== 3. Theorem 1: E[rank error] = 1/(k+1) ===")
    out = rank_error.fig2_experiment(seed=0, n=1024, ks=[4, 16, 64],
                                     trials=16)
    print(f"  {'k':>4} {'random':>8} {'quantile':>9} {'1/(k+1)':>8}")
    for k, r, q, t in zip(out["k"], out["random"], out["quantile"],
                          out["theory"]):
        print(f"  {k:4d} {r:8.4f} {q:9.4f} {t:8.4f}")
    print("  -> quantile binning is no better than random (the claim).")


if __name__ == "__main__":
    main()
