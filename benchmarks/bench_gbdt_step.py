"""§Perf pair 3 — the paper's own workload: one distributed-GBDT boosting
round, decomposed into proposal / binning / histogram / split stages with
REAL wall-clock timings (CPU backend; the only measurable pair in this
container) plus the hillclimb variants:

  hist-v0  scatter-add histogram (ref.py — GPU-style formulation)
  hist-v1  one-hot matmul, fp32  (the Pallas kernel's TPU formulation,
           executed through XLA:CPU as a dense contraction)
  hist-v2  v1 with bins pre-packed to uint8 (less index traffic)

and proposal random vs weighted-quantile vs GK (Table-2 T columns),
plus the headline trainer comparison: the single-compile lax.scan fit
(direct and histogram-subtraction growth) vs the unrolled per-round
reference loop (n_trees=50, max_depth=6).  Warm timings are
median-of-k (k>=5) interleaved repeats with the min/max spread;
wall-clock, round-step trace counts and the measured scatter-update
telemetry are written to ``BENCH_gbdt_step.json``.

``--smoke`` runs a tiny CI-sized workload instead and asserts the two
hard invariants (one round-step trace per scanned fit; subtraction
issues strictly fewer scatter updates than direct growth).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import binning, boosting, proposal, tree as tree_lib
from repro.kernels import ops, ref
from repro.launch.compile_cache import enable_compile_cache

_JSON_PATH = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_gbdt_step.json")


def _time(fn, reps=3):
    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / reps * 1e6


def _hist_onehot(bins, node, gh, n_nodes, nbins):
    """One-hot matmul formulation (what the Pallas kernel does per tile)."""
    n, f = bins.shape
    idx = node[:, None] * nbins + bins                 # (n, f)
    width = n_nodes * nbins
    onehot = jax.nn.one_hot(idx, width, dtype=jnp.float32)   # (n, f, W)
    out = jnp.einsum("nfw,nc->fwc", onehot, gh)
    return out.reshape(f, n_nodes, nbins, 2).transpose(1, 0, 2, 3)


def run(csv_rows: list, *, update_json: bool = True) -> None:
    key = jax.random.PRNGKey(0)
    n, f, k = 200_000, 16, 32
    nbins = k + 1
    depth_nodes = 16
    x = jax.random.normal(key, (n, f))
    g = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    h = jnp.abs(jax.random.normal(jax.random.fold_in(key, 2), (n,)))
    gh = jnp.stack([g, h], 1)
    node = jax.random.randint(jax.random.fold_in(key, 3), (n,), 0,
                              depth_nodes)

    # stage timings
    t_prop = _time(lambda: jax.block_until_ready(
        proposal.random_candidates(key, x, k)))
    csv_rows.append((f"gbdt_step/proposal_random", t_prop, f"n={n} f={f}"))
    cand = proposal.random_candidates(key, x, k)
    t_bin = _time(lambda: jax.block_until_ready(
        binning.bin_features(x, cand)))
    csv_rows.append((f"gbdt_step/binning", t_bin, ""))
    bins = binning.bin_features(x, cand)

    hist_fns = {
        "hist_v0_scatter": jax.jit(lambda b, nd, s: ref.hist_ref(
            b, nd, s, n_nodes=depth_nodes, nbins=nbins)),
        "hist_v1_onehot": jax.jit(lambda b, nd, s: _hist_onehot(
            b, nd, s, depth_nodes, nbins)),
    }
    outs = {}
    for name, fn in hist_fns.items():
        t = _time(lambda fn=fn: jax.block_until_ready(fn(bins, node, gh)))
        outs[name] = fn(bins, node, gh)
        csv_rows.append((f"gbdt_step/{name}", t,
                         f"{n / (t / 1e6) / 1e6:.1f}M rows/s"))
    err = float(jnp.abs(outs["hist_v0_scatter"]
                        - outs["hist_v1_onehot"]).max())
    csv_rows.append(("gbdt_step/hist_v0_vs_v1_err", 0.0, f"{err:.2e}"))

    # v2: uint8 bins
    bins8 = bins.astype(jnp.uint8)
    fn8 = jax.jit(lambda b, nd, s: ref.hist_ref(
        b.astype(jnp.int32), nd, s, n_nodes=depth_nodes, nbins=nbins))
    t8 = _time(lambda: jax.block_until_ready(fn8(bins8, node, gh)))
    csv_rows.append((f"gbdt_step/hist_v2_uint8bins", t8,
                     f"{n / (t8 / 1e6) / 1e6:.1f}M rows/s"))

    # v3: complex64-packed scatter (the 'packed' backend — CPU default)
    fnp = jax.jit(lambda b, nd, s: ref.hist_packed(
        b, nd, s, n_nodes=depth_nodes, nbins=nbins))
    tp = _time(lambda: jax.block_until_ready(fnp(bins, node, gh)))
    errp = float(jnp.abs(outs["hist_v0_scatter"]
                         - fnp(bins, node, gh)).max())
    csv_rows.append((f"gbdt_step/hist_v3_packed", tp,
                     f"{n / (tp / 1e6) / 1e6:.1f}M rows/s "
                     f"err_vs_v0={errp:.1e}"))

    # v4: level-batched packed scatter — the HistSpec entry point with
    # L=5 node assignments of the same rows in ONE complex64 scatter
    # (what a depth-5 grower pays per level, amortised across levels
    # when node ids are known up front).
    L = 5
    node_lvls = jnp.stack([
        jax.random.randint(jax.random.fold_in(key, 40 + l), (n,), 0,
                           depth_nodes) for l in range(L)])
    spec_l = ops.HistSpec(n_nodes=depth_nodes, nbins=nbins, n_levels=L,
                          backend="packed")
    fnl = jax.jit(lambda b, nd, s: ops.hist_levels(b, nd, s, spec_l))
    tl = _time(lambda: jax.block_until_ready(fnl(bins, node_lvls, gh)))
    csv_rows.append((f"gbdt_step/hist_v4_levels{L}_packed", tl,
                     f"{tl / L:.0f}us/level "
                     f"{n * L / (tl / 1e6) / 1e6:.1f}M row-levels/s"))

    # whole tree level (hist + split) through the HistSpec API
    spec5 = ops.HistSpec(n_nodes=depth_nodes, nbins=nbins, n_levels=5)
    t_level = _time(lambda: jax.block_until_ready(tree_lib.build_tree(
        bins, gh, cand, max_depth=5, spec=spec5)))
    csv_rows.append(("gbdt_step/full_tree_depth5", t_level, ""))

    # ------------------------------------------------------------------
    # Headline: single-compile scanned fit (direct and subtraction
    # growth) vs unrolled reference loop.  n_trees=50, max_depth=6 — the
    # acceptance workload.  The baseline is pinned to backend='ref' so
    # fit_reference follows the SEED's exact execution path (the
    # unrolled loop with the scatter hist, which is what backend='auto'
    # resolved to on CPU before this change); the scanned fits use the
    # default 'auto' (-> 'packed' on CPU).  'cold' includes
    # trace+compile; 'warm' is MEDIAN-of-k (k >= 5) over interleaved
    # refits with every jit cache hot, reported with the [min, max]
    # spread (interleaving so container CPU noise hits all trainers
    # alike).
    # ------------------------------------------------------------------
    nf, ff = 10_000, 16
    warm_reps = 7
    kf = jax.random.fold_in(key, 100)
    xf = jax.random.normal(kf, (nf, ff))
    wf = jax.random.normal(jax.random.fold_in(kf, 1), (ff,))
    yf = (xf @ wf > 0).astype(jnp.float32)
    cfg = boosting.GBDTConfig(n_trees=50, max_depth=6, n_candidates=32)
    cfg_seed = boosting.GBDTConfig(n_trees=50, max_depth=6,
                                   n_candidates=32, backend="ref")
    cfg_sub = dataclasses.replace(cfg, subtract=True)

    def fit_s(fn, c):
        t0 = time.perf_counter()
        m = fn(xf, yf, c, jax.random.PRNGKey(0))
        return time.perf_counter() - t0, m

    def med_spread(ts):
        return (round(statistics.median(ts), 4),
                [round(min(ts), 4), round(max(ts), 4)])

    # telemetry-enabled fit rides the same warm loop: per-round
    # TrainReport rows on the scan; the overhead vs the plain scanned
    # fit is the price of observability (must stay small — the report is
    # a handful of scalars per round next to the histogram work).
    cfg_tel = dataclasses.replace(cfg, telemetry=True)
    tr0 = boosting.round_trace_count()
    ref_cold, _ = fit_s(boosting.fit_reference, cfg_seed)
    scan_cold, _ = fit_s(boosting.fit, cfg)
    scan_traces = boosting.round_trace_count() - tr0
    tr0 = boosting.round_trace_count()
    sub_cold, _ = fit_s(boosting.fit, cfg_sub)
    sub_traces = boosting.round_trace_count() - tr0
    fit_s(boosting.fit, cfg_tel)               # compile (separate config)
    ref_warm, scan_warm, sub_warm, tel_warm = [], [], [], []
    for _ in range(warm_reps):
        t, m_ref = fit_s(boosting.fit_reference, cfg_seed)
        ref_warm.append(t)
        t, m_scan = fit_s(boosting.fit, cfg)
        scan_warm.append(t)
        t, m_sub = fit_s(boosting.fit, cfg_sub)
        sub_warm.append(t)
        t, m_tel = fit_s(boosting.fit, cfg_tel)
        tel_warm.append(t)
    ref_med, ref_spread = med_spread(ref_warm)
    scan_med, scan_spread = med_spread(scan_warm)
    sub_med, sub_spread = med_spread(sub_warm)
    tel_med, _ = med_spread(tel_warm)
    acc_gap = abs(boosting.accuracy(m_scan, xf, yf)
                  - boosting.accuracy(m_ref, xf, yf))
    acc_gap_sub = abs(boosting.accuracy(m_sub, xf, yf)
                      - boosting.accuracy(m_ref, xf, yf))
    tel_overhead_pct = 100 * (tel_med / scan_med - 1)
    csv_rows.append(("gbdt_step/fit50_telemetry_warm", tel_med * 1e6,
                     f"overhead={tel_overhead_pct:+.1f}% vs scanned"))

    # measured scatter updates, direct vs subtraction (one telemetry'd
    # subtract fit outside the timed loop; the counter is exact, not
    # timing-sensitive)
    _, m_sub_tel = fit_s(boosting.fit,
                         dataclasses.replace(cfg_sub, telemetry=True))
    upd_direct = float(np.asarray(m_tel.report.hist_updates).sum())
    upd_sub = float(np.asarray(m_sub_tel.report.hist_updates).sum())

    csv_rows.append(("gbdt_step/fit50_reference_warm", ref_med * 1e6,
                     f"cold={ref_cold:.2f}s "
                     f"spread=[{ref_spread[0]},{ref_spread[1]}]s"))
    csv_rows.append(("gbdt_step/fit50_scanned_warm", scan_med * 1e6,
                     f"cold={scan_cold:.2f}s traces={scan_traces} "
                     f"spread=[{scan_spread[0]},{scan_spread[1]}]s"))
    csv_rows.append(("gbdt_step/fit50_subtract_warm", sub_med * 1e6,
                     f"cold={sub_cold:.2f}s traces={sub_traces} "
                     f"{100 * (1 - sub_med / scan_med):+.1f}% vs direct "
                     f"updates {upd_sub:.0f}/{upd_direct:.0f}"))
    if not update_json:
        csv_rows.append(("gbdt_step/fit50", 0.0,
                         "(dry run: BENCH_gbdt_step.json NOT updated)"))
        return

    rec = {
        "workload": {"n": nf, "f": ff, "n_trees": cfg.n_trees,
                     "max_depth": cfg.max_depth,
                     "n_candidates": cfg.n_candidates,
                     "strategy": cfg.strategy,
                     "platform": jax.default_backend(),
                     "baseline_backend": "ref",
                     "scanned_backend": ops.resolve(cfg.backend)},
        "timing_protocol": {"warm_reps": warm_reps, "stat": "median",
                            "spread": "min_max",
                            "interleaved": True},
        "reference_fit_s": {"cold": round(ref_cold, 4),
                            "warm": ref_med, "warm_spread": ref_spread},
        "scanned_fit_s": {"cold": round(scan_cold, 4),
                          "warm": scan_med, "warm_spread": scan_spread},
        "subtract_fit_s": {"cold": round(sub_cold, 4),
                           "warm": sub_med, "warm_spread": sub_spread},
        "warm_speedup": round(ref_med / scan_med, 3),
        "warm_reduction_pct": round(100 * (1 - scan_med / ref_med), 1),
        "cold_reduction_pct": round(100 * (1 - scan_cold / ref_cold), 1),
        "subtract_vs_direct_warm_pct": round(
            100 * (1 - sub_med / scan_med), 1),
        "round_step_traces_scanned_fit": scan_traces,
        "round_step_traces_subtract_fit": sub_traces,
        "accuracy_gap_scan_vs_ref": round(acc_gap, 6),
        "accuracy_gap_subtract_vs_ref": round(acc_gap_sub, 6),
        "scatter_updates": {
            "direct_total": upd_direct,
            "subtract_total": upd_sub,
            "reduction_ratio": round(upd_direct / upd_sub, 3),
            "note": "measured per-fit scatter updates (rows x features "
                    "summed over levels and rounds) from "
                    "TrainReport.hist_updates",
        },
        "telemetry": {
            "warm_fit_s": tel_med,
            "overhead_pct_vs_scanned_warm": round(tel_overhead_pct, 1),
            "summary": m_tel.report.summarize(),
        },
    }
    with open(_JSON_PATH, "w") as fh:
        json.dump(rec, fh, indent=1)


def smoke() -> None:
    """CI-sized invariant check (seconds, not minutes): one round-step
    trace per scanned fit, and subtraction growth must issue strictly
    fewer scatter updates than direct growth while fitting the exact
    same forest.  Exits non-zero via AssertionError on violation."""
    key = jax.random.PRNGKey(0)
    n, f = 2000, 6
    x = jax.random.normal(key, (n, f))
    w = jax.random.normal(jax.random.fold_in(key, 1), (f,))
    y = (x @ w > 0).astype(jnp.float32)
    fits = {}
    for name, sub in (("direct", False), ("subtract", True)):
        cfg = boosting.GBDTConfig(n_trees=8, max_depth=4, n_candidates=8,
                                  subtract=sub, telemetry=True)
        tr0 = boosting.round_trace_count()
        m = boosting.fit(x, y, cfg, jax.random.PRNGKey(0))
        traces = boosting.round_trace_count() - tr0
        assert traces == 1, \
            f"{name}: round_step_traces_scanned_fit={traces}, want 1"
        fits[name] = (m, float(np.asarray(m.report.hist_updates).sum()))
    (m_dir, upd_dir), (m_sub, upd_sub) = fits["direct"], fits["subtract"]
    assert 0 < upd_sub < upd_dir, \
        f"subtract updates {upd_sub} not strictly below direct {upd_dir}"
    for a, b in zip(m_dir.forest, m_sub.forest):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    print(f"SMOKE OK: traces=1/fit, scatter updates direct={upd_dir:.0f} "
          f"subtract={upd_sub:.0f} ({upd_dir / upd_sub:.2f}x), "
          "forests identical")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--update", action="store_true",
                    help="write the fit50 record to BENCH_gbdt_step.json "
                         "(default: dry run, print timings only)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI invariant check (trace count, scatter-"
                         "update reduction); no timings, no JSON write")
    args = ap.parse_args()
    enable_compile_cache()
    if args.smoke:
        smoke()
        return
    rows: list = []
    run(rows, update_json=args.update)
    for name, us, note in rows:
        print(f"{name:40s} {us:12.1f} us  {note}")
    if args.update:
        print(f"updated {os.path.abspath(_JSON_PATH)}")


if __name__ == "__main__":
    main()
