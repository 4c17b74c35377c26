"""§Perf pair 4 — forest inference: the batched level-synchronous
traversal engine (repro.core.predict) vs the seed per-tree ``lax.scan``
predictor, at the acceptance workload (500 trees x depth 6, CPU):

  scan_baseline   sequential per-tree scan (tree._forest_predict_scan,
                  the seed forest_predict_raw) — n_trees dependent
                  dispatch chains of max_depth gathers each
  engine_raw      level-synchronous chunked traversal on raw floats
                  (ONE fused gather+compare per depth level per chunk)
  engine_binned   same engine on pre-binned uint8->int32 bin ids
                  (binning done once outside the timed loop, the
                  serving amortisation)

Each variant is timed as warm full-batch predicts (median semantics
live in the PredictReport percentiles; requests are interleaved-free
full repeats after a 2x warmup).  Wall-clock, rows/s, p50/p99 and the
traversal trace count are written to ``BENCH_predict.json`` with
``--update``.

``--smoke`` runs a tiny CI-sized check instead and asserts the two hard
invariants: ONE traversal-chunk trace per fresh compiled predict
regardless of n_trees (and zero on repeat calls), and the batched
engine bit-identical to the per-tree scan oracle.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import predict as predict_lib, tree as tree_lib
from repro.kernels import ops
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve_gbdt import synthetic_gbdt
from repro.obs import PredictReport

_JSON_PATH = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_predict.json")


def _measure_interleaved(fns: dict, *, reps: int) -> dict:
    """Per-rep warm wall-clock seconds for each variant, measured
    rep-major (scan, raw, binned, scan, raw, ...) after 2 untimed
    warmup calls each — container CPU noise hits every variant alike,
    so the recorded speedup ratios are robust to frequency drift."""
    for fn in fns.values():
        for _ in range(2):
            jax.block_until_ready(fn())
    lat = {name: np.empty((reps,), np.float64) for name in fns}
    for i in range(reps):
        for name, fn in fns.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            lat[name][i] = time.perf_counter() - t0
    return lat


def run(csv_rows: list, *, update_json: bool = False) -> None:
    n_trees, depth, f, k = 500, 6, 32, 32
    rows, reps = 50_000, 7
    chunk = predict_lib.DEFAULT_TREE_CHUNK
    backend = ops.resolve("auto")

    model = synthetic_gbdt(n_trees=n_trees, max_depth=depth, n_features=f,
                           n_candidates=k, seed=0)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(rows, f)).astype(np.float32))
    bins = jnp.asarray(model.bin_features(x), jnp.int32)

    engine_desc = {"n_trees": n_trees, "max_depth": depth,
                   "n_features": f, "tree_chunk": chunk,
                   "backend": backend}

    def scan_fn():
        return tree_lib._forest_predict_scan(model.forest, x,
                                             max_depth=depth)

    def raw_fn():
        return predict_lib.forest_predict(model.forest, x, max_depth=depth,
                                          tree_chunk=chunk)

    def binned_fn():
        return predict_lib.forest_predict(model.forest, bins,
                                          max_depth=depth, binned=True,
                                          tree_chunk=chunk)

    # exactness first — a fast wrong predictor is worthless.  The first
    # raw_fn() call is also the fresh-compile probe: exactly one
    # traversal-chunk trace for the whole 500-tree forest.
    base = np.asarray(scan_fn())
    tr0 = predict_lib.traverse_trace_count()
    identical_raw = np.array_equal(np.asarray(raw_fn()), base)
    traces = predict_lib.traverse_trace_count() - tr0
    identical_binned = np.array_equal(np.asarray(binned_fn()), base)
    assert identical_raw, "engine_raw diverged from the per-tree scan"
    assert identical_binned, "engine_binned diverged from the per-tree scan"
    assert traces <= 1, f"traversal traces per fresh predict: {traces}"

    lats = _measure_interleaved(
        {"scan_baseline": scan_fn, "engine_raw": raw_fn,
         "engine_binned": binned_fn}, reps=reps)
    reports = {}
    for name, lat in lats.items():
        baseline = (reports["scan_baseline"].summarize()["rows_per_s"]
                    if name != "scan_baseline" else 0.0)
        reports[name] = PredictReport(
            latencies_s=lat, rows_per_request=rows,
            engine={**engine_desc, "variant": name,
                    "binned": name == "engine_binned"},
            baseline_rows_per_s=baseline)
        s = reports[name].summarize()
        note = (f"{s['rows_per_s'] / 1e6:.2f}M rows/s "
                f"p99={s['latency_ms']['p99']:.0f}ms")
        if "speedup_vs_scan" in s:
            note += f" {s['speedup_vs_scan']:.1f}x vs scan"
        csv_rows.append((f"predict/{name}", s["latency_ms"]["mean"] * 1e3,
                         note))
    csv_rows.append(("predict/traversal_traces_fresh", 0.0,
                     f"{traces} (want <= 1 for any n_trees)"))

    if not update_json:
        csv_rows.append(("predict/500x6", 0.0,
                         "(dry run: BENCH_predict.json NOT updated)"))
        return

    rec = {
        "workload": {"n_trees": n_trees, "max_depth": depth, "rows": rows,
                     "n_features": f, "n_candidates": k,
                     "tree_chunk": chunk, "backend": backend,
                     "platform": jax.default_backend()},
        "timing_protocol": {"warm_reps": reps, "warmup_calls": 2,
                            "scope": "full-batch predict wall-clock"},
        "bit_identical_engine_vs_scan": {"raw": bool(identical_raw),
                                         "binned": bool(identical_binned)},
        "traversal_traces_per_fresh_predict": int(traces),
        "variants": {name: json.loads(r.to_json())
                     for name, r in reports.items()},
    }
    with open(_JSON_PATH, "w") as fh:
        json.dump(rec, fh, indent=1)


def smoke() -> None:
    """CI-sized invariant check (seconds): one traversal-chunk trace per
    fresh compiled predict regardless of n_trees (zero when the cache is
    hot), and batched predict bit-identical to the per-tree scan oracle.
    Exits non-zero via AssertionError on violation."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(600, 8)).astype(np.float32))
    chunk = 8
    traces_per_forest = {}
    for n_trees in (24, 56):
        model = synthetic_gbdt(n_trees=n_trees, max_depth=4, n_features=8,
                               n_candidates=9, seed=n_trees)
        base = np.asarray(tree_lib._forest_predict_scan(model.forest, x,
                                                        max_depth=4))
        tr0 = predict_lib.traverse_trace_count()
        out = predict_lib.forest_predict(model.forest, x, max_depth=4,
                                         tree_chunk=chunk)
        fresh = predict_lib.traverse_trace_count() - tr0
        assert np.array_equal(np.asarray(out), base), \
            f"engine != scan oracle at n_trees={n_trees}"
        tr0 = predict_lib.traverse_trace_count()
        predict_lib.forest_predict(model.forest, x, max_depth=4,
                                   tree_chunk=chunk)
        repeat = predict_lib.traverse_trace_count() - tr0
        assert fresh <= 1 and repeat == 0, \
            (f"n_trees={n_trees}: fresh={fresh} (want <=1), "
             f"repeat={repeat} (want 0)")
        traces_per_forest[n_trees] = fresh
    print(f"SMOKE OK: traces per fresh predict {traces_per_forest} "
          "(<=1 each, 0 warm), batched == per-tree scan bit-for-bit")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--update", action="store_true",
                    help="write the 500x6 record to BENCH_predict.json "
                         "(default: dry run, print timings only)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI invariant check (trace count, "
                         "bit-identity); no timings, no JSON write")
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
