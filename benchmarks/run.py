"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Modules:
  bench_rank_error     — Fig. 2 (rank error vs k, random vs quantile)
  bench_table2         — Table 2 (DT/GBDT accuracy + proposal time, S vs Q)
  bench_proposal_time  — Table 2 T columns (scaling with rows)
  bench_kernels        — Pallas kernel hot spots
  bench_roofline       — §Roofline terms from the dry-run artifacts
  bench_predict        — batched inference engine vs per-tree scan
"""

from __future__ import annotations

import sys
import traceback

from . import (bench_gbdt_step, bench_kernels, bench_predict,
               bench_proposal_time, bench_rank_error, bench_roofline,
               bench_table2)
from repro.launch.compile_cache import enable_compile_cache

MODULES = [
    ("rank_error", bench_rank_error),
    ("table2", bench_table2),
    ("proposal_time", bench_proposal_time),
    ("kernels", bench_kernels),
    ("gbdt_step", bench_gbdt_step),
    ("roofline", bench_roofline),
    ("predict", bench_predict),
]


def main() -> None:
    enable_compile_cache()
    only = sys.argv[1] if len(sys.argv) > 1 else None
    rows: list = []
    print("name,us_per_call,derived")
    for name, mod in MODULES:
        if only and only != name:
            continue
        try:
            n0 = len(rows)
            mod.run(rows)
            for r in rows[n0:]:
                print(f"{r[0]},{r[1]:.1f},{r[2]}", flush=True)
        except Exception:
            traceback.print_exc()
            print(f"{name}/ERROR,0,failed")
            raise SystemExit(1)


if __name__ == "__main__":
    main()
