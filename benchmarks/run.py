"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Mapping to the paper:
  table1  -> Table 1 (long-tail click distribution)
  table3  -> Table 3 (PLM recommender quality vs small-encoder baseline)
  speedup -> Table 4 (module-wise training speedup ladder)
  table5  -> Table 5 (ablations: bus / cache / refine)
  table6  -> Table 6 (cache expiry gamma sweep)
  fig8    -> Figure 8 (data efficiency: buckets x CNE)
  fig9    -> Figure 9 (BusLM cost vs #segments)
  roofline-> §Roofline terms from the multi-pod dry-run artifacts
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset (e.g. table1,fig9)")
    args = ap.parse_args()

    from . import roofline_table, speedup, tables
    suites = {
        "table1": tables.table1_longtail,
        "table3": tables.table3_quality,
        "speedup": speedup.run,
        "table5": tables.table5_ablation,
        "table6": tables.table6_cache_gamma,
        "fig8": tables.fig8_data_efficiency,
        "fig9": tables.fig9_buslm,
        "roofline": roofline_table.run,
    }
    if args.only:
        keep = args.only.split(",")
        suites = {k: v for k, v in suites.items() if k in keep}

    print("name,us_per_call,derived")
    ok = True
    for name, fn in suites.items():
        t0 = time.time()
        try:
            for row in fn():
                print(f"{row[0]},{row[1]:.2f},{row[2]}")
            print(f"_meta/{name}_wall_s,{(time.time()-t0)*1e6:.0f},"
                  f"{time.time()-t0:.1f}", flush=True)
        except Exception as e:
            ok = False
            traceback.print_exc()
            print(f"_error/{name},0,\"{type(e).__name__}: {e}\"", flush=True)
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
