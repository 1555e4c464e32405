"""Benchmark harness entry: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only table2,...]

Output: `table,key=value,...` CSV lines (greppable); EXPERIMENTS.md
quotes these outputs directly.
"""
import argparse
import time
import traceback

from repro.runtime import compile_cache

from benchmarks import (fig7_scaling, fig13_precision, lm_roofline,
                        nnps_throughput, table1_circle,
                        table2_neighbor_accuracy, table3_gradient,
                        table5_poiseuille, table6_sort_locality)

MODULES = {
    "table1": table1_circle,
    "table2": table2_neighbor_accuracy,
    "table3": table3_gradient,
    "roofline": lm_roofline,
    "fig13": fig13_precision,
    "table6": table6_sort_locality,
    "fig7": fig7_scaling,
    "table5": table5_poiseuille,
    "nnps": nnps_throughput,
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (slow on CPU)")
    ap.add_argument("--only", default="",
                    help="comma-separated module keys")
    args = ap.parse_args()
    only = [s for s in args.only.split(",") if s]
    compile_cache.enable()
    failures = 0
    for name, mod in MODULES.items():
        if only and name not in only:
            continue
        t0 = time.time()
        print(f"# --- {name} ({mod.__name__}) ---", flush=True)
        try:
            mod.main(full=args.full)
            print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception:
            failures += 1
            print(f"# {name} FAILED:\n{traceback.format_exc()}",
                  flush=True)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
