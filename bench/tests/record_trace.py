"""Record a small scoped trace for the reduction's tests (needs a TPU).

    python3 bench/tests/record_trace.py --out <dir>

Runs the traced window of ``bench/scope_report.py`` on the dam break at
ds = 0.02 (1,958 particles, the Pallas force kernel, a rebuild before
every step), two chunks of two steps under the profiler, and writes
``<dir>/tiny_scoped.xplane.pb``, ``<dir>/tiny_scoped.hlo.txt.gz`` and
``<dir>/tiny_scoped.scopes.json``: the trace, the timed program's
compiled HLO text (the checkout's path taken out of its source
locations) and its op-to-scope map (``scopes.scope_map``).
"""
from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, CHECKOUT)
sys.path.insert(0, os.path.join(CHECKOUT, "src"))

NAME = "tiny_scoped"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--seed", type=int, default=2**33 + 5)
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("# needs a TPU", file=sys.stderr)
        return 2
    from bench import scope_report, scopes, spec, trace_reduce
    from bench.tests import small

    work = dict(spec.workload("dam_break.1m_rebuild"), chunk_steps=2,
                trace_chunks=2)
    trace_dir = os.path.join(args.out, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    res = scope_report.traced(small.scaled("dam_break", 0.02), work,
                              args.seed, trace_dir)
    (found,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True)
    shutil.copy(found, os.path.join(args.out, f"{NAME}.xplane.pb"))
    shutil.rmtree(trace_dir)
    hlo = res["program_text"].replace(CHECKOUT + os.sep, "")
    with gzip.open(os.path.join(args.out, f"{NAME}.hlo.txt.gz"), "wt") as f:
        f.write(hlo)
    smap = scopes.scope_map(hlo)
    with open(os.path.join(args.out, f"{NAME}.scopes.json"), "w") as f:
        json.dump(smap, f, indent=0, sort_keys=True)
    s = trace_reduce.reduce_file(os.path.join(args.out, f"{NAME}.xplane.pb"))
    print(json.dumps({"n": res["n"], "steps": res["steps"],
                      "busy_s": s.busy_s(), "window_s": s.window_s,
                      "breakdown": scopes.Scoped(s, smap).breakdown()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
