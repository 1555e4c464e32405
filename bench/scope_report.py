"""Device time of one cell's step by the program's ``sph.*`` scopes.

    python3 bench/scope_report.py --workload <cell> --seed <n>

Runs the cell's traced window as the ``solo`` mode does (set-up, one
warm-up chunk, then ``trace_chunks`` chunks under the profiler, each
dispatch and wait in a ``bench.*`` span). After the window it loads the
timed program (``run_persistent``, from the compile cache), reads its
op-to-scope map (``scopes.scope_map``) and its compiled temporaries,
and counts the force kernel's grid steps on the final carry's binning
(``ops.force_grid_work``). Prints one JSON line: per-step device time
of each scope and of the unscoped ops, the kernel and the rest
(``force_kernel_ms``, ``non_force_ms``, read as the benchmark reads
them), the useful share of the kernel's grid, ``program_temp_bytes``
beside ``memory_peak_bytes``, and the breakdown with scope labels.
Without a TPU plane in the trace the scope times are null. Checks no
output: ``bench/run.py`` is the benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
sys.path.insert(0, CHECKOUT)
sys.path.insert(0, os.path.join(CHECKOUT, "src"))

TRACE_DIR = os.path.join(CHECKOUT, ".bench_trace", "scope_report")
KERNEL = "rcll_force"
SCOPES = ("sph.rebuild", "sph.rebuild.pack", "sph.rebuild.permute",
          "sph.rebuild.mass_table", "sph.rebuild.search", "sph.skin_check",
          "sph.force", "sph.cell_tables", "sph.unpack", "sph.integrate")


def traced(cfg: dict, work: dict, seed: int, trace_dir: str) -> dict:
    """The cell's traced window; what the program says of itself after."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from bench import initial, program
    from bench.modes import solo
    from repro.kernels import ops

    chunk = int(work["chunk_steps"])
    scfg = program.sph_config(cfg, work)
    inputs = initial.build(cfg, seed)
    carry = program.init(scfg, *inputs)
    carry = program.run(scfg, carry, chunk)
    lead_in = jax.jit(jnp.negative)
    jax.block_until_ready((carry, lead_in(carry.steps)))
    steps0 = int(carry.steps)
    jax.profiler.start_trace(trace_dir)
    jax.block_until_ready(lead_in(carry.steps))
    for _ in range(int(work["trace_chunks"])):
        with TraceAnnotation(solo.DISPATCH):
            carry = program.run(scfg, carry, chunk)
        with TraceAnnotation(solo.WAIT):
            jax.block_until_ready(carry)
    jax.profiler.stop_trace()
    compiled = program.run.lower(scfg, carry, chunk).compile()
    launched, useful = ops.force_grid_work(scfg.domain, carry.binning)
    stats = jax.devices()[0].memory_stats() or {}
    return {"n": int(inputs.x.shape[0]),
            "steps": int(carry.steps) - steps0,
            "program_text": compiled.as_text(),
            "program_temp_bytes":
                compiled.memory_analysis().temp_size_in_bytes,
            "memory_peak_bytes": stats.get("peak_bytes_in_use"),
            "grid_launched": int(launched), "grid_useful": int(useful)}


def report(name: str, seed: int, *, cfg: dict | None = None,
           work: dict | None = None, trace_dir: str = TRACE_DIR) -> dict:
    """The JSON line of one cell (``cfg``/``work`` stand in for the
    cell's files where given)."""
    from bench import scopes, spec, trace_reduce

    work = spec.workload(name) if work is None else work
    cfg = spec.config(work["config"]) if cfg is None else cfg
    shutil.rmtree(trace_dir, ignore_errors=True)
    res = traced(cfg, work, seed, trace_dir)
    summary = trace_reduce.reduce_dir(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    steps = res["steps"]
    if summary.truncated:  # the profiler dropped the window's end
        steps = summary.modules_done() * int(work["chunk_steps"])
    scoped = scopes.Scoped(summary, scopes.scope_map(res["program_text"]))

    def ms(s):
        return None if s is None else 1e3 * s / steps

    leaf = summary.op_totals()
    kernel = summary.op_s(KERNEL)
    return {
        "workload": name, "seed": seed, "n": res["n"], "steps": steps,
        "busy_s": summary.busy_s(), "window_s": summary.window_s,
        "force_kernel_ms": ms(kernel),
        "non_force_ms": ms(summary.busy_s() - kernel),
        "scope_ms": {s: ms(scoped.scope_s(s)) for s in SCOPES},
        "unscoped_ms": ms(scoped.unscoped_s()),
        "path_ms": {k: ms(v) for k, v in sorted(scoped.by_path().items())},
        # leaf time of ops the map does not know: 0 for a complete map
        "unmapped_s": (None if scoped.scopes is None else
                       sum(v for k, v in leaf.items()
                           if k not in scoped.scopes)),
        "force_grid_launched": res["grid_launched"],
        "force_grid_useful_pct":
            100.0 * res["grid_useful"] / res["grid_launched"],
        "program_temp_bytes": res["program_temp_bytes"],
        "memory_peak_bytes": res["memory_peak_bytes"],
        "breakdown": scoped.breakdown(),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from repro.runtime import compile_cache

    compile_cache.enable()
    import jax

    # cached, so the load after the window finds the timed program
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("# needs a TPU", file=sys.stderr)
        return 2
    print(json.dumps(report(args.workload, args.seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
