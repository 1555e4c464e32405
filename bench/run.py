"""Run one cell of the benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its parameters,
configuration, run mode and per-layer readers are files under
``bench/`` (see ``bench/spec.py``). One process builds the inputs from
the seed on the chip, warms up, runs the measured window, checks the
program's output against the plain reference and prints, as the last
line of standard output, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced),
then ``checks``, each compared number beside its limit. With
``--trace 0`` the metrics are the cell's end-to-end ones, with
``--trace 1`` its per-layer ones, read from a profiler trace of a few
chunks. Without a TPU, or with fewer chips than the cell asks for, it
exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
sys.path.insert(0, CHECKOUT)
sys.path.insert(0, os.path.join(CHECKOUT, "src"))

from bench import check, roofline, spec  # noqa: E402

TRACE_DIR = os.path.join(CHECKOUT, ".bench_trace")


def _log(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr, flush=True)


class CompileCounter:
    """Counts programs lowered for compilation, cached or not."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kwargs) -> None:
        if event == self.EVENT:
            self.count += 1


def _memory_peak(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def execute(name: str, seed: int, seconds: float, trace: bool, *,
            device, peak: dict, bench: dict | None = None,
            cfg: dict | None = None, work: dict | None = None,
            t_start: float = T_START) -> dict:
    """Everything of a run after the look for the chip: the result line.

    ``cfg``/``work`` stand in for the cell's files where given.
    """
    import jax

    bench = spec.benchmark() if bench is None else bench
    entry = next(w for w in bench["workloads"] if w["name"] == name)
    work = spec.workload(name) if work is None else work
    if work["config"] != entry["config"]:
        raise ValueError(f"{name}: workload file names config "
                         f"{work['config']!r}, BENCHMARK.json "
                         f"{entry['config']!r}")
    cfg = spec.config(entry["config"]) if cfg is None else cfg
    counter = CompileCounter()
    trace_dir = None
    if trace:
        trace_dir = os.path.join(TRACE_DIR, name)
        shutil.rmtree(trace_dir, ignore_errors=True)
    job = types.SimpleNamespace(
        cfg=cfg, work=work, seed=seed, seconds=seconds, trace_dir=trace_dir,
        t_start=t_start, compiles=lambda: counter.count,
        memory_peak=lambda: _memory_peak(device))
    res = spec.mode(work["mode"]).run(job)
    _log(f"{name}: {res['window_steps']} steps of {res['n']} particles in "
         f"{res['window_s']:.3f} s after {res['setup_s']:.3f} s of set-up")

    line = {"correct": None, "attempted": 1, "failed": 0}
    if trace:
        from bench import trace_reduce

        summary = trace_reduce.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        steps = res["window_steps"]
        if summary.truncated:  # the profiler dropped the window's end
            steps = summary.modules_done() * int(work["chunk_steps"])
            _log(f"trace truncated: per-step numbers over {steps} steps")
        ctx = types.SimpleNamespace(
            trace=summary, steps=steps, counters=res["counters"],
            n=res["n"], peak=peak, cfg=cfg,
            counts=roofline.force_pass(cfg, res["n"]))
        metrics = {}
        for m in spec.cell_metrics(bench, name, "per_layer"):
            value = spec.metric_reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device_info = {"busy_s": summary.busy_s(),
                       "window_s": summary.window_s}
        line["breakdown"] = summary.breakdown()
    else:
        metrics = {m["name"]: {"value": res["end_to_end"][m["name"]],
                               "unit": m["unit"]}
                   for m in spec.cell_metrics(bench, name, "end_to_end")}
        device_info = {}

    ref_mod = spec.reference(cfg["reference"])
    ref = ref_mod.simulate(cfg, res["inputs"], res["steps"])
    numbers = check.gaps(res["inputs"], res["final"], ref)
    numbers["window_compiles"] = res["window_compiles"]
    numbers["ref_overflow"] = int(ref["overflow"])
    numbers["steps_gap"] = res["steps_gap"]
    limits = dict(work["limits"], window_compiles=0, ref_overflow=0,
                  steps_gap=0)
    rows = check.judge(numbers, limits)
    line["correct"] = all(r["ok"] for r in rows.values())
    line["failed"] = 0 if line["correct"] else 1
    line["metrics"] = metrics
    line["device"] = {
        "platform": device.platform, "kind": device.device_kind,
        "count": jax.device_count(),
        "memory_peak_bytes": res["memory_peak_bytes"], **device_info,
    }
    line["checks"] = {k: {"value": r["value"], "limit": r["limit"]}
                      for k, r in rows.items()}
    for k, r in rows.items():
        _log(f"check {k} {r['value']!r} limit {r['limit']!r} "
             f"{'ok' if r['ok'] else 'FAILED'}")
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = spec.benchmark()
    entry = next((w for w in bench["workloads"]
                  if w["name"] == args.workload), None)
    if entry is None:
        _log(f"no workload {args.workload!r} in BENCHMARK.json")
        return 2
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from repro.runtime import compile_cache

    compile_cache.enable()
    import jax

    # every program of the run, however quick to compile, is cached, so
    # only the first run of a cell in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < entry["chips"]:
        _log(f"needs {entry['chips']} TPU chip(s); JAX found "
             f"{len(devices)} {devices[0].platform} device(s)")
        return 2
    peak = spec.peaks(devices[0].device_kind)
    line = execute(args.workload, args.seed, args.seconds,
                   bool(args.trace), device=devices[0], peak=peak,
                   bench=bench)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
