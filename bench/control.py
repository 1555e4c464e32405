"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/control.py --workload <cell> --steps <S> \
        --seeds <k> --control-seeds <c> [--first-seed <n>]

For each of ``k`` seeds, in one process: the program as the cell runs
it (``solver.run_persistent`` in chunks of the cell's ``chunk_steps``,
from the same inputs) for ``S`` steps, then the plain reference for
``S`` steps, and the gaps between them (``bench/check.py``): the
lower readings. For the first ``c`` seeds also the two controls, each
against the reference: the upper readings.

* ``fp8``: the reference in the program's place with its record fields
  (v, m) read in fp8, the precision below the configuration's fp16
  records;
* ``bf16``: the program itself with its own bf16 record path switched
  on (``precision.records = "bf16"``), the lower-precision layout a
  later change could switch to. It runs on the program's XLA force path
  (``backend = "xla"``): the Pallas kernel decodes bf16 record words as
  integers, so its bf16 path gives no sound reading (PERF.md, Open
  questions).

One JSON line per run on standard output, then a summary line with the
largest program gap and each control's smallest gap of each number.
``S`` is rounded up to whole chunks.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

from bench import check, initial, program, spec  # noqa: E402


def _program(cfg: dict, work: dict, inputs, nchunks: int):
    """The cell's timed entry from ``inputs``: (steps, rebuilds, v, rho)."""
    import jax
    import numpy as np

    scfg = program.sph_config(cfg, work)
    chunk = int(work["chunk_steps"])
    carry = program.init(scfg, *inputs)
    for _ in range(nchunks):
        carry = program.run(scfg, carry, chunk)
    jax.block_until_ready(carry)
    done, rebuilds = int(carry.steps), int(carry.rebuilds)
    _, v, rho = (np.asarray(a) for a in program.finalize(scfg, carry))
    return done, rebuilds, v, rho


def readings(name: str, steps: int, seeds: list[int],
             control_seeds: int, cfg: dict | None = None) -> list[dict]:
    """One row per seed: the program's gaps and, for the first
    ``control_seeds`` seeds, the controls'."""
    entry = next(w for w in spec.benchmark()["workloads"]
                 if w["name"] == name)
    work = spec.workload(name)
    cfg = spec.config(entry["config"]) if cfg is None else cfg
    ref_mod = spec.reference(cfg["reference"])
    nchunks = -(-steps // int(work["chunk_steps"]))
    bf16 = copy.deepcopy(cfg)
    bf16["precision"]["records"] = "bf16"
    bf16["backend"] = "xla"
    rows = []
    for k, seed in enumerate(seeds):
        t = time.perf_counter()
        inputs = initial.build(cfg, seed)
        done, rebuilds, v, rho = _program(cfg, work, inputs, nchunks)
        t_prog = time.perf_counter() - t
        t = time.perf_counter()
        ref = ref_mod.simulate(cfg, inputs, done)
        t_ref = time.perf_counter() - t
        row = {"seed": seed, "steps": done, "rebuilds": rebuilds,
               "program_s": t_prog, "reference_s": t_ref,
               "ref_overflow": ref["overflow"],
               "program": check.gaps(inputs, {"v": v, "rho": rho}, ref)}
        if k < control_seeds:
            ctl = ref_mod.simulate(cfg, inputs, done, records="fp8")
            _, _, v, rho = _program(bf16, work, inputs, nchunks)
            row["control"] = {
                "fp8": check.gaps(inputs, ctl, ref),
                "bf16": check.gaps(inputs, {"v": v, "rho": rho}, ref)}
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def summary(rows: list[dict]) -> dict:
    """Per number: the largest program gap ("lower") and each control's
    smallest ("upper"); None where a gap could not be formed."""
    out = {}
    for name in rows[0]["program"]:
        prog = [r["program"][name] for r in rows]
        upper = {}
        for ctl in ("fp8", "bf16"):
            got = [r["control"][ctl][name] for r in rows if "control" in r]
            upper[ctl] = None if (not got or None in got) else min(got)
        out[name] = {"lower": None if None in prog else max(prog),
                     "upper": upper}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--steps", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_001)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from repro.runtime import compile_cache

    compile_cache.enable()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"# needs a TPU; JAX found {dev.platform}", file=sys.stderr)
        return 2
    seeds = [args.first_seed + 7919 * k for k in range(args.seeds)]
    rows = readings(args.workload, args.steps, seeds, args.control_seeds)
    print(json.dumps({"workload": args.workload, "device": dev.device_kind,
                      "summary": summary(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
