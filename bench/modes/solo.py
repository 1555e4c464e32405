"""Run mode ``solo``: one simulation, advanced chunk by chunk.

Set-up builds the inputs on the device, packs them
(``solver.init_persistent``) and runs one chunk of ``chunk_steps``
(``solver.run_persistent``), which compiles or loads every program the
window calls. The window then repeats ``carry = run_persistent(cfg,
carry, chunk_steps)`` and waits for it, until ``seconds`` have passed;
its time runs from its start to the end of its last chunk. With a trace
directory, the window is ``trace_chunks`` chunks under the profiler,
each dispatch and each wait inside a host span of its own. The mode
returns its end-to-end values, the solver's counters over the window,
and the final particles for the check.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from bench import initial, program

DISPATCH = "bench.dispatch"
WAIT = "bench.wait"


def run(job) -> dict:
    cfg, work = job.cfg, job.work
    chunk = int(work["chunk_steps"])
    scfg = program.sph_config(cfg, work)
    inputs = initial.build(cfg, job.seed)
    n = int(inputs.x.shape[0])
    if n != cfg["n_particles"]:
        raise ValueError(f"{n} particles built; the configuration "
                         f"states {cfg['n_particles']}")
    carry = program.init(scfg, *inputs)
    carry = program.run(scfg, carry, chunk)
    jax.block_until_ready(carry)
    steps0, rebuilds0 = int(carry.steps), int(carry.rebuilds)
    if job.trace_dir is not None:
        lead_in = jax.jit(jnp.negative)
        jax.block_until_ready(lead_in(carry.steps))
    compiles0 = job.compiles()
    t0 = time.perf_counter()
    setup_s = t0 - job.t_start

    chunks = 0
    if job.trace_dir is None:
        while True:
            carry = program.run(scfg, carry, chunk)
            jax.block_until_ready(carry)
            chunks += 1
            t1 = time.perf_counter()
            if t1 - t0 >= job.seconds:
                break
    else:
        jax.profiler.start_trace(job.trace_dir)
        # a tiny program first, outside the spans: the profiler's first
        # device launch has been seen to stall or lose events
        jax.block_until_ready(lead_in(carry.steps))
        for _ in range(int(work["trace_chunks"])):
            with TraceAnnotation(DISPATCH):
                carry = program.run(scfg, carry, chunk)
            with TraceAnnotation(WAIT):
                jax.block_until_ready(carry)
            chunks += 1
        t1 = time.perf_counter()
        jax.profiler.stop_trace()

    compiles = job.compiles() - compiles0
    steps, rebuilds = int(carry.steps), int(carry.rebuilds)
    memory_peak = job.memory_peak()
    x, v, rho = (np.asarray(a) for a in program.finalize(scfg, carry))
    del carry
    return {
        "n": n,
        "inputs": inputs,
        "setup_s": setup_s,
        "window_s": t1 - t0,
        "window_steps": chunks * chunk,
        "end_to_end": {
            "particle_steps_per_s": n * chunks * chunk / (t1 - t0),
            "setup_s": setup_s,
        },
        "counters": {"steps": steps - steps0,
                     "rebuilds": rebuilds - rebuilds0},
        "steps_gap": abs(steps - steps0 - chunks * chunk),
        "steps": steps0 + chunks * chunk,
        "window_compiles": compiles,
        "memory_peak_bytes": memory_peak,
        "final": {"x": x, "v": v, "rho": rho},
    }
