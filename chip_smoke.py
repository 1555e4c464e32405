"""Smoke test of the RCLL solver and its service on a TPU.

    python chip_smoke.py             # one chip: phases (a), (b), (c)
    python chip_smoke.py --chips 4   # four chips: the service only

Phases, in order; any failure exits non-zero:

  (a) service — ``python -m repro.sph serve --port 0 --slots 2`` as a
      child process answers 3 ``taylor_green`` and 3 ``dam_break``
      requests at n=64000 through :mod:`repro.sph.client`; every reply
      is a ``done`` frame with a finite state, the server ran one
      engine worker, on the TPU, and SIGTERM drains it to exit 0. This process
      touches no JAX backend until the phase is over: the chip belongs
      to the worker.
  (b) solver — ``dam_break`` at n=1M through ``Simulation`` on the
      default backend (``pallas``, fp16 records): 20 steps with
      rebuilds, every field finite, the compiled step carrying the
      force kernel as a ``tpu_custom_call``; prints compile seconds and
      ``peak_bytes_in_use``.
  (c) agreement — ``dam_break`` at n=64k, 10 steps on ``pallas`` and on
      ``xla``: positions, velocities and densities agree within the
      trajectory tolerance of ``tests/test_fused_force.py``; then 10 more
      steps each, compiled, are timed.

With ``--chips 4`` only the service runs: over four workers pinned one
per chip, with two more buckets (n=32000) so that every chip owns a
bucket, then again with the host held to one chip; every request's
final state must be bit-identical between the two runs.

The last line of standard output is one JSON object, ``{"ok": true,
"device": {"platform", "kind", "count"}}``. Without a TPU the script
exits 2 and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

from repro.runtime import compile_cache  # noqa: E402
from repro.sph import client  # noqa: E402
from repro.sph.supervisor import pin_env, probe_devices  # noqa: E402

SERVE_N = 64000
SERVE_NSTEPS = 32  # one block of the default GuardPolicy
SOLVER_N = 1_000_000
SOLVER_STEPS = 20
AGREE_N = 64000
AGREE_STEPS = 10
# test_dynamic_dam_break_backends_agree_with_rebuilds: xla vs pallas on
# positions, velocities and densities
AGREE_ATOL = 2e-5


def log(msg: str):
    print(f"# {msg}", flush=True)


class Failed(Exception):
    pass


def check(cond, what: str):
    if not cond:
        raise Failed(what)


# --------------------------------------------------------------------------
# (a) the service
# --------------------------------------------------------------------------
def service_requests(extra_buckets: bool) -> list[dict]:
    reqs = [{"case": case, "n": SERVE_N}
            for case in ("taylor_green", "dam_break") for _ in range(3)]
    if extra_buckets:
        reqs += [{"case": case, "n": SERVE_N // 2}
                 for case in ("taylor_green", "dam_break")]
    return [{**r, "nsteps": SERVE_NSTEPS, "return_state": True}
            for r in reqs]


def run_service(reqs: list[dict], env: dict, label: str) -> tuple:
    """Serve ``reqs`` concurrently; returns (final states, stats)."""
    cmd = [sys.executable, "-m", "repro.sph", "serve", "--port", "0",
           "--slots", "2"]
    t0 = time.perf_counter()
    proc = subprocess.Popen(cmd, cwd=HERE, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    lines: list[str] = []
    try:
        port = None
        for line in proc.stdout:
            lines.append(line.rstrip())
            if line.startswith("# serving on"):
                port = int(line.split()[3].rsplit(":", 1)[1])
                break
        check(port is not None, f"{label}: server exited before its "
              "banner:\n" + "\n".join(lines[-40:]))
        threading.Thread(target=lambda: lines.extend(
            ln.rstrip() for ln in proc.stdout), daemon=True).start()
        log(f"{label}: {lines[-1][2:]}")
        results: list = [None] * len(reqs)

        def fire(i):
            results[i] = client.run_request(
                "127.0.0.1", port, reqs[i], timeout=1100.0)

        threads = [threading.Thread(target=fire, args=(i,))
                   for i in range(len(reqs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(1150.0)
        _, stats = client.run_request("127.0.0.1", port, {"op": "stats"},
                                      timeout=60.0)
        states = []
        for req, res in zip(reqs, results):
            check(res is not None, f"{label}: no reply to {req}")
            frames, term = res
            errors = [f for f in frames if f.get("type") == "error"]
            check(not errors, f"{label}: error frame {errors}")
            check(term is not None and term["type"] == "done",
                  f"{label}: {req['case']} n={req['n']} ended with {term}")
            st = client.final_state(term)
            for k, v in st.items():
                if v.dtype.kind == "f":
                    check(np.isfinite(v).all(), f"{label}: {k} not finite")
            states.append(st)
        for w in stats["workers"]:
            check(w["platform"] == "tpu",
                  f"{label}: worker {w['tag']} ran on {w['platform']}")
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
        check(rc == 0, f"{label}: serve exited {rc} on SIGTERM")
        workers = [(w["tag"], w["kind"], w["buckets"])
                   for w in stats["workers"]]
        log(f"{label}: {len(reqs)} requests done in "
            f"{time.perf_counter() - t0:.1f}s (server start included); "
            f"workers={workers}"
            f" restarts={stats['worker_restarts']}")
        return states, stats
    except Failed as e:
        tail = "\n".join(lines[-80:])
        raise Failed(f"{e}\n--- {label}: server log (tail) ---\n{tail}")
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)


def phase_service(chips: int) -> None:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(HERE, "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    if chips == 1:
        reqs = service_requests(extra_buckets=False)
        _, stats = run_service(reqs, env, "one-chip")
        check(len(stats["workers"]) == 1 and stats["buckets"] == 2,
              f"one chip should run one worker for two buckets: {stats}")
        check(stats["worker_restarts"] == 0, f"restarts: {stats}")
        return
    reqs = service_requests(extra_buckets=True)
    four, st4 = run_service(reqs, env, f"{chips}-chips")
    check(st4["chips"] == chips and len(st4["workers"]) == chips,
          f"expected {chips} workers: {st4}")
    check(all(len(w["buckets"]) == 1 for w in st4["workers"]),
          f"buckets not spread one per chip: {st4}")
    # the reference: the same requests served with the host held to
    # one chip (one worker, every bucket)
    one, st1 = run_service(reqs, {**env, **pin_env(0)}, "pinned-one-chip")
    check(len(st1["workers"]) == 1, f"one-chip run: {st1}")
    for req, a, b in zip(reqs, one, four):
        check(set(a) == set(b), f"state keys differ for {req}")
        for k in a:
            check(np.array_equal(a[k], b[k]),
                  f"{req['case']} n={req['n']}: {k} differs between "
                  "one chip and four")
    log(f"{chips}-chip states bit-identical to one chip for "
        f"{len(reqs)} requests")


# --------------------------------------------------------------------------
# (b) the solver at 1M, (c) pallas vs xla
# --------------------------------------------------------------------------
def phase_solver(jax) -> None:
    from repro.core import cases, solver
    from repro.core.api import Simulation

    t0 = time.perf_counter()
    sim = Simulation.from_case(
        "dam_break", ds=cases.resolve_ds("dam_break", SOLVER_N))
    cfg = sim.cfg
    n = sim.n_particles
    log(f"solver: dam_break N={n} grid={cfg.domain.ncells} "
        f"cap={cfg.cap(n)} backend={cfg.resolved_backend} "
        f"records={cfg.policy.records} (built in "
        f"{time.perf_counter() - t0:.1f}s)")
    check(cfg.resolved_backend == "pallas", "default backend not pallas")
    check(cfg.policy.records == "fp16", "default records not fp16")
    t0 = time.perf_counter()
    compiled = solver._simulate_stats_jit.lower(
        cfg, sim.state, SOLVER_STEPS).compile()
    compile_s = time.perf_counter() - t0
    hlo = compiled.as_text()
    check("tpu_custom_call" in hlo and "rcll_force" in hlo,
          "compiled step has no rcll_force tpu_custom_call")
    log(f"solver: compiled {SOLVER_STEPS}-step program in {compile_s:.1f}s;"
        f" tpu_custom_call rcll_force present; "
        f"memory_analysis={compiled.memory_analysis()}")
    t0 = time.perf_counter()
    res = sim.run(SOLVER_STEPS)
    jax.block_until_ready(res.state)
    run_s = time.perf_counter() - t0
    rebuilds = int(res.stats.rebuilds)
    check(int(res.stats.steps) == SOLVER_STEPS, f"steps {res.stats.steps}")
    check(rebuilds >= 1, "no rebuild ran")
    check(not bool(res.stats.overflow), "cell table overflow")
    for path, leaf in jax.tree_util.tree_leaves_with_path(res.state):
        a = np.asarray(leaf)
        if a.dtype.kind == "f":
            check(np.isfinite(a).all(),
                  f"non-finite {jax.tree_util.keystr(path)}")
    peak = jax.devices()[0].memory_stats().get("peak_bytes_in_use")
    log(f"solver: {SOLVER_STEPS} steps, {rebuilds} rebuilds, all fields "
        f"finite; first run {run_s:.1f}s (compile-cache load included); "
        f"peak_bytes_in_use={peak}")


def phase_agreement(jax) -> None:
    from repro.core import cases, solver
    from repro.core.api import Simulation

    ds = cases.resolve_ds("dam_break", AGREE_N)
    out = {}
    for backend in ("pallas", "xla"):
        sim = Simulation.from_case("dam_break", ds=ds, backend=backend)
        t0 = time.perf_counter()
        res = sim.run(AGREE_STEPS)
        st = res.state
        out[backend] = [np.asarray(a) for a in (
            solver.positions(sim.cfg, st), st.fluid.v, st.fluid.rho)]
        log(f"agreement: {backend} N={sim.n_particles} {AGREE_STEPS} steps"
            f" in {time.perf_counter() - t0:.1f}s (compile included), "
            f"rebuilds={int(res.stats.rebuilds)}")
        t0 = time.perf_counter()
        jax.block_until_ready(sim.run(AGREE_STEPS).state)
        log(f"agreement: {backend} warm {AGREE_STEPS} steps in "
            f"{time.perf_counter() - t0!r}s")
    for name, a, b in zip(("x", "v", "rho"), out["pallas"], out["xla"]):
        err = float(np.max(np.abs(a - b)))
        check(np.isfinite(a).all() and np.isfinite(b).all(),
              f"agreement: {name} not finite")
        log(f"agreement: max |pallas - xla| {name} = {err!r} "
            f"(atol {AGREE_ATOL})")
        check(err <= AGREE_ATOL, f"agreement: {name} off by {err}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the service, over four pinned "
                    "workers, against the same service on one chip")
    args = ap.parse_args(argv)
    compile_cache.enable()
    # a child asks JAX for the devices: this process stays off the chip
    count, platform = probe_devices()
    if platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {count} x {platform})",
              file=sys.stderr)
        return 2
    if count < args.chips:
        print(f"chip_smoke: --chips {args.chips} on a host with {count}",
              file=sys.stderr)
        return 2
    try:
        phase_service(args.chips)
        import jax

        devices = jax.devices()
        check(devices[0].platform == "tpu", "JAX lost the TPU")
        if args.chips == 1:
            phase_solver(jax)
            phase_agreement(jax)
    except Failed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
