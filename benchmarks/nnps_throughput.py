"""End-to-end step throughput: fused force pass vs the gather path, plus
the persistent-pipeline NNPS diagnostics (Verlet-skin reuse, rebuild
cost) and an HBM bytes/step model.

For each particle count the Poiseuille channel runs under the production
persistent RCLL solver with a Verlet skin (cells sized to cover r+skin):

  * ``reference``          - PR 1's gather path: per-pair arrays (disp,
    grad W, pair fields) materialized in HBM every step;
  * ``xla`` records=fp16   - the production half-width record sweep
    (core/fused.py): one uint16 record gather + one fp32 rho gather per
    pair, EOS-folded p/ρ², counting-sort rebuild, window search;
  * ``xla`` records=fp32   - the full-width record sweep (the PR 2
    layout) as the measured A/B for the record quantization.

Reported per case:
  * steps/sec measured on the donating scan entry point
    (``solver.run_persistent`` — chained segments, buffers updated in
    place, init/compile excluded);
  * physics-only ms/step (a scan of pure ``_physics_step``, no rebuild
    cond) vs the NNPS rebuild cost in ms and the observed rebuild
    frequency — the paper's Table 6 style split. CPU wall times are a
    proxy (see _util).

Results are APPENDED to ``BENCH_nnps.json`` (the file holds a list of
run records, oldest first) so the perf trajectory persists across PRs;
``benchmarks/compare_bench.py`` diffs consecutive records. CI smoke runs
pass ``--no-append`` (optionally with ``--out FILE``) so they never
pollute the history.

``--n 1000000`` reaches the paper's 1M-particle case (expect minutes per
backend on CPU; tiers above 200k run the production xla/fp16 combo only,
and a tier that OOMs is recorded as a failed row with the reason, and
the run then exits non-zero);
``--quick`` runs the 8k case only. ``--dynamic`` adds dam-break rows
with a Verlet skin — the collapse keeps the rebuild ``lax.cond`` firing
inside the timed scan, so their steps/sec is the AMORTIZED physics +
rebuild throughput the steady poiseuille rows (rebuilds=0) cannot see,
reported alongside rebuilds_per_100_steps.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from functools import partial

import jax
import numpy as np

from benchmarks._util import emit, time_fn
from repro.core import cases, solver
from repro.core.precision import PrecisionPolicy

BENCH_PATH = "BENCH_nnps.json"


@partial(jax.jit, static_argnums=(0, 2))
def _physics_only(cfg, carry, nsteps):
    """Scan of the raw physics step (no rebuild cond) for the time split."""

    def body(c, _):
        return solver._physics_step(cfg, c), None

    return jax.lax.scan(body, carry, None, length=nsteps)[0]


def _build(
    n_target: int,
    backend: str,
    skin_frac_hc: float,
    records: str,
    case_name: str = "poiseuille",
    dynamic: bool = False,
):
    if case_name == "poiseuille":
        # historical default: unit-square channel, skin-capable cells
        ds = float((1.0 / n_target) ** 0.5)
        cell_factor = 1.0 + skin_frac_hc
        max_neighbors = 64 if skin_frac_hc > 0 else 40
        case = cases.PoiseuilleCase(
            ds=ds, L=1.0, Lx=1.0, algo="rcll",
            cell_factor=cell_factor, max_neighbors=max_neighbors,
            backend=backend, policy=PrecisionPolicy(records=records),
        )
        cfg, st = case.build()
        if skin_frac_hc > 0:
            cfg = dataclasses.replace(
                cfg, skin=skin_frac_hc * cfg.domain.radius
            )
        return cfg, st, max_neighbors
    if dynamic and case_name == "dam_break":
        # The --dynamic mode: a dam-break column started at a
        # collapse-representative fall speed (v0) so the Verlet
        # criterion fires rebuilds INSIDE the short timed window (a
        # quiescent column needs O(sqrt(col_h/g)) of physical time —
        # thousands of steps at fine ds — before anything moves a
        # cell). Skin-capable cells sized like the poiseuille rows.
        ds = cases.resolve_ds(case_name, n_target)
        radius = 2.0 * cases.build_case(case_name, ds=ds).h  # support 2h
        case = cases.build_case(
            case_name, ds=ds, backend=backend,
            policy=PrecisionPolicy(records=records),
            cell_factor=1.0 + max(skin_frac_hc, 0.5),
            skin=max(skin_frac_hc, 0.5) * radius,
            max_neighbors=64,
            v0=1.0,  # ~sqrt(g * col_h)
        )
        cfg, st = case.build()
        return cfg, st, cfg.max_neighbors
    # any registered scenario (--case): scaled to n_target via the case
    # registry; these cases size their own cells (no Verlet skin knob),
    # so skin_frac_hc is ignored and the rebuild runs per step.
    case = cases.build_case(
        case_name,
        ds=cases.resolve_ds(case_name, n_target),
        backend=backend,
        policy=PrecisionPolicy(records=records),
    )
    cfg, st = case.build()
    return cfg, st, cfg.max_neighbors


def run_case(
    n_target: int,
    backend: str,
    nsteps: int,
    skin_frac_hc: float = 0.5,
    records: str = "fp16",
    case_name: str = "poiseuille",
    dynamic: bool = False,
) -> dict:
    if case_name != "poiseuille" and not dynamic:
        skin_frac_hc = 0.0
    cfg, st, max_neighbors = _build(
        n_target, backend, skin_frac_hc, records, case_name, dynamic
    )
    n = int(st.xn.shape[0])

    # warm the flow a little so velocities/densities are nontrivial
    st = jax.block_until_ready(solver.simulate(cfg, st, 10))

    # physics-only vs NNPS(rebuild) split (non-donating jits)
    carry = solver.init_persistent(cfg, st)
    np_steps = min(8, nsteps)
    t_phys = time_fn(
        lambda: _physics_only(cfg, carry, np_steps), warmup=1, repeats=2
    ) / np_steps
    reb = jax.jit(lambda c: solver._rebuild(cfg, c))
    t_rebuild = time_fn(lambda: reb(carry), warmup=1, repeats=2)

    # steps/sec on the donating scan entry point (init/compile excluded).
    # run_persistent donates the carry — and the carry aliases ``st``'s
    # buffers — so this phase runs LAST and rebinds carry each call.
    carry = jax.block_until_ready(solver.run_persistent(cfg, carry, nsteps))
    rebuilds_before = int(carry.rebuilds)
    times = []
    timed_segments = 3
    for _ in range(timed_segments):
        t0 = time.perf_counter()
        carry = jax.block_until_ready(
            solver.run_persistent(cfg, carry, nsteps)
        )
        times.append(time.perf_counter() - t0)
    t_run = min(times)
    # diagnostics from the SAME timed segments, not a separate run
    rebuilds = int(carry.rebuilds) - rebuilds_before
    rebuild_frequency = rebuilds / (timed_segments * nsteps)
    overflow = bool(carry.overflow)

    row = {
        "case": case_name,
        "dynamic": dynamic,
        "n_target": n_target,
        "n_particles": n,
        "backend": backend,
        "records": records,
        "skin_frac_hc": skin_frac_hc,
        "skin": float(cfg.skin),
        "max_neighbors": max_neighbors,
        "nsteps": nsteps,
        # the donated-scan steps/sec INCLUDES every in-scan rebuild: in
        # --dynamic mode this IS the amortized throughput
        "steps_per_sec": round(nsteps / t_run, 3),
        "physics_ms_per_step": round(t_phys * 1e3, 3),
        "rebuild_ms": round(t_rebuild * 1e3, 3),
        "rebuilds": rebuilds,
        "rebuild_frequency": round(rebuild_frequency, 4),
        "rebuilds_per_100_steps": round(100.0 * rebuild_frequency, 1),
        "overflow": overflow,
    }
    if dynamic:
        # alias, emitted only where it means something (rebuilds fired
        # inside the timed scan)
        row["amortized_steps_per_sec"] = row["steps_per_sec"]
    emit("step_throughput", row)
    return row


def _append_record(record: dict) -> None:
    """BENCH_nnps.json holds a list of run records, oldest first."""
    history = []
    if os.path.exists(BENCH_PATH):
        with open(BENCH_PATH) as f:
            prev = json.load(f)
        history = prev if isinstance(prev, list) else [prev]
    history.append(record)
    with open(BENCH_PATH, "w") as f:
        json.dump(history, f, indent=2)


def default_steps(n: int) -> int:
    return max(8, min(48, int(3_000_000 / max(n, 1))))


#: Above this particle count only the production combo (xla, fp16) runs:
#: the gather/full-width A/Bs would triple a multi-minute CPU tier for a
#: ratio the smaller tiers already establish.
BIG_TIER = 200_000


def main(
    full: bool = True,
    sizes: list[tuple[int, int]] | None = None,
    skin_compare: bool = True,
    append: bool = True,
    out: str | None = None,
    case_name: str = "poiseuille",
    dynamic_sizes: list[tuple[int, int]] | None = None,
):
    """``full`` selects the 8k+64k grid (benchmarks.run interface);
    ``sizes`` overrides it with explicit (n_target, nsteps) pairs;
    ``case_name`` benchmarks any registered scenario (BENCH records are
    tagged with it); ``dynamic_sizes`` adds dam-break rows with a
    Verlet skin — rebuilds fire inside the timed scan, so their
    steps/sec is the amortized (physics + rebuild) throughput. A tier
    that fails to build or run (e.g. an OOM at the 1M tier) is recorded
    as a failed row with the reason, the remaining tiers still run, and
    the call then raises: a failed tier fails the benchmark."""
    if sizes is None:
        targets = [8000, 64000] if full else [8000]
        sizes = [(t, default_steps(t)) for t in targets]
    runs = [("reference", "fp32"), ("xla", "fp32"), ("xla", "fp16")]
    rows, failed = [], []

    def attempt(n_target, backend, nsteps, **kw):
        try:
            rows.append(run_case(n_target, backend, nsteps, **kw))
        except Exception as e:  # noqa: BLE001 - record, run the rest, raise below
            reason = f"{type(e).__name__}: {e}"[:300]
            failed.append({
                "case": kw.get("case_name", case_name),
                "dynamic": kw.get("dynamic", False),
                "n_target": n_target, "backend": backend,
                "records": kw.get("records", "fp16"), "failed": reason,
            })
            emit("step_throughput_failed", failed[-1])

    for n_target, nsteps in sizes:
        combos = runs if n_target <= BIG_TIER else [("xla", "fp16")]
        for backend, records in combos:
            attempt(n_target, backend, nsteps, records=records,
                    case_name=case_name)
    for n_target, nsteps in dynamic_sizes or []:
        combos = (
            [("reference", "fp32"), ("xla", "fp16")]
            if n_target <= BIG_TIER else [("xla", "fp16")]
        )
        for backend, records in combos:
            attempt(n_target, backend, nsteps, records=records,
                    case_name="dam_break", dynamic=True)
    if skin_compare and case_name == "poiseuille":
        # PR 1's skin-vs-none tracking metric (fused backend, 8k)
        attempt(sizes[0][0], "xla", sizes[0][1], skin_frac_hc=0.0)

    if not rows:
        # every tier failed (e.g. a 1M-only invocation that OOMed)
        _finish({
            "label": "rebuild_round",
            "case": case_name,
            "backend": jax.default_backend(),
            "cpu_count": os.cpu_count(),
            "cases": [],
        }, failed, append, out, {})

    def pick(n_target, backend, records):
        for r in rows:
            if r.get("dynamic"):
                continue
            if (r["n_target"], r["backend"], r["records"]) == (
                n_target, backend, records
            ) and (r["skin_frac_hc"] > 0 or case_name != "poiseuille"):
                return r
        return None

    speedups, layout_speedups = {}, {}
    for n_target, _ in sizes:
        ref = pick(n_target, "reference", "fp32")
        h16 = pick(n_target, "xla", "fp16")
        f32 = pick(n_target, "xla", "fp32")
        if ref and h16:
            speedups[str(n_target)] = round(
                h16["steps_per_sec"] / ref["steps_per_sec"], 3
            )
        if f32 and h16:
            layout_speedups[str(n_target)] = round(
                h16["steps_per_sec"] / f32["steps_per_sec"], 3
            )
    record = {
        "label": "rebuild_round",
        "case": case_name,
        "backend": jax.default_backend(),
        # CPU wall-clocks are machine-sensitive: record the core count so
        # cross-record comparisons (compare_bench) can be read in context.
        "cpu_count": os.cpu_count(),
        "cases": rows,
        "steps_per_sec_speedup_fused_vs_gather": speedups,
        "steps_per_sec_half_vs_fp32_records": layout_speedups,
    }
    return _finish(record, failed, append, out, speedups)


def _finish(record, failed, append, out, summary):
    """Write the run record (failed tiers included, with their reasons),
    then raise if any tier failed."""
    if failed:
        record["failed"] = failed
    if append:
        _append_record(record)
    if out:
        with open(out, "w") as f:
            json.dump(record, f, indent=2)
    emit("step_throughput_summary", {**summary, "failed": len(failed)})
    if failed:
        raise RuntimeError(
            f"{len(failed)} benchmark tier(s) failed: "
            + "; ".join(f["failed"] for f in failed))
    return record


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--n", type=int, action="append", default=None,
        help="particle-count target (repeatable); e.g. --n 1000000 for "
        "the paper's 1M case. Default: 8000 and 64000.",
    )
    ap.add_argument("--quick", action="store_true", help="8k only")
    ap.add_argument(
        "--nsteps", type=int, default=None,
        help="timed steps per segment (default: scaled by size)",
    )
    ap.add_argument(
        "--no-append", action="store_true",
        help="do not append the run record to BENCH_nnps.json (CI smoke "
        "runs must not pollute the perf history)",
    )
    ap.add_argument(
        "--out", type=str, default=None,
        help="also write this run's record to a standalone JSON file "
        "(pairs with compare_bench --candidate)",
    )
    ap.add_argument(
        "--case", type=str, default="poiseuille",
        choices=cases.case_names(),
        help="registered scenario to benchmark (BENCH records are "
        "tagged with it); non-poiseuille cases run skinless",
    )
    ap.add_argument(
        "--dynamic", action="store_true",
        help="also run dam-break rows with a Verlet skin at the same "
        "tiers: rebuilds fire inside the timed scan, so steps/sec is "
        "the amortized physics+rebuild throughput (reported with "
        "rebuilds_per_100_steps)",
    )
    ap.add_argument(
        "--dynamic-n", type=int, action="append", default=None,
        help="override the --dynamic tier list (repeatable)",
    )
    args = ap.parse_args()
    if args.n:
        targets = args.n
    elif args.quick:
        targets = [8000]
    else:
        targets = [8000, 64000]
    sizes = [(t, args.nsteps or default_steps(t)) for t in targets]
    dynamic_sizes = None
    if args.dynamic or args.dynamic_n:
        dyn_targets = args.dynamic_n or targets
        # dynamic rows need enough steps for the Verlet criterion to
        # fire several rebuilds inside the timed segments (~1 rebuild
        # per ~25-30 steps at the v0 drop speed)
        dynamic_sizes = [
            (t, max(32, args.nsteps or default_steps(t)))
            for t in dyn_targets
        ]
    main(
        sizes=sizes,
        skin_compare=not args.n,
        append=not args.no_append,
        out=args.out,
        case_name=args.case,
        dynamic_sizes=dynamic_sizes,
    )
