"""``correct``: sound tiny runs pass, the control and planted faults fail.

These drive the whole of ``bench/run.py``'s ``execute`` — inputs from a
seed, set-up, window, reference, comparison — on the CPU at a size a
test can hold (the configurations' own numbers at a coarser spacing,
the stated Pallas path in interpret mode), skipping only the look for
a chip. The window is one chunk (``seconds=0``).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import pytest

from bench import check, initial, program, run, spec
from bench.tests import small

DS = {"dam_break": 0.02, "poiseuille": 0.02}
SEED = 2**41 + 17
CELLS = [w["name"] for w in spec.benchmark()["workloads"]]


def _execute(name: str, seed: int = SEED) -> dict:
    entry = next(w for w in spec.benchmark()["workloads"]
                 if w["name"] == name)
    cfg = small.scaled(entry["config"], DS[entry["config"]])
    return run.execute(name, seed, 0.0, False, device=jax.devices()[0],
                       peak=spec.peaks("TPU v5 lite"), cfg=cfg,
                       t_start=time.perf_counter())


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    line = _execute(name)
    assert line["correct"] is True, line["checks"]
    assert list(line)[-1] == "checks"
    assert line["metrics"]["particle_steps_per_s"]["value"] > 0


def test_state_left_unchanged_is_not_correct(monkeypatch):
    """The step counter moves, the particles do not."""
    monkeypatch.setattr(program, "run", lambda scfg, carry, n:
                        carry._replace(steps=carry.steps + n))
    line = _execute("dam_break.1m_rebuild")
    assert line["correct"] is False
    assert line["checks"]["rho_gap"]["value"] > 0.5


def test_counter_left_unchanged_is_not_correct(monkeypatch):
    monkeypatch.setattr(program, "run", lambda scfg, carry, n: carry)
    line = _execute("dam_break.1m_rebuild")
    assert line["correct"] is False
    assert line["checks"]["steps_gap"]["value"] > 0


def test_answer_altered_where_produced_is_not_correct(monkeypatch):
    real = program.run

    def altered(scfg, carry, n):
        carry = real(scfg, carry, n)
        return _alter(carry)

    monkeypatch.setattr(program, "run", altered)
    line = _execute("dam_break.1m_rebuild")
    assert line["correct"] is False


@pytest.mark.parametrize("name", ["dam_break.1m_rebuild",
                                  "poiseuille.1m_skin"])
def test_control_in_lower_precision_fails(name):
    """The reference with fp8 records in the program's place fails at
    least one of the cell's limits; the reference against itself reads
    0. (The program's bf16 path is the other control, below.) The
    channel runs at its own spacing on a strip: the rounding of v in the
    viscous sum grows as 1/ds², so a coarse grid would hide it."""
    entry = next(w for w in spec.benchmark()["workloads"]
                 if w["name"] == name)
    work = spec.workload(name)
    if entry["config"] == "poiseuille":
        cfg = small.strip("poiseuille", 0.02)
    else:
        cfg = small.scaled(entry["config"], DS[entry["config"]])
    ref_mod = spec.reference(cfg["reference"])
    inputs = initial.build(cfg, SEED)
    steps = 6
    ref = ref_mod.simulate(cfg, inputs, steps)
    again = check.gaps(inputs, ref_mod.simulate(cfg, inputs, steps), ref)
    ctl = check.gaps(inputs, ref_mod.simulate(cfg, inputs, steps,
                                              records="fp8"), ref)
    assert all(v == 0.0 for v in again.values() if v is not None)
    rows = check.judge(ctl, work["limits"])
    assert not all(r["ok"] for r in rows.values()), rows


def test_bf16_records_control_fails():
    """The program with its own bf16 records (on its XLA force path) in
    place of the stated fp16 fails the Poiseuille cell's limit, at the
    cell's own spacing on a strip of the channel, over the steps of a
    traced run."""
    from bench import control

    cfg = small.strip("poiseuille", 0.02)
    work = spec.workload("poiseuille.1m_skin")
    bf16 = dict(cfg, backend="xla",
                precision=dict(cfg["precision"], records="bf16"))
    inputs = initial.build(cfg, SEED)
    steps = 8
    ref = spec.reference(cfg["reference"]).simulate(cfg, inputs, steps)
    nchunks = steps // work["chunk_steps"]
    _, _, v, rho = control._program(bf16, work, inputs, nchunks)
    rows = check.judge(check.gaps(inputs, {"v": v, "rho": rho}, ref),
                       work["limits"])
    assert not all(r["ok"] for r in rows.values()), rows


@jax.jit
def _alter(carry):
    """One particle's velocity and density changed by a visible amount."""
    fl = carry.st.fluid
    v = fl.v.at[0].add(0.5 * jnp.max(jnp.abs(fl.v)) + 1e-3)
    rho = fl.rho.at[0].mul(1.01)
    return carry._replace(st=carry.st._replace(
        fluid=fl._replace(v=v, rho=rho)))
