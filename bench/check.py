"""The comparison that decides ``correct``.

The program's final particles (positions, velocities, densities, in the
inputs' order) are set against the plain reference run from the same
inputs for the same number of steps. Each compared number is a widest
gap: the largest gap over all particles, as a share of the largest
change the reference made to that field over the run.

* ``v_gap``   max_i |v_i − v_i^ref| / max_i |v_i^ref − v_i(0)|
* ``rho_gap`` max_i |ρ_i − ρ_i^ref| / max_i |ρ_i^ref − ρ_i(0)|

A gap whose field the reference left unchanged cannot be formed (None).
Positions are not compared: over a window the reference moves no
particle by more than about 2e-7, below the resolution of the program's
fp16 cell-relative coordinates, whose advance rounds such steps away.

A cell compares the numbers that its workload file gives a limit. Three
guarantees of the run are held at 0 beside them: no program compiled
inside the measured window (``window_compiles``), the solver's step
counter advanced by the steps the window asked for (``steps_gap``), and
the reference never dropped a particle from its cell list
(``ref_overflow``).
"""
from __future__ import annotations

import numpy as np


def gaps(inputs, program_out: dict, reference_out: dict) -> dict:
    """The widest gaps of ``program_out`` against ``reference_out``."""
    out = {}
    for name, field, start in (("v_gap", "v", inputs.v),
                               ("rho_gap", "rho", inputs.rho)):
        ref = np.asarray(reference_out[field], np.float64)
        got = np.asarray(program_out[field], np.float64)
        change = np.max(np.abs(ref - np.asarray(start, np.float64)))
        gap = np.max(np.abs(got - ref))
        out[name] = float(gap / change) if change > 0 else None
    return out


def judge(numbers: dict, limits: dict) -> dict:
    """{name: {"value", "limit", "ok"}} for every limit. A number that
    could not be formed (None: the reference did not change the field)
    or is not finite fails."""
    rows = {}
    for name, limit in limits.items():
        value = numbers[name]
        ok = value is not None and bool(np.isfinite(value) and value <= limit)
        rows[name] = {"value": value, "limit": limit, "ok": ok}
    return rows
