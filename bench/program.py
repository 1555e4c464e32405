"""The system under test, built from a configuration and a workload.

Everything the benchmark takes from the program passes through here:
the solver's configuration object, its state built from the generated
inputs, and the jitted entry points the window drives
(``solver.init_persistent`` -> ``solver.run_persistent`` ->
``solver.finalize_persistent``).
"""
from __future__ import annotations

import os
import sys
from functools import partial

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(CHECKOUT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(CHECKOUT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import scheme as scheme_lib  # noqa: E402
from repro.core import solver  # noqa: E402
from repro.core.domain import Domain  # noqa: E402
from repro.core.precision import PrecisionPolicy  # noqa: E402


def sph_config(cfg: dict, work: dict) -> solver.SPHConfig:
    """The solver's configuration as the cell runs it.

    The workload adds the Verlet skin (``skin_frac`` of the search radius
    2h) and sizes the cells to cover it (``cell_factor = 1 + skin_frac``).
    """
    p = cfg["physics"]
    skin_frac = float(work["skin_frac"])
    domain = Domain(
        lo=tuple(cfg["box"]["lo"]), hi=tuple(cfg["box"]["hi"]), h=p["h"],
        cell_factor=1.0 + skin_frac,
        periodic=tuple(bool(x) for x in cfg["box"]["periodic"]),
    )
    sch = scheme_lib.Scheme(
        c0=p["c0"], rho0=p["rho0"], eos=p["eos"], gamma=p["gamma"],
        viscosity=p["viscosity"], mu=p["mu"], alpha=p["alpha"],
        delta=p["delta"], body_force=tuple(p["body_force"]),
    )
    return solver.SPHConfig(
        domain=domain, ds=cfg["ds"], dt=cfg["dt"], rho0=p["rho0"],
        c0=p["c0"], mu=p["mu"], body_force=tuple(p["body_force"]),
        algo="rcll", policy=PrecisionPolicy(**cfg["precision"]),
        scheme=sch, wall_rho_clamp=bool(p["wall_rho_clamp"]),
        skin=skin_frac * 2.0 * p["h"], backend=cfg["backend"],
    )


@partial(jax.jit, static_argnums=(0,))
def init(scfg: solver.SPHConfig, x, v, rho, m, wall) -> solver.PersistentCarry:
    """Solver state from the inputs, packed for the persistent scan."""
    st = solver.init_state(scfg, x, v, m, rho, kind=wall.astype(jnp.int8))
    return solver.init_persistent(scfg, st)


run = solver.run_persistent


@partial(jax.jit, static_argnums=(0,))
def finalize(scfg: solver.SPHConfig, carry: solver.PersistentCarry):
    """(positions, velocities, densities) in the inputs' particle order."""
    st = solver.finalize_persistent(scfg, carry)
    return solver.positions(scfg, st), st.fluid.v, st.fluid.rho
