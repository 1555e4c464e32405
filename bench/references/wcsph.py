"""Plain weakly compressible SPH, written from the published equations.

The reference that decides ``correct``: it imports nothing of the
solver under test and takes nothing it made. Positions are held as the
initial fp32 lattice position plus an fp32 displacement, so a pair
distance keeps fp32 precision however far the box reaches; every field
and every sum is fp32. Neighbours come from a plain cell list of cell
size ``2h`` (its own grid), rebuilt every step: each particle meets
every particle in the 3x3 cells around its own.

Equations (2-D, cubic B-spline of Monaghan & Lattanzio 1985 with
support 2h; symplectic Euler as in DualSPHysics, arXiv:1110.3711):

* continuity  dρ_i/dt = Σ_j m_j (v_i − v_j)·∇W_ij
  plus δ-SPH (Molteni & Colagrossi 2009)
  δ h c0 Σ_j 2 (ρ_j − ρ_i) (x_ji·∇W_ij) / (r² + 0.01 h²) · m_j/ρ_j;
* momentum    dv_i/dt = −Σ_j m_j (p_i/ρ_i² + p_j/ρ_j² + Π_ij) ∇W_ij
  + Σ_j m_j 2μ (x_ij·∇W_ij) / (ρ_i ρ_j (r² + 0.01 h²)) (v_i − v_j) + g,
  with Monaghan's Π_ij = −α c0 h (v_ij·x_ij) / ((r² + 0.01 h²) ρ̄_ij)
  where v_ij·x_ij < 0 (Morris, Fox & Zhu 1997 for the μ term);
* EOS  p = c0² (ρ − ρ0) (linear) or B ((ρ/ρ0)^γ − 1), B = c0² ρ0 / γ;
* update  ρ += dt dρ/dt (walls clamped at ρ0 where stated),
  v += dt dv/dt (walls held at 0), x += dt v (fluid only).

``records`` names the precision in which the pair sums read v and m
(the solver's record fields). ``"fp32"`` is the reference itself; a
lower one, each field scaled by its largest magnitude first, is the
control that must fail the comparison.
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# (exponent bits, mantissa bits) of the record precisions: the
# reference's own, and the control's
RECORD_BITS = {
    "fp32": None,
    "fp8": (4, 3),  # float8_e4m3
}


class Physics(NamedTuple):
    """Static numbers of one flow (hashable: a jit static argument)."""

    h: float
    dt: float
    rho0: float
    c0: float
    eos: str  # "linear" | "tait"
    gamma: float
    mu: float
    alpha: float
    delta: float
    body_force: tuple
    wall_rho_clamp: bool
    lo: tuple  # box bounds (walls included)
    hi: tuple
    periodic: tuple
    cap: int  # slots per cell of the reference grid
    block: int  # pairs of slots per block of the pair sums
    records: str = "fp32"


class State(NamedTuple):
    x0: jax.Array  # (N, 2) f32 initial position
    u: jax.Array  # (N, 2) f32 displacement since t = 0
    v: jax.Array  # (N, 2) f32
    rho: jax.Array  # (N,) f32
    m: jax.Array  # (N,) f32
    wall: jax.Array  # (N,) bool


def physics_of(cfg: dict, *, records: str = "fp32",
               block: int = 1 << 24) -> Physics:
    """The reference's numbers from a configuration file's ``physics``."""
    p = cfg["physics"]
    lo, hi = cfg["box"]["lo"], cfg["box"]["hi"]
    h = p["h"]
    per = tuple(bool(x) for x in cfg["box"]["periodic"])
    # A cell of edge >= 2h holds at most (2h/ds + 1)^2 lattice nodes;
    # half again for compression, checked every step.
    cap = int(math.ceil((2.0 * h / cfg["ds"] + 1.0) ** 2 * 1.5))
    return Physics(
        h=h, dt=cfg["dt"], rho0=p["rho0"], c0=p["c0"], eos=p["eos"],
        gamma=p["gamma"], mu=p["mu"], alpha=p["alpha"], delta=p["delta"],
        body_force=tuple(p["body_force"]),
        wall_rho_clamp=bool(p["wall_rho_clamp"]),
        lo=tuple(lo), hi=tuple(hi), periodic=per, cap=cap,
        block=block, records=records,
    )


def _grid(ph: Physics):
    """Cells per axis and their edges: >= 2h, tiling periodic axes."""
    nc, edge = [], []
    for lo, hi, per in zip(ph.lo, ph.hi, ph.periodic):
        span = hi - lo
        if per:
            n = max(3, int(math.floor(span / (2.0 * ph.h))))
            nc.append(n)
            edge.append(span / n)
        else:
            n = int(math.ceil(span / (2.0 * ph.h))) + 1
            nc.append(n)
            edge.append(2.0 * ph.h)
    return tuple(nc), tuple(edge)


def _quantize(x, records: str):
    """x as the pair sums read it: rounded to ``records`` after scaling
    by its largest magnitude. ``reduce_precision`` rounds for certain:
    a compiler may drop a cast to a narrower type and back."""
    bits = RECORD_BITS[records]
    if bits is None:
        return x
    s = jnp.max(jnp.abs(x))
    s = jnp.where(s > 0, s, 1.0)
    return jax.lax.reduce_precision(x / s, *bits) * s


def _pressure(ph: Physics, rho):
    if ph.eos == "linear":
        return ph.c0 * ph.c0 * (rho - ph.rho0)
    b = ph.c0 * ph.c0 * ph.rho0 / ph.gamma
    return b * ((rho / ph.rho0) ** ph.gamma - 1.0)


def _dw_over_r(ph: Physics, r):
    """(dW/dr)/r of the 2-D cubic B-spline; 0 at r = 0 and r >= 2h."""
    h = ph.h
    q = r / h
    a = 15.0 / (7.0 * math.pi * h * h) / h
    d = jnp.where(q < 1.0, -2.0 * q + 1.5 * q * q,
                  jnp.where(q < 2.0, -0.5 * (2.0 - q) ** 2, 0.0))
    return jnp.where(r > 0.0, a * d / jnp.where(r > 0.0, r, 1.0), 0.0)


def _pad_cells(a, periodic, fill):
    """One cell of padding on each side of axes 0 and 1: the wrapped
    neighbour cells on a periodic axis, empty cells (``fill``) on a
    walled one."""
    for axis, per in enumerate(periodic):
        if per:
            lo = jax.lax.slice_in_dim(a, a.shape[axis] - 1, a.shape[axis],
                                      axis=axis)
            hi = jax.lax.slice_in_dim(a, 0, 1, axis=axis)
        else:
            shape = list(a.shape)
            shape[axis] = 1
            lo = hi = jnp.broadcast_to(fill, shape).astype(a.dtype)
        a = jnp.concatenate([lo, a, hi], axis=axis)
    return a


def _rates(ph: Physics, st: State):
    """(dρ/dt, dv/dt) of every particle, and the cell-overflow flag.

    Particles are sorted by cell and copied into a cell table of ``cap``
    slots per cell. Every cell then meets each of its nine neighbour
    cells (the table shifted by one cell along each axis) slot by slot,
    in blocks of grid rows.
    """
    n = st.x0.shape[0]
    (nx, ny), edge = _grid(ph)
    ncell = nx * ny
    cap = ph.cap
    lo = jnp.asarray(ph.lo, jnp.float32)
    span = jnp.asarray([h - l for l, h in zip(ph.lo, ph.hi)], jnp.float32)
    per = jnp.asarray(ph.periodic)
    ncv = jnp.asarray((nx, ny), jnp.int32)
    x = st.x0 + st.u
    x = jnp.where(per, lo + jnp.mod(x - lo, span), x)
    ixy = jnp.floor((x - lo) / jnp.asarray(edge, jnp.float32))
    ixy = jnp.clip(ixy.astype(jnp.int32), 0, ncv - 1)
    cid = ixy[:, 0] * ny + ixy[:, 1]

    order = jnp.argsort(cid, stable=True).astype(jnp.int32)
    scid = cid[order]
    counts = jnp.zeros((ncell,), jnp.int32).at[cid].add(1)
    starts = jnp.cumsum(counts) - counts
    slot = jnp.arange(n, dtype=jnp.int32) - starts[scid]
    overflow = jnp.max(counts) > cap
    dest = jnp.where(slot < cap, scid * cap + slot, ncell * cap)

    rho = st.rho
    fields = jnp.concatenate([
        st.x0, st.u, _quantize(st.v, ph.records), rho[:, None],
        _quantize(st.m, ph.records)[:, None],
        (_pressure(ph, rho) / (rho * rho))[:, None],
    ], axis=1)  # (N, 9): x0, u, v, ρ, m, p/ρ²
    empty = jnp.zeros((9,), jnp.float32).at[6].set(ph.rho0)
    tab = jnp.broadcast_to(empty, (ncell * cap, 9))
    tab = tab.at[dest].set(fields[order], mode="drop")
    ids = jnp.full((ncell * cap,), n, jnp.int32)
    ids = ids.at[dest].set(order, mode="drop")

    # rows of the grid per block: about 2^24 pairs of slots at a time
    bx = max(1, min(nx, ph.block // (ny * cap * cap)))
    nblk = -(-nx // bx)
    tab = _pad_cells(tab.reshape(nx, ny, cap, 9), ph.periodic, empty)
    ids = _pad_cells(ids.reshape(nx, ny, cap), ph.periodic, n)
    extra = nblk * bx - nx  # empty rows so every block is whole
    tab = jnp.concatenate(
        [tab, jnp.broadcast_to(empty, (extra, ny + 2, cap, 9))])
    ids = jnp.concatenate([ids, jnp.full((extra, ny + 2, cap), n)])

    h, c0 = ph.h, ph.c0
    eta2 = 0.01 * h * h

    def block(b):
        slab = jax.lax.dynamic_slice_in_dim(tab, b * bx, bx + 2, axis=0)
        islab = jax.lax.dynamic_slice_in_dim(ids, b * bx, bx + 2, axis=0)
        fi = slab[1:-1, 1:-1][:, :, :, None, :]  # (bx, ny, cap, 1, 9)
        idi = islab[1:-1, 1:-1][:, :, :, None]
        drho = jnp.zeros((bx, ny, cap), jnp.float32)
        acc = jnp.zeros((bx, ny, cap, 2), jnp.float32)
        for ox in range(3):
            for oy in range(3):
                fj = slab[ox:ox + bx, oy:oy + ny][:, :, None]  # (.., 1, cap, 9)
                idj = islab[ox:ox + bx, oy:oy + ny][:, :, None]
                dx = (fi[..., 0:2] - fj[..., 0:2]) + (fi[..., 2:4]
                                                      - fj[..., 2:4])
                dx = jnp.where(per, dx - span * jnp.round(dx / span), dx)
                r2 = jnp.sum(dx * dx, axis=-1)
                r = jnp.sqrt(r2)
                use = (idj != n) & (idj != idi) & (r < 2.0 * h)
                f = jnp.where(use, _dw_over_r(ph, r), 0.0)  # ∇W = f · dx
                mj = fj[..., 7]
                dv = fi[..., 4:6] - fj[..., 4:6]
                vdx = jnp.sum(dv * dx, axis=-1)
                xgw = f * r2  # x_ij·∇W_ij
                d = mj * f * vdx
                coef = mj * (fi[..., 8] + fj[..., 8])
                rho_i, rho_j = fi[..., 6], fj[..., 6]
                if ph.alpha:
                    rbar = 0.5 * (rho_i + rho_j)
                    pi_ij = -ph.alpha * c0 * h * vdx / ((r2 + eta2) * rbar)
                    coef = coef + mj * jnp.where(vdx < 0.0, pi_ij, 0.0)
                a = -(coef * f)[..., None] * dx
                if ph.mu:
                    vc = mj * 2.0 * ph.mu * xgw / (rho_i * rho_j * (r2 + eta2))
                    a = a + vc[..., None] * dv
                if ph.delta:
                    d = d + ph.delta * h * c0 * (
                        2.0 * (rho_j - rho_i) * (-xgw) / (r2 + eta2)
                        * mj / rho_j)
                drho = drho + jnp.sum(d, axis=3)
                acc = acc + jnp.sum(a, axis=3)
        return drho, acc

    drho, acc = jax.lax.map(block, jnp.arange(nblk, dtype=jnp.int32))
    at = jnp.minimum(scid * cap + slot, ncell * cap - 1)
    drho = drho.reshape(-1)[:ncell * cap][at]
    acc = acc.reshape(-1, 2)[:ncell * cap][at]
    drho = jnp.zeros((n,), jnp.float32).at[order].set(drho)
    acc = jnp.zeros((n, 2), jnp.float32).at[order].set(acc)
    return drho, acc, overflow


def _step(ph: Physics, st: State, overflow):
    drho, acc, over = _rates(ph, st)
    rho = st.rho + ph.dt * drho
    if ph.wall_rho_clamp:
        rho = jnp.where(st.wall, jnp.maximum(rho, ph.rho0), rho)
    g = jnp.asarray(ph.body_force, jnp.float32)
    v = st.v + ph.dt * (acc + g)
    v = jnp.where(st.wall[:, None], 0.0, v)
    u = st.u + jnp.where(st.wall[:, None], 0.0, ph.dt * v)
    return st._replace(u=u, v=v, rho=rho), overflow | over


@partial(jax.jit, static_argnums=(0,))
def run(ph: Physics, st: State, nsteps):
    """``nsteps`` (traced) steps from ``st``; returns (state, overflow)."""
    with jax.default_matmul_precision("highest"):
        return jax.lax.fori_loop(
            0, nsteps, lambda _, c: _step(ph, *c),
            (st, jnp.zeros((), bool)))


def initial_state(x, v, rho, m, wall) -> State:
    """The reference's state from the generated inputs (host or device)."""
    x = jnp.asarray(x, jnp.float32)
    return State(x0=x, u=jnp.zeros_like(x), v=jnp.asarray(v, jnp.float32),
                 rho=jnp.asarray(rho, jnp.float32),
                 m=jnp.asarray(m, jnp.float32),
                 wall=jnp.asarray(wall, bool))


def positions(st: State) -> np.ndarray:
    return np.asarray(st.x0 + st.u)


def simulate(cfg: dict, inputs, steps: int, *, records: str = "fp32",
             block: int = 1 << 24) -> dict:
    """The reference after ``steps`` steps from ``inputs``, on the host.

    Returns {"x", "v", "rho"} as numpy arrays in the inputs' order and
    "overflow": whether a cell of the reference grid ever held more
    particles than its capacity (the run is then no reference).
    """
    ph = physics_of(cfg, records=records, block=block)
    st, over = run(ph, initial_state(*inputs), jnp.int32(steps))
    return {"x": positions(st), "v": np.asarray(st.v),
            "rho": np.asarray(st.rho), "overflow": bool(over)}
