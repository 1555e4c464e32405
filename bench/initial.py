"""The cell's inputs: a particle lattice from a configuration and a seed.

One general generator reads the configuration's ``lattice`` block:

* nodes sit at ``lo + (i + 1/2) ds`` over the box ``lattice.lo`` ..
  ``lattice.hi``;
* a node inside a ``fluid`` rectangle is fluid; a node outside the open
  ``interior`` rectangle is a wall (dummy) particle; any other node is
  empty space and holds no particle;
* fluid nodes move by a seeded jitter, uniform in ``±jitter·ds`` per
  axis; walls never move;
* density is ρ0, or hydrostatic under ``hydrostatic`` (Tait-inverted
  ρ0 (1 + γ ρ0 g (H − y) / (ρ0 c0²))^(1/γ) below the surface H, as in
  DualSPHysics); mass is ρ0 ds²;
* fluid starts at the uniform ``velocity`` (0 where not given), or,
  under ``series``, at the start-up profile of a body-force-driven
  channel at time ``t`` (Morris, Fox & Zhu 1997, eq. 21): with y the
  distance from the lower wall across a channel of width ``L``,
  v_x = F/(2ν) y (L − y) − Σ_n 4 F L² / (ν π³ k³) sin(k π y / L)
  exp(−k² π² ν t / L²), k = 2n + 1; walls at rest.

Counts per kind follow from the axes alone (host arithmetic on 1-D
grids), so the device builds everything in one jitted call with static
shapes. Fluid particles come first, then walls.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Inputs(NamedTuple):
    x: jax.Array  # (N, d) f32 physical position
    v: jax.Array  # (N, d) f32
    rho: jax.Array  # (N,) f32
    m: jax.Array  # (N,) f32
    wall: jax.Array  # (N,) bool


def _axes(cfg: dict) -> list[np.ndarray]:
    lat, ds = cfg["lattice"], cfg["ds"]
    return [np.arange(lo + ds / 2, hi, ds)
            for lo, hi in zip(lat["lo"], lat["hi"])]


def _inside(axis: np.ndarray, lo: float, hi: float, ds: float) -> np.ndarray:
    eps = 1e-9 * ds
    return (axis > lo + eps) & (axis < hi - eps)


def _rect_masks(cfg: dict):
    """Per-axis membership of the fluid rectangle and the interior."""
    ds, lat = cfg["ds"], cfg["lattice"]
    axes = _axes(cfg)
    fl = [_inside(a, lo, hi, ds) for a, lo, hi in
          zip(axes, lat["fluid"]["lo"], lat["fluid"]["hi"])]
    inn = [_inside(a, lo, hi, ds) for a, lo, hi in
           zip(axes, lat["interior"]["lo"], lat["interior"]["hi"])]
    return axes, fl, inn


def counts(cfg: dict) -> tuple[int, int]:
    """(fluid, wall) particle counts of a configuration."""
    axes, fl, inn = _rect_masks(cfg)
    total = int(np.prod([a.size for a in axes]))
    n_fluid = int(np.prod([m.sum() for m in fl]))
    n_inner = int(np.prod([m.sum() for m in inn]))
    return n_fluid, total - n_inner


def _seed_key(seed: int) -> jax.Array:
    """A key from any whole number up to 64 bits."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def build(cfg: dict, seed: int) -> Inputs:
    """The configuration's particles on the default device."""
    axes, fl, inn = _rect_masks(cfg)
    n_fluid, n_wall = counts(cfg)
    hyd = cfg["lattice"].get("hydrostatic")
    p = cfg["physics"]
    v0 = tuple(float(x) for x in cfg["lattice"].get("velocity",
                                                 [0.0] * len(axes)))
    ser = cfg["lattice"].get("series")
    if ser is not None:
        ser = tuple(float(ser[k]) for k in ("F", "nu", "L", "t", "terms"))
    static = (
        n_fluid, n_wall, float(cfg["ds"]), float(cfg["jitter"]),
        float(p["rho0"]), v0, ser,
        None if hyd is None else (float(hyd["g"]), float(hyd["surface"]),
                                  float(p["gamma"]), float(p["c0"])),
    )
    return _build(static, _seed_key(int(seed)),
                  tuple(np.asarray(a, np.float32) for a in axes),
                  tuple(np.asarray(m) for m in fl),
                  tuple(np.asarray(m) for m in inn))


@partial(jax.jit, static_argnums=(0,))
def _build(static, key, axes, fl, inn) -> Inputs:
    n_fluid, n_wall, ds, jitter, rho0, v0, ser, hyd = static
    dim = len(axes)
    grid = jnp.meshgrid(*axes, indexing="ij")
    pts = jnp.stack([g.ravel() for g in grid], axis=-1)
    in_fl = jnp.ones(grid[0].shape, bool)
    in_in = jnp.ones(grid[0].shape, bool)
    for a in range(dim):
        shape = [1] * dim
        shape[a] = -1
        in_fl = in_fl & fl[a].reshape(shape)
        in_in = in_in & inn[a].reshape(shape)
    (fi,) = jnp.nonzero(in_fl.ravel(), size=n_fluid)
    (wi,) = jnp.nonzero(~in_in.ravel(), size=n_wall)
    xf = pts[fi]
    xw = pts[wi]
    jit = jax.random.uniform(key, xf.shape, jnp.float32, -1.0, 1.0)
    x = jnp.concatenate([xf + jitter * ds * jit, xw])
    n = n_fluid + n_wall
    wall = jnp.arange(n) >= n_fluid
    rho = jnp.full((n,), rho0, jnp.float32)
    if hyd is not None:
        g, surface, gamma, c0 = hyd
        p_h = rho0 * g * jnp.maximum(surface - xf[:, 1], 0.0)
        rho_f = rho0 * (1.0 + gamma * p_h / (rho0 * c0 * c0)) ** (1.0 / gamma)
        rho = rho.at[:n_fluid].set(rho_f)
    m = jnp.full((n,), rho0 * ds * ds, jnp.float32)
    v = jnp.where(wall[:, None], 0.0, jnp.asarray(v0, jnp.float32))
    if ser is not None:
        vx = jnp.concatenate([_series(ser, x[:n_fluid, 1]),
                              jnp.zeros((n_wall,), jnp.float32)])
        v = v.at[:, 0].set(vx)
    return Inputs(x=x, v=v, rho=rho, m=m, wall=wall)


def _series(ser, y):
    """Morris et al.'s start-up profile v_x(y, t) of a channel flow."""
    f, nu, width, t, terms = ser
    y = jnp.clip(y, 0.0, width)
    v = f / (2.0 * nu) * y * (width - y)
    for n in range(int(terms)):
        k = 2 * n + 1
        amp = 4.0 * f * width**2 / (nu * np.pi**3 * k**3)
        decay = np.exp(-(k * np.pi) ** 2 * nu * t / width**2)
        v = v - amp * decay * jnp.sin(k * np.pi * y / width)
    return v.astype(jnp.float32)
