"""Fused cell-blocked force pass: backend agreement (reference / xla /
pallas-interpret), half-width record quantization (derived tolerance +
bit-exactness), stale-binning re-anchoring under cell migration,
overflow surfacing, and the donating scan entry point."""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import cases, cells, domain as D, fused, rcll, solver, sph
from repro.core.precision import FP32_RECORDS, PrecisionPolicy

ON_TPU = jax.default_backend() == "tpu"

C0, RHO0 = 1.25, 1.0


def _poiseuille(backend, *, ds=0.1, skin_frac=0.0, records="fp32", **kw):
    kw.setdefault("max_neighbors", 96 if skin_frac > 0 else 40)
    case = cases.PoiseuilleCase(
        ds=ds, Lx=0.8, algo="rcll", backend=backend,
        cell_factor=2.0 if skin_frac > 0 else 1.0,
        policy=PrecisionPolicy(records=records),
        **kw,
    )
    cfg, st = case.build()
    if skin_frac > 0:
        cfg = dataclasses.replace(
            cfg, skin=skin_frac * min(cfg.domain.cell_sizes)
        )
    return cfg, st


def _cloud_setup(n=800, seed=0, k=256, hi=(1.0, 1.0), periodic=None,
                 cell_factor=2.0, safety=3.0):
    """Random cloud + packed state + skin-inflated list (no overflow)."""
    rng = np.random.default_rng(seed)
    dim = len(hi)
    ds = (float(np.prod(hi)) / n) ** (1.0 / dim)
    dom = D.Domain(lo=(0.0,) * dim, hi=tuple(hi), h=1.2 * ds,
                   cell_factor=cell_factor,
                   periodic=periodic or (False,) * dim)
    x = rng.uniform(0, hi, (n, dim))
    rc = rcll.init_state(dom, dom.normalize(jnp.asarray(x)), jnp.float16)
    cfg = solver.SPHConfig(
        domain=dom, ds=ds, dt=1e-3, max_neighbors=k, algo="rcll",
        skin=0.5 * min(dom.cell_sizes) if cell_factor > 1 else 0.0,
    )
    cfg.validate_skin()
    cap = cells.default_capacity(dom, n, safety=safety)
    ps = rcll.pack_state(dom, rc, cap)
    assert int(ps.packing.binning.overflow) == 0
    nl = rcll.packed_neighbors(
        dom, ps, dtype=jnp.float16, compute_dtype=jnp.float32, k=k,
        radius_cell=cfg.search_radius_cell, window=3**dim * cap,
    )
    assert not bool(nl.overflowed)
    fields = dict(
        v=jnp.asarray(rng.normal(size=(n, dim)) * 0.1, jnp.float32),
        m=jnp.full((n,), 1.0 / n, jnp.float32),
        rho=jnp.asarray(1.0 + 0.01 * rng.normal(size=(n,)), jnp.float32),
    )
    return dom, cfg, ps, nl, fields


# Grids of the Pallas kernel's row layout (one grid step per row of
# cells along the last axis, a ghost cell at each end of every axis,
# rows padded to 128 lanes): walls on both axes; a periodic slow or fast
# axis, whose ghosts copy the opposite edge; a fast axis of 130 cells,
# whose rows span two lane tiles; a 3-D grid.
GEOMETRIES = {
    "walls": dict(),
    "periodic_slow": dict(periodic=(True, False)),
    "periodic_fast": dict(periodic=(False, True)),
    "wide_fast": dict(n=2300, hi=(0.26, 11.1), cell_factor=1.0),
    "3d": dict(n=500, hi=(1.0, 1.0, 1.34), periodic=(False, False, True),
               cell_factor=1.0),
}


def _wrapped_pairs(dom, ps, nl) -> int:
    """Pairs of the list whose cells lie at opposite ends of a periodic
    axis (they interact across the periodic boundary)."""
    xy = np.asarray(ps.rc.cell_xy)
    idx = np.minimum(np.asarray(nl.idx), xy.shape[0] - 1)
    far = np.abs(xy[:, None, :] - xy[idx]) > 1
    return int(np.sum(np.any(far, -1) & np.asarray(nl.mask)))


def _reference_rhs(dom, rc, nl, v, m, rho, *, h, mu, rho0=RHO0, c0=C0):
    disp, r = rcll.pair_displacements(dom, rc, nl)
    gw = sph.grad_w(disp, r, h, dom.dim, nl.mask)
    pf = sph.gather_pair_fields(v, m, nl.idx, nl.mask)
    drho = sph.continuity_rhs_pairs(pf, gw)
    p = sph.eos_tait(rho, rho0, c0)
    acc = sph.momentum_rhs_pairs(
        pf, rho, p, nl.idx, gw, disp, r, h=h, mu=mu,
        body_force=jnp.zeros((dom.dim,), jnp.float32),
    )
    return drho, acc, p


# --------------------------------------------------------------------------
# drho / acc agreement on a static configuration
# --------------------------------------------------------------------------
def test_fused_xla_rhs_matches_reference():
    dom, cfg, ps, nl, f = _cloud_setup()
    drho_r, acc_r, p = _reference_rhs(
        dom, ps.rc, nl, f["v"], f["m"], f["rho"], h=dom.h, mu=1.0
    )
    for chunk in (0, 100, 10**6):  # padded map, odd chunk, single chunk
        drho_f, acc_f = fused.force_rhs(
            dom, ps.rc, nl, f["v"], f["m"], f["rho"],
            c0=C0, rho0=RHO0, chunk=chunk, mu=1.0,
        )
        np.testing.assert_allclose(drho_f, drho_r, rtol=2e-5, atol=1e-5)
        np.testing.assert_allclose(acc_f, acc_r, rtol=2e-5, atol=2e-3)


@pytest.mark.parametrize("geometry", list(GEOMETRIES) + ["vmap2"])
def test_fused_pallas_rhs_matches_reference(geometry):
    """The kernel against the reference gather path on each grid of
    ``GEOMETRIES``: a pair across a periodic axis interacts through the
    ghost copy, and across a wall it does not (the reference's list has
    such pairs exactly where the axis is periodic). ``vmap2``: a batch
    of two field sets through ``jax.vmap``, as the ensemble runs it."""
    from repro.kernels import ops

    batch = geometry == "vmap2"
    dom, cfg, ps, nl, f = _cloud_setup(
        **GEOMETRIES["walls" if batch else geometry])
    assert (_wrapped_pairs(dom, ps, nl) > 0) == any(dom.periodic)

    def kernel(v, rho):
        return ops.rcll_force_particles(
            dom, ps.packing.binning, ps.rc, v, f["m"], rho,
            mu=1.0, c0=C0, rho0=RHO0, interpret=not ON_TPU,
        )

    fields = [(f["v"], f["rho"])]
    if batch:
        fields.append((-0.5 * f["v"], 2.0 - f["rho"]))
        drho_b, acc_b = jax.vmap(kernel)(
            *[jnp.stack(x) for x in zip(*fields)])
        got = list(zip(drho_b, acc_b))
    else:
        got = [kernel(*fields[0])]
    for (v, rho), (drho_k, acc_k) in zip(fields, got):
        drho_r, acc_r, _ = _reference_rhs(
            dom, ps.rc, nl, v, f["m"], rho, h=dom.h, mu=1.0
        )
        np.testing.assert_allclose(drho_k, drho_r, rtol=2e-5, atol=1e-5)
        np.testing.assert_allclose(acc_k, acc_r, rtol=2e-5, atol=2e-3)


@pytest.mark.parametrize("geometry", ["walls", "periodic_slow",
                                      "periodic_fast"])
def test_fused_pallas_stale_binning_with_migrations(geometry):
    """Between Verlet rebuilds the binning is stale; particles that
    migrated cells must decode exactly via the int16 shift re-anchor,
    across a periodic boundary too (minimum-image shift, ghost copy)."""
    from repro.kernels import ops

    rng = np.random.default_rng(3)
    dom, cfg, ps, nl, f = _cloud_setup(seed=3, **GEOMETRIES[geometry])
    n = ps.rc.rel.shape[0]
    # displace by < skin/2 in random directions -> boundary-adjacent
    # particles migrate cells while the neighbor list stays valid
    dxn = jnp.asarray(rng.uniform(-1, 1, (n, 2)), jnp.float32)
    dxn = dxn / jnp.linalg.norm(dxn, axis=1, keepdims=True) * (
        0.45 * cfg.skin_norm / 2
    )
    rc1 = rcll.advance(dom, ps.rc, dxn, dtype=jnp.float16)
    migrated = np.any(
        np.asarray(rc1.cell_xy) != np.asarray(ps.rc.cell_xy), axis=1
    )
    assert migrated.sum() > 0, "setup must actually migrate particles"

    drho_r, acc_r, p = _reference_rhs(
        dom, rc1, nl, f["v"], f["m"], f["rho"], h=dom.h, mu=1.0
    )
    drho_k, acc_k = ops.rcll_force_particles(
        dom, ps.packing.binning, rc1, f["v"], f["m"], f["rho"],
        mu=1.0, c0=C0, rho0=RHO0, interpret=not ON_TPU,
    )
    np.testing.assert_allclose(drho_k, drho_r, rtol=2e-5, atol=1e-5)
    np.testing.assert_allclose(acc_k, acc_r, rtol=2e-5, atol=2e-3)
    # fused xla path too (consumes the same stale list + current state)
    drho_f, acc_f = fused.force_rhs(
        dom, rc1, nl, f["v"], f["m"], f["rho"], c0=C0, rho0=RHO0, mu=1.0
    )
    np.testing.assert_allclose(drho_f, drho_r, rtol=2e-5, atol=1e-5)
    np.testing.assert_allclose(acc_f, acc_r, rtol=2e-5, atol=2e-3)


# --------------------------------------------------------------------------
# half-width record quantization
# --------------------------------------------------------------------------
def _quantize(x, dtype):
    return jnp.asarray(x).astype(dtype).astype(jnp.float32)


@pytest.mark.parametrize("records", ["fp16", "bf16"])
def test_half_records_match_quantized_oracle(records):
    """The half-width sweep IS fp32 arithmetic on records-quantized v/m:
    it must tightly match the fp32 reference path evaluated on the
    pre-quantized inputs (same tolerances as the fp32-record tests)."""
    rdt = {"fp16": jnp.float16, "bf16": jnp.bfloat16}[records]
    dom, cfg, ps, nl, f = _cloud_setup(seed=5)
    vq = _quantize(f["v"], rdt)
    # m is stored normalized by the mean mass (fp16 subnormal guard);
    # quantize the oracle's m at the same point
    s = fused.mass_scale(f["m"])
    mq = _quantize(f["m"] / s, rdt) * s
    drho_r, acc_r, _ = _reference_rhs(
        dom, ps.rc, nl, vq, mq, f["rho"], h=dom.h, mu=1.0
    )
    drho_h, acc_h = fused.force_rhs(
        dom, ps.rc, nl, f["v"], f["m"], f["rho"],
        c0=C0, rho0=RHO0, mu=1.0, records=records,
    )
    np.testing.assert_allclose(drho_h, drho_r, rtol=2e-5, atol=1e-5)
    np.testing.assert_allclose(acc_h, acc_r, rtol=2e-5, atol=2e-3)


def test_half_records_within_derived_tolerance():
    """drho under fp16 records agrees with fp32 records within the bound
    DERIVED from the actual quantization deltas:

      |Δdrho_i| <= Σ_j [ |Δm_j| |dv·∇W| + m_j Σ_a (|Δv_i|+|Δv_j|)_a |∇W_a| ]

    plus an fp32 round-off allowance."""
    dom, cfg, ps, nl, f = _cloud_setup(seed=7)
    v, m, rho = f["v"], f["m"], f["rho"]
    drho32, acc32 = fused.force_rhs(
        dom, ps.rc, nl, v, m, rho, c0=C0, rho0=RHO0, mu=1.0, records="fp32"
    )
    drho16, acc16 = fused.force_rhs(
        dom, ps.rc, nl, v, m, rho, c0=C0, rho0=RHO0, mu=1.0, records="fp16"
    )
    # derived per-particle bound from the true quantization deltas
    disp, r = rcll.pair_displacements(dom, ps.rc, nl)
    gw = np.abs(np.asarray(sph.grad_w(disp, r, dom.h, dom.dim, nl.mask)))
    # invalid slots hold the dummy id N (window-search padding): clip
    # for the numpy gathers below — every use is masked by ``mask``.
    idx = np.minimum(np.asarray(nl.idx), v.shape[0] - 1)
    mask = np.asarray(nl.mask)
    dv = np.abs(np.asarray(v)[:, None, :] - np.asarray(v)[idx])
    dm = np.abs(np.asarray(m) - np.asarray(_quantize(m, jnp.float16)))
    dv_err = np.abs(np.asarray(v) - np.asarray(_quantize(v, jnp.float16)))
    pair_dv_err = dv_err[:, None, :] + dv_err[idx]
    mj = np.where(mask, np.asarray(m)[idx], 0.0)
    bound = (
        np.sum(dm[idx] * mask * np.sum(dv * gw, -1), -1)
        + np.sum(mj * np.sum(pair_dv_err * gw, -1), -1)
    )
    slack = 1e-5 * (1.0 + np.abs(np.asarray(drho32)))
    err = np.abs(np.asarray(drho16) - np.asarray(drho32))
    assert np.all(err <= bound + slack), float((err - bound).max())
    # acc stays within the same order: quantization-dominated, bounded
    scale = np.abs(np.asarray(acc32)).max()
    assert np.abs(np.asarray(acc16) - np.asarray(acc32)).max() < 2e-3 * (
        1.0 + scale
    )


def test_half_records_bit_exact_on_grid():
    """Where v and m are exactly representable in fp16 the half-width
    sweep is BIT-identical to the fp32-record sweep: both decode to the
    same fp32 values (q = I + rel/2 is exact either way, the EOS fold is
    the same expression) and run the same ``_pair_rhs`` arithmetic."""
    dom, cfg, ps, nl, f = _cloud_setup(seed=9)
    n = ps.rc.rel.shape[0]
    rng = np.random.default_rng(9)
    # v on the 2^-8 grid, |v| < 1; m a power of two: all fp16-exact
    v = jnp.asarray(
        rng.integers(-256, 257, (n, 2)).astype(np.float32) / 256.0
    )
    m = jnp.full((n,), 2.0**-10, jnp.float32)
    for chunk in (0, 100):
        drho32, acc32 = fused.force_rhs(
            dom, ps.rc, nl, v, m, f["rho"],
            c0=C0, rho0=RHO0, chunk=chunk, mu=1.0, records="fp32",
        )
        drho16, acc16 = fused.force_rhs(
            dom, ps.rc, nl, v, m, f["rho"],
            c0=C0, rho0=RHO0, chunk=chunk, mu=1.0, records="fp16",
        )
        np.testing.assert_array_equal(
            np.asarray(drho16), np.asarray(drho32)
        )
        np.testing.assert_array_equal(np.asarray(acc16), np.asarray(acc32))


def test_half_records_survive_tiny_masses():
    """Raw SPH masses below fp16's subnormal range (< 6e-8) would store
    as exactly 0 and silently zero all forces; the mean-mass
    normalization keeps full precision at any resolution scale."""
    from repro.kernels import ops

    dom, cfg, ps, nl, f = _cloud_setup(seed=13)
    n = ps.rc.rel.shape[0]
    m_tiny = jnp.full((n,), 2e-8, jnp.float32)  # flushes to 0 in fp16
    assert float(m_tiny.astype(jnp.float16)[0]) == 0.0
    drho32, acc32 = fused.force_rhs(
        dom, ps.rc, nl, f["v"], m_tiny, f["rho"],
        c0=C0, rho0=RHO0, mu=1.0, records="fp32",
    )
    drho16, acc16 = fused.force_rhs(
        dom, ps.rc, nl, f["v"], m_tiny, f["rho"],
        c0=C0, rho0=RHO0, mu=1.0, records="fp16",
    )
    assert float(jnp.max(jnp.abs(drho32))) > 0
    # near-zero sums cancel, so tolerance scales with the field magnitude
    atol_d = 2e-3 * float(jnp.max(jnp.abs(drho32)))
    atol_a = 2e-3 * float(jnp.max(jnp.abs(acc32)))
    np.testing.assert_allclose(drho16, drho32, rtol=2e-3, atol=atol_d)
    np.testing.assert_allclose(acc16, acc32, rtol=2e-3, atol=atol_a)
    drho_p, acc_p = ops.rcll_force_particles(
        dom, ps.packing.binning, ps.rc, f["v"], m_tiny, f["rho"],
        mu=1.0, c0=C0, rho0=RHO0, records_dtype=jnp.float16,
        interpret=not ON_TPU,
    )
    np.testing.assert_allclose(drho_p, drho32, rtol=2e-3, atol=atol_d)
    np.testing.assert_allclose(acc_p, acc32, rtol=2e-3, atol=atol_a)


@pytest.mark.parametrize("records", ["fp16", "bf16", "fp32"])
def test_half_records_pallas_matches_xla(records):
    """Both backends quantize the records identically and decode in
    fp32: they agree to reduction-order round-off. bf16 words decode as
    floats in the kernel (bfloat16's dtype kind is not "f")."""
    from repro.kernels import ops

    dom, cfg, ps, nl, f = _cloud_setup(seed=11)
    drho_x, acc_x = fused.force_rhs(
        dom, ps.rc, nl, f["v"], f["m"], f["rho"],
        c0=C0, rho0=RHO0, mu=1.0, records=records,
    )
    drho_p, acc_p = ops.rcll_force_particles(
        dom, ps.packing.binning, ps.rc, f["v"], f["m"], f["rho"],
        mu=1.0, c0=C0, rho0=RHO0,
        records_dtype={"fp16": jnp.float16, "bf16": jnp.bfloat16,
                       "fp32": jnp.float32}[records],
        interpret=not ON_TPU,
    )
    np.testing.assert_allclose(drho_p, drho_x, rtol=2e-5, atol=1e-5)
    np.testing.assert_allclose(acc_p, acc_x, rtol=2e-5, atol=2e-3)


def test_half_records_reject_huge_grids():
    """16-bit cell anchors cap the grid per axis (fp16: 2^11) — loudly."""
    from repro.core import nnps

    dom = D.Domain(lo=(0.0, 0.0), hi=(2000.0, 1.0), h=0.2)
    assert max(dom.ncells) >= 1 << 11
    n = 8
    rc = rcll.init_state(dom, jnp.zeros((n, 2)), jnp.float16)
    nl = nnps.NeighborList(
        idx=jnp.zeros((n, 4), jnp.int32),
        mask=jnp.zeros((n, 4), bool),
        count=jnp.zeros((n,), jnp.int32),
    )
    with pytest.raises(ValueError, match="16-bit"):
        fused.force_rhs(
            dom, rc, nl, jnp.zeros((n, 2)), jnp.ones((n,)), jnp.ones((n,)),
            c0=C0, rho0=RHO0, records="fp16",
        )
    # the solver degrades gracefully instead: fp32 layout past the cap
    cfg = solver.SPHConfig(domain=dom, ds=0.1, dt=1e-3, algo="rcll")
    assert solver._resolved_records(cfg) == "fp32"
    small = solver.SPHConfig(
        domain=D.Domain(lo=(0.0, 0.0), hi=(1.0, 1.0), h=0.2),
        ds=0.1, dt=1e-3, algo="rcll",
    )
    assert solver._resolved_records(small) == "fp16"


# --------------------------------------------------------------------------
# end-to-end trajectories across skin settings
# --------------------------------------------------------------------------
@pytest.mark.parametrize("skin_frac", [0.0, 0.5])
def test_backend_trajectories_agree(skin_frac):
    """Cross-backend EXACTNESS oracle: pinned to fp32 records (the
    reference gather path has no record quantization to compare to)."""
    backends = ["reference", "xla", "pallas"]
    if ON_TPU is False and skin_frac > 0:
        # interpret-mode pallas is slow; the skinned pallas case is
        # covered by the stale-binning unit test above
        backends = ["reference", "xla"]
    nsteps = 15
    outs = {}
    for be in backends:
        # the skinned case needs cells covering r + skin AND >= 3 cells
        # on the periodic axis -> finer spacing
        cfg, st = _poiseuille(
            be, ds=0.05 if skin_frac > 0 else 0.1, skin_frac=skin_frac,
            records="fp32",
        )
        out = solver.simulate(cfg, st, nsteps)
        outs[be] = (
            np.asarray(solver.positions(cfg, out)),
            np.asarray(out.fluid.v),
            np.asarray(out.fluid.rho),
        )
    ref = outs["reference"]
    for be in backends[1:]:
        np.testing.assert_allclose(outs[be][0], ref[0], atol=1e-6)
        np.testing.assert_allclose(outs[be][1], ref[1], atol=1e-7)
        np.testing.assert_allclose(outs[be][2], ref[2], atol=1e-6)


def test_half_record_trajectory_tracks_fp32():
    """End-to-end: the default (fp16-record) production path stays within
    a small fraction of the particle spacing of the fp32-record oracle
    over a short run — record quantization perturbs forces at the fp16
    ulp level, it does not change the flow."""
    cfg16, st16 = _poiseuille("xla", records="fp16")
    cfg32, st32 = _poiseuille("xla", records="fp32")
    out16 = solver.simulate(cfg16, st16, 40)
    out32 = solver.simulate(cfg32, st32, 40)
    p16 = np.asarray(solver.positions(cfg16, out16))
    p32 = np.asarray(solver.positions(cfg32, out32))
    assert np.abs(p16 - p32).max() < 1e-3 * cfg32.ds
    v16, v32 = np.asarray(out16.fluid.v), np.asarray(out32.fluid.v)
    assert np.abs(v16 - v32).max() < 1e-6 + 1e-2 * np.abs(v32).max()


# --------------------------------------------------------------------------
# overflow surfacing
# --------------------------------------------------------------------------
def test_overflow_reported_in_stats():
    cfg, st = _poiseuille("xla", max_neighbors=4)  # far too small
    _, stats = solver.simulate_stats(cfg, st, 3)
    assert bool(stats.overflow)


def test_check_overflow_raises():
    cfg, st = _poiseuille("xla", max_neighbors=4)
    cfg = dataclasses.replace(cfg, check_overflow=True)
    with pytest.raises(Exception, match="overflow"):
        jax.block_until_ready(solver.simulate_stats(cfg, st, 3))


def test_check_overflow_silent_when_sized_right():
    cfg, st = _poiseuille("xla")
    cfg = dataclasses.replace(cfg, check_overflow=True)
    out, stats = solver.simulate_stats(cfg, st, 3)
    jax.block_until_ready(out)
    assert not bool(stats.overflow)


# --------------------------------------------------------------------------
# donating scan entry point
# --------------------------------------------------------------------------
def test_run_persistent_matches_simulate():
    cfg, st = _poiseuille("xla")
    want = solver.simulate(cfg, st, 12)
    carry = solver.init_persistent(cfg, st)
    for _ in range(3):  # chained segments, carry donated each call
        carry = solver.run_persistent(cfg, carry, 4)
    got = solver.finalize_persistent(cfg, carry)
    np.testing.assert_allclose(
        np.asarray(solver.positions(cfg, got)),
        np.asarray(solver.positions(cfg, want)), atol=1e-7,
    )
    np.testing.assert_allclose(
        np.asarray(got.fluid.v), np.asarray(want.fluid.v), atol=1e-7
    )
    assert int(carry.steps) == 12


# --------------------------------------------------------------------------
# dynamic case: backend agreement across in-scan rebuilds
# --------------------------------------------------------------------------
def test_dynamic_dam_break_backends_agree_with_rebuilds():
    """Acceptance criterion for the rebuild round: reference vs xla vs
    pallas agree on a DYNAMIC case whose Verlet criterion fires >= 3
    in-scan rebuilds (the dropped-column dam break the --dynamic
    benchmark runs). Pinned to fp32 records (the exactness oracle)."""
    from repro.core import cases

    nsteps = 120
    backends = ["reference", "xla"]
    if ON_TPU:
        backends.append("pallas")
    outs, rebuilds = {}, {}
    for be in backends:
        ds = 0.08
        radius = 2.0 * cases.build_case("dam_break", ds=ds).h
        case = cases.build_case(
            "dam_break", ds=ds, backend=be, cell_factor=1.5,
            skin=0.25 * radius, v0=1.0, max_neighbors=64,
            policy=FP32_RECORDS,
        )
        cfg, st = case.build()
        out, stats = solver.simulate_stats(cfg, st, nsteps)
        outs[be] = (
            np.asarray(solver.positions(cfg, out)),
            np.asarray(out.fluid.v),
            np.asarray(out.fluid.rho),
        )
        rebuilds[be] = int(stats.rebuilds)
        assert not bool(stats.overflow), be
    # init build + >= 3 genuinely dynamic in-scan rebuilds
    assert rebuilds["reference"] >= 4, rebuilds
    assert rebuilds["xla"] == rebuilds["reference"], rebuilds
    ref = outs["reference"]
    for be in backends[1:]:
        np.testing.assert_allclose(outs[be][0], ref[0], atol=2e-5)
        np.testing.assert_allclose(outs[be][1], ref[1], atol=2e-5)
        np.testing.assert_allclose(outs[be][2], ref[2], atol=2e-5)


def test_dynamic_dam_break_pallas_short():
    """The pallas backend on the same dynamic path (shorter horizon:
    interpret mode pays per-call overhead on CPU), including at least
    one in-scan rebuild with migrated particles re-anchored against the
    stale binning."""
    from repro.core import cases

    nsteps = 40
    outs = {}
    for be in ["reference", "pallas"]:
        ds = 0.1
        radius = 2.0 * cases.build_case("dam_break", ds=ds).h
        case = cases.build_case(
            "dam_break", ds=ds, backend=be, cell_factor=1.5,
            skin=0.125 * radius, v0=1.0, max_neighbors=64,
            policy=FP32_RECORDS,
        )
        cfg, st = case.build()
        out, stats = solver.simulate_stats(cfg, st, nsteps)
        outs[be] = np.asarray(solver.positions(cfg, out))
        assert int(stats.rebuilds) >= 2, be
    np.testing.assert_allclose(outs["pallas"], outs["reference"],
                               atol=1e-4)


def test_pallas_fp32_coords_not_quantized():
    """APPROACH_I stores rel as fp32; the cell-pack record slabs must
    stream it losslessly (fp32 slab), not quantize it through the
    16-bit row — the pallas RHS then matches the reference gather path
    to fp32 round-off, not fp16 coordinate granularity."""
    from repro.kernels import ops

    rng = np.random.default_rng(11)
    n = 600
    ds = (1.0 / n) ** 0.5
    dom = D.Domain(lo=(0.0, 0.0), hi=(1.0, 1.0), h=1.2 * ds)
    x = rng.uniform(0, 1, (n, 2))
    rc = rcll.init_state(dom, dom.normalize(jnp.asarray(x)), jnp.float32)
    assert rc.rel.dtype == jnp.float32
    cap = cells.default_capacity(dom, n, safety=8.0)
    ps = rcll.pack_state(dom, rc, cap)
    k = 96
    nl = rcll.packed_neighbors(
        dom, ps, dtype=jnp.float32, compute_dtype=jnp.float32, k=k
    )
    v = jnp.asarray(rng.normal(size=(n, 2)) * 0.1, jnp.float32)
    m = jnp.full((n,), 1.0 / n, jnp.float32)
    rho = jnp.asarray(1.0 + 0.01 * rng.normal(size=(n,)), jnp.float32)
    drho_r, acc_r, _ = _reference_rhs(
        dom, ps.rc, nl, v, m, rho, h=dom.h, mu=1.0
    )
    drho_k, acc_k = ops.rcll_force_particles(
        dom, ps.packing.binning, ps.rc, v, m, rho,
        mu=1.0, c0=C0, rho0=RHO0, interpret=not ON_TPU,
    )
    # fp16-quantized coordinates miss by ~1e-4 RELATIVE (measured when
    # the bug existed); fp32 summation round-off sits below ~3e-5, so
    # this tolerance separates the two regimes cleanly
    np.testing.assert_allclose(drho_k, drho_r, rtol=1e-5, atol=2e-6)
    np.testing.assert_allclose(acc_k, acc_r, rtol=1e-5, atol=1e-4)
