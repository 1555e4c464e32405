"""Mixed-precision SPH solver (paper Fig. 6 flowchart).

One jit-able ``step`` covering the paper's three approaches (Table 4):
  I   : cell-list NNPS in hi precision, absolute fp32 positions.
  II  : cell-list NNPS in fp16 *absolute* coordinates, fp32 positions.
  III : RCLL - positions live permanently as (int cell, fp16 relative);
        NNPS in fp16 relative coordinates (Eq. 7); positions advanced in
        relative form (Eq. 8). No absolute round-trip after init.

The physics tier (density/momentum/EOS/integration) is always the
policy's ``physics`` dtype (fp32 here; fp64 on CPU for the accuracy
benchmarks via scoped x64).

Persistent cell-packed pipeline (the production RCLL path)
----------------------------------------------------------
The RCLL path no longer re-bins and re-searches every step. Instead the
scan carry holds a *cell-packed* state (all per-particle arrays physically
reordered by flat cell id - the paper's Thrust xy-sort locality
optimization made persistent) plus a Verlet-skin neighbor list:

  * at (re)build time, particles are stably sorted by flat cell id
    (``rcll.pack_state``) and neighbors are searched with the radius
    inflated to ``r + skin``;
  * between rebuilds only pair geometry (Eq. 7 decode) and the physics
    sums run; the neighbor list is reused verbatim. Extra skin pairs are
    exactly harmless because the B-spline kernel and its derivative vanish
    beyond the true support ``2h``;
  * per-particle displacement since the last rebuild is accumulated in
    fp32 and the list is rebuilt (via ``lax.cond`` inside the scanned
    step) only when ``max_i |disp_i| > skin/2`` - the classic Verlet-list
    criterion. ``skin=0`` degenerates to per-step rebuild (the seed
    behavior); ``rebuild_every=n`` forces a static cadence for
    benchmarking.

Fused force pass (this PR's tentpole)
-------------------------------------
``backend`` now selects the whole NNPS + force pipeline, not just the
neighbor producer:

  * ``"reference"`` - the gather path: per-particle neighbor list,
    ``rcll.pair_displacements`` (N, K, d), ``sph.gather_pair_fields``.
    Every pair intermediate round-trips through HBM; kept as the oracle.
  * ``"xla"`` - jnp neighbor search + the fused cell-blocked force pass
    (``core/fused.py``): pair geometry decoded and consumed in chunks of
    packed (cell-sorted) rows, peak pair memory O(chunk*K*d).
  * ``"pallas"`` - Pallas neighbor tables + Pallas fused force kernels
    (``kernels/rcll_force.py``): per row of cells, all 3^d neighbour
    offsets, Eq. 7 decode + B-spline gradient + continuity/momentum
    accumulation in VMEM; no neighbor list is consumed at all (compact
    support masks out-of-range candidates exactly).

The default is pallas on TPU and xla elsewhere, so CPU tests always
exercise the fused path with the reference path as the test oracle.
"""
from __future__ import annotations

import dataclasses
import logging
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import cells as cells_lib
from repro.core import fused, health, nnps, rcll, sph, statepack
from repro.core import scheme as scheme_lib
from repro.core.domain import Domain
from repro.core.precision import PrecisionPolicy

_log = logging.getLogger(__name__)

Array = jnp.ndarray


@dataclasses.dataclass(frozen=True)
class SPHConfig:
    domain: Domain
    ds: float  # particle spacing
    dt: float
    rho0: float = 1.0
    c0: float = 1.25  # speed of sound (>= 10 * v_max for WCSPH)
    mu: float = 1.0  # dynamic viscosity (rho0 * nu)
    body_force: tuple[float, ...] = (0.0, 0.0)
    max_neighbors: int = 40
    capacity: int | None = None
    algo: str = "rcll"  # "all" | "cell" | "rcll"
    policy: PrecisionPolicy = PrecisionPolicy()
    # Physics-term specification (core/scheme.py). None builds the
    # legacy WCSPH scheme from rho0/c0/mu/body_force above, so every
    # pre-scheme call site keeps its exact behavior; cases that want a
    # different EOS / viscosity model pass a Scheme directly (the
    # legacy scalar fields are then ignored by the solver).
    scheme: scheme_lib.Scheme | None = None
    # Clamp wall-particle density at >= rho0 after the continuity
    # update (the DualSPHysics dummy-particle treatment): free-surface
    # cases (dam break) otherwise develop tensile wall underpressure
    # that sticks fluid to the walls.
    wall_rho_clamp: bool = False
    # --- persistent-pipeline knobs (RCLL path only) ---
    skin: float = 0.0  # physical Verlet-skin width added to the search radius
    rebuild_every: int | None = None  # static rebuild cadence (overrides skin)
    backend: str | None = None  # None=auto | "reference" | "xla" | "pallas"
    # Rows per chunk of the fused XLA force pass (0 = auto). Static.
    force_chunk: int = 0
    # Merged candidate budget per particle of the table-free window
    # search (the production rebuild path). 0 = auto: the 3^dim-block
    # lattice bound from ``ds`` (``nnps.auto_window``);
    # ``3^dim * capacity`` reproduces the dense-table coverage
    # guarantee exactly. Tighter windows cut search bandwidth;
    # truncation is flagged through the overflow plumbing. ``None``
    # selects the dense-table candidate search (``nnps.rcll_neighbors``
    # over the (C, cap) table) as the oracle path. Static.
    window: int | None = 0
    # DEPRECATED alias for the strict guard policy: raise
    # (health.SimulationDiverged) from simulate / simulate_stats when
    # any cell-table or neighbor-list capacity overflowed during the
    # run. The check is ONE host read of the overflow flag after the
    # scan returns — the in-scan jax.debug.callback sync point it used
    # to cost is gone. New code should run under the health guard
    # (core/recovery.py), which detects AND recovers. See README for
    # the ``max_neighbors`` sizing rule.
    check_overflow: bool = False
    # Deterministic fault-injection hook (health.FaultSpec) driven by
    # the recovery tests and the CI guard smoke: None in production.
    # Fires inside step_persistent when the step counter matches.
    fault: health.FaultSpec | None = None

    @property
    def h(self) -> float:
        return self.domain.h

    def cap(self, n: int) -> int:
        """Per-cell table capacity: explicit override or the robust
        estimate (``cells.robust_capacity`` — covers BOTH the
        domain-mean occupancy and the close-packed lattice bound, so a
        mostly-empty free-surface domain cannot silently under-size its
        cells; see the dam-break post-mortem in cells.py)."""
        return self.capacity or cells_lib.robust_capacity(
            self.domain, self.ds, n
        )

    def resolved_window(self) -> int:
        """The window search's merged candidate budget (window == 0 ->
        the ds-derived 3^dim-block lattice bound)."""
        if self.window is None:
            raise ValueError("window=None selects the table oracle path")
        if self.window > 0:
            return self.window
        return nnps.auto_window(self.domain, ds=self.ds)

    @property
    def skin_norm(self) -> float:
        """Skin width in normalized (Eq. 5) units."""
        return 2.0 * self.skin / self.domain.h_d

    @property
    def search_radius_cell(self) -> float:
        """Inflated search radius in reference-cell units (r + skin)."""
        return float(
            (self.domain.radius_norm + self.skin_norm) / self.domain.hc_ref
        )

    @property
    def resolved_scheme(self) -> scheme_lib.Scheme:
        """The physics-term spec the force backends consume (static)."""
        if self.scheme is not None:
            return self.scheme
        return scheme_lib.wcsph(
            self.c0, self.rho0, self.mu, self.body_force
        )

    @property
    def resolved_backend(self) -> str:
        if self.backend is not None:
            if self.backend not in ("reference", "xla", "pallas"):
                raise ValueError(
                    f"unknown backend {self.backend!r}; one of "
                    "'reference', 'xla', 'pallas'"
                )
            return self.backend
        return "pallas" if jax.default_backend() == "tpu" else "xla"

    def validate_skin(self) -> None:
        """The 3^dim cell neighborhood only guarantees coverage up to one
        cell edge: pairs separated by >= min(cell_sizes) can be missed.
        The inflated radius must stay inside that guarantee - build the
        Domain with ``cell_factor >= (r + skin) / r`` to use a skin."""
        if self.skin < 0:
            raise ValueError(f"skin must be >= 0, got {self.skin}")
        limit = min(self.domain.cell_sizes)
        if self.domain.radius + self.skin > limit * (1 + 1e-9):
            raise ValueError(
                f"skin {self.skin} too large: r + skin = "
                f"{self.domain.radius + self.skin:.6g} exceeds the cell "
                f"coverage guarantee {limit:.6g}; increase cell_factor to "
                f">= {(self.domain.radius + self.skin) / self.domain.radius:.3f}"
            )


class SPHState(NamedTuple):
    """Particle system state. ``xn`` is the normalized-absolute position
    (source of truth for algos all/cell); ``rc`` is the RCLL state (source
    of truth for algo rcll). The inactive representation is frozen at its
    initial value and never read.

    Boundary fields (core/boundaries.py): ``fixed`` marks wall/dummy
    particles — they ride every pair sum (density, pressure, viscosity)
    through the same arrays/record rows as fluid particles but are never
    advected, and their velocity is PRESCRIBED: ``v_wall`` where given
    (moving lids), 0 otherwise. ``kind`` is the int8 classification the
    mask derives from (boundaries.FLUID/WALL), carried for observables
    and future kinds; None on legacy states (then fixed is authoritative).
    """

    xn: Array  # (N, d) fp32 normalized absolute positions
    rc: rcll.RCLLState
    fluid: sph.FluidState
    fixed: Array  # (N,) bool - wall/dummy particles (never advected)
    t: Array  # () fp32 simulation time
    kind: Array | None = None  # (N,) int8 boundaries.FLUID / WALL
    v_wall: Array | None = None  # (N, d) fp32 prescribed wall velocity


class PersistentCarry(NamedTuple):
    """Scan carry of the packed persistent pipeline.

    All per-particle arrays inside ``st`` are in PACKED (cell-sorted)
    order; ``order`` maps packed position -> original particle id so the
    API boundary (``finalize``) can restore user indexing. ``nl`` is in
    packed indexing and was built with the skin-inflated radius.
    """

    st: SPHState
    order: Array  # (N,) int32 packed -> original
    nl: nnps.NeighborList  # packed indexing, radius r + skin
    disp_acc: Array  # (N, d) fp32 normalized displacement since rebuild
    rebuilds: Array  # () int32 number of bin+search rebuilds so far
    steps: Array  # () int32 steps taken since init
    overflow: Array  # () bool any cell-table/neighbor-list overflow seen
    # The packed-state binning of the last rebuild (all rcll backends).
    # Between rebuilds it is stale but exact to decode against: the
    # pallas force kernels re-anchor migrated particles against its
    # (C, cap) slot structure, and the next rebuild's counting-sort
    # pack reuses its near-sorted run structure for the O(N) stable
    # rank (cells.pack_particles prev=...).
    binning: cells_lib.CellBinning | None = None
    # XLA fused backend only (None otherwise): neighbor ids with invalid
    # slots redirected to the dummy row N. The production window search
    # emits this layout directly (sort compaction pads with N); the
    # table-oracle path sanitizes once per rebuild. Static between
    # rebuilds either way.
    idx_dummy: Array | None = None
    # Half-record mass normalizer (fused.mass_scale), computed ONCE at
    # init: masses never change during a run, so the per-step O(N)
    # reduction (a sync point in the chunked sweep) is hoisted out of
    # the scan entirely. None on paths that don't consume it.
    m_scale: Array | None = None
    # Pallas backend only: the static mass table in the force kernel's
    # row layout (ops.mass_table). Masses never change, so it is rebuilt only when
    # the packed ORDER changes (i.e. at rebuild) — the per-step tile
    # refresh then touches exactly the coordinate/velocity/density
    # halves of the record stream.
    m_table: Array | None = None
    # () uint32 accumulated health bits (health.CELL_OVERFLOW /
    # WINDOW_TRUNC) ORed in at every rebuild — unlike the live binning
    # and list sentinels, this sees overflow in ANY intermediate
    # rebuild. The guarded-block driver clears it at block entry to get
    # per-block semantics; ``overflow`` above stays the run-sticky bool
    # every existing consumer reads.
    flags: Array | None = None


class SimStats(NamedTuple):
    """Diagnostics of a persistent-pipeline run (see simulate_stats)."""

    rebuilds: Array  # () int32
    steps: Array  # () int32
    overflow: Array  # () bool


def init_state(
    cfg: SPHConfig, x_phys, v, m, rho, fixed=None, kind=None, v_wall=None
) -> SPHState:
    xn = cfg.domain.normalize(jnp.asarray(x_phys), dtype=jnp.float32)
    rc = rcll.init_state(cfg.domain, xn, dtype=cfg.policy.coords_dtype)
    n = xn.shape[0]
    fluid = sph.FluidState(
        v=jnp.asarray(v, jnp.float32),
        rho=jnp.asarray(rho, jnp.float32),
        m=jnp.asarray(m, jnp.float32),
    )
    if kind is not None:
        kind = jnp.asarray(kind, jnp.int8)
        if fixed is None:
            fixed = kind != 0  # boundaries.FLUID
    if fixed is None:
        fixed = jnp.zeros((n,), bool)
    fixed = jnp.asarray(fixed, bool)
    if kind is None:
        kind = fixed.astype(jnp.int8)  # boundaries.WALL == 1
    if v_wall is not None:
        v_wall = jnp.asarray(v_wall, jnp.float32)
    return SPHState(xn=xn, rc=rc, fluid=fluid, fixed=fixed,
                    t=jnp.zeros((), jnp.float32), kind=kind, v_wall=v_wall)


def positions(cfg: SPHConfig, state: SPHState, dtype=jnp.float32) -> Array:
    """Physical positions decoded from the active representation."""
    if cfg.algo == "rcll":
        xn = rcll.to_normalized(cfg.domain, state.rc, dtype=dtype)
    else:
        xn = state.xn
    return cfg.domain.denormalize(xn, dtype=dtype)


# --------------------------------------------------------------------------
# Persistent cell-packed RCLL pipeline
# --------------------------------------------------------------------------
def _permute_state(st: SPHState, perm: Array, rc: rcll.RCLLState) -> SPHState:
    """Reorder every per-particle array by ``perm`` (rc supplied pre-sorted).

    One gather per field — the readable oracle form, used at the API
    boundary (``finalize_persistent``) and as the test reference for the
    fused row permutation the hot rebuild runs (``_permute_state_fused``).
    """
    return SPHState(
        xn=st.xn[perm],
        rc=rc,
        fluid=sph.FluidState(
            v=st.fluid.v[perm], rho=st.fluid.rho[perm], m=st.fluid.m[perm]
        ),
        fixed=st.fixed[perm],
        t=st.t,
        kind=None if st.kind is None else st.kind[perm],
        v_wall=None if st.v_wall is None else st.v_wall[perm],
    )


def _permute_state_fused(
    st: SPHState, perm: Array, rc: rcll.RCLLState, order: Array
) -> tuple[SPHState, Array]:
    """Reorder the whole per-particle state (and ``order``) by ONE gather.

    All fields are bit-packed into one contiguous u32 row buffer and
    permuted together (``statepack.permute_fields``) — bit-identical to
    :func:`_permute_state` plus ``order[perm]``, at a single row gather
    instead of ~8 strided per-field gathers. ``rc`` arrives pre-sorted
    from the counting-sort pack (its gathers live inside
    ``rcll.pack_state``).
    """
    xn, v, rho, m, fixed, kind, v_wall, order = statepack.permute_fields(
        (st.xn, st.fluid.v, st.fluid.rho, st.fluid.m, st.fixed,
         st.kind, st.v_wall, order),
        perm,
    )
    st2 = SPHState(
        xn=xn, rc=rc, fluid=sph.FluidState(v=v, rho=rho, m=m),
        fixed=fixed, t=st.t, kind=kind, v_wall=v_wall,
    )
    return st2, order


def _packed_neighbor_list(
    cfg: SPHConfig, ps: rcll.PackedState
) -> nnps.NeighborList:
    """Produce the (packed-indexing) neighbor list at rebuild time.

    Production (``cfg.window`` int): the table-free merged-window search
    (``nnps.rcll_neighbors_windows``) — no (C, cap, K) candidate table,
    no candidate-id gather, dummy-padded ids. Oracle (``window=None``):
    the dense-table candidate search over the (C, cap) cell table.
    One arithmetic dtype either way: the path choice must never change
    neighbor sets (asserted by the window-vs-table suite).
    """
    pol = cfg.policy
    if cfg.window is None:  # dense-table oracle
        return nnps.rcll_neighbors(
            cfg.domain,
            ps.rc.rel,
            ps.rc.cell_xy,
            dtype=pol.nnps_dtype,
            compute_dtype=pol.nnps_compute_dtype,
            k=cfg.max_neighbors,
            binning=ps.packing.binning,
            radius_cell=cfg.search_radius_cell,
        )
    return rcll.packed_neighbors(
        cfg.domain,
        ps,
        dtype=pol.nnps_dtype,
        compute_dtype=pol.nnps_compute_dtype,
        k=cfg.max_neighbors,
        radius_cell=cfg.search_radius_cell,
        window=cfg.resolved_window(),
    )


def _empty_neighbor_list(n: int) -> nnps.NeighborList:
    """Zero-capacity list for backends that never consume one."""
    return nnps.NeighborList(
        idx=jnp.zeros((n, 0), jnp.int32),
        mask=jnp.zeros((n, 0), bool),
        count=jnp.zeros((n,), jnp.int32),
    )


def _rebuild(cfg: SPHConfig, carry: PersistentCarry) -> PersistentCarry:
    """Re-sort by cell, re-bin, and re-search with the inflated radius.

    The minimal-bandwidth rebuild pipeline: counting-sort pack -> ONE
    fused state permutation -> merged-window search.

      * The re-sort is the counting-sort pack: the carried binning
        describes the run structure the arrays are currently in (the
        previous rebuild's), which turns the stable re-sort into O(N)
        bincount + exclusive-scan + rank passes
        (``cells.pack_particles``) — no argsort on the hot path (a
        ``lax.cond`` falls back to it if any particle out-ran the 3^dim
        neighborhood since the last rebuild).
      * The whole per-particle state rides one bit-packed u32 row
        buffer through a SINGLE gather (``_permute_state_fused``)
        instead of one strided gather per field.
      * The search is the table-free merged-window search: candidate
        ids are counting-sort range arithmetic (never gathered), the
        distance filter gathers one bit-packed row per candidate, and
        the sort compaction emits dummy-padded ids — so the fused force
        pass needs no per-slot sanitize (``idx_dummy`` is the list
        itself). The dense-table oracle (``window=None``) still
        sanitizes its select_k output.

    The pallas force path walks the 3^dim cell neighborhood directly and
    never reads a neighbor list, so its rebuild skips the search
    entirely and carries a zero-capacity list; its overflow flag then
    means exactly "cell table dropped particles" (K truncation cannot
    happen - the fused kernel sees every in-support pair).
    """
    n = carry.order.shape[0]
    with jax.named_scope("sph.rebuild"):
        with jax.named_scope("sph.rebuild.pack"):
            ps = rcll.pack_state(
                cfg.domain, carry.st.rc, cfg.cap(n), prev=carry.binning
            )
        perm = ps.packing.order  # current-packed -> new-packed
        with jax.named_scope("sph.rebuild.permute"):
            st, order = _permute_state_fused(
                carry.st, perm, ps.rc, carry.order
            )
        cell_over = ps.packing.binning.overflow > 0
        overflow = carry.overflow | cell_over
        flags = health.fold_flag(carry.flags, cell_over,
                                 health.CELL_OVERFLOW)
        binning = ps.packing.binning
        m_table = carry.m_table
        if cfg.resolved_backend == "pallas":
            from repro.kernels import ops  # deferred: core stays kernel-free

            nl = _empty_neighbor_list(n)
            idx_dummy = None
            with jax.named_scope("sph.rebuild.mass_table"):
                m_table = ops.mass_table(
                    cfg.domain, binning, st.fluid.m, cfg.policy.records_dtype,
                    carry.m_scale,
                )
        else:
            with jax.named_scope("sph.rebuild.search"):
                nl = _packed_neighbor_list(cfg, ps)
            overflow = overflow | nl.overflowed
            win_bad = nl.overflowed
            if nl.trunc is not None:
                win_bad = win_bad | nl.trunc
            flags = health.fold_flag(flags, win_bad, health.WINDOW_TRUNC)
            # The window search already pads invalid slots with the dummy
            # id N — the fused sweep reads nl.idx directly (idx_dummy
            # stays None: carrying nl.idx twice would alias two donated
            # buffers). Only the table-oracle list (garbage invalid
            # slots) sanitizes.
            idx_dummy = (
                fused._sanitized_idx(nl, n)
                if cfg.resolved_backend == "xla" and cfg.window is None
                else None
            )
        return PersistentCarry(
            st=st,
            order=order,
            nl=nl,
            disp_acc=jnp.zeros_like(carry.disp_acc),
            rebuilds=carry.rebuilds + 1,
            steps=carry.steps,
            overflow=overflow,
            binning=binning,
            idx_dummy=idx_dummy,
            m_scale=carry.m_scale,
            m_table=m_table,
            flags=flags,
        )


def init_persistent(cfg: SPHConfig, state: SPHState) -> PersistentCarry:
    """Pack the state and build the first skin-inflated neighbor list."""
    cfg.validate_skin()
    n = state.xn.shape[0]
    # Masses are constant over a run: the half-record normalizer is
    # computed once here and carried, never re-reduced inside the scan.
    m_scale = (
        fused.mass_scale(state.fluid.m)
        if cfg.policy.half_records and cfg.resolved_backend != "reference"
        else None
    )
    carry = PersistentCarry(
        st=state,
        order=jnp.arange(n, dtype=jnp.int32),
        nl=nnps.NeighborList(
            idx=jnp.zeros((n, cfg.max_neighbors), jnp.int32),
            mask=jnp.zeros((n, cfg.max_neighbors), bool),
            count=jnp.zeros((n,), jnp.int32),
        ),
        disp_acc=jnp.zeros((n, cfg.domain.dim), jnp.float32),
        rebuilds=jnp.zeros((), jnp.int32),
        steps=jnp.zeros((), jnp.int32),
        overflow=jnp.zeros((), bool),
        m_scale=m_scale,
        flags=jnp.zeros((), jnp.uint32),
    )
    carry = _rebuild(cfg, carry)
    # _rebuild hands the SAME array to st.rc.cell_xy and binning.cell_xy
    # (they only diverge once a step migrates particles). run_persistent
    # donates the carry, and XLA refuses to donate one buffer through two
    # arguments — materialize a distinct copy at this eager boundary.
    rc = carry.st.rc
    return carry._replace(
        st=carry.st._replace(rc=rc._replace(cell_xy=jnp.copy(rc.cell_xy)))
    )


def finalize_persistent(cfg: SPHConfig, carry: PersistentCarry) -> SPHState:
    """Restore original particle indexing at the API boundary."""
    inverse = cells_lib.inverse_permutation(carry.order)
    rc = rcll.RCLLState(
        cell_xy=carry.st.rc.cell_xy[inverse], rel=carry.st.rc.rel[inverse]
    )
    return _permute_state(carry.st, inverse, rc)


def _needs_rebuild(cfg: SPHConfig, carry: PersistentCarry) -> Array:
    """The Verlet-list criterion (or the static-cadence fallback)."""
    if cfg.rebuild_every is not None:
        return (carry.steps > 0) & (carry.steps % cfg.rebuild_every == 0)
    if cfg.skin == 0.0:
        # Degenerate skin: any movement invalidates the list.
        return jnp.max(jnp.abs(carry.disp_acc)) > 0.0
    max_disp = jnp.sqrt(
        jnp.max(jnp.sum(carry.disp_acc * carry.disp_acc, axis=-1))
    )
    return max_disp > 0.5 * cfg.skin_norm


def _gathered_pair_rhs(
    sch: scheme_lib.Scheme,
    dom: Domain,
    fl: sph.FluidState,
    nl: nnps.NeighborList,
    disp: Array,  # (N, K, d) x_i - x_j
    r: Array,  # (N, K)
    gw: Array,  # (N, K, d) masked kernel gradient
):
    """(drho, acc) pair sums of ``sch`` on gathered (N, K) pair arrays.

    The gather-path evaluation of the scheme's two momentum channels —
    the same ∇W/dv split as ``fused._pair_rhs`` and the Pallas force
    kernel, on the materialized pair arrays. Shared by the reference
    RCLL backend and the absolute-coordinate step, so every path in the
    solver consumes ONE scheme definition. Densities enter as
    reciprocals exactly like the fused layouts (N divisions, none per
    pair).
    """
    # Gather pair fields ONCE; continuity + momentum share them.
    pf = sph.gather_pair_fields(fl.v, fl.m, nl.idx, nl.mask)
    drho = sph.continuity_rhs_pairs(pf, gw)
    inv = (1.0 / fl.rho).astype(jnp.float32)
    por2 = sch.por2_inv(inv)
    inv_i, inv_j = inv[:, None], inv[nl.idx]
    r2 = r * r
    dv_dot_disp = jnp.sum(pf.dv * disp, axis=-1)
    gc = sch.gradw_pair_coef(
        pf.mj, por2[:, None], por2[nl.idx], inv_i, inv_j,
        dv_dot_disp, r2, h=dom.h,
    )
    acc = -jnp.sum(gc[..., None] * gw, axis=-2)
    if sch.has_dv_term or sch.has_delta_term:
        x_dot_gw = jnp.sum(disp * gw, axis=-1)
    if sch.has_dv_term:
        vc = sch.dv_pair_coef(pf.mj, x_dot_gw, inv_i, inv_j, r2, h=dom.h)
        acc = acc + jnp.sum(vc[..., None] * pf.dv, axis=-2)
    if sch.has_delta_term:
        drho = drho + jnp.sum(
            sch.drho_pair_term(
                pf.mj, inv_i, inv_j, x_dot_gw, r2, h=dom.h
            ),
            axis=-1,
        )
    return drho, acc


def _force_rhs_reference(cfg: SPHConfig, carry: PersistentCarry):
    """Gather path: per-pair arrays materialized in HBM (the oracle).

    Returns (drho, acc), both evaluated at the CURRENT state (standard
    explicit WCSPH: every RHS term from the common state, DualSPHysics-
    style symplectic Euler) - the property that lets the fused backends
    compute the entire right-hand side in one cell-blocked pass.
    """
    dom, pol = cfg.domain, cfg.policy
    st, nl = carry.st, carry.nl
    disp, r = rcll.pair_displacements(dom, st.rc, nl, dtype=pol.physics_dtype)
    gw = sph.grad_w(disp, r, cfg.h, dom.dim, nl.mask)
    return _gathered_pair_rhs(
        cfg.resolved_scheme, dom, st.fluid, nl, disp, r, gw
    )


def _resolved_records(cfg: SPHConfig) -> str:
    """The record layout the fused XLA pass actually runs.

    Half-width rows anchor coordinates in 16-bit cell columns, which
    caps the grid per axis (``fused.HALF_CELL_LIMIT``); past the cap the
    solver falls back to the fp32 layout rather than erroring — the
    policy's dtype is a bandwidth knob, not a correctness contract.
    """
    records = cfg.policy.records
    if records != "fp32":
        limit = fused.HALF_CELL_LIMIT.get(jnp.dtype(cfg.policy.records_dtype))
        if limit is not None and max(cfg.domain.ncells) >= limit:
            # Build-time fallback, loud once per compile (this helper
            # runs at trace time, not per step).
            _log.warning(
                "half-record layout %r disabled: grid %s exceeds the "
                "%d-cell anchor range; using fp32 records",
                records, tuple(cfg.domain.ncells), limit,
            )
            return "fp32"
    return records


def _force_rhs_fused_xla(cfg: SPHConfig, carry: PersistentCarry):
    """Fused cell-blocked force pass over packed row chunks (core/fused)."""
    st, nl, fl = carry.st, carry.nl, carry.st.fluid
    idx_dummy = carry.idx_dummy
    if idx_dummy is None and cfg.window is not None:
        # Window-search lists are dummy-padded by construction: the
        # list IS the sanitized id array, no extra buffer carried.
        idx_dummy = nl.idx
    return fused.force_rhs(
        cfg.domain, st.rc, nl, fl.v, fl.m, fl.rho,
        scheme=cfg.resolved_scheme, chunk=cfg.force_chunk,
        records=_resolved_records(cfg), idx_dummy=idx_dummy,
        m_scale=carry.m_scale,
    )


def _force_rhs_fused_pallas(cfg: SPHConfig, carry: PersistentCarry):
    """Fused Pallas tile kernels over the (stale-binning) cell tables."""
    from repro.kernels import ops  # deferred: core stays kernel-free

    dom = cfg.domain
    st, fl = carry.st, carry.st.fluid
    return ops.rcll_force_particles(
        dom, carry.binning, st.rc, fl.v, fl.m, fl.rho,
        scheme=cfg.resolved_scheme,
        records_dtype=cfg.policy.records_dtype,
        m_scale=carry.m_scale,
        m_table=carry.m_table,
    )


_FORCE_BACKENDS = {
    "reference": _force_rhs_reference,
    "xla": _force_rhs_fused_xla,
    "pallas": _force_rhs_fused_pallas,
}


def _physics_step(
    cfg: SPHConfig, carry: PersistentCarry, dt: Array | float | None = None
) -> PersistentCarry:
    """One WCSPH step on the packed state, reusing ``carry.nl``.

    Pair geometry is decoded fresh from the *current* RCLL state (exact
    cell deltas + relative payloads), so only the neighbor LIST is stale -
    and the skin guarantees it remains a superset of the true neighbors.
    The continuity + momentum pair sums run through the backend-selected
    force path (see module docstring); EOS/integration/boundary terms are
    per-particle and shared.

    ``dt`` optionally overrides ``cfg.dt`` with a TRACED value — the
    batched ensemble engine (core/ensemble.py) threads a per-member
    timestep through one shared compiled program so a single member can
    back off its dt without recompiling (or perturbing) the batch. The
    force pass itself never consumes dt, so this touches only the
    per-particle update below.
    """
    dom, pol = cfg.domain, cfg.policy
    sch = cfg.resolved_scheme
    if dt is None:
        dt = cfg.dt
    st, fl = carry.st, carry.st.fluid
    with jax.named_scope("sph.force"):
        drho, acc = _FORCE_BACKENDS[cfg.resolved_backend](cfg, carry)
    with jax.named_scope("sph.integrate"):
        rho = fl.rho + dt * drho
        if cfg.wall_rho_clamp:
            rho = jnp.where(st.fixed, jnp.maximum(rho, sch.rho0), rho)

        bf = sch.body_force_vec(dom.dim)
        v = fl.v + dt * (acc + bf)
        # Walls: prescribed velocity (0 or v_wall), never advected. The
        # prescribed values flow into the next step's pair sums through the
        # same v array (and thus the fused record rows) as fluid velocities.
        vw = 0.0 if st.v_wall is None else st.v_wall
        v = jnp.where(st.fixed[:, None], vw, v)

        dxn = jnp.where(
            st.fixed[:, None], 0.0, v * dt * (2.0 / dom.h_d)
        ).astype(jnp.float32)
        rc = rcll.advance(dom, st.rc, dxn, dtype=pol.coords_dtype)
        st2 = SPHState(
            xn=st.xn,
            rc=rc,
            fluid=sph.FluidState(v=v, rho=rho, m=fl.m),
            fixed=st.fixed,
            t=st.t + dt,
            kind=st.kind,
            v_wall=st.v_wall,
        )
        return PersistentCarry(
            st=st2,
            order=carry.order,
            nl=carry.nl,
            disp_acc=carry.disp_acc + dxn,
            rebuilds=carry.rebuilds,
            steps=carry.steps + 1,
            overflow=carry.overflow,
            binning=carry.binning,
            idx_dummy=carry.idx_dummy,
            m_scale=carry.m_scale,
            m_table=carry.m_table,
            flags=carry.flags,
        )


def exact_neighbor_list(
    cfg: SPHConfig, carry: PersistentCarry
) -> nnps.NeighborList:
    """Exact-radius neighbor sets (packed indexing) from the reused list.

    Refilters the skin-inflated ``carry.nl`` with the true support radius
    using the same Eq. (7) arithmetic as a fresh search - the result's
    neighbor SETS are identical to rebuilding at the current positions
    whenever the skin invariant (max displacement < skin/2) holds.

    Requires a list-producing backend: the pallas force path carries no
    neighbor list (its rebuild skips the search entirely).
    """
    if cfg.resolved_backend == "pallas":
        raise ValueError(
            "exact_neighbor_list needs backend='reference' or 'xla'; the "
            "pallas force path does not carry a neighbor list"
        )
    pol = cfg.policy
    d2 = rcll.pair_r2_cell(
        cfg.domain, carry.st.rc, carry.nl,
        dtype=pol.nnps_dtype, compute_dtype=pol.nnps_compute_dtype,
    )
    r_exact = nnps.rcll_radius_cell_units(cfg.domain)
    r2 = jnp.asarray(r_exact, d2.dtype) ** 2
    return nnps.refilter(carry.nl, d2, r2)


def step_persistent(cfg: SPHConfig, carry: PersistentCarry) -> PersistentCarry:
    """Rebuild-if-needed (lax.cond) + one physics step."""
    if cfg.fault is not None:
        # Injection precedes the rebuild decision so a teleported
        # particle's spiked displacement can trigger the Verlet rebuild
        # in the SAME step (the overlap must reach the neighbor list).
        carry = health.inject_fault(cfg.fault, carry)
    with jax.named_scope("sph.skin_check"):
        rebuild = _needs_rebuild(cfg, carry)
    carry = jax.lax.cond(
        rebuild,
        lambda c: _rebuild(cfg, c),
        lambda c: c,
        carry,
    )
    return _physics_step(cfg, carry)


def _scan_steps(
    cfg: SPHConfig, carry: PersistentCarry, nsteps: int
) -> PersistentCarry:
    """``nsteps`` persistent steps under one lax.scan (shared hot loop)."""

    def body(c, _):
        return step_persistent(cfg, c), None

    carry, _ = jax.lax.scan(body, carry, None, length=nsteps)
    return carry


@partial(jax.jit, static_argnums=(0, 2), donate_argnums=(1,))
def run_persistent(
    cfg: SPHConfig, carry: PersistentCarry, nsteps: int
) -> PersistentCarry:
    """Production scan entry point: advances a carry IN PLACE.

    The carry argument is donated, so the packed state buffers are
    updated without a second copy resident in HBM (honored on CPU and
    TPU) — call as ``carry = run_persistent(cfg, carry, n)`` and never
    touch the old carry again: its buffers are invalidated, INCLUDING
    arrays it aliases from the ``SPHState`` that ``init_persistent``
    consumed. Chain segments to checkpoint or stream diagnostics:

        carry = init_persistent(cfg, state)
        for _ in range(segments):
            carry = run_persistent(cfg, carry, steps_per_segment)
        state = finalize_persistent(cfg, carry)

    ``simulate``/``simulate_stats`` stay non-donating (callers reuse
    their ``state`` argument freely).
    """
    return _scan_steps(cfg, carry, nsteps)


def _raise_on_overflow(overflow, max_neighbors: int) -> None:
    """Strict-mode overflow raise (the deprecated check_overflow alias).

    Runs HOST-side after the jitted scan returns — the jax.debug.callback
    this used to ride (an in-scan device sync point) is retired; the
    health guard (core/recovery.py) is the recovering superset.
    """
    if overflow:
        raise health.SimulationDiverged(
            "neighbor capacity overflow: some particle saw more "
            f"candidates than max_neighbors={max_neighbors} (or a cell "
            "table row filled). Results silently dropped pairs - raise "
            "max_neighbors (see the sizing rule in README) or enlarge "
            "capacity.",
            checks=("window_trunc", "cell_overflow"),
            word=health.CAPACITY_CHECKS,
        )


# --------------------------------------------------------------------------
# Legacy absolute-coordinate path (algos "all" / "cell")
# --------------------------------------------------------------------------
def _neighbors_and_pairs(cfg: SPHConfig, state: SPHState):
    """NNPS (low-precision tier) + pair geometry (physics tier)."""
    dom, pol = cfg.domain, cfg.policy
    n = state.xn.shape[0]
    k = cfg.max_neighbors
    if cfg.algo == "cell":
        nl = nnps.cell_list_neighbors(
            dom, state.xn, dtype=pol.nnps_dtype, k=k, capacity=cfg.cap(n)
        )
    elif cfg.algo == "all":
        nl = nnps.all_list_neighbors(
            state.xn, dom.radius_norm, dtype=pol.nnps_dtype, k=k, domain=dom
        )
    else:
        raise ValueError(cfg.algo)
    # Physics-tier pair geometry from hi-precision absolute positions.
    xi = state.xn[:, None, :]
    xj = state.xn[nl.idx]
    diff = nnps.min_image(
        (xi - xj).astype(pol.physics_dtype), nnps.wrap_span_norm(dom)
    )
    disp = diff * (dom.h_d / 2.0)  # physical units
    r = jnp.sqrt(jnp.sum(disp * disp, axis=-1))
    return nl, disp, r


def _step_absolute(cfg: SPHConfig, state: SPHState) -> SPHState:
    """One mixed-precision WCSPH step on absolute positions.

    Same explicit update as the RCLL backends: continuity AND momentum
    evaluated at the current state (p from the pre-update density), so
    every algo integrates the identical scheme.
    """
    dom = cfg.domain
    sch = cfg.resolved_scheme
    nl, disp, r = _neighbors_and_pairs(cfg, state)
    gw = sph.grad_w(disp, r, cfg.h, dom.dim, nl.mask)

    fl = state.fluid
    drho, acc = _gathered_pair_rhs(sch, dom, fl, nl, disp, r, gw)
    rho = fl.rho + cfg.dt * drho
    if cfg.wall_rho_clamp:
        rho = jnp.where(state.fixed, jnp.maximum(rho, sch.rho0), rho)

    v = fl.v + cfg.dt * (acc + sch.body_force_vec(dom.dim))
    vw = 0.0 if state.v_wall is None else state.v_wall
    v = jnp.where(state.fixed[:, None], vw, v)

    dxn = jnp.where(state.fixed[:, None], 0.0, v * cfg.dt * (2.0 / dom.h_d))
    xn = state.xn + dxn
    # wrap periodic axes back into the box
    span = jnp.asarray(
        [2.0 * s / dom.h_d if p else 0.0
         for s, p in zip(dom.spans, dom.periodic)], jnp.float32)
    org = jnp.asarray(dom.origin_norm, jnp.float32)
    wrapped = org + jnp.mod(xn - org, jnp.where(span > 0, span, 1.0))
    xn = jnp.where(span > 0, wrapped, xn)
    return SPHState(
        xn=xn, rc=state.rc,
        fluid=sph.FluidState(v=v, rho=rho, m=fl.m),
        fixed=state.fixed, t=state.t + cfg.dt,
        kind=state.kind, v_wall=state.v_wall,
    )


def step(cfg: SPHConfig, state: SPHState) -> SPHState:
    """One WCSPH step from/to original particle indexing.

    The RCLL path packs, builds a fresh neighbor list, steps once, and
    unpacks - identical physics to one ``simulate`` iteration (reuse
    across steps requires carrying ``PersistentCarry`` via
    ``step_persistent``; this wrapper is the stateless convenience form).
    """
    if cfg.algo == "rcll":
        carry = init_persistent(cfg, state)
        return finalize_persistent(cfg, _physics_step(cfg, carry))
    return _step_absolute(cfg, state)


@partial(jax.jit, static_argnums=(0, 2))
def _simulate_stats_jit(
    cfg: SPHConfig, state: SPHState, nsteps: int
) -> tuple[SPHState, SimStats]:
    if cfg.algo == "rcll":
        carry = init_persistent(cfg, state)
        carry = _scan_steps(cfg, carry, nsteps)
        stats = SimStats(
            rebuilds=carry.rebuilds, steps=carry.steps,
            overflow=carry.overflow,
        )
        return finalize_persistent(cfg, carry), stats

    def body(s, _):
        return _step_absolute(cfg, s), None

    out, _ = jax.lax.scan(body, state, None, length=nsteps)
    stats = SimStats(
        rebuilds=jnp.asarray(nsteps, jnp.int32),
        steps=jnp.asarray(nsteps, jnp.int32),
        overflow=jnp.zeros((), bool),
    )
    return out, stats


def simulate_stats(
    cfg: SPHConfig, state: SPHState, nsteps: int
) -> tuple[SPHState, SimStats]:
    """Run ``nsteps`` steps; also report rebuild/overflow diagnostics.

    With ``cfg.check_overflow`` (the deprecated strict-guard alias) the
    run raises :class:`health.SimulationDiverged` on any capacity
    overflow — via one host read of the overflow flag AFTER the scan
    returns, not the in-scan callback sync point this used to cost.
    """
    out, stats = _simulate_stats_jit(cfg, state, nsteps)
    if cfg.check_overflow and bool(stats.overflow):
        _raise_on_overflow(True, cfg.max_neighbors)
    return out, stats


def simulate(cfg: SPHConfig, state: SPHState, nsteps: int) -> SPHState:
    """Run ``nsteps`` steps under lax.scan (single fused XLA program)."""
    return simulate_stats(cfg, state, nsteps)[0]
