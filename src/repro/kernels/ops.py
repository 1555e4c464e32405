"""Public jit'd wrappers for the Pallas kernels + cell-table packing.

The (cell, neighbour cell) kernels consume *cell-major* dense tables
(C+1, d, cap) - the packing here is the TPU analogue of the paper's
particle sort (particles that share a cell are contiguous; row-major cell
order keeps spatial neighbors close in HBM). Row C is a sentinel empty
cell: out-of-domain neighborhood slots point at it, so the kernels never
branch on validity. The force kernel consumes the same cells in its row
layout (:func:`cell_tables`, ``kernels/rcll_force.py``): ghost cells in
place of the sentinel, cells on the lanes.

``interpret=None`` resolves through :func:`default_interpret`: compiled
on TPU, interpreted on every other backend (tests on the CPU). All
wrappers are shape-polymorphic over (C, cap, d, M).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cells as cells_lib
from repro.core import nnps as nnps_lib
from repro.core import rcll as rcll_lib
from repro.core.domain import Domain
from repro.core.precision import NNPS_STORE
from repro.kernels import nnps_pairwise, rcll_force, sph_gradient

Array = jnp.ndarray


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def nb_with_sentinel(domain: Domain) -> Array:
    """(C+1, M) neighbor-cell ids; the sentinel row points at itself."""
    nb = jnp.asarray(cell_neighbor_ids(domain))
    return jnp.concatenate(
        [nb, jnp.full((1, nb.shape[1]), nb.shape[0], nb.dtype)], axis=0
    )


def cell_neighbor_ids(domain: Domain) -> np.ndarray:
    """(C, M) int32 flat neighbor-cell ids per cell; invalid -> sentinel C.

    Static (host-side numpy): the cell graph depends only on the Domain.
    """
    ncells = np.asarray(domain.ncells)
    C = int(np.prod(ncells))
    dim = domain.dim
    offs = cells_lib.neighbor_cell_offsets(dim)  # (M, d)
    coords = np.stack(
        np.meshgrid(*[np.arange(n) for n in ncells], indexing="ij"), -1
    ).reshape(C, dim)
    nb = coords[:, None, :] + offs[None, :, :]  # (C, M, d)
    per = np.asarray(domain.periodic)
    wrapped = np.where(per, nb % ncells, nb)
    valid = np.all((wrapped >= 0) & (wrapped < ncells), axis=-1)
    clipped = np.clip(wrapped, 0, ncells - 1)
    flat = clipped[..., 0]
    for a in range(1, dim):
        flat = flat * ncells[a] + clipped[..., a]
    return np.where(valid, flat, C).astype(np.int32)


def pack_cells(
    binning: cells_lib.CellBinning,
    rel: Array,  # (N, d) storage dtype
    *fields: Array,  # (N,) f32 each
) -> tuple[Array, Array, list[Array]]:
    """Pack per-particle data into cell-major tables with a sentinel row.

    Thin kernel-facing wrapper over ``cells.to_cell_major``: transposes
    rel to the (C, d, cap) sublane/lane layout and appends the sentinel
    empty-cell row the kernels' neighborhood indexing relies on.

    Returns (rel_table (C+1, d, cap), occ (C+1, cap), field_tables).
    """
    C, cap = binning.table.shape
    d = rel.shape[1]
    occ = (binning.table >= 0).astype(jnp.float32)
    rel_t = cells_lib.to_cell_major(binning, rel).transpose(0, 2, 1)
    rel_t = jnp.concatenate(
        [rel_t, jnp.zeros((1, d, cap), rel_t.dtype)], axis=0
    )
    occ = jnp.concatenate([occ, jnp.zeros((1, cap), occ.dtype)], axis=0)
    packed_fields = []
    for f in fields:
        ft = cells_lib.to_cell_major(binning, f.astype(jnp.float32))
        ft = jnp.concatenate([ft, jnp.zeros((1, cap), ft.dtype)], axis=0)
        packed_fields.append(ft)
    return rel_t, occ, packed_fields


def unpack_per_particle(
    table: Array, binning: cells_lib.CellBinning
) -> Array:
    """Gather per-particle values out of a (C+1, cap, ...) table -> (N, ...).

    Inverse of ``pack_cells`` outputs: drops the sentinel row and gathers
    each particle's slot via ``cells.from_cell_major``.
    """
    return cells_lib.from_cell_major(binning, table[: binning.table.shape[0]])


def cell_tables(
    rows16: Array,  # (N, F16) u16 cell-sorted 16-bit record rows
    rows32: Array,  # (N, F32) f32 cell-sorted fp32 rows
    counts: Array,  # (C,) int32 per-cell occupancy
    fill32: Array,  # (F32,) f32 empty-slot fill per fp32 column
    *,
    cap: int,
    ncells: tuple,
    periodic: tuple,
) -> tuple[Array, Array]:
    """The force kernel's row-layout tables from cell-sorted rows.

    The persistent pipeline's arrays are cell-sorted, so cell c's
    particles are the contiguous rows ``starts[c] .. starts[c] +
    counts[c] - 1`` (the counting-sort invariant): each cell's tile is
    the ``cap``-row window at ``starts[c]`` of a record slab, masked past
    the occupancy — one windowed gather per slab instead of one id-table
    gather per field. The windows are taken at every cell of the
    ghost-padded grid (``rcll_force.padded_cell_ids``: a periodic ghost
    reads the opposite edge's window, a wall ghost an empty one), then
    laid out as ``(n0+2, [n1+2,] F, cap8, W)`` (``rcll_force.to_rows``):
    ``t16`` u16 with 0 in empty slots, ``t32`` f32 with each column's
    fill there, so denominator fields stay finite.
    """
    ids = jnp.asarray(rcll_force.padded_cell_ids(ncells, periodic).ravel())
    starts = jnp.concatenate([cells_lib.exclusive_cumsum(counts),
                              jnp.full((1,), rows16.shape[0], jnp.int32)])
    cnt = jnp.concatenate([counts.astype(jnp.int32),
                           jnp.zeros((1,), jnp.int32)])  # C: the empty cell
    starts, cnt = starts[ids], cnt[ids]
    t16 = rcll_force.to_rows(_windows(rows16, starts, cnt, cap, 0), ncells)
    t32 = rcll_force.to_rows(
        _windows(rows32, starts, cnt, cap, fill32[None, :]), ncells)
    return t16, t32


def _windows(rows: Array, starts: Array, cnt: Array, cap: int,
             fill) -> Array:
    """``(len(starts), cap8, F)``: the ``cap8``-row window at each start
    (``rcll_force.slot_rows``), ``fill`` past its occupancy and past
    ``cap``."""
    cap8 = rcll_force.slot_rows(cap)
    # cap8 rows of padding: an empty window (and a full last cell's)
    # never reads past the slab
    pad = jnp.concatenate([rows, jnp.zeros((cap8,) + rows.shape[1:],
                                           rows.dtype)])
    win = jax.vmap(
        lambda s: jax.lax.dynamic_slice_in_dim(pad, s, cap8, 0)
    )(starts)
    occ = jnp.arange(cap8, dtype=jnp.int32)[None, :] < jnp.minimum(
        cnt, cap)[:, None]
    return jnp.where(occ[..., None], win, fill)


# --------------------------------------------------------------------------
# RCLL adjacency + neighbor counts (kernel wrapper)
# --------------------------------------------------------------------------
def rcll_adjacency_cells(
    domain: Domain,
    binning: cells_lib.CellBinning,
    rel: Array,  # (N, d) storage dtype
    *,
    compute_dtype=jnp.float32,
    interpret: bool | None = None,
) -> tuple[Array, Array]:
    """Cell-blocked adjacency via the Pallas kernel.

    Returns (adj (C+1, M, cap, cap) f32, counts per particle (N,) f32).
    """
    interpret = default_interpret() if interpret is None else interpret
    rel_t, occ, _ = pack_cells(binning, rel)
    nb = nb_with_sentinel(domain)
    offs = tuple(map(tuple, cells_lib.neighbor_cell_offsets(domain.dim)))
    adj, cnt = nnps_pairwise.rcll_adjacency(
        rel_t,
        occ,
        nb,
        offs=offs,
        weights=tuple(domain.cell_weights),
        r_cell=nnps_lib.rcll_radius_cell_units(domain),
        compute_dtype=compute_dtype,
        interpret=interpret,
    )
    counts = unpack_per_particle(cnt, binning)
    return adj, counts


# --------------------------------------------------------------------------
# RCLL packed neighbor lists (the production neighbor producer)
# --------------------------------------------------------------------------
def rcll_neighbor_lists(
    domain: Domain,
    binning: cells_lib.CellBinning,
    rel: Array,  # (N, d) storage dtype
    *,
    k: int,
    radius_cell: float | None = None,
    nnps_dtype=NNPS_STORE,
    compute_dtype=None,
    interpret: bool | None = None,
) -> nnps_lib.NeighborList:
    """Per-particle neighbor lists via the cell-blocked Pallas kernel.

    Returns a NeighborList whose ids live in the same indexing as the
    entries of ``binning.table`` - with the packed (cell-sorted) binning
    of the persistent pipeline these are packed indices, ready to gather
    from packed per-particle arrays with near-contiguous reads.

    radius_cell: search radius override in reference-cell units (the
    Verlet-skin inflated radius); defaults to the exact support radius.

    compute_dtype defaults to fp32 (TPU-native: fp16 storage upconverted
    by the VPU for free). fp32 arithmetic on fp16-quantized inputs is
    exact through Eq. (7)'s subtract/halve/shift, which makes the kernel
    agree with the jnp fallback bit-for-bit; fp16 arithmetic (the paper's
    A100 mode) can flip exactly-on-boundary pairs between backends.
    """
    interpret = default_interpret() if interpret is None else interpret
    cdt = compute_dtype or jnp.float32
    rel_t, occ, _ = pack_cells(binning, rel.astype(nnps_dtype))
    ids_t = jnp.concatenate(
        [binning.table,
         jnp.full((1, binning.table.shape[1]), -1, jnp.int32)], axis=0
    )
    nb = nb_with_sentinel(domain)
    offs = tuple(map(tuple, cells_lib.neighbor_cell_offsets(domain.dim)))
    if radius_cell is None:
        radius_cell = nnps_lib.rcll_radius_cell_units(domain)
    ids_out, cnt = nnps_pairwise.rcll_neighbor_list_tables(
        rel_t,
        occ,
        ids_t,
        nb,
        offs=offs,
        weights=tuple(domain.cell_weights),
        r_cell=float(radius_cell),
        k_slots=k,
        compute_dtype=cdt,
        interpret=interpret,
    )
    idx = unpack_per_particle(ids_out, binning)  # (N, K)
    mask = idx >= 0
    count = unpack_per_particle(cnt, binning).astype(jnp.int32)
    return nnps_lib.NeighborList(
        idx=jnp.maximum(idx, 0), mask=mask, count=count
    )


# --------------------------------------------------------------------------
# Fused RCLL search + A5 gradient (kernel wrapper)
# --------------------------------------------------------------------------
def rcll_gradient_particles(
    domain: Domain,
    binning: cells_lib.CellBinning,
    rel: Array,  # (N, d)
    f: Array,  # (N,) f32
    *,
    nnps_dtype=NNPS_STORE,
    interpret: bool | None = None,
    eps: float = 1e-12,
) -> Array:
    """Per-particle A5 gradient (N, d) via the fused Pallas kernel."""
    interpret = default_interpret() if interpret is None else interpret
    rel_t, occ, (f_t,) = pack_cells(binning, rel, f)
    nb = nb_with_sentinel(domain)
    offs = tuple(map(tuple, cells_lib.neighbor_cell_offsets(domain.dim)))
    hc_phys = tuple(domain.cell_sizes)
    num, den = sph_gradient.rcll_gradient(
        rel_t,
        f_t,
        occ,
        nb,
        offs=offs,
        weights=tuple(domain.cell_weights),
        r_cell=nnps_lib.rcll_radius_cell_units(domain),
        hc_phys=hc_phys,
        h=domain.h,
        dim=domain.dim,
        nnps_dtype=nnps_dtype,
        interpret=interpret,
    )
    den = jnp.where(jnp.abs(den) > eps, den, jnp.where(den >= 0, eps, -eps))
    grad_t = (num / den).transpose(0, 2, 1)  # (C+1, cap, d)
    return unpack_per_particle(grad_t, binning)


# --------------------------------------------------------------------------
# Fused RCLL force pass (kernels/rcll_force.py wrappers)
# --------------------------------------------------------------------------
def mass_table(
    domain: Domain,
    binning: cells_lib.CellBinning,
    m: Array,
    records_dtype,
    m_scale: Array | None = None,
) -> Array:
    """``(n0+2, [n1+2,] 1, cap8, W)`` static mass table for the force
    kernel, in its row layout (:func:`cell_tables`).

    Masses never change during a run, so the persistent solver builds
    this once per REBUILD (packed order changes there) instead of once
    per step; half-width layouts store ``m / m_scale``
    (``fused.mass_scale`` — see the subnormal-mass note there). Its slots
    are :func:`cell_tables`' under the PACKED binning (slot s of cell c
    holds particle ``starts[c] + s`` in both).
    """
    from repro.core import fused

    half = jnp.dtype(records_dtype).itemsize == 2
    if half:
        if m_scale is None:
            m_scale = fused.mass_scale(m)
        m = m.astype(jnp.float32) / m_scale
    cap = binning.table.shape[1]
    mt = cells_lib.to_cell_major(binning, m.astype(records_dtype)[:, None])
    mt = jnp.pad(mt, [(0, 1), (0, rcll_force.slot_rows(cap) - cap), (0, 0)])
    ids = rcll_force.padded_cell_ids(domain.ncells, domain.periodic)
    return rcll_force.to_rows(mt[ids.ravel()], domain.ncells)


def force_grid_work(
    domain: Domain, binning: cells_lib.CellBinning
) -> tuple[int, Array]:
    """Force-kernel work of one pass, in (self cell, neighbour offset)
    pairs: ``(launched, useful)``.

    ``launched`` (a static int) counts what the kernel evaluates: grid
    steps (:func:`rcll_force.force_grid`) × the cells of a step, ghost
    and padding lanes included (:func:`rcll_force.lane_width`) × 3^dim.
    ``useful`` (an int32 scalar) counts the pairs whose self cell and
    neighbour cell both hold a particle under ``binning``, the tables'
    binning; an out-of-domain neighbour is empty.
    """
    ncells = tuple(int(n) for n in domain.ncells)
    launched = (int(np.prod(rcll_force.force_grid(ncells)))
                * rcll_force.lane_width(ncells[-1]) * 3 ** len(ncells))
    occupied = jnp.concatenate([binning.counts > 0, jnp.zeros((1,), bool)])
    nb = cell_neighbor_ids(domain)  # (C, 3^d), C where off a wall
    useful = jnp.sum(occupied[:-1, None] & occupied[nb], dtype=jnp.int32)
    return launched, useful


def rcll_force_particles(
    domain: Domain,
    binning: cells_lib.CellBinning,
    rc: "rcll_lib.RCLLState",  # CURRENT state, packed indexing
    v: Array,  # (N, d) f32
    m: Array,  # (N,) f32
    rho: Array,  # (N,) f32 current density
    *,
    mu: float = 0.0,
    c0: float | None = None,
    rho0: float = 1.0,
    records_dtype=jnp.float32,
    interpret: bool | None = None,
    scheme=None,
    m_scale: Array | None = None,
    m_table: Array | None = None,
) -> tuple[Array, Array]:
    """The full SPH pair RHS via the fused Pallas kernel.

    Returns (drho (N,), acc (N, d)); body force / wall-particle masking
    are per-particle terms applied by the caller. The physics terms come
    from the static ``scheme`` (core/scheme.py) — the legacy
    ``c0``/``rho0``/``mu`` kwargs build the WCSPH scheme (linear Tait +
    Morris) when ``scheme`` is omitted. Pressure is derived in-kernel
    from the streamed reciprocal density — no p/ρ² table.

    ``records_dtype`` is the storage dtype of the v/m tile streams
    (``PrecisionPolicy.records``): fp16/bf16 is the half-width
    production layout, fp32 the accuracy oracle. The coordinate tiles
    always stream the raw storage-dtype rel (lossless).

    REQUIRES the persistent pipeline's PACKED binning (the per-particle
    arrays are cell-sorted and ``binning.table`` holds consecutive
    packed ids): the cell-major tiles are then contiguous row slices,
    built by :func:`cell_tables` from two record slabs — one 16-bit row
    ``[rel | shift | v]`` and one fp32 row ``[1/ρ]`` — instead of one
    id-table gather per field.
    ``m_table``/``m_scale``: optionally precomputed static mass tile
    (:func:`mass_table`) — the solver rebuilds it only when the packed
    order changes, so the per-step refresh touches exactly the
    coordinate/velocity/density halves.

    Between Verlet-skin rebuilds the binning is STALE: a particle may
    have migrated to an adjacent cell while still occupying its old slot.
    The decode stays exact by streaming the small-int cell shift
    cell_now - cell_stale (minimum-image wrapped) next to the raw rel
    and re-anchoring rel' = rel + 2·shift in fp32 registers — the shift
    is an exact small integer, so rel' decodes to the identical fp32
    position, and the skin invariant (drift <= skin/2 <= half a cell)
    keeps every true pair within the stale 3^dim neighborhood.
    """
    from repro.core import fused  # shared mass normalizer
    from repro.core import scheme as scheme_lib

    if scheme is None:
        if c0 is None:
            raise ValueError("pass either scheme= or the legacy c0=")
        scheme = scheme_lib.wcsph(c0, rho0, mu)
    interpret = default_interpret() if interpret is None else interpret
    d = rc.rel.shape[1]
    half = jnp.dtype(records_dtype).itemsize == 2
    if not half:
        m_scale = jnp.float32(1.0)
    elif m_scale is None:
        m_scale = fused.mass_scale(m)
    if m_table is None:
        m_table = mass_table(domain, binning, m, records_dtype, m_scale)
    cap = binning.table.shape[1]

    with jax.named_scope("sph.cell_tables"):
        delta = domain.wrap_cell_delta(rc.cell_xy - binning.cell_xy)

        def u16(x):
            return jax.lax.bitcast_convert_type(x, jnp.uint16)

        # One 16-bit record slab + one fp32 slab: the dynamic halves of the
        # step, packed cell-major in ONE sweep (contiguous slices — the
        # arrays are cell-sorted), columns in the kernel's
        # ``slab_fields`` order.
        cols = {
            "rel": rc.rel,
            "shift": delta.astype(jnp.int16),
            "v": v.astype(records_dtype),
            "inv": (1.0 / rho).astype(jnp.float32)[:, None],
        }
        fill = {"inv": [1.0 / scheme.rho0], "rel": [0.0] * d, "v": [0.0] * d}
        f16, f32 = rcll_force.slab_fields(rc.rel.dtype, records_dtype)
        t16, t32 = cell_tables(
            jnp.concatenate([u16(cols[f]) for f in f16], axis=1),
            jnp.concatenate([cols[f].astype(jnp.float32) for f in f32],
                            axis=1),
            binning.counts,
            jnp.asarray([x for f in f32 for x in fill[f]], jnp.float32),
            cap=cap,
            ncells=tuple(domain.ncells),
            periodic=tuple(domain.periodic),
        )
    out = rcll_force.rcll_force(
        t16, t32, m_table,
        hc_phys=tuple(domain.cell_sizes),
        h=domain.h,
        dim=domain.dim,
        rel_dtype=rc.rel.dtype,
        records_dtype=records_dtype,
        scheme=scheme,
        cap=cap,
        interpret=interpret,
    )
    with jax.named_scope("sph.unpack"):
        out = unpack_rows(out, binning, tuple(domain.ncells)) * m_scale
    return out[:, 0], out[:, 1:]


def unpack_rows(out: Array, binning: cells_lib.CellBinning,
                ncells: tuple) -> Array:
    """Per-particle rows ``(N, F)`` of the kernel's row-layout output.

    Particle p of the PACKED binning sits in slot ``p - starts[cell]``
    of its cell (the counting-sort invariant :func:`cell_tables` packs
    by). A particle past ``cap`` (a cell overflow, flagged by the
    binning) reads the last slot.
    """
    n = binning.cell_id.shape[0]
    slot = jnp.arange(n, dtype=jnp.int32) - cells_lib.exclusive_cumsum(
        binning.counts)[binning.cell_id]
    slot = jnp.clip(slot, 0, binning.table.shape[1] - 1)
    return rcll_force.from_rows(out, binning.cell_id, slot, ncells)
