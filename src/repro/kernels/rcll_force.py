"""Pallas TPU kernel: fused cell-blocked WCSPH force evaluation.

The paper's bandwidth argument (Table 6: NNPS + gradient are ~8% compute
/ ~51% bandwidth) applied to the *force* stage: instead of gathering
per-pair arrays (disp, grad W, dv, m_j — all (N, K, d)-sized HBM round
trips), the kernel decodes the relative coordinates + the exact integer
cell offset (Eq. 7) in registers, evaluates the B-spline gradient in
place, and accumulates the continuity AND momentum sums directly into
fp32 accumulators of the self slot — the full WCSPH right-hand side in
ONE pass over the 3^d neighbourhood (the solver integrates the standard
explicit scheme, so both sums read the same state). The pair physics
goes through the same primitives as the reference path
(``core/bspline.py`` / ``core/sph.py``).

Mosaic layout (the row layout; what the TPU compiler accepts at 1M
particles and what keeps each grid step busy):

  * the cell grid gains one ghost cell on each side of every axis, and
    the fast (last) axis is padded to ``W``, a multiple of 128 lanes
    (:func:`lane_width`). Every per-cell table is ``(n0+2, [n1+2,] F,
    cap8, W)``: slow axes leading, then the F fields, the self slots
    on the sublanes (``cap`` rounded up to 8) and the cells of one row
    on the lanes. A ghost cell off a wall is empty (m = 0, 1/ρ = 1/ρ0);
    on a periodic axis it is a copy of the opposite edge's cell
    (:func:`padded_cell_ids`), so no neighbour address needs a mask;
  * grid ``(n0,)`` in 2-D, ``(n0, n1)`` in 3-D (:func:`force_grid`): one
    step per row of cells. Each table is read through 3^(d-1)
    ``BlockSpec``s whose index maps pick the neighbour rows ``y``,
    ``y+1``, ``y+2`` of the padded array; a block ``(1, F, cap8, W)``
    has its last two dims equal to the array's, legal at any cap;
  * the body decodes each neighbour row to fp32 once (16-bit floats
    stream as int16 words, Mosaic has no float16 vector load, and decode
    in registers bit-identically to ``astype(float32)``,
    ``tiling.bits16_to_f32``), rolls it by the fast-axis offset
    (a static lane rotation; the wrapped lanes land on ghost or padding
    lanes, whose results are never read) and keeps the 3^d shifted
    copies in VMEM scratch;
  * then, for each (8 slots × 128 cells) self tile, a ``fori_loop``
    over the 3^d offsets and, inside it, over the neighbour slots
    ``sj``, :data:`SLOT_UNROLL` per iteration: slot ``sj``'s row of the
    shifted fields is broadcast along the sublanes against the self tile
    (a stride-0 load), and the sums accumulate in fp32 vregs;
  * the scoped VMEM is the compiler's default unless a row is wide
    enough to need more (:func:`_vmem_limit`).

Half-width streams. The per-step inputs are two record slabs, sized by
``PrecisionPolicy.records`` (:func:`slab_fields`):

  * coordinates stream as the RAW storage-dtype relative coordinate
    (fp16 — lossless, it IS the RCLL state) plus an int16 stale-cell
    shift; the re-anchor ``rel' = rel + 2·(cell_now − cell_stale)``
    happens in fp32 at decode — exact, as the shift is a small integer;
  * v and m stream in the records dtype (fp16/bf16 production, fp32
    oracle) and upcast to fp32 at decode;
  * the density streams fp32 as the RECIPROCAL 1/ρ: p/ρ² is recomputed
    division-free through the scheme's EOS (``Scheme.por2_inv`` —
    linear or Tait) and the viscosity ρ-product division disappears.

The physics terms themselves (EOS, viscosity channels, delta-SPH) come
from the static ``Scheme`` (core/scheme.py) — the same declarative spec
the reference and fused-XLA backends consume, so the kernel cannot
drift from them.

No neighbor list is consumed: the B-spline derivative vanishes
identically beyond the support 2h and at r = 0, so every out-of-support
candidate in the 3^dim neighborhood (and the self pair) contributes an
exact 0.0 — the kernel sums over every slot and lets compact support
do the masking. Empty slots and ghost cells are killed by m_j = 0 (1/ρ
is 1/ρ0 there so every factor stays finite); the lanes past a row hold
zeros and reach ghost lanes only. Garbage accumulated into a vacant
SELF slot or a ghost lane is never read back — the unpack gathers
occupied slots only. Consequence: the fused kernel never
truncates at K — it sees every in-support pair even where the
K-compacted list would overflow.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import bspline
from repro.core import scheme as scheme_lib
from repro.kernels import tiling

Array = jnp.ndarray

SUBLANES, LANES = 8, 128
# Neighbour slots per iteration of the slot loop: independent pair
# chains the scheduler interleaves (one chain per iteration waits out
# the sqrt and reciprocal latencies). 4 was the fastest of 1, 2, 4, 8
# on a v5e at the 1M shapes, caps 18, 32 and 41 (PERF.md, section 6).
SLOT_UNROLL = 4


def _half(dtype) -> bool:
    return jnp.dtype(dtype).itemsize == 2


def slab_fields(rel_dtype, records_dtype) -> tuple[tuple, tuple]:
    """Field names of the 16-bit and of the fp32 record slab, in order.

    Each field rides the slab of its own storage width: rel keeps its raw
    storage bits (16-bit, or fp32 for fp32-coordinate policies — never
    quantized), the stale-cell shift is an exact int16, v follows the
    records dtype, 1/ρ is fp32. ``inv`` is one column, the others d.
    """
    f16 = (("rel",) if _half(rel_dtype) else ()) + ("shift",) + (
        ("v",) if _half(records_dtype) else ())
    f32 = ("inv",) + (() if _half(rel_dtype) else ("rel",)) + (
        () if _half(records_dtype) else ("v",))
    return f16, f32


def lane_width(n_fast: int) -> int:
    """Lanes of a row: the fast-axis cells and two ghosts, to 128."""
    return -(-(int(n_fast) + 2) // LANES) * LANES


def slot_rows(cap: int) -> int:
    """Sublanes of a cell: ``cap`` slots rounded up to a full vreg."""
    return -(-int(cap) // SUBLANES) * SUBLANES


def force_grid(ncells: tuple) -> tuple[int, ...]:
    """The kernel's ``pallas_call`` grid: one step per row of cells,
    the slow axes ``ncells[:-1]``. Each step evaluates every lane of its
    row (``lane_width(ncells[-1])`` cells) against all 3^dim offsets,
    whatever their occupancy."""
    return tuple(int(n) for n in ncells[:-1])


def padded_cell_ids(ncells: tuple, periodic: tuple) -> np.ndarray:
    """Flat cell id at every cell of the ghost-padded grid.

    Shape ``(n0+2, ..., n_{d-1}+2)``; position ``p`` holds cell ``p-1``
    per axis, wrapped on a periodic axis; a ghost off a wall holds
    ``prod(ncells)``, the empty cell. Static (host-side numpy).
    """
    flat, valid = 0, True
    grids = np.meshgrid(*[np.arange(-1, n + 1) for n in ncells],
                        indexing="ij")
    for g, n, p in zip(grids, ncells, periodic):
        if p:
            g = g % n
        else:
            valid = valid & (g >= 0) & (g < n)
        flat = flat * int(n) + np.clip(g, 0, n - 1)
    return np.where(valid, flat, int(np.prod(ncells))).astype(np.int32)


def to_rows(t: Array, ncells: tuple) -> Array:
    """Per-cell tables ``(prod(padded), cap8, F)``, the cells in
    :func:`padded_cell_ids` order and their slots past ``cap`` filled as
    empty, to the row layout ``(n0+2, [n1+2,] F, cap8, W)``. The lanes
    past the row hold zeros: a roll brings them into ghost lanes only,
    whose sums are never read."""
    *slow, x = (int(n) + 2 for n in ncells)
    nslow = len(slow)
    t = t.reshape(tuple(slow) + (x,) + t.shape[1:])
    t = jnp.moveaxis(t, (nslow, nslow + 2), (nslow + 2, nslow))
    return jnp.pad(t, [(0, 0)] * (t.ndim - 1) + [(0, lane_width(x - 2) - x)])


def from_rows(out: Array, cell: Array, slot: Array, ncells: tuple) -> Array:
    """``(N, F)``: the kernel's output ``(n0, [n1,] F, cap8, W)`` at each
    particle's flat ``cell`` and ``slot``; fast-axis cell ``x`` is lane
    ``x + 1``."""
    f, cap8, width = out.shape[-3:]
    row, x = cell // ncells[-1], cell % ncells[-1]
    base = (row * f * cap8 + slot) * width + x + 1
    field = jnp.arange(f, dtype=jnp.int32) * (cap8 * width)
    return out.reshape(-1)[base[:, None] + field[None, :]]


def _decode_row(ref16, ref32, m_ref, *, lead, f16, f32, d, rel_dtype,
                records_dtype, scheme):
    """One neighbour row's fields at fp32, each ``(cap8, W)``:
    re-anchored coordinates q_a = rel_a + 2·shift_a, v_a, m, 1/ρ, p/ρ²."""
    cols = {}
    for ref, names in ((ref16, f16), (ref32, f32)):
        o = 0
        for name in names:
            w = 1 if name == "inv" else d
            cols[name] = [ref[lead + (o + a,)] for a in range(w)]
            o += w
    q = [tiling.decode_f32(r, rel_dtype)
         + 2.0 * tiling.decode_f32(s, jnp.int16)
         for r, s in zip(cols["rel"], cols["shift"])]
    v = [tiling.decode_f32(x, records_dtype) for x in cols["v"]]
    m = tiling.decode_f32(m_ref[lead + (0,)], records_dtype)
    inv = cols["inv"][0]
    return q + v + [m, inv, scheme.por2_inv(inv)]


def _force_kernel(*refs, nrow, offsets, f16, f32, hc_phys, h, dim,
                  rel_dtype, records_dtype, scheme, cap, interpret):
    rows16, rows32 = refs[:nrow], refs[nrow:2 * nrow]
    rows_m = refs[2 * nrow:3 * nrow]
    out_ref, nb_ref = refs[3 * nrow:]
    d = dim
    lead = (0,) * (dim - 1)
    cap8, width = out_ref.shape[-2], out_ref.shape[-1]
    # neighbour rows, decoded once and rolled by each fast-axis offset:
    # nb_ref[k, :, c] holds the fields of lane tile c's offset-k neighbours
    for r in range(nrow):
        fields = jnp.stack(_decode_row(
            rows16[r], rows32[r], rows_m[r], lead=lead, f16=f16, f32=f32,
            d=d, rel_dtype=rel_dtype, records_dtype=records_dtype,
            scheme=scheme,
        ))
        for k, off in enumerate(offsets):
            if _row_of(off) != r:
                continue
            shift = (-int(off[-1])) % width
            x = fields if shift == 0 else pltpu.roll(fields, shift, 2)
            for c in range(width // LANES):
                nb_ref[k, :, c] = x[:, :, c * LANES:(c + 1) * LANES]

    center = next(k for k, off in enumerate(offsets) if not any(off))
    nsub = cap8 // SUBLANES
    nf = 2 * d + 3  # q, v, m, 1/rho, p/rho^2

    def tile(g, carry):
        s0 = pl.multiple_of((g % nsub) * SUBLANES, SUBLANES)
        c = g // nsub
        own = [nb_ref[center, f, c, pl.ds(s0, SUBLANES)] for f in range(nf)]
        qi, vi = own[:d], own[d:2 * d]
        inv_i, por2_i = own[2 * d + 1], own[2 * d + 2]
        acc = tuple(jnp.zeros((SUBLANES, LANES), jnp.float32)
                    for _ in range(d + 1))

        def offset(k, acc):
            # offsets in cells.neighbor_cell_offsets order: k's base-3
            # digits, most significant first, less one
            off = [((k // 3 ** (d - 1 - a)) % 3 - 1).astype(jnp.float32)
                   for a in range(d)]

            def pair(sj, acc):
                nbr = [_slot_row(nb_ref, (k, f, c), sj, interpret)
                       for f in range(nf)]
                qj, vj = nbr[:d], nbr[d:2 * d]
                mj, inv_j, por2_j = nbr[2 * d:]
                disp = [((qi[a] - qj[a]) * 0.5 - off[a])
                        * hc_phys[a] for a in range(d)]
                r2 = disp[0] * disp[0]
                for a in range(1, d):
                    r2 = r2 + disp[a] * disp[a]
                coef = bspline.dw_over_r(jnp.sqrt(r2), h, dim)
                # dv·disp first: the scheme's ∇W-channel coefficient
                # (pressure + optional artificial viscosity) needs it
                dv = [vi[a] - vj[a] for a in range(d)]
                dv_dot_disp = dv[0] * disp[0]
                for a in range(1, d):
                    dv_dot_disp = dv_dot_disp + dv[a] * disp[a]
                gc = scheme.gradw_pair_coef(
                    mj, por2_i, por2_j, inv_i, inv_j, dv_dot_disp, r2, h=h,
                ) * coef
                if scheme.has_dv_term:
                    # x·∇W = coef * Σ disp² = coef * r2
                    vc = scheme.dv_pair_coef(mj, coef * r2, inv_i, inv_j,
                                             r2, h=h)
                dterm = mj * coef * dv_dot_disp
                if scheme.has_delta_term:
                    dterm = dterm + scheme.drho_pair_term(
                        mj, inv_i, inv_j, coef * r2, r2, h=h)
                new = [acc[0] + dterm]
                for a in range(d):
                    contrib = -gc * disp[a]
                    if scheme.has_dv_term:
                        contrib = contrib + vc * dv[a]
                    new.append(acc[1 + a] + contrib)
                return tuple(new)

            def slots(i, acc):
                # SLOT_UNROLL independent pair chains per iteration, for
                # the scheduler to interleave; the slots past cap (up to
                # cap8) are empty and add exact zeros
                for u in range(SLOT_UNROLL):
                    acc = pair(i * SLOT_UNROLL + u, acc)
                return acc

            return jax.lax.fori_loop(0, -(-cap // SLOT_UNROLL), slots, acc)

        acc = jax.lax.fori_loop(0, len(offsets), offset, acc)
        for f in range(d + 1):
            out_ref[lead + (f, pl.ds(s0, SUBLANES),
                            pl.ds(pl.multiple_of(c * LANES, LANES), LANES))] = (
                acc[f])
        return carry

    jax.lax.fori_loop(0, nsub * (width // LANES), tile, 0)


def _slot_row(ref, idx, sj, interpret):
    """Row ``sj`` of ``ref[idx]``, broadcast along the sublanes: a
    stride-0 load on the TPU (one sublane-broadcast load per vreg; the
    interpreter reads stride 0 as 1, so it slices and broadcasts)."""
    if interpret:
        return jnp.broadcast_to(ref[idx + (pl.ds(sj, 1),)], (SUBLANES, LANES))
    return ref[idx + (pl.ds(sj, SUBLANES, stride=0),)]


def _row_of(off) -> int:
    """Index of an offset's row among the 3^(d-1) neighbour rows."""
    r = 0
    for o in off[:-1]:
        r = 3 * r + int(o) + 1
    return r


def _vmem_limit(tables, nrow, nout, scratch) -> int | None:
    """Scoped VMEM for a row of ``width`` lanes, where it exceeds three
    quarters of the compiler's default (16 MiB): the neighbour rows'
    blocks and the output block, double-buffered; the shifted-row
    scratch; the decoded row and its rolled copies."""
    cap8, width = tables[0].shape[-2:]

    def tile_rows(itemsize):  # sublanes of a VMEM tile: 8 x 32-bit
        return -(-cap8 // (32 // itemsize)) * (32 // itemsize)

    rows = sum(t.shape[-3] * tile_rows(t.dtype.itemsize) * t.dtype.itemsize
               for t in tables) * width * nrow
    need = (2 * (rows + nout * cap8 * width * 4) + 4 * math.prod(scratch)
            + 3 * 4 * scratch[1] * cap8 * width)
    default = 16 * 2**20
    return None if need <= default * 3 // 4 else need * 4 // 3


def _words(x: Array) -> Array:
    """16-bit floats travel as their int16 words (Mosaic loads those)."""
    if x.dtype.itemsize == 2:
        return jax.lax.bitcast_convert_type(x, jnp.int16)
    return x


@functools.partial(
    jax.jit,
    static_argnames=(
        "hc_phys", "h", "dim", "rel_dtype", "records_dtype", "scheme",
        "cap", "interpret",
    ),
)
def rcll_force(
    t16: Array,  # (n0+2, [n1+2,] F16, cap8, W) 16-bit record slab
    t32: Array,  # (n0+2, [n1+2,] F32, cap8, W) f32 record slab
    m: Array,  # (n0+2, [n1+2,] 1, cap8, W) records dtype, 0 if empty
    *,
    hc_phys: tuple,  # (d,) physical cell edges (static)
    h: float,
    dim: int,
    rel_dtype,  # storage dtype of rel (its slab: slab_fields)
    records_dtype,  # storage dtype of v and m
    scheme: scheme_lib.Scheme,
    cap: int,  # slots per cell that can hold a particle
    interpret: bool = False,
) -> Array:
    """Fused SPH RHS in the row layout: ``(n0, [n1,] 1+d, cap8, W)`` f32,
    field 0 drho, fields 1..d acc; lane ``x+1`` is fast-axis cell ``x``.

    Tables are in the ghost-padded row layout (module docstring), the
    slabs' columns in :func:`slab_fields` order. The physics terms
    (EOS, viscosity channels) come from the static ``scheme`` — the same
    declarative spec the XLA and reference backends consume.
    """
    from repro.core import cells  # deferred: kernels stay import-light

    nslow = dim - 1
    grid = tuple(int(n) - 2 for n in t16.shape[:nslow])
    cap8, width = t16.shape[-2:]
    d = dim
    f16, f32 = slab_fields(rel_dtype, records_dtype)
    offsets = tuple(tuple(int(o) for o in off)
                    for off in cells.neighbor_cell_offsets(dim))
    nrow = 3 ** nslow
    kernel = functools.partial(
        _force_kernel,
        nrow=nrow,
        offsets=offsets,
        f16=f16,
        f32=f32,
        hc_phys=tuple(float(x) for x in hc_phys),
        h=float(h),
        dim=int(dim),
        rel_dtype=jnp.dtype(rel_dtype),
        records_dtype=jnp.dtype(records_dtype),
        scheme=scheme,
        cap=int(cap),
        interpret=bool(interpret),
    )

    def rows(table):
        block = (1,) * nslow + table.shape[nslow:]
        tail = (0,) * (table.ndim - nslow)
        specs = []
        for r in range(nrow):
            dy = [(r // 3 ** (nslow - 1 - a)) % 3 for a in range(nslow)]
            specs.append(pl.BlockSpec(
                block, lambda *y, dy=dy: tuple(
                    yi + o for yi, o in zip(y, dy)) + tail))
        return specs

    nf = 2 * d + 3
    out_block = (1,) * nslow + (1 + d, cap8, width)
    tables = (_words(t16), t32.astype(jnp.float32), _words(m))
    scratch = (3 ** d, nf, width // LANES, cap8, LANES)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[s for t in tables for s in rows(t)],
        out_specs=pl.BlockSpec(out_block, lambda *y: y + (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(grid + (1 + d, cap8, width),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM(scratch, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * nslow,
            vmem_limit_bytes=_vmem_limit(tables, nrow, 1 + d, scratch),
        ),
        interpret=interpret,
        name="rcll_force",
    )(*[t for t in tables for _ in range(nrow)])
