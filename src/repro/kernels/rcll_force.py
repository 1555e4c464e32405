"""Pallas TPU kernel: fused cell-blocked WCSPH force evaluation.

The paper's bandwidth argument (Table 6: NNPS + gradient are ~8% compute
/ ~51% bandwidth) applied to the *force* stage: instead of gathering
per-pair arrays (disp, grad W, dv, m_j — all (N, K, d)-sized HBM round
trips), each (cell, neighbor-cell) tile decodes the relative coordinates
+ the exact integer cell offset (Eq. 7) in registers, evaluates the
B-spline gradient in place, and accumulates the continuity AND momentum
sums directly into fp32 VMEM accumulators indexed by the self cell — the
full WCSPH right-hand side in ONE pass over the neighbor tiles (the
solver integrates the standard explicit scheme, so both sums read the
same state). The tile math is shared with ``nnps_pairwise.py`` /
``sph_gradient.py`` (``kernels/tiling.py``); the pair physics goes
through the same primitives as the reference path (``core/bspline.py``
/ ``core/sph.py``).

Mosaic layout (what the TPU compiler accepts at 1M particles):

  * grid (C, 3^d), one (self cell, neighbor cell) pair per step; every
    per-cell operand is a ``(C+1, rows, cap)`` table read in
    ``(1, rows, cap)`` blocks, so the block's last two dims equal the
    array's (scalar rows are ``(C+1, 1, cap)``);
  * the neighbor cell id is computed in the BlockSpec index map from
    ``c``, ``k`` and the static grid (:func:`neighbor_cell`) — no
    ``(C, 3^d)`` table is prefetched into SMEM, which cannot hold one
    past ~2k cells; the 3^d x d offsets ride SMEM;
  * 16-bit floats stream as their int16 words (Mosaic has no float16
    vector load) and decode to fp32 in registers with integer ops,
    bit-identical to ``astype(float32)`` (``tiling.bits16_to_f32``).

Half-width tile streams (the bandwidth round). The kernel's per-tile
inputs are sized by ``PrecisionPolicy.records``:

  * coordinates stream as the RAW storage-dtype relative coordinate
    (fp16 — lossless, it IS the RCLL state) plus an int16 stale-cell
    shift; the re-anchor ``rel' = rel + 2·(cell_now − cell_stale)``
    happens in fp32 registers (``tiling.tile_phys_disp_shifted``) — an
    exact decode at 4 bytes/axis (fp16 word + int16 shift word), the
    same bytes as a pre-shifted fp32 coordinate;
  * v and m stream in the records dtype (fp16/bf16 production, fp32
    oracle) and upcast to fp32 in-register;
  * the density tier streams fp32 as the RECIPROCAL 1/ρ (full fp32
    density information, one reciprocal per particle at pack time):
    p/ρ² is recomputed division-free in-register through the scheme's
    EOS (``Scheme.por2_inv`` — linear or Tait) and the viscosity
    ρ-product division disappears — no p/ρ² table, no occupancy table
    (see below). 2-D bytes per slot per tile: 16 vs 32 for PR 2.

The physics terms themselves (EOS, viscosity channels, delta-SPH) come
from the static ``Scheme`` (core/scheme.py) — the same declarative spec
the reference and fused-XLA backends consume, so the kernel cannot
drift from them.

No neighbor list is consumed: the B-spline derivative vanishes
identically beyond the support 2h and at r = 0, so every out-of-support
candidate in the 3^dim neighborhood (and the self pair) contributes an
exact 0.0 — the kernel sums over the full tile and lets compact support
do the masking. Empty slots are killed by m_j = 0 (zero-filled tables;
1/ρ tables are 1/rho0-filled so every factor stays finite and the EOS
decode yields ~0); an occupancy mask adds nothing the m_j
factor and compact support don't already guarantee, so none is streamed.
Garbage accumulated into a vacant SELF slot (i empty, j occupied) is
finite and never read back — ``ops.unpack_per_particle`` gathers
occupied slots only. Consequence: the fused kernel never truncates at
K — it sees every in-support pair even where the K-compacted list would
overflow.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import bspline
from repro.core import scheme as scheme_lib
from repro.kernels import tiling

Array = jnp.ndarray


def _force_kernel(
    # inputs
    off_ref,  # (M*d,) f32 neighborhood offsets, SMEM
    rel_i_ref,  # (1, d, cap) self cell: rel words (16-bit) or f32
    rel_j_ref,  # (1, d, cap) neighbor cell
    shift_i_ref,  # (1, d, cap) int16 stale-cell shift
    shift_j_ref,  # (1, d, cap)
    v_i_ref,  # (1, d, cap) records words (16-bit) or f32
    v_j_ref,  # (1, d, cap)
    m_j_ref,  # (1, 1, cap) records words or f32 (0 in empty slots)
    inv_i_ref,  # (1, 1, cap) f32 reciprocal density (1/rho0 in empty slots)
    inv_j_ref,  # (1, 1, cap) f32
    # outputs (indexed by c only -> accumulated across the k axis)
    drho_ref,  # (1, 1, cap) f32
    acc_ref,  # (1, d, cap) f32
    *,
    hc_phys: tuple,
    h: float,
    dim: int,
    rel_dtype,
    records_dtype,
    scheme: scheme_lib.Scheme,
):
    k = pl.program_id(1)
    d = rel_i_ref.shape[1]

    @pl.when(k == 0)
    def _init():
        drho_ref[...] = jnp.zeros_like(drho_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    off_k = [off_ref[k * d + a] for a in range(d)]
    disp, r2 = tiling.tile_phys_disp_shifted(
        tiling.decode_f32(rel_i_ref[0], rel_dtype),
        tiling.decode_f32(rel_j_ref[0], rel_dtype),
        tiling.decode_f32(shift_i_ref[0], jnp.int16),
        tiling.decode_f32(shift_j_ref[0], jnp.int16),
        off_k, hc_phys,
    )
    coef = bspline.dw_over_r(jnp.sqrt(r2), h, dim)

    v_i = tiling.decode_f32(v_i_ref[0], records_dtype)
    v_j = tiling.decode_f32(v_j_ref[0], records_dtype)
    mj = tiling.decode_f32(m_j_ref[0, 0], records_dtype)[None, :]
    inv_i = inv_i_ref[0, 0][:, None]
    inv_j = inv_j_ref[0, 0][None, :]
    por2_i = scheme.por2_inv(inv_i_ref[0, 0])
    por2_j = scheme.por2_inv(inv_j_ref[0, 0])
    # Pair velocity deltas and dv·disp first: the scheme's ∇W-channel
    # coefficient (pressure + optional artificial viscosity) needs the
    # full dot product before the per-axis accumulation loop.
    dv = [v_i[a][:, None] - v_j[a][None, :] for a in range(d)]
    dv_dot_disp = jnp.zeros_like(r2)
    for a in range(d):
        dv_dot_disp += dv[a] * disp[a]
    gc = scheme.gradw_pair_coef(
        mj, por2_i[:, None], por2_j[None, :], inv_i, inv_j,
        dv_dot_disp, r2, h=h,
    ) * coef
    if scheme.has_dv_term:
        # x·∇W = coef * Σ disp² = coef * r2 (gw tiles are coef * disp_a).
        vc = scheme.dv_pair_coef(mj, coef * r2, inv_i, inv_j, r2, h=h)
    for a in range(d):
        contrib = -gc * disp[a]
        if scheme.has_dv_term:
            contrib += vc * dv[a]
        acc_ref[0, a] += jnp.sum(contrib, axis=1)
    dterm = mj * coef * dv_dot_disp
    if scheme.has_delta_term:
        dterm += scheme.drho_pair_term(
            mj, inv_i, inv_j, coef * r2, r2, h=h
        )
    drho_ref[0, 0] += jnp.sum(dterm, axis=1)


def neighbor_cell(c, k, *, ncells: tuple, periodic: tuple):
    """Flat id of the k-th 3^dim neighbor of flat cell ``c``.

    Scalar integer arithmetic on the static grid (row-major, last axis
    fastest; offsets in ``cells.neighbor_cell_offsets`` order): periodic
    axes wrap, out-of-domain offsets map to the sentinel cell
    ``prod(ncells)``. It runs inside the BlockSpec index map, so no
    O(C) neighbor table is ever streamed or held in SMEM.
    """
    dim = len(ncells)
    total = int(np.prod(ncells))
    flat, valid = 0, None
    for a in range(dim):
        stride = int(np.prod(ncells[a + 1:]))
        n = int(ncells[a])
        x = jax.lax.rem(jax.lax.div(c, stride), n)
        o = jax.lax.rem(jax.lax.div(k, 3 ** (dim - 1 - a)), 3) - 1
        y = x + o
        if periodic[a]:
            y = jnp.where(y < 0, y + n, jnp.where(y >= n, y - n, y))
        else:
            ok = (y >= 0) & (y < n)
            valid = ok if valid is None else valid & ok
        flat = flat + y * stride
    if valid is None:
        return flat
    return jnp.where(valid, flat, total)


def force_grid(ncells: tuple) -> tuple[int, int]:
    """The kernel's ``pallas_call`` grid: (C self cells, 3^dim neighbours).

    Every grid step runs, whatever the occupancy of its two cells.
    """
    from repro.core import cells  # deferred: kernels stay import-light

    dim = len(ncells)
    return (int(np.prod(ncells)),
            int(cells.neighbor_cell_offsets(dim).shape[0]))


def _words(x: Array) -> Array:
    """16-bit floats travel as their int16 words (Mosaic loads those)."""
    if jnp.issubdtype(x.dtype, jnp.floating) and x.dtype.itemsize == 2:
        return jax.lax.bitcast_convert_type(x, jnp.int16)
    return x


@functools.partial(
    jax.jit,
    static_argnames=(
        "ncells", "periodic", "hc_phys", "h", "dim", "scheme", "interpret"
    ),
)
def rcll_force(
    rel: Array,  # (C+1, d, cap) raw storage-dtype relative coords
    shift: Array,  # (C+1, d, cap) int16 cell shift (cell_now - cell_stale)
    v: Array,  # (C+1, d, cap) records dtype
    m: Array,  # (C+1, cap) records dtype, 0 in empty slots
    inv_rho: Array,  # (C+1, cap) f32 reciprocal density, 1/rho0 if empty
    *,
    ncells: tuple,  # (d,) cells per axis (static); C = prod(ncells)
    periodic: tuple,  # (d,) periodic-axis flags (static)
    hc_phys: tuple,  # (d,) physical cell edges (static)
    h: float,
    dim: int,
    scheme: scheme_lib.Scheme,
    interpret: bool = False,
) -> tuple[Array, Array]:
    """Fused SPH RHS: (drho (C, cap), acc (C, d, cap)), one tile pass.

    Row C of every input table is the sentinel empty cell that
    out-of-domain neighbors read. The physics terms (EOS, viscosity
    channels) come from the static ``scheme`` — the same declarative
    spec the XLA and reference backends consume (core/scheme.py).
    """
    from repro.core import cells  # deferred: kernels stay import-light

    c1, d, cap = rel.shape
    C, M = grid = force_grid(tuple(ncells))
    if c1 != C + 1:
        raise ValueError(f"tables hold {c1} cells; grid {ncells} needs {C + 1}")
    offs = cells.neighbor_cell_offsets(dim)
    offs_flat = jnp.asarray(offs.astype(np.float32).reshape(M * d))
    kernel = functools.partial(
        _force_kernel,
        hc_phys=tuple(float(x) for x in hc_phys),
        h=float(h),
        dim=int(dim),
        rel_dtype=jnp.dtype(rel.dtype),
        records_dtype=jnp.dtype(v.dtype),
        scheme=scheme,
    )
    nb = functools.partial(
        neighbor_cell, ncells=tuple(int(n) for n in ncells),
        periodic=tuple(bool(p) for p in periodic),
    )

    def cell_block(rows):
        return pl.BlockSpec((1, rows, cap), lambda c, k: (c, 0, 0))

    def nbcell_block(rows):
        return pl.BlockSpec((1, rows, cap), lambda c, k: (nb(c, k), 0, 0))

    rel_w, v_w = _words(rel), _words(v)
    m_w = _words(m).reshape(c1, 1, cap)
    inv_w = inv_rho.astype(jnp.float32).reshape(c1, 1, cap)
    drho, acc = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            cell_block(d), nbcell_block(d),  # rel i, j
            cell_block(d), nbcell_block(d),  # shift i, j
            cell_block(d), nbcell_block(d),  # v i, j
            nbcell_block(1),  # m_j
            cell_block(1), nbcell_block(1),  # 1/rho i, j
        ],
        out_specs=[cell_block(1), cell_block(d)],
        out_shape=[
            jax.ShapeDtypeStruct((C, 1, cap), jnp.float32),
            jax.ShapeDtypeStruct((C, d, cap), jnp.float32),
        ],
        interpret=interpret,
        name="rcll_force",
    )(offs_flat, rel_w, rel_w, shift, shift, v_w, v_w, m_w, inv_w, inv_w)
    return drho.reshape(C, cap), acc
