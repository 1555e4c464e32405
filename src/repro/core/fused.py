"""Fused cell-blocked WCSPH force pass (the ``backend="xla"`` hot path).

The reference step (``backend="reference"``) round-trips every pair
intermediate through HBM — ``pair_displacements`` (N, K, d), ``grad_w``
(N, K, d), the gathered pair fields (N, K)x3, one (N, K) coefficient per
RHS term — and pays 5-6 *separate* neighbor gathers (rel, cell, v, m,
rho, p/ρ²), each a strided walk over the particle arrays. Profiling
(paper Table 6) identifies exactly this pattern as bandwidth-bound.

This module evaluates the same sums with two structural changes:

**One record gather per sweep.** All per-particle inputs of a sweep are
packed into a single record row (Domínguez et al.'s float4-texture
trick, arXiv:1110.3711). A sweep then gathers ``rec[idx]`` once —
contiguous rows, cache-line friendly — instead of 5-6 scalar gathers.
Two layouts, selected by ``PrecisionPolicy.records``:

  * ``records="fp32"`` (the accuracy oracle): one fp32 row
    ``[q | v | m | 1/ρ | p/ρ²]`` where ``q = I + x/2`` is the position
    in per-axis *cell units*, built from the RCLL state by exact fp32
    arithmetic: the integer cell coordinate is exact in fp32 and the
    fp16 payload halving is exact, so ``q_i - q_j`` reproduces the
    Eq. (7) anchored decode to ~1 ulp of q — two orders of magnitude
    below the fp16 *storage* granularity that bounds both decodes.
  * ``records="fp16"``/``"bf16"`` (the half-width production layout —
    the bandwidth round): one 16-bit row ``[I | rel | v | m]`` plus a
    single separate fp32 ``1/ρ`` gather. The coordinate payload is the
    RAW RCLL storage value (fp16 rel — lossless by construction,
    exactly the paper's point that cell-relative values are fp16-safe)
    next to its integer cell anchor (see ``_records_half`` for the two
    row encodings); v is quantized to the records dtype, m is stored
    normalized by ``mass_scale`` (raw SPH masses go subnormal in fp16
    at fine ds — every pair term is linear in m, so the sweep rescales
    its outputs once); the density tier stays fp32 as the reciprocal,
    and ``p/ρ² = c0²(1/ρ − ρ0/ρ²)`` is recomputed *division-free*
    in-register through the linearized Tait EOS
    (``sph.eos_tait_por2_inv``) instead of being gathered — the flops
    are free on a bandwidth-bound sweep and 4 bytes per pair disappear.
    Everything upcasts to fp32 before any pair arithmetic
    (``q = I + rel/2`` is the SAME exact fp32 value as the fp32 layout
    stores), so the only deviation from the oracle is the v/m storage
    quantization itself. 2-D bytes per pair: 7×16-bit + 1×fp32 = 18 vs
    7×fp32 = 28.

Periodic axes wrap by minimum image on the integer cell span.

**Chunked reduction, no pair HBM round-trip.** Particles are cell-sorted
in the persistent pipeline, so a contiguous run of packed rows IS a
contiguous run of background cells — ``lax.map`` over chunks of packed
rows is the cell-blocked traversal with zero empty-slot padding (the
dense (C, cap, K) cell tables pad by cap/mean-occupancy; packed rows
visit the same cells in the same order without the padding). Each chunk
decodes pair geometry, evaluates the B-spline gradient and the
continuity/momentum terms through the SAME primitives as the reference
path (``core/bspline.py`` + ``sph.momentum_rhs_terms``), and reduces
over K immediately: peak pair-intermediate memory is O(chunk · K · d) —
cache-resident — instead of O(N · K · d) in HBM.

Physics ordering note: the solver integrates the standard explicit
WCSPH scheme (symplectic Euler, as in DualSPHysics): continuity AND
momentum are evaluated at the common current state, with the Tait
pressure of the pre-update density. That is what makes a SINGLE pass
possible — a semi-implicit rho-then-momentum ordering would force all
drho to exist (a global barrier) before any momentum term, i.e. a
second full geometry sweep.

Masking note: there is no per-pair mask at all. Invalid neighbor slots
are redirected to a dummy record row (index N) holding ``m = 0`` (with
the density field kept positive so denominators stay finite): every
pair term carries an m_j factor, and the B-spline derivative vanishes
identically beyond the support 2h and at r = 0, so invalid slots,
padding rows, the self pair, and Verlet-skin extras all contribute an
exact 0.0 without any per-term select or (N, K) boolean traffic in the
hot loop.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import bspline, rcll, sph
from repro.core import scheme as scheme_lib
from repro.core.domain import Domain
from repro.core.nnps import NeighborList
from repro.core.precision import dtype_of

Array = jnp.ndarray

#: Default rows per chunk of the mapped sweep. At K = 64, d = 2 this
#: bounds live pair intermediates to a few MB — cache-resident on CPU
#: hosts (measured best among {2048..16384} at N = 64k).
DEFAULT_CHUNK = 8192

#: Below this row count the sweep runs as ONE chunk (no lax.map): the
#: intermediates fit in cache anyway and skipping the loop + pad was
#: measurably faster at N = 8k.
SINGLE_CHUNK_MAX = 12288


def resolve_chunk(n: int, chunk: int = 0) -> int:
    """Static chunk size: ``chunk`` (0 = auto), equalized.

    Auto picks one chunk for small n (<= SINGLE_CHUNK_MAX) and
    DEFAULT_CHUNK above. The requested size fixes the number of chunks;
    the returned size is the smallest that still covers n in that many —
    e.g. n=8455 with a 4096 request becomes 3 chunks of 2819 instead of
    2x4096+263 (which would waste ~93% of the last chunk's pair work on
    padding).
    """
    if chunk <= 0:
        chunk = n if n <= SINGLE_CHUNK_MAX else DEFAULT_CHUNK
    c = max(1, min(n, chunk))
    nchunk = -(-n // c)
    return -(-n // nchunk)


def _chunk_rows(x: Array, nchunk: int, chunk: int, pad_row: Array) -> Array:
    """Pad axis 0 to nchunk*chunk with ``pad_row`` rows and reshape to
    (nchunk, chunk, ...)."""
    pad = nchunk * chunk - x.shape[0]
    if pad:
        x = jnp.concatenate(
            [x, jnp.broadcast_to(pad_row, (pad,) + x.shape[1:])], axis=0
        )
    return x.reshape((nchunk, chunk) + x.shape[1:])


def _map_chunks(body, row_args: tuple, pad_rows: tuple, n: int, chunk: int):
    """lax.map ``body`` over row-chunks of every array in ``row_args``.

    Short final chunks are padded with the caller-supplied ``pad_rows``
    (one per row arg) — the force pass pads the id rows with the dummy
    index N and the record rows with the dummy record itself, so pad
    rows evaluate all-dummy pairs: exactly zero, finite, no NaN. The
    pad is sliced off the output. Returns the per-row results, (n, ...).
    """
    chunk = resolve_chunk(n, chunk)
    nchunk = -(-n // chunk)
    if nchunk == 1:  # chunk covers all rows: no pad, no map
        return body(row_args)
    chunked = tuple(
        _chunk_rows(a, nchunk, chunk, p) for a, p in zip(row_args, pad_rows)
    )
    out = jax.lax.map(body, chunked)
    return jax.tree_util.tree_map(
        lambda o: o.reshape((nchunk * chunk,) + o.shape[2:])[:n], out
    )


def cell_coords_f32(rc: rcll.RCLLState) -> Array:
    """(N, d) fp32 positions in per-axis CELL units: q = I + x/2.

    Integer cell coordinates are exact in fp32 (grids are far below
    2^24 cells per axis) and halving the fp16 payload is exact, so q
    carries the full information of the RCLL state to ~1 ulp — the
    storage quantization of ``rel`` remains the dominant error exactly
    as in the anchored Eq. (7) decode.
    """
    return rc.cell_xy.astype(jnp.float32) + rc.rel.astype(jnp.float32) * 0.5


def _pair_geometry(domain: Domain, q_i, q_j):
    """Physical pair displacement / distance factors from cell-unit coords.

    disp_a = (q_i - q_j)_a * hc_phys_a — the same per-axis scaling as the
    Pallas tile decode (``kernels/tiling.tile_phys_disp``). The minimum
    image is applied per-axis at trace time (only periodic axes pay it),
    in select form: true pairs sit in adjacent cells, so |du| > span/2
    happens only across the periodic seam and a single +-span correction
    is exact. Returns (disp, r2, coef) with coef = (dW/dr)/r — the shared
    scalar factor of every gradient component (gw_a = coef * disp_a).
    """
    du = q_i - q_j
    cols = []
    for a, (per, ncell, hc) in enumerate(
        zip(domain.periodic, domain.ncells, domain.cell_sizes)
    ):
        da = du[..., a]
        if per:
            span = jnp.float32(ncell)
            half = jnp.float32(ncell / 2.0)
            da = da - span * (da > half).astype(jnp.float32) \
                + span * (da < -half).astype(jnp.float32)
        cols.append(da * jnp.float32(hc))
    disp = jnp.stack(cols, axis=-1)
    r2 = jnp.sum(disp * disp, axis=-1)
    # Unmasked: dW/dr vanishes beyond 2h and at r = 0, and every consumer
    # multiplies by mj (0 on invalid slots) — no select needed.
    coef = bspline.dw_over_r(jnp.sqrt(r2), domain.h, domain.dim)
    return disp, r2, coef


def _pair_rhs(
    domain: Domain,
    q_i, q_j,  # (..., d) fp32 cell-unit coords
    v_i, v_j,  # (..., d) fp32
    mj,  # (...,) fp32, 0 on invalid slots
    por2_i, por2_j,  # (...,) fp32 p/ρ²
    inv_i, inv_j,  # (...,) fp32 reciprocal densities 1/ρ
    *,
    scheme: scheme_lib.Scheme,
):
    """(drho, acc) pair sums over the trailing K axis.

    The ONE arithmetic body both record layouts decode into: the pair
    algebra folds the shared scalar coefficient first (s = coef *
    pair-coefficient, then s * disp_a / s * dv_a), an exact regrouping
    of ``sph.momentum_rhs_terms`` / ``continuity_rhs_pairs`` — same
    terms, fewer per-axis multiplies. Densities enter as reciprocals
    (see ``sph.eos_tait_por2_inv``). The physics terms themselves come
    from the static ``scheme`` (core/scheme.py): the ∇W channel
    (pressure + optional artificial viscosity) and the dv channel
    (Morris viscosity), each skipped entirely at trace time when the
    scheme disables it.
    """
    disp, r2, coef = _pair_geometry(domain, q_i, q_j)
    dv = v_i - v_j
    dv_dot_disp = jnp.sum(dv * disp, axis=-1)
    # Σ m_j (dv·∇W): ∇W_a = coef·disp_a -> fold coef out of the dot.
    drho = jnp.sum(mj * coef * dv_dot_disp, axis=-1)
    if scheme.has_delta_term:
        # continuity channel: delta-SPH diffusion (x·∇W = coef·r2)
        drho = drho + jnp.sum(
            scheme.drho_pair_term(
                mj, inv_i, inv_j, coef * r2, r2, h=domain.h
            ),
            axis=-1,
        )
    # ∇W channel: -Σ [C_ij coef] disp_a (pressure + artificial visc).
    gc = scheme.gradw_pair_coef(
        mj, por2_i, por2_j, inv_i, inv_j, dv_dot_disp, r2, h=domain.h
    ) * coef
    if scheme.has_dv_term:
        # dv channel: x·∇W = coef·r2 (already folded in the shared coef).
        vc = scheme.dv_pair_coef(
            mj, coef * r2, inv_i, inv_j, r2, h=domain.h
        )
        acc = jnp.sum(vc[..., None] * dv - gc[..., None] * disp, axis=-2)
    else:
        acc = -jnp.sum(gc[..., None] * disp, axis=-2)
    return drho, acc


def _records(rc: rcll.RCLLState, v: Array, m: Array, *extra: Array) -> Array:
    """(N+1, 2d+1+len(extra)) fp32 record rows [q | v | m | extra...].

    Row N is the dummy target of invalid neighbor slots: m = 0 zeroes
    every pair term exactly; extras default to 1.0 so denominator fields
    (rho) stay positive — callers overwrite columns that must be 0.
    """
    cols = [cell_coords_f32(rc), v.astype(jnp.float32),
            m.astype(jnp.float32)[:, None]]
    cols += [e.astype(jnp.float32)[:, None] for e in extra]
    rec = jnp.concatenate(cols, axis=1)
    dummy = jnp.zeros((1, rec.shape[1]), jnp.float32)
    dummy = dummy.at[0, 2 * v.shape[1] + 1:].set(1.0)
    return jnp.concatenate([rec, dummy], axis=0)


def _u16(x: Array) -> Array:
    return jax.lax.bitcast_convert_type(x, jnp.uint16)


#: Largest per-axis cell count whose integer coordinates are exactly
#: representable in the half-record coordinate column (fp16 integers are
#: exact through 2^11; bf16 rides in a uint16 row, exact through 2^16).
HALF_CELL_LIMIT = {jnp.dtype(jnp.float16): 1 << 11,  # sphlint: disable=dtype-literal
                   jnp.dtype(jnp.bfloat16): 1 << 16}  # sphlint: disable=dtype-literal


def mass_scale(m: Array) -> Array:
    """Normalizer for the half-record mass column: mean |m|.

    SPH masses are ~rho0·ds^dim — far below fp16's normal range once ds
    is small (ds=1e-3 in 2-D gives m~1e-6: subnormal, ~0.2-3%
    quantization; below 6e-8 it flushes to exactly 0). Every pair term
    is LINEAR in m_j, so the record stores m/scale (O(1), full fp16
    precision) and the sweep multiplies its outputs by scale once —
    resolution-independent accuracy for two O(N) multiplies.
    """
    return jnp.maximum(
        jnp.mean(jnp.abs(m)).astype(jnp.float32), jnp.float32(1e-30)
    )


def _records_half(
    rc: rcll.RCLLState, v: Array, m: Array, records_dtype
) -> Array:
    """(N+1, 3d+1) half-width record rows [I | rel | v | m].

    ``m`` must arrive pre-normalized by ``mass_scale`` (callers rescale
    the sweep outputs).

    Two encodings of the same 16-bit row, chosen by the records dtype:

      * fp16: one PLAIN fp16 array — the cell coordinate is stored as an
        fp16 VALUE (exact: grids are guarded to < 2^11 cells per axis),
        rel is the raw RCLL storage value, v/m are fp16. The sweep then
        decodes with a single upconvert and zero bitcasts — measured
        ~25% faster than a bitcast row on CPU, and TPU VPUs upconvert
        fp16 storage for free.
      * bf16: a uint16-bitcast row — rel must stay fp16 (bf16's 8-bit
        mantissa would quantize the coordinate), so the row mixes uint16
        cell values, fp16 rel bits, and bf16 v/m bits.

    Either way the decode reconstructs the IDENTICAL fp32 values. Row N
    is the all-zero dummy row (m = 0 kills every term).
    """
    d = rc.rel.shape[1]
    if jnp.dtype(records_dtype) == jnp.float16:  # sphlint: disable=dtype-literal
        rec = jnp.concatenate(
            [
                rc.cell_xy.astype(jnp.float16),  # sphlint: disable=dtype-literal
                rc.rel.astype(jnp.float16),  # sphlint: disable=dtype-literal
                v.astype(jnp.float16),  # sphlint: disable=dtype-literal
                m.astype(jnp.float16)[:, None],  # sphlint: disable=dtype-literal
            ],
            axis=1,
        )
        pad = jnp.zeros((1, 3 * d + 1), jnp.float16)  # sphlint: disable=dtype-literal
    else:
        rec = jnp.concatenate(
            [
                rc.cell_xy.astype(jnp.uint16),
                _u16(rc.rel.astype(jnp.float16)),  # sphlint: disable=dtype-literal
                _u16(v.astype(records_dtype)),
                _u16(m.astype(records_dtype))[:, None],
            ],
            axis=1,
        )
        pad = jnp.zeros((1, 3 * d + 1), jnp.uint16)
    return jnp.concatenate([rec, pad], axis=0)


def _sanitized_idx(nl: NeighborList, n: int) -> Array:
    """Neighbor ids with invalid slots redirected to the dummy row N."""
    return jnp.where(nl.mask, nl.idx, jnp.int32(n))


@partial(
    jax.jit,
    static_argnames=(
        "domain", "chunk", "mu", "c0", "rho0", "records", "scheme"
    ),
)
def force_rhs(
    domain: Domain,
    rc: rcll.RCLLState,  # packed (N, d) state
    nl: NeighborList,  # packed indexing, K-compacted
    v: Array,  # (N, d) f32
    m: Array,  # (N,) f32
    rho: Array,  # (N,) f32 current density
    *,
    c0: float | None = None,  # legacy WCSPH shorthand (see ``scheme``)
    rho0: float = 1.0,
    chunk: int = 0,
    mu: float = 0.0,
    records: str = "fp32",
    idx_dummy: Array | None = None,
    scheme: scheme_lib.Scheme | None = None,
    m_scale: Array | None = None,
) -> tuple[Array, Array]:
    """The full SPH pair RHS in ONE cell-blocked pass.

    Returns (drho (N,), acc (N, d)): the continuity sum and the momentum
    sum (∇W channel + dv channel of the ``scheme``), both at the current
    state. One record gather (plus, in the half-width layout, one fp32
    rho gather) and one geometry decode feed both sums; no (N, K)
    intermediate exists outside the live chunk. Body force and the
    wall-particle mask are applied by the caller (per-particle terms —
    nothing pairwise about them).

    ``scheme`` (static) selects the physics terms (core/scheme.py).
    The legacy ``c0``/``rho0``/``mu`` kwargs build the PR 2/3 WCSPH
    scheme (linear Tait + Morris) when ``scheme`` is omitted — existing
    callers are unchanged.

    ``records`` selects the record layout (see module docstring):
    "fp32" is the full-width accuracy oracle, "fp16"/"bf16" the
    half-width production layout. Both run the identical fp32 pair
    arithmetic (``_pair_rhs``) on their decoded slabs, so half-width
    results are bit-identical to fp32-record results whenever v and m
    are exactly representable in the records dtype.

    ``idx_dummy``: optional pre-sanitized neighbor ids (invalid -> N).
    The persistent solver computes them once per REBUILD (the list is
    static between rebuilds) instead of once per step — and the window
    search emits this layout directly.

    ``m_scale``: optional precomputed half-record mass normalizer
    (``mass_scale(m)``). Masses are constant over a run, so the
    persistent solver computes it ONCE at init instead of reducing m
    every step.
    """
    if scheme is None:
        if c0 is None:
            raise ValueError("pass either scheme= or the legacy c0=")
        scheme = scheme_lib.wcsph(c0, rho0, mu)
    rho0 = scheme.rho0
    d = domain.dim
    n = rc.rel.shape[0]
    rdt = dtype_of(records)
    half = jnp.dtype(rdt).itemsize == 2
    if half and max(domain.ncells) >= HALF_CELL_LIMIT[jnp.dtype(rdt)]:
        raise ValueError(
            "half-width records store cell coordinates in 16-bit rows "
            f"(exact through {HALF_CELL_LIMIT[jnp.dtype(rdt)]} cells per "
            f"axis for records={records!r}); grid {domain.ncells} exceeds "
            "that — use records='fp32'"
        )
    idx = _sanitized_idx(nl, n) if idx_dummy is None else idx_dummy
    # The single fp32 density field of BOTH layouts is the reciprocal:
    # p/ρ² becomes division-free per pair (sph.eos_tait_por2_inv) and
    # the viscosity ρ-product division disappears. N divisions once
    # instead of N·K per sweep.
    inv = (1.0 / rho).astype(jnp.float32)

    if not half:
        rec = _records(rc, v, m, inv, scheme.por2_inv(inv))
        rec = rec.at[n, 2 * d + 2].set(0.0)  # dummy p/ρ² (1/ρ stays 1)

        def body(args):
            idx_c, rec_i = args
            rec_j = rec[idx_c]  # ONE gather: (chunk, K, 2d+3)
            return _pair_rhs(
                domain,
                rec_i[:, None, :d], rec_j[..., :d],
                rec_i[:, None, d:2 * d], rec_j[..., d:2 * d],
                rec_j[..., 2 * d],  # m_j: 0 on the dummy row
                rec_i[:, None, 2 * d + 2], rec_j[..., 2 * d + 2],
                rec_i[:, None, 2 * d + 1], rec_j[..., 2 * d + 1],
                scheme=scheme,
            )

        pad_rows = (jnp.full((idx.shape[1],), n, jnp.int32), rec[n])
        return _map_chunks(body, (idx, rec[:n]), pad_rows, n, chunk)

    if m_scale is None:
        m_scale = mass_scale(m)
    rec16 = _records_half(rc, v, m.astype(jnp.float32) / m_scale, rdt)
    # Dummy 1/ρ = 1/ρ0: p/ρ² decodes to ~0 and denominators stay
    # positive; m = 0 on the dummy row kills every pair term regardless.
    inv32 = jnp.concatenate(
        [inv, jnp.full((1,), 1.0 / rho0, jnp.float32)]
    )

    plain = jnp.dtype(rdt) == jnp.float16  # plain-fp16 row, no bitcasts  # sphlint: disable=dtype-literal

    def decode(r16):
        """ONE upconvert of the whole gathered row -> (q, v, m) fp32.

        q = I + rel/2 is the exact fp32 value the full-width row
        stores, so past this point the body is the fp32 body.
        """
        if plain:
            r32 = r16.astype(jnp.float32)
        else:  # bf16: mixed-bits row [u16 cell | f16 rel | bf16 v m]
            r32 = jnp.concatenate(
                [
                    r16[..., :d].astype(jnp.float32),
                    jax.lax.bitcast_convert_type(
                        r16[..., d:2 * d], jnp.float16  # sphlint: disable=dtype-literal
                    ).astype(jnp.float32),
                    jax.lax.bitcast_convert_type(
                        r16[..., 2 * d:], rdt
                    ).astype(jnp.float32),
                ],
                axis=-1,
            )
        q = r32[..., :d] + r32[..., d:2 * d] * 0.5
        return q, r32[..., 2 * d:3 * d], r32[..., 3 * d]

    def body(args):
        idx_c, r16_i, inv_i = args
        r16_j = rec16[idx_c]  # ONE half-width gather: (chunk, K, 3d+1)
        inv_j = inv32[idx_c]  # the single fp32 pair field
        q_i, v_i, _ = decode(r16_i)
        q_j, v_j, m_j = decode(r16_j)
        return _pair_rhs(
            domain,
            q_i[:, None, :], q_j,
            v_i[:, None, :], v_j,
            m_j,
            scheme.por2_inv(inv_i)[:, None],
            scheme.por2_inv(inv_j),
            inv_i[:, None], inv_j,
            scheme=scheme,
        )

    pad_rows = (
        jnp.full((idx.shape[1],), n, jnp.int32), rec16[n], inv32[n]
    )
    drho, acc = _map_chunks(
        body, (idx, rec16[:n], inv32[:n]), pad_rows, n, chunk
    )
    return drho * m_scale, acc * m_scale  # undo the mass normalization
