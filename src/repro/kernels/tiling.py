"""Shared cell-pair tile math for the RCLL Pallas kernels.

The (cell, neighbour cell) kernels of this package (``nnps_pairwise``,
``sph_gradient``) walk the same structure: grid (C, M), block (c, k)
holding the self cell's (d, cap) coordinate tile and the k-th neighbor
cell's tile (scalar-prefetched ``nb_ids``), with the neighborhood offset
as the exact Eq. (7) integer anchor. These helpers are that structure's
tile math, factored once so a change to the distance arithmetic or
masking cannot diverge between kernels; the 16-bit decodes serve the
row-layout force kernel (``rcll_force``) too.

All functions are plain jnp on (d, cap)/(cap,) tiles — they trace inside
``pallas_call`` bodies and in the pure-jnp oracles (``kernels/ref.py``)
identically.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jnp.ndarray


def tile_r2_cell(
    rel_i: Array,  # (d, cap) self-cell relative coords, arithmetic dtype
    rel_j: Array,  # (d, cap) neighbor-cell relative coords
    off_k: Array,  # (d,) neighborhood offset (j_cell - i_cell), f32
    weights: tuple,  # (d,) static anisotropy weights hc_a / hc_ref
    dtype,
) -> Array:
    """Eq. (7) squared distances in reference-cell units, (cap_i, cap_j).

    The NNPS tier: arithmetic runs in ``dtype`` (fp16 paper-faithful /
    fp32 TPU-native). Static unroll over the 2-3 axes.
    """
    d, cap = rel_i.shape
    ri = rel_i.astype(dtype)
    rj = rel_j.astype(dtype)
    d2 = jnp.zeros((cap, cap), dtype)
    for a in range(d):
        du = (ri[a][:, None] - rj[a][None, :]) * dtype(0.5)
        du = (du - off_k[a].astype(dtype)) * dtype(weights[a])
        d2 = d2 + du * du
    return d2


def tile_phys_disp(
    rel_i: Array,  # (d, cap) self-cell relative coords (any float dtype)
    rel_j: Array,  # (d, cap)
    off_k: Array,  # (d,) f32
    hc_phys: tuple,  # (d,) static physical cell edges
) -> tuple[list[Array], Array]:
    """Physics-tier (fp32) pair displacement x_i - x_j per axis.

    Returns (disp [d x (cap_i, cap_j)], r2 (cap_i, cap_j)). The cell
    delta I - J is ``-off_k`` (off is j's offset from i), so the decode
    is ``((rel_i - rel_j)/2 - off) * hc`` — the tile form of
    ``rcll.decode_pair_disp``.
    """
    ri = rel_i.astype(jnp.float32)
    rj = rel_j.astype(jnp.float32)
    d = ri.shape[0]
    disp = []
    r2 = None
    for a in range(d):
        du = (ri[a][:, None] - rj[a][None, :]) * 0.5 - off_k[a]
        dx = du * hc_phys[a]
        disp.append(dx)
        r2 = dx * dx if r2 is None else r2 + dx * dx
    return disp, r2


def tile_occ_pair(occ_i: Array, occ_j: Array) -> Array:
    """(cap_i, cap_j) bool: both slots occupied."""
    return (occ_i[:, None] > 0) & (occ_j[None, :] > 0)


def tile_self_mask(cap: int) -> Array:
    """(cap, cap) bool eye via iota (TPU needs >= 2-D iota)."""
    return jax.lax.broadcasted_iota(jnp.int32, (cap, cap), 0) == \
        jax.lax.broadcasted_iota(jnp.int32, (cap, cap), 1)


def tile_pair_mask(
    occ_i: Array, occ_j: Array, is_self_cell: Array, cap: int
) -> Array:
    """Occupancy mask with the self-pair (same cell, same slot) removed."""
    return tile_occ_pair(occ_i, occ_j) & ~(is_self_cell & tile_self_mask(cap))


def bits16_to_f32(bits: Array, dtype) -> Array:
    """Decode raw 16-bit float words to f32 with integer ops.

    ``bits`` is an int16/uint16 array holding the storage words of a
    float16 or bfloat16 array (``dtype``). The result is bit-identical
    to ``x.astype(jnp.float32)`` of the original array on every one of
    the 65,536 patterns: signed zeros, subnormals (decoded as
    ``mantissa * 2^-24``, an exact fp32 normal), infinities, and NaNs
    (quieted, payload kept, as XLA's conversion does). Mosaic cannot
    load float16 vectors, so the kernels stream the words and decode
    them here in registers.
    """
    h = bits.astype(jnp.int32) & 0xFFFF
    if jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16):  # sphlint: disable=dtype-literal
        return jax.lax.bitcast_convert_type(h << 16, jnp.float32)
    if jnp.dtype(dtype) != jnp.dtype(jnp.float16):  # sphlint: disable=dtype-literal
        raise ValueError(f"no 16-bit float decode for {dtype}")
    sign = (h & 0x8000) << 16
    exp = (h >> 10) & 0x1F
    man = h & 0x3FF
    normal = sign | ((exp + 112) << 23) | (man << 13)
    quiet = jnp.where(man != 0, 0x00400000, 0)
    special = sign | 0x7F800000 | (man << 13) | quiet
    sub = jax.lax.bitcast_convert_type(
        man.astype(jnp.float32) * (2.0 ** -24), jnp.int32
    ) | sign
    out = jnp.where(exp == 0, sub, jnp.where(exp == 31, special, normal))
    return jax.lax.bitcast_convert_type(out, jnp.float32)


def decode_f32(x: Array, dtype) -> Array:
    """Kernel-side load: 16-bit words of ``dtype`` -> f32, else astype."""
    if x.dtype in (jnp.int16, jnp.uint16) and jnp.issubdtype(dtype,
                                                             jnp.floating):
        return bits16_to_f32(x, dtype)
    if x.dtype == jnp.int16:
        return x.astype(jnp.int32).astype(jnp.float32)
    return x.astype(jnp.float32)
