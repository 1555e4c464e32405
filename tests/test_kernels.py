"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret
mode (CPU validation of the TPU-target kernels)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import anchored, cells, domain as D, nnps, rcll
from repro.kernels import (flash_attention as fa, ops,
                           rcll_kv_attention as rk, ref as kref)


def _particle_setup(n, dim=2, seed=0, dtype=jnp.float16, cap=16):
    rng = np.random.default_rng(seed)
    ds = (1.0 / n) ** (1.0 / dim)
    dom = (D.unit_square(h=1.2 * ds) if dim == 2
           else D.unit_cube(h=1.2 * ds))
    x = rng.uniform(0, 1, (n, dim))
    xn = dom.normalize(jnp.asarray(x))
    st = rcll.init_state(dom, xn, dtype=dtype)
    b = cells.bin_by_cell_id(dom, dom.flat_cell_id(st.cell_xy),
                             st.cell_xy, cap)
    assert int(b.overflow) == 0
    return dom, x, st, b


@pytest.mark.parametrize("n,dim,cap", [(500, 2, 16), (1500, 2, 24),
                                       (800, 3, 32), (200, 2, 128)])
@pytest.mark.parametrize("dtype", [jnp.float16, jnp.bfloat16, jnp.float32])
def test_nnps_adjacency_kernel_sweep(n, dim, cap, dtype):
    dom, x, st, b = _particle_setup(n, dim, dtype=dtype, cap=cap)
    adj_k, cnt_k = ops.rcll_adjacency_cells(dom, b, st.rel, interpret=True)
    rel_t, occ, _ = ops.pack_cells(b, st.rel)
    nb = jnp.asarray(ops.cell_neighbor_ids(dom))
    nb = jnp.concatenate(
        [nb, jnp.full((1, nb.shape[1]), nb.shape[0], nb.dtype)], axis=0)
    adj_r, _ = kref.ref_rcll_adjacency(
        rel_t, occ, nb, cells.neighbor_cell_offsets(dim),
        np.asarray(dom.cell_weights), nnps.rcll_radius_cell_units(dom))
    np.testing.assert_allclose(adj_k, adj_r)
    # counts agree with the core (non-kernel) search
    nl = nnps.rcll_neighbors(dom, st.rel, st.cell_xy, dtype=dtype,
                             compute_dtype=jnp.float32, k=96, binning=b)
    np.testing.assert_array_equal(
        np.asarray(cnt_k).astype(np.int32), np.asarray(nl.count))


@pytest.mark.parametrize("n,dim", [(600, 2), (400, 3)])
@pytest.mark.parametrize("nnps_dtype", [jnp.float16, jnp.float32])
def test_sph_gradient_kernel_sweep(n, dim, nnps_dtype):
    dom, x, st, b = _particle_setup(n, dim, cap=40)
    f = jnp.asarray(x[:, 0] ** 3, jnp.float32)
    g_k = ops.rcll_gradient_particles(dom, b, st.rel, f,
                                      nnps_dtype=nnps_dtype,
                                      interpret=True)
    rel_t, occ, (f_t,) = ops.pack_cells(b, st.rel, f)
    nb = jnp.asarray(ops.cell_neighbor_ids(dom))
    nb = jnp.concatenate(
        [nb, jnp.full((1, nb.shape[1]), nb.shape[0], nb.dtype)], axis=0)
    num, den = kref.ref_rcll_gradient(
        rel_t, f_t, occ, nb, cells.neighbor_cell_offsets(dim),
        np.asarray(dom.cell_weights), nnps.rcll_radius_cell_units(dom),
        np.asarray(dom.cell_sizes), dom.h, dim, compute_dtype=nnps_dtype)
    den = jnp.where(jnp.abs(den) > 1e-12,
                    den, jnp.where(den >= 0, 1e-12, -1e-12))
    g_r = ops.unpack_per_particle((num / den).transpose(0, 2, 1), b)
    np.testing.assert_allclose(g_k, g_r, rtol=2e-4, atol=2e-4)
    # physics: interior gradient approximates 3x^2 (skip if the domain
    # is too coarse to have interior particles, e.g. small 3-D sets)
    interior = (np.abs(x - 0.5) < 0.5 - 2.5 * dom.h).all(axis=1)
    if interior.sum() >= 10:
        want = 3 * x[interior, 0] ** 2
        got = np.asarray(g_k)[interior, 0]
        assert np.sqrt(np.mean((got - want) ** 2)) < 0.15


@pytest.mark.parametrize("B,H,Hkv,L,Dh,bq,bk", [
    (1, 2, 2, 128, 32, 64, 64),
    (2, 4, 2, 256, 64, 128, 64),
    (1, 8, 1, 512, 64, 128, 128),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("in_dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, H, Hkv, L, Dh, bq, bk, causal, in_dtype):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, H, L, Dh)), in_dtype)
    k = jnp.asarray(rng.normal(size=(B, Hkv, L, Dh)), in_dtype)
    v = jnp.asarray(rng.normal(size=(B, Hkv, L, Dh)), in_dtype)
    out = fa.flash_attention(q, k, v, causal=causal, block_q=bq,
                             block_k=bk, interpret=True)
    ref = kref.ref_attention(q, k, v, causal=causal)
    tol = 2e-5 if in_dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out, ref, rtol=tol, atol=tol)


@pytest.mark.parametrize("B,H,Hkv,Dh,nblk,blk", [
    (1, 4, 4, 32, 2, 128),
    (2, 8, 2, 64, 4, 128),
    (3, 6, 2, 128, 3, 256),
])
@pytest.mark.parametrize("resid_dtype", [jnp.float16, jnp.int8])
def test_rcll_kv_decode_sweep(B, H, Hkv, Dh, nblk, blk, resid_dtype):
    rng = np.random.default_rng(1)
    L = nblk * blk
    q = jnp.asarray(rng.normal(size=(B, H, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Hkv, L, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Hkv, L, Dh)), jnp.float32)
    length = jnp.asarray(rng.integers(1, L + 1, (B,)), jnp.int32)
    ek = anchored.encode(k, block=blk, axis=2, dtype=resid_dtype)
    ev = anchored.encode(v, block=blk, axis=2, dtype=resid_dtype)
    out = rk.rcll_kv_decode(q, ek.residual, ek.anchor, ek.scale,
                            ev.residual, ev.anchor, ev.scale, length,
                            interpret=True)
    ref = kref.ref_rcll_kv_decode(q, ek.residual, ek.anchor, ek.scale,
                                  ev.residual, ev.anchor, ev.scale, length)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)
    # quantization keeps attention output close to exact
    exact = kref.ref_attention(q[:, :, None], k, v, causal=False)[:, :, 0]
    # compare only rows with full length (mask semantics differ otherwise)
    full = np.asarray(length) == L
    if full.any():
        err = np.abs(np.asarray(out)[full] - np.asarray(exact)[full]).max()
        assert err < (0.01 if resid_dtype == jnp.int8 else 0.001)


def test_fused_gradient_matches_two_pass():
    """Fusion argument (Table 6): fused kernel == adjacency-then-gradient
    two-pass reference on the same tables."""
    dom, x, st, b = _particle_setup(700, 2, cap=24)
    f = jnp.asarray(np.sin(3 * x[:, 0]) + x[:, 1], jnp.float32)
    g_fused = ops.rcll_gradient_particles(dom, b, st.rel, f,
                                          nnps_dtype=jnp.float16,
                                          interpret=True)
    # two-pass: neighbor list from core search + pure-jnp A5 gradient
    from repro.core import sph
    nl = nnps.rcll_neighbors(dom, st.rel, st.cell_xy, dtype=jnp.float16,
                             k=64, binning=b)
    disp, r = rcll.pair_displacements(dom, st, nl)
    g_two = sph.gradient_normalized_pairs(f, disp, r, nl.idx, nl.mask,
                                          dom.h, 2)
    np.testing.assert_allclose(g_fused, g_two, rtol=2e-3, atol=2e-3)


# --------------------------------------------------------------------------
# One-sweep cell-pack kernel vs its jnp oracle
# --------------------------------------------------------------------------
@pytest.mark.parametrize("n,dim,seed,periodic", [
    (500, 2, 0, (False, False)), (300, 3, 1, (False, False, False)),
    (500, 2, 2, (True, False)), (500, 2, 3, (False, True)),
])
def test_cell_pack_kernel_matches_ref(n, dim, seed, periodic):
    """The windowed slab pack equals the per-field id-table gather
    (``cells.to_cell_major``) laid out on the force kernel's rows: every
    cell of the ghost-padded grid (a periodic ghost copies the opposite
    edge, a wall ghost is empty), fields leading, slots on the sublanes,
    cells on the lanes; slots past cap filled as empty, lanes past the
    row zero."""
    from repro.kernels import rcll_force

    rng = np.random.default_rng(seed)
    ds = (1.0 / n) ** (1.0 / dim)
    dom = D.Domain(lo=(0.0,) * dim, hi=(1.0,) * dim, h=1.2 * ds,
                   periodic=periodic)
    x = rng.uniform(0, 1, (n, dim))
    st = rcll.init_state(dom, dom.normalize(jnp.asarray(x)), jnp.float16)
    cap = cells.default_capacity(dom, n, safety=6.0)
    ps = rcll.pack_state(dom, st, cap)
    b = ps.packing.binning
    rows16 = jax.lax.bitcast_convert_type(ps.rc.rel, jnp.uint16)
    rows32 = jnp.asarray(rng.normal(size=(n, 2)), jnp.float32)
    fill32 = jnp.asarray([1.0, 0.0], jnp.float32)
    out_k = ops.cell_tables(rows16, rows32, b.counts, fill32, cap=cap,
                            ncells=dom.ncells, periodic=periodic)

    ids = rcll_force.padded_cell_ids(dom.ncells, periodic)
    cap8 = rcll_force.slot_rows(cap)
    width = rcll_force.lane_width(dom.ncells[-1])
    for got, rows, fill in ((out_k[0], rows16, np.zeros(dim)),
                            (out_k[1], rows32, np.asarray(fill32))):
        f = rows.shape[1]
        per_cell = np.stack([
            np.asarray(cells.to_cell_major(b, rows[:, j], fill=fill[j]))
            for j in range(f)], axis=-1)  # (C, cap, F)
        per_cell = np.concatenate(
            [per_cell, np.broadcast_to(fill, (1, cap, f))])  # empty cell
        want = np.zeros(ids.shape[:-1] + (f, cap8, width), per_cell.dtype)
        want[..., :ids.shape[-1]] = fill[:, None, None]
        # (..., X, cap, F) -> (..., F, cap, X)
        want[..., :cap, :ids.shape[-1]] = np.moveaxis(
            per_cell[ids], (-3, -1), (-1, -3))
        np.testing.assert_array_equal(np.asarray(got), want)


# --------------------------------------------------------------------------
# In-kernel 16-bit float decode (the force kernel's fp16 loads)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float16, jnp.bfloat16])
def test_bits16_decode_bit_exact_all_patterns(dtype):
    """Every one of the 65,536 words decodes inside a Pallas kernel to
    exactly the bits of ``astype(float32)``: signed zeros, subnormals,
    infinities and NaNs included."""
    from jax.experimental import pallas as pl

    from repro.kernels import tiling

    words = np.arange(65536, dtype=np.uint16).view(np.int16).reshape(
        512, 128)

    def kernel(w_ref, o_ref):
        o_ref[...] = tiling.bits16_to_f32(w_ref[...], dtype)

    got = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((512, 128), jnp.float32),
        interpret=True,
    )(jnp.asarray(words))
    want = jnp.asarray(words).view(dtype).astype(jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(got).view(np.uint32), np.asarray(want).view(np.uint32))
