"""``ops.force_grid_work``: the force kernel's (self cell, neighbour
offset) pairs, launched and useful, against a brute-force count."""
import itertools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cells
from repro.core.domain import Domain
from repro.kernels import ops, rcll_force


def _domain(hi, periodic) -> Domain:
    d = len(hi)
    return Domain(lo=(0.0,) * d, hi=tuple(hi), h=0.1, periodic=periodic)


def _brute(ncells, periodic, counts) -> tuple[int, int]:
    """Every (cell, neighbour offset) pair the kernel evaluates: each
    lane of each row, ghost cells and the padding to 128 lanes included;
    useful where both cells of a pair hold a particle (a neighbour off a
    wall is an empty ghost)."""
    lanes = -(-(ncells[-1] + 2) // 128) * 128
    launched = int(np.prod(ncells[:-1])) * lanes * 3 ** len(ncells)
    useful = 0
    grid = np.asarray(counts).reshape(ncells)
    for c in itertools.product(*(range(n) for n in ncells)):
        for off in itertools.product((-1, 0, 1), repeat=len(ncells)):
            nb = []
            for x, o, n, p in zip(c, off, ncells, periodic):
                y = x + o
                if p:
                    y %= n
                elif not 0 <= y < n:
                    break
                nb.append(y)
            else:
                useful += int(grid[c] > 0 and grid[tuple(nb)] > 0)
    return launched, useful


def _work(dom, counts):
    binning = types.SimpleNamespace(counts=jnp.asarray(counts, jnp.int32))
    launched, useful = ops.force_grid_work(dom, binning)
    return launched, int(useful)


GRIDS = [
    ((1.0, 0.8), (False, False)),
    ((1.0, 0.8), (True, False)),
    ((1.0, 0.8), (True, True)),
    ((0.8, 0.6, 1.0), (False, False, False)),
    ((0.8, 0.6, 1.0), (True, False, True)),
]


@pytest.mark.parametrize("hi,periodic", GRIDS)
def test_random_occupancy_matches_brute_force(hi, periodic):
    dom = _domain(hi, periodic)
    C = dom.ncells_total
    rng = np.random.default_rng(C)
    counts = rng.integers(0, 3, C) * (rng.random(C) < 0.6)
    assert 0 < np.count_nonzero(counts) < C
    assert _work(dom, counts) == _brute(dom.ncells, periodic, counts)


@pytest.mark.parametrize("hi,periodic", GRIDS)
def test_full_grid_loses_only_the_sentinel_steps(hi, periodic):
    dom = _domain(hi, periodic)
    C, d = dom.ncells_total, dom.dim
    launched, useful = _work(dom, np.ones(C, np.int32))
    rows, lanes = C // dom.ncells[-1], 128  # rows of under 126 cells
    assert launched == rows * lanes * 3 ** d
    # pairs whose neighbour lies off a wall: per axis, the cells at a
    # wall see one offset out of three off the grid
    inside = 1
    for n, p in zip(dom.ncells, periodic):
        inside *= 3 * n if p else 3 * n - 2
    assert useful == inside
    assert (useful == C * 3 ** d) == all(periodic)


def test_one_empty_cell():
    dom = _domain((1.0, 0.8), (True, True))
    C = dom.ncells_total
    counts = np.ones(C, np.int32)
    counts[7] = 0
    launched, useful = _work(dom, counts)
    # its own 9 pairs and the 8 pairs of its neighbours that read it
    assert C * 9 - useful == 9 + 8
    assert (launched, useful) == _brute(dom.ncells, (True, True), counts)


def test_empty_grid_does_no_useful_work():
    dom = _domain((1.0, 0.8), (False, False))
    assert _work(dom, np.zeros(dom.ncells_total, np.int32))[1] == 0


def test_binning_of_particles():
    """The count reads a real binning's occupancy."""
    dom = _domain((1.0, 0.8), (True, False))
    xn = jax.random.uniform(jax.random.key(3), (40, 2), minval=-1.0,
                            maxval=1.0)
    xn = xn * jnp.asarray([1.0, 0.3])  # leaves the upper cells empty
    binning = cells.bin_particles(dom, xn, 16)
    counts = np.asarray(binning.counts)
    assert 0 < np.count_nonzero(counts) < dom.ncells_total
    launched, useful = ops.force_grid_work(dom, binning)
    assert (launched, int(useful)) == _brute(dom.ncells, (True, False),
                                             counts)


@pytest.mark.parametrize("hi,periodic", [GRIDS[1], GRIDS[4]])
def test_launched_is_the_grid_the_kernel_is_launched_over(hi, periodic):
    """``launched`` = grid steps × cells per step × 3^d, read off the
    ``pallas_call`` the wrapper emits: its grid, and the lanes of its
    output block (one cell per lane)."""
    from repro.core import scheme

    dom = _domain(hi, periodic)
    d, cap = dom.dim, 8
    padded = tuple(n + 2 for n in dom.ncells[:-1])
    lanes = rcll_force.lane_width(dom.ncells[-1])

    def table(rows, dtype):
        return jnp.zeros(padded + (rows, cap, lanes), dtype)

    jaxpr = jax.make_jaxpr(lambda *a: rcll_force.rcll_force(
        *a, hc_phys=dom.cell_sizes, h=dom.h, dim=d,
        rel_dtype=jnp.float16, records_dtype=jnp.float16,
        scheme=scheme.wcsph(1.0, 1.0, 0.1), cap=cap, interpret=True,
    ))(table(3 * d, jnp.uint16), table(1, jnp.float32),
       table(1, jnp.float16))
    calls = [e.params["grid_mapping"] for e in _eqns(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    grid = calls[0].grid
    assert grid == rcll_force.force_grid(dom.ncells)
    out_block = calls[0].block_mappings[-1].block_aval.shape
    per_step = out_block[-1]  # lanes; the leading dims are one row
    assert out_block[:d - 1] == (1,) * (d - 1)
    counts = np.ones(dom.ncells_total, np.int32)
    assert _work(dom, counts)[0] == int(np.prod(grid)) * per_step * 3 ** d


def _eqns(jaxpr):
    """Every equation, those of nested jits included."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            if hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
                yield from _eqns(v.jaxpr)
