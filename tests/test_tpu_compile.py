"""The main path's kernels compile for a TPU v5e at production shapes.

Ahead-of-time compiles against a described ``v5e:2x2`` topology (no
chip attached) with ``interpret=False``: the TPU compiler refuses here
what interpret mode cannot see — block shapes off the (8, 128) tiling,
vector loads of types Mosaic has no layout for, scalar-prefetch tables
larger than SMEM — and ``memory_analysis`` shows whether the program
fits the chip's 16 GiB. Shapes are those of ``cases.build_case(...)``
at the stated particle counts.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every test worker
imports this file.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import cases
from repro.kernels import ops, rcll_force

HBM_BYTES = 16 * 2**30  # TPU v5e

# (case, target n, particles N, grid, cap) from build_case(...).build()
SHAPES = {
    "dam_break-1M": ("dam_break", 1_000_000, 1_019_228, (1182, 768), 18),
    "dam_break-64k": ("dam_break", 64_000, 69_038, (301, 196), 18),
    "taylor_green-1M": ("taylor_green", 1_000_000, 1_000_000, (416, 416),
                        20),
}
RECORDS = {"fp16": jnp.float16, "bf16": jnp.bfloat16, "fp32": jnp.float32}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    """Described-topology compiles cannot be read back without a chip:
    keep them out of the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _fits(compiled) -> int:
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert total < HBM_BYTES, ma
    return total


def _case(key):
    name, n, _, grid, cap = SHAPES[key]
    case = cases.build_case(name, ds=cases.resolve_ds(name, n))
    dom = case.domain()
    assert tuple(dom.ncells) == grid, dom.ncells
    return case, dom, cap


def _poiseuille_1m():
    """The 1M Poiseuille channel with a Verlet skin of half the search
    radius (cells 1.5 radii wide): Morris, Fox & Zhu's channel at ds =
    1e-3, periodic along the slow axis."""
    from repro.core import scheme
    from repro.core.domain import Domain

    dom = Domain(lo=(0.0, -0.003), hi=(1.0, 1.003), h=0.0012,
                 cell_factor=1.5, periodic=(True, False))
    assert tuple(dom.ncells) == (277, 280), dom.ncells
    return dom, scheme.wcsph(0.25, 1.0, 1.0), 41


def _wide_rows():
    """A dam break of rows 2400 cells long: its kernel needs more scoped
    VMEM than the compiler's default, and asks for it."""
    from repro.core.domain import Domain

    case, dom, cap = _case("dam_break-1M")
    hi = (dom.hi[0], dom.lo[1] + 2400 * dom.cell_sizes[1])
    wide = Domain(lo=dom.lo, hi=hi, h=dom.h, cell_factor=dom.cell_factor)
    assert wide.ncells[1] == 2400, wide.ncells
    return wide, case.scheme(), cap


@pytest.mark.parametrize("key,records", [
    ("dam_break-1M", "fp16"), ("dam_break-1M", "bf16"),
    ("dam_break-1M", "fp32"), ("dam_break-64k", "fp16"),
    ("taylor_green-1M", "fp16"), ("poiseuille-1M", "fp16"),
    ("wide-rows", "fp16"),
])
def test_force_kernel_compiles_for_v5e(one_chip, key, records):
    if key == "poiseuille-1M":
        dom, sch, cap = _poiseuille_1m()
    elif key == "wide-rows":
        dom, sch, cap = _wide_rows()
    else:
        case, dom, cap = _case(key)
        sch = case.scheme()
    d = dom.dim
    rdt = RECORDS[records]
    padded = tuple(n + 2 for n in dom.ncells[:-1])
    tail = (rcll_force.slot_rows(cap), rcll_force.lane_width(dom.ncells[-1]))
    f16, f32 = rcll_force.slab_fields(jnp.float16, rdt)
    width = {"inv": 1}

    def spec(rows, dtype):
        return jax.ShapeDtypeStruct(padded + (rows,) + tail, dtype,
                                    sharding=one_chip)

    traced = rcll_force.rcll_force.trace(
        spec(sum(width.get(f, d) for f in f16), jnp.uint16),
        spec(sum(width.get(f, d) for f in f32), jnp.float32),
        spec(1, rdt),  # m
        hc_phys=tuple(dom.cell_sizes), h=dom.h, dim=d,
        rel_dtype=jnp.float16, records_dtype=rdt, scheme=sch, cap=cap,
        interpret=False,
    )
    grids = [e.params["grid_mapping"].grid for e in traced.jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    assert len(grids) == 1
    assert np.prod(grids[0]) <= dom.ncells_total / 100, grids
    compiled = traced.lower().compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


@pytest.mark.parametrize("key", ["dam_break-1M", "dam_break-64k"])
def test_cell_pack_compiles_for_v5e(one_chip, key):
    _, n, N, grid, cap = SHAPES[key]
    d = len(grid)
    C = int(np.prod(grid))

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pack = jax.jit(functools.partial(
        ops.cell_tables, cap=cap, ncells=grid, periodic=(False,) * d))
    compiled = pack.lower(
        spec((N, 3 * d), jnp.uint16),  # [rel | shift | v] 16-bit slab
        spec((N, 1), jnp.float32),  # [1/rho] fp32 slab
        spec((C,), jnp.int32),
        spec((1,), jnp.float32),
    ).compile()
    _fits(compiled)


_MOVES = ("gather", "dynamic-slice", "dynamic-update-slice", "scatter",
          "while", "reduce-window")


def _computations(hlo_text: str) -> dict:
    """{computation: [(opcode, op path, called computation)]}."""
    comps, cur = {}, None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            cur = comps.setdefault(line.split("(")[0].split()[-1]
                                   .lstrip("%"), [])
        elif cur is not None and " = " in line:
            rhs = line.split(" = ", 1)[1]
            op = re.search(r"\s([a-z][a-z\-]*)\(", " " + rhs)
            path = re.search(r'op_name="([^"]*)"', rhs)
            calls = re.search(r"calls=%?([\w.\-]+)", rhs)
            cur.append((op.group(1) if op else "", path.group(1) if path
                        else "", calls.group(1) if calls else None))
    return comps


def test_step_scopes_survive_the_tpu_compile(one_chip, monkeypatch):
    """``run_persistent`` compiled for the v5e at a small dam break: the
    force kernel sits under ``sph.force``, and no gather or window loop
    of the cell-table pack or of the unpack is fused into an op of
    ``sph.integrate`` (a fusion counts under its root's scope; only the
    unpack's elementwise mass rescale joins the density update). The
    CPU compiler fuses the unpack's gather into the update; the chip's
    does not, and this is the program the chip runs."""
    from repro.core import solver

    case = cases.build_case("dam_break", ds=0.1, backend="pallas")
    cfg, st = case.build()
    carry = solver.init_persistent(cfg, st)
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    specs = jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), carry)
    try:
        text = solver.run_persistent.lower(cfg, specs, 2).compile().as_text()
    finally:
        # the trace holds the compiled kernel (interpret=False): keep it
        # from a later CPU lowering of the same step in this process
        jax.clear_caches()
    comps = _computations(text)
    kernel = [p for ins in comps.values() for op, p, _ in ins
              if op == "custom-call" and p.endswith("rcll_force/pallas_call")]
    assert kernel and all("/sph.force/" in p for p in kernel), kernel
    for ins in comps.values():
        for op, path, called in ins:
            if op != "fusion" or "sph.integrate" not in path.split("/"):
                continue
            for inner_op, inner, _ in comps[called]:
                parts = inner.split("/")
                if "sph.cell_tables" in parts or "sph.unpack" in parts:
                    assert inner_op not in _MOVES, (path, inner_op, inner)
