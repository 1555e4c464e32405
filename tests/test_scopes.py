"""The persistent step names its layers with ``jax.named_scope``.

The scopes are metadata: each instruction of the compiled program
carries its path in ``metadata={op_name=...}`` (a fusion its root's),
which is how a profile's device ops are attributed to the rebuild, the
cell-table pack, the force kernel and its unpack, and integration.
"""
import re

import pytest

from repro.core import cases, solver

REBUILD = {"sph.rebuild", "sph.rebuild.pack", "sph.rebuild.permute"}
STEP = {"sph.skin_check", "sph.force", "sph.integrate"}
SCOPES = {
    "pallas": REBUILD | STEP | {"sph.rebuild.mass_table",
                                "sph.cell_tables", "sph.unpack"},
    "xla": REBUILD | STEP | {"sph.rebuild.search"},
}
TOP = {"sph.rebuild", "sph.skin_check", "sph.force", "sph.integrate"}
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def op_paths(hlo_text: str) -> list[list[str]]:
    """The ``sph.`` components of every instruction's op path."""
    return [[c for c in m.group(1).split("/") if c.startswith("sph.")]
            for m in _OP_NAME.finditer(hlo_text)]


def _compiled_step(backend: str) -> str:
    case = cases.build_case("dam_break", ds=0.1, backend=backend)
    cfg, st = case.build()
    carry = solver.init_persistent(cfg, st)
    return solver.run_persistent.lower(cfg, carry, 2).compile().as_text()


@pytest.fixture(scope="module", params=sorted(SCOPES))
def compiled(request):
    return request.param, _compiled_step(request.param)


def test_every_scope_reaches_the_compiled_program(compiled):
    backend, text = compiled
    seen = {c for p in op_paths(text) for c in p}
    assert SCOPES[backend] <= seen, SCOPES[backend] - seen
    assert not seen - SCOPES[backend], seen - SCOPES[backend]


def test_scopes_nest_as_the_step_does(compiled):
    """Rebuild parts sit inside the rebuild, the force parts inside the
    force pass, and the step's layers never nest in one another."""
    _, text = compiled
    for path in op_paths(text):
        tops = [c for c in path if c in TOP]
        assert len(tops) <= 1, path
        for c in path:
            if c.startswith("sph.rebuild."):
                assert "sph.rebuild" in path, path
            if c in ("sph.cell_tables", "sph.unpack"):
                assert tops == ["sph.force"], path


def test_rebuild_scope_is_taken_at_init_too():
    """``init_persistent`` runs the same rebuild, under the same name."""
    import jax

    case = cases.build_case("dam_break", ds=0.1, backend="xla")
    cfg, st = case.build()
    text = jax.jit(solver.init_persistent, static_argnums=0).lower(
        cfg, st).as_text(debug_info=True)
    assert "sph.rebuild.pack" in text and "sph.rebuild.search" in text
