"""sphlint Layer B: compile the production programs, audit the jaxprs.

What the AST layer cannot see, the jaxpr can: this module builds the
persistent step and rebuild programs for registered cases across the
force backends and checks the invariants the mixed-precision design
actually rests on:

* **fp16 confinement** — every equation producing an fp16/bf16 value is
  a STRUCTURAL op (gather/bitcast/convert/reshape/…): half precision is
  a storage format here, never an arithmetic one. An `add` or
  `dot_general` with an f16 output means a computation silently dropped
  to half precision (the accumulate-in-fp32 rule broke).
* **no host callbacks** — no debug/io callback primitives anywhere in
  the step program (the PR 6 in-scan overflow-callback incident).
* **donation** — ``run_persistent``'s declared ``donate_argnums``
  buffers actually donate: compiling must not emit "donated buffer was
  not usable" warnings.
* **no carry self-aliasing** — no two leaves of the donated carry share
  a device buffer (the PR 3 ``st.rc.cell_xy``/``binning.cell_xy``
  incident class, checked by pointer this time).

The report includes a per-program dtype census (equation-output counts
by dtype) so precision drift between PRs is visible as a diff.
"""
from __future__ import annotations

import collections
import json
import sys
import warnings
from pathlib import Path

#: Primitives allowed to OUTPUT an fp16/bf16 value: data movement,
#: layout, and format conversion — no arithmetic. Container primitives
#: (scan/cond/pjit/…) are allowed because their inner equations are
#: audited individually by the recursive walk.
STRUCTURAL_F16_PRIMS = frozenset({
    "gather", "bitcast_convert_type", "convert_element_type",
    "concatenate", "reshape", "slice", "dynamic_slice",
    "dynamic_update_slice", "broadcast_in_dim", "transpose", "squeeze",
    "expand_dims", "pad", "rev", "select_n", "scatter", "copy",
    "stop_gradient", "device_put", "iota",
    # Pallas ref load/store (pl.load / ref[...] / pl.store) — memory
    # movement. `addupdate` is deliberately NOT here: an f16 in-ref
    # accumulate would break the fp32-accumulator rule.
    "get", "swap", "masked_load", "masked_store",
    # containers — audited by recursing into their sub-jaxprs
    "scan", "while", "cond", "pjit", "closed_call", "core_call",
    "custom_jvp_call", "custom_vjp_call", "remat", "remat2",
    "checkpoint", "pallas_call", "custom_jvp_call_jaxpr",
})

CALLBACK_PRIMS = ("callback", "debug_print", "outside_call", "infeed",
                  "outfeed")

HALF_DTYPES = ("float16", "bfloat16")


# --------------------------------------------------------------------------
# jaxpr walking
# --------------------------------------------------------------------------
def _sub_jaxprs(value):
    """Yield every Jaxpr nested in an eqn param value."""
    # jax.extend is a submodule: import it (``hasattr(jax, "extend")``
    # held only once something else had imported it)
    from jax.extend import core

    ClosedJaxpr = core.ClosedJaxpr
    Jaxpr = core.Jaxpr
    if isinstance(value, ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _sub_jaxprs(v)


def iter_eqns(jaxpr, depth=0):
    """All equations of ``jaxpr`` and every nested sub-jaxpr.

    Yields (eqn, depth); depth > 0 means inside at least one container
    primitive (scan body, cond branch, pjit call, pallas kernel, …).
    """
    for eqn in jaxpr.eqns:
        yield eqn, depth
        for value in eqn.params.values():
            for sub in _sub_jaxprs(value):
                yield from iter_eqns(sub, depth + 1)


def _out_dtypes(eqn):
    out = []
    for var in eqn.outvars:
        aval = getattr(var, "aval", None)
        dt = getattr(aval, "dtype", None)
        if dt is not None:
            out.append(str(dt))
    return out


def audit_jaxpr(closed_jaxpr, program: str) -> dict:
    """Audit one program: returns census + violation lists."""
    census: collections.Counter = collections.Counter()
    f16_viol: list[str] = []
    callback_viol: list[str] = []
    for eqn, depth in iter_eqns(closed_jaxpr.jaxpr):
        prim = eqn.primitive.name
        dtypes = _out_dtypes(eqn)
        for dt in dtypes:
            census[dt] += 1
        if any(dt in HALF_DTYPES for dt in dtypes) and \
                prim not in STRUCTURAL_F16_PRIMS:
            f16_viol.append(
                f"{program}: `{prim}` outputs {dtypes} at depth {depth} "
                "— arithmetic in half precision"
            )
        if any(tag in prim for tag in CALLBACK_PRIMS):
            callback_viol.append(
                f"{program}: host-callback primitive `{prim}` at "
                f"depth {depth}"
            )
    return {
        "program": program,
        "census": dict(sorted(census.items())),
        "f16_violations": f16_viol,
        "callback_violations": callback_viol,
    }


# --------------------------------------------------------------------------
# program construction
# --------------------------------------------------------------------------
def _build(case_name: str, backend: str, n: int):
    from repro.core import cases as cases_lib

    ds = cases_lib.resolve_ds(case_name, n)
    case = cases_lib.build_case(case_name, ds=ds, backend=backend)
    return case.build()


def _audit_case(case_name: str, backend: str, n: int, nsteps: int = 4):
    """Audit step + rebuild programs for one (case, backend) pair."""
    import jax

    from repro.core import solver

    cfg, state = _build(case_name, backend, n)
    carry = solver.init_persistent(cfg, state)

    results = []
    label = f"{case_name}/{backend}"

    step_jaxpr = jax.make_jaxpr(
        lambda c: solver.run_persistent(cfg, c, nsteps)
    )(carry)
    results.append(audit_jaxpr(step_jaxpr, f"{label}/step"))

    rebuild_jaxpr = jax.make_jaxpr(
        lambda c: solver._rebuild(cfg, c)
    )(carry)
    results.append(audit_jaxpr(rebuild_jaxpr, f"{label}/rebuild"))

    donation = _audit_donation(cfg, carry, nsteps, label)
    alias = _audit_carry_aliasing(carry, label)
    return results, donation, alias


def _audit_donation(cfg, carry, nsteps: int, label: str) -> dict:
    """Compile run_persistent and catch 'donated buffer unused' warnings."""
    from repro.core import solver

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        solver.run_persistent.lower(cfg, carry, nsteps).compile()
    msgs = [str(w.message) for w in caught
            if "donat" in str(w.message).lower()]
    return {
        "program": f"{label}/step",
        "donation_warnings": msgs,
    }


def _audit_carry_aliasing(carry, label: str) -> dict:
    """No two leaves of the donated carry may share a device buffer."""
    import jax

    leaves, _ = jax.tree_util.tree_flatten_with_path(carry)
    by_ptr: dict[int, list[str]] = {}
    for path, leaf in leaves:
        try:
            ptr = leaf.unsafe_buffer_pointer()
        except Exception:
            continue  # committed-elsewhere / non-array leaf
        by_ptr.setdefault(ptr, []).append(jax.tree_util.keystr(path))
    aliases = [paths for paths in by_ptr.values() if len(paths) > 1]
    return {
        "program": f"{label}/carry",
        "aliased_leaves": aliases,
    }


# --------------------------------------------------------------------------
# entry point
# --------------------------------------------------------------------------
def run_trace_audit(backends, cases, n=300, report_path: Path | None = None,
                    verbose: bool = False) -> int:
    print(f"sphlint trace: cases={cases} backends={backends} n~{n}",
          flush=True)
    report = {"cases": cases, "backends": backends, "n": n, "programs": [],
              "donation": [], "aliasing": []}
    failures: list[str] = []
    for case_name in cases:
        for backend in backends:
            label = f"{case_name}/{backend}"
            try:
                results, donation, alias = _audit_case(
                    case_name, backend, n)
            except Exception as e:  # surface, keep auditing the rest
                failures.append(f"{label}: audit crashed: {e!r}")
                print(f"  {label}: CRASH {e!r}", flush=True)
                continue
            report["programs"].extend(results)
            report["donation"].append(donation)
            report["aliasing"].append(alias)
            bad = []
            for r in results:
                bad += r["f16_violations"] + r["callback_violations"]
            bad += [f"{donation['program']}: {m}"
                    for m in donation["donation_warnings"]]
            bad += [f"{alias['program']}: leaves share one buffer: {p}"
                    for p in alias["aliased_leaves"]]
            failures.extend(bad)
            status = "FAIL" if bad else "ok"
            print(f"  {label}: {status} "
                  f"({len(results)} programs audited)", flush=True)
            if verbose:
                for r in results:
                    print(f"    {r['program']} dtype census: "
                          f"{r['census']}")
    if report_path is not None:
        report["failures"] = failures
        report_path.write_text(json.dumps(report, indent=2) + "\n")
        print(f"sphlint trace: report -> {report_path}")
    if failures:
        print(f"sphlint trace: {len(failures)} violation(s):",
              file=sys.stderr)
        for f in failures:
            print(f"  {f}", file=sys.stderr)
        return 1
    print("sphlint trace: all invariants hold "
          f"({len(report['programs'])} programs)")
    return 0
