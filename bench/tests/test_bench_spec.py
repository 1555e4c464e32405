"""The benchmark's files: found by name, named as the contract allows."""
from __future__ import annotations

import os

import numpy as np
import pytest

from bench import initial, program, spec
from bench.tests import small

BENCH = spec.benchmark()
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
CONFIGS = [c["name"] for c in BENCH["configs"]]
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


def test_top_level_keys_and_paths():
    assert set(BENCH) == TOP_KEYS
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


@pytest.mark.parametrize("name", WORKLOADS)
def test_workload_names_existing_config_and_mode(name):
    entry = next(w for w in BENCH["workloads"] if w["name"] == name)
    work = spec.workload(name)
    assert work["config"] == entry["config"]
    assert work["traffic"] == entry["traffic"]
    assert entry["config"] in CONFIGS
    spec.config(entry["config"])
    assert hasattr(spec.mode(work["mode"]), "run")
    assert entry["chips"] in (1, 4)
    assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    assert work["why"] == entry["why"]
    assert set(work["limits"]) <= {"v_gap", "rho_gap"} and work["limits"]


def test_every_name_and_unit_uses_allowed_characters():
    names = [w["name"] for w in BENCH["workloads"]]
    names += [w["config"] for w in BENCH["workloads"]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    names += [c["name"] for c in BENCH["configs"]]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names += [m["name"] for m in metrics]
    for n in names:
        assert spec.NAME.match(n), n
    for m in metrics:
        assert spec.UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    all_names = ([w["name"] for w in BENCH["workloads"]]
                 + [c["name"] for c in BENCH["configs"]]
                 + [m["name"] for m in metrics])
    assert len(all_names) == len(set(all_names))


def test_per_layer_metrics_move_particle_steps_and_have_readers():
    for m in BENCH["per_layer"]:
        assert m["moves"] == "particle_steps_per_s", m["name"]
        assert m["layer"] and "\n" not in m["layer"]
        assert hasattr(spec.metric_reader(m["name"]), "read")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25


def test_unknown_device_kind_raises():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 8.19e11
    with pytest.raises(KeyError, match="no peaks"):
        spec.peaks("TPU v99")


def test_missing_mode_or_metric_is_an_error():
    with pytest.raises(FileNotFoundError):
        spec.mode("no_such_mode")
    with pytest.raises(FileNotFoundError):
        spec.metric_reader("no_such_metric")


@pytest.mark.parametrize("name", CONFIGS)
def test_config_file_matches_the_program_case(name):
    """The numbers in the file are those of the program's case at the
    file's spacing, and the generator makes the stated particle count."""
    from repro.core import cases

    cfg = spec.config(name)
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    assert entry["file"] == f"bench/configs/{name}.json"
    assert entry["source"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"]
    case = cases.build_case(cfg["case"], **cfg["case_overrides"])
    dom = case.domain()
    assert cfg["ds"] == case.ds
    assert cfg["dt"] == case.dt
    assert cfg["physics"]["h"] == case.h
    assert cfg["physics"]["c0"] == case.c0
    assert tuple(cfg["box"]["lo"]) == dom.lo
    assert tuple(cfg["box"]["hi"]) == dom.hi
    assert tuple(cfg["box"]["periodic"]) == dom.periodic
    assert sum(initial.counts(cfg)) == cfg["n_particles"]
    scfg = program.sph_config(cfg, {"skin_frac": 0.0})
    case_cfg, case_state = case.build()
    assert case_state.xn.shape[0] == cfg["n_particles"]
    assert scfg.resolved_scheme == case_cfg.resolved_scheme
    assert scfg.wall_rho_clamp == case_cfg.wall_rho_clamp
    assert scfg.domain == case_cfg.domain
    assert scfg.policy == case_cfg.policy
    assert (scfg.dt, scfg.ds, scfg.algo) == (case_cfg.dt, case_cfg.ds,
                                             case_cfg.algo)


@pytest.mark.parametrize("name,ds", [("dam_break", 0.02),
                                     ("poiseuille", 0.02)])
def test_generator_matches_the_case_lattice(name, ds):
    """Without jitter the generated particles are the case's own."""
    from repro.core import cases

    cfg = small.scaled(name, ds)
    cfg["jitter"] = 0.0
    inp = initial.build(cfg, 5)
    _, st = cases.build_case(cfg["case"], **cfg["case_overrides"]).build()
    from repro.core import solver

    scfg, _ = cases.build_case(cfg["case"], **cfg["case_overrides"]).build()
    x_case = np.asarray(solver.positions(scfg, st))
    lo = np.asarray(cfg["lattice"]["lo"])

    def key(a):  # lattice node index of each particle
        ij = np.floor((a - lo) / ds).astype(np.int64)
        return np.lexsort((ij[:, 1], ij[:, 0]))

    xa = np.asarray(inp.x)[key(np.asarray(inp.x))]
    xb = x_case[key(x_case)]
    assert xa.shape == xb.shape
    # the case's positions come back through the fp16 RCLL state
    np.testing.assert_allclose(xa, xb, atol=2e-3 * ds)
    wall_a = np.asarray(inp.wall)[key(np.asarray(inp.x))]
    wall_b = np.asarray(st.fixed)[key(x_case)]
    np.testing.assert_array_equal(wall_a, wall_b)
    rho_a = np.asarray(inp.rho)[key(np.asarray(inp.x))]
    rho_b = np.asarray(st.fluid.rho)[key(x_case)]
    np.testing.assert_allclose(rho_a, rho_b, rtol=1e-6)


@pytest.mark.parametrize("t,share", [(0.1125, None), (50.0, 1.0)])
def test_series_start_is_the_channel_start_up_profile(t, share):
    """Fluid starts on Morris et al.'s series: on the steady parabola
    F/(2ν) y (L − y) for large t, below it at t = 0.1125, where the
    centre is at 66% of v_max; walls stay at rest."""
    import copy

    cfg = copy.deepcopy(small.scaled("poiseuille", 0.02))
    cfg["lattice"]["series"]["t"] = t
    inp = initial.build(cfg, 7)
    x, v = np.asarray(inp.x), np.asarray(inp.v)
    wall = np.asarray(inp.wall)
    ser = cfg["lattice"]["series"]
    y = np.clip(x[~wall, 1], 0.0, ser["L"])
    steady = ser["F"] / (2 * ser["nu"]) * y * (ser["L"] - y)
    v_max = ser["F"] * ser["L"] ** 2 / (8 * ser["nu"])
    assert np.all(v[wall] == 0.0) and np.all(v[:, 1] == 0.0)
    if share is None:
        centre = np.abs(y - 0.5 * ser["L"]) < cfg["ds"]
        np.testing.assert_allclose(v[~wall, 0][centre] / v_max, 0.66,
                                   atol=0.005)
        assert np.all(v[~wall, 0] < steady + 1e-7)
    else:
        np.testing.assert_allclose(v[~wall, 0], share * steady,
                                   atol=1e-4 * v_max)


def test_seed_changes_the_inputs_and_repeats():
    cfg = small.scaled("dam_break", 0.02)
    a = np.asarray(initial.build(cfg, 2**40 + 1).x)
    b = np.asarray(initial.build(cfg, 2**40 + 1).x)
    c = np.asarray(initial.build(cfg, 2**40 + 2).x)
    np.testing.assert_array_equal(a, b)
    assert np.abs(a - c).max() > 0
    assert np.abs(a - c).max() <= 2 * cfg["jitter"] * cfg["ds"] * 1.0001


def test_reference_imports_nothing_of_the_program():
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for fn in os.listdir(os.path.join(here, "references")):
        if fn.endswith(".py"):
            src = open(os.path.join(here, "references", fn)).read()
            assert "repro" not in src, fn
            assert "bench" not in src.replace('"""', ""), fn


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    import subprocess
    import sys

    root = os.path.dirname(spec.HERE)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", WORKLOADS[0],
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr
