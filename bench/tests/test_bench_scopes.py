"""Device time by the program's ``sph.*`` scopes (``bench/scopes.py``),
and the benchmark's readers on the recorded trace, which the scopes
leave as they were."""
from __future__ import annotations

import os

import pytest

from bench import scopes, trace_reduce
from bench.tests.test_bench_trace_reduce import HOST, RECORDED, _plane

HERE = os.path.dirname(os.path.abspath(__file__))


# Every reader's number on the recorded trace as the harness read it
# before the program had scopes: without a scope map the readers, the
# breakdown and the gap labels are unchanged to the last digit.
RECORDED_READINGS = {"force_kernel_ms": 4.5364345,
                     "rcll_force_roofline": 0.0013702111241489358,
                     "non_force_ms": 2.5679482499999993,
                     "rebuilds_per_step": 1.0,
                     "device_idle_pct": 10.528845651305218}


@pytest.mark.parametrize("metric", sorted(RECORDED_READINGS))
def test_recorded_trace_readings_are_unchanged(metric):
    import types

    from bench import roofline, spec
    from bench.tests import small

    s = trace_reduce.reduce_file(RECORDED)
    cfg = small.scaled("dam_break", 0.02)
    ctx = types.SimpleNamespace(
        trace=s, steps=4, counters={"steps": 4, "rebuilds": 4}, n=1958,
        peak=spec.peaks("TPU v5 lite"), cfg=cfg,
        counts=roofline.force_pass(cfg, 1958))
    assert spec.metric_reader(metric).read(ctx) == RECORDED_READINGS[metric]
    bd = s.breakdown()
    assert bd["device_ops"][:2] == [
        ["rcll_force.10", 0.018145738],
        ["bitcast_dynamic-update-slice_fusion.2", 0.002394605]]
    assert bd["idle_gaps"][:2] == [["bench.block", 0.002008821],
                                   ["bench.block", 0.001334867]]


# --------------------------------------------------------------------------
# scopes: the program's sph.* layers, read from its compiled HLO
# --------------------------------------------------------------------------
SCOPED = [("fusion.4", 50, 120), ("fusion.1", 120, 300),
          ("rcll_force", 300, 700), ("fusion.2", 700, 760),
          ("copy.3", 900, 950), ("fusion.1", 1200, 1500),
          ("fusion.2", 1900, 2100)]  # the last one past the window's end
SCOPES = {"fusion.4": "sph.rebuild/sph.rebuild.pack",
          "fusion.1": "sph.force/sph.cell_tables",
          "rcll_force": "sph.force", "fusion.2": "sph.integrate",
          "copy.3": ""}


@pytest.fixture(scope="module")
def scoped():
    from jax.profiler import ProfileData

    text = (_plane(1, "/device:TPU:0", "XLA Ops", SCOPED)
            + _plane(2, "/host:CPU", "python3", HOST))
    return scopes.Scoped(
        trace_reduce.reduce(ProfileData.from_text_proto(text)), SCOPES)


def test_scope_time_holds_its_children(scoped):
    assert scoped.scope_s("sph.force") == pytest.approx(880e-9)
    assert scoped.scope_s("sph.cell_tables") == pytest.approx(480e-9)
    assert scoped.scope_s("sph.rebuild") == pytest.approx(70e-9)
    assert scoped.scope_s("sph.rebuild.pack") == pytest.approx(70e-9)
    # a scope matches whole path components, never a prefix of one
    assert scoped.scope_s("sph.rebuild.p") == 0.0
    assert scoped.scope_s("sph.skin_check") == 0.0


def test_scope_time_is_clipped_to_the_window(scoped):
    # 60 ns inside the window, then 100 of the 200 ns op past it
    assert scoped.scope_s("sph.integrate") == pytest.approx(160e-9)


def test_scopes_and_unscoped_add_up_to_the_busy_time(scoped):
    assert scoped.unscoped_s() == pytest.approx(50e-9)
    tops = sum(scoped.scope_s(s) for s in ("sph.rebuild", "sph.force",
                                           "sph.integrate"))
    busy = scoped.summary.busy_s()
    assert tops + scoped.unscoped_s() == pytest.approx(busy)
    # no op holds another here, so that is the leaves' time too
    assert busy == pytest.approx(sum(scoped.summary.op_totals().values()))


def test_a_loop_counts_its_time_between_ops_for_its_scope():
    """The scan's loop holds a loop of the cell-table pack, which holds
    two ops: the time between them is the inner loop's own, the time
    around it the scan's."""
    d = trace_reduce.DeviceOps(
        ["while.15", "while.13", "fusion.1", "fusion.2", "fusion.9"],
        [0, 10, 10, 30, 70], [100, 60, 20, 50, 90])
    s = scopes.Scoped(
        trace_reduce.Summary([d], [("bench.wait", 0.0, 100.0)]),
        {"while.13": "sph.force/sph.cell_tables",
         "fusion.1": "sph.force/sph.cell_tables",
         "fusion.2": "sph.force/sph.cell_tables",
         "fusion.9": "sph.integrate", "while.15": ""})
    assert scopes.own_ns(d, 0, 100).tolist() == [30, 20, 10, 20, 20]
    assert s.scope_s("sph.cell_tables") == pytest.approx(50e-9)
    assert s.scope_s("sph.integrate") == pytest.approx(20e-9)
    assert s.unscoped_s() == pytest.approx(30e-9)
    # the leaves alone miss the loops' own time
    assert sum(s.summary.op_totals().values()) == pytest.approx(50e-9)


def test_own_time_of_overlapping_and_clipped_ops():
    d = trace_reduce.DeviceOps(["a", "b", "c"], [0, 5, 40], [10, 15, 80])
    # b outlives a: each op is credited once, in turn; c is clipped
    assert scopes.own_ns(d, 0, 60).tolist() == [5, 10, 20]
    assert sum(scopes.own_ns(d, 0, 60)) == trace_reduce.union_ns(
        d.intervals(), 0, 60)


def test_op_missing_from_the_map_is_unscoped():
    d = trace_reduce.DeviceOps(["fusion.1", "fusion.7"], [0, 10], [10, 40])
    s = scopes.Scoped(trace_reduce.Summary([d], [("bench.wait", 0.0, 50.0)]),
                      {"fusion.1": "sph.integrate"})
    assert s.scope_s("sph.integrate") == pytest.approx(10e-9)
    assert s.unscoped_s() == pytest.approx(30e-9)


def test_no_map_no_scope_time(scoped):
    unmapped = scopes.Scoped(scoped.summary, None)
    assert unmapped.scope_s("sph.force") is None
    assert unmapped.unscoped_s() is None
    assert unmapped.by_path() == {}
    # without a map the breakdown is the summary's own
    assert unmapped.breakdown() == scoped.summary.breakdown()
    empty = scopes.Scoped(
        trace_reduce.Summary([], [("bench.wait", 0.0, 1.0)]), {})
    assert empty.scope_s("sph.force") is None


def test_gap_labels_name_the_scope_that_ran_last(scoped):
    gaps = dict(scoped.idle_gaps())
    assert gaps == {"bench.wait@sph.cell_tables": pytest.approx(400e-9),
                    "bench.dispatch@unscoped": pytest.approx(250e-9),
                    "bench.wait@sph.integrate": pytest.approx(140e-9),
                    "bench.dispatch": pytest.approx(50e-9)}


def test_breakdown_ops_carry_their_innermost_scope(scoped):
    ops = dict(scoped.breakdown()["device_ops"])
    assert ops["sph.cell_tables:fusion.1"] == pytest.approx(480e-9)
    assert ops["sph.force:rcll_force"] == pytest.approx(400e-9)
    assert ops["sph.rebuild.pack:fusion.4"] == pytest.approx(70e-9)
    assert ops["copy.3"] == pytest.approx(50e-9)


HLO = """\
HloModule jit_run_persistent, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %mul.3 = f32[8]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(run_persistent)/while/body/sph.force/sph.unpack/mul"}
}

%wide.body (arg.1: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg.1 = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%arg.1), index=1
  %dynamic-slice_fusion.2 = f32[8]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.1
  %gte.0 = s32[] get-tuple-element(%arg.1), index=0
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%gte.0, %dynamic-slice_fusion.2)
}

%wide.cond (arg.2: (s32[], f32[8])) -> pred[] {
  %arg.2 = (s32[], f32[8]{0}) parameter(0)
  %gte.2 = s32[] get-tuple-element(%arg.2), index=0
  ROOT %lt.1 = pred[] compare(%gte.2, %gte.2), direction=LT
}

%branch.rebuild (arg.3: f32[8]) -> f32[8] {
  %arg.3 = f32[8]{0} parameter(0)
  %sort.1 = f32[8]{0} sort(%arg.3), dimensions={0}, metadata={op_name="jit(run_persistent)/while/body/cond/branch_1_fun/sph.rebuild/sph.rebuild.pack/sort"}
  %fusion.7 = f32[8]{0} fusion(%sort.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(run_persistent)/while/body/cond/branch_1_fun/sph.rebuild/sph.rebuild.permute/gather"}
  ROOT %copy.9 = f32[8]{0} copy(%fusion.7)
}

%branch.keep (arg.4: f32[8]) -> f32[8] {
  ROOT %arg.4 = f32[8]{0} parameter(0)
}

%body (arg.5: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg.5 = (s32[], f32[8]{0}) parameter(0)
  %gte.5 = f32[8]{0} get-tuple-element(%arg.5), index=1
  %reduce-window.3 = f32[8]{0} reduce-window(%gte.5), window={size=8}, metadata={op_name="reduce_window_sum"}
  %while.13 = (s32[], f32[8]{0}) while(%tuple.4), condition=%wide.cond, body=%wide.body, metadata={op_name="jit(run_persistent)/while/body/sph.force/sph.cell_tables/vmap()/gather"}
  %fusion.5 = f32[8]{0} fusion(%reduce-window.3, %gte.5), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(run_persistent)/while/body/sph.force/sph.cell_tables/add"}
  %pred.1 = pred[] constant(true)
  %cond.4 = f32[8]{0} conditional(%pred.1, %fusion.5, %fusion.5), branch_computations={%branch.keep, %branch.rebuild}, metadata={op_name="jit(run_persistent)/while/body/cond"}
  %copy.12 = f32[8]{0} copy(%gte.5)
  %fusion.9 = f32[8]{0} fusion(%cond.4, %copy.12), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(run_persistent)/while/body/sph.integrate/add"}
  %copy.13 = f32[8]{0} copy(%fusion.9)
  ROOT %tuple.5 = (s32[], f32[8]{0}) tuple(%gte.5, %copy.13)
}

ENTRY %main.50 (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  %copy.66 = f32[8]{0} copy(%p.1)
  %while.15 = (s32[], f32[8]{0}) while(%copy.66), condition=%wide.cond, body=%body, metadata={op_name="jit(run_persistent)/while"}
  ROOT %gte.9 = f32[8]{0} get-tuple-element(%while.15), index=1
}
"""


def test_scope_map_reads_each_op_path():
    m = scopes.scope_map(HLO)
    assert m["fusion.5"] == "sph.force/sph.cell_tables"
    assert m["fusion.9"] == "sph.integrate"
    assert m["sort.1"] == "sph.rebuild/sph.rebuild.pack"
    assert m["fusion.7"] == "sph.rebuild/sph.rebuild.permute"
    # a fusion's own path, not that of the ops fused into it
    assert m["mul.3"] == "sph.force/sph.unpack"


def test_scope_map_gives_an_op_without_a_scope_its_users_scope():
    m = scopes.scope_map(HLO)
    assert m["reduce-window.3"] == "sph.force/sph.cell_tables"
    assert m["copy.12"] == "sph.integrate"


def test_scope_map_gives_loop_bodies_the_loop_scope():
    m = scopes.scope_map(HLO)
    assert m["dynamic-slice_fusion.2"] == "sph.force/sph.cell_tables"
    assert m["tuple.1"] == "sph.force/sph.cell_tables"


def test_scope_map_gives_a_branch_the_scope_its_ops_share():
    m = scopes.scope_map(HLO)
    assert m["copy.9"] == "sph.rebuild"
    # the other branch holds no scoped op
    assert m["arg.4"] == ""


def test_scope_map_leaves_the_scan_machinery_unscoped():
    m = scopes.scope_map(HLO)
    for op in ("copy.13", "cond.4", "copy.66", "while.15", "tuple.5"):
        assert m[op] == "", op
    assert scopes.innermost("sph.force/sph.cell_tables") == (
        "sph.cell_tables")


def test_scope_map_of_a_program_without_scopes_is_none():
    assert scopes.scope_map(HLO.replace("sph.", "x.")) is None


# A trace recorded on a TPU v5e with the scopes in the program
# (``bench/tests/record_trace.py``: two chunks of two steps of the dam
# break at ds = 0.02, a rebuild before every step), the op-to-scope map
# of the program it timed, and that program's HLO text as compiled ahead
# of time for a described v5e (the checkout's path taken out of the
# trace's and the text's source locations).
SCOPED_TRACE = os.path.join(HERE, "data", "tiny_scoped.xplane.pb")
SCOPED_MAP = os.path.join(HERE, "data", "tiny_scoped.scopes.json")
SCOPED_HLO = os.path.join(HERE, "data", "tiny_scoped.hlo.txt.gz")
PALLAS_SCOPES = ("sph.rebuild", "sph.rebuild.pack", "sph.rebuild.permute",
                 "sph.rebuild.mass_table", "sph.skin_check", "sph.force",
                 "sph.cell_tables", "sph.unpack", "sph.integrate")


@pytest.fixture(scope="module")
def recorded_scoped():
    import json

    with open(SCOPED_MAP) as f:
        smap = json.load(f)
    return scopes.Scoped(trace_reduce.reduce_file(SCOPED_TRACE), smap)


def test_recorded_map_is_the_map_of_the_compiled_text():
    import gzip
    import json

    with gzip.open(SCOPED_HLO, "rt") as f:
        text = f.read()
    with open(SCOPED_MAP) as f:
        assert scopes.scope_map(text) == json.load(f)


def test_recorded_scopes_account_for_the_leaf_time(recorded_scoped):
    """The top-level scopes, less the kernel, plus the kernel and the
    unscoped time give the summed leaf time within 1% (and the busy
    time exactly: the loops' own time is in their scopes)."""
    s = recorded_scoped
    kernel = s.summary.op_s("rcll_force")
    tops = (s.scope_s("sph.rebuild") + s.scope_s("sph.skin_check")
            + s.scope_s("sph.force") - kernel + s.scope_s("sph.integrate"))
    total = tops + kernel + s.unscoped_s()
    busy = s.summary.busy_s()
    assert total == pytest.approx(sum(s.summary.op_totals().values()),
                                  rel=0.01)
    assert total == pytest.approx(busy, rel=1e-9)
    assert kernel > 0.5 * busy


def test_recorded_trace_ops_all_have_a_scope_in_the_map(recorded_scoped):
    s = recorded_scoped
    names = set(s.summary.devices[0].names)
    assert names <= set(s.scopes)
    seen = {c for n in names for c in s.scope_of(n).split("/") if c}
    assert set(PALLAS_SCOPES) <= seen
    for scope in PALLAS_SCOPES:
        assert s.scope_s(scope) > 0, scope
    assert s.scope_s("sph.cell_tables") > s.scope_s("sph.unpack")
    # the scan's loop counter and carry copies: a small remainder
    assert 0 < s.unscoped_s() < 0.01 * s.summary.busy_s()
    for label, _ in s.breakdown()["device_ops"]:
        assert label.startswith("sph."), label


def test_scope_report_reads_the_timed_program(tmp_path):
    """A whole report on the CPU (the XLA force path, whose trace stays
    small): the timed program's temporaries and the force grid's useful
    share reach the line, and a trace with no TPU plane gives no scope
    time rather than 0."""
    from bench import scope_report, spec
    from bench.tests import small

    work = spec.workload("dam_break.1m_skin")
    cfg = dict(small.scaled("dam_break", 0.02), backend="xla")
    line = scope_report.report("dam_break.1m_skin", 2**41 + 17, cfg=cfg,
                               trace_dir=str(tmp_path / "trace"))
    assert line["steps"] == work["chunk_steps"] * work["trace_chunks"]
    assert line["program_temp_bytes"] > 0
    assert line["force_grid_launched"] > 0
    assert 0 < line["force_grid_useful_pct"] < 100
    assert line["scope_ms"]["sph.force"] is None
    assert line["unscoped_ms"] is None
    assert not (tmp_path / "trace").exists()
