"""The trace reduction: busy/idle union, per-op sums, gap attribution."""
from __future__ import annotations

import os

import numpy as np
import pytest

from bench import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "tiny_dam_break.xplane.pb")


def _events(evs, meta_ids):
    out = []
    for name, start, end in evs:
        out.append(f"events {{ metadata_id: {meta_ids[name]} "
                   f"offset_ps: {start * 1000} "
                   f"duration_ps: {(end - start) * 1000} }}")
    return "\n".join(out)


def _plane(pid, name, line, evs):
    names = sorted({e[0] for e in evs})
    ids = {n: k + 1 for k, n in enumerate(names)}
    meta = "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                     f'name: "{n}" }} }}' for n, i in ids.items())
    return (f'planes {{ id: {pid} name: "{name}" '
            f'lines {{ id: 1 name: "{line}" timestamp_ns: 0 '
            f'{_events(evs, ids)} }} {meta} }}')


HOST = [("bench.dispatch", 0, 100), ("bench.wait", 100, 1000),
        ("bench.dispatch", 1000, 1100), ("bench.wait", 1100, 2000),
        ("other", 0, 2000)]
DEVICE = [("rcll_force", 150, 650), ("fusion.1", 600, 700),
          ("fusion.2", 900, 950), ("rcll_force", 1200, 1800)]


@pytest.fixture(scope="module")
def synthetic():
    from jax.profiler import ProfileData

    text = (_plane(1, "/device:TPU:0", "XLA Ops", DEVICE)
            + _plane(2, "/host:CPU", "python3", HOST))
    return trace_reduce.reduce(ProfileData.from_text_proto(text))


def test_window_is_the_host_spans(synthetic):
    assert synthetic.window_s == pytest.approx(2000e-9)


def test_busy_is_the_union_of_operations(synthetic):
    # [150, 700] + [900, 950] + [1200, 1800]: the overlap counts once
    assert synthetic.busy_s() == pytest.approx(1200e-9)


def test_per_op_sums(synthetic):
    assert synthetic.op_s("rcll_force") == pytest.approx(1100e-9)
    tot = synthetic.op_totals()
    # leaves only: every synthetic op is a leaf
    assert tot["rcll_force"] == pytest.approx(1100e-9)
    assert tot["fusion.1"] == pytest.approx(100e-9)
    assert tot["fusion.2"] == pytest.approx(50e-9)


def test_loops_count_once_in_the_totals():
    """A loop op and the ops inside it: the union counts the loop's
    span once and the totals count only the ops inside."""
    d = trace_reduce.DeviceOps(["while.1", "fusion.1", "fusion.2"],
                               [0, 10, 50], [100, 40, 90])
    s = trace_reduce.Summary([d], [("bench.wait", 0.0, 200.0)])
    assert s.busy_s() == pytest.approx(100e-9)
    assert s.op_totals() == {"fusion.1": pytest.approx(30e-9),
                             "fusion.2": pytest.approx(40e-9)}


def test_idle_gaps_are_labelled_by_host_spans(synthetic):
    gaps = synthetic.idle_gaps()
    assert [g[0] for g in gaps] == ["bench.dispatch", "bench.wait",
                                    "bench.wait", "bench.dispatch"]
    assert [g[1] for g in gaps] == pytest.approx([250e-9, 200e-9,
                                                  200e-9, 150e-9])
    assert sum(g[1] for g in gaps) == pytest.approx(
        synthetic.window_s - synthetic.busy_s())
    bd = synthetic.breakdown()
    assert bd["device_ops"][0] == ["rcll_force", pytest.approx(1100e-9)]
    assert len(bd["idle_gaps"]) == 4


def test_union_and_gaps_by_hand():
    iv = [(5, 10), (0, 3), (2, 4), (9, 12)]
    assert trace_reduce.union_ns(iv, 0, 20) == 4 + 7
    assert trace_reduce.union_ns(iv, 1, 6) == 3 + 1
    assert trace_reduce.gaps_ns(iv, 0, 20).tolist() == [[4, 5], [12, 20]]


def test_no_host_span_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.Summary([], [])


def test_op_names_are_the_hlo_op_names():
    text = ("%rcll_force.2 = (f32[8,1,18]{2,1,0}) custom-call(f32[18] "
            "%get-tuple-element.1), custom_call_target=\"tpu_custom_call\"")
    assert trace_reduce.op_name(text) == "rcll_force.2"
    assert trace_reduce.op_name("%gte.5 = f32[2] get-tuple-element("
                                "%rcll_force.2)") == "gte.5"
    assert trace_reduce.op_name("jit_run(123)") == "jit_run(123)"


def test_window_ends_before_dropped_events():
    from jax.profiler import ProfileData

    text = (_plane(1, "/device:TPU:0", "XLA Ops", DEVICE)
            + _plane(2, "/host:CPU", "python3", HOST))
    text = text.replace('lines { id: 1 name: "XLA Ops"', (
        'lines { id: 2 name: "XLA Modules" timestamp_ns: 0 '
        'events { metadata_id: 91 offset_ps: 100000 duration_ps: 900000 } '
        'events { metadata_id: 91 offset_ps: 1100000 '
        'duration_ps: 900000 } } '
        'lines { id: 3 name: "XLA TraceMe" timestamp_ns: 0 '
        'events { metadata_id: 92 offset_ps: 1500000 duration_ps: 1000 } } '
        'event_metadata { key: 91 value { id: 91 name: "jit_run" } } '
        'event_metadata { key: 92 value { id: 92 '
        'name: "Trace Buffers Dropped" } } '
        'lines { id: 1 name: "XLA Ops"'), 1)
    s = trace_reduce.reduce(ProfileData.from_text_proto(text))
    assert s.truncated and s.window_s == pytest.approx(1000e-9)
    assert s.modules_done() == 1
    # [150, 700] + [900, 950] inside [0, 1000]
    assert s.busy_s() == pytest.approx(600e-9)


def _brute_union(evs, lo, hi):
    """Union length by sweeping every breakpoint (the slow, plain way)."""
    pts = np.unique(np.clip(np.asarray(evs).ravel(), lo, hi))
    mids = 0.5 * (pts[1:] + pts[:-1])
    ev = np.asarray(evs)
    covered = np.zeros(len(mids), bool)
    for s, e in ev:
        covered |= (s <= mids) & (mids < e)
    return float(np.sum(np.diff(pts)[covered]))


def test_recorded_trace():
    """A trace recorded on a TPU v5e: two chunks of two steps of a small
    dam break (1,958 particles, the Pallas force kernel) inside host
    spans around each dispatch and each wait. The reduction agrees with
    a brute-force union, finds the force kernel, and its gaps fill the
    window."""
    s = trace_reduce.reduce_file(RECORDED)
    assert len(s.devices) == 1 and s.devices[0]
    assert {sp[0] for sp in s.spans} == {"bench.dispatch", "bench.block"}
    d = s.devices[0]
    busy = _brute_union(list(zip(d.starts, d.ends)), s.lo, s.hi) / 1e9
    assert s.busy_s() == pytest.approx(busy, rel=1e-9)
    assert 0 < s.busy_s() < s.window_s
    assert s.op_s("rcll_force") > 0
    assert sum(g[1] for g in s.idle_gaps()) == pytest.approx(
        s.window_s - s.busy_s(), rel=1e-6)
    assert s.modules_done() == 2 and not s.truncated
    assert sum(s.op_totals().values()) <= s.busy_s() * (1 + 1e-9)
    assert max(s.op_totals(), key=s.op_totals().get).startswith(
        ("rcll_force", "while"))
