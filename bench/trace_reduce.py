"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

What is read, with nothing but ``jax.profiler.ProfileData``:

* device operations: the events of the ``XLA Ops`` line of every
  ``/device:TPU:<k>`` plane. An event's name is the HLO text of the op
  (``%rcll_force.2 = (...) custom-call(...)``); the op's own name, the
  part before `` = ``, is what is kept and matched;
* host spans: the events of the host planes whose names start with
  ``bench.`` (the harness's ``TraceAnnotation``s around each chunk's
  dispatch and its wait).

The window is the union of the host spans: from the first span's start
to the last span's end. Where the profiler dropped events to keep its
output under its size limit (an ``XLA TraceMe`` event named ``Trace
Buffers Dropped``), the window ends with the last program run
(``XLA Modules`` event) that finished before the drop. Within the
window a device is busy where any of its operations runs; busy time is
the length of the union of those intervals, averaged over the devices.
Every gap between busy intervals is labelled by the host span that
covers its midpoint ("none" if no span does). Time by operation counts
the leaves only (an op inside a loop or a conditional counts, the loop
around it does not), so the parts add up to no more than the busy time.
"""
from __future__ import annotations

import glob
import os

import numpy as np

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TRACEME_LINE = "XLA TraceMe"
DROPPED = "Trace Buffers Dropped"
DEVICE_PREFIX = "/device:TPU:"


def op_name(text: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    head = text.split(" = ", 1)[0] if " = " in text else text
    return head.lstrip("%")


class DeviceOps:
    """One device's operations: interned names, starts and ends (ns),
    sorted by start (an enclosing op before the ops inside it)."""

    def __init__(self, names, starts, ends):
        table: dict[str, int] = {}
        codes = np.fromiter((table.setdefault(n, len(table)) for n in names),
                            np.int64, count=len(names))
        starts = np.asarray(starts, np.float64)
        ends = np.asarray(ends, np.float64)
        order = np.lexsort((-ends, starts))
        self.names = list(table)
        self.codes, self.starts, self.ends = (codes[order], starts[order],
                                              ends[order])

    def clipped(self, lo: float, hi: float):
        s = np.clip(self.starts, lo, hi)
        e = np.clip(self.ends, lo, hi)
        return s, e

    def intervals(self) -> np.ndarray:
        return np.stack([self.starts, self.ends], axis=1)

    def matching(self, prefix: str) -> np.ndarray:
        hit = np.array([n.startswith(prefix) for n in self.names], bool)
        return hit[self.codes] if len(self.codes) else hit[:0]

    def leaves(self) -> np.ndarray:
        """Ops that hold no other op: the next op (in start order) does
        not lie inside them."""
        nxt_s = np.append(self.starts[1:], np.inf)
        nxt_e = np.append(self.ends[1:], np.inf)
        return ~((nxt_s < self.ends) & (nxt_e <= self.ends))


def _merged(intervals, lo: float, hi: float) -> np.ndarray:
    """The union of ``intervals`` clipped to [lo, hi], as sorted,
    disjoint (start, end) rows."""
    iv = np.asarray(intervals, np.float64).reshape(-1, 2)
    iv = np.clip(iv, lo, hi)
    iv = iv[iv[:, 1] > iv[:, 0]]
    if not len(iv):
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    # a new run starts where an interval begins past all earlier ends
    new = np.concatenate([[True], iv[1:, 0] > reach[:-1]])
    starts = iv[new, 0]
    ends = reach[np.concatenate([np.nonzero(new)[0][1:] - 1,
                                 [len(iv) - 1]])]
    return np.stack([starts, ends], axis=1)


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` clipped to [lo, hi]."""
    m = _merged(intervals, lo, hi)
    return float(np.sum(m[:, 1] - m[:, 0]))


def gaps_ns(intervals, lo: float, hi: float) -> np.ndarray:
    """The (start, end) rows of [lo, hi] that no interval covers."""
    m = _merged(intervals, lo, hi)
    edges = np.concatenate([[lo], m.ravel(), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


class Summary:
    """The reduced trace: device ops per device and the host spans."""

    def __init__(self, devices: list[DeviceOps], spans: list[tuple],
                 modules: list[list[tuple]] | None = None,
                 dropped_at: float | None = None):
        if not spans:
            raise ValueError("the trace holds no bench.* host span")
        self.devices = devices
        self.spans = sorted(spans, key=lambda s: s[1])
        self.modules = modules or [[] for _ in devices]
        self.lo = min(s[1] for s in spans)
        self.hi = max(s[2] for s in spans)
        self.truncated = dropped_at is not None and dropped_at < self.hi
        self.dropped_at = dropped_at if self.truncated else None
        if self.truncated:
            # end at the last program run that finished before the drop
            done = [e for s, e in self.modules[0]
                    if s >= self.lo and e <= dropped_at] if self.modules else []
            self.hi = max([self.lo] + done) if done else max(self.lo,
                                                             dropped_at)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def busy_s(self) -> float:
        """Seconds in which any op ran, per device, averaged."""
        if not self.devices:
            return 0.0
        return sum(union_ns(d.intervals(), self.lo, self.hi)
                   for d in self.devices) / len(self.devices) / 1e9

    def op_s(self, prefix: str) -> float:
        """Seconds of the ops whose name starts with ``prefix``, summed
        over the window and averaged over the devices."""
        if not self.devices:
            return 0.0
        tot = 0.0
        for d in self.devices:
            s, e = d.clipped(self.lo, self.hi)
            tot += float(np.sum((e - s)[d.matching(prefix)]))
        return tot / len(self.devices) / 1e9

    def modules_done(self) -> int:
        """Program runs (``XLA Modules`` events) on device 0 that overlap
        the window and were not cut by a drop. (The device's clock and
        the host's agree to about a millisecond, so a run may start a
        little before the first host span.)"""
        if not self.modules:
            return 0
        end = np.inf if self.dropped_at is None else self.dropped_at
        return sum(1 for s, e in self.modules[0]
                   if s < self.hi and e > self.lo and e <= end)

    def op_totals(self) -> dict:
        """{op name: seconds} of the leaf ops, over window and devices."""
        tot: dict[str, float] = {}
        for d in self.devices:
            s, e = d.clipped(self.lo, self.hi)
            keep = d.leaves() & (e > s)
            sums = np.bincount(d.codes[keep], weights=(e - s)[keep],
                               minlength=len(d.names))
            for k in np.nonzero(sums)[0]:
                tot[d.names[k]] = tot.get(d.names[k], 0.0) + sums[k] / 1e9
        return tot

    def idle_gaps(self, top: int | None = None) -> list[tuple]:
        """[(label, seconds)] of device 0's idle stretches, longest
        first (the ``top`` longest where given)."""
        if not self.devices:
            return [("none", self.window_s)]
        g = gaps_ns(self.devices[0].intervals(), self.lo, self.hi)
        order = np.argsort(-(g[:, 1] - g[:, 0]), kind="stable")[:top]
        out = []
        for s, e in g[order]:
            mid = 0.5 * (s + e)
            label = next((sp[0] for sp in self.spans
                          if sp[1] <= mid <= sp[2]), "none")
            out.append((label, (e - s) / 1e9))
        return out

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_totals().items(), key=lambda kv: -kv[1])
        return {"device_ops": [[k, v] for k, v in ops[:top]],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps(top)]}


def reduce(profile) -> Summary:
    """Summary of a ``jax.profiler.ProfileData``."""
    devices, modules, spans, dropped = [], [], [], None
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            names, starts, ends, mods = [], [], [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        names.append(op_name(e.name))
                        starts.append(e.start_ns)
                        ends.append(e.start_ns + e.duration_ns)
                elif line.name == MODULES_LINE:
                    mods = [(e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
                elif line.name == TRACEME_LINE:
                    for e in line.events:
                        if e.name == DROPPED:
                            t = float(e.start_ns)
                            dropped = t if dropped is None else min(dropped, t)
            devices.append(DeviceOps(names, starts, ends))
            modules.append(mods)
        elif plane.name.startswith("/host:"):
            spans += [(e.name, float(e.start_ns),
                       float(e.start_ns + e.duration_ns))
                      for line in plane.lines for e in line.events
                      if e.name.startswith(SPAN_PREFIX)]
    return Summary(devices, spans, modules, dropped)


def reduce_file(path: str) -> Summary:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(path))


def reduce_dir(trace_dir: str) -> Summary:
    """Summary of the one trace that ``jax.profiler`` wrote under a dir."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} traces under {trace_dir}")
    return reduce_file(found[0])
