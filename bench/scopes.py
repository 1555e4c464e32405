"""The program's layers in a trace: device time by ``sph.*`` scope.

The program names its layers with ``jax.named_scope("sph.*")``. A
trace's ops do not carry them (their HLO text has ``metadata={}``), so
:func:`scope_map` reads them from the timed program's compiled HLO
text, where each instruction's ``metadata={op_name=...}`` holds its
path (a fusion takes its root's). An instruction without a ``sph.``
scope of its own takes, in turn, the one scope its scoped users share,
then the scope of the instruction that calls its computation (a
``while`` body's ops that of the loop), then the scope all scoped ops
of its computation share. Ops left with none are "unscoped": the
scan's loop counter and carry copies.

:class:`Scoped` sets a map beside a :class:`trace_reduce.Summary` and
reads each scope's device time. That is each op's own time (a leaf's
whole time, a loop's or a branch's time between the ops inside it), so
the scopes and the unscoped time add up to the busy time: leaf time
alone misses the time between the ops of XLA's gather loops.
"""
from __future__ import annotations

import re

import numpy as np

from bench import trace_reduce

SCOPE_PREFIX = "sph."

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"\b(?:body|condition|calls|to_apply|true_computation"
                     r"|false_computation|branch_computations)="
                     r"(\{[^}]*\}|%?[\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")
_OPCODE = re.compile(r"\s([a-z][a-z\-]*)\(")
_CONTROL = ("while", "conditional", "call")


def _parse_hlo(hlo_text: str) -> list[tuple[str, list]]:
    """[(computation, [(name, own scope, operands, callees, control)])]
    in text order (callees before callers, operands before users);
    ``control`` marks a while, conditional or call."""
    comps: list[tuple[str, list]] = []
    for line in hlo_text.splitlines():
        if not line.strip() or line.startswith("HloModule"):
            continue
        if not line[0].isspace():
            if line.rstrip().endswith("{"):
                head = line.split("(", 1)[0].split()
                comps.append((head[-1].lstrip("%"), []))
            continue
        if " = " not in line or not comps:
            continue
        lhs, rhs = line.split(" = ", 1)
        name = lhs.split()[-1].lstrip("%")
        m = _OP_NAME.search(rhs)
        own = "/".join(c for c in (m.group(1).split("/") if m else ())
                       if c.startswith(SCOPE_PREFIX))
        callees = [c for g in _CALLED.findall(rhs) for c in
                   re.findall(r"[\w.\-]+", g.replace("%", " "))]
        op = _OPCODE.search(" " + rhs)
        comps[-1][1].append((name, own, _REF.findall(rhs.split(
            "metadata=")[0]), callees, bool(op) and op.group(1) in _CONTROL))
    return comps


def scope_map(hlo_text: str) -> dict[str, str] | None:
    """{op name: scope path} of a compiled program's HLO text.

    The path is the op's ``sph.`` scopes, outermost first, joined by
    ``/`` (``"sph.force/sph.cell_tables"``); ``""`` for an unscoped op.
    An op without a scope of its own takes the one scope its scoped
    users share, else the scope of the instruction that calls its
    computation, else the scope that all scoped ops of its computation
    share (a ``lax.cond`` branch's copies that of the branch). None
    when no op has a scope: a program without scopes.
    """
    comps = _parse_hlo(hlo_text)
    if not any(i[1] for _, instrs in comps for i in instrs):
        return None
    caller: dict[str, str] = {}
    for _, instrs in comps:
        for name, _, _, callees, _ in instrs:
            for c in callees:
                caller.setdefault(c, name)
    out: dict[str, str] = {}
    # callers before their callees, users before their operands
    for comp, instrs in reversed(comps):
        names = {i[0] for i in instrs}
        users: dict[str, set] = {}
        for name, _, operands, _, _ in instrs:
            for o in operands:
                if o in names:
                    users.setdefault(o, set()).add(name)
        shared = _common([i[1] for i in instrs if i[1]])
        for name, own, _, _, control in reversed(instrs):
            scope = own
            # a loop or branch holds work of its own: never its users'
            if not scope and not control:
                seen = {out[u] for u in users.get(name, ()) if out.get(u)}
                if len(seen) == 1:
                    scope = seen.pop()
            scope = scope or out.get(caller.get(comp, ""), "") or shared
            out[name] = scope
    return out


def _common(paths: list[str]) -> str:
    """The longest scope path that every one of ``paths`` starts with."""
    if not paths:
        return ""
    parts = [p.split("/") for p in paths]
    n = 0
    while all(len(q) > n and q[n] == parts[0][n] for q in parts):
        n += 1
    return "/".join(parts[0][:n])


def innermost(path: str) -> str:
    """``"sph.force/sph.cell_tables"`` -> ``"sph.cell_tables"``."""
    return path.rsplit("/", 1)[-1]


def own_ns(d: trace_reduce.DeviceOps, lo: float, hi: float) -> np.ndarray:
    """Per op of ``d``, the time within [lo, hi] in which it is the
    innermost op running: a leaf's whole time, a loop's or a branch's
    time between the ops inside it. The parts add up to the busy time."""
    s, e = (a.tolist() for a in d.clipped(lo, hi))
    n = len(s)
    own = [0.0] * n
    stack: list[int] = []  # ops running, the innermost last
    cur = lo  # time credited so far
    for i in range(n + 1):
        at = s[i] if i < n else hi
        if i < n and e[i] <= at:
            continue  # nothing of it in the window
        while stack and e[stack[-1]] <= at:  # close the ops done
            j = stack.pop()
            if e[j] > cur:
                own[j] += e[j] - cur
                cur = e[j]
        if stack and at > cur:
            own[stack[-1]] += at - cur
        cur = max(cur, at)
        if i < n:
            stack.append(i)
    return np.asarray(own)


class Scoped:
    """A trace's :class:`trace_reduce.Summary` read by scope, with the
    timed program's :func:`scope_map` (None for a program without
    scopes: every time is then None, never 0)."""

    def __init__(self, summary: trace_reduce.Summary,
                 scopes: dict[str, str] | None):
        self.summary = summary
        self.scopes = scopes
        self._own = None

    def scope_of(self, op: str) -> str:
        """The op's scope path; ``""`` if unscoped or not in the map."""
        return (self.scopes or {}).get(op, "")

    def own_totals(self) -> dict:
        """{op name: seconds} of each op's own time (:func:`own_ns`),
        over the window and the devices; they add up to the busy time."""
        if self._own is None:
            s = self.summary
            tot: dict[str, float] = {}
            for d in s.devices:
                sums = np.bincount(d.codes, weights=own_ns(d, s.lo, s.hi),
                                   minlength=len(d.names))
                for k in np.nonzero(sums)[0]:
                    tot[d.names[k]] = tot.get(d.names[k], 0.0) + sums[k] / 1e9
            self._own = tot
        return self._own

    def scope_s(self, scope: str) -> float | None:
        """Device seconds of the ops whose scope path holds ``scope`` as
        a whole component (its children included), over the window,
        averaged over the devices. None without a map or a device."""
        if self.scopes is None or not self.summary.devices:
            return None
        tot = sum(v for k, v in self.own_totals().items()
                  if scope in self.scope_of(k).split("/"))
        return tot / len(self.summary.devices)

    def unscoped_s(self) -> float | None:
        """Device seconds of the ops in no scope, as :meth:`scope_s`."""
        if self.scopes is None or not self.summary.devices:
            return None
        tot = sum(v for k, v in self.own_totals().items()
                  if not self.scope_of(k))
        return tot / len(self.summary.devices)

    def by_path(self) -> dict[str, float]:
        """{scope path or "unscoped": device seconds}, as
        :meth:`scope_s` but each op under its own path alone."""
        out: dict[str, float] = {}
        if self.scopes is None or not self.summary.devices:
            return out
        for k, v in self.own_totals().items():
            path = self.scope_of(k) or "unscoped"
            out[path] = out.get(path, 0.0) + v / len(self.summary.devices)
        return out

    def _labelled(self, op: str) -> str:
        scope = self.scope_of(op)
        return f"{innermost(scope)}:{op}" if scope else op

    def idle_gaps(self, top: int | None = None) -> list[tuple]:
        """The summary's idle gaps (device 0, longest first), each label
        with the innermost scope of the op that ended last before the
        gap (``bench.wait@sph.integrate``; ``@unscoped``)."""
        s = self.summary
        if not s.devices or self.scopes is None:
            return s.idle_gaps(top)
        d = s.devices[0]
        g = trace_reduce.gaps_ns(d.intervals(), s.lo, s.hi)
        order = np.argsort(-(g[:, 1] - g[:, 0]), kind="stable")[:top]
        # ops by end, the innermost (latest start) last among equal ends
        by_end = np.lexsort((d.starts, d.ends))
        out = []
        for lo, hi in g[order]:
            mid = 0.5 * (lo + hi)
            label = next((sp[0] for sp in s.spans
                          if sp[1] <= mid <= sp[2]), "none")
            k = np.searchsorted(d.ends[by_end], lo, side="right") - 1
            if k >= 0:
                op = d.names[d.codes[by_end[k]]]
                label += "@" + (innermost(self.scope_of(op)) or "unscoped")
            out.append((label, (hi - lo) / 1e9))
        return out

    def breakdown(self, top: int = 10) -> dict:
        """The summary's breakdown, each op as ``<innermost scope>:<op>``
        and each gap labelled as :meth:`idle_gaps`."""
        ops = sorted(self.summary.op_totals().items(), key=lambda kv: -kv[1])
        return {"device_ops": [[self._labelled(k), v]
                               for k, v in ops[:top]],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps(top)]}
