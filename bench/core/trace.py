"""Reduction of a profiler trace of the window to the numbers the
per-layer metrics read.

A traced run wraps its window in ``jax.profiler`` and a host span named
``bench.window``; the benchmark's own code marks its calls into the
system with host spans named ``bench.<system>.<call>``.  From the
``.xplane.pb`` the reduction keeps:

- the device operations of each chip used (the ``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane): name, start, end and the XLA module that
  ran them (the event's ``hlo_module``, else the ``XLA Modules`` event
  around it);
- the host spans whose names start with ``bench.``;
- the system's own host spans, ``repro.*``, with their arguments, as
  ``Trace.program_spans`` (``bench/core/program_spans.py``).

Every time is in seconds on the trace's clock.  A cell may run on
several chips: device busy time is the union of a chip's operation
intervals inside the window, and a kernel's time the union of its
operations' intervals on a chip, each averaged over the chips used, so a
per-chip quantity (a shard's bytes) goes over them.  The first chip's
idle time goes, piece by piece, to the innermost span open in it, of
either family.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Callable, Dict, List, Sequence, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
PROGRAM_PREFIX = "repro."
OUTSIDE = "outside bench spans"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
TOP = 10


@dataclasses.dataclass
class Op:
    name: str
    start: float
    end: float
    module: str = ""


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _innermost(spans: Sequence[tuple]) -> Callable[[float], str]:
    """A function from a time to the name of the innermost of ``spans``
    (tuples that start ``name, start, end``) open at it.  Spans of one
    thread nest, so the innermost span open at ``t`` is the span that
    started last before ``t`` or one of its ancestors."""
    order = sorted(spans, key=lambda s: (s[1], -s[2]))
    starts = [s[1] for s in order]
    parent: List[int] = []
    stack: List[int] = []
    for k, s in enumerate(order):
        while stack and order[stack[-1]][2] <= s[1]:
            stack.pop()
        parent.append(stack[-1] if stack else -1)
        stack.append(k)

    def at(t: float) -> str:
        k = bisect.bisect_right(starts, t) - 1
        while k >= 0 and order[k][2] <= t:
            k = parent[k]
        return order[k][0] if k >= 0 else OUTSIDE

    return at


class Trace:
    """The reduced trace: a window, device operations per chip, the
    benchmark's host spans ``(name, start, end)`` and the program's
    ``(name, start, end, args)``."""

    def __init__(self, window: Tuple[float, float],
                 ops: Sequence[Sequence[Op]],
                 spans: Sequence[Tuple[str, float, float]],
                 program_spans: Sequence[tuple] = ()):
        self.window = window
        lo, hi = window
        self.ops = [[Op(o.name, max(o.start, lo), min(o.end, hi), o.module)
                     for o in dev if o.end > lo and o.start < hi]
                    for dev in ops]
        self.spans = [s for s in spans if s[2] > lo and s[1] < hi]
        self.program_spans = list(program_spans)

    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def _busy(self, dev: int) -> List[Tuple[float, float]]:
        return _union([(o.start, o.end) for o in self.ops[dev]])

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.ops:
            return 0.0
        return sum(sum(b - a for a, b in self._busy(d))
                   for d in range(len(self.ops))) / len(self.ops)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s()

    def op_s(self, match: Callable[[Op], bool]) -> float:
        """Seconds in which an operation that ``match`` selects ran,
        averaged over the chips: the union of their intervals, so that an
        operation nested in another (a fusion inside a ``while``) counts
        once."""
        if not self.ops:
            return 0.0
        return sum(sum(b - a for a, b in _union(
            [(o.start, o.end) for o in dev if match(o)]))
            for dev in self.ops) / len(self.ops)

    def op_count(self, match: Callable[[Op], bool]) -> int:
        return sum(1 for dev in self.ops for o in dev if match(o))

    def idle_gaps(self) -> List[list]:
        """The idle time of the first chip by what the host was doing:
        each gap is cut where a span opens or closes, and each piece goes
        to the innermost span open through it, of the program's or the
        benchmark's own, so that a ``bench.*`` name is left only where no
        program span was open.  (On several chips a chip can idle through
        most of a push in one gap.)"""
        if not self.ops:
            return []
        spans = self.spans + self.program_spans
        at = _innermost(spans)
        cuts = sorted({x for s in spans for x in s[1:3]})
        gaps: Dict[str, float] = {}
        t = self.window[0]
        for a, b in self._busy(0) + [(self.window[1], self.window[1])]:
            if a > t:
                edges = [t] + cuts[bisect.bisect_right(cuts, t):
                                   bisect.bisect_left(cuts, a)] + [a]
                for u, v in zip(edges, edges[1:]):
                    who = at(0.5 * (u + v))
                    gaps[who] = gaps.get(who, 0.0) + v - u
            t = max(t, b)
        return sorted(([k, v] for k, v in gaps.items()),
                      key=lambda kv: -kv[1])[:TOP]

    def breakdown(self) -> dict:
        """The device operations that took most time, and the idle time
        on the first chip by what the host was doing (``idle_gaps``)."""
        by_op: Dict[str, float] = {}
        for dev in self.ops:
            for o in dev:
                by_op[o.name] = by_op.get(o.name, 0.0) + o.end - o.start
        n = max(len(self.ops), 1)
        device_ops = sorted(([k, v / n] for k, v in by_op.items()),
                            key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": device_ops, "idle_gaps": self.idle_gaps()}


_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def _stat(ev, key: str) -> str:
    for k, v in ev.stats:
        if k == key:
            return str(v)
    return ""


def _module_at(modules, t_ns) -> str:
    """The XLA module that was running at ``t_ns``, for an operation whose
    event does not name it."""
    i = bisect.bisect_right(modules, (t_ns, float("inf"), "")) - 1
    if i >= 0 and modules[i][0] <= t_ns < modules[i][1]:
        return modules[i][2]
    return ""


def _newest_profile(trace_dir: str):
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(max(paths, key=os.path.getmtime))


def read_profile(trace_dir: str):
    """One pass over the newest ``.xplane.pb`` under ``trace_dir``: the
    operations of each chip by its index, the window, the benchmark's
    spans and the program's (in order of their start)."""
    devices, spans, program, window = {}, [], [], None
    for plane in _newest_profile(trace_dir).planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    modules = sorted((ev.start_ns, ev.start_ns
                                      + ev.duration_ns, ev.name)
                                     for ev in line.events)
                elif line.name == OP_LINE:
                    ops = [(ev.name, ev.start_ns, ev.duration_ns,
                            _stat(ev, "hlo_module")) for ev in line.events]
            devices[int(m.group(1))] = [
                Op(name, a * 1e-9, (a + d) * 1e-9,
                   module or _module_at(modules, a))
                for name, a, d, module in ops]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    a = ev.start_ns * 1e-9
                    b = a + ev.duration_ns * 1e-9
                    if ev.name == WINDOW_SPAN:
                        window = (a, b)
                    elif ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, a, b))
                    elif ev.name.startswith(PROGRAM_PREFIX):
                        program.append((ev.name, a, b, dict(ev.stats)))
    program.sort(key=lambda s: (s[1], -s[2]))
    return devices, window, spans, program


def load_trace(trace_dir: str, n_devices: int) -> Trace:
    """Read the ``.xplane.pb`` a traced window wrote under ``trace_dir``:
    the first ``n_devices`` chips' operations, the benchmark's spans and
    the program's."""
    devices, window, spans, program = read_profile(trace_dir)
    if window is None:
        raise ValueError("the trace holds no bench.window span")
    ops = [devices[k] for k in sorted(devices)[:n_devices]]
    lo, hi = window
    return Trace(window, ops, spans,
                 [s for s in program if s[2] > lo and s[1] < hi])
