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
- the host spans whose names start with ``bench.``.

Every time is in seconds on the trace's clock.  Device busy time is the
union of a chip's operation intervals inside the window, averaged over
the chips; a kernel's time is the sum of its operations' durations.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
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


class Trace:
    """The reduced trace: a window, device operations per chip, host
    spans ``(name, start, end)``."""

    def __init__(self, window: Tuple[float, float],
                 ops: Sequence[Sequence[Op]],
                 spans: Sequence[Tuple[str, float, float]]):
        self.window = window
        lo, hi = window
        self.ops = [[Op(o.name, max(o.start, lo), min(o.end, hi), o.module)
                     for o in dev if o.end > lo and o.start < hi]
                    for dev in ops]
        self.spans = [s for s in spans if s[2] > lo and s[1] < hi]

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

    def _host_at(self, t: float) -> str:
        """The innermost benchmark span open at ``t``."""
        best: Optional[Tuple[float, str]] = None
        for name, a, b in self.spans:
            if a <= t < b and (best is None or b - a < best[0]):
                best = (b - a, name)
        return best[1] if best else "outside bench spans"

    def breakdown(self) -> dict:
        """The device operations that took most time, and the idle time
        on the first chip by what the host was doing."""
        by_op: Dict[str, float] = {}
        for dev in self.ops:
            for o in dev:
                by_op[o.name] = by_op.get(o.name, 0.0) + o.end - o.start
        n = max(len(self.ops), 1)
        device_ops = sorted(([k, v / n] for k, v in by_op.items()),
                            key=lambda kv: -kv[1])[:TOP]
        gaps: Dict[str, float] = {}
        if self.ops:
            t = self.window[0]
            for a, b in self._busy(0) + [(self.window[1], self.window[1])]:
                if a > t:
                    who = self._host_at(0.5 * (t + a))
                    gaps[who] = gaps.get(who, 0.0) + a - t
                t = max(t, b)
        idle_gaps = sorted(([k, v] for k, v in gaps.items()),
                           key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": device_ops, "idle_gaps": idle_gaps}


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


def load_trace(trace_dir: str, n_devices: int) -> Trace:
    """Read the ``.xplane.pb`` a traced window wrote under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices, spans, window = {}, [], None
    for plane in pd.planes:
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
                    if ev.name.startswith(SPAN_PREFIX):
                        a = ev.start_ns * 1e-9
                        b = a + ev.duration_ns * 1e-9
                        if ev.name == WINDOW_SPAN:
                            window = (a, b)
                        else:
                            spans.append((ev.name, a, b))
    if window is None:
        raise ValueError("the trace holds no bench.window span")
    ops = [devices[k] for k in sorted(devices)[:n_devices]]
    return Trace(window, ops, spans)
