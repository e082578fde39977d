"""Plain reference of the best-fit (Linear) PLA method (paper §3.5).

Greedy longest runs: each run keeps the least-squares line of its points
(Welford sums) while that line stays within ``eps`` of every point, as
checked against the two hull envelopes of the error intervals.  A run
also ends at ``max_run`` points.  Sequential float64 Python, written from
the paper's description; it imports nothing of the system under test.
"""

from __future__ import annotations

from typing import Optional

from .geometry import (DisjointKnot, HullChain, JointKnot, Line,
                       MethodOutput, Segment)


class LinearRun:
    def __init__(self, t: float, y: float, eps: float):
        self.eps = eps
        self.n = 1
        self.mt = t
        self.my = y
        self.stt = 0.0
        self.sty = 0.0
        self.env_lo = HullChain(upper=True)
        self.env_hi = HullChain(upper=False)
        self.env_lo.add((t, y - eps))
        self.env_hi.add((t, y + eps))
        self.valid_line = Line(0.0, y)

    def try_add(self, t: float, y: float) -> bool:
        n1 = self.n + 1
        dt = t - self.mt
        dy = y - self.my
        mt1 = self.mt + dt / n1
        my1 = self.my + dy / n1
        stt1 = self.stt + dt * (t - mt1)
        sty1 = self.sty + dt * (y - my1)
        a = sty1 / stt1 if stt1 > 0 else 0.0
        line = Line(a, my1 - a * mt1)
        lo_ok = line(t) >= y - self.eps - 1e-12 and \
            self.env_lo.line_clears(line)
        hi_ok = line(t) <= y + self.eps + 1e-12 and \
            self.env_hi.line_clears(line)
        if not (lo_ok and hi_ok):
            return False
        self.n, self.mt, self.my, self.stt, self.sty = n1, mt1, my1, stt1, sty1
        self.env_lo.add((t, y - self.eps))
        self.env_hi.add((t, y + self.eps))
        self.valid_line = line
        return True

    @property
    def count(self) -> int:
        return self.n

    def line(self) -> Line:
        return self.valid_line


def run_greedy(run_cls, ts, ys, eps: float,
               max_run: Optional[int]) -> MethodOutput:
    """Longest runs, each restarted from the point that broke the last;
    knots as the implicit protocol streams them (an opening joint knot,
    a disjoint knot at each break, a closing joint knot)."""
    n = len(ts)
    segments, knots = [], []
    run = run_cls(float(ts[0]), float(ys[0]), eps)
    i0 = 0
    prev_line = None

    def close(i1: int) -> None:
        nonlocal prev_line
        line = run.line()
        segments.append(Segment(i0, i1, line))
        tb = float(ts[i0])
        if prev_line is None:
            knots.append(JointKnot(tb, line(tb)))
        else:
            knots.append(DisjointKnot(tb, prev_line(tb), line(tb)))
        prev_line = line

    for i in range(1, n):
        t, y = float(ts[i]), float(ys[i])
        hit_cap = max_run is not None and run.count >= max_run
        if not hit_cap and run.try_add(t, y):
            continue
        close(i)
        run = run_cls(t, y, eps)
        i0 = i
    close(n)
    t_end = float(ts[n - 1])
    knots.append(JointKnot(t_end, prev_line(t_end)))
    return MethodOutput(segments, knots)


def run(ts, ys, eps: float, max_run: Optional[int] = None) -> MethodOutput:
    return run_greedy(LinearRun, ts, ys, eps, max_run)
