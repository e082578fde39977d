"""Plain reference of the Angle PLA method (paper §3.1, after Xie et al.).

Greedy longest runs, each a wedge of lines through a fixed origin.  The
first two points of a run fix the origin: the two extreme lines through
their error intervals, the steepest (from ``(t0, y0 - eps)`` to
``(t1, y1 + eps)``) and the shallowest (from ``(t0, y0 + eps)`` to
``(t1, y1 - eps)``), cross there, and their slopes bound the wedge.  Each
later point narrows the slope interval to the lines through the origin
that pass through its error interval; the run ends at the first point
that would leave the interval empty, or at ``max_run`` points, and that
point starts the next run.  A run's line is the one of mid slope through
the origin.

Departures from the paper, none of which changes a line: the emptiness
test allows ``NUM_SLACK`` (1e-12) of float64 rounding, and a run of one
point (a stream's last) is the horizontal line through it.  Sequential
float64 Python, written from the paper's description; it imports nothing
of the system under test.
"""

from __future__ import annotations

from typing import Optional

from .geometry import NUM_SLACK, Line, MethodOutput, SlopeWedge
from .linear import run_greedy


class AngleRun:
    def __init__(self, t: float, y: float, eps: float):
        self.eps = eps
        self.first = (t, y)
        self.wedge: Optional[SlopeWedge] = None
        self.count = 1

    def try_add(self, t: float, y: float) -> bool:
        eps = self.eps
        if self.wedge is None:
            (t0, y0) = self.first
            steep = Line.through((t0, y0 - eps), (t, y + eps))
            shallow = Line.through((t0, y0 + eps), (t, y - eps))
            # The slopes differ by 4 eps / (t - t0) > 0, so the lines cross.
            ox = (shallow.b - steep.b) / (steep.a - shallow.a)
            self.wedge = SlopeWedge(ox, steep(ox))
            self.wedge.slo, self.wedge.shi = shallow.a, steep.a
        else:
            w = self.wedge
            lo = (y - eps - w.oy) / (t - w.ot)
            hi = (y + eps - w.oy) / (t - w.ot)
            if max(w.slo, lo) > min(w.shi, hi) + NUM_SLACK:
                return False
            w.add(t, y - eps, y + eps)
        self.count += 1
        return True

    def line(self) -> Line:
        if self.wedge is None:
            return Line(0.0, self.first[1])
        return self.wedge.mid_line()


def run(ts, ys, eps: float, max_run: Optional[int] = None) -> MethodOutput:
    return run_greedy(AngleRun, ts, ys, eps, max_run)
