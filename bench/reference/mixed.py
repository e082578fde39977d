"""Plain reference of MixedPLA (paper §3.4, after Luo et al.).

Stage 1 finds greedy maximal disjoint runs (free-origin hull fit of the
error intervals, at most ``max_run`` points).  Stage 2 looks back one run:
at each break it joins the previous run to the current one with a joint
knot at the previous run's last point when the two runs' feasible values
there overlap (and the current run has room for the shared point), else
it leaves a disjoint knot at the break.  Sequential float64 Python,
written from the paper's description; it imports nothing of the system
under test.
"""

from __future__ import annotations

from typing import List, Optional

from .geometry import (DisjointKnot, HullFitter, JointKnot, Line,
                       MethodOutput, Segment, SlopeWedge)


def run(ts, ys, eps: float, max_run: Optional[int] = None) -> MethodOutput:
    n = len(ts)
    segments: List[Segment] = []
    knots: List[object] = []

    def wedge_over(origin, i0: int, i1: int) -> SlopeWedge:
        w = SlopeWedge(*origin)
        for j in range(i0, i1):
            w.add(float(ts[j]), float(ys[j]) - eps, float(ys[j]) + eps)
        return w

    class Run:
        def __init__(self, i0: int):
            self.i0 = i0
            self.i1 = i0 + 1
            self.fitter = HullFitter()
            self.fitter.add(float(ts[i0]), float(ys[i0]) - eps,
                            float(ys[i0]) + eps)
            self.left_knot = None

        def value_range_at(self, tau: float):
            if self.left_knot is None:
                return self.fitter.value_range_at(tau)
            return wedge_over(self.left_knot, self.i0,
                              self.i1).value_range_at(tau)

        def chosen_line(self) -> Line:
            if self.left_knot is None:
                return self.fitter.mid_line()
            return wedge_over(self.left_knot, self.i0, self.i1).mid_line()

    pending: List[DisjointKnot] = []   # disjoint knots awaiting y2

    def emit(seg: Segment) -> None:
        segments.append(seg)
        if pending:
            dk = pending.pop()
            dk.y2 = seg.line(dk.t)

    def decide(prev: Run, cur: Run) -> None:
        room = max_run is None or cur.i1 - cur.i0 < max_run
        if room and prev.i1 - prev.i0 >= 2:
            tau = float(ts[prev.i1 - 1])
            plo, phi = prev.value_range_at(tau)
            clo, chi = cur.fitter.value_range_at(tau)
            lo, hi = max(plo, clo), min(phi, chi)
            if lo <= hi:
                v = 0.5 * (lo + hi)
                if prev.left_knot is not None:
                    line = Line.through(prev.left_knot, (tau, v))
                else:
                    line = wedge_over((tau, v), prev.i0,
                                      prev.i1 - 1).mid_line()
                emit(Segment(prev.i0, prev.i1 - 1, line))
                knots.append(JointKnot(tau, v))
                cur.left_knot = (tau, v)
                cur.i0 = prev.i1 - 1
                return
        tau = float(ts[cur.i0])
        line = prev.chosen_line()
        emit(Segment(prev.i0, prev.i1, line))
        dk = DisjointKnot(tau, line(tau), None)
        knots.append(dk)
        pending.append(dk)

    prev: Optional[Run] = None
    cur = Run(0)
    for i in range(1, n):
        t, y = float(ts[i]), float(ys[i])
        hit_cap = max_run is not None and cur.i1 - cur.i0 >= max_run
        if not hit_cap and cur.fitter.can_add(t, y - eps, y + eps):
            cur.fitter.add(t, y - eps, y + eps)
            cur.i1 = i + 1
            continue
        if prev is not None:
            decide(prev, cur)
        prev, cur = cur, Run(i)
    if prev is not None:
        decide(prev, cur)
    line = cur.chosen_line()
    emit(Segment(cur.i0, cur.i1, line))
    t0, t_end = float(ts[0]), float(ts[n - 1])
    knots.insert(0, JointKnot(t0, segments[0].line(t0)))
    knots.append(JointKnot(t_end, line(t_end)))
    return MethodOutput(segments, knots)
