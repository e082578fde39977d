"""The six PLA methods evaluated by the paper, as exact sequential code.

========== ==================================================== ============
Name       Strategy                                              Knots
========== ==================================================== ============
SwingFilter greedy wedge, origin = previous segment endpoint     joint
Angle       greedy wedge, origin = extreme-lines intersection    disjoint
Disjoint    optimal #segments, free origin (convex hulls)        disjoint
Continuous  connected polyline, gate-deferred knot choice        joint
MixedPLA    disjoint segments + joint-merge where feasible       mixed
Linear      greedy best-fit (least squares) line, hull-checked   disjoint
========== ==================================================== ============

All methods guarantee ``|y_i - reconstruct(t_i)| <= eps`` for every input
point.  ``max_run`` optionally caps the number of points per segment (the
streaming protocols of §5.2 require 256 / 127); when the cap is hit the
method finalizes the segment immediately and restarts — this is what gives
the protocols their bounded worst-case latency.

Implementation notes vs. the paper (also see DESIGN.md):

- *Continuous* implements the Hakimi–Schmeichel idea with a vertical *gate*
  carried between segments and knot selection deferred until the following
  segment breaks (which is exactly why the paper measures one extra segment
  of latency for this method).  The emitted polyline is always connected and
  eps-correct; the knot choice ("chosen to offer the most possibilities",
  paper footnote 3) is the midline evaluation at the gate.
- *MixedPLA* implements Luo et al.'s joint/disjoint size trade-off as a
  single-segment-lookahead merge over the optimal disjoint segmentation
  (join when the two adjacent feasible-value ranges overlap at the boundary
  timestamp).  Its output size is never worse than Disjoint's (a joint knot
  replaces a disjoint knot only when feasible, saving one field), and its
  output delay matches the 2–4 segment early-output delays reported by Luo
  et al.; global DP optimality is traded for bounded delay.
"""

from __future__ import annotations

import math
from typing import List, Optional, Tuple

from .hulls import HullFitter, SlopeWedge, _HullChain
from .types import DisjointKnot, JointKnot, Line, MethodOutput, Segment

__all__ = [
    "run_swing",
    "run_angle",
    "run_disjoint",
    "run_continuous",
    "run_mixed",
    "run_linear",
    "METHODS",
]


def _check_input(ts, ys) -> int:
    n = len(ts)
    if len(ys) != n:
        raise ValueError("ts and ys must have equal length")
    for i in range(1, n):
        if not ts[i] > ts[i - 1]:
            raise ValueError(f"timestamps must be strictly increasing at {i}")
    return n


def _horizontal(y: float) -> Line:
    return Line(0.0, y)


# ---------------------------------------------------------------------------
# SwingFilter — greedy joint knots, O(1)/point
# ---------------------------------------------------------------------------

def run_swing(ts, ys, eps: float, max_run: Optional[int] = None) -> MethodOutput:
    n = _check_input(ts, ys)
    segments: List[Segment] = []
    knots: List[object] = []
    if n == 0:
        return MethodOutput(segments, knots)

    origin = (float(ts[0]), float(ys[0]))
    knots.append(JointKnot(origin[0], origin[1], emitted_at=0))
    wedge = SlopeWedge(*origin)
    i0 = 0  # first input index covered by the current segment
    i = 1
    while i < n:
        t, y = float(ts[i]), float(ys[i])
        run_len = i - i0
        hit_cap = max_run is not None and run_len >= max_run
        if not hit_cap and wedge.can_add(t, y - eps, y + eps):
            wedge.add(t, y - eps, y + eps)
            i += 1
            continue
        # Break-up (or cap) at index i: finalize segment over [i0, i).
        line = wedge.mid_line()
        end_t = float(ts[i - 1])
        end_y = line(end_t)
        segments.append(Segment(i0, i, line, finalized_at=i))
        knots.append(JointKnot(end_t, end_y, emitted_at=i))
        origin = (end_t, end_y)
        wedge = SlopeWedge(*origin)
        wedge.add(t, y - eps, y + eps)  # always feasible: single constraint
        i0 = i
        i += 1
    # Flush the trailing segment (a fresh wedge yields the horizontal line
    # through the origin, which is exact for single-point runs).
    line = wedge.mid_line()
    segments.append(Segment(i0, n, line, finalized_at=n - 1))
    knots.append(JointKnot(float(ts[n - 1]), line(float(ts[n - 1])),
                           emitted_at=n - 1))
    return MethodOutput(segments, knots)


# ---------------------------------------------------------------------------
# Greedy disjoint-knot drivers (Angle / Disjoint / Linear share the frame)
# ---------------------------------------------------------------------------

class _AngleRun:
    """Per-run state for the Angle method (Xie et al. variant)."""

    def __init__(self, t: float, y: float, eps: float):
        self.eps = eps
        self.first = (t, y)
        self.wedge: Optional[SlopeWedge] = None
        self.count = 1

    def try_add(self, t: float, y: float) -> bool:
        eps = self.eps
        if self.wedge is None:
            # Second point: build extreme lines through both error segments
            # and anchor the wedge at their intersection (paper Fig. 3).
            (t0, y0) = self.first
            lmax = Line.through((t0, y0 - eps), (t, y + eps))
            lmin = Line.through((t0, y0 + eps), (t, y - eps))
            if abs(lmax.a - lmin.a) < 1e-300:
                px = 0.5 * (t0 + t)
            else:
                px = (lmin.b - lmax.b) / (lmax.a - lmin.a)
            py = lmax.a * px + lmax.b
            w = SlopeWedge(px, py)
            w.slo, w.shi = lmin.a, lmax.a
            self.wedge = w
            self.count = 2
            return True
        if self.wedge.can_add(t, y - eps, y + eps):
            self.wedge.add(t, y - eps, y + eps)
            self.count += 1
            return True
        return False

    def line(self) -> Line:
        if self.wedge is None:
            return _horizontal(self.first[1])
        return self.wedge.mid_line()


class _HullRun:
    """Per-run state for the optimal Disjoint method."""

    def __init__(self, t: float, y: float, eps: float):
        self.eps = eps
        self.fitter = HullFitter()
        self.fitter.add(t, y - eps, y + eps)
        self.count = 1

    def try_add(self, t: float, y: float) -> bool:
        eps = self.eps
        if self.fitter.can_add(t, y - eps, y + eps):
            self.fitter.add(t, y - eps, y + eps)
            self.count += 1
            return True
        return False

    def line(self) -> Line:
        return self.fitter.mid_line()


class _LinearRun:
    """Per-run state for the best-fit (Linear) method, new in the paper.

    Maintains the running simple-regression sums plus the two partial convex
    hulls used to verify the best-fit line against the error tolerance in
    (amortized) sub-linear time (paper §3.5, Fig. 7).
    """

    def __init__(self, t: float, y: float, eps: float):
        self.eps = eps
        self.n = 1
        self.mt = t
        self.my = y
        self.stt = 0.0  # sum (t - mt)^2, Welford-style
        self.sty = 0.0  # sum (t - mt)(y - my)
        self.env_lo = _HullChain(upper=True)
        self.env_hi = _HullChain(upper=False)
        self.env_lo.add((t, y - eps))
        self.env_hi.add((t, y + eps))
        self.valid_line: Line = _horizontal(y)

    def try_add(self, t: float, y: float) -> bool:
        # Tentative update of the regression sums (Welford update).
        n1 = self.n + 1
        dt = t - self.mt
        dy = y - self.my
        mt1 = self.mt + dt / n1
        my1 = self.my + dy / n1
        stt1 = self.stt + dt * (t - mt1)
        sty1 = self.sty + dt * (y - my1)
        a = sty1 / stt1 if stt1 > 0 else 0.0
        line = Line(a, my1 - a * mt1)
        # Hull-based validity check of the best-fit line (paper Fig. 7):
        # above the upper hull of lower endpoints, below the lower hull of
        # upper endpoints — with the new point's error segment included.
        lo_ok = line(t) >= y - self.eps - 1e-12 and self.env_lo.line_clears(line)
        hi_ok = line(t) <= y + self.eps + 1e-12 and self.env_hi.line_clears(line)
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


def _run_greedy_disjoint(run_cls, ts, ys, eps: float,
                         max_run: Optional[int]) -> MethodOutput:
    """Common greedy frame: longest run, restart from the break-up point."""
    n = _check_input(ts, ys)
    segments: List[Segment] = []
    knots: List[object] = []
    if n == 0:
        return MethodOutput(segments, knots)

    run = run_cls(float(ts[0]), float(ys[0]), eps)
    i0 = 0
    prev_line: Optional[Line] = None  # line of the last finalized segment
    i = 1
    while i < n:
        t, y = float(ts[i]), float(ys[i])
        hit_cap = max_run is not None and run.count >= max_run
        if not hit_cap and run.try_add(t, y):
            i += 1
            continue
        # Finalize [i0, i); restart from the break-up point i (or, on cap,
        # from the first un-covered point which is also i).
        line = run.line()
        fin = i  # decision is made while processing input index i
        segments.append(Segment(i0, i, line, finalized_at=fin))
        if prev_line is None:
            knots.append(JointKnot(float(ts[i0]), line(float(ts[i0])),
                                   emitted_at=fin))
        else:
            tb = float(ts[i0])
            knots.append(DisjointKnot(tb, prev_line(tb), line(tb),
                                      emitted_at_first=segments[-2].finalized_at,
                                      emitted_at_second=fin))
        prev_line = line
        run = run_cls(t, y, eps)
        i0 = i
        i += 1
    # Trailing segment.
    line = run.line()
    segments.append(Segment(i0, n, line, finalized_at=n - 1))
    if prev_line is None:
        knots.append(JointKnot(float(ts[i0]), line(float(ts[i0])),
                               emitted_at=n - 1))
    else:
        tb = float(ts[i0])
        knots.append(DisjointKnot(tb, prev_line(tb), line(tb),
                                  emitted_at_first=segments[-2].finalized_at,
                                  emitted_at_second=n - 1))
    knots.append(JointKnot(float(ts[n - 1]), line(float(ts[n - 1])),
                           emitted_at=n - 1))
    return MethodOutput(segments, knots)


def run_angle(ts, ys, eps: float, max_run: Optional[int] = None) -> MethodOutput:
    return _run_greedy_disjoint(_AngleRun, ts, ys, eps, max_run)


def run_disjoint(ts, ys, eps: float, max_run: Optional[int] = None) -> MethodOutput:
    return _run_greedy_disjoint(_HullRun, ts, ys, eps, max_run)


def run_linear(ts, ys, eps: float, max_run: Optional[int] = None) -> MethodOutput:
    return _run_greedy_disjoint(_LinearRun, ts, ys, eps, max_run)


# ---------------------------------------------------------------------------
# Continuous — connected polyline with deferred knot choice
# ---------------------------------------------------------------------------

def run_continuous(ts, ys, eps: float, max_run: Optional[int] = None) -> MethodOutput:
    n = _check_input(ts, ys)
    segments: List[Segment] = []
    knots: List[object] = []
    if n == 0:
        return MethodOutput(segments, knots)

    # Gate: the vertical interval each new segment's line must cross.  The
    # first gate is simply the first point's error segment.
    gate: Tuple[float, float, float] = (float(ts[0]), float(ys[0]) - eps,
                                        float(ys[0]) + eps)
    fitter = HullFitter()
    fitter.add(*gate)
    i0 = 0                      # first *data* index of the current segment
    prev_knot: Optional[Tuple[float, float]] = None  # K_{s-1}
    pending: Optional[Tuple[int, int, Tuple[float, float]]] = None
    # pending = (i0, i1, K_left) of the segment whose line awaits K_right.

    def _fix_knot_and_flush(break_idx: int, last_idx: int):
        """At a break: pick the current segment's gate knot; flush previous."""
        nonlocal prev_knot, pending, gate, fitter, i0
        line_sel = fitter.mid_line()
        K = (gate[0], line_sel(gate[0]))
        if pending is not None:
            pi0, pi1, K_left = pending
            seg_line = Line.through(K_left, K)
            segments.append(Segment(pi0, pi1, seg_line, finalized_at=break_idx))
        knots.append(JointKnot(K[0], K[1], emitted_at=break_idx))
        # Rebuild the wedge of the current segment from the fixed knot K to
        # compute the next gate (feasible values at the last covered t).
        w = SlopeWedge(*K)
        for j in range(i0, last_idx + 1):
            w.add(float(ts[j]), float(ys[j]) - eps, float(ys[j]) + eps)
        glo, ghi = w.value_range_at(float(ts[last_idx]))
        return K, (float(ts[last_idx]), glo, ghi)

    i = 1
    while i < n:
        t, y = float(ts[i]), float(ys[i])
        run_len = i - i0
        hit_cap = max_run is not None and run_len >= max_run
        if not hit_cap and fitter.can_add(t, y - eps, y + eps):
            fitter.add(t, y - eps, y + eps)
            i += 1
            continue
        K, new_gate = _fix_knot_and_flush(break_idx=i, last_idx=i - 1)
        pending = (i0, i, K)
        gate = new_gate
        fitter = HullFitter()
        fitter.add(*gate)
        fitter.add(t, y - eps, y + eps)  # gate + 1 interval: always feasible
        i0 = i
        i += 1

    # End of stream: fix the last two knots and flush both pending segments.
    line_sel = fitter.mid_line()
    K = (gate[0], line_sel(gate[0]))
    if pending is not None:
        pi0, pi1, K_left = pending
        segments.append(Segment(pi0, pi1, Line.through(K_left, K),
                                finalized_at=n - 1))
    knots.append(JointKnot(K[0], K[1], emitted_at=n - 1))
    segments.append(Segment(i0, n, line_sel, finalized_at=n - 1))
    t_end = float(ts[n - 1])
    knots.append(JointKnot(t_end, line_sel(t_end), emitted_at=n - 1))
    return MethodOutput(segments, knots)


# ---------------------------------------------------------------------------
# MixedPLA — joint/disjoint size optimization (Luo et al. style)
# ---------------------------------------------------------------------------

def run_mixed(ts, ys, eps: float, max_run: Optional[int] = None) -> MethodOutput:
    n = _check_input(ts, ys)
    segments: List[Segment] = []
    knots: List[object] = []
    if n == 0:
        return MethodOutput(segments, knots)

    # Stage 1 state: greedy maximal disjoint runs (HullFitter).
    # Stage 2 state: previous finalized run awaiting its join decision.
    class _Run:
        def __init__(self, i0: int):
            self.i0 = i0
            self.i1 = i0 + 1
            self.fitter = HullFitter()
            self.left_knot: Optional[Tuple[float, float]] = None
            self.break_idx = -1

        def value_range_at(self, tau: float, n_pts_ts, n_pts_ys):
            if self.left_knot is None:
                return self.fitter.value_range_at(tau)
            w = SlopeWedge(*self.left_knot)
            for j in range(self.i0, self.i1):
                w.add(float(n_pts_ts[j]), float(n_pts_ys[j]) - eps,
                      float(n_pts_ys[j]) + eps)
            return w.value_range_at(tau)

        def chosen_line(self, n_pts_ts, n_pts_ys) -> Line:
            if self.left_knot is None:
                return self.fitter.mid_line()
            w = SlopeWedge(*self.left_knot)
            for j in range(self.i0, self.i1):
                w.add(float(n_pts_ts[j]), float(n_pts_ys[j]) - eps,
                      float(n_pts_ys[j]) + eps)
            return w.mid_line()

    def _new_run(i0: int) -> "_Run":
        r = _Run(i0)
        r.fitter.add(float(ts[i0]), float(ys[i0]) - eps, float(ys[i0]) + eps)
        return r

    prev: Optional[_Run] = None
    pending_dk: List[DisjointKnot] = []  # disjoint knot awaiting its y''

    def _emit_segment(seg: Segment) -> None:
        """Emit a segment; resolve the y'' of the knot on its left."""
        segments.append(seg)
        if pending_dk:
            dk = pending_dk.pop()
            dk.y2 = seg.line(dk.t)
            dk.emitted_at_second = seg.finalized_at

    def _decide(prev_run: _Run, cur_run: _Run, decision_idx: int):
        """Join prev|cur with a joint knot if feasible, else disjoint.

        A joint knot can never sit at the break point itself (the break
        condition separates the feasible value ranges there), so — as in
        Luo et al.'s optimal mixed PLA, which considers non-maximal
        segments — the candidate knot is placed at prev's *last* point,
        which then transfers to cur's coverage.
        """
        joined = False
        # A join hands cur prev's last point: a run already at max_run
        # stays disjoint so no segment exceeds max_run points.
        room = max_run is None or cur_run.i1 - cur_run.i0 < max_run
        if room and prev_run.i1 - prev_run.i0 >= 2:
            tau = float(ts[prev_run.i1 - 1])  # prev's last covered point
            plo, phi = prev_run.value_range_at(tau, ts, ys)
            clo, chi = cur_run.fitter.value_range_at(tau)
            lo, hi = max(plo, clo), min(phi, chi)
            if lo <= hi:  # joint knot feasible: shorten prev by one point
                v = 0.5 * (lo + hi)
                K = (tau, v)
                if prev_run.left_knot is not None:
                    line = Line.through(prev_run.left_knot, K)
                else:
                    w = SlopeWedge(*K)
                    for j in range(prev_run.i0, prev_run.i1 - 1):
                        w.add(float(ts[j]), float(ys[j]) - eps,
                              float(ys[j]) + eps)
                    line = w.mid_line()
                _emit_segment(Segment(prev_run.i0, prev_run.i1 - 1, line,
                                      finalized_at=decision_idx))
                knots.append(JointKnot(tau, v, emitted_at=decision_idx))
                cur_run.left_knot = K
                cur_run.i0 = prev_run.i1 - 1  # absorb the shared point
                joined = True
        if not joined:
            tau = float(ts[cur_run.i0])  # the break point
            line = prev_run.chosen_line(ts, ys)
            _emit_segment(Segment(prev_run.i0, prev_run.i1, line,
                                  finalized_at=decision_idx))
            # Disjoint knot at tau: y'' (= cur's start value) resolves when
            # cur's own line is chosen — i.e. at the *next* decision.
            dk = DisjointKnot(tau, line(tau), None,
                              emitted_at_first=decision_idx,
                              emitted_at_second=-1)
            knots.append(dk)
            pending_dk.append(dk)

    cur = _new_run(0)
    i = 1
    while i < n:
        t, y = float(ts[i]), float(ys[i])
        run_len = cur.i1 - cur.i0
        hit_cap = max_run is not None and run_len >= max_run
        if not hit_cap and cur.fitter.can_add(t, y - eps, y + eps):
            cur.fitter.add(t, y - eps, y + eps)
            cur.i1 = i + 1
            i += 1
            continue
        cur.break_idx = i
        if prev is None:
            # First run: its left end is free; emit the opening joint knot
            # once its line resolves (at this decision or later join).
            pass
        else:
            _decide(prev, cur, decision_idx=i)
        prev = cur
        cur = _new_run(i)
        i += 1

    # Final decisions at end of stream.
    if prev is not None:
        _decide(prev, cur, decision_idx=n - 1)
    line = cur.chosen_line(ts, ys)
    _emit_segment(Segment(cur.i0, cur.i1, line, finalized_at=n - 1))
    # Opening and closing joint knots for well-formed record streams.
    first_line = segments[0].line
    knots.insert(0, JointKnot(float(ts[0]), first_line(float(ts[0])),
                              emitted_at=segments[0].finalized_at))
    knots.append(JointKnot(float(ts[n - 1]), line(float(ts[n - 1])),
                           emitted_at=n - 1))
    return MethodOutput(segments, knots)


METHODS = {
    "swing": run_swing,
    "angle": run_angle,
    "disjoint": run_disjoint,
    "continuous": run_continuous,
    "mixed": run_mixed,
    "linear": run_linear,
}
