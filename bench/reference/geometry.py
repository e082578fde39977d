"""Lines, segments, knots and the two feasible-line structures that the
plain PLA references share.

A copy, kept with the benchmark, of the sequential float64 geometry of
the paper's methods (Duvignau et al. 2018, §3): a line through the error
intervals ``[y - eps, y + eps]`` of a run of points, kept either as a
wedge of slopes through a fixed origin (:class:`SlopeWedge`) or as the
two extreme lines of a free-origin fit over convex hull chains
(:class:`HullFitter`).  Nothing here imports the system under test.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Tuple

NUM_SLACK = 1e-12  # numerical slack of every feasibility test


@dataclasses.dataclass
class Line:
    """``y = a * t + b``."""

    a: float
    b: float

    def __call__(self, t: float) -> float:
        return self.a * t + self.b

    @staticmethod
    def through(p, q) -> "Line":
        (t0, y0), (t1, y1) = p, q
        a = (y1 - y0) / (t1 - t0)
        return Line(a, y0 - a * t0)


@dataclasses.dataclass
class Segment:
    """Covers input indices ``[i0, i1)`` with ``line``."""

    i0: int
    i1: int
    line: Line

    @property
    def n(self) -> int:
        return self.i1 - self.i0


@dataclasses.dataclass
class JointKnot:
    """``(t, y)``: the shared end of two consecutive segments."""

    t: float
    y: float


@dataclasses.dataclass
class DisjointKnot:
    """``(t, y1, y2)``: one segment ends at ``(t, y1)``, the next starts at
    ``(t, y2)``."""

    t: float
    y1: float
    y2: Optional[float]


@dataclasses.dataclass
class MethodOutput:
    segments: List[Segment]
    knots: List[object]


def _cross(o, a, b) -> float:
    return (a[0] - o[0]) * (b[1] - o[1]) - (a[1] - o[1]) * (b[0] - o[0])


class HullChain:
    """Incremental upper (``upper=True``) or lower convex hull of points
    with increasing t."""

    def __init__(self, upper: bool):
        self.upper = upper
        self.pts: List[Tuple[float, float]] = []

    def add(self, p) -> None:
        pts = self.pts
        if self.upper:
            while len(pts) >= 2 and _cross(pts[-2], pts[-1], p) >= 0:
                pts.pop()
        else:
            while len(pts) >= 2 and _cross(pts[-2], pts[-1], p) <= 0:
                pts.pop()
        pts.append(p)

    def line_clears(self, line: Line, tol: float = NUM_SLACK) -> bool:
        """The line lies above (upper) or below (lower) every vertex."""
        if self.upper:
            return all(line(t) >= y - tol for (t, y) in self.pts)
        return all(line(t) <= y + tol for (t, y) in self.pts)


class SlopeWedge:
    """Feasible slopes of lines through a fixed origin."""

    def __init__(self, origin_t: float, origin_y: float):
        self.ot = origin_t
        self.oy = origin_y
        self.slo = -math.inf
        self.shi = math.inf

    def _bounds(self, t: float, lo: float, hi: float):
        dt = t - self.ot
        if dt == 0.0:
            return (-math.inf, math.inf)
        b1 = (lo - self.oy) / dt
        b2 = (hi - self.oy) / dt
        return (b1, b2) if b1 <= b2 else (b2, b1)

    def add(self, t: float, lo: float, hi: float) -> None:
        nlo, nhi = self._bounds(t, lo, hi)
        self.slo = max(self.slo, nlo)
        self.shi = min(self.shi, nhi)

    def mid_line(self) -> Line:
        if math.isinf(self.slo) and math.isinf(self.shi):
            a = 0.0
        elif math.isinf(self.slo):
            a = self.shi
        elif math.isinf(self.shi):
            a = self.slo
        else:
            a = 0.5 * (self.slo + self.shi)
        return Line(a, self.oy - a * self.ot)

    def value_range_at(self, tau: float):
        dt = tau - self.ot
        v1 = self.oy + self.slo * dt
        v2 = self.oy + self.shi * dt
        return (min(v1, v2), max(v1, v2))


class HullFitter:
    """The lines that cross every added interval, kept as the extreme-slope
    lines ``lmin``/``lmax`` and the two binding hull envelopes (O'Rourke
    1981)."""

    def __init__(self) -> None:
        self.env_lo = HullChain(upper=True)
        self.env_hi = HullChain(upper=False)
        self.constraints: List[Tuple[float, float, float]] = []
        self.lmin: Optional[Line] = None
        self.lmax: Optional[Line] = None

    @property
    def n(self) -> int:
        return len(self.constraints)

    def can_add(self, t: float, lo: float, hi: float) -> bool:
        if self.n <= 1:
            return True
        return (self.lmax(t) >= lo - NUM_SLACK) and \
            (self.lmin(t) <= hi + NUM_SLACK)

    def value_range_at(self, tau: float):
        if self.n == 0:
            return (-math.inf, math.inf)
        if self.n == 1:
            t, lo, hi = self.constraints[0]
            return (lo, hi) if tau == t else (-math.inf, math.inf)
        v1, v2 = self.lmin(tau), self.lmax(tau)
        return (min(v1, v2), max(v1, v2))

    def add(self, t: float, lo: float, hi: float) -> None:
        if self.n == 1:
            t0, lo0, hi0 = self.constraints[0]
            self.lmax = Line.through((t0, lo0), (t, hi))
            self.lmin = Line.through((t0, hi0), (t, lo))
        elif self.n >= 2:
            if self.lmax(t) > hi:
                best = None
                for (qt, qy) in self.env_lo.pts:
                    if qt < t:
                        a = (hi - qy) / (t - qt)
                        if best is None or a < best:
                            best = a
                if best is not None:
                    self.lmax = Line(best, hi - best * t)
            if self.lmin(t) < lo:
                best = None
                for (qt, qy) in self.env_hi.pts:
                    if qt < t:
                        a = (lo - qy) / (t - qt)
                        if best is None or a > best:
                            best = a
                if best is not None:
                    self.lmin = Line(best, lo - best * t)
        self.constraints.append((t, lo, hi))
        self.env_lo.add((t, lo))
        self.env_hi.add((t, hi))

    def mid_line(self) -> Line:
        """The mean of the extreme lines (paper, footnote 2): the midpoint
        of two feasible lines in (slope, intercept) space, itself
        feasible since the feasible set is convex."""
        if self.n == 0:
            return Line(0.0, 0.0)
        if self.n == 1:
            _, lo, hi = self.constraints[0]
            return Line(0.0, 0.5 * (lo + hi))
        return Line(0.5 * (self.lmin.a + self.lmax.a),
                    0.5 * (self.lmin.b + self.lmax.b))
