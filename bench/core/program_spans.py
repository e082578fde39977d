"""The system's own host spans in a profiler trace, and the numbers that
the program-span metrics read from them.

The system under test opens ``jax.profiler.TraceAnnotation`` spans named
``repro.<layer>.<phase>`` at its layer boundaries (``docs/ARCHITECTURE.md``
lists them); their keyword arguments arrive as the event's stats.  They
lie on the profiler's host plane, on the device planes' clock, so they
can name what the host was doing in each gap of the device.

A span here is ``(name, start, end, args)``: seconds on the trace's
clock and a dict of the span's arguments.  ``bench/core/trace.py`` keeps
only the benchmark's own ``bench.*`` spans; ``load_program_spans`` reads
the ``repro.*`` ones from the same ``.xplane.pb``.
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

from bench.core.trace import _union

PREFIX = "repro."
OUTSIDE = "outside bench spans"
TOP = 10

Span = Tuple[str, float, float, dict]


def load_program_spans(trace_dir: str,
                       window: Optional[Tuple[float, float]] = None
                       ) -> List[Span]:
    """The ``repro.*`` host spans of the newest ``.xplane.pb`` under
    ``trace_dir``, in order of their start; those that overlap ``window``
    where one is given."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    a = ev.start_ns * 1e-9
                    spans.append((ev.name, a, a + ev.duration_ns * 1e-9,
                                  dict(ev.stats)))
    if window is not None:
        spans = [s for s in spans if s[2] > window[0] and s[1] < window[1]]
    return sorted(spans, key=lambda s: (s[1], -s[2]))


def _named(spans: Sequence[Span], name: str) -> List[Span]:
    return [s for s in spans if s[0] == name]


def span_s(spans: Sequence[Span], name: str) -> float:
    """Summed seconds of the spans called ``name``."""
    return sum(b - a for _, a, b, _ in _named(spans, name))


def span_count(spans: Sequence[Span], name: str) -> int:
    return len(_named(spans, name))


def nested_count(spans: Sequence[Span], inner: str, outer: str) -> int:
    """How many ``inner`` spans lie inside an ``outer`` span."""
    outers = sorted((a, b) for _, a, b, _ in _named(spans, outer))
    starts = [a for a, _ in outers]
    n = 0
    for _, a, b, _ in _named(spans, inner):
        i = bisect.bisect_right(starts, a) - 1
        n += i >= 0 and b <= outers[i][1]
    return n


def weighted_arg(spans: Sequence[Span], name: str, key: str,
                 weight: str) -> Optional[float]:
    """The mean of argument ``key`` over the spans called ``name``,
    weighted by their argument ``weight``; None where they weigh
    nothing."""
    pairs = [(s[3][key], s[3][weight]) for s in _named(spans, name)
             if key in s[3] and weight in s[3]]
    total = sum(w for _, w in pairs)
    if not total:
        return None
    return sum(v * w for v, w in pairs) / total


def covered_share(spans: Sequence[Span], parent: str,
                  children: Sequence[str]) -> Optional[float]:
    """The share of the ``parent`` spans' time during which one of the
    ``children`` spans is open; None where there is no ``parent``."""
    kids = _union([(a, b) for n, a, b, _ in spans if n in children])
    starts = [a for a, _ in kids]
    total = covered = 0.0
    for _, a, b, _ in _named(spans, parent):
        total += b - a
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(kids) and kids[i][0] < b:
            covered += max(0.0, min(b, kids[i][1]) - max(a, kids[i][0]))
            i += 1
    return covered / total if total else None


def _innermost(spans: Sequence[Span]):
    """A function from a time to the name of the innermost span open at
    it.  Spans of one thread nest, so the innermost span open at ``t`` is
    the span that started last before ``t`` or one of its ancestors."""
    order = sorted(spans, key=lambda s: (s[1], -s[2]))
    starts = [s[1] for s in order]
    parent: List[int] = []
    stack: List[int] = []
    for k, (_, a, _, _) in enumerate(order):
        while stack and order[stack[-1]][2] <= a:
            stack.pop()
        parent.append(stack[-1] if stack else -1)
        stack.append(k)

    def at(t: float) -> str:
        k = bisect.bisect_right(starts, t) - 1
        while k >= 0 and order[k][2] <= t:
            k = parent[k]
        return order[k][0] if k >= 0 else OUTSIDE

    return at


def idle_gaps(trace, spans: Sequence[Span]) -> List[list]:
    """The idle time of the first chip by what the host was doing: as
    ``Trace.breakdown``'s ``idle_gaps``, but each gap goes to the
    innermost span open at its middle of either family, a program span
    or the benchmark's own, so that a ``bench.*`` name is left only
    where no program span was open."""
    if not trace.ops:
        return []
    at = _innermost([(n, a, b, {}) for n, a, b in trace.spans]
                    + list(spans))
    busy = _union([(o.start, o.end) for o in trace.ops[0]])
    gaps: Dict[str, float] = {}
    t = trace.window[0]
    for a, b in busy + [(trace.window[1], trace.window[1])]:
        if a > t:
            who = at(0.5 * (t + a))
            gaps[who] = gaps.get(who, 0.0) + a - t
        t = max(t, b)
    return sorted(([k, v] for k, v in gaps.items()),
                  key=lambda kv: -kv[1])[:TOP]
