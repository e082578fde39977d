"""The system's own host spans in a profiler trace, and the numbers that
the program-span metrics read from them.

The system under test opens ``jax.profiler.TraceAnnotation`` spans named
``repro.<layer>.<phase>`` at its layer boundaries (``docs/ARCHITECTURE.md``
lists them); their keyword arguments arrive as the event's stats.  They
lie on the profiler's host plane, on the device planes' clock, so they
can name what the host was doing in each gap of the device.

A span here is ``(name, start, end, args)``: seconds on the trace's
clock and a dict of the span's arguments.  ``bench/core/trace.py:
load_trace`` keeps the window's as ``Trace.program_spans``, and
``Trace.idle_gaps`` names each idle gap of the device by the innermost
span of either family open in it.
"""

from __future__ import annotations

import bisect
from typing import List, Optional, Sequence, Tuple

from bench.core.trace import read_profile, _union

Span = Tuple[str, float, float, dict]


def load_program_spans(trace_dir: str) -> List[Span]:
    """The ``repro.*`` host spans of the newest ``.xplane.pb`` under
    ``trace_dir``, in order of their start; the trace need hold no
    window."""
    return read_profile(trace_dir)[3]


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
