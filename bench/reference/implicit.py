"""Plain reference of the implicit protocol (paper §5.1, Luo et al.).

The knot stream as it is computed: a joint knot is ``(t, y)``, a disjoint
knot ``(-t, y1)`` followed, once the next segment's line is known, by its
bare ``y2``.  All fields are little-endian float64.  Values between two
consecutive knots lie on the line from the left knot's start value to the
right knot's end value.

A knot stream is compared by its *shape*: one ``(index, kind)`` entry per
knot, where ``index`` is the knot's sample index and ``kind`` is ``"j"``
(joint) or ``"d"`` (disjoint).
"""

from __future__ import annotations

import struct


def _walk(knots, ts):
    """Values at ``ts`` from ``(t, y_end, y_start_next)`` knot triples."""
    vals = []
    j = 0
    for t in ts:
        t = float(t)
        while j + 1 < len(knots) - 1 and t >= knots[j + 1][0]:
            j += 1
        (t0, _, y0), (t1, y1, _) = knots[j], knots[j + 1]
        vals.append(y1 if t1 == t0 else y0 + (y1 - y0) / (t1 - t0) * (t - t0))
    return vals


def _index(t: float, ts) -> int:
    return int(round((t - float(ts[0])) / (float(ts[1]) - float(ts[0]))))


def decode(data: bytes, ts):
    """``(values, shape)`` of an implicit byte stream."""
    knots, shape = [], []
    off = 0
    expect_y2 = False
    while off < len(data):
        if expect_y2:
            (y2,) = struct.unpack_from("<d", data, off)
            off += 8
            t, y1, _ = knots[-1]
            knots[-1] = (t, y1, y2)
            expect_y2 = False
            continue
        t, y = struct.unpack_from("<dd", data, off)
        off += 16
        if t >= 0:
            knots.append((t, y, y))
            shape.append((_index(t, ts), "j"))
        else:
            knots.append((-t, y, float("nan")))
            shape.append((_index(-t, ts), "d"))
            expect_y2 = True
    return _walk(knots, ts), shape


def expected(out, ts, ys):
    """``(values, shape)`` that a method output should be sent as."""
    triples, shape = [], []
    for k in out.knots:
        if hasattr(k, "y1"):
            triples.append((k.t, k.y1, k.y2))
            shape.append((_index(k.t, ts), "d"))
        else:
            triples.append((k.t, k.y, k.y))
            shape.append((_index(k.t, ts), "j"))
    return _walk(triples, ts), shape
