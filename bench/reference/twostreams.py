"""Plain reference of the TwoStreams protocol (paper §5.2).

A run of ``n >= 4`` points is sent on the segment stream as
``(t0: f64, n - 1: u8, a: f64, b: f64)``, where ``t0`` is the timestamp
of its first point and its value at timestamp ``t`` is ``a * t + b``; each
point of a shorter run is sent as its bare sample, ``y: f64``, on the
singleton stream.  All fields are little-endian.  A receiver takes the
next point from the segment stream when its timestamp has reached the
next segment's ``t0``, and from the singleton stream otherwise.

An answer is the pair ``(segment bytes, singleton bytes)``.  A record
stream is compared by its *shape*: one entry per record, in time order,
the segment's point count or 0 for a singleton.
"""

from __future__ import annotations

import struct

MIN_SEGMENT = 4
SEGMENT = struct.Struct("<dBdd")
SINGLETON = struct.Struct("<d")


def decode(answer, ts):
    """``(values, shape)`` of a TwoStreams answer; raises where the two
    streams do not hold exactly one value per timestamp."""
    segs, singles = answer
    vals, shape = [], []
    goff = soff = i = 0
    while i < len(ts):
        if goff < len(segs) and float(ts[i]) >= \
                SEGMENT.unpack_from(segs, goff)[0]:
            _, nm1, a, b = SEGMENT.unpack_from(segs, goff)
            goff += SEGMENT.size
            if i + nm1 + 1 > len(ts):
                raise ValueError("a segment runs past the last timestamp")
            for _ in range(nm1 + 1):
                vals.append(a * float(ts[i]) + b)
                i += 1
            shape.append(nm1 + 1)
        else:
            (y,) = SINGLETON.unpack_from(singles, soff)
            soff += SINGLETON.size
            vals.append(y)
            shape.append(0)
            i += 1
    if goff != len(segs) or soff != len(singles):
        raise ValueError("bytes left over after the last timestamp")
    return vals, shape


def expected(out, ts, ys):
    """``(values, shape)`` that a method output should be sent as."""
    vals, shape = [], []
    for seg in out.segments:
        if seg.n >= MIN_SEGMENT:
            vals.extend(seg.line(float(ts[i])) for i in range(seg.i0, seg.i1))
            shape.append(seg.n)
        else:
            vals.extend(float(ys[i]) for i in range(seg.i0, seg.i1))
            shape.extend([0] * seg.n)
    return vals, shape
