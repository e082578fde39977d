"""Plain reference of the SingleStream protocol (paper §5.2).

Records ``(n - 1: u8, a: f64, b: f64)`` for a segment of ``n >= 3``
points and ``(0: u8, y: f64)`` for each point of a shorter run; the value
at timestamp ``t`` of a segment is ``a * t + b``.

A record stream is compared by its *shape*: one entry per record, the
segment's point count or 0 for a singleton.
"""

from __future__ import annotations

import struct

MIN_SEGMENT = 3


def decode(data: bytes, ts):
    """``(values, shape)`` of a SingleStream byte stream."""
    vals, shape = [], []
    off = i = 0
    while off < len(data):
        (nm1,) = struct.unpack_from("<B", data, off)
        off += 1
        if nm1 == 0:
            (y,) = struct.unpack_from("<d", data, off)
            off += 8
            vals.append(y)
            shape.append(0)
            i += 1
        else:
            a, b = struct.unpack_from("<dd", data, off)
            off += 16
            for _ in range(nm1 + 1):
                vals.append(a * float(ts[i]) + b)
                i += 1
            shape.append(nm1 + 1)
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
