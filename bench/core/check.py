"""The comparison that decides ``correct``.

Each sampled stream's wire bytes, as the timed path produced them, are
decoded with the protocol's plain reference decoder and held against the
plain reference method run on the same samples (``bench/reference``).
Three numbers come out; a cell compares those that its limits file
(``bench/limits/<workload>.json``) gives a limit:

- ``shape_mismatch_pct``: the share of sampled streams whose record
  shape (segment lengths, or knot positions and kinds) differs from the
  reference's, or whose bytes do not decode to one value per sample;
- ``value_gap_eps``: over the streams of equal shape, the widest gap
  between a decoded value and the reference's value, in units of eps;
- ``error_eps``: over all sampled streams, the widest gap between a
  decoded value and the sample itself, in units of eps (the method's
  guarantee is 1).

The control (``reference_answers(..., bf16=True)``) puts the reference in
the program's place with its samples held in bfloat16, the precision
below the float32 that the configuration states.
"""

from __future__ import annotations

import importlib
import math
from typing import List, Optional, Sequence

import numpy as np

# What a stream that does not decode reads as ``error_eps``.
UNDECODABLE = 1.0e9


def reference_module(name: str):
    return importlib.import_module(f"bench.reference.{name}")


class Sample:
    """One sampled stream: its timestamps, its samples as the system got
    them (float32), and the answer to judge."""

    def __init__(self, stream, ts: np.ndarray, ys: np.ndarray, answer):
        self.stream = stream
        self.ts = ts
        self.ys = np.asarray(ys, np.float32).astype(np.float64)
        self.answer = answer


def decode_answers(samples: Sequence[Sample], protocol: str) -> list:
    """``(values, shape)`` per sample, or None where the bytes do not
    decode to one value per timestamp."""
    proto = reference_module(protocol)
    out = []
    for s in samples:
        try:
            vals, shape = proto.decode(s.answer, s.ts)
        except Exception:          # malformed wire of any kind
            out.append(None)
            continue
        vals = np.asarray(vals, np.float64)
        out.append((vals, shape) if vals.shape == s.ts.shape
                   and np.all(np.isfinite(vals)) else None)
    return out


def reference_answers(samples: Sequence[Sample], method: str,
                      protocol: str, eps: float, max_run: Optional[int],
                      bf16: bool = False) -> list:
    """The reference's ``(values, shape)`` for each sample; with ``bf16``
    the samples are rounded to bfloat16 first (the control)."""
    meth = reference_module(method)
    proto = reference_module(protocol)
    out = []
    for s in samples:
        ys = s.ys
        if bf16:
            import ml_dtypes
            ys = ys.astype(ml_dtypes.bfloat16).astype(np.float64)
        res = meth.run(s.ts, ys, eps, max_run)
        vals, shape = proto.expected(res, s.ts, ys)
        out.append((np.asarray(vals, np.float64), shape))
    return out


def numbers(samples: Sequence[Sample], got: list, ref: list,
            eps: float) -> dict:
    """The three compared numbers for answers ``got`` against ``ref``."""
    mismatch = 0
    gap: Optional[float] = None
    err = 0.0
    for s, g, r in zip(samples, got, ref):
        if g is None:
            mismatch += 1
            err = UNDECODABLE
            continue
        vals, shape = g
        err = max(err, float(np.max(np.abs(vals - s.ys))) / eps)
        if shape != r[1]:
            mismatch += 1
            continue
        d = float(np.max(np.abs(vals - r[0]))) / eps
        gap = d if gap is None else max(gap, d)
    return {"shape_mismatch_pct": 100.0 * mismatch / max(len(samples), 1),
            "value_gap_eps": gap,
            "error_eps": err}


def judge(nums: dict, limits: dict) -> List[dict]:
    """One entry per compared number: its value, its limit, and whether
    it passed.  A number with no reading (no stream of equal shape, or
    no stream at all) fails."""
    rows = []
    for name, limit in limits.items():
        v = nums.get(name)
        ok = v is not None and not math.isnan(v) and v <= limit
        rows.append({"name": name, "value": v, "limit": limit, "ok": ok})
    return rows
