"""The plain references of the Angle method and the TwoStreams protocol:
hand-worked cases, and seeded walks held against the program's own
sequential float64 code (``core/methods.py:run_angle``,
``core/protocols.py``), which the references do not import."""

import ast
import struct

import numpy as np
import pytest

from bench.core.cell import BENCH_DIR
from bench.reference import angle, twostreams
from bench.reference.geometry import Line, MethodOutput, Segment

EPS = 1.0


def _ts(n, t0=0.0, dt=1.0):
    return t0 + dt * np.arange(n, dtype=np.float64)


def _bounds(out):
    return [(s.i0, s.i1) for s in out.segments]


@pytest.mark.parametrize("path", sorted((BENCH_DIR / "reference")
                                        .glob("*.py")), ids=lambda p: p.stem)
def test_references_import_nothing_of_the_system(path):
    tree = ast.parse(path.read_text())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            names = [node.module or ""]
        else:
            continue
        assert not any(n.split(".")[0] == "repro" for n in names), names


def test_angle_wedge_origin_on_two_points():
    # Steepest line (0, -1)-(2, 5), slope 3; shallowest (0, 1)-(2, 3),
    # slope 1: they cross at (1, 2), and the mid slope 2 gives y = 2t.
    run = angle.AngleRun(0.0, 0.0, EPS)
    assert run.try_add(2.0, 4.0)
    w = run.wedge
    assert (w.ot, w.oy, w.slo, w.shi) == pytest.approx((1.0, 2.0, 1.0, 3.0))
    assert run.line() == Line(2.0, 0.0)
    out = angle.run(np.array([0.0, 2.0]), np.array([0.0, 4.0]), EPS)
    assert _bounds(out) == [(0, 2)]
    # A flat pair: the lines (0, -1)-(1, 1) and (0, 1)-(1, -1) cross at
    # (0.5, 0), with slopes -2 and 2.
    run = angle.AngleRun(0.0, 0.0, EPS)
    run.try_add(1.0, 0.0)
    assert (run.wedge.ot, run.wedge.oy, run.wedge.slo, run.wedge.shi) == \
        pytest.approx((0.5, 0.0, -2.0, 2.0))


def test_angle_run_ends_at_an_empty_interval():
    # From the origin (0.5, 0): the point (2, 0) narrows the slopes to
    # [-1/1.5, 1/1.5]; (3, 10) asks for at least 9/2.5 = 3.6, so the run
    # ends there and that point starts the next, alone.
    ts, ys = _ts(4), np.array([0.0, 0.0, 0.0, 10.0])
    out = angle.run(ts, ys, EPS)
    assert _bounds(out) == [(0, 3), (3, 4)]
    assert out.segments[0].line == pytest.approx(Line(0.0, 0.0))
    assert out.segments[1].line == Line(0.0, 10.0)
    run = angle.AngleRun(0.0, 0.0, EPS)
    assert run.try_add(1.0, 0.0) and run.try_add(2.0, 0.0)
    assert (run.wedge.slo, run.wedge.shi) == pytest.approx((-2 / 3, 2 / 3))
    assert not run.try_add(3.0, 10.0) and run.count == 3


def test_angle_run_ends_at_max_run():
    ts = _ts(600)
    ys = 0.5 * ts
    out = angle.run(ts, ys, EPS, max_run=256)
    assert _bounds(out) == [(0, 256), (256, 512), (512, 600)]
    for s in out.segments:
        assert s.line == pytest.approx(Line(0.5, 0.0))
    assert _bounds(angle.run(ts, ys, EPS)) == [(0, 600)]


def _seg(i0, i1, a=0.0, b=0.0):
    return Segment(i0, i1, Line(a, b))


def test_twostreams_run_of_three_is_sent_as_singletons():
    ts, ys = _ts(3), np.array([1.0, 1.5, 2.0])
    out = MethodOutput([_seg(0, 3, 0.5, 1.0)], [])
    vals, shape = twostreams.expected(out, ts, ys)
    assert shape == [0, 0, 0] and vals == [1.0, 1.5, 2.0]
    wire = (b"", struct.pack("<3d", 1.0, 1.5, 2.0))
    assert twostreams.decode(wire, ts) == ([1.0, 1.5, 2.0], [0, 0, 0])


def test_twostreams_singletons_before_the_first_segment():
    ts = _ts(7, t0=100.0, dt=10.0)
    ys = np.array([5.0, 9.0, 3.0, 3.5, 4.0, 4.5, 5.0])
    out = MethodOutput([_seg(0, 1, 0.0, 5.0), _seg(1, 2, 0.0, 9.0),
                        _seg(2, 7, 0.05, -3.0)], [])
    vals, shape = twostreams.expected(out, ts, ys)
    assert shape == [0, 0, 5]
    assert vals == pytest.approx([5.0, 9.0, 3.0, 3.5, 4.0, 4.5, 5.0])
    wire = (struct.pack("<dBdd", 120.0, 4, 0.05, -3.0),
            struct.pack("<2d", 5.0, 9.0))
    got, got_shape = twostreams.decode(wire, ts)
    assert got_shape == [0, 0, 5] and got == pytest.approx(vals)


def test_twostreams_counter_at_255():
    ts = _ts(258)
    ys = 0.25 * ts
    out = MethodOutput([_seg(0, 256, 0.25, 0.0), _seg(256, 258, 0.0, 64.0)],
                       [])
    vals, shape = twostreams.expected(out, ts, ys)
    assert shape == [256, 0, 0]
    segs = struct.pack("<dBdd", 0.0, 255, 0.25, 0.0)
    assert segs[8] == 255
    wire = (segs, struct.pack("<2d", 64.0, 64.25))
    got, got_shape = twostreams.decode(wire, ts)
    assert got_shape == [256, 0, 0] and got == pytest.approx(vals)


def test_twostreams_decode_refuses_a_wrong_count():
    ts = _ts(3)
    three = struct.pack("<3d", 1.0, 2.0, 3.0)
    with pytest.raises(ValueError):
        twostreams.decode((b"", three + struct.pack("<d", 4.0)), ts)
    with pytest.raises(struct.error):
        twostreams.decode((b"", three[:16]), ts)
    with pytest.raises(ValueError):
        twostreams.decode((struct.pack("<dBdd", 1.0, 4, 0.0, 0.0),
                           three[:8]), ts)


def _walk(seed, n):
    """A TSBS cpu field: uniform start, N(0, 1) steps, clamped to
    [0, 100], held in float32 as the chip holds it."""
    rng = np.random.default_rng(seed)
    y = np.clip(rng.uniform(0, 100) + np.cumsum(rng.normal(0, 1, n)), 0, 100)
    return y.astype(np.float32).astype(np.float64)


@pytest.mark.parametrize("seed", [0, 1, 2, 2315100001])
def test_references_agree_with_the_golden_code(seed):
    from repro.core.methods import run_angle
    from repro.core.protocols import (decode_twostreams, encode_twostreams,
                                      protocol_twostreams)
    ts = _ts(3000, t0=1451606400.0, dt=10.0)
    ys = _walk(seed, ts.size)
    if seed == 2:                       # long straight runs hit the cap
        ys = np.round(ys / 50.0) * 50.0
    gold = run_angle(ts, ys, EPS, max_run=256)
    ref = angle.run(ts, ys, EPS, max_run=256)
    assert _bounds(ref) == [(s.i0, s.i1) for s in gold.segments]
    records = protocol_twostreams(gold, ts, ys)
    wire = encode_twostreams(records)
    gold_vals = np.array(decode_twostreams(*wire, ts))
    gold_shape = [len(r.covers) if r.kind == "segment" else 0
                  for r in records]
    vals, shape = twostreams.expected(ref, ts, ys)
    assert shape == gold_shape
    assert np.max(np.abs(np.array(vals) - gold_vals)) <= 1e-9 * EPS
    got, got_shape = twostreams.decode(wire, ts)
    assert got_shape == gold_shape
    assert np.max(np.abs(np.array(got) - gold_vals)) <= 1e-9 * EPS
    assert np.max(np.abs(np.array(vals) - ys)) <= EPS * (1 + 1e-9)
    if seed == 2:
        assert 256 in shape
