"""The reduction of the system's own ``repro.*`` spans, and the metrics
that read them, on synthetic traces."""

import pytest

from bench.core import program_spans as ps
from bench.core.cell import BENCH_DIR, load_module
from bench.core.trace import OUTSIDE, Op, Trace
from bench.tests.test_trace import _Run, _trace

OLD_READERS = ("device_idle_share.fleet", "segmenter_kernel_ms_per_push",
               "segmenter_roofline", "device_idle_share.serve",
               "masked_step_device_ms")
NEW_READERS = ("fleet_put_ms_per_push", "fleet_fetch_ms_per_push",
               "fleet_emit_ms_per_push", "serve_emit_ms_per_tick",
               "serve_queue_wait_ms", "serve_drain_ticks_per_evict")

# One tick on its own, an evict that drains its queue in two ticks (the
# second takes nothing), an evict with nothing queued, and a last tick.
SERVE = [
    ("repro.serve.tick", 0.00, 0.10, {"tick": 1}),
    ("repro.slots.step", 0.01, 0.09, {"slots_fed": 3, "points": 100,
                                      "wait_mean_ms": 10.0,
                                      "wait_max_ms": 20.0}),
    ("repro.slots.emit", 0.05, 0.08, {}),
    ("repro.serve.evict", 0.20, 0.50, {"queued": 40}),
    ("repro.serve.tick", 0.21, 0.30, {"tick": 2}),
    ("repro.slots.step", 0.22, 0.29, {"slots_fed": 1, "points": 300,
                                      "wait_mean_ms": 30.0,
                                      "wait_max_ms": 30.0}),
    ("repro.slots.emit", 0.25, 0.28, {}),
    ("repro.serve.tick", 0.31, 0.40, {"tick": 3}),
    ("repro.slots.step", 0.32, 0.39, {"slots_fed": 0, "points": 0,
                                      "wait_mean_ms": 0.0,
                                      "wait_max_ms": 0.0}),
    ("repro.slots.evict", 0.41, 0.49, {}),
    ("repro.serve.evict", 0.60, 0.70, {"queued": 0}),
    ("repro.slots.evict", 0.61, 0.69, {}),
    ("repro.serve.tick", 0.80, 0.90, {"tick": 4}),
]

# Inside ``_trace()``'s ``bench.fleet.push`` [0, 0.45] and
# ``bench.serve.tick`` [0.45, 0.8]; the device idles in [0, 0.1],
# [0.3, 0.5] and [0.6, 0.9].
FLEET = [
    ("repro.fleet.push", 0.00, 0.44, {"streams": 256, "width": 1024}),
    ("repro.fleet.put", 0.00, 0.08, {}),
    ("repro.fleet.segment", 0.08, 0.10, {}),
    ("repro.fleet.fetch", 0.10, 0.30, {"bytes": 256 * 1024 * 9}),
    ("repro.fleet.emit", 0.30, 0.44, {}),
    ("repro.serve.tick", 0.46, 0.70, {"tick": 1}),
]


def _reader(name):
    return load_module(BENCH_DIR / "metrics" / f"{name}.py").read


def _with_spans(trace, spans):
    trace.program_spans = spans
    return trace


def test_span_seconds_and_counts():
    assert ps.span_count(SERVE, "repro.serve.tick") == 4
    assert ps.span_count(SERVE, "repro.fleet.push") == 0
    assert ps.span_s(SERVE, "repro.slots.emit") == pytest.approx(0.06)
    assert ps.span_s(SERVE, "repro.serve.evict") == pytest.approx(0.4)
    assert ps.span_s(SERVE, "repro.fleet.push") == 0.0


def test_drain_ticks_nested_in_evicts():
    assert ps.nested_count(SERVE, "repro.serve.tick",
                           "repro.serve.evict") == 2
    assert ps.nested_count(SERVE, "repro.slots.step",
                           "repro.serve.evict") == 2
    assert ps.nested_count(SERVE, "repro.slots.evict",
                           "repro.serve.evict") == 2
    assert ps.nested_count(SERVE, "repro.serve.evict",
                           "repro.serve.tick") == 0


def test_points_weighted_wait():
    # (10 ms x 100 + 30 ms x 300 + 0 ms x 0) / 400 points.
    assert ps.weighted_arg(SERVE, "repro.slots.step", "wait_mean_ms",
                           "points") == pytest.approx(25.0)
    assert ps.weighted_arg(SERVE, "repro.serve.tick", "wait_mean_ms",
                           "points") is None
    idle = [("repro.slots.step", 0.0, 1.0, {"points": 0,
                                            "wait_mean_ms": 5.0})]
    assert ps.weighted_arg(idle, "repro.slots.step", "wait_mean_ms",
                           "points") is None


def test_covered_share():
    # Steps cover 0.08 + 0.07 + 0.07 + 0 of ticks of 0.1 + 0.09 + 0.09 +
    # 0.1 seconds.
    assert ps.covered_share(SERVE, "repro.serve.tick",
                            ["repro.slots.step"]) == \
        pytest.approx(0.22 / 0.38)
    assert ps.covered_share(FLEET, "repro.fleet.push",
                            ["repro.fleet.put", "repro.fleet.segment",
                             "repro.fleet.fetch", "repro.fleet.emit"]) == \
        pytest.approx(1.0)
    assert ps.covered_share(FLEET, "repro.serve.evict",
                            ["repro.serve.tick"]) is None


def test_idle_gaps_go_to_the_innermost_program_span():
    tr = _with_spans(_trace(), FLEET)
    gaps = dict(tr.idle_gaps())
    # [0, 0.1] in the put and the segment; [0.3, 0.5] in the emit, the
    # two benchmark spans and the program's tick; [0.6, 0.9] in that
    # tick, then the benchmark's, then none.
    assert gaps == pytest.approx({"repro.fleet.put": 0.08,
                                  "repro.fleet.segment": 0.02,
                                  "repro.fleet.emit": 0.14,
                                  "bench.fleet.push": 0.01,
                                  "bench.serve.tick": 0.11,
                                  "repro.serve.tick": 0.14,
                                  OUTSIDE: 0.1})
    assert dict(tr.breakdown()["idle_gaps"]) == gaps
    # With no program span the gaps are the benchmark's spans' alone.
    assert dict(_with_spans(tr, []).idle_gaps()) == \
        pytest.approx(dict(_trace().breakdown()["idle_gaps"]))


def test_idle_gaps_of_either_family_and_none():
    tr = Trace((0.0, 1.0), [[Op("fusion", 0.4, 0.6)]],
               [("bench.serve.tick", 0.0, 0.3)])
    # [0, 0.3] in the benchmark's tick, [0.7, 1.0] in the program's, the
    # rest of the idle time in none.
    tr.program_spans = [("repro.serve.tick", 0.7, 1.0, {})]
    assert dict(tr.idle_gaps()) == pytest.approx(
        {"bench.serve.tick": 0.3, "repro.serve.tick": 0.3, OUTSIDE: 0.2})
    # Spans that open together: the shorter is the inner one.
    tr = Trace((0.0, 1.0), [[Op("fusion", 0.9, 1.0)]],
               [("bench.serve.tick", 0.0, 0.2)])
    tr.program_spans = [("repro.serve.tick", 0.0, 0.1, {})]
    assert dict(tr.idle_gaps()) == pytest.approx(
        {"repro.serve.tick": 0.1, "bench.serve.tick": 0.1, OUTSIDE: 0.7})
    assert Trace((0.0, 1.0), [], []).idle_gaps() == []


@pytest.mark.parametrize("name", OLD_READERS)
def test_existing_readers_ignore_program_spans(name):
    records = {"pushes": [(0.0, 0.5, 100), (0.5, 1.0, 100)],
               "n_streams": 256, "push_width": 1024, "ticks": 3}
    plain = _reader(name)(_Run(_trace(), records, {"method": "linear"}))
    spanned = _reader(name)(_Run(_with_spans(_trace(), FLEET + SERVE),
                                 records, {"method": "linear"}))
    assert plain is not None and spanned == plain


def test_fleet_readers():
    records = {"pushes": [(0.0, 0.45, 100), (0.45, 1.0, 100)]}
    run = _Run(_with_spans(_trace(), FLEET), records, {"method": "linear"})
    assert _reader("fleet_put_ms_per_push")(run) == pytest.approx(40.0)
    assert _reader("fleet_fetch_ms_per_push")(run) == pytest.approx(100.0)
    assert _reader("fleet_emit_ms_per_push")(run) == pytest.approx(70.0)
    for name in NEW_READERS[3:]:
        assert _reader(name)(run) is None


def test_serve_readers():
    tr = Trace((0.0, 1.0), [[]], [])
    run = _Run(_with_spans(tr, SERVE), {"ticks": 4}, {"method": "linear"})
    # 60 ms of emit over the 2 ticks that stepped (of 4), one of them an
    # evict's drain.
    assert _reader("serve_emit_ms_per_tick")(run) == pytest.approx(30.0)
    assert _reader("serve_queue_wait_ms")(run) == pytest.approx(25.0)
    assert _reader("serve_drain_ticks_per_evict")(run) == pytest.approx(1.0)
    for name in NEW_READERS[:3]:
        assert _reader(name)(run) is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_find_nothing_without_program_spans(name):
    """A trace reduced without the program's spans, or a system that
    opens none, gives no reading and does not raise."""
    records = {"pushes": [(0.0, 1.0, 1)], "ticks": 3}
    assert _reader(name)(_Run(_trace(), records, {"method": "linear"})) \
        is None
    assert _reader(name)(_Run(None, records, {"method": "linear"})) is None
    empty = _Run(_with_spans(_trace(), []), records, {"method": "linear"})
    assert _reader(name)(empty) is None


def test_spans_tool_report():
    from bench.core.cell import load_cell
    from bench.tools.spans import PROGRAM_METRICS, report
    cell = load_cell("fleet-devops-L2")
    records = {"attempted": 2, "pushes": [(0.0, 0.45, 100),
                                          (0.45, 1.0, 100)],
               "n_streams": 256, "push_width": 1024}
    tr = _with_spans(_trace(), FLEET)
    line = report(cell, records, tr, "TPU v5 lite", on_chip=True)
    assert line["window"] == {"attempted": 2}
    assert line["spans"]["repro.fleet.fetch"] == \
        {"s": pytest.approx(0.2), "n": 1, "max_s": pytest.approx(0.2)}
    assert line["cover"]["bench.fleet.push"] == pytest.approx(0.44 / 0.45)
    assert line["metrics"]["fleet_emit_ms_per_push"] == pytest.approx(70.0)
    assert line["metrics"]["segmenter_kernel_ms_per_push"] == \
        pytest.approx(100.0)
    assert set(PROGRAM_METRICS) | {m["name"] for m in cell.per_layer} == \
        set(line["metrics"])
    assert dict(line["idle_gaps"]) == pytest.approx(dict(tr.idle_gaps()))
    assert dict(line["idle_gaps"])["repro.fleet.emit"] == pytest.approx(0.14)
    off = report(cell, records, tr, "cpu", on_chip=False)
    assert "metrics" not in off and "idle_gaps" not in off
