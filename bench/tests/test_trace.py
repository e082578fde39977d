"""The reduction from a trace to per-layer metrics, on a synthetic trace."""

import pytest

from bench.core.kernels import segmenter_bytes
from bench.core.trace import Op, Trace
from bench.core.cell import BENCH_DIR, load_module


def _trace():
    ops = [[Op("linear_pallas.1", 0.10, 0.20, "jit_linear_pallas"),
            Op("fusion.3", 0.15, 0.30, "jit__masked_scan"),
            Op("copy.1", 0.50, 0.60, "jit_copy"),
            Op("linear_pallas.1", 0.90, 1.20, "jit_linear_pallas")]]
    spans = [("bench.fleet.push", 0.0, 0.45), ("bench.serve.tick", 0.45, 0.8)]
    return Trace((0.0, 1.0), ops, spans)


class _Run:
    def __init__(self, trace, records, traffic, device_kind="TPU v5 lite",
                 chips=1):
        self.trace = trace
        self.records = records
        self.cell = type("C", (), {"traffic": traffic, "chips": chips})()
        self.device_kind = device_kind


def test_busy_and_idle_share():
    tr = _trace()
    # Union of [0.1, 0.3], [0.5, 0.6] and [0.9, 1.0] (clipped to the window).
    assert tr.window_s() == pytest.approx(1.0)
    assert tr.busy_s() == pytest.approx(0.4)
    assert tr.idle_share() == pytest.approx(0.6)


def test_kernel_time_by_name_and_module():
    tr = _trace()
    from bench.core.kernels import is_segmenter
    assert tr.op_s(is_segmenter("linear")) == pytest.approx(0.2)
    assert tr.op_s(is_segmenter("mixed")) == 0.0
    assert tr.op_s(lambda o: "_masked_scan" in o.module) == \
        pytest.approx(0.15)


def test_nested_operations_count_once():
    # A while loop's event spans the fusions of its body on the same line.
    ops = [[Op("while.4", 0.10, 0.50, "jit__masked_scan"),
            Op("fusion.1", 0.15, 0.25, "jit__masked_scan"),
            Op("fusion.2", 0.30, 0.45, "jit__masked_scan"),
            Op("while.4", 0.70, 0.80, "jit__masked_scan")]]
    tr = Trace((0.0, 1.0), ops, [])
    assert tr.op_s(lambda o: "_masked_scan" in o.module) == \
        pytest.approx(0.5)
    assert tr.op_s(lambda o: "_masked_scan" in o.module) == \
        pytest.approx(tr.busy_s())


def test_breakdown_attributes_idle_time_to_host_spans():
    bd = _trace().breakdown()
    assert bd["device_ops"][0][0] == "linear_pallas.1"
    assert bd["device_ops"][0][1] == pytest.approx(0.2)
    # Idle [0, 0.1] and [0.3, 0.45] fall in the push span, [0.45, 0.5]
    # and [0.6, 0.8] in the tick span, [0.8, 0.9] in none.
    gaps = dict(bd["idle_gaps"])
    assert gaps == pytest.approx({"bench.fleet.push": 0.25,
                                  "bench.serve.tick": 0.25,
                                  "outside bench spans": 0.1})
    # Where one of the program's spans is the innermost, it takes its part.
    tr = _trace()
    tr.program_spans = [("repro.fleet.emit", 0.3, 0.44, {})]
    gaps = dict(tr.breakdown()["idle_gaps"])
    assert gaps == pytest.approx({"bench.fleet.push": 0.11,
                                  "repro.fleet.emit": 0.14,
                                  "bench.serve.tick": 0.25,
                                  "outside bench spans": 0.1})


def _reader(name):
    return load_module(BENCH_DIR / "metrics" / f"{name}.py").read


def test_metric_readers_on_a_fleet_trace():
    records = {"pushes": [(0.0, 0.5, 100), (0.5, 1.0, 100)],
               "n_streams": 256, "push_width": 1024}
    run = _Run(_trace(), records, {"method": "linear"})
    assert _reader("device_idle_share.fleet")(run) == pytest.approx(60.0)
    assert _reader("segmenter_kernel_ms_per_push")(run) == \
        pytest.approx(100.0)
    moved = 2 * segmenter_bytes(256, 1024)
    assert moved == 2 * 256 * 1024 * 13
    assert _reader("segmenter_roofline")(run) == \
        pytest.approx(100.0 * moved / 819e9 / 0.2)
    assert _reader("device_idle_share.serve")(run) is None
    assert _reader("masked_step_device_ms")(run) is None


def test_roofline_is_reckoned_per_chip():
    # Four chips, each running the one chip's kernel time over a shard as
    # large as the one chip's whole fleet, read as the one chip does.
    records = {"pushes": [(0.0, 0.5, 100), (0.5, 1.0, 100)],
               "n_streams": 256, "push_width": 1024}
    one = _Run(_trace(), records, {"method": "linear"})
    four = _Run(Trace((0.0, 1.0), _trace().ops * 4, []),
                dict(records, n_streams=4 * 256), {"method": "linear"},
                chips=4)
    for name in ("segmenter_roofline", "segmenter_kernel_ms_per_push",
                 "device_idle_share.fleet"):
        assert _reader(name)(four) == pytest.approx(_reader(name)(one))
    assert segmenter_bytes(4 * 256, 1024, 4) == segmenter_bytes(256, 1024)
    # Each chip's shard is padded to whole 128-lane blocks.
    assert segmenter_bytes(4 * 100, 8, 4) == 128 * 8 * 13


def test_load_trace_keeps_program_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    from bench.core.trace import WINDOW_SPAN, load_trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            with jax.profiler.TraceAnnotation("bench.fleet.push"):
                with jax.profiler.TraceAnnotation("repro.fleet.push",
                                                  streams=8):
                    jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    tr = load_trace(str(tmp_path), n_devices=1)
    assert [s[0] for s in tr.spans] == ["bench.fleet.push"]
    assert [s[0] for s in tr.program_spans] == ["repro.fleet.push"]
    assert tr.program_spans[0][3]["streams"] == 8


def test_metric_readers_on_a_serve_trace():
    run = _Run(_trace(), {"ticks": 3}, {"method": "linear"})
    assert _reader("masked_step_device_ms")(run) == pytest.approx(50.0)
    assert _reader("device_idle_share.serve")(run) == pytest.approx(60.0)
    assert _reader("segmenter_roofline")(run) is None


def test_readers_find_nothing_without_a_trace():
    run = _Run(None, {"pushes": [(0.0, 1.0, 1)], "ticks": 1},
               {"method": "linear"})
    for name in ("device_idle_share.fleet", "segmenter_kernel_ms_per_push",
                 "segmenter_roofline", "device_idle_share.serve",
                 "masked_step_device_ms"):
        assert _reader(name)(run) is None


def test_unknown_device_has_no_peak():
    records = {"pushes": [(0.0, 1.0, 1)], "n_streams": 128,
               "push_width": 128}
    run = _Run(_trace(), records, {"method": "linear"}, "TPU v9")
    with pytest.raises(KeyError):
        _reader("segmenter_roofline")(run)
