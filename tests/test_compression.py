"""Telemetry compression: the SingleStreamV sender for host metric
streams."""

import numpy as np

from repro.compression.telemetry import TelemetryCompressor


def test_telemetry_eps_and_flush():
    tc = TelemetryCompressor(eps=0.01, flush_every=32)
    rng = np.random.default_rng(5)
    for s in range(100):
        tc.append(s, {"loss": 3 * np.exp(-s / 40) + rng.normal(0, 1e-3)})
    tc.flush_all()
    assert tc.max_err_seen <= 0.01 * (1 + 1e-6)
    assert 0 < tc.ratio < 1.0


def test_telemetry_deferred_methods_stream_lag_aware():
    """continuous/mixed channels stream through the emitter too (ISSUE
    5): the released-column watermark lags mid-window (the paper's extra
    segment of latency), drains at the flush, and the window blob is
    bit-identical to the one-shot engine + emitter on the same values."""
    from repro.core import jax_pla
    from repro.core.protocol_engine import ProtocolEmitter
    from repro.core.protocols import PROTOCOL_CAPS

    for method in ("continuous", "mixed"):
        tc = TelemetryCompressor(eps=0.01, method=method, flush_every=64,
                                 step_every=16)
        assert tc.streaming, method
        rng = np.random.default_rng(5)
        vals, blobs, max_lag = [], [], 0
        for s in range(80):
            v = 3 * np.exp(-s / 40) + rng.normal(0, 1e-3)
            vals.append(v)
            b = tc.append(s, {"loss": v})
            max_lag = max(max_lag, tc.lag("loss"))
            if b:
                blobs.append(b)
        assert max_lag > 0                      # deferred release lagged
        assert tc.lag("loss") == len(vals) - 64  # flush drained the window
        tc.flush_all()
        assert tc.max_err_seen <= 0.01 * (1 + 1e-6)
        assert 0 < tc.ratio < 1.0

        y = np.asarray(vals[:64], np.float32)[None]
        st = jax_pla.init_state(method, 1, 0.01,
                                max_run=PROTOCOL_CAPS["singlestreamv"])
        em = ProtocolEmitter("singlestreamv", 1, t0=0.0, dt=1.0)
        st, out = jax_pla.step_chunk(st, y)
        wire = em.step_chunk(out, np.asarray(vals[:64], np.float64)[None])[0]
        st, out_f = jax_pla.flush(st)
        wire += em.step_chunk(out_f)[0] + em.flush()[0]
        assert blobs[0] == wire, method
