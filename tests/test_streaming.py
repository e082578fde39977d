"""Chunked streaming engine: bit-equality with the offline segmenters.

The contract under test (ISSUE 2): pushing a stream through the
init/step/flush carry-state API — at the jnp reference layer
(``repro.core.jax_pla``) or through the Pallas kernels
(``repro.kernels.ops.StreamingSegmenter``) — in *arbitrary* chunk sizes
yields a SegmentOutput bit-identical to the one-shot offline call.

Deterministic splits (chunk size 1, non-divisors of the time block, a
final partial chunk) always run; the hypothesis property test sweeps
random splits when hypothesis is installed (CI; requirements-dev.txt).
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import jax_pla
from repro.core.jax_pla import (STREAMING_METHODS, flush,
                                init_state, propagate_lines, step_chunk)
from repro.kernels.ops import KERNEL_SEGMENTERS, StreamingSegmenter
from repro.kernels.reconstruct import reconstruct_pallas

REF_FNS = {"angle": jax_pla.angle_segment, "swing": jax_pla.swing_segment,
           "disjoint": jax_pla.disjoint_segment,
           "linear": jax_pla.linear_segment,
           "continuous": jax_pla.continuous_segment,
           "mixed": jax_pla.mixed_segment}

# Small kernel tiles keep interpret mode fast; chunk splits deliberately
# include size 1, non-divisors of block_t, and a final partial chunk.
KBLOCK_T = 32
SPLITS = {
    105: (1, 31, 32, 40, 1),
    97: (50, 47),
    64: (64,),
    3: (1, 1, 1),
    2: (2,),
}


def _make(seed, S, T):
    rng = np.random.default_rng(seed)
    return jnp.asarray(np.cumsum(rng.normal(0, 0.5, (S, T)), axis=1),
                       jnp.float32)


def _assert_bit_equal(chunks, offline, label):
    brk = np.concatenate([np.asarray(o.breaks) for o in chunks], axis=1)
    a = np.concatenate([np.asarray(o.a) for o in chunks], axis=1)
    v = np.concatenate([np.asarray(o.v) for o in chunks], axis=1)
    assert brk.shape == offline.breaks.shape, label
    np.testing.assert_array_equal(brk, np.asarray(offline.breaks),
                                  err_msg=label)
    np.testing.assert_array_equal(a, np.asarray(offline.a), err_msg=label)
    np.testing.assert_array_equal(v, np.asarray(offline.v), err_msg=label)


def _run_core_chunked(method, y, splits, eps=1.0, max_run=24):
    st = init_state(method, y.shape[0], eps, max_run=max_run)
    outs = []
    pos = 0
    for w in splits:
        st, out = step_chunk(st, y[:, pos:pos + w])
        outs.append(out)
        pos += w
    assert pos == y.shape[1]
    st, out_f = flush(st)
    outs.append(out_f)
    return outs


@pytest.mark.parametrize("method", STREAMING_METHODS)
@pytest.mark.parametrize("T,splits", sorted(SPLITS.items()))
def test_core_chunked_equals_offline(method, T, splits):
    y = _make(0, 6, T)
    offline = REF_FNS[method](y, 1.0, max_run=24)
    outs = _run_core_chunked(method, y, splits)
    _assert_bit_equal(outs, offline, f"core/{method}/T={T}")


@pytest.mark.parametrize("method", sorted(KERNEL_SEGMENTERS))
@pytest.mark.parametrize("T,splits", sorted(SPLITS.items()))
def test_kernel_chunked_equals_offline(method, T, splits):
    y = _make(1, 5, T)
    offline = KERNEL_SEGMENTERS[method](y, 1.0, max_run=24, block_t=KBLOCK_T)
    ss = StreamingSegmenter(method, 5, 1.0, max_run=24, block_t=KBLOCK_T)
    outs = []
    pos = 0
    for w in splits:
        outs.append(ss.push(y[:, pos:pos + w]))
        pos += w
    assert pos == T
    outs.append(ss.finish())
    _assert_bit_equal(outs, offline, f"kernel/{method}/T={T}")
    assert ss.pushed == T


def test_kernel_streaming_empty_and_misuse():
    ss = StreamingSegmenter("angle", 4, 1.0, block_t=KBLOCK_T)
    out = ss.finish()
    assert out.breaks.shape == (4, 0)
    with pytest.raises(RuntimeError):
        ss.push(jnp.zeros((4, 3)))
    with pytest.raises(RuntimeError):
        ss.finish()
    with pytest.raises(ValueError):
        StreamingSegmenter("nope", 4, 1.0)
    with pytest.raises(ValueError):
        StreamingSegmenter("angle", 4, 1.0, window=512)


def test_core_flush_restarts_fresh_stream():
    """After flush the carry is gone; the next chunk starts a new stream
    (the adaptive controller's retune boundary)."""
    y = _make(2, 3, 80)
    st = init_state("disjoint", 3, 1.0, max_run=24)
    st, o1 = step_chunk(st, y[:, :40])
    st, f1 = flush(st)
    assert st.carry is None and st.emitted == 40
    st, o2 = step_chunk(st, y[:, 40:])
    st, f2 = flush(st)
    assert st.emitted == 80
    # Each half independently equals its offline segmentation (positions in
    # the second half are absolute, so compare events only).
    off2 = REF_FNS["disjoint"](y[:, 40:], 1.0, max_run=24)
    got = np.concatenate([np.asarray(o2.breaks), np.asarray(f2.breaks)],
                         axis=1)
    np.testing.assert_array_equal(got, np.asarray(off2.breaks))


def test_reconstruct_carry_split_equals_one_launch():
    y = _make(5, 5, 100)
    seg = REF_FNS["disjoint"](y, 1.0, max_run=24)
    S, T, Sp, Tp, bt = 5, 100, 128, 128, KBLOCK_T

    def padded(x, fill, dtype):
        out = np.full((Sp, Tp), fill, dtype)
        out[:S, :T] = np.asarray(x)
        return jnp.asarray(out.T)

    B = padded(seg.breaks.astype(jnp.int8), 1, np.int8)
    A = padded(seg.a, 0.0, np.float32)
    V = padded(seg.v, 0.0, np.float32)
    full, _ = reconstruct_pallas(B, A, V, block_s=128, block_t=bt)
    # Reverse-chunked: later slab first, carry into the earlier slab.
    late, c = reconstruct_pallas(B[64:], A[64:], V[64:],
                                 block_s=128, block_t=bt)
    early, _ = reconstruct_pallas(B[:64], A[:64], V[:64],
                                  block_s=128, block_t=bt, carry=c)
    two = np.concatenate([np.asarray(early), np.asarray(late)], axis=0)
    np.testing.assert_array_equal(two, np.asarray(full))
    # and the reconstruction itself obeys eps on the real region
    np.testing.assert_allclose(np.asarray(full).T[:S, :T],
                               np.asarray(propagate_lines(seg)),
                               rtol=1e-6, atol=1e-5)


def test_streaming_adaptive_eps_retunes_and_bounds_error():
    from repro.core.adaptive import StreamingAdaptiveEps
    rng = np.random.default_rng(6)
    n = 4096
    ys = np.concatenate([np.cumsum(rng.normal(0, 0.02, n // 2)),
                         10 * rng.normal(0, 1.0, n - n // 2)])
    ctl = StreamingAdaptiveEps(target_ratio=0.2, eps0=0.1)
    rep = ctl.run(ys, chunk=512)
    eps_vals = [e for _, e in rep["eps_trace"]]
    assert max(eps_vals) / min(eps_vals) > 3      # it actually adapted
    assert 0 < rep["overall_ratio"] < 1.0
    # eps guarantee: bounded by the largest eps active during any run
    assert rep["errors"].max() <= max(eps_vals) * (1 + 1e-4) + 1e-5


def test_telemetry_streaming_matches_guarantee_and_fallback():
    from repro.compression.telemetry import TelemetryCompressor
    rng = np.random.default_rng(7)
    tc = TelemetryCompressor(eps=0.02, flush_every=64, step_every=16)
    for s in range(300):
        tc.append(s, {"loss": float(np.sin(s / 25) + rng.normal(0, 1e-3)),
                      "gnorm": float(np.cos(s / 40))})
    tc.flush_all()
    assert tc.max_err_seen <= 0.02 * (1 + 1e-6)
    assert 0 < tc.ratio < 1.0
    # irregular timestamps take the exact sequential fallback
    tc2 = TelemetryCompressor(eps=0.02, flush_every=32)
    step = 0
    for _ in range(64):
        step += int(rng.integers(1, 4))
        tc2.append(step, {"m": float(np.sin(step / 10))})
    tc2.flush_all()
    assert tc2.max_err_seen <= 0.02 * (1 + 1e-6)
    # the deferred methods (continuous/mixed) stream too since the
    # lag-aware sender (ISSUE 5): their released columns lag one segment
    # but the flush drains the tail, and the eps guarantee holds off wire
    tc3 = TelemetryCompressor(eps=0.05, method="continuous", flush_every=40)
    assert tc3.streaming is True
    for s in range(90):
        tc3.append(s, {"x": float(np.sin(s / 9))})
        assert tc3.lag("x") >= 0
    tc3.flush_all()
    assert tc3.max_err_seen <= 0.05 * (1 + 1e-6)
    with pytest.raises(ValueError):
        TelemetryCompressor(method="nope")


# The property sweep over random chunk splits lives in
# tests/test_streaming_property.py; its deterministic fixed-draw twins run
# even without hypothesis (requirements-dev installs the real sweep).
