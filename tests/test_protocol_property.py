"""Property tests for the protocol engine (ISSUE 3, extended in ISSUE 4).

Round-trips for all four protocol codecs — engine-encoded bytes decoded
by the *legacy* decoders (wire-format compatibility) must reconstruct
within eps — plus SingleStreamV bursts straddling the 127 counter cap and
chunked-vs-offline ProtocolEmitter byte equality under random splits,
over all six batched methods (the deferred continuous/mixed included).

Every hypothesis test has a **deterministic fixed-draw twin** running the
same check body on handpicked draws, so the suite exercises these paths
even when hypothesis is absent (dev dep; requirements-dev.txt / CI
install it) instead of silently skipping.
"""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # fixed-draw twins below still run
    HAVE_HYPOTHESIS = False

from repro.core import jax_pla
from repro.core.methods import run_mixed
from repro.core.protocol_engine import (ENGINE_PROTOCOLS, ProtocolEmitter,
                                        encode_batch)
from repro.core.protocols import (PROTOCOL_CAPS, decode_implicit,
                                  decode_singlestream, decode_singlestreamv,
                                  decode_twostreams)

SEGMENTERS = {"angle": jax_pla.angle_segment,
              "swing": jax_pla.swing_segment,
              "disjoint": jax_pla.disjoint_segment,
              "linear": jax_pla.linear_segment,
              "continuous": jax_pla.continuous_segment,
              "mixed": jax_pla.mixed_segment}
KNOT_KIND = {"swing": "joint", "continuous": "continuous", "mixed": "mixed"}

# Fixed stream lengths so hypothesis sweeps data/eps, not trace cache.
T_CHOICES = (8, 64, 127, 254, 300)


def _kk(method):
    return KNOT_KIND.get(method, "disjoint")


def _walk(seed, n, scale=1.0):
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.normal(0, scale, (1, n)), axis=1) \
        .astype(np.float32)


def _decode(protocol, blob, ts):
    if protocol == "implicit":
        return decode_implicit(blob, ts)
    if protocol == "twostreams":
        return decode_twostreams(blob[0], blob[1], ts)
    if protocol == "singlestream":
        return decode_singlestream(blob, ts)
    return decode_singlestreamv(blob, ts)


# ---------------------------------------------------------------------------
# Check bodies (shared by the hypothesis sweeps and the fixed-draw twins)
# ---------------------------------------------------------------------------

def check_codec_roundtrip(protocol, seed, n, eps, method):
    """encode -> legacy decode -> reconstruct within eps, any stream."""
    y = _walk(seed, n)
    ts = np.arange(n, dtype=float)
    cap = PROTOCOL_CAPS[protocol] or 256
    seg = SEGMENTERS[method](y, eps, max_run=cap)
    blob = encode_batch(seg, y, protocol, knot_kind=_kk(method))[0]
    dec = np.asarray(_decode(protocol, blob, ts))
    assert len(dec) == n
    scale = float(np.abs(y).max()) + 1.0
    assert np.abs(dec - y[0]).max() <= eps * (1 + 1e-4) + 1e-5 * scale, \
        (method, protocol)


def check_bursts_straddle_counter_cap(seed, n, n_long):
    """Singleton runs longer than 127 split into full bursts + remainder,
    and every burst value decodes exactly."""
    rng = np.random.default_rng(seed)
    y = rng.normal(0, 100, (1, n)).astype(np.float32)  # all singletons
    for j in range(n_long):  # optionally embed compressible plateaus
        lo = rng.integers(0, n - 8)
        y[0, lo:lo + 8] = y[0, lo]
    ts = np.arange(n, dtype=float)
    seg = jax_pla.disjoint_segment(y, 1e-5, max_run=127)
    blob = encode_batch(seg, y, "singlestreamv")[0]
    dec = np.asarray(decode_singlestreamv(blob, ts))
    assert len(dec) == n
    # counter bytes are signed and never exceed the cap in magnitude
    off = 0
    counters = []
    while off < len(blob):
        c = int(np.frombuffer(blob[off:off + 1], np.int8)[0])
        counters.append(c)
        assert -127 <= c <= 127 and c != 0
        off += 1 + 8 * (-c if c < 0 else 2)
    assert off == len(blob)
    if n > 254 and n_long == 0:
        assert counters.count(-127) >= 2  # straddled the cap twice
    # singleton values are exact
    singles = np.abs(dec - y[0]) == 0
    assert singles.mean() > 0.9


def check_emitter_equals_offline(seed, splits, method, protocol):
    """Arbitrary chunk splits: emitter bytes == offline encoder bytes."""
    T = sum(splits)
    y = _walk(seed, T, scale=0.7)
    y = np.concatenate([y, _walk(seed + 1, T, scale=20.0)])  # + noisy row
    cap = PROTOCOL_CAPS[protocol] or 256
    kk = _kk(method)
    eps = 0.8
    seg = SEGMENTERS[method](y, eps, max_run=cap)
    offline = encode_batch(seg, y, protocol, knot_kind=kk)

    stt = jax_pla.init_state(method, 2, eps, max_run=cap)
    em = ProtocolEmitter(protocol, 2, knot_kind=kk)
    got = [[] for _ in range(2)]
    pos = 0
    for w in splits:
        stt, out = jax_pla.step_chunk(stt, y[:, pos:pos + w])
        for s, b in enumerate(em.step_chunk(out, y[:, pos:pos + w])):
            got[s].append(b)
        pos += w
    stt, out_f = jax_pla.flush(stt)
    for s, b in enumerate(em.step_chunk(out_f)):
        got[s].append(b)
    for s, b in enumerate(em.flush()):
        got[s].append(b)
    for s in range(2):
        if protocol == "twostreams":
            merged = (b"".join(p[0] for p in got[s]),
                      b"".join(p[1] for p in got[s]))
        else:
            merged = b"".join(got[s])
        assert merged == offline[s], (method, protocol, splits, s)


# ---------------------------------------------------------------------------
# Hypothesis sweeps — skipped without hypothesis
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    @pytest.mark.parametrize("protocol", ENGINE_PROTOCOLS)
    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1),
           n=st.sampled_from(T_CHOICES),
           eps=st.floats(min_value=1e-2, max_value=20.0),
           method=st.sampled_from(sorted(SEGMENTERS)))
    def test_property_codec_roundtrip(protocol, seed, n, eps, method):
        check_codec_roundtrip(protocol, seed, n, eps, method)

    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1),
           n=st.integers(130, 400),
           n_long=st.integers(0, 2))
    def test_property_bursts_straddle_counter_cap(seed, n, n_long):
        check_bursts_straddle_counter_cap(seed, n, n_long)

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1),
           data=st.data(),
           method=st.sampled_from(sorted(SEGMENTERS)),
           protocol=st.sampled_from(ENGINE_PROTOCOLS))
    def test_property_emitter_equals_offline(seed, data, method, protocol):
        T = 96
        splits, left = [], T
        while left > 0:
            w = data.draw(st.integers(1, left), label="chunk")
            splits.append(w)
            left -= w
        check_emitter_equals_offline(seed, tuple(splits), method, protocol)


# ---------------------------------------------------------------------------
# Deterministic fixed-draw twins — always run
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("protocol", ENGINE_PROTOCOLS)
@pytest.mark.parametrize("method", sorted(SEGMENTERS))
def test_fixed_codec_roundtrip(protocol, method):
    for seed, n, eps in ((7, 64, 0.05), (11, 254, 1.5), (13, 300, 8.0)):
        check_codec_roundtrip(protocol, seed, n, eps, method)


def test_fixed_bursts_straddle_counter_cap():
    for seed, n, n_long in ((0, 300, 0), (1, 130, 2), (2, 399, 1)):
        check_bursts_straddle_counter_cap(seed, n, n_long)


@pytest.mark.parametrize("protocol", ("twostreams", "singlestream",
                                      "singlestreamv"))
def test_mixed_segments_fit_counter_cap(protocol):
    """A mixed join hands the previous run's last point to the next
    segment; at max_run = the protocol's cap every segment must still fit
    the counter, in the batched engine, the Pallas kernel and the
    sequential reference."""
    from repro.kernels.ops import KERNEL_SEGMENTERS
    cap = PROTOCOL_CAPS[protocol]
    T = 3 * cap + 50
    # Two capped runs on one line join; the jump after them makes the next
    # decision disjoint, so the joined run keeps the point it was handed.
    ramp = 0.01 * np.arange(T, dtype=np.float32)
    ramp[2 * cap:] += 100.0
    y = np.concatenate([ramp[None, :], _walk(4, T, scale=0.05)])
    eps = 0.5
    seg = jax_pla.mixed_segment(y, np.float32(eps), max_run=cap)
    kseg = KERNEL_SEGMENTERS["mixed"](y, eps, max_run=cap)
    np.testing.assert_array_equal(np.asarray(kseg.breaks),
                                  np.asarray(seg.breaks))
    ts = np.arange(T, dtype=float)
    for s in range(2):
        ends = np.flatnonzero(np.asarray(seg.breaks[s]))
        assert np.diff(ends, prepend=-1).max() <= cap, (protocol, s)
        ref = run_mixed(ts, y[s].astype(float), eps, max_run=cap)
        assert max(g.i1 - g.i0 for g in ref.segments) <= cap, (protocol, s)
    blobs = encode_batch(seg, y, protocol, knot_kind="mixed")
    for s in range(2):
        dec = np.asarray(_decode(protocol, blobs[s], ts))
        assert np.abs(dec - y[s]).max() <= eps * (1 + 1e-4) + 1e-4


@pytest.mark.parametrize("method", sorted(SEGMENTERS))
def test_fixed_emitter_equals_offline(method):
    for protocol in ENGINE_PROTOCOLS:
        for seed, splits in ((3, (1, 30, 31, 33, 1)),
                             (5, (96,)),
                             (8, (50, 46))):
            check_emitter_equals_offline(seed, splits, method, protocol)
