"""Vectorized protocol & metrics engine vs. the legacy golden references.

The contract under test (ISSUE 3): for every jnp streaming method x every
§5 protocol, the array engine of ``repro.core.protocol_engine`` must
produce (a) wire bytes identical to the legacy ``encode_*`` codecs, and
(b) §4.2 per-point metrics equal to ``metrics.point_metrics`` — both run
on the *same* segmentation via the ``to_method_outputs`` translation.
Also covers the fused reconstruction/error kernel path, the fixed-slot
record expansion, the streaming ``ProtocolEmitter``, the serving
plane's ``SlotPlaneEmitter`` against one ``ProtocolEmitter`` per row, and
the 2^24 absolute-time guard of the jnp reference segmenters.
"""

import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import jax_pla
from repro.core.evaluate import METHOD_KNOT_KINDS, evaluate_batched
from repro.core.metrics import point_metrics
from repro.core.protocol_engine import (ENGINE_PROTOCOLS, ProtocolEmitter,
                                        SlotPlaneEmitter,
                                        batched_point_metrics, encode_batch,
                                        protocol_nbytes,
                                        protocol_point_metrics,
                                        to_method_outputs)
from repro.core.protocols import (PROTOCOLS, PROTOCOL_CAPS, encode_implicit,
                                  encode_singlestream, encode_singlestreamv,
                                  encode_twostreams, decode_singlestreamv)

SEGMENTERS = {"angle": jax_pla.angle_segment,
              "swing": jax_pla.swing_segment,
              "disjoint": jax_pla.disjoint_segment,
              "linear": jax_pla.linear_segment}

LEGACY_ENCODERS = {
    "implicit": lambda recs, mo: encode_implicit(recs, mo),
    "twostreams": lambda recs, mo: encode_twostreams(recs),
    "singlestream": lambda recs, mo: encode_singlestream(recs),
    "singlestreamv": lambda recs, mo: encode_singlestreamv(recs),
}


def _knot_kind(method):
    return "joint" if method == "swing" else "disjoint"


def _batch(seed=0, S=4, T=257):
    """Random walks plus one noise row (forces singleton/burst paths)."""
    rng = np.random.default_rng(seed)
    y = np.cumsum(rng.normal(0, 0.6, (S, T)), axis=1)
    y[-1] = rng.normal(0, 25, T)
    return y.astype(np.float32)


@pytest.mark.parametrize("method", sorted(SEGMENTERS))
@pytest.mark.parametrize("protocol", ENGINE_PROTOCOLS)
def test_engine_matches_legacy_codecs_and_metrics(method, protocol):
    y = _batch()
    S, T = y.shape
    ts = np.arange(T, dtype=float)
    cap = PROTOCOL_CAPS[protocol] or 256
    kk = _knot_kind(method)
    seg = SEGMENTERS[method](y, 1.0, max_run=cap)

    mos = to_method_outputs(seg, ts, y, knot_kind=kk)
    blobs = encode_batch(seg, y, protocol, knot_kind=kk)
    bm = batched_point_metrics(seg, y, protocol, kk)
    nbytes, n_records = protocol_nbytes(seg, protocol, kk)

    for s in range(S):
        recs = PROTOCOLS[protocol](mos[s], ts, y[s])
        pm = point_metrics(recs, ts, y[s])
        # (a) byte-identical wire encodings
        ref = LEGACY_ENCODERS[protocol](recs, mos[s])
        got = tuple(blobs[s]) if protocol == "twostreams" else blobs[s]
        assert got == ref, f"{method}/{protocol}: wire bytes differ"
        # (b) metric-identical §4.2 arrays (float64, same expressions)
        np.testing.assert_array_equal(bm.ratio[s], pm.ratio)
        np.testing.assert_array_equal(bm.latency[s], pm.latency)
        np.testing.assert_array_equal(bm.error[s], pm.error)
        # (c) byte accounting
        assert int(nbytes[s]) == sum(r.nbytes for r in recs)
        assert int(n_records[s]) == len(recs)


@pytest.mark.parametrize("protocol", ENGINE_PROTOCOLS)
def test_device_metrics_single_jit(protocol):
    """The f32 device path agrees with the host float64 metrics."""
    y = _batch(seed=3, S=3, T=180)
    seg = jax_pla.disjoint_segment(y, 1.0,
                                   max_run=PROTOCOL_CAPS[protocol] or 256)
    ratio, latency, error = protocol_point_metrics(seg, jnp.asarray(y),
                                                   protocol)
    bm = batched_point_metrics(seg, y, protocol)
    np.testing.assert_allclose(np.asarray(ratio), bm.ratio, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(latency), bm.latency)
    np.testing.assert_allclose(np.asarray(error), bm.error, atol=2e-4)


def test_burst_split_at_counter_cap():
    """An all-singleton stream packs bursts of exactly 127 + remainder."""
    T = 300
    y = _batch(seed=9, S=1, T=T)[[0]]
    y[0] = np.random.default_rng(1).normal(0, 50, T).astype(np.float32)
    seg = jax_pla.disjoint_segment(y, 1e-6, max_run=127)
    bm = batched_point_metrics(seg, y, "singlestreamv")
    blobs = encode_batch(seg, y, "singlestreamv")
    # all points exact, wire = 3 counters + 8 bytes per value
    assert (bm.error[0] == 0).all()
    assert len(blobs[0]) == 3 + 8 * T
    dec = decode_singlestreamv(blobs[0], np.arange(T, dtype=float))
    np.testing.assert_array_equal(dec, np.asarray(y[0], np.float64))
    # burst ratio per point: (1 + 8m)/8/m with m in {127, 127, 46}
    m1 = (1 + 8 * 127) / 8 / 127
    m2 = (1 + 8 * 46) / 8 / 46
    np.testing.assert_allclose(np.sort(np.unique(bm.ratio[0])),
                               np.sort([m1, m2]))


def test_batched_summary_matches_pointmetrics_summary():
    y = _batch(seed=5, S=3, T=200)
    seg = jax_pla.angle_segment(y, 1.0, max_run=256)
    bm = batched_point_metrics(seg, y, "singlestream")
    full = bm.summary()
    for s in range(3):
        single = bm.stream(s).summary()
        for metric, stats in single.items():
            for stat, val in stats.items():
                assert full[metric][stat][s] == val, (s, metric, stat)


def test_evaluate_batched_matches_legacy_rows():
    y = _batch(seed=7, S=3, T=220)
    ts = np.arange(y.shape[1], dtype=float)
    r = evaluate_batched("linear", "singlestream", y, 1.0)
    seg = jax_pla.linear_segment(y, 1.0, max_run=256)
    for s, mo in enumerate(to_method_outputs(seg, ts, y)):
        recs = PROTOCOLS["singlestream"](mo, ts, y[s])
        assert r.n_records[s] == len(recs)
        assert r.overall_ratio[s] == sum(x.nbytes for x in recs) / (8 * 220)
    # the kernel reconstruction path agrees within f32 rounding
    rp = evaluate_batched("linear", "singlestream", y, 1.0,
                          reconstruct="pallas")
    np.testing.assert_allclose(rp.metrics.error, r.metrics.error, atol=2e-4)


def test_emitter_chunked_equals_offline():
    y = _batch(seed=11, S=3, T=150)
    T = y.shape[1]
    for method in ("angle", "swing"):
        kk = _knot_kind(method)
        for protocol in ENGINE_PROTOCOLS:
            cap = PROTOCOL_CAPS[protocol] or 256
            seg = SEGMENTERS[method](y, 0.8, max_run=cap)
            offline = encode_batch(seg, y, protocol, knot_kind=kk)
            for splits in [(T,), (1, 30, 31, 40, 47, 1), (149, 1)]:
                st = jax_pla.init_state(method, 3, 0.8, max_run=cap)
                em = ProtocolEmitter(protocol, 3, knot_kind=kk)
                got = [[] for _ in range(3)]
                pos = 0
                for w in splits:
                    st, out = jax_pla.step_chunk(st, y[:, pos:pos + w])
                    for s, b in enumerate(em.step_chunk(out,
                                                        y[:, pos:pos + w])):
                        got[s].append(b)
                    pos += w
                st, out_f = jax_pla.flush(st)
                for s, b in enumerate(em.step_chunk(out_f)):
                    got[s].append(b)
                for s, b in enumerate(em.flush()):
                    got[s].append(b)
                for s in range(3):
                    if protocol == "twostreams":
                        merged = (b"".join(p[0] for p in got[s]),
                                  b"".join(p[1] for p in got[s]))
                        assert merged == offline[s], (method, protocol,
                                                      splits, s)
                    else:
                        assert b"".join(got[s]) == offline[s], \
                            (method, protocol, splits, s)


def test_emitter_vectorized_bookkeeping_bit_identical_at_s256():
    """The vectorized (array-state, O(events)) emitter stays bit-identical
    to encode_batch on a 256-stream fleet for every protocol (ISSUE 4:
    the per-stream Python row-codec walk was hoisted into numpy)."""
    S, T = 256, 96
    rng = np.random.default_rng(21)
    y = np.cumsum(rng.normal(0, 0.6, (S, T)), axis=1).astype(np.float32)
    y[::5] = rng.normal(0, 25, (len(range(0, S, 5)), T))  # singleton rows
    for protocol in ENGINE_PROTOCOLS:
        cap = PROTOCOL_CAPS[protocol] or 256
        seg = jax_pla.disjoint_segment(y, 1.0, max_run=cap)
        offline = encode_batch(seg, y, protocol)
        st = jax_pla.init_state("disjoint", S, 1.0, max_run=cap)
        em = ProtocolEmitter(protocol, S)
        got = [[] for _ in range(S)]
        pos = 0
        for w in (40, 31, 25):
            st, out = jax_pla.step_chunk(st, y[:, pos:pos + w])
            for s, b in enumerate(em.step_chunk(out, y[:, pos:pos + w])):
                got[s].append(b)
            pos += w
        st, out_f = jax_pla.flush(st)
        for s, b in enumerate(em.step_chunk(out_f)):
            got[s].append(b)
        for s, b in enumerate(em.flush()):
            got[s].append(b)
        for s in range(S):
            if protocol == "twostreams":
                merged = (b"".join(p[0] for p in got[s]),
                          b"".join(p[1] for p in got[s]))
                assert merged == tuple(offline[s]), (protocol, s)
            else:
                assert b"".join(got[s]) == offline[s], (protocol, s)


@pytest.mark.parametrize("method", ["continuous", "mixed"])
def test_emitter_fused_packer_deferred_kinds_at_s64(method):
    """The fused cumsum-offset packer stays bit-identical for the
    deferred knot kinds at fleet width — the mixed pending-y'' chain and
    the grouped first-event seeding are exercised across many streams
    and chunk boundaries at once (ISSUE 5: the per-event Python byte
    assembly was replaced by vectorized packing)."""
    S, T = 64, 120
    rng = np.random.default_rng(33)
    y = np.cumsum(rng.normal(0, 0.6, (S, T)), axis=1).astype(np.float32)
    y[::4] = rng.normal(0, 25, (S // 4, T)).astype(np.float32)
    seg_fn = {"continuous": jax_pla.continuous_segment,
              "mixed": jax_pla.mixed_segment}[method]
    offline = encode_batch(seg_fn(y, 1.0, max_run=256), y, "implicit",
                           knot_kind=method)
    st = jax_pla.init_state(method, S, 1.0, max_run=256)
    em = ProtocolEmitter("implicit", S, knot_kind=method)
    got = [b""] * S
    pos = 0
    for w in (37, 41, 42):
        st, out = jax_pla.step_chunk(st, y[:, pos:pos + w])
        for s, b in enumerate(em.step_chunk(out, y[:, pos:pos + w])):
            got[s] += b
        pos += w
    st, out_f = jax_pla.flush(st)
    for s, b in enumerate(em.step_chunk(out_f)):
        got[s] += b
    for s, b in enumerate(em.flush()):
        got[s] += b
    assert got == offline


class _OneRow:
    """One stream's own one-row ``ProtocolEmitter``, the reference for a
    plane row: a tick's events, tagged with row-local positions, are
    scattered onto the columns finalized since the last feed (the close
    comes with no values)."""

    def __init__(self, protocol, knot_kind):
        self.em = ProtocolEmitter(protocol, 1, knot_kind=knot_kind)
        self.points = 0
        self.emitted = 0

    def feed(self, pos, a, v, values=None):
        c = 0 if values is None else len(values)
        frontier = self.points + c - 1 if values is not None else self.points
        w = max(frontier - self.emitted, 0)
        events = None
        if w:
            brk = np.zeros((1, w), bool)
            A = np.zeros((1, w), np.float32)
            V = np.zeros((1, w), np.float32)
            brk[0, pos - self.emitted] = True
            A[0, pos - self.emitted] = a
            V[0, pos - self.emitted] = v
            events = jax_pla.SegmentOutput(brk, A, V)
            self.emitted += w
        self.points += c
        return self.em.step_chunk(
            events, None if values is None else values[None])[0]


def _host(events):
    return jax_pla.MaskedEvents(*(np.asarray(x) for x in events))


@pytest.mark.parametrize("method,protocol", [
    ("linear", "singlestream"), ("swing", "twostreams"),
    ("angle", "singlestreamv"), ("disjoint", "implicit"),
    ("swing", "implicit")])
def test_slot_plane_emitter_matches_one_emitter_per_row(method, protocol):
    """Per row and per tick, a shard's one ``SlotPlaneEmitter`` gives the
    bytes (twostreams pairs included) that one ``ProtocolEmitter`` per row
    gives, through ragged lengths, idle rows, segments that reach
    ``max_run``, planes that widen the ring, and rows closed and started
    afresh mid-run."""
    R, max_run, eps = 6, 64, 0.8
    rng = np.random.default_rng(41)
    kk = METHOD_KNOT_KINDS.get(method, "disjoint")
    T = 3000
    src = np.cumsum(rng.normal(0, 0.6, (R, T)), axis=1)
    src[2] = rng.normal(0, 25, T)                   # singletons and bursts
    src[3] = 0.25 * np.arange(T)                    # runs capped at max_run
    src = src.astype(np.float32)
    cursor = np.zeros(R, np.int64)
    st = jax_pla.masked_init_state(method, R, np.full(R, eps, np.float32),
                                   max_run=max_run)
    plane_em = SlotPlaneEmitter(protocol, R, max_run=max_run, knot_kind=kk)
    rows = [_OneRow(protocol, kk) for _ in range(R)]
    empty = (b"", b"") if protocol == "twostreams" else b""

    def close(r):
        nonlocal st
        mask = np.arange(R) == r
        st, final = jax_pla.masked_flush_rows(st, mask)
        final = jax_pla.MaskedEvents(*(np.asarray(x)[:, None]
                                       for x in final))
        got = plane_em.step_chunk(final)
        assert all(got[i] == empty for i in range(R) if i != r)
        want = rows[r].feed(final.pos[r, :1], final.a[r, :1],
                            final.v[r, :1]) if rows[r].points else empty
        assert got[r] == want
        assert plane_em.flush_rows(mask) == rows[r].em.flush()
        plane_em.reset_rows(mask)
        rows[r] = _OneRow(protocol, kk)

    for tick in range(48):
        if tick % 9 == 8:
            close(int(rng.integers(R)))
        n = int(rng.choice([1, 7] if tick < 12 else [1, 7, 16, 33]))
        lengths = rng.integers(0, n + 1, R)
        if 14 <= tick < 22:
            lengths[4] = 0                          # idle for eight ticks
        y = np.zeros((R, n), np.float32)
        for r in range(R):
            y[r, :lengths[r]] = src[r, cursor[r]:cursor[r] + lengths[r]]
        cursor += lengths
        st, out = jax_pla.masked_step_chunk(st, y, lengths)
        out = _host(out)
        got = plane_em.step_chunk(out, (y, lengths))
        for r in range(R):
            if not lengths[r]:
                assert got[r] == empty
                continue
            js = np.flatnonzero(out.ev[r])
            assert got[r] == rows[r].feed(out.pos[r, js], out.a[r, js],
                                          out.v[r, js], y[r, :lengths[r]])
    assert np.array_equal(plane_em.points, [o.points for o in rows])
    for r in range(R):
        close(r)


def test_records_to_events_roundtrip_and_kernel_reconstruct():
    from repro.kernels.ops import (reconstruct_error_tpu,
                                   reconstruct_records_tpu)
    y = _batch(seed=13, S=4, T=100)[:, :100]
    yj = jnp.asarray(y)
    seg = jax_pla.disjoint_segment(yj, 1.0, max_run=24)
    rec = jax_pla.to_records(seg, 64)
    assert int(rec.overflow.sum()) == 0
    back = jax_pla.records_to_events(rec, 100)
    np.testing.assert_array_equal(np.asarray(back.breaks),
                                  np.asarray(seg.breaks))
    ref = np.asarray(jax_pla.propagate_lines(seg))
    out = np.asarray(reconstruct_records_tpu(rec, 100, block_s=8,
                                             block_t=32))
    np.testing.assert_array_equal(out, ref)
    recon, err = reconstruct_error_tpu(seg, yj, block_s=8, block_t=32)
    np.testing.assert_array_equal(np.asarray(recon), ref)
    np.testing.assert_array_equal(np.asarray(err), np.abs(ref - y))


def test_step_chunk_guards_2pow24_absolute_time():
    st = jax_pla.init_state("angle", 2, 1.0)
    near = dataclasses.replace(st, t=jax_pla.MAX_STREAM_T - 2)
    with pytest.raises(ValueError, match="2\\^24"):
        jax_pla.step_chunk(near, jnp.zeros((2, 4), jnp.float32))
    # reaching the limit exactly is fine ...
    at = dataclasses.replace(st, t=jax_pla.MAX_STREAM_T - 4)
    st2, _ = jax_pla.step_chunk(at, jnp.zeros((2, 4), jnp.float32))
    assert st2.t == jax_pla.MAX_STREAM_T
    # ... but flush does NOT rebase absolute time (callers keep absolute
    # record positions): only a fresh state does.
    st3, _ = jax_pla.flush(st2)
    with pytest.raises(ValueError, match="fresh"):
        jax_pla.step_chunk(st3, jnp.zeros((2, 1), jnp.float32))
    fresh = jax_pla.init_state("angle", 2, 1.0)
    st4, _ = jax_pla.step_chunk(fresh, jnp.zeros((2, 4), jnp.float32))
    assert st4.carry is not None
