"""SegmentStore: indexed random access + closed-form analytics.

Deterministic pins for the queryable store (PR 10):

- ``scan`` (the brute-force path) is bit-identical to the legacy
  ``repro.core.protocols.decode_*`` codecs on the same blobs, for all 13
  Table-2 combinations;
- windowed decodes only touch index-located payload slices (asserted on
  the store's ``bytes_touched`` counter) yet return exactly the
  overlap-filtered records of a full decode;
- every analytics answer ``(value, error_bound)`` contains both the
  decoded brute-force answer and the answer on the *original* data
  within its bound;
- the blob hand-offs (``FleetStream(store=...)``,
  ``SlotManager(store=...)``) produce archives equal to one offline
  ``encode_batch`` of the same data — payload bytes, index entries,
  scans and queries.

The randomized sweeps (hypothesis + fixed-draw twins) live in
tests/test_store_property.py.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import protocols as legacy
from repro.core.evaluate import (BATCHED_SEGMENTERS, COMBINATIONS,
                                 METHOD_KNOT_KINDS)
from repro.core.protocol_engine import decode_batch, encode_batch
from repro.core.protocols import PROTOCOL_CAPS
from repro.store import SegmentStore

PROTOCOLS = ("implicit", "twostreams", "singlestream", "singlestreamv")


def _make(seed, S, T, scale=0.5):
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.normal(0, scale, (S, T)), axis=1).astype(
        np.float32)


def _encode(method, protocol, y, eps, *, t0=0.0, dt=1.0):
    cap = PROTOCOL_CAPS[protocol] or 256
    seg = BATCHED_SEGMENTERS[method](
        jnp.asarray(y), jnp.full((y.shape[0],), eps, jnp.float32),
        max_run=cap)
    kk = METHOD_KNOT_KINDS.get(method, "disjoint")
    return encode_batch(seg, y, protocol, kk, t0=t0, dt=dt)


def _legacy_decode(blob, protocol, ts):
    if protocol == "twostreams":
        vals = legacy.decode_twostreams(blob[0], blob[1], ts)
    else:
        vals = getattr(legacy, "decode_" + protocol)(blob, ts)
    return np.asarray(vals, np.float64)


def _build_store(method, protocol, y, eps, **kw):
    store = SegmentStore(protocol, eps=eps, **kw)
    store.append(_encode(method, protocol, y, eps,
                         t0=kw.get("t0", 0.0), dt=kw.get("dt", 1.0)),
                 close=True)
    return store


# ---------------------------------------------------------------------------
# Brute-force parity and windowed access
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("key", sorted(COMBINATIONS))
def test_scan_matches_legacy_decoders(key):
    method, protocol = COMBINATIONS[key]
    y = _make(0, 2, 257)
    wire = _encode(method, protocol, y, 0.5)
    store = SegmentStore(protocol, eps=0.5)
    store.append(wire, close=True)
    ts = np.arange(257, dtype=np.float64)
    for s, got in store.scan().items():
        ref = _legacy_decode(wire[s], protocol, ts)
        np.testing.assert_array_equal(got, ref, err_msg=key)
        assert np.max(np.abs(ref - y[s].astype(np.float64))) \
            <= 0.5 * (1 + 1e-3) + 1e-3


@pytest.mark.parametrize("protocol", PROTOCOLS)
def test_windowed_decode_touches_few_bytes(protocol):
    method = "swing" if protocol == "implicit" else "linear"
    T = 4096
    store = _build_store(method, protocol, _make(1, 1, T), 0.5,
                         index_every=32)
    total = store.n_bytes(0)
    full = store._streams[0].decode(0, T)[0]
    # A 1% window decodes from the located index snapshot, not byte 0.
    lo, hi = 2000, 2000 + T // 100
    store.reset_stats()
    win = store.decode(0, float(lo), float(hi))
    assert store.stats["bytes_touched"] < 0.15 * total
    assert store.stats["decodes"] == 1
    # ... and is exactly the overlap-filtered slice of the full decode.
    mask = (full.start < hi) & (full.start + full.length > lo)
    for col in ("off", "sub", "size", "kind", "start", "length", "a",
                "tref", "yref"):
        np.testing.assert_array_equal(getattr(win, col),
                                      getattr(full, col)[mask],
                                      err_msg=f"{protocol}/{col}")
    np.testing.assert_array_equal(win.reconstruct(lo, hi, 0.0, 1.0),
                                  full.reconstruct(lo, hi, 0.0, 1.0))


def test_locate_is_monotone_and_bounded():
    store = _build_store("linear", "singlestream", _make(2, 1, 2000), 0.3,
                         index_every=16)
    offs = [store.locate(0, float(t)) for t in range(0, 2000, 50)]
    assert all(b >= a for a, b in zip(offs, offs[1:]))
    assert offs[0] == 0 and offs[-1] <= store.n_bytes(0)


# ---------------------------------------------------------------------------
# Closed-form analytics vs brute force
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("key", sorted(COMBINATIONS))
def test_query_bounds_contain_brute_force(key):
    method, protocol = COMBINATIONS[key]
    eps, S, T = 0.5, 3, 900
    y = _make(3, S, T)
    store = _build_store(method, protocol, y, eps)
    recon = np.stack([store.scan()[s] for s in range(S)])
    for lo, hi in ((0, T), (100, 400), (713, 714), (0, 7)):
        sl = recon[:, lo:hi]
        brute = {"sum": sl.sum(axis=1), "avg": sl.mean(axis=1),
                 "min": sl.min(axis=1), "max": sl.max(axis=1),
                 "count": np.full(S, hi - lo, float)}
        orig = y[:, lo:hi].astype(np.float64)
        brute_o = {"sum": orig.sum(axis=1), "avg": orig.mean(axis=1),
                   "min": orig.min(axis=1), "max": orig.max(axis=1),
                   "count": brute["count"]}
        for kind, ref in brute.items():
            out = store.query(kind, list(range(S)), float(lo), float(hi))
            for s, (val, bound) in enumerate(out):
                assert bound >= 0
                tol = 1e-6 * (1.0 + abs(val))
                # closed form == brute force on the decoded series ...
                assert abs(val - ref[s]) <= bound + tol, (key, kind, s)
                # ... and the bound also covers the *original* data.
                assert abs(val - brute_o[kind][s]) \
                    <= bound * (1 + 1e-3) + 1e-3, (key, kind, s)
        if hi - lo >= 3:
            r_hat, bound = store.query("corr", [0, 1], float(lo),
                                       float(hi))
            ref = np.corrcoef(recon[0, lo:hi], recon[1, lo:hi])[0, 1]
            if np.isnan(ref):
                assert np.isinf(bound)
            else:
                assert abs(r_hat - ref) <= bound + 1e-6, (key, lo, hi)


def test_count_is_exact_and_free():
    store = _build_store("linear", "singlestream", _make(4, 2, 300), 1.0)
    for (val, bound) in store.query("count", [0, 1], 10.0, 250.0):
        assert val == 240.0 and bound == 0.0


def test_query_on_time_grid_with_offset_and_stride():
    t0, dt = 100.0, 0.5
    T = 400
    y = _make(5, 1, T)
    store = _build_store("linear", "singlestream", y, 0.4, t0=t0, dt=dt)
    recon = store.scan()[0]
    # real-time window [110, 130) -> grid [20, 60)
    (val, bound), = store.query("sum", [0], 110.0, 130.0)
    ref = recon[20:60].sum()
    assert abs(val - ref) <= bound + 1e-6 * (1 + abs(val))
    assert store.n_points(0) == T
    got = store.scan(t0=110.0, t1=130.0)[0]
    np.testing.assert_array_equal(got, recon[20:60])


# ---------------------------------------------------------------------------
# Blob hand-offs: fleet ingest and serving slots feed the same archive
# ---------------------------------------------------------------------------

# All 13 Table-2 combinations, plus linear/implicit (off the table).
HANDOFF_COMBOS = dict(COMBINATIONS, **{"linear-implicit":
                                       ("linear", "implicit")})


@pytest.mark.parametrize("key", sorted(HANDOFF_COMBOS))
def test_fleet_handoff_equals_offline_store(key):
    from repro.sharding.fleet import FleetStream

    method, protocol = HANDOFF_COMBOS[key]
    S, T, eps = 4, 500, 0.5
    y = _make(5, S, T)
    store = SegmentStore(protocol, eps=eps)
    fs = FleetStream(method, protocol, S, eps, store=store)
    for lo in range(0, T, 77):
        fs.push(y[:, lo:lo + 77])
    fs.finish()
    off = _build_store(method, protocol, y, eps)
    assert store.keys() == off.keys()
    for k in store.keys():
        assert store.n_points(k) == off.n_points(k) == T
        assert bytes(store._streams[k].payload) \
            == bytes(off._streams[k].payload)
        assert bytes(store._streams[k].payload2) \
            == bytes(off._streams[k].payload2)
        assert store._streams[k].e_pos == off._streams[k].e_pos
        np.testing.assert_array_equal(store.scan([k])[k], off.scan([k])[k])
    assert store.query("avg", list(range(S)), 40.0, 460.0) \
        == off.query("avg", list(range(S)), 40.0, 460.0)


@pytest.mark.parametrize("protocol", PROTOCOLS)
def test_slots_handoff_equals_offline_store(protocol):
    from repro.serving.slots import SlotManager

    eps = 0.5
    store = SegmentStore(protocol, eps=eps)
    mgr = SlotManager("linear", protocol, capacity=2, eps0=eps,
                      max_run=PROTOCOL_CAPS[protocol] or 256, store=store)
    y = _make(6, 1, 300)[0]
    slot = mgr.admit("s0")
    key = ("s0", slot.index, slot.generation)
    for lo in range(0, 300, 13):
        chunk = y[lo:lo + 13]
        plane = np.zeros((mgr.capacity, chunk.size), np.float32)
        lens = np.zeros(mgr.capacity, np.int64)
        plane[slot.index, :] = chunk
        lens[slot.index] = chunk.size
        mgr.step(plane, lens)
    mgr.evict("s0")
    assert store._streams[key].closed
    off = _build_store("linear", protocol, y[None], eps)
    assert store.n_points(key) == 300
    assert bytes(store._streams[key].payload) \
        == bytes(off._streams[0].payload)
    assert bytes(store._streams[key].payload2) \
        == bytes(off._streams[0].payload2)
    np.testing.assert_array_equal(store.scan([key])[key],
                                  off.scan([0])[0])
    assert store.query("max", [key], 20.0, 280.0) \
        == off.query("max", [0], 20.0, 280.0)


def test_store_protocol_mismatch_is_rejected():
    from repro.serving.slots import SlotManager
    from repro.sharding.fleet import FleetStream

    store = SegmentStore("singlestream")
    with pytest.raises(ValueError, match="store speaks"):
        FleetStream("linear", "implicit", 2, 1.0, store=store)
    with pytest.raises(ValueError, match="store speaks"):
        SlotManager("linear", "twostreams", capacity=2, store=store)


# ---------------------------------------------------------------------------
# Engine re-export and error paths
# ---------------------------------------------------------------------------

def test_decode_batch_engine_reexport():
    y = _make(7, 2, 200)
    wire = _encode("linear", "singlestream", y, 0.5)
    ts = np.arange(200, dtype=np.float64)
    for s, recs in enumerate(decode_batch(wire, "singlestream")):
        assert (np.diff(recs.off) > 0).all()   # offsets ride along
        assert recs.size.sum() == len(wire[s])
        np.testing.assert_array_equal(recs.reconstruct(0, 200, 0.0, 1.0),
                                      _legacy_decode(wire[s],
                                                     "singlestream", ts))


def test_store_error_paths():
    with pytest.raises(ValueError, match="unknown protocol"):
        SegmentStore("morse")
    store = _build_store("linear", "singlestream", _make(8, 2, 100), 1.0)
    with pytest.raises(ValueError, match="unknown query kind"):
        store.query("median", [0])
    with pytest.raises(ValueError, match="exactly two"):
        store.query("corr", [0])
    with pytest.raises(KeyError):
        store.query("sum", [99])
    with pytest.raises(ValueError, match="already exists"):
        store.add_stream(0)
    with pytest.raises(ValueError, match="closed"):
        store.append_stream(0, b"\x00" * 17)
    with pytest.raises(ValueError, match="outside the readable"):
        store._streams[0].decode(0, 101)
    with pytest.raises(TypeError, match="expects bytes"):
        SegmentStore("singlestream").append_stream("k", (b"", b""))
    with pytest.raises(ValueError):
        SegmentStore("twostreams").append_stream("k", b"notapair")
    from repro.store import StreamIndex
    with pytest.raises(ValueError, match="index_every"):
        StreamIndex("singlestream", index_every=0)


def test_analytics_guards_and_eps_notes():
    from repro.store.analytics import cover_arrays, window_aggregate

    store = _build_store("linear", "singlestream", _make(9, 2, 200), 1.0)
    # note_eps widens the bound monotonically (running max in force).
    (_, b0), = store.query("sum", [0], 0.0, 200.0)
    store.note_eps(0, 4.0)
    (_, b1), = store.query("sum", [0], 0.0, 200.0)
    assert b1 > b0
    recs = store.decode(0)
    cov = cover_arrays(recs, 0, 200, 0.0, 1.0)
    with pytest.raises(ValueError, match="do not tile"):
        cover_arrays(recs, 0, 201, 0.0, 1.0)
    with pytest.raises(ValueError, match="unknown aggregate"):
        window_aggregate("median", [cov], np.ones(1), 0, 200)
    with pytest.raises(ValueError, match="incomplete"):
        window_aggregate("sum", [cov], np.ones(1), 0, 150)
    from repro.store.analytics import window_correlation
    with pytest.raises(ValueError, match="incomplete"):
        window_correlation(cov, cov, 1.0, 1.0, 0, 150)
    # Mismatched windows across streams are refused, not averaged away.
    store.add_stream("short")
    store.append_stream(
        "short", bytes(_build_store("linear", "singlestream",
                                    _make(9, 1, 50), 1.0)
                       ._streams[0].payload), close=True)
    with pytest.raises(ValueError, match="resolve identically"):
        store.query("sum", [0, "short"])
