"""Serving front-end (ISSUE 9): churny admission over padded slots.

The contracts under test:

- **Recycling is invisible.**  A slot that has been freed and re-admitted
  (generation bumped) produces segmenter output and wire bytes
  bit-identical to a fresh single-stream run of the new occupant's data —
  the masked engine rebuilds the carry row from the stream's first point,
  so no prior state can leak.
- **Eviction closes the books.**  A stream's lifetime wire bytes
  (per-tick blobs + the eviction tail) equal the offline
  :func:`repro.core.protocol_engine.encode_batch` of its own data,
  regardless of tick phasing, slot placement, or fleet churn around it.
- **Backpressure is visible.**  Bounded ingress queues shed (counted) or
  refuse (caller retries) — never silently drop.
- **The budget holds.**  With a :class:`repro.serving.GlobalEpsBudget`
  attached, fleet egress converges into a band around the operator's
  bytes/s target after warm-up.

The hypothesis churn test has a deterministic fixed-draw twin so its body
runs without hypothesis (dev dep); the 8-device case runs in a
subprocess (XLA_FLAGS must precede jax init — same pattern as
tests/test_fleet.py).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core.evaluate import (BATCHED_SEGMENTERS, COMBINATIONS,
                                 METHOD_KNOT_KINDS)
from repro.core.jax_pla import DEFERRED_METHODS
from repro.core.protocol_engine import encode_batch
from repro.core.protocols import PROTOCOL_CAPS
from repro.serving import (FleetFull, GlobalEpsBudget, INACTIVE_EPS,
                           ServeLoop, SlotManager)

EPS = 0.4


def _walk(rng, n):
    return np.cumsum(rng.normal(0, 0.6, n)).astype(np.float32)


def _offline_parts(y, method="linear", protocol="singlestream",
                   eps=EPS, max_run=256):
    """The offline encode of one stream: its bytes, or the twostreams
    ``(segment, singleton)`` pair."""
    seg = BATCHED_SEGMENTERS[method](y[None], eps, max_run=max_run)
    return encode_batch(seg, y[None], protocol,
                        METHOD_KNOT_KINDS.get(method, "disjoint"))[0]


def _offline_bytes(y, method="linear", protocol="singlestream",
                   eps=EPS, max_run=256) -> bytes:
    rec = _offline_parts(y, method, protocol, eps, max_run)
    return b"".join(rec) if isinstance(rec, tuple) else rec


def _store_for(protocol):
    """An archive for twostreams, whose wire interleaves the pair tick by
    tick; the archive keeps the two streams apart, as the offline encoder
    does."""
    from repro.store import SegmentStore
    return SegmentStore(protocol, eps=EPS) \
        if protocol == "twostreams" else None


def _assert_offline(wire, y, method, protocol, max_run, store=None,
                    key=None):
    """A stream's lifetime wire equals the offline encode of its data;
    for twostreams the archived pair equals the offline pair and the
    interleaved wire has its length."""
    ref = _offline_parts(y, method, protocol, max_run=max_run)
    if protocol == "twostreams":
        held = store._streams[key]
        assert (bytes(held.payload), bytes(held.payload2)) == ref, key
        assert len(wire) == len(b"".join(ref)), key
    else:
        assert wire == ref, key


# ---------------------------------------------------------------------------
# Slot recycling: generation N output == fresh run, bytes == offline
# ---------------------------------------------------------------------------

CHURN_COMBOS = [("linear", "singlestream"), ("swing", "twostreams"),
                ("angle", "singlestreamv"), ("disjoint", "implicit")]

# The 11 Table-2 combinations serving admits: all but the deferred
# methods (C, M), which the slot plane rejects.
SERVE_COMBOS = [COMBINATIONS[k] for k in sorted(COMBINATIONS)
                if COMBINATIONS[k][0] not in DEFERRED_METHODS]


def _churn_body(seed, n_ops, method="linear", protocol="singlestream",
                capacity=4):
    """Random admit / evict / offer-and-tick over a ServeLoop; checks
    every evicted stream's lifetime wire against the offline encode of
    its own accepted data (for twostreams, the archived pair against the
    offline pair).

    Ticks take planes of random width; streams go quiet for several
    ticks or are offered nothing; a third of them follow exact ramps, so
    their segments reach ``max_run``; offers outpace the ticks, so evicts
    drain queued points; and every draw ends by evicting a stream and
    admitting another into its slot."""
    rng = np.random.default_rng(seed)
    store = _store_for(protocol)
    mgr = SlotManager(method, protocol, capacity=capacity, eps0=EPS,
                      max_run=64, store=store)
    loop = ServeLoop(mgr, tick_width=16, queue_cap=1 << 16)
    fed = {}                    # stream_id -> list of accepted chunks
    wire = {}                   # stream_id -> accumulated bytes
    quiet = {}                  # stream_id -> ticks left without offers
    ramp = {}                   # stream_id -> [next value, slope] or None
    live = []

    def admit():
        sid = f"s{len(fed)}"
        loop.admit(sid)
        fed[sid], wire[sid], quiet[sid] = [np.zeros(0, np.float32)], b"", 0
        ramp[sid] = [float(rng.uniform(-5, 5)), float(rng.uniform(-.5, .5))] \
            if rng.random() < 1 / 3 else None
        live.append(sid)

    def offer(sid, c):
        if ramp[sid] is None:
            y = _walk(rng, c)
        else:
            y0, a = ramp[sid]
            y = (y0 + a * np.arange(c)).astype(np.float32)
            ramp[sid][0] = y0 + a * c
        assert loop.offer(sid, y) == c
        fed[sid].append(y)

    def collect(blobs):
        for sid, _gen, blob in blobs:
            wire[sid] += blob

    def close(sid):
        rep = loop.evict(sid)   # drains the points still queued
        live.remove(sid)
        collect(rep.wire)
        wire[sid] += rep.tail
        y = np.concatenate(fed[sid])
        if y.size:
            _assert_offline(wire[sid], y, method, protocol, 64, store,
                            (sid, rep.slot, rep.generation))
            assert rep.points == y.size
        assert rep.nbytes == len(wire[sid])

    for _ in range(n_ops):
        op = rng.integers(3)
        if op == 0 and len(live) < mgr.capacity:
            admit()
        elif op == 1 and live:
            close(live[int(rng.integers(len(live)))])
        elif live:
            loop.tick_width = int(rng.integers(1, 40))
            for sid in live:
                if quiet[sid]:
                    quiet[sid] -= 1
                elif rng.random() < 0.2:
                    quiet[sid] = int(rng.integers(2, 6))
                else:
                    offer(sid, int(rng.integers(0, 2 * loop.tick_width)))
            collect(loop.tick().wire)
    # Slots recycle LIFO: an evict with points queued, then an admit,
    # puts the new stream in the slot just freed.
    if not live:
        admit()
    offer(live[0], 50)
    close(live[0])
    admit()
    offer(live[-1], 50)
    for sid in list(live):
        close(sid)
    assert any(s.generation > 1 for s in mgr.slots)


def test_churn_fixed_draws():
    for seed in (0, 1, 7):
        _churn_body(seed, 40)


@pytest.mark.parametrize("method,protocol",
                         SERVE_COMBOS + [("swing", "twostreams")])
def test_churn_other_combinations(method, protocol):
    _churn_body(3, 30, method=method, protocol=protocol)


if HAVE_HYPOTHESIS:
    @settings(max_examples=15, deadline=None)
    @given(st.integers(0, 10_000), st.integers(5, 45))
    def test_churn_property(seed, n_ops):
        _churn_body(seed, n_ops)


@pytest.mark.parametrize("method,protocol", SERVE_COMBOS)
def test_recycled_slot_bit_identical_to_fresh_run(method, protocol):
    """Generation 2 of a slot == the same data through a generation-1
    manager: the slot plane carries no memory of its previous occupant."""
    rng = np.random.default_rng(5)
    ya, yb = _walk(rng, 300), _walk(rng, 300)
    max_run = PROTOCOL_CAPS[protocol] or 256
    kw = dict(capacity=1, eps0=EPS, max_run=max_run)

    store = _store_for(protocol)
    mgr = SlotManager(method, protocol, store=store, **kw)
    mgr.admit("a")
    lengths = np.full(1, 100, np.int64)
    for k in range(3):
        mgr.step(ya[None, 100 * k:100 * (k + 1)], lengths)
    mgr.evict("a")
    slot = mgr.admit("b")                 # recycles the only slot
    assert slot.generation == 2
    blobs = b""
    for k in range(3):
        for _, _, b in mgr.step(yb[None, 100 * k:100 * (k + 1)], lengths):
            blobs += b
    blobs += mgr.evict("b").tail

    fresh = SlotManager(method, protocol, **kw)
    fresh.admit("b")
    ref = b""
    for k in range(3):
        for _, _, b in fresh.step(yb[None, 100 * k:100 * (k + 1)], lengths):
            ref += b
    ref += fresh.evict("b").tail
    assert blobs == ref
    _assert_offline(blobs, yb, method, protocol, max_run, store,
                    ("b", slot.index, 2))


# ---------------------------------------------------------------------------
# Admission errors and the ε plane
# ---------------------------------------------------------------------------

def test_admission_errors():
    mgr = SlotManager(capacity=2)
    mgr.admit("a")
    with pytest.raises(ValueError, match="already admitted"):
        mgr.admit("a")
    mgr.admit("b")
    with pytest.raises(FleetFull):
        mgr.admit("c")
    with pytest.raises(KeyError):
        mgr.evict("nope")
    plane = np.zeros((mgr.capacity, 4), np.float32)
    lengths = np.array([0, 2], np.int64)
    mgr.evict("b")
    with pytest.raises(ValueError, match="free slot"):
        mgr.step(plane, lengths)


def test_set_eps_masks_free_rows():
    mgr = SlotManager(capacity=4, eps0=1.0)
    mgr.admit("a")
    mgr.admit("b")
    mgr.set_eps(np.full(4, 0.25))
    eps = mgr.eps
    live = mgr.live_mask()
    assert (eps[live] == 0.25).all()
    assert (eps[~live] == np.float32(INACTIVE_EPS)).all()


def test_deferred_methods_rejected():
    with pytest.raises(ValueError, match="deferred"):
        SlotManager("continuous", capacity=2)


# ---------------------------------------------------------------------------
# Tick loop: phasing invariance + backpressure
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method,protocol", SERVE_COMBOS)
def test_tick_phasing_leaves_no_trace_in_wire(method, protocol):
    """Out-of-phase ragged offers produce the same per-stream bytes as
    the offline encode — tick batching is pure transport."""
    rng = np.random.default_rng(9)
    data = {f"s{i}": _walk(rng, 257 + 31 * i) for i in range(3)}
    max_run = PROTOCOL_CAPS[protocol] or 256
    store = _store_for(protocol)
    loop = ServeLoop(SlotManager(method, protocol, capacity=4, eps0=EPS,
                                 max_run=max_run, store=store),
                     tick_width=48, queue_cap=4096)
    got = {sid: b"" for sid in data}
    cursors = {sid: 0 for sid in data}
    for sid in data:
        loop.admit(sid)
    while any(cursors[s] < data[s].size for s in data) \
            or loop.backlog().sum():
        for sid, y in data.items():
            step = int(rng.integers(0, 70))
            take = loop.offer(sid, y[cursors[sid]:cursors[sid] + step])
            cursors[sid] += take
        rep = loop.tick()
        for sid, _, blob in rep.wire:
            got[sid] += blob
    for sid, y in data.items():
        rep = loop.evict(sid)
        got[sid] += rep.tail
        _assert_offline(got[sid], y, method, protocol, max_run, store,
                        (sid, rep.slot, rep.generation))


def test_backpressure_shed_counts_drops():
    loop = ServeLoop(SlotManager(capacity=2), tick_width=8, queue_cap=10,
                     policy="shed")
    loop.admit("a")
    assert loop.offer("a", np.zeros(25)) == 10
    assert loop.shed_total == 15
    rep = loop.tick()
    assert rep.shed_total == 15 and rep.consumed == 8
    assert rep.backlog == 2


def test_backpressure_block_leaves_retry_to_caller():
    loop = ServeLoop(SlotManager(capacity=2), tick_width=8, queue_cap=10,
                     policy="block")
    loop.admit("a")
    y = np.arange(25, dtype=np.float32)
    took = loop.offer("a", y)
    assert took == 10 and loop.shed_total == 0
    loop.tick()
    # caller retries the refused suffix; nothing was lost
    took += loop.offer("a", y[took:])
    assert took == 18


def test_evict_drains_backlog_by_default():
    rng = np.random.default_rng(11)
    y = _walk(rng, 200)
    loop = ServeLoop(SlotManager("linear", capacity=2, eps0=EPS),
                     tick_width=16, queue_cap=1024)
    loop.admit("a")
    loop.offer("a", y)
    blobs = b""
    rep0 = loop.tick()
    for _, _, b in rep0.wire:
        blobs += b
    rep = loop.evict("a")        # drain=True pushes the other 184 points
    assert rep.points == 200
    # the drain ticks' blobs are *delivered* on the report, not just
    # counted: concatenated per-tick wire + tail == the offline encode
    for sid, _, b in rep.wire:
        assert sid == "a"
        blobs += b
    blobs += rep.tail
    assert blobs == _offline_bytes(y)
    assert rep.nbytes == len(blobs)


def test_evict_drain_delivers_bystander_wire():
    """Drain ticks also step other streams with queued data; their blobs
    must reach the caller via EvictReport.wire, not vanish."""
    rng = np.random.default_rng(13)
    ya, yb = _walk(rng, 180), _walk(rng, 180)
    loop = ServeLoop(SlotManager("linear", capacity=2, eps0=EPS),
                     tick_width=16, queue_cap=1024)
    loop.admit("a")
    loop.admit("b")
    loop.offer("a", ya)
    loop.offer("b", yb)
    got = {"a": b"", "b": b""}
    rep = loop.evict("a")             # drains both queues tick by tick
    for sid, _, b in rep.wire:
        got[sid] += b
    got["a"] += rep.tail
    assert got["a"] == _offline_bytes(ya)
    assert loop.backlog().sum() == 0  # b's queue drained alongside
    rep_b = loop.evict("b")
    assert rep_b.wire == []           # nothing left to drain
    got["b"] += rep_b.tail
    assert got["b"] == _offline_bytes(yb)


# ---------------------------------------------------------------------------
# Global ε budget
# ---------------------------------------------------------------------------

def test_budget_allocator_units():
    """Water-filling sanity: heavy streams squeezed, idle rows untouched."""
    from repro.core.adaptive import allocate_eps_budget
    eps = np.ones(4)
    new_eps, targets = allocate_eps_budget(
        eps, [100.0, 50.0, 10.0, 0.0], [100.0, 100.0, 100.0, 0.0], 120.0,
        deadband=0.05)
    assert targets[3] == 0.0 and new_eps[3] == 1.0      # idle: no share
    assert new_eps[0] > 1.0                             # over budget: loosen
    assert new_eps[2] < 1.0                             # under: tighten
    np.testing.assert_allclose(targets[:3], 40.0)


def test_budget_water_filling_redistributes_pinned_share():
    from repro.core.adaptive import allocate_eps_budget
    eps = np.array([1e6, 1.0])                  # row 0 already at eps_max
    new_eps, targets = allocate_eps_budget(
        eps, [90.0, 10.0], [100.0, 100.0], 40.0, rounds=3)
    # row 0 pins at the bound; its measured 90 bytes swallow the whole
    # 40-byte pool, so row 1's target collapses and its ε is driven up
    # (coarser, fewer bytes) by the full clamped step.
    assert new_eps[0] == 1e6
    assert new_eps[1] == 8.0    # max_step, loosening to shed bytes


def test_budget_pinned_rows_keep_their_bound():
    """A stream pinned at a bound in round 1 must *stay* at that bound
    through later redistribution rounds — rebuilding from eps0 each
    round used to snap it back while its bytes were still charged
    against the pool (ε plane vs pool accounting disagreement)."""
    from repro.core.adaptive import allocate_eps_budget
    eps = np.ones(3)
    # row 0 is 10x over its share -> clamps at eps_max in round 1 and
    # pins; rounds 2+ redistribute the (exhausted) pool over rows 1-2.
    new_eps, _ = allocate_eps_budget(
        eps, [100.0, 1.0, 1.0], [100.0, 100.0, 100.0], 30.0,
        eps_max=4.0, max_step=8.0, rounds=3)
    assert new_eps[0] == 4.0    # clamped value survives round 2


def test_budget_converges_within_band():
    """Fleet egress lands within ±15% of the operator target after
    warm-up (the BENCH_serve acceptance bar, pinned here at test size)."""
    rng = np.random.default_rng(17)
    tick_width, n_streams = 64, 6
    budget = GlobalEpsBudget(1200.0, sample_hz=float(tick_width),
                             smoothing=0.3)
    loop = ServeLoop(SlotManager("linear", capacity=8, eps0=0.05),
                     tick_width=tick_width, queue_cap=4096, budget=budget)
    for i in range(n_streams):
        loop.admit(f"s{i}")
    rates = []
    for k in range(40):
        for i in range(n_streams):
            loop.offer(f"s{i}", _walk(rng, tick_width))
        rep = loop.tick()
        # bytes/s of stream time: each tick spans tick_width points at
        # sample_hz = tick_width -> one second per tick.
        rates.append(rep.nbytes)
    tail = np.asarray(rates[25:], float)
    assert abs(tail.mean() - 1200.0) / 1200.0 < 0.15, tail.mean()


def test_budget_resets_rate_history_on_recycle():
    budget = GlobalEpsBudget(100.0)
    eps = np.ones(2)
    budget.retune(eps, [50.0, 50.0], [10.0, 10.0], np.ones(2, bool))
    assert budget._ema_bytes is not None and budget._ema_bytes[0] == 50.0
    budget.reset_rows([True, False])
    assert budget._ema_bytes[0] == 0.0 and budget._ema_bytes[1] == 50.0


# ---------------------------------------------------------------------------
# Masked engine host bookkeeping
# ---------------------------------------------------------------------------

def test_masked_pos_host_mirrors_device_pos():
    """The host-side position twin (used so per-chunk validation never
    synchronizes on the device value) tracks the traced ``pos`` exactly
    through steps and row flushes."""
    from repro.core import jax_pla
    st = jax_pla.masked_init_state("linear", 4, 0.4)
    rng = np.random.default_rng(3)
    for lengths in ([3, 0, 7, 5], [0, 2, 1, 0], [8, 8, 0, 8]):
        y = rng.normal(size=(4, 8)).astype(np.float32)
        st, _ = jax_pla.masked_step_chunk(st, y,
                                          np.asarray(lengths, np.int64))
        np.testing.assert_array_equal(st.pos_host,
                                      np.asarray(st.pos, np.int64))
    st, _ = jax_pla.masked_flush_rows(st, [True, False, True, False])
    np.testing.assert_array_equal(st.pos_host,
                                  np.asarray(st.pos, np.int64))


# ---------------------------------------------------------------------------
# Padded plane over a real 8-device mesh (subprocess)
# ---------------------------------------------------------------------------

EIGHT_DEVICES = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
assert jax.device_count() == 8, jax.devices()
from repro.serving import SlotManager
sys.path.insert(0, "tests")
from test_serving import _churn_body

mgr = SlotManager(sys.argv[1], sys.argv[2], capacity=12)
assert mgr.capacity == 16 and mgr.rows_per_shard == 2   # padded to 8 devs
_churn_body(2, 30, sys.argv[1], sys.argv[2], capacity=12)
print("SERVE8 OK")
"""


@pytest.mark.parametrize("method,protocol", CHURN_COMBOS)
def test_serving_churn_8_devices_subprocess(method, protocol):
    repo = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"),
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", EIGHT_DEVICES, method,
                          protocol], capture_output=True, text=True,
                         timeout=560, env=env, cwd=repo)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SERVE8 OK" in out.stdout, out.stdout[-2000:]


# ---------------------------------------------------------------------------
# CLI: the fleet-serving front-end parses and runs
# ---------------------------------------------------------------------------

def test_serve_cli_fleet_args():
    from repro.launch.serve import build_parser
    a = build_parser().parse_args(
        ["--fleet-streams", "4", "--churn", "0.2",
         "--budget-bytes-per-s", "500"])
    assert a.fleet_streams == 4 and a.churn == 0.2
    assert a.budget_bytes_per_s == 500.0


def test_serve_cli_fleet_main_runs(capsys):
    """``main`` serves a small churny fleet end to end: a tick line every
    ten ticks and on the last, then the summary line."""
    from repro.launch.serve import main
    main(["--fleet-streams", "4", "--fleet-ticks", "6", "--tick-width", "16",
          "--churn", "0.25", "--budget-bytes-per-s", "400"])
    lines = capsys.readouterr().out.splitlines()
    ticks = [ln for ln in lines if ln.startswith("tick ")]
    assert len(ticks) == 2, lines
    assert all("live=4" in ln and "shed=0" in ln for ln in ticks), ticks
    assert "pool=" in ticks[-1]
    assert lines[-1].startswith("served 384 points"), lines[-1]
    assert "across 10 stream admissions" in lines[-1]
