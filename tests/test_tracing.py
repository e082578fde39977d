"""The system's host spans (``repro.*``) and the queue-wait counter, read
back from a profiler trace on the CPU; and the device names the
benchmark's trace reduction finds the kernels by.

The spans are what ``bench/core/program_spans.py`` reduces; their names,
nesting and arguments are its input, so they are pinned here.  No span
may open inside a loop over slots: the count of spans a tick opens must
not grow with the slot plane.
"""

import json
import os
import subprocess
import sys
import time
from collections import Counter
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.core import program_spans as ps  # noqa: E402
from bench.core.kernels import is_segmenter  # noqa: E402
from bench.core.trace import Op  # noqa: E402
from repro.serving import ServeLoop, SlotManager  # noqa: E402
from repro.sharding.fleet import FleetStream  # noqa: E402

FLEET_PHASES = ("repro.fleet.put", "repro.fleet.segment",
                "repro.fleet.fetch", "repro.fleet.emit")


def _walk(rows, cols, seed=0):
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.normal(0, 0.6, (rows, cols)), 1).astype(np.float32)


def _traced(tmp_path, body):
    """Run ``body()`` under the profiler; its result and the program
    spans of the trace."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = body()
    finally:
        jax.profiler.stop_trace()
    return out, ps.load_program_spans(str(tmp_path))


def _inside(spans, outer):
    """Spans by name, counted where they lie inside an ``outer`` span."""
    return Counter(n for n, a, b, _ in spans if n != outer and any(
        oa <= a and b <= ob for m, oa, ob, _ in spans if m == outer))


def test_fleet_push_spans(tmp_path, monkeypatch):
    from repro.sharding import fleet as fleet_mod
    S, W = 16, 64
    y = _walk(S, 3 * W)
    # Row blocks of 4 streams, so the packer runs on its thread pool.
    monkeypatch.setattr(fleet_mod, "MIN_BLOCK_ROWS", 4)
    fs = FleetStream("linear", "singlestream", S, 0.8, block_s=8,
                     block_t=32)
    fs.push(y[:, :W])           # compiles outside the trace

    def body():
        out = [fs.push(y[:, W:2 * W]), fs.push(y[:, 2 * W:])]
        return out + [fs.finish()]

    _, spans = _traced(tmp_path, body)
    names = Counter(s[0] for s in spans)
    assert names["repro.fleet.push"] == 2
    assert names["repro.fleet.finish"] == 1
    assert "repro.fleet.store" not in names
    pushes = [s for s in spans if s[0] == "repro.fleet.push"]
    assert all(s[3] == {"streams": S, "width": W} for s in pushes)
    # One of each phase per push and shard, each inside its push, in
    # order: every put and launch before the fetch and the emitter.
    inside = _inside(spans, "repro.fleet.push")
    assert {n: inside[n] for n in FLEET_PHASES} == \
        {n: 2 for n in FLEET_PHASES}
    first = [s[0] for s in spans
             if pushes[0][1] <= s[1] and s[2] <= pushes[0][2]
             and s[0] in FLEET_PHASES]
    assert first == list(FLEET_PHASES)
    # The fetch moves the event planes: a flag and two float32 per
    # stream and released column.
    fetched = [s[3]["bytes"] for s in _within(spans, pushes)
               if s[0] == "repro.fleet.fetch"]
    assert sum(fetched) == S * 2 * W * (1 + 4 + 4)
    # Every per-shard span names its shard; the emitter span names its
    # row blocks too, and each block's worker opens one span inside it,
    # on whichever thread ran it.  ``finish`` fetches and packs once
    # more.
    blocks = len(fs._blocks)
    for name in FLEET_PHASES:
        assert all(s[3].get("shard") == 0 for s in spans if s[0] == name)
    emits = [s for s in spans if s[0] == "repro.fleet.emit"]
    assert len(emits) == 3
    assert all(s[3] == {"shard": 0, "blocks": blocks} for s in emits)
    assert _inside(spans, "repro.fleet.finish")["repro.fleet.fetch"] == 1
    for _, a, b, _ in pushes:
        assert sum(1 for n, c, d, _ in spans if n == "repro.fleet.emit_block"
                   and a <= c and d <= b) == blocks * fs.n_devices
    assert sum(s[3]["rows"] for s in spans
               if s[0] == "repro.fleet.emit_block"
               and pushes[0][1] <= s[1] and s[2] <= pushes[0][2]) == S
    assert inside["repro.fleet.emit_block"] == 2 * blocks * fs.n_devices


def _within(spans, outers):
    """The spans that lie inside one of ``outers``."""
    return [s for s in spans if any(a <= s[1] and s[2] <= b
                                    for _, a, b, _ in outers)]


FOUR_DEVICES = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
import numpy as np
from bench.core import program_spans as ps
from repro.sharding.fleet import FleetStream
assert jax.device_count() == 4, jax.devices()
y = np.cumsum(np.random.default_rng(5).normal(0, 0.6, (32, 128)),
              1).astype(np.float32)
fs = FleetStream("angle", "twostreams", 32, 1.0, block_s=8, block_t=32)
fs.push(y[:, :64])              # compiles outside the trace
jax.profiler.start_trace(sys.argv[1])
fs.push(y[:, 64:])
jax.profiler.stop_trace()
print(json.dumps([[n, a, b, {k: v for k, v in args.items()
                             if isinstance(v, (int, float, str))}]
                  for n, a, b, args in ps.load_program_spans(sys.argv[1])]))
"""


def test_fleet_push_spans_on_four_devices(tmp_path):
    """A push over four devices fetches and packs each shard once, and
    each per-shard span names its shard (``fleet_pack_overlap`` groups
    the emit spans by it)."""
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=f"{root}:{root / 'src'}")
    out = subprocess.run([sys.executable, "-c", FOUR_DEVICES,
                          str(tmp_path)], capture_output=True, text=True,
                         timeout=300, env=env, cwd=root)
    assert out.returncode == 0, out.stderr[-3000:]
    spans = [tuple(s) for s in json.loads(out.stdout.splitlines()[-1])]
    assert [s[0] for s in spans].count("repro.fleet.push") == 1
    for name in FLEET_PHASES:
        shards = sorted(s[3]["shard"] for s in spans if s[0] == name)
        assert shards == [0, 1, 2, 3], name
    assert {s[3]["blocks"] for s in spans
            if s[0] == "repro.fleet.emit"} == {1}


def test_fleet_store_span(tmp_path):
    from repro.store import SegmentStore
    S = 8
    y = _walk(S, 64, seed=1)
    store = SegmentStore("singlestream")
    fs = FleetStream("linear", "singlestream", S, 0.8, block_s=8,
                     block_t=32, store=store)
    fs.push(y[:, :32])
    _, spans = _traced(tmp_path, lambda: fs.push(y[:, 32:]))
    assert _inside(spans, "repro.fleet.push")["repro.fleet.store"] == 1


def _serve(capacity, tick_width=16):
    mgr = SlotManager("linear", "singlestream", capacity=capacity,
                      eps0=0.5)
    return ServeLoop(mgr, tick_width=tick_width, queue_cap=256)


def _events_between(y, lo, hi):
    """Breaks the engine finalizes in stream ``y`` while its count of
    points goes from ``lo`` to ``hi`` (a break at p comes with point
    p + 1), read off the offline segmentation ``_serve`` matches."""
    from repro.core.evaluate import BATCHED_SEGMENTERS
    brk = np.asarray(BATCHED_SEGMENTERS["linear"](
        y[None], 0.5, max_run=256).breaks)[0]
    return int(brk[max(lo - 1, 0):hi - 1].sum())


def test_tick_spans_counter_and_evict_drain(tmp_path):
    loop = _serve(8)
    for s in range(4):
        loop.admit(str(s))
    data = _walk(4, 80, seed=2)
    for s in range(4):
        loop.offer(str(s), data[s, :8])
    loop.tick()                 # compiles outside the trace

    def body():
        for s in range(3):      # stream 3 gets nothing this turn
            loop.offer(str(s), data[s, 8:8 + 10 + s])
        time.sleep(0.05)
        rep = loop.tick()
        loop.offer("1", data[1, 40:80])     # three ticks' worth queued
        return rep, loop.evict("1")

    (rep, ev), spans = _traced(tmp_path, body)
    assert rep.consumed == 10 + 11 + 12
    assert rep.wait_mean_s >= 0.05 and rep.wait_max_s >= rep.wait_mean_s
    assert ev.wire and ev.points == 8 + 11 + 40

    ticks = [s for s in spans if s[0] == "repro.serve.tick"]
    assert [s[3]["tick"] for s in ticks] == [2, 3, 4, 5]
    steps = [s[3] for s in spans if s[0] == "repro.slots.step"]
    # ``slots_fed`` is the count of non-zero lengths.
    assert [(st["slots_fed"], st["points"]) for st in steps] == \
        [(3, 33), (1, 16), (1, 16), (1, 8)]
    assert steps[0]["wait_mean_ms"] == pytest.approx(1e3 * rep.wait_mean_s)
    assert steps[0]["wait_max_ms"] == pytest.approx(1e3 * rep.wait_max_s)

    in_tick = _inside(spans, "repro.serve.tick")
    for name in ("repro.serve.drain", "repro.slots.step",
                 "repro.slots.dispatch", "repro.slots.fetch",
                 "repro.slots.emit", "repro.serve.report"):
        assert in_tick[name] == 4, name
    assert in_tick["repro.serve.budget"] == 0
    assert _inside(spans, "repro.slots.step")["repro.slots.emit"] == 4
    # Each shard's emitter call names the rows it packs (those with
    # events) and the events: the traced tick's three streams, then the
    # evict's three drain ticks of stream 1.
    fed = [data[0, :18], np.r_[data[1, :19], data[1, 40:80]], data[2, :20]]
    ev = [_events_between(fed[s], 8, 18 + s) for s in range(3)]
    expect = [{"rows": sum(e > 0 for e in ev), "events": sum(ev)}]
    for lo, hi in ((19, 35), (35, 51), (51, 59)):
        e = _events_between(fed[1], lo, hi)
        expect.append({"rows": int(e > 0), "events": e})
    assert [s[3] for s in spans if s[0] == "repro.slots.emit"] == expect
    assert expect[0]["rows"] == 3 and expect[0]["events"] > 3

    evicts = [s for s in spans if s[0] == "repro.serve.evict"]
    assert len(evicts) == 1 and evicts[0][3] == {"queued": 40}
    # The evict drains the queue with three ticks, then closes the slot,
    # which uploads its shard's ε plane.
    assert ps.nested_count(spans, "repro.serve.tick",
                           "repro.serve.evict") == 3
    in_evict = _inside(spans, "repro.serve.evict")
    assert in_evict["repro.slots.evict"] == 1
    assert in_evict["repro.slots.set_eps"] == 1
    assert _inside(spans, "repro.slots.evict")["repro.slots.set_eps"] == 1


def test_admit_and_budget_spans(tmp_path):
    from repro.serving import GlobalEpsBudget
    mgr = SlotManager("linear", "singlestream", capacity=4, eps0=0.5)
    loop = ServeLoop(mgr, tick_width=16, queue_cap=64,
                     budget=GlobalEpsBudget(200.0, sample_hz=16.0))
    loop.admit("a")
    loop.offer("a", _walk(1, 16)[0])
    loop.tick()

    def body():
        loop.admit("b")
        loop.offer("b", _walk(1, 16, seed=3)[0])
        return loop.tick()

    rep, spans = _traced(tmp_path, body)
    assert rep.budget_pool is not None
    assert _inside(spans, "repro.slots.admit")["repro.slots.set_eps"] == 1
    in_budget = _inside(spans, "repro.serve.budget")
    assert in_budget["repro.slots.set_eps"] == 1   # one shard
    assert _inside(spans, "repro.serve.tick")["repro.serve.budget"] == 1


def test_wait_is_zero_without_points():
    loop = _serve(4)
    loop.admit("a")
    rep = loop.tick()
    assert rep.consumed == 0
    assert rep.wait_mean_s == 0.0 and rep.wait_max_s == 0.0


def _spans_per_tick(tmp_path, capacity):
    loop = _serve(capacity)
    data = _walk(capacity, 48, seed=4)
    for s in range(capacity):
        loop.admit(str(s))
        loop.offer(str(s), data[s, :16])
    loop.tick()

    def body():
        for k in (1, 2):
            for s in range(capacity):
                loop.offer(str(s), data[s, 16 * k:16 * (k + 1)])
            loop.tick()

    _, spans = _traced(tmp_path, body)
    per_tick = _inside(spans, "repro.serve.tick")
    per_tick["repro.serve.tick"] = sum(
        1 for s in spans if s[0] == "repro.serve.tick")
    return per_tick


def test_spans_per_tick_do_not_grow_with_slots(tmp_path):
    small = _spans_per_tick(tmp_path / "8", 8)
    large = _spans_per_tick(tmp_path / "64", 64)
    assert small["repro.serve.tick"] == 2
    assert small == large


# -- the device names the trace reduction reads ------------------------------

def _module_name(lowered) -> str:
    head = lowered.as_text().split("\n", 1)[0]
    assert head.startswith("module @"), head
    return head.split()[1][1:]


@pytest.mark.parametrize("method", ["angle", "swing", "disjoint", "linear",
                                    "continuous", "mixed"])
def test_fleet_kernel_launchers_keep_their_names(method):
    """``segmenter_kernel_ms_per_push`` and ``segmenter_roofline`` find
    the fleet's kernel by its jitted launcher, ``<method>_pallas``."""
    from repro.kernels.ops import StreamingSegmenter
    seg = StreamingSegmenter(method, 128, 1.0, block_s=128, block_t=32)
    kw = dict(eps=seg.eps, max_run=seg.max_run, block_s=seg.block_s,
              block_t=seg.block_t, carry=seg._carry, **seg._kw)
    kw["t_stop" if seg._deferred else "t_real"] = seg.block_t
    y_t = jax.ShapeDtypeStruct((seg.block_t, 128), jnp.float32)
    module = _module_name(seg._kernel_fn.lower(y_t, **kw))
    assert is_segmenter(method)(Op("fusion", 0.0, 1.0, module)), module
    others = [m for m in ("angle", "swing", "disjoint", "linear",
                          "continuous", "mixed") if m != method]
    assert not any(is_segmenter(m)(Op("fusion", 0.0, 1.0, module))
                   for m in others), module


def test_masked_step_keeps_its_name():
    """``masked_step_device_ms`` finds the serving step by its jit,
    ``_masked_scan``."""
    from repro.core import jax_pla
    st = jax_pla.masked_init_state("linear", 8, np.ones(8, np.float32))
    lowered = jax_pla._masked_scan.lower(
        "linear", st.max_run, st.window, st.carry, st.started, st.pos,
        st.eps, jnp.zeros((8, 16), jnp.float32), jnp.full(8, 16, jnp.int32))
    assert "_masked_scan" in _module_name(lowered)
