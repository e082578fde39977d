"""Fleet-scale sharded ingest (ISSUE 5) vs. the single-device engine.

The contract under test: sharding streams over devices must be
*invisible* in the numbers — fleet metrics bit-equal per stream to
:func:`repro.core.protocol_engine.batched_point_metrics`, fleet wire
bytes byte-identical to :func:`~repro.core.protocol_engine.encode_batch`,
chunked :class:`repro.sharding.fleet.FleetStream` output bit-identical to
the offline encode — plus the gather-free per-shard byte accounting.
The 8-device case runs in a subprocess (``XLA_FLAGS`` must precede jax
init); in-process tests cover the same paths on the ambient device count.

The hypothesis random-split test has a deterministic fixed-draw twin so
its body runs without hypothesis (dev dep).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # fixed-draw twin below still runs
    HAVE_HYPOTHESIS = False

import jax

from repro.core import jax_pla
from repro.core.evaluate import BATCHED_SEGMENTERS, METHOD_KNOT_KINDS
from repro.core.protocol_engine import batched_point_metrics, encode_batch
from repro.core.protocols import PROTOCOL_CAPS
from repro.sharding.fleet import (FleetStream, fleet_encode, fleet_mesh,
                                  fleet_point_metrics, fleet_shard)

COMBOS = [("angle", "singlestream"), ("linear", "singlestreamv"),
          ("swing", "implicit"), ("mixed", "implicit")]


def _batch(seed=0, S=8, T=220):
    rng = np.random.default_rng(seed)
    y = np.cumsum(rng.normal(0, 0.6, (S, T)), axis=1)
    y[::3] = rng.normal(0, 25, (len(range(0, S, 3)), T))
    return y.astype(np.float32)


@pytest.mark.parametrize("method,protocol", COMBOS)
def test_fleet_metrics_bit_equal_to_batched(method, protocol):
    y = _batch()
    cap = PROTOCOL_CAPS[protocol] or 256
    kk = METHOD_KNOT_KINDS.get(method, "disjoint")
    fm = fleet_point_metrics(y, 1.0, method, protocol)
    seg = BATCHED_SEGMENTERS[method](y, 1.0, max_run=cap)
    bm = batched_point_metrics(seg, y, protocol, kk)
    np.testing.assert_array_equal(fm.metrics.ratio, bm.ratio)
    np.testing.assert_array_equal(fm.metrics.latency, bm.latency)
    np.testing.assert_array_equal(fm.metrics.error, bm.error)
    # gather-free byte accounting is consistent at every level
    assert fm.shard_nbytes.shape == (fm.n_devices,)
    assert int(fm.shard_nbytes.sum()) == int(fm.nbytes.sum()) \
        == fm.fleet_nbytes
    # the wire bytes ride the same segmentation
    assert fleet_encode(fm, y) == encode_batch(seg, y, protocol, kk)


def test_fleet_stream_chunked_bit_identical():
    y = _batch(seed=4, S=4, T=150)
    for method, protocol in (("angle", "singlestreamv"),
                             ("swing", "implicit"),
                             ("continuous", "implicit")):
        cap = PROTOCOL_CAPS[protocol] or 256
        kk = METHOD_KNOT_KINDS.get(method, "disjoint")
        fs = FleetStream(method, protocol, 4, 0.8, block_s=8, block_t=32)
        got = [b""] * 4
        for lo in (0, 50, 100):
            for s, b in enumerate(fs.push(y[:, lo:lo + 50])):
                got[s] += b
        for s, b in enumerate(fs.finish()):
            got[s] += b
        off = encode_batch(BATCHED_SEGMENTERS[method](y, 0.8, max_run=cap),
                           y, protocol, kk)
        assert got == off, (method, protocol)
        assert fs.total_bytes == sum(len(b) for b in got)


# ---------------------------------------------------------------------------
# Row-block packing: several emitters per shard give the one emitter's bytes
# ---------------------------------------------------------------------------

def _fleet_bytes(fs, y, widths):
    """Every stream's bytes over pushes of ``widths`` and the finish."""
    outs, pos = [], 0
    for w in widths:
        outs.append(fs.push(y[:, pos:pos + w]))
        pos += w
    outs.append(fs.finish())
    if fs.protocol == "twostreams":
        return [(b"".join(a for a, _ in parts), b"".join(b for _, b in parts))
                for parts in zip(*outs)]
    return [b"".join(parts) for parts in zip(*outs)]


@pytest.mark.parametrize("method,protocol,S,min_rows,blocks", [
    ("linear", "singlestream", 16, 8, 2),
    ("angle", "singlestreamv", 24, 8, 3),
    ("continuous", "implicit", 32, 8, 4),
    ("linear", "twostreams", 30, 10, 3),
])
def test_fleet_row_blocks_bytes_equal_one_block(monkeypatch, method,
                                                protocol, S, min_rows,
                                                blocks):
    from repro.sharding import fleet as fleet_mod
    from repro.store import SegmentStore

    eps, widths = 0.8, (37, 64, 5, 90)
    y = _batch(seed=7, S=S, T=sum(widths))
    kw = dict(block_s=8, block_t=32)
    monkeypatch.setattr(os, "sched_getaffinity",
                        lambda pid: set(range(blocks)), raising=False)
    one_store = SegmentStore(protocol, eps=eps)
    one = FleetStream(method, protocol, S, eps, store=one_store, **kw)
    monkeypatch.setattr(fleet_mod, "MIN_BLOCK_ROWS", min_rows)
    store = SegmentStore(protocol, eps=eps)
    fs = FleetStream(method, protocol, S, eps, store=store, **kw)
    assert len(one._blocks) == 1 and one._pool is None
    assert len(fs._blocks) == blocks
    got = _fleet_bytes(fs, y, widths)
    assert got == _fleet_bytes(one, y, widths)
    cap = PROTOCOL_CAPS[protocol] or 256
    kk = METHOD_KNOT_KINDS.get(method, "disjoint")
    off = encode_batch(BATCHED_SEGMENTERS[method](y, eps, max_run=cap), y,
                       protocol, kk)
    assert got == [tuple(b) if protocol == "twostreams" else b
                   for b in off]
    assert fs.total_bytes == one.total_bytes == sum(
        len(b"".join(b)) if protocol == "twostreams" else len(b)
        for b in got)
    assert store.keys() == one_store.keys()
    for k in store.keys():
        mine, ref = store._streams[k], one_store._streams[k]
        assert bytes(mine.payload) == bytes(ref.payload)
        assert bytes(mine.payload2) == bytes(ref.payload2)
        assert mine.e_pos == ref.e_pos
        np.testing.assert_array_equal(store.scan([k])[k],
                                      one_store.scan([k])[k])


@pytest.mark.parametrize("cpus,rows", [
    (1, 40960), (13, 40960), (64, 40960), (8, 1023), (8, 2048),
    (24, 10240), (4, 5000),
])
def test_fleet_row_block_count(monkeypatch, cpus, rows):
    """The block count follows the usable CPUs and the rows per shard,
    and the blocks tile the shard's rows in order."""
    from repro.sharding import fleet as fleet_mod
    from repro.sharding.fleet import MIN_BLOCK_ROWS, _row_blocks

    monkeypatch.setattr(os, "sched_getaffinity",
                        lambda pid: set(range(cpus)), raising=False)
    blocks = _row_blocks(rows)
    k = len(blocks)
    assert k == max(1, min(cpus, rows // MIN_BLOCK_ROWS))
    assert k <= cpus and (k == 1 or k <= rows // MIN_BLOCK_ROWS)
    assert blocks[0].start == 0 and blocks[-1].stop == rows
    assert all(a.stop == b.start for a, b in zip(blocks, blocks[1:]))
    assert k == 1 or min(b.stop - b.start for b in blocks) \
        >= MIN_BLOCK_ROWS
    # Without an affinity mask the CPU count stands in for it.
    monkeypatch.delattr(os, "sched_getaffinity", raising=False)
    monkeypatch.setattr(os, "cpu_count", lambda: cpus)
    assert len(fleet_mod._row_blocks(rows)) == k


def test_fleet_small_shard_packs_in_one_block(monkeypatch):
    monkeypatch.setattr(os, "sched_getaffinity",
                        lambda pid: set(range(64)), raising=False)
    fs = FleetStream("linear", "singlestream", 16, 1.0)
    assert [(b.start, b.stop) for b in fs._blocks] == [(0, 16)]
    assert fs._pool is None
    assert all(len(ems) == 1 for ems in fs._ems)


def test_fleet_shape_and_mesh_errors():
    y = _batch(S=8)
    with pytest.raises(ValueError, match="unknown protocol"):
        fleet_point_metrics(y, 1.0, "angle", "nope")
    with pytest.raises(ValueError, match="no batched segmenter"):
        fleet_point_metrics(y, 1.0, "nope", "implicit")
    d = jax.device_count()
    if d > 1:  # divisibility guard (needs an actual multi-device mesh)
        with pytest.raises(ValueError, match="shard evenly"):
            fleet_point_metrics(_batch(S=d + 1, T=64), 1.0,
                                "angle", "singlestream")
    bad = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))
    with pytest.raises(ValueError, match="streams"):
        fleet_shard(y, bad)
    with pytest.raises(ValueError, match="counter cap"):
        fleet_point_metrics(y, 1.0, "angle", "singlestreamv", max_run=200)
    fs = FleetStream("angle", "singlestream", 4, 1.0)
    with pytest.raises(ValueError, match="chunk must be"):
        fs.push(np.zeros((3, 10), np.float32))


def test_fleet_sharded_8_devices_subprocess():
    """Bit-equality of the sharded pipeline under a real 8-device mesh
    (host-platform devices; XLA_FLAGS must precede jax init, hence the
    subprocess — same pattern as test_runtime's multipod test)."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax
assert jax.device_count() == 8, jax.devices()
from repro.core.evaluate import BATCHED_SEGMENTERS, METHOD_KNOT_KINDS
from repro.core.protocol_engine import batched_point_metrics, encode_batch
from repro.sharding.fleet import FleetStream, fleet_point_metrics

rng = np.random.default_rng(3)
S, T = 16, 160
y = np.cumsum(rng.normal(0, 0.6, (S, T)), axis=1)
y[::3] = rng.normal(0, 25, (len(range(0, S, 3)), T))
y = y.astype(np.float32)

for method, protocol in [("angle", "singlestream"),
                         ("continuous", "implicit")]:
    kk = METHOD_KNOT_KINDS.get(method, "disjoint")
    fm = fleet_point_metrics(y, 1.0, method, protocol)
    assert fm.n_devices == 8
    assert fm.shard_nbytes.shape == (8,)
    seg = BATCHED_SEGMENTERS[method](y, 1.0, max_run=256)
    bm = batched_point_metrics(seg, y, protocol, kk)
    for name in ("ratio", "latency", "error"):
        a = getattr(fm.metrics, name)
        b = getattr(bm, name)
        assert (a == b).all(), (method, protocol, name)
    assert int(fm.shard_nbytes.sum()) == fm.fleet_nbytes

fs = FleetStream("angle", "singlestream", S, 1.0, block_s=8, block_t=32)
got = [b""] * S
for lo in range(0, T, 64):
    for s, b in enumerate(fs.push(y[:, lo:lo + 64])):
        got[s] += b
for s, b in enumerate(fs.finish()):
    got[s] += b
off = encode_batch(BATCHED_SEGMENTERS["angle"](y, 1.0, max_run=256), y,
                   "singlestream")
assert got == off
assert (fs.shard_bytes > 0).all() and fs.n_devices == 8
print("FLEET8 OK")
"""
    repo = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"),
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=560,
                         env=env, cwd=repo)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "FLEET8 OK" in out.stdout, out.stdout[-2000:]


# ---------------------------------------------------------------------------
# Random chunk splits through the fleet stream == offline encode
# ---------------------------------------------------------------------------

def _check_fleet_splits(seed: int, splits):
    T = sum(splits)
    y = _batch(seed=seed, S=4, T=T)
    fs = FleetStream("angle", "singlestreamv", 4, 0.8,
                     block_s=8, block_t=32)
    got = [b""] * 4
    pos = 0
    for w in splits:
        for s, b in enumerate(fs.push(y[:, pos:pos + w])):
            got[s] += b
        pos += w
    for s, b in enumerate(fs.finish()):
        got[s] += b
    off = encode_batch(jax_pla.angle_segment(y, 0.8, max_run=127), y,
                       "singlestreamv")
    assert got == off, splits


FIXED_SPLIT_DRAWS = [(0, (1, 30, 31, 40, 47, 1)), (1, (150,)),
                     (2, (64, 64, 22)), (3, (149, 1))]


@pytest.mark.parametrize("seed,splits", FIXED_SPLIT_DRAWS)
def test_fixed_fleet_stream_random_splits(seed, splits):
    """Deterministic twin of the hypothesis test below (same body)."""
    _check_fleet_splits(seed, splits)


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 10),
           splits=st.lists(st.integers(1, 60), min_size=1, max_size=6)
           .filter(lambda ws: 8 <= sum(ws) <= 200))
    def test_fleet_stream_random_splits(seed, splits):
        _check_fleet_splits(seed, tuple(splits))
