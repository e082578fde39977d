"""Fleet-scale sharded ingest: the (S, T) pipeline over a device mesh.

The paper's two scenarios — sensor-fleet transmission reduction and
datacenter telemetry storage — are many-stream workloads: thousands of
independent channels, each cheap, all at once.  This module runs the full
segment → descriptor → metrics → encode pipeline of the batched engine
(:mod:`repro.core.jax_pla` + :mod:`repro.core.protocol_engine`) over a
**stream-sharded** mesh: streams are partitioned across devices along the
``"streams"`` axis, every device runs the identical array program on its
own ``(S/D, T)`` shard, and the only cross-device traffic is a scalar
``psum``/``pmean`` reduction of the fleet-level aggregates — no gathers,
no resharding, wire totals stay per-shard.

Layers:

- :func:`fleet_mesh` / :func:`fleet_shard` — build the 1-D streams mesh
  (``compat.sharding.make_mesh``) and place an ``(S, T)`` batch on it;
- :func:`fleet_point_metrics` — one ``shard_map`` launch computing the
  segmentation, §5 protocol descriptors, per-stream wire totals, and the
  three §4.2 metric surfaces for every shard in parallel, plus the
  gather-free per-shard byte totals and their ``psum`` fleet reduction.
  The float64 host finish reuses
  :func:`repro.core.protocol_engine.descriptors_point_metrics`, so each
  stream row is **bit-equal** to the single-device
  :func:`~repro.core.protocol_engine.batched_point_metrics`
  (descriptor math is per-stream independent — sharding cannot change
  it);
- :func:`fleet_encode` — the wire bytes of every stream via the
  vectorized host packer (:func:`~repro.core.protocol_engine.encode_batch`);
- :class:`FleetStream` — the chunked face: per-device
  :class:`~repro.kernels.ops.StreamingSegmenter` carries and
  :class:`~repro.core.protocol_engine.ProtocolEmitter` codec state (one
  emitter per row block of a shard, the blocks packed concurrently), so
  a live fleet can push ``(S, n)`` column batches and receive wire-ready
  bytes per stream, bit-identical to the offline encode of the whole
  stream (PR-2 carry contract per shard).

Every shard_map here is manual over ``"streams"`` only, leaving any other
mesh axes auto.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.profiler import TraceAnnotation as span
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import sharding as cs
from repro.core.evaluate import BATCHED_SEGMENTERS, METHOD_KNOT_KINDS
from repro.core.metrics import BatchedPointMetrics
from repro.core.protocol_engine import (ENGINE_PROTOCOLS,
                                        ProtocolPointDescriptors,
                                        descriptors_point_metrics,
                                        encode_batch,
                                        metrics_from_descriptors,
                                        protocol_descriptors)
from repro.core.protocol_engine import ProtocolEmitter
from repro.core.protocols import PROTOCOL_CAPS
from repro.core.jax_pla import SegmentOutput

__all__ = ["FLEET_AXIS", "FleetPointMetrics", "FleetStream",
           "fleet_mesh", "fleet_shard", "fleet_point_metrics",
           "fleet_encode", "pad_to_mesh"]

FLEET_AXIS = "streams"

# Fewest rows a shard's packer gives one worker thread: a block of 1,024
# streams keeps the emitter's per-call Python a few percent of its numpy.
MIN_BLOCK_ROWS = 1024


def fleet_mesh(n_devices: Optional[int] = None, *,
               devices=None) -> jax.sharding.Mesh:
    """A 1-D ``("streams",)`` mesh over ``n_devices`` (default: all)."""
    devs = list(devices) if devices is not None else jax.devices()
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(f"asked for {n_devices} devices; "
                             f"only {len(devs)} available")
        devs = devs[:n_devices]
    return cs.make_mesh((len(devs),), (FLEET_AXIS,), devices=devs)


def _shard_count(mesh: jax.sharding.Mesh) -> int:
    """Number of stream shards of a fleet mesh (its ``"streams"`` size)."""
    if FLEET_AXIS not in mesh.axis_names:
        raise ValueError(f"fleet mesh needs a {FLEET_AXIS!r} axis; "
                         f"got {tuple(mesh.axis_names)}")
    return int(mesh.shape[FLEET_AXIS])


def _check_shards(S: int, d: int) -> None:
    if S % d:
        pad = -S % d
        raise ValueError(
            f"{S} streams do not shard evenly over {d} devices — pad the "
            f"batch with {pad} quiet row(s) (see pad_to_mesh(), quiet "
            f"rows are cheap), resize the mesh, or let the serving layer "
            f"manage padding for you: repro.serving.SlotManager rounds "
            f"its slot plane up to a multiple of the device count and "
            f"masks the padding rows with eps=INACTIVE_EPS")


def pad_to_mesh(y, mesh: jax.sharding.Mesh):
    """Pad ``(S, T)`` rows up to a multiple of the mesh's device count.

    Returns ``(y_padded, S)`` where the ``y_padded.shape[0] - S`` extra
    rows are zeros — quiet streams that segment into one run each and
    cost a constant handful of wire bytes.  Callers slice per-stream
    outputs back to ``[:S]``; fleet byte totals include the (tiny,
    deterministic) padding contribution, so compare like against like.
    """
    d = _shard_count(mesh)
    y = jnp.asarray(y, jnp.float32)
    S = y.shape[0]
    pad = -S % d
    if pad:
        y = jnp.concatenate(
            [y, jnp.zeros((pad, y.shape[1]), y.dtype)], axis=0)
    return y, S


def fleet_shard(y, mesh: jax.sharding.Mesh) -> jax.Array:
    """Place an ``(S, T)`` batch on the mesh, streams over devices."""
    d = _shard_count(mesh)
    y = jnp.asarray(y, jnp.float32)
    _check_shards(y.shape[0], d)
    return jax.device_put(y, NamedSharding(mesh, P(FLEET_AXIS, None)))


def _max_run(protocol: str, max_run: Optional[int]) -> int:
    """Points per segment: the protocol's counter cap by default, never
    more."""
    cap = PROTOCOL_CAPS[protocol]
    max_run = max_run or cap or 256
    if cap is not None and max_run > cap:
        raise ValueError(f"max_run={max_run} exceeds the {protocol!r} "
                         f"counter cap ({cap})")
    return max_run


@dataclasses.dataclass
class FleetPointMetrics:
    """One protocol evaluated over a device-sharded stream fleet.

    ``metrics`` rows are bit-equal to the single-device
    :func:`~repro.core.protocol_engine.batched_point_metrics` on the same
    batch; ``shard_nbytes[d]`` is device ``d``'s wire total (computed on
    that device, never gathered), ``fleet_nbytes`` their ``psum``.
    ``fleet_means`` are the monitoring-grade float32 on-device ``pmean``
    aggregates of the three §4.2 metrics (exact float64 per-stream values
    live in ``metrics``).
    """

    method: str
    protocol: str
    knot_kind: str
    n_devices: int
    seg: SegmentOutput            # (S, T), device-sharded
    metrics: BatchedPointMetrics  # float64 host finish, (S, T)
    nbytes: np.ndarray            # (S,) per-stream wire totals
    n_records: np.ndarray         # (S,)
    shard_nbytes: np.ndarray      # (D,) per-shard totals, gather-free
    fleet_nbytes: int             # psum over shards
    fleet_means: Dict[str, float]  # pmean'd ratio / latency / error


@functools.lru_cache(maxsize=None)
def _fleet_pipeline(mesh: jax.sharding.Mesh, method: str, protocol: str,
                    knot_kind: str, max_run: int, burst_cap: int):
    """Build + cache the jitted shard_map'd device pipeline for one
    (mesh, method, protocol) configuration."""
    segment = BATCHED_SEGMENTERS[method]

    def body(y_blk, eps_blk):
        seg = segment(y_blk, eps_blk, max_run=max_run)
        d = protocol_descriptors(seg, protocol, knot_kind, burst_cap)
        nbytes = jnp.where(d.head, d.rec_bytes, 0).sum(axis=1)
        n_records = d.head.sum(axis=1).astype(jnp.int32)
        shard_nbytes = nbytes.sum()[None]
        fleet_nbytes = jax.lax.psum(shard_nbytes[0], FLEET_AXIS)
        ratio, latency, error = metrics_from_descriptors(d, y_blk)
        means = jnp.stack([ratio.mean(), latency.mean(), error.mean()])
        fleet_means = jax.lax.pmean(means, FLEET_AXIS)
        return (seg, d, nbytes, n_records, shard_nbytes, fleet_nbytes,
                fleet_means)

    row = P(FLEET_AXIS)                   # leading axis over streams
    sharded = cs.shard_map(
        body, mesh=mesh,
        in_specs=(P(FLEET_AXIS, None), P(FLEET_AXIS)),
        out_specs=(
            SegmentOutput(*([P(FLEET_AXIS, None)] * 3)),
            ProtocolPointDescriptors(*([P(FLEET_AXIS, None)] * 10)),
            row, row,                     # per-stream bytes / records
            P(FLEET_AXIS),                # (1,) per shard -> (D,)
            P(), P(),                     # psum/pmean: replicated
        ),
        axis_names=(FLEET_AXIS,))
    return jax.jit(sharded)


def fleet_point_metrics(y, eps, method: str, protocol: str, *,
                        mesh: Optional[jax.sharding.Mesh] = None,
                        knot_kind: Optional[str] = None,
                        max_run: Optional[int] = None,
                        burst_cap: int = 127) -> FleetPointMetrics:
    """Segment + §5 descriptors + §4.2 metrics for a sharded fleet.

    One ``shard_map`` launch runs the whole device pipeline on every
    shard in parallel; the float64 host finish makes each stream row
    bit-equal to single-device
    :func:`~repro.core.protocol_engine.batched_point_metrics`.  ``eps``
    may be a scalar or per-stream ``(S,)`` (it shards with the streams).
    """
    if protocol not in ENGINE_PROTOCOLS:
        raise ValueError(f"unknown protocol {protocol!r}; "
                         f"have {sorted(ENGINE_PROTOCOLS)}")
    if method not in BATCHED_SEGMENTERS:
        raise ValueError(f"no batched segmenter for {method!r}; "
                         f"have {sorted(BATCHED_SEGMENTERS)}")
    mesh = mesh if mesh is not None else fleet_mesh()
    d_count = _shard_count(mesh)
    y = np.asarray(y, np.float32)
    S, T = y.shape
    _check_shards(S, d_count)
    knot_kind = knot_kind or METHOD_KNOT_KINDS.get(method, "disjoint")
    max_run = _max_run(protocol, max_run)
    eps_arr = jnp.broadcast_to(jnp.asarray(eps, jnp.float32), (S,))
    fn = _fleet_pipeline(mesh, method, protocol, knot_kind, int(max_run),
                         int(burst_cap))
    with cs.use_mesh(mesh):
        (seg, d, nbytes, n_records, shard_nbytes, fleet_nbytes,
         fleet_means) = fn(fleet_shard(y, mesh), eps_arr)
    pm = descriptors_point_metrics(d, y)
    means = np.asarray(fleet_means, np.float64)
    return FleetPointMetrics(
        method=method, protocol=protocol, knot_kind=knot_kind,
        n_devices=d_count, seg=seg, metrics=pm,
        nbytes=np.asarray(nbytes), n_records=np.asarray(n_records),
        shard_nbytes=np.asarray(shard_nbytes),
        fleet_nbytes=int(fleet_nbytes),
        fleet_means={"ratio": float(means[0]), "latency": float(means[1]),
                     "error": float(means[2])})


def fleet_encode(fm: FleetPointMetrics, y, *, t0: float = 0.0,
                 dt: float = 1.0, burst_cap: int = 127) -> List:
    """Wire-encode every stream of a fleet result, bit-identical to the
    legacy codecs, with the vectorized host packer
    (:func:`repro.core.protocol_engine.encode_batch`)."""
    return encode_batch(fm.seg, y, fm.protocol, fm.knot_kind, t0=t0, dt=dt,
                        burst_cap=burst_cap)


# ---------------------------------------------------------------------------
# Chunked fleet ingest: per-device carries + per-row-block codec state
# ---------------------------------------------------------------------------

def _usable_cpus() -> int:
    """CPUs this process may run on (its affinity mask where the OS has
    one)."""
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:
        return os.cpu_count() or 1


def _row_blocks(rows: int) -> List[slice]:
    """Contiguous row blocks of a shard, one per packer thread: as many
    as there are usable CPUs, but none under ``MIN_BLOCK_ROWS`` rows
    (a single block below that)."""
    k = max(1, min(_usable_cpus(), rows // MIN_BLOCK_ROWS))
    edges = [rows * i // k for i in range(k + 1)]
    return [slice(a, b) for a, b in zip(edges, edges[1:])]


class FleetStream:
    """Live fleet ingest: push ``(S, n)`` column batches, get wire bytes.

    The stream fleet is partitioned row-wise into one shard per device;
    each shard owns a :class:`~repro.kernels.ops.StreamingSegmenter`
    (kernel carry state pinned to that device via ``jax.device_put`` of
    its chunks) and a host
    :class:`~repro.core.protocol_engine.ProtocolEmitter` per row block,
    whose float64 codec arithmetic is the legacy codecs' own on every
    platform.  A stream's codec state depends on its own row alone, so
    the blocks of a shard pack concurrently on a thread pool (the numpy
    work releases the GIL) and give exactly the bytes one emitter over
    the shard would; the block count follows the usable CPUs and the
    rows per shard (:func:`_row_blocks`).
    ``push`` fans the chunk out shard-by-shard and returns the newly
    wire-ready bytes per stream — for the deferred methods
    (continuous/mixed) a shard's emission lags its released columns,
    exactly like the single-device engine.  Concatenating all
    ``push`` outputs with the ``finish`` output is bit-identical per
    stream to the offline
    :func:`~repro.core.protocol_engine.encode_batch` of the one-shot
    segmentation.

    ``shard_bytes`` / ``total_bytes`` track wire totals per device shard
    and for the whole fleet without any cross-device traffic.
    """

    def __init__(self, method: str, protocol: str, n_streams: int,
                 eps: float, *, devices=None, knot_kind: Optional[str] = None,
                 max_run: Optional[int] = None,
                 window: Optional[int] = None, t0: float = 0.0,
                 dt: float = 1.0, burst_cap: int = 127, store=None,
                 **segmenter_kw):
        from repro.kernels.ops import StreamingSegmenter  # lazy: layering
        if protocol not in ENGINE_PROTOCOLS:
            raise ValueError(f"unknown protocol {protocol!r}; "
                             f"have {sorted(ENGINE_PROTOCOLS)}")
        if store is not None and store.protocol != protocol:
            raise ValueError(f"store speaks {store.protocol!r}, "
                             f"fleet emits {protocol!r}")
        self.devices = list(devices) if devices is not None \
            else jax.devices()
        d = len(self.devices)
        _check_shards(n_streams, d)
        self.method = method
        self.protocol = protocol
        self.n_streams = n_streams
        self.knot_kind = knot_kind or METHOD_KNOT_KINDS.get(method,
                                                            "disjoint")
        max_run = _max_run(protocol, max_run)
        self._rows = n_streams // d
        self._segs = [StreamingSegmenter(method, self._rows, eps,
                                         max_run=max_run, window=window,
                                         **segmenter_kw)
                      for _ in range(d)]
        self._blocks = _row_blocks(self._rows)
        self._ems = [[ProtocolEmitter(protocol, b.stop - b.start,
                                      knot_kind=self.knot_kind, t0=t0,
                                      dt=dt, burst_cap=burst_cap)
                      for b in self._blocks]
                     for _ in range(d)]
        self._pool = ThreadPoolExecutor(len(self._blocks)) \
            if len(self._blocks) > 1 else None
        self.shard_bytes = np.zeros(d, np.int64)
        self.pushed = 0
        self._finished = False
        # Optional hand-off: every blob this fleet emits is appended to
        # the SegmentStore under the stream's global row number, so
        # serving and storage share one wire format (and the store's
        # differential guarantee makes the archive equal to an offline
        # encode_batch of the same data).
        self.store = store
        if store is not None:
            for k in range(n_streams):
                store.add_stream(k, eps=float(np.max(eps)))

    @property
    def n_devices(self) -> int:
        return len(self.devices)

    @property
    def total_bytes(self) -> int:
        return int(self.shard_bytes.sum())

    def _account(self, d: int, blobs) -> None:
        if self.protocol == "twostreams":
            self.shard_bytes[d] += sum(len(a) + len(b) for a, b in blobs)
        else:
            self.shard_bytes[d] += sum(len(b) for b in blobs)

    @staticmethod
    def _fetch(d: int, events):
        """Shard ``d``'s event planes on the host: the host's wait for its
        kernel and the copy, which the packer would otherwise make."""
        with span("repro.fleet.fetch", shard=d,
                  bytes=sum(x.nbytes for x in events)):
            return jax.device_get(events)

    def _emit(self, ems, events, rows=None, close=False):
        """Step each row block's emitter on its rows of the host
        ``events`` and values ``rows`` (and flush it, with ``close``),
        concurrently where there are several blocks.  Returns the
        shard's stepped bytes and flush tails, each in row order."""
        def step(em, b):
            with span("repro.fleet.emit_block", rows=b.stop - b.start):
                blobs = em.step_chunk(
                    SegmentOutput(*(x[b] for x in events)),
                    None if rows is None else rows[b])
                return blobs, em.flush() if close else []

        if self._pool is None:
            parts = [step(ems[0], self._blocks[0])]
        else:
            parts = list(self._pool.map(step, ems, self._blocks))
        return ([blob for blobs, _ in parts for blob in blobs],
                [tail for _, tails in parts for tail in tails])

    def push(self, y_chunk) -> List:
        """Feed ``(S, n)`` columns; returns the new bytes per stream."""
        if self._finished:
            raise RuntimeError("push after finish()")
        y = np.asarray(y_chunk, np.float32)
        if y.ndim != 2 or y.shape[0] != self.n_streams:
            raise ValueError(f"chunk must be ({self.n_streams}, n); "
                             f"got {y.shape}")
        with span("repro.fleet.push", streams=self.n_streams,
                  width=y.shape[1]):
            # Dispatch every shard's segmenter launch before packing any
            # of them: the fetch blocks on its shard's device, so a fused
            # loop would serialize the devices.
            shard_events = []
            for d, seg in enumerate(self._segs):
                rows = y[d * self._rows:(d + 1) * self._rows]
                with span("repro.fleet.put", shard=d):
                    shard = jax.device_put(rows, self.devices[d])
                with span("repro.fleet.segment", shard=d):
                    shard_events.append((rows, seg.push(shard)))
            out: List = []
            for d, (ems, (rows, events)) in enumerate(zip(self._ems,
                                                          shard_events)):
                events = self._fetch(d, events)
                with span("repro.fleet.emit", shard=d,
                          blocks=len(self._blocks)):
                    blobs, _ = self._emit(ems, events, rows)
                self._account(d, blobs)
                out.extend(blobs)
            self.pushed += y.shape[1]
            if self.store is not None:
                with span("repro.fleet.store"):
                    self.store.append(out)
        return out

    def finish(self) -> List:
        """Flush every shard's trailing run; returns the final bytes."""
        if self._finished:
            raise RuntimeError("finish() called twice")
        self._finished = True
        with span("repro.fleet.finish"):
            finals = [seg.finish() for seg in self._segs]
            out: List = []
            for d, (ems, events) in enumerate(zip(self._ems, finals)):
                events = self._fetch(d, events)
                with span("repro.fleet.emit", shard=d,
                          blocks=len(self._blocks)):
                    blobs, tails = self._emit(ems, events, close=True)
                self._account(d, blobs)
                self._account(d, tails)
                if self.protocol == "twostreams":
                    out.extend((a + c, b + e)
                               for (a, b), (c, e) in zip(blobs, tails))
                else:
                    out.extend(b + t for b, t in zip(blobs, tails))
            if self.store is not None:
                self.store.append(out, close=True)
        if self._pool is not None:
            self._pool.shutdown()
        return out
