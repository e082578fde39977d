"""Device-resident protocol & metrics engine (paper §5 + §4.2, batched).

The legacy layer (:mod:`repro.core.protocols` / :mod:`repro.core.metrics`)
walks ``List[CompressionRecord]`` one record at a time — exact, but
host-bound.  This module is the array program equivalent: it consumes the
``(S, T)`` :class:`~repro.core.jax_pla.SegmentOutput` produced by the
batched segmenters (jnp references or Pallas kernels) and computes, for
all ``S`` streams at once,

- the *protocol record structure* of §5 (implicit / twostreams /
  singlestream / singlestreamv) as per-point descriptor arrays — which
  record covers each input point, the record's byte cost, coverage and
  emission time — including the SingleStreamV *burst* packing with the
  signed-byte counter semantics preserved (bursts split at 127);
- the three per-point streaming metrics of §4.2 (compression ratio,
  reconstruction latency, approximation error) as ``(S, T)`` arrays, in
  one jit with no per-record Python;
- per-stream wire byte totals, and — on the host — the actual wire bytes,
  packed with vectorized numpy and **bit-identical** to the legacy
  ``encode_*`` codecs on the same segmentation.

Segments live on the index grid ``t = 0..T-1`` (the framework's streams
are index-stamped); a uniform real-time grid ``t = t0 + dt*i`` is supported
by the byte encoders for wire compatibility with the sequential methods.

:class:`ProtocolEmitter` is the streaming face of the same codecs: an
``init / step_chunk / flush`` object (mirroring the PR-2 carry API of
:mod:`repro.core.jax_pla`) that consumes finalized event columns plus the
raw value columns and emits wire-ready bytes incrementally, bit-identical
to the offline encoders — the concatenation of every ``step_chunk`` output
plus the ``flush`` output equals the one-shot encoding.  Its byte
assembly is a **fused cumsum-offset packer**: one flat buffer per chunk,
vectorized sizes/offsets/field scatters, no per-event Python (the same
technique as :func:`_encode_row`, made stateful across chunks).

For device-sharded fleets (:mod:`repro.sharding.fleet`) the metrics
pipeline splits at the descriptor level: :func:`protocol_descriptors` /
:func:`metrics_from_descriptors` run per shard on device, and the exact
float64 host finish (:func:`descriptors_point_metrics`) is shared with
:func:`batched_point_metrics` — descriptor math is per-stream
independent, so sharding is invisible in the numbers.

The legacy Python codecs remain the golden references:
:func:`to_method_outputs` translates a ``SegmentOutput`` row back into the
sequential-layer :class:`~repro.core.types.MethodOutput` (segments *and*
knots, joint or disjoint convention) so tests can prove byte-for-byte and
metric-for-metric equality against :mod:`repro.core.protocols`.
"""

from __future__ import annotations

import functools
from typing import List, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .jax_pla import SegmentOutput
from .metrics import BatchedPointMetrics
from .wire_decode import WireRecords, decode_records
from .types import (COUNTER_BYTES, DisjointKnot, JointKnot, Line,
                    MethodOutput, Segment, VALUE_BYTES)

__all__ = [
    "ENGINE_PROTOCOLS", "KNOT_KINDS", "PROTOCOL_MIN_SEG",
    "ProtocolPointDescriptors",
    "protocol_descriptors", "protocol_point_metrics", "protocol_nbytes",
    "metrics_from_descriptors", "descriptors_point_metrics",
    "batched_point_metrics", "encode_batch", "to_method_outputs",
    "ProtocolEmitter", "SlotPlaneEmitter", "WireRecords", "decode_records",
    "decode_batch",
]

ENGINE_PROTOCOLS = ("implicit", "twostreams", "singlestream",
                    "singlestreamv")

# Minimum run length for a segment record; shorter runs flush as
# singletons / bursts (paper §5.2; matches repro.core.protocols).
PROTOCOL_MIN_SEG = {"twostreams": 4, "singlestream": 3, "singlestreamv": 3}

# Per-point record kinds.
KIND_SEGMENT = 1
KIND_SINGLETON = 2
KIND_BURST = 3

_SEG_BYTES = {  # segment-record wire cost per protocol
    "twostreams": 3 * VALUE_BYTES + COUNTER_BYTES,      # (t0, n, a, b) = 25
    "singlestream": 2 * VALUE_BYTES + COUNTER_BYTES,    # (n, a, b) = 17
    "singlestreamv": 2 * VALUE_BYTES + COUNTER_BYTES,   # (n, a, b) = 17
}
_SINGLE_BYTES = {
    "twostreams": VALUE_BYTES,                  # bare value on stream 2
    "singlestream": VALUE_BYTES + COUNTER_BYTES,  # (1, y) = 9
}


class ProtocolPointDescriptors(NamedTuple):
    """Per-point record structure of one protocol over ``(S, T)`` streams.

    For input point ``i`` with completing record ``r = record(i)``
    (paper §4.2): ``rec_bytes[i] = |r|`` in bytes, ``rec_len[i] =
    |reconstruct(r)|``, ``emit[i] = time(r)``.  ``kind`` is one of
    ``KIND_SEGMENT / KIND_SINGLETON / KIND_BURST``; ``head`` marks the
    first point of each record (summing ``rec_bytes`` over heads gives the
    stream's wire size).  ``seg_end / a / v`` describe the covering
    *segment*'s anchored line ``y(t) = v + a*(t - seg_end)`` (segment
    points reconstruct through it; singleton/burst points are exact).
    """

    kind: jax.Array       # (S, T) int32
    head: jax.Array       # (S, T) bool
    rec_bytes: jax.Array  # (S, T) int32
    rec_len: jax.Array    # (S, T) int32
    emit: jax.Array       # (S, T) int32
    seg_end: jax.Array    # (S, T) int32 — end of covering segment
    seg_start: jax.Array  # (S, T) int32
    seg_len: jax.Array    # (S, T) int32
    a: jax.Array          # (S, T) — covering segment's slope
    v: jax.Array          # (S, T) — covering segment's value at seg_end


def _segment_geometry(seg: SegmentOutput):
    """Per-point covering-segment arrays from (S, T) break events."""
    brk = seg.breaks.astype(bool)
    S, T = brk.shape
    brk = brk.at[:, T - 1].set(True)  # canonical form: stream end breaks
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (S, T))
    # Next break at-or-after t (the covering segment's end).
    e = jnp.flip(jax.lax.cummin(
        jnp.flip(jnp.where(brk, pos, T - 1), 1), axis=1), 1)
    # Last break strictly before t; the segment starts one past it.
    cm = jax.lax.cummax(jnp.where(brk, pos, -1), axis=1)
    prevb = jnp.concatenate(
        [jnp.full((S, 1), -1, jnp.int32), cm[:, :-1]], axis=1)
    start = prevb + 1
    n = e - start + 1
    # The processing of e+1 decides the break => earliest emission time.
    fin = jnp.minimum(e + 1, T - 1)
    a_pt = jnp.take_along_axis(seg.a, e, axis=1)
    v_pt = jnp.take_along_axis(seg.v, e, axis=1)
    return pos, e, start, n, fin, a_pt, v_pt


# Relative tolerance of the joint-knot continuity detector for mixed
# segmentations: joint knots agree to f32 rounding (~1e-7 relative), while
# disjoint knots are separated by the infeasibility gap that caused the
# break — 1e-4 sits three decades above the former.
_JOINT_RTOL = 1e-4

KNOT_KINDS = ("joint", "disjoint", "continuous", "mixed")


def _joint_flags(e, a_pt, v_pt):
    """Per-position jointness of the break at that position (meaningful at
    break positions only): the covering segment's line and the *next*
    segment's line agree at ``p + 1`` within ``_JOINT_RTOL``.  The closing
    break at T-1 is always a joint knot."""
    S, T = e.shape
    nxt = jnp.minimum(jnp.arange(T, dtype=jnp.int32) + 1, T - 1)[None, :]
    e_n = jnp.take_along_axis(e, jnp.broadcast_to(nxt, (S, T)), axis=1)
    a_n = jnp.take_along_axis(a_pt, jnp.broadcast_to(nxt, (S, T)), axis=1)
    v_n = jnp.take_along_axis(v_pt, jnp.broadcast_to(nxt, (S, T)), axis=1)
    left = v_pt + a_pt                      # this line at p + 1
    right = v_n - a_n * (e_n - nxt).astype(a_n.dtype)
    tol = _JOINT_RTOL * (1.0 + jnp.abs(left) + jnp.abs(right))
    return (jnp.abs(left - right) <= tol) \
        | (jnp.arange(T, dtype=jnp.int32)[None, :] == T - 1)


@functools.partial(jax.jit,
                   static_argnames=("protocol", "knot_kind", "burst_cap"))
def protocol_descriptors(seg: SegmentOutput, protocol: str,
                         knot_kind: str = "disjoint",
                         burst_cap: int = 127) -> ProtocolPointDescriptors:
    """Vectorize one §5 protocol over an ``(S, T)`` segmentation.

    ``knot_kind`` only matters for ``implicit``: ``"joint"`` (SwingFilter)
    knots cost 2 fields, ``"disjoint"`` knots 3 (streamed in two parts;
    the stream's closing knot is joint, hence 2).  ``"continuous"`` is
    joint with the one-segment-deferred emission of the continuous method
    (a segment's line resolves only when the *next* segment breaks);
    ``"mixed"`` detects joint vs disjoint knots from line continuity
    (:func:`_joint_flags`) and defers emission likewise (a join shifts the
    decision one extra position).
    """
    if protocol not in ENGINE_PROTOCOLS:
        raise ValueError(f"unknown protocol {protocol!r}; "
                         f"have {sorted(ENGINE_PROTOCOLS)}")
    if knot_kind not in KNOT_KINDS:
        raise ValueError(f"knot_kind must be one of {KNOT_KINDS}; "
                         f"{knot_kind!r}")
    pos, e, start, n, fin, a_pt, v_pt = _segment_geometry(seg)
    S, T = pos.shape
    at_start = pos == start

    if protocol == "implicit":
        kind = jnp.full((S, T), KIND_SEGMENT, jnp.int32)
        if knot_kind == "joint":
            nbytes = jnp.full((S, T), 2 * VALUE_BYTES, jnp.int32)
            emit = fin
        elif knot_kind == "disjoint":
            # Interior segments terminate on a 3-field disjoint knot; the
            # last segment's right knot is the closing joint knot (2).
            nbytes = jnp.where(e == T - 1, 2 * VALUE_BYTES, 3 * VALUE_BYTES)
            emit = fin
        else:
            # Deferred methods: the segment ending at e is emitted at the
            # break of the *next* segment (end e2); for mixed, a join at
            # that next break pushes the decision one position further.
            e2 = jnp.take_along_axis(e, jnp.minimum(e + 1, T - 1), axis=1)
            if knot_kind == "continuous":
                nbytes = jnp.full((S, T), 2 * VALUE_BYTES, jnp.int32)
                emit = jnp.minimum(e2 + 1, T - 1)
            else:  # mixed
                joint = _joint_flags(e, a_pt, v_pt)
                j_e = jnp.take_along_axis(joint, e, axis=1)
                j_e2 = jnp.take_along_axis(joint, e2, axis=1)
                nbytes = jnp.where(j_e, 2 * VALUE_BYTES, 3 * VALUE_BYTES)
                emit = jnp.minimum(e2 + 1 + j_e2.astype(jnp.int32), T - 1)
        return ProtocolPointDescriptors(
            kind=kind, head=at_start, rec_bytes=nbytes.astype(jnp.int32),
            rec_len=n, emit=emit, seg_end=e, seg_start=start, seg_len=n,
            a=a_pt, v=v_pt)

    long = n >= PROTOCOL_MIN_SEG[protocol]
    seg_bytes = _SEG_BYTES[protocol]

    if protocol in ("twostreams", "singlestream"):
        kind = jnp.where(long, KIND_SEGMENT, KIND_SINGLETON)
        head = jnp.where(long, at_start, True)
        nbytes = jnp.where(long, seg_bytes, _SINGLE_BYTES[protocol])
        rec_len = jnp.where(long, n, 1)
        return ProtocolPointDescriptors(
            kind=kind.astype(jnp.int32), head=head,
            rec_bytes=nbytes.astype(jnp.int32), rec_len=rec_len, emit=fin,
            seg_end=e, seg_start=start, seg_len=n, a=a_pt, v=v_pt)

    # singlestreamv: short-run points buffer into bursts.  A maximal run of
    # buffered points spans consecutive short segments; it flushes when the
    # next segment record is emitted, at ``burst_cap`` values, or at end of
    # stream (repro.core.protocols.protocol_singlestreamv semantics).
    single = ~long
    # Start of the maximal singleton run containing t.
    run_start = jax.lax.cummax(jnp.where(~single, pos + 1, 0), axis=1)
    c = pos - run_start                       # index within the run
    b_start = run_start + (c // burst_cap) * burst_cap
    # First non-singleton position after t (T when the run hits the end).
    nxt_ns = jnp.flip(jax.lax.cummin(
        jnp.flip(jnp.where(~single, pos, T), 1), axis=1), 1)
    b_last = jnp.minimum(b_start + burst_cap - 1, nxt_ns - 1)
    m = b_last - b_start + 1
    fin_at = lambda idx: jnp.take_along_axis(  # noqa: E731
        fin, jnp.clip(idx, 0, T - 1), axis=1)
    # Cap-filled bursts flush while their last point's segment is being
    # scattered; partial bursts wait for the next segment record (or the
    # end of the stream, where fin[T-1] == T-1).
    emit_burst = jnp.where(m == burst_cap, fin_at(b_last),
                           fin_at(jnp.minimum(nxt_ns, T - 1)))
    kind = jnp.where(long, KIND_SEGMENT, KIND_BURST)
    head = jnp.where(long, at_start, c % burst_cap == 0)
    nbytes = jnp.where(long, seg_bytes,
                       COUNTER_BYTES + VALUE_BYTES * m)
    rec_len = jnp.where(long, n, m)
    emit = jnp.where(long, fin, emit_burst)
    return ProtocolPointDescriptors(
        kind=kind.astype(jnp.int32), head=head,
        rec_bytes=nbytes.astype(jnp.int32), rec_len=rec_len, emit=emit,
        seg_end=e, seg_start=start, seg_len=n, a=a_pt, v=v_pt)


@functools.partial(jax.jit,
                   static_argnames=("protocol", "knot_kind", "burst_cap"))
def protocol_point_metrics(seg: SegmentOutput, y: jax.Array, protocol: str,
                           knot_kind: str = "disjoint", burst_cap: int = 127
                           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The §4.2 per-point metrics as (S, T) device arrays, in one jit.

    Returns ``(ratio, latency, error)``: ``ratio = |r| / |reconstruct(r)|``
    in y-value units, ``latency = time(r) - i`` in tuples, ``error =
    |y'_i - y_i|`` (0 for singleton/burst points, which ship exact
    values).  Reconstruction is the anchored gather
    ``v + a * (t - seg_end)`` — no scan, no per-record host work.
    """
    d = protocol_descriptors(seg, protocol, knot_kind, burst_cap)
    return metrics_from_descriptors(d, y)


def metrics_from_descriptors(d: ProtocolPointDescriptors, y: jax.Array
                             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The device (float32) §4.2 metric expressions over precomputed
    descriptors — shared by :func:`protocol_point_metrics` and the
    sharded fleet pipeline (:mod:`repro.sharding.fleet`), where the
    descriptors already live on each device shard."""
    pos = jnp.arange(y.shape[1], dtype=jnp.int32)[None, :]
    ratio = (d.rec_bytes.astype(jnp.float32) / VALUE_BYTES) \
        / d.rec_len.astype(jnp.float32)
    latency = (d.emit - pos).astype(jnp.float32)
    y_hat = d.v + d.a * (pos - d.seg_end).astype(d.a.dtype)
    error = jnp.where(d.kind == KIND_SEGMENT,
                      jnp.abs(y_hat - y), jnp.zeros_like(y))
    return ratio, latency, error


@functools.partial(jax.jit,
                   static_argnames=("protocol", "knot_kind", "burst_cap"))
def protocol_nbytes(seg: SegmentOutput, protocol: str,
                    knot_kind: str = "disjoint", burst_cap: int = 127
                    ) -> Tuple[jax.Array, jax.Array]:
    """Per-stream ``(record_bytes, n_records)`` wire accounting, jitted.

    ``record_bytes`` sums each record once (at its head); dividing by
    ``VALUE_BYTES * T`` gives the whole-stream compression ratio of
    :func:`repro.core.metrics.overall_compression`.  The implicit
    protocol's byte-level codec adds one opening joint knot
    (``2 * VALUE_BYTES``) on top of the per-record accounting.
    """
    d = protocol_descriptors(seg, protocol, knot_kind, burst_cap)
    nbytes = jnp.where(d.head, d.rec_bytes, 0).sum(axis=1)
    n_records = d.head.sum(axis=1).astype(jnp.int32)
    return nbytes, n_records


# ---------------------------------------------------------------------------
# Host wrappers: float64 metrics + batched summaries, legacy-exact
# ---------------------------------------------------------------------------

def batched_point_metrics(seg: SegmentOutput, ys, protocol: str,
                          knot_kind: str = "disjoint", *,
                          eps: Optional[float] = None,
                          burst_cap: int = 127,
                          y_hat=None, abs_err=None) -> BatchedPointMetrics:
    """Batched §4.2 metrics, bit-equal to the per-record reference.

    Pulls the jitted descriptors once and finishes in float64 numpy with
    the exact expressions of :func:`repro.core.metrics.point_metrics`
    (``(nbytes / POINT_BYTES) / m``; values via the global-intercept line
    ``A*t + B``), so each row equals the legacy single-stream result to
    the last bit.  ``y_hat`` optionally substitutes a device-computed
    reconstruction (e.g. :func:`repro.kernels.ops.reconstruct_tpu`) for
    the line evaluation, and ``abs_err`` a device-computed ``|y' - y|``
    surface (the second output of the fused
    :func:`repro.kernels.ops.reconstruct_error_tpu`) — errors then carry
    that path's float32 rounding.
    """
    d = protocol_descriptors(seg, protocol, knot_kind, burst_cap)
    return descriptors_point_metrics(d, ys, eps=eps, y_hat=y_hat,
                                     abs_err=abs_err)


def descriptors_point_metrics(d: ProtocolPointDescriptors, ys, *,
                              eps: Optional[float] = None,
                              y_hat=None, abs_err=None
                              ) -> BatchedPointMetrics:
    """The float64 host finish of :func:`batched_point_metrics` over
    already-computed (possibly device-sharded) descriptors.

    Descriptor math is per-stream independent, so descriptors computed
    shard-by-shard (:mod:`repro.sharding.fleet`) equal the full-batch
    descriptors row for row — finishing them here keeps the fleet
    pipeline bit-equal per stream to the single-device
    :func:`batched_point_metrics`.
    """
    ys = np.asarray(ys, np.float64)
    S, T = ys.shape
    pos = np.arange(T, dtype=np.float64)[None, :]
    rec_bytes = np.asarray(d.rec_bytes, np.float64)
    rec_len = np.asarray(d.rec_len, np.float64)
    ratio = (rec_bytes / VALUE_BYTES) / rec_len
    latency = np.asarray(d.emit, np.float64) - pos
    is_seg = np.asarray(d.kind) == KIND_SEGMENT
    if abs_err is not None:
        abs_err = np.asarray(abs_err, np.float64)
    elif y_hat is not None:
        abs_err = np.abs(np.asarray(y_hat, np.float64) - ys)
    else:
        a64 = np.asarray(d.a, np.float64)
        v64 = np.asarray(d.v, np.float64)
        e64 = np.asarray(d.seg_end, np.float64)
        y_hat = a64 * pos + (v64 - a64 * e64)   # Line(A, B) evaluation
        abs_err = np.abs(y_hat - ys)
    error = np.where(is_seg, abs_err, 0.0)
    if eps is not None:
        # float32 engine slack (the jnp segmenters fit in f32; cf. the
        # tighter f64 tolerance of metrics.point_metrics).  eps may be a
        # scalar or a per-stream (S,) array.
        eps_row = np.broadcast_to(np.asarray(eps, np.float64).reshape(-1),
                                  (S,))
        bad = error > eps_row[:, None] * (1 + 1e-4) + 1e-5
        if bad.any():
            s, i = map(int, np.argwhere(bad)[0])
            raise ValueError(
                f"max-error guarantee violated at stream {s} point {i}: "
                f"err={error[s, i]:.3e} > eps={eps_row[s]:.3e}")
    return BatchedPointMetrics(ratio=ratio, latency=latency, error=error)


# ---------------------------------------------------------------------------
# Vectorized byte-level encoders (host; bit-identical to repro.core.protocols)
# ---------------------------------------------------------------------------

def _put_f64(buf: np.ndarray, offs: np.ndarray, vals: np.ndarray) -> None:
    """Scatter little-endian float64 values at per-record byte offsets."""
    if len(offs) == 0:
        return
    b = np.ascontiguousarray(vals, "<f8").view(np.uint8).reshape(-1, 8)
    buf[offs[:, None] + np.arange(8)] = b


def _row_lines(brk_row, a_row, v_row, t0: float, dt: float):
    """Per-segment (ends, starts, n, A, B) with the legacy float64 math:
    ``A = a/dt``; ``B = v - a*e - A*t0`` (e on the index grid)."""
    ends = np.flatnonzero(brk_row)
    if len(ends) == 0 or ends[-1] != len(brk_row) - 1:
        ends = np.append(ends, len(brk_row) - 1)
    starts = np.concatenate([[0], ends[:-1] + 1])
    n = ends - starts + 1
    a64 = np.asarray(a_row, np.float64)[ends]
    v64 = np.asarray(v_row, np.float64)[ends]
    A = a64 / dt
    B = v64 - a64 * ends - A * t0
    return ends, starts, n, A, B


def _encode_row(protocol: str, brk_row, a_row, v_row, ys_row,
                knot_kind: str, t0: float, dt: float, burst_cap: int):
    T = len(ys_row)
    ends, starts, n, A, B = _row_lines(brk_row, a_row, v_row, t0, dt)
    ys64 = np.asarray(ys_row, np.float64)
    t_of = lambda i: t0 + dt * np.asarray(i, np.float64)  # noqa: E731

    if protocol == "implicit":
        K = len(ends)
        t_end = t_of(ends[-1])
        if knot_kind in ("joint", "continuous"):
            # One joint knot per segment end, on the segment's line.  The
            # opening knot is the raw first point for SwingFilter (its
            # wedge origin) and the first line's value for the continuous
            # polyline (methods.run_continuous's first fixed knot).
            y_open = ys64[0] if knot_kind == "joint" \
                else A[0] * t_of(0) + B[0]
            ts_k = np.concatenate([[t_of(0)], t_of(ends)])
            ys_k = np.concatenate([[y_open], A * t_of(ends) + B])
            return np.stack([ts_k, ys_k], 1).ravel().astype("<f8").tobytes()
        if knot_kind == "mixed":
            # Joint knots (detected from line continuity) pack as (t, y);
            # disjoint knots use Luo et al.'s sign trick with the y''
            # value interleaved before the next knot's first part.
            buf = bytearray()
            buf += np.array([t_of(0), A[0] * t_of(0) + B[0]],
                            "<f8").tobytes()
            tb = t_of(ends[:-1] + 1)
            y1 = A[:-1] * tb + B[:-1]
            y2 = A[1:] * tb + B[1:]
            joint = np.abs(y1 - y2) <= _JOINT_RTOL * (1 + np.abs(y1)
                                                      + np.abs(y2))
            pend: List[float] = []
            for k in range(K - 1):
                if pend:
                    buf += np.array([pend.pop()], "<f8").tobytes()
                if joint[k]:
                    buf += np.array([tb[k], y1[k]], "<f8").tobytes()
                else:
                    buf += np.array([-tb[k], y1[k]], "<f8").tobytes()
                    pend.append(y2[k])
            if pend:
                buf += np.array([pend.pop()], "<f8").tobytes()
            buf += np.array([t_end, A[-1] * t_end + B[-1]], "<f8").tobytes()
            return bytes(buf)
        head = np.array([t_of(0), A[0] * t_of(0) + B[0]])
        if K == 1:
            body = np.empty(0)
        else:
            tb = t_of(starts[1:])
            y1 = A[:-1] * tb + B[:-1]
            y2 = A[1:] * tb + B[1:]
            body = np.stack([-tb, y1, y2], 1).ravel()
        tail = np.array([t_end, A[-1] * t_end + B[-1]])
        return np.concatenate([head, body, tail]).astype("<f8").tobytes()

    long = n >= PROTOCOL_MIN_SEG[protocol]
    n_cap = 127 if protocol == "singlestreamv" else 256
    if int(n[long].max(initial=0)) > n_cap:
        raise ValueError(
            f"{protocol}: segment of {int(n[long].max())} points exceeds "
            f"the {n_cap}-point counter range — segment with "
            f"max_run=PROTOCOL_CAPS[{protocol!r}]")
    seg_id = np.searchsorted(ends, np.arange(T))
    long_pt = long[seg_id]

    if protocol == "twostreams":
        kl = np.flatnonzero(long)
        seg_buf = np.zeros(25 * len(kl), np.uint8)
        offs = 25 * np.arange(len(kl))
        _put_f64(seg_buf, offs, t_of(starts[kl]))
        seg_buf[offs + 8] = (n[kl] - 1).astype(np.uint8)
        _put_f64(seg_buf, offs + 9, A[kl])
        _put_f64(seg_buf, offs + 17, B[kl])
        single_buf = ys64[~long_pt].astype("<f8").tobytes()
        return seg_buf.tobytes(), single_buf

    if protocol == "singlestream":
        head_pt = np.flatnonzero(np.where(long_pt,
                                          np.arange(T) == starts[seg_id],
                                          True))
        is_seg = long_pt[head_pt]
        sizes = np.where(is_seg, 17, 9)
        offs = np.concatenate([[0], np.cumsum(sizes)[:-1]])
        buf = np.zeros(int(sizes.sum()), np.uint8)
        buf[offs] = np.where(is_seg, n[seg_id[head_pt]] - 1, 0) \
            .astype(np.uint8)
        _put_f64(buf, offs[is_seg] + 1, A[seg_id[head_pt[is_seg]]])
        _put_f64(buf, offs[is_seg] + 9, B[seg_id[head_pt[is_seg]]])
        _put_f64(buf, offs[~is_seg] + 1, ys64[head_pt[~is_seg]])
        return buf.tobytes()

    # singlestreamv
    pos = np.arange(T)
    run_start = np.maximum.accumulate(np.where(long_pt, pos + 1, 0))
    c = pos - run_start
    head_pt = np.flatnonzero(np.where(long_pt, pos == starts[seg_id],
                                      c % burst_cap == 0))
    is_seg = long_pt[head_pt]
    nxt_ns = np.minimum.accumulate(np.where(long_pt, pos, T)[::-1])[::-1]
    b_last = np.minimum(head_pt + burst_cap - 1, nxt_ns[head_pt] - 1)
    m = np.where(is_seg, 0, b_last - head_pt + 1)
    sizes = np.where(is_seg, 17, 1 + 8 * m)
    offs = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    buf = np.zeros(int(sizes.sum()), np.uint8)
    buf[offs] = np.where(is_seg, n[seg_id[head_pt]],
                         -m).astype(np.int8).view(np.uint8)
    _put_f64(buf, offs[is_seg] + 1, A[seg_id[head_pt[is_seg]]])
    _put_f64(buf, offs[is_seg] + 9, B[seg_id[head_pt[is_seg]]])
    # Burst payloads: each buffered point writes its exact value at
    # head_offset + 1 + 8 * (its index within the burst).
    sp = np.flatnonzero(~long_pt)
    if len(sp):
        r = np.searchsorted(head_pt, sp, "right") - 1
        _put_f64(buf, offs[r] + 1 + 8 * (sp - head_pt[r]), ys64[sp])
    return buf.tobytes()


def encode_batch(seg: SegmentOutput, ys, protocol: str,
                 knot_kind: str = "disjoint", *, t0: float = 0.0,
                 dt: float = 1.0, burst_cap: int = 127) -> List:
    """Wire-encode every stream of an (S, T) segmentation.

    Returns one ``bytes`` per stream (``(seg_bytes, singleton_bytes)``
    pairs for ``twostreams``), bit-identical to the legacy
    ``repro.core.protocols.encode_*`` codecs run on the same segmentation
    (see :func:`to_method_outputs`).
    """
    if protocol not in ENGINE_PROTOCOLS:
        raise ValueError(f"unknown protocol {protocol!r}")
    brk = np.asarray(seg.breaks, bool)
    a = np.asarray(seg.a)
    v = np.asarray(seg.v)
    ys = np.asarray(ys)
    return [_encode_row(protocol, brk[s], a[s], v[s], ys[s], knot_kind,
                        t0, dt, burst_cap) for s in range(brk.shape[0])]


def decode_batch(wire: Sequence, protocol: str, *, t0: float = 0.0,
                 dt: float = 1.0, closed: bool = True
                 ) -> List["WireRecords"]:
    """Descriptor-decode every stream of an ``encode_batch`` blob list.

    The inverse of :func:`encode_batch` one level above raw samples:
    each blob becomes a :class:`~repro.core.wire_decode.WireRecords`
    column table — one row per wire record with its byte offset, grid
    span and anchored line (or exact values) — so callers can window,
    index or run closed-form analytics without materializing the
    series.  ``records.reconstruct(0, n, t0, dt)`` is bit-identical to
    the legacy ``repro.core.protocols.decode_*`` codecs.
    """
    return [decode_records(blob, protocol, t0=t0, dt=dt, closed=closed)
            for blob in wire]


# ---------------------------------------------------------------------------
# Golden-reference translation: SegmentOutput -> sequential MethodOutput
# ---------------------------------------------------------------------------

def to_method_outputs(seg: SegmentOutput, ts, ys,
                      knot_kind: str = "disjoint") -> List[MethodOutput]:
    """Translate each stream row into the sequential-layer MethodOutput.

    Uses the same anchored-to-global line conversion as the engine
    (``A = a/dt``, ``B = v - a*e - A*t0``), the break-decision emission
    times (``finalized_at = min(e+1, T-1)``), and the knot conventions of
    :mod:`repro.core.methods` — so the legacy protocols + codecs applied
    to the result are the *golden reference* for the vectorized paths.
    """
    ts = np.asarray(ts, np.float64)
    ys = np.asarray(ys)
    T = ts.shape[-1]
    dt = float(ts[1] - ts[0]) if T > 1 else 1.0
    t0 = float(ts[0])
    brk = np.asarray(seg.breaks, bool)
    outs: List[MethodOutput] = []
    for s in range(brk.shape[0]):
        ends, starts, n, A, B = _row_lines(brk[s], np.asarray(seg.a)[s],
                                           np.asarray(seg.v)[s], t0, dt)
        fins = np.minimum(ends + 1, T - 1)
        lines = [Line(float(A[k]), float(B[k])) for k in range(len(ends))]
        segments = [Segment(int(starts[k]), int(ends[k]) + 1, lines[k],
                            finalized_at=int(fins[k]))
                    for k in range(len(ends))]
        knots: List[object] = []
        if knot_kind == "joint":
            knots.append(JointKnot(float(ts[0]), float(ys[s][0]),
                                   emitted_at=0))
            for k, sg in enumerate(segments):
                te = float(ts[ends[k]])
                knots.append(JointKnot(te, sg.line(te),
                                       emitted_at=int(fins[k])))
        else:
            knots.append(JointKnot(float(ts[0]), lines[0](float(ts[0])),
                                   emitted_at=int(fins[0])))
            for k in range(1, len(segments)):
                tb = float(ts[starts[k]])
                knots.append(DisjointKnot(
                    tb, lines[k - 1](tb), lines[k](tb),
                    emitted_at_first=int(fins[k - 1]),
                    emitted_at_second=int(fins[k])))
            te = float(ts[T - 1])
            knots.append(JointKnot(te, lines[-1](te), emitted_at=T - 1))
        outs.append(MethodOutput(segments=segments, knots=knots))
    return outs


# ---------------------------------------------------------------------------
# Streaming emitter: init / step_chunk / flush over event columns
# ---------------------------------------------------------------------------

class ProtocolEmitter:
    """Streaming protocol encoder over finalized event columns.

    Mirrors the carry API of :mod:`repro.core.jax_pla`: construct, feed
    ``step_chunk(events, y_chunk)`` any number of times, then ``flush()``.
    ``events`` is a (S, w) :class:`SegmentOutput` of *newly finalized*
    columns (the output of ``jax_pla.step_chunk`` / ``jax_pla.flush`` or
    ``kernels.ops.StreamingSegmenter.push/finish``); ``y_chunk`` is the
    matching raw (S, n) value columns (pass the values no later than the
    events they produce — singleton records ship exact values).  Either
    argument may be ``None``.

    Each call returns the newly wire-ready bytes per stream (pairs of
    ``(segment, singleton)`` bytes for ``twostreams``); concatenating all
    returns plus the ``flush()`` return is **bit-identical** to the
    offline :func:`encode_batch` / legacy ``encode_*`` on the one-shot
    segmentation.  Values are buffered as float64, so feeding the same
    arrays gives the same bytes as the host codecs.

    The per-stream row-codec bookkeeping (segment counter, previous break
    and line, burst window, pending disjoint y'') lives in flat ``(S,)``
    numpy arrays, and the whole chunk packs in one fused vectorized pass:
    event extraction (``np.nonzero``), line conversion, per-record byte
    sizes, ``cumsum`` byte offsets into a single flat buffer, and
    ``_put_f64``-style scatters for every field — the same technique as
    the offline :func:`_encode_row`, with the cross-event codec state
    (previous break/line, burst fill, pending y'') resolved by grouped
    shifts and segmented cumulative sums instead of a Python walk.  No
    per-event Python runs even in the dense-event worst case (every point
    a singleton); quiet fleets cost O(events), not O(S).

    Its streams share one column clock: how a chunk is taken in
    (``_take``) and which values are kept (``_gather_runs``, ``_trim``)
    follow from that; :class:`SlotPlaneEmitter` replaces those three for
    a plane of streams with clocks of their own and shares the rest.

    ``knot_kind`` extends to the deferred methods: ``"continuous"``
    (joint knots on the connected polyline, opening knot on the first
    line) and ``"mixed"`` (joint/disjoint detected from line continuity,
    one knot of lag, sign-trick interleaving) — byte-identical to
    :func:`encode_batch` with the same kind.
    """

    def __init__(self, protocol: str, n_streams: int, *,
                 knot_kind: str = "disjoint", t0: float = 0.0,
                 dt: float = 1.0, burst_cap: int = 127):
        self._init_codec(protocol, n_streams, knot_kind, t0, dt, burst_cap)
        self._ybuf = np.zeros((n_streams, 0), np.float64)
        self._ybase = 0            # absolute position of _ybuf[:, 0]
        self._epos = 0             # absolute position of next event column

    def _init_codec(self, protocol: str, n_streams: int, knot_kind: str,
                    t0: float, dt: float, burst_cap: int) -> None:
        if protocol not in ENGINE_PROTOCOLS:
            raise ValueError(f"unknown protocol {protocol!r}; "
                             f"have {sorted(ENGINE_PROTOCOLS)}")
        if knot_kind not in KNOT_KINDS:
            raise ValueError(f"knot_kind must be one of {KNOT_KINDS}; "
                             f"{knot_kind!r}")
        self.protocol = protocol
        self.n_streams = n_streams
        self.knot_kind = knot_kind
        self.t0 = float(t0)
        self.dt = float(dt)
        self.burst_cap = burst_cap
        S = n_streams
        # Vectorized row-codec state (one slot per stream).
        self._k = np.zeros(S, np.int64)            # segments finalized
        self._prev_end = np.full(S, -1, np.int64)  # last break position
        self._prev_A = np.zeros(S, np.float64)     # last segment's Line
        self._prev_B = np.zeros(S, np.float64)
        self._pend_start = np.zeros(S, np.int64)   # singlestreamv window
        self._pend_len = np.zeros(S, np.int64)
        self._pend_y2 = np.zeros(S, np.float64)    # mixed: deferred y''
        self._has_y2 = np.zeros(S, bool)
        self._finished = False

    # -- plumbing -----------------------------------------------------------

    def _t(self, i):
        """Wall-clock time of absolute position(s) ``i`` (vectorized)."""
        return self.t0 + self.dt * np.asarray(i, np.float64)

    def _trim(self) -> None:
        """Drop value columns no future record can reference."""
        if self.protocol == "singlestreamv":
            keep_from = int(self._pend_start.min())
        elif self.protocol == "implicit" and self.knot_kind == "joint" \
                and (self._k == 0).any():
            keep_from = 0  # the opening knot ships the raw first value
        else:
            keep_from = int(self._prev_end.min()) + 1
        drop = keep_from - self._ybase
        if drop > 0:
            self._ybuf = self._ybuf[:, drop:]
            self._ybase = keep_from

    def _gather_runs(self, rows, lo, lens):
        """Buffered values of contiguous runs ``[lo, lo + lens)``, flat.

        Returns ``(vals, within)``: the concatenated run values and each
        value's index inside its own run — exactly what the packers need
        to scatter variable-length payloads at ``repeat(offs) + k*within``
        byte positions in one shot.
        """
        lens = np.asarray(lens, np.int64)
        if not lens.sum():
            return np.empty(0, np.float64), np.empty(0, np.int64)
        lo = np.asarray(lo, np.int64)
        have_lo = self._ybase
        within = _run_offsets(lo, lens, have_lo,
                              have_lo + self._ybuf.shape[1])
        vals = self._ybuf[np.repeat(rows, lens),
                          np.repeat(lo - have_lo, lens) + within]
        return vals, within

    def _per_stream(self, buf: np.ndarray, sizes: np.ndarray, ss) -> List:
        """Slice the flat event-major buffer into one bytes per stream.

        Event order is stream-major (``np.nonzero`` row-major), so each
        stream's records are contiguous in ``buf``.
        """
        out = [b""] * self.n_streams
        per = np.zeros(self.n_streams, np.int64)
        np.add.at(per, ss, sizes.astype(np.int64))
        ends = np.cumsum(per)
        for s in np.flatnonzero(per):
            out[s] = buf[ends[s] - per[s]:ends[s]].tobytes()
        return out

    def _check_cap(self, n, long) -> None:
        n_cap = 127 if self.protocol == "singlestreamv" else 256
        bad = long & (n > n_cap)
        if bad.any():
            raise ValueError(
                f"{self.protocol}: segment of {int(n[bad][0])} points "
                f"exceeds the {n_cap}-point counter range — segment with "
                f"max_run=PROTOCOL_CAPS[{self.protocol!r}]")

    def _event_geometry(self, ss, es, a, v) -> "_ChunkEvents":
        """Per-event codec geometry, resolved without a Python walk.

        ``ss``/``es`` are each event's stream and absolute break
        position, stream-major and time-sorted within a stream.
        Cross-event state (previous break position / line, segment
        ordinal) comes from the carried ``(S,)`` arrays for each stream's
        first event of the chunk and from a one-element shift for the
        rest — events are stream-major so a stream's events are adjacent.
        """
        As = a / self.dt
        Bs = v - a * es - As * self.t0
        N = len(ss)
        first = np.empty(N, bool)
        first[0] = True
        np.not_equal(ss[1:], ss[:-1], out=first[1:])
        gstart = np.flatnonzero(first)
        glast = np.r_[gstart[1:] - 1, N - 1]
        counts = np.diff(np.r_[gstart, N])
        prev = np.empty(N, np.int64)
        prev[1:] = es[:-1]
        prev[gstart] = self._prev_end[ss[gstart]]
        pA = np.empty(N)
        pA[1:] = As[:-1]
        pA[gstart] = self._prev_A[ss[gstart]]
        pB = np.empty(N)
        pB[1:] = Bs[:-1]
        pB[gstart] = self._prev_B[ss[gstart]]
        k_ev = self._k[ss] + np.arange(N) - np.repeat(gstart, counts)
        return _ChunkEvents(ss=ss, es=es, prev=prev, n=es - prev, As=As,
                            Bs=Bs, pA=pA, pB=pB, k_ev=k_ev, gstart=gstart,
                            glast=glast, counts=counts)

    # -- fused packers (one flat buffer + cumsum offsets per chunk) ---------

    def _pack_implicit(self, ev: "_ChunkEvents"):
        kk = self.knot_kind
        o = ev.k_ev == 0                      # stream's first-ever event
        no = int(o.sum())
        te = self._t(ev.es)
        ye = ev.As * te + ev.Bs
        t0_ = self._t(0)
        if kk in ("joint", "continuous"):
            sizes = np.where(o, 32, 16)
            offs, total = _excl_offsets(sizes)
            buf = np.zeros(total, np.uint8)
            if no:
                if kk == "joint":   # wedge origin: the raw first value
                    y_open, _ = self._gather_runs(
                        ev.ss[o], np.zeros(no, np.int64),
                        np.ones(no, np.int64))
                else:               # polyline: the first line at t0
                    y_open = ev.As[o] * t0_ + ev.Bs[o]
                _put_f64(buf, offs[o], np.full(no, t0_))
                _put_f64(buf, offs[o] + 8, y_open)
            coff = offs + 16 * o
            _put_f64(buf, coff, te)
            _put_f64(buf, coff + 8, ye)
            return buf, sizes
        # Disjoint-family kinds: the knot lives at the previous segment's
        # break; y1/y2 are the two lines evaluated at the shared point.
        m = ~o
        tb = self._t(ev.prev + 1)
        y1 = ev.pA * tb + ev.pB
        y2 = ev.As * tb + ev.Bs
        if kk == "disjoint":
            sizes = np.where(o, 16, 24)
            offs, total = _excl_offsets(sizes)
            buf = np.zeros(total, np.uint8)
            if no:
                _put_f64(buf, offs[o], np.full(no, t0_))
                _put_f64(buf, offs[o] + 8, ev.As[o] * t0_ + ev.Bs[o])
            _put_f64(buf, offs[m], -tb[m])
            _put_f64(buf, offs[m] + 8, y1[m])
            _put_f64(buf, offs[m] + 16, y2[m])
            return buf, sizes
        # mixed: joint knots by line continuity; a disjoint knot defers
        # its y'' one knot (Luo et al.'s sign trick).  The pending y''
        # chains event-to-event: a one-element shift of the disjoint
        # flags/values, seeded from the carried per-stream state.
        joint = np.abs(y1 - y2) <= _JOINT_RTOL * (1 + np.abs(y1)
                                                  + np.abs(y2))
        dj = m & ~joint
        N = len(ev.ss)
        pw = np.empty(N, bool)
        pv = np.empty(N, np.float64)
        pw[1:] = dj[:-1]
        pv[1:] = y2[:-1]
        pw[ev.gstart] = self._has_y2[ev.ss[ev.gstart]]
        pv[ev.gstart] = self._pend_y2[ev.ss[ev.gstart]]
        pw &= m                       # a first-ever event has no knot yet
        sizes = np.where(o, 16, 16 + 8 * pw)
        offs, total = _excl_offsets(sizes)
        buf = np.zeros(total, np.uint8)
        if no:
            _put_f64(buf, offs[o], np.full(no, t0_))
            _put_f64(buf, offs[o] + 8, ev.As[o] * t0_ + ev.Bs[o])
        _put_f64(buf, offs[pw], pv[pw])
        koff = offs + 8 * pw
        _put_f64(buf, koff[m], np.where(joint[m], tb[m], -tb[m]))
        _put_f64(buf, koff[m] + 8, y1[m])
        gs = ev.ss[ev.gstart]
        self._has_y2[gs] = dj[ev.glast]
        self._pend_y2[gs] = np.where(dj[ev.glast], y2[ev.glast],
                                     self._pend_y2[gs])
        return buf, sizes

    def _pack_twostreams(self, ev: "_ChunkEvents"):
        long = ev.n >= PROTOCOL_MIN_SEG["twostreams"]
        self._check_cap(ev.n, long)
        sizes = np.where(long, 25, 0)
        offs, total = _excl_offsets(sizes)
        seg = np.zeros(total, np.uint8)
        kl = np.flatnonzero(long)
        _put_f64(seg, offs[kl], self._t(ev.prev[kl] + 1))
        seg[offs[kl] + 8] = (ev.n[kl] - 1).astype(np.uint8)
        _put_f64(seg, offs[kl] + 9, ev.As[kl])
        _put_f64(seg, offs[kl] + 17, ev.Bs[kl])
        sh = ~long
        ssizes = np.where(sh, 8 * ev.n, 0)
        soffs, stotal = _excl_offsets(ssizes)
        single = np.zeros(stotal, np.uint8)
        vals, within = self._gather_runs(ev.ss[sh], ev.prev[sh] + 1,
                                         ev.n[sh])
        _put_f64(single, np.repeat(soffs[sh], ev.n[sh]) + 8 * within, vals)
        return (seg, sizes), (single, ssizes)

    def _pack_singlestream(self, ev: "_ChunkEvents"):
        long = ev.n >= PROTOCOL_MIN_SEG["singlestream"]
        self._check_cap(ev.n, long)
        sizes = np.where(long, 17, 9 * ev.n)
        offs, total = _excl_offsets(sizes)
        buf = np.zeros(total, np.uint8)
        kl = np.flatnonzero(long)
        buf[offs[kl]] = (ev.n[kl] - 1).astype(np.uint8)
        _put_f64(buf, offs[kl] + 1, ev.As[kl])
        _put_f64(buf, offs[kl] + 9, ev.Bs[kl])
        sh = ~long                    # n x (0x00, value) 9-byte records
        vals, within = self._gather_runs(ev.ss[sh], ev.prev[sh] + 1,
                                         ev.n[sh])
        _put_f64(buf, np.repeat(offs[sh], ev.n[sh]) + 9 * within + 1, vals)
        return buf, sizes

    def _pack_singlestreamv(self, ev: "_ChunkEvents"):
        """Bursts as a segmented cumulative sum over the chunk's events.

        The pending-burst fill is a per-stream running count of short-
        segment points that resets at long segments (which flush the
        remainder) and wraps at ``burst_cap`` (full bursts flush eagerly)
        — i.e. ``pending_before = raw % cap`` where ``raw`` counts
        singletons since the last long segment (seeded with the carried
        fill).  Full bursts emitted by an event are the ``cap`` floor
        crossings between its before/after raw counts; burst payloads are
        contiguous positions, so one :meth:`_gather_runs` fetches them
        all.
        """
        cap = self.burst_cap
        long = ev.n >= PROTOCOL_MIN_SEG["singlestreamv"]
        self._check_cap(ev.n, long)
        N = len(ev.ss)
        idx = np.arange(N)
        gfirst = np.repeat(ev.gstart, ev.counts)
        addn = np.where(long, 0, ev.n).astype(np.int64)
        cs = np.cumsum(addn)
        cs0 = cs - addn
        lastlong = np.empty(N, np.int64)   # last long event STRICTLY before
        lastlong[0] = -1
        lastlong[1:] = np.maximum.accumulate(np.where(long, idx, -1))[:-1]
        valid = lastlong >= gfirst    # a long event earlier in this group
        ll = np.clip(lastlong, 0, None)
        reset_cs = np.where(valid, cs[ll], np.repeat(cs0[ev.gstart],
                                                     ev.counts))
        raw0 = cs0 - reset_cs + np.where(valid, 0, self._pend_len[ev.ss])
        raw1 = raw0 + addn
        origin = np.where(valid, ev.es[ll] + 1, self._pend_start[ev.ss])
        nfull = np.where(long, 0, raw1 // cap - raw0 // cap)
        plen = np.where(long, raw0 % cap, 0)
        sizes = np.where(long,
                         np.where(plen > 0, 1 + 8 * plen, 0) + 17,
                         nfull * (1 + 8 * cap))
        offs, total = _excl_offsets(sizes)
        buf = np.zeros(total, np.uint8)
        kl = np.flatnonzero(long)     # segment records (after the partial)
        roffs = offs[kl] + np.where(plen[kl] > 0, 1 + 8 * plen[kl], 0)
        buf[roffs] = ev.n[kl].astype(np.int8).view(np.uint8)
        _put_f64(buf, roffs + 1, ev.As[kl])
        _put_f64(buf, roffs + 9, ev.Bs[kl])
        # Enumerate emitted bursts: cap-filled ones at short events plus
        # the flushed partial at each long event.
        src = np.flatnonzero((nfull > 0) | (long & (plen > 0)))
        bcount = np.where(long, (plen > 0).astype(np.int64), nfull)[src]
        b_ev = np.repeat(src, bcount)
        b_j = np.arange(len(b_ev)) - np.repeat(np.cumsum(bcount) - bcount,
                                               bcount)
        partial = long[b_ev]
        b_len = np.where(partial, plen[b_ev], cap)
        b_start = origin[b_ev] \
            + (raw0[b_ev] // cap + np.where(partial, 0, b_j)) * cap
        b_off = offs[b_ev] + np.where(partial, 0, b_j * (1 + 8 * cap))
        buf[b_off] = (-b_len).astype(np.int8).view(np.uint8)
        vals, within = self._gather_runs(ev.ss[b_ev], b_start, b_len)
        _put_f64(buf, np.repeat(b_off + 1, b_len) + 8 * within, vals)
        # Pending window after the chunk, per stream with events.
        gl, gs = ev.glast, ev.ss[ev.gstart]
        last_long = long[gl]
        self._pend_len[gs] = np.where(last_long, 0, raw1[gl] % cap)
        self._pend_start[gs] = np.where(
            last_long, ev.es[gl] + 1,
            origin[gl] + (raw1[gl] // cap) * cap)
        return buf, sizes

    # -- public API ---------------------------------------------------------

    def step_chunk(self, events: Optional[SegmentOutput] = None,
                   y_chunk=None) -> List:
        """Consume new event columns / value columns; return new bytes."""
        if self._finished:
            raise RuntimeError("step_chunk after flush()")
        found = self._take(events, y_chunk)
        if found is None:
            empty = [b""] * self.n_streams
            return [(b, b"") for b in empty] \
                if self.protocol == "twostreams" else empty
        ev = self._event_geometry(*found)
        p = self.protocol
        if p == "implicit":
            packed = self._pack_implicit(ev)
        elif p == "twostreams":
            packed = self._pack_twostreams(ev)
        elif p == "singlestream":
            packed = self._pack_singlestream(ev)
        else:
            packed = self._pack_singlestreamv(ev)
        # Carry the per-stream codec state past the chunk.
        gs = ev.ss[ev.gstart]
        self._k[gs] += ev.counts
        self._prev_end[gs] = ev.es[ev.glast]
        self._prev_A[gs] = ev.As[ev.glast]
        self._prev_B[gs] = ev.Bs[ev.glast]
        if p != "singlestreamv":      # its packer manages the burst window
            self._pend_start[gs] = ev.es[ev.glast] + 1
        self._trim()
        if p == "twostreams":
            (seg, sizes), (single, ssizes) = packed
            return list(zip(self._per_stream(seg, sizes, ev.ss),
                            self._per_stream(single, ssizes, ev.ss)))
        buf, sizes = packed
        return self._per_stream(buf, sizes, ev.ss)

    def _take(self, events: Optional[SegmentOutput], y_chunk):
        """Buffer the value columns and advance the shared column clock
        past the event columns; the chunk's events as ``(ss, es, a, v)``,
        or None where it has none."""
        if y_chunk is not None:
            y = np.asarray(y_chunk, np.float64)
            if y.ndim != 2 or y.shape[0] != self.n_streams:
                raise ValueError(f"y_chunk must be ({self.n_streams}, n); "
                                 f"got {y.shape}")
            self._ybuf = np.concatenate([self._ybuf, y], axis=1)
        if events is not None and events.breaks.shape[0] != self.n_streams:
            raise ValueError(f"events must cover ({self.n_streams}, w) "
                             f"streams; got {events.breaks.shape}")
        if events is None or not events.breaks.shape[1]:
            return None
        brk = np.asarray(events.breaks, bool)
        ss, jj = np.nonzero(brk)      # row-major: stream-major, time-sorted
        es = self._epos + jj.astype(np.int64)
        self._epos += brk.shape[1]
        if not len(ss):
            self._trim()
            return None
        return (ss, es, np.asarray(events.a, np.float64)[ss, jj],
                np.asarray(events.v, np.float64)[ss, jj])

    def flush(self) -> List:
        """Close the stream: trailing bursts and the closing knot."""
        if self._finished:
            raise RuntimeError("flush() called twice")
        self._finished = True
        return self._close(np.ones(self.n_streams, bool))

    def _close(self, rows: np.ndarray) -> List:
        """The closing bytes of the streams in the ``(S,)`` mask ``rows``
        (b"" for the others): trailing bursts and the closing knot."""
        outs = [b""] * self.n_streams
        if self.protocol == "singlestreamv":
            act = np.flatnonzero(rows & (self._pend_len > 0))
            if len(act):
                lens = self._pend_len[act]
                sizes = 1 + 8 * lens
                offs, total = _excl_offsets(sizes)
                buf = np.zeros(total, np.uint8)
                buf[offs] = (-lens).astype(np.int8).view(np.uint8)
                vals, within = self._gather_runs(act, self._pend_start[act],
                                                 lens)
                _put_f64(buf, np.repeat(offs + 1, lens) + 8 * within, vals)
                ends = np.cumsum(sizes)
                for i, s in enumerate(act.tolist()):
                    outs[s] = buf[ends[i] - sizes[i]:ends[i]].tobytes()
                self._pend_start[act] += lens
                self._pend_len[act] = 0
        elif self.protocol == "implicit" \
                and self.knot_kind in ("disjoint", "mixed"):
            act = np.flatnonzero(rows & (self._k > 0))
            if len(act):
                pw = self._has_y2[act] if self.knot_kind == "mixed" \
                    else np.zeros(len(act), bool)
                sizes = np.where(pw, 24, 16)
                offs, total = _excl_offsets(sizes)
                buf = np.zeros(total, np.uint8)
                _put_f64(buf, offs[pw], self._pend_y2[act][pw])
                te = self._t(self._prev_end[act])
                _put_f64(buf, offs + 8 * pw, te)
                _put_f64(buf, offs + 8 * pw + 8,
                         self._prev_A[act] * te + self._prev_B[act])
                ends = np.cumsum(sizes)
                for i, s in enumerate(act.tolist()):
                    outs[s] = buf[ends[i] - sizes[i]:ends[i]].tobytes()
                self._has_y2[act] = False
        if self.protocol == "twostreams":
            return [(o, b"") for o in outs]
        return outs


class SlotPlaneEmitter(ProtocolEmitter):
    """A :class:`ProtocolEmitter` over the rows of one slot-plane shard,
    where every row is a stream with a clock of its own.

    The serving plane (:class:`repro.serving.slots.SlotManager`) admits
    and evicts streams row by row and feeds each row its own number of
    points a tick, so its rows share no column clock.  ``step_chunk``
    takes the shard's :class:`~repro.core.jax_pla.MaskedEvents` as
    :func:`~repro.core.jax_pla.masked_step_chunk` returns them (events
    tagged with row-local positions) and, as ``y_chunk``, the tick's
    ``(plane, lengths)``: row ``r`` takes ``plane[r, :lengths[r]]``.
    ``points[r]`` counts the values row ``r`` took since its reset.

    Values wait in a per-row ring, position ``p`` at ``p % W``.  No record
    reaches back from its break past a ``max_run``-point segment and the
    partial burst before it (under ``burst_cap`` values), and a plane of
    width ``n`` finalizes breaks at most ``n + 1`` positions behind the
    row's newest value, so ``W`` is ``max_run + burst_cap`` plus the
    widest plane taken so far.

    ``reset_rows`` starts rows afresh (admission); ``flush_rows`` closes
    rows and returns their closing bytes (eviction).  Each row's bytes
    equal those of a one-stream :class:`ProtocolEmitter` fed that row's
    stream alone.
    """

    def __init__(self, protocol: str, n_rows: int, *, max_run: int,
                 knot_kind: str = "disjoint", burst_cap: int = 127):
        self._init_codec(protocol, n_rows, knot_kind, 0.0, 1.0, burst_cap)
        self._reach = max_run + burst_cap
        self.points = np.zeros(n_rows, np.int64)
        self._ring = np.zeros((n_rows, 0), np.float64)

    def reset_rows(self, rows) -> None:
        """Start the rows of the ``(R,)`` mask ``rows`` as fresh streams."""
        rows = np.asarray(rows, bool)
        for state in (self._k, self._pend_start, self._pend_len,
                      self.points, self._prev_A, self._prev_B,
                      self._pend_y2, self._has_y2):
            state[rows] = 0
        self._prev_end[rows] = -1

    def flush_rows(self, rows) -> List:
        """Close the rows of the ``(R,)`` mask ``rows``: their closing
        bytes, in row order."""
        rows = np.asarray(rows, bool)
        outs = self._close(rows)
        return [outs[r] for r in np.flatnonzero(rows)]

    def _take(self, events, y_chunk):
        if y_chunk is not None:
            self._push(*y_chunk)
        if events is None:
            return None
        ss, jj = np.nonzero(np.asarray(events.ev, bool))
        if not len(ss):
            return None
        es = np.asarray(events.pos, np.int64)[ss, jj]
        if ((es <= self._prev_end[ss]) | (es >= self.points[ss])).any():
            raise ValueError("an event lies at or before its row's last "
                             "break, or at or past its newest value")
        return (ss, es, np.asarray(events.a, np.float64)[ss, jj],
                np.asarray(events.v, np.float64)[ss, jj])

    def _push(self, plane, lengths) -> None:
        """Write each row's valid prefix into the ring."""
        plane = np.asarray(plane)
        lengths = np.asarray(lengths, np.int64)
        if plane.ndim != 2 or plane.shape[0] != self.n_streams \
                or lengths.shape != (self.n_streams,):
            raise ValueError(f"plane must be ({self.n_streams}, n) and "
                             f"lengths ({self.n_streams},); got "
                             f"{plane.shape} and {lengths.shape}")
        if self._reach + plane.shape[1] > self._ring.shape[1]:
            self._grow(self._reach + plane.shape[1])
        rr, jj = np.nonzero(np.arange(plane.shape[1]) < lengths[:, None])
        W = self._ring.shape[1]
        self._ring[rr, (self.points[rr] + jj) % W] = plane[rr, jj]
        self.points += lengths

    def _grow(self, W: int) -> None:
        """Widen the ring to ``W`` positions, keeping what it holds."""
        old = self._ring.shape[1]
        ring = np.zeros((self.n_streams, W), np.float64)
        if old:
            p = self.points[:, None] - old + np.arange(old)
            rr, jj = np.nonzero(p >= 0)
            ring[rr, p[rr, jj] % W] = self._ring[rr, p[rr, jj] % old]
        self._ring = ring

    def _gather_runs(self, rows, lo, lens):
        lens = np.asarray(lens, np.int64)
        if not lens.sum():
            return np.empty(0, np.float64), np.empty(0, np.int64)
        lo = np.asarray(lo, np.int64)
        hi = self.points[rows]
        W = self._ring.shape[1]
        within = _run_offsets(lo, lens, np.maximum(hi - W, 0), hi)
        vals = self._ring[np.repeat(rows, lens),
                          (np.repeat(lo, lens) + within) % W]
        return vals, within

    def _trim(self) -> None:
        """Nothing to drop: the ring overwrites what no record reaches."""


class _ChunkEvents(NamedTuple):
    """One chunk's finalized events, flat and stream-major, with the
    cross-event codec geometry already resolved (see
    :meth:`ProtocolEmitter._event_geometry`)."""

    ss: np.ndarray      # (N,) stream index per event
    es: np.ndarray      # (N,) absolute break position
    prev: np.ndarray    # (N,) previous break position (-1 for none)
    n: np.ndarray       # (N,) segment length es - prev
    As: np.ndarray      # (N,) global-line slope
    Bs: np.ndarray      # (N,) global-line intercept
    pA: np.ndarray      # (N,) previous segment's line
    pB: np.ndarray      # (N,)
    k_ev: np.ndarray    # (N,) segment ordinal within the stream
    gstart: np.ndarray  # (G,) index of each stream's first event
    glast: np.ndarray   # (G,) index of each stream's last event
    counts: np.ndarray  # (G,) events per stream


def _run_offsets(lo, lens, have_lo, have_hi) -> np.ndarray:
    """Each value's index inside its run ``[lo, lo + lens)``, once every
    run is checked to lie inside the held values ``[have_lo, have_hi)``
    (scalars, or one bound per run)."""
    bad = (lo < have_lo) | (lo + lens > have_hi)
    if bad.any():
        b = int(np.flatnonzero(bad)[0])
        held_lo, held_hi = (int(np.broadcast_to(h, lo.shape)[b])
                            for h in (have_lo, have_hi))
        raise ValueError(
            f"record needs values [{int(lo[b])}, {int(lo[b] + lens[b])})"
            f" but only [{held_lo}, {held_hi}) are held — pass values no "
            f"later than their events")
    return np.arange(int(lens.sum()), dtype=np.int64) \
        - np.repeat(np.cumsum(lens) - lens, lens)


def _excl_offsets(sizes: np.ndarray):
    """Exclusive cumsum byte offsets for variable-size records."""
    sizes = sizes.astype(np.int64)
    return np.cumsum(sizes) - sizes, int(sizes.sum())
