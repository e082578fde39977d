"""Vectorized (batched) streaming PLA in pure JAX.

This is the TPU-native adaptation of the paper's sequential algorithms
(DESIGN.md §3): the parallel axis is *streams* (S independent rows), time is
walked by ``jax.lax.scan``, and the dynamic convex hulls are replaced by
exact bounded-window vector reductions (the paper's own protocols cap
segments at 256 points, so the current segment always fits a window).

All six Table-2 segmenters:

- :func:`angle_segment`    — O(1)-state greedy (Angle, §3.1)
- :func:`swing_segment`    — O(1)-state greedy, joint knots (SwingFilter)
- :func:`disjoint_segment` — optimal greedy (ConvexHull, §3.2) with the
  hull replaced by an exact masked argmin/argmax over the run window
- :func:`linear_segment`   — best-fit line (Linear, §3.5) with window
  revalidation instead of hull checks
- :func:`continuous_segment` — connected polyline (§3.3): a *gate*
  interval + run fitter with the knot choice deferred one segment
- :func:`mixed_segment`    — MixedPLA (§3.4): disjoint stage-1 runs with
  a joint-merge decision one run behind the frontier

The last two are **deferred** (``DEFERRED_METHODS``): a break finalizes a
segment one knot in the past, so their scan emits position-tagged events
``(ev, pos, a, v)`` that the wrappers scatter into the canonical event
arrays, and their chunked output has data-dependent width (below).

All take ``y: (S, T)`` on the regular grid ``t = 0..T-1`` (the fleet's
sensor streams and host telemetry are index-stamped)
and return dense, shape-static output:

- ``breaks: (S, T) bool`` — True where a segment *ends* (last covered t)
- ``a, v:   (S, T) f32``  — the segment's line as (slope, value at the
  break position).  The *anchored* form ``y(t) = v + a*(t - t_break)``
  keeps float32 exact for streams as long as 2^24 (global-intercept form
  ``a*t + b`` loses ~|a|*t*2^-24 to cancellation — fatal at T=500k).

Streaming (chunked) API
-----------------------

Every segmenter is built from an explicit ``(init, step, flush)`` carry
triple, and that carry is public: a stream may be pushed in chunks of any
size with output **bit-identical** to the one-shot offline call.

- :func:`init_state` — make a fresh :class:`SegmenterState` for ``S``
  streams (no data consumed yet; the carry materializes on the first chunk).
- :func:`step_chunk` — consume ``y_chunk: (S, n)`` (any ``n >= 1``,
  including 1) and return the *newly finalized* event columns: processing
  absolute time ``t`` can only decide that a segment ended at ``t - 1``, so
  a chunk covering positions ``[t0, t0+n)`` finalizes positions
  ``[t0-1, t0+n-1)`` (the very first chunk of a stream finalizes one column
  fewer — position ``-1`` does not exist).
- :func:`flush` — close the trailing run: emits the single final event
  column (a forced break at the last consumed position) and resets the
  carry, so the next :func:`step_chunk` starts a fresh stream at the next
  absolute position (used by the adaptive-ε controller's retune
  boundaries).

Concatenating all :func:`step_chunk` outputs plus the :func:`flush` column
reproduces the offline ``(S, T)`` :class:`SegmentOutput` exactly.  Offline
functions are thin wrappers over one full-length chunk of the same
building blocks, so the equality is structural, not coincidental.

For the deferred methods (``continuous`` / ``mixed``) the same
concatenation guarantee holds, but each :func:`step_chunk` returns a
**data-dependent** number of columns (possibly zero): an event can only
be released once no future break may target its position (the last fixed
knot bounds that frontier), so finalized columns are buffered host-side
and ``flush`` releases the remainder.  Widths differ, positions do not:
output column ``j`` of the concatenation is always absolute position
``j``.
Chunk boundaries are host-side (Python) decisions; the per-chunk work is a
single jitted ``lax.scan`` whose absolute-time offset is a traced scalar —
pushing many chunks does not retrace (one trace per distinct chunk width).
``eps`` is traced as well, so per-chunk ε retuning is recompile-free.
Caveat: the reference segmenters walk *absolute* time (``disjoint`` /
``linear`` cast positions to float32 before differencing), so a single
:class:`SegmenterState` supports streams up to ``MAX_STREAM_T = 2^24``
points over its lifetime — :func:`step_chunk` raises past that (flush
does **not** rebase; start a fresh state to rebase time).  The Pallas
kernels (:mod:`repro.kernels`) renumber time per launch and have no such
limit.

:func:`propagate_lines` turns segments into per-point reconstruction;
:func:`to_records` / :func:`decode_records` give the fixed-slot record form
used by the tests as a reference record layout.
All internal line state is likewise anchored at the current run's start, so
t enters only through differences bounded by the run cap.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "SegmentOutput", "angle_segment", "disjoint_segment", "linear_segment",
    "swing_segment", "continuous_segment", "mixed_segment",
    "disjoint_segment_windowed", "linear_segment_windowed",
    "SegmenterState", "init_state", "step_chunk", "flush",
    "STREAMING_METHODS", "DEFERRED_METHODS", "MAX_STREAM_T", "check_window",
    "mixed_ring",
    "MaskedEvents", "MaskedSegmenterState", "masked_init_state",
    "masked_step_chunk", "masked_flush_rows", "masked_set_eps",
    "propagate_lines", "to_records", "decode_records", "records_to_events",
    "scatter_events", "release_deferred", "assemble_deferred_events",
    "PLARecords",
]

_BIG = jnp.float32(3.4e38)

# Per-method lax.scan unroll for the segmenter scans.  Unrolled group
# bodies let XLA fuse arithmetic across steps, and that fusion depends on
# the trace's scan length and the step's position within its group —
# ulp-level differences that can break the chunked==offline
# bit-transparency guarantee.  The wedge methods keep the running wedge
# in carried slots XLA cannot re-associate across steps, so they stay
# bit-transparent when unrolled (test_streaming verifies at odd splits);
# continuous does NOT — any unroll > 1 fails test_streaming — so the
# deferred methods (and anything unlisted) MUST stay 1.  Factors are
# measured at the bench shape (S=256, T=16k): angle gains ~10% at 2 on
# one long scan and regresses past that; swing regresses at any unroll.
# Short scans (chunked pushes) lose up to ~40% to the unrolled body's
# extra code size, so the factor only kicks in past a length floor —
# the trace is keyed by scan length anyway, so this costs no retraces.
_SCAN_UNROLL = {"angle": 2}
_UNROLL_MIN_T = 4096


def _scan_unroll(method: str, n: int) -> int:
    return _SCAN_UNROLL.get(method, 1) if n >= _UNROLL_MIN_T else 1

# The jnp reference segmenters walk *absolute* time (the windowed methods
# cast positions to float32 before differencing), so a single
# SegmenterState supports at most 2^24 points over its lifetime — flush()
# deliberately does not rebase, because callers use state.t/state.emitted
# as absolute record positions across flushes.  step_chunk enforces the
# limit with a clear error; the Pallas kernels renumber time per launch
# and have no such limit.
MAX_STREAM_T = 1 << 24


class SegmentOutput(NamedTuple):
    breaks: jax.Array  # (S, T) bool — segment ends here
    a: jax.Array       # (S, T) — slope, valid at break positions
    v: jax.Array       # (S, T) — line value AT the break position


# ---------------------------------------------------------------------------
# Algorithm building blocks
#
# Each method is an (init, step, flush) triple over a per-stream carry
# pytree.  The offline segmenters below and the chunked streaming API share
# these functions verbatim, which is what makes chunked == offline bitwise.
#
#   init(y0, eps, max_run, window, t0) -> carry     (consumes the 1st point)
#   step(eps, max_run, window, carry, (t, y_t))
#       -> (carry, (brk, a, v))                     (event for position t-1)
#   flush(carry, t_last) -> (a_f, v_f)              (trailing-run line)
#   flush(eps, max_run, window, carry, t_last)      (deferred methods)
# ---------------------------------------------------------------------------


class _MethodImpl(NamedTuple):
    init: Callable
    step: Callable
    flush: Callable
    int_ts: bool      # scan times as int32 (ring methods) vs value dtype
    windowed: bool    # takes a window parameter
    deferred: bool = False  # emits (ev, pos, a, v) events at past positions


# ---- Angle: O(1) state per stream -----------------------------------------

def _angle_init(y0, eps, max_run, window, t0):
    S = y0.shape[0]
    dtype = y0.dtype
    return (
        jnp.zeros((S,), jnp.int32),          # phase
        y0,                                  # p0y
        jnp.zeros((S,), dtype),              # od (origin offset)
        jnp.zeros((S,), dtype),              # oy
        jnp.full((S,), -_BIG, dtype), jnp.full((S,), _BIG, dtype),
        jnp.ones((S,), jnp.int32),           # run_len
    )


def _angle_step(eps, max_run, window, state, inp):
    (phase, p0y, od, oy, slo, shi, run_len) = state
    # ``od`` = origin position relative to the *current* step t:
    # origin_t = t - od (od grows by 1 each step).
    t, yt = inp
    S = yt.shape[0]
    dtype = yt.dtype
    t = jnp.broadcast_to(t, (S,)).astype(dtype)

    # Phase 0 -> 1: origin from p0 = (t-1, p0y) and this error segment,
    # all in origin-relative coordinates (p0 at offset 0, t at +1).
    amax = (yt + eps) - (p0y - eps)
    amin = (yt - eps) - (p0y + eps)
    # Extreme lines in the relative frame: max-slope through (0, p0y-e)
    # and (1, y+e); min-slope through (0, p0y+e) and (1, y-e).  Their
    # crossing: x = 2*eps / (amax - amin) with value amax*x + p0y - eps.
    da = amax - amin
    das = jnp.where(jnp.abs(da) < 1e-30, 1.0, da)
    ox_rel = jnp.where(jnp.abs(da) < 1e-30, 0.5, 2.0 * eps / das)
    oy_new = amax * ox_rel + (p0y - eps)
    od_new0 = 1.0 - ox_rel   # distance from origin to current t

    # Phase 1: wedge update (origin at t - od).
    dt = od
    dts = jnp.where(dt == 0, 1.0, dt)
    n1 = (yt - eps - oy) / dts
    n2 = (yt + eps - oy) / dts
    nlo = jnp.minimum(n1, n2)
    nhi = jnp.maximum(n1, n2)
    t_slo = jnp.maximum(slo, nlo)
    t_shi = jnp.minimum(shi, nhi)
    feasible = t_slo <= t_shi
    cap_hit = run_len >= max_run
    brk = (phase == 1) & (~feasible | cap_hit)

    # Finalized segment line, anchored at the break position (t-1).
    a_out = jnp.where(phase == 1, 0.5 * (slo + shi), 0.0)
    v_out = jnp.where(phase == 1, oy + a_out * (od - 1.0), p0y)

    new_phase = jnp.where(brk, 0, 1).astype(jnp.int32)
    new_p0y = jnp.where(brk, yt, p0y)
    go0 = (phase == 0) & ~brk
    new_od = jnp.where(go0, od_new0 + 1.0, jnp.where(brk, 0.0, od + 1.0))
    new_oy = jnp.where(go0, oy_new, oy)
    new_slo = jnp.where(go0, amin, jnp.where(brk, -_BIG, t_slo))
    new_shi = jnp.where(go0, amax, jnp.where(brk, _BIG, t_shi))
    new_run_len = jnp.where(brk, 1, run_len + 1)
    new_state = (new_phase, new_p0y, new_od, new_oy,
                 new_slo, new_shi, new_run_len)
    return new_state, (brk, a_out, v_out)


def _angle_flush(carry, t_last):
    # ``od`` is pre-incremented at commit time (it holds the origin distance
    # for the *next* step), so the distance from the origin to the last
    # consumed position is od - 1.
    (phase, p0y, od, oy, slo, shi, _) = carry
    a_f = jnp.where(phase == 0, 0.0, 0.5 * (slo + shi))
    v_f = jnp.where(phase == 0, p0y, oy + a_f * (od - 1.0))
    return a_f, v_f


# ---- SwingFilter: O(1) state, joint knots ---------------------------------

def _swing_init(y0, eps, max_run, window, t0):
    S = y0.shape[0]
    dtype = y0.dtype
    return (jnp.ones((S,), dtype),            # od: origin at t0, next t=1
            y0,                               # oy = y0 (exact first origin)
            jnp.full((S,), -_BIG, dtype), jnp.full((S,), _BIG, dtype),
            jnp.ones((S,), jnp.int32))


def _swing_step(eps, max_run, window, state, inp):
    (od, oy, slo, shi, run_len) = state
    # origin sits od steps behind the current t
    t, yt = inp
    dts = jnp.where(od == 0, 1.0, od)
    n1 = (yt - eps - oy) / dts
    n2 = (yt + eps - oy) / dts
    nlo = jnp.minimum(n1, n2)
    nhi = jnp.maximum(n1, n2)
    t_slo = jnp.maximum(slo, nlo)
    t_shi = jnp.minimum(shi, nhi)
    feasible = t_slo <= t_shi
    cap_hit = run_len >= max_run
    brk = ~feasible | cap_hit

    a_out = 0.5 * (slo + shi)
    v_out = oy + a_out * (od - 1.0)   # knot at t-1 (on the old line)

    # on break: new origin = the knot (t-1, v_out); re-add this point.
    b_lo = (yt - eps - v_out)          # dt == 1 from the new origin
    b_hi = (yt + eps - v_out)
    new_od = jnp.where(brk, 1.0, od) + 1.0
    new_oy = jnp.where(brk, v_out, oy)
    new_slo = jnp.where(brk, jnp.minimum(b_lo, b_hi), t_slo)
    new_shi = jnp.where(brk, jnp.maximum(b_lo, b_hi), t_shi)
    new_run_len = jnp.where(brk, 1, run_len + 1)
    return (new_od, new_oy, new_slo, new_shi, new_run_len), \
        (brk, a_out, v_out)


def _swing_flush(carry, t_last):
    (od, oy, slo, shi, run_len) = carry
    a_f = jnp.where(jnp.isfinite(slo) & jnp.isfinite(shi) & (run_len > 0),
                    0.5 * (slo + shi), 0.0)
    a_f = jnp.where(run_len >= 1, a_f, 0.0)
    v_f = oy + a_f * (od - 1.0)
    return a_f, v_f


# ---- Convex-chain primitives (amortized O(1) hull carries) ----------------
#
# The windowed disjoint/linear steps below (``*_windowed``) retighten with
# an O(W) masked reduction per point.  The default steps replace that with
# the paper's amortized-O(1) structure (O'Rourke / SlideFilter; see also
# arXiv 2503.23025): per-stream monotone convex chains stored as (S, W)
# position/value planes plus an int32 length, popped at the tail with the
# exact ``hulls._HullChain.add`` cross tests, and queried by a *tangent
# walk* from a carried contact hint (the slope sequence from an external
# query point to successive chain vertices is unimodal, so the walk finds
# the extremum; the hint makes it amortized O(1) because the contact
# drifts slowly).  Slope/value expressions are kept identical to the
# windowed reference, so equal pivots give bit-identical lines; pivot
# choice can differ from the windowed argmin only by fp ulps on the slope
# comparisons (the documented fp-tolerance pin — break positions are
# pinned equal in tests/test_streaming_property.py).


def _chain_slot_dtype(window: int):
    """Slot-index dtype for chain planes (u8 keeps the carry tiny)."""
    return jnp.uint8 if window <= 256 else jnp.int32


_CHAIN_CAP = 16  # chain capacity: hulls of realistic runs are ~log-sized


def _chain_cap(window: int) -> int:
    return min(_CHAIN_CAP, window)


def _chain_planes(ring, idx, t_i, window, value_of):
    """Vertex coordinate planes of a slot-index chain over a time ring.

    ``ring (S, W)`` holds raw point values keyed by ``t mod W``; ``idx``
    ``(S, C)`` holds ring slots in chain order (C = ``_chain_cap`` —
    convex chains of realistic runs are ~log-sized, and a run whose hull
    outgrows C flips the lane into exact windowed mode, see the step
    functions).  Returns ``(S, C)`` planes ``(qx, qy)``: the vertex time
    reconstructed from the slot's age ``(t_i - slot) mod W`` (exact —
    run length <= W and ``t < 2**24``) and the value put through
    ``value_of`` (e.g. ``y -+ eps``), reproducing the exact f32
    coordinates the windowed reference computes from its own value ring.
    Columns past the chain length hold garbage; callers mask.

    The ring/index split exists for throughput, not elegance: the chains
    carry *no* f32 payload, so the scan's only scatter-written carried
    plane is the ring — written once per step *before* any read, which
    lets XLA update it in place.  (Any pre-update read of a
    scatter-written carried plane forces a full copy-on-write of the
    plane per scan step — measured at ~15us per (256, 256) plane, many
    times the cost of the rest of the step.)
    """
    sl = idx.astype(jnp.int32)
    tc = t_i[:, None] if jnp.ndim(t_i) else t_i  # per-row time: (S, 1)
    qx = (tc - jnp.mod(tc - sl, window)).astype(ring.dtype)
    return qx, value_of(jnp.take_along_axis(ring, sl, axis=1))


def _ring_write(ring, slot, yt):
    """Scatter ``yt`` into per-stream ring ``slot`` — scalar slot (lockstep
    time) or ``(S,)`` slots (per-row time, the masked serving engine)."""
    if jnp.ndim(slot):
        return ring.at[jnp.arange(ring.shape[0]), slot].set(yt)
    return ring.at[:, slot].set(yt)


def _window_positions(t_i, window):
    """Absolute positions of the ``window`` ring entries ending at
    ``t_i - 1``, as a 2-D plane: ``(1, W)`` for scalar ``t_i`` (lockstep)
    or ``(S, W)`` for per-row time."""
    ar = jnp.arange(window)
    if jnp.ndim(t_i):
        return t_i[:, None] - 1 - ar[None, :]
    return (t_i - 1 - ar)[None, :]


def _chain_append(idx, ln, keep, px, py, qx, qy, slot, upper: bool):
    """Append the step's vertex ``(px, py)`` to per-stream convex chains.

    Tail pops are evaluated in closed form: popping stops at the first
    (largest) candidate length ``k`` whose tail cross test keeps the
    chain convex, so the post-pop length is ``max({1} | {k in [2, ln] :
    keep_k})`` — one masked integer max over the cross signs of every
    candidate ``k`` at once, reproducing the sequential pop loop of
    ``hulls._HullChain.add`` decision-for-decision (upper chains pop
    while the cross product is ``>= 0``, lower chains while ``<= 0``).
    The vertex value is already in the ring, so the append just records
    the ring ``slot`` — a small-plane ``where`` write, which XLA fuses
    elementwise instead of the copy-on-write a scatter on a carried
    plane would force.  ``keep=False`` rows reset their chain to the
    single new vertex (run restart).  An append past capacity C writes
    nothing and raises the overflow flag (the lane's hull no longer fits
    — the caller flips it to windowed mode).  Returns the updated
    ``(idx, len, overflow)``.
    """
    C = idx.shape[1]
    ox, oy = qx[:, :-1], qy[:, :-1]
    ax, ay = qx[:, 1:], qy[:, 1:]
    cr = (ax - ox) * (py[:, None] - oy) - (ay - oy) * (px[:, None] - ox)
    keep_k = (cr < 0) if upper else (cr > 0)
    karr = jnp.arange(2, C + 1, dtype=jnp.int32)[None, :]
    ln_kept = jnp.max(jnp.where(keep_k & (karr <= ln[:, None]), karr, 1),
                      axis=1)
    wp = jnp.where(keep, ln_kept, 0)
    overflow = keep & (wp >= C)
    col = jnp.arange(C, dtype=jnp.int32)[None, :]
    sc = slot[:, None] if jnp.ndim(slot) else slot  # per-row slot: (S, 1)
    idx = jnp.where(col == wp[:, None], sc.astype(idx.dtype), idx)
    return idx, jnp.minimum(wp + 1, C), overflow


def _chain_extremum(qx, qy, ln, slope_of, minimize: bool):
    """Masked extremum of ``slope_of(qx, qy)`` over chain vertices
    ``[0, ln)`` — the vectorized form of the hull tangent query (the
    extremum of a linear functional over a convex chain)."""
    s = slope_of(qx, qy)
    col = jnp.arange(qx.shape[1], dtype=jnp.int32)[None, :]
    member = col < ln[:, None]
    if minimize:
        return jnp.min(jnp.where(member, s, _BIG), axis=1)
    return jnp.max(jnp.where(member, s, -_BIG), axis=1)


# ---- Disjoint (optimal greedy): windowed reference --------------------------

def _disjoint_init_windowed(y0, eps, max_run, window, t0):
    S = y0.shape[0]
    dtype = y0.dtype
    W = window
    t0 = jnp.asarray(t0, jnp.int32)
    ybuf0 = jnp.zeros((S, W), dtype).at[:, t0 % W].set(y0)
    z = jnp.zeros((S,), dtype)
    return (ybuf0,
            jnp.full((S,), t0, jnp.int32),    # run_start (absolute pos)
            jnp.ones((S,), jnp.int32),        # run_len
            z, z, z, z,                       # extreme lines (a, v@rs)
            y0, y0)                           # prev_y, y0


def _disjoint_step_windowed(eps, max_run, window, state, inp):
    (ybuf, run_start, run_len, a_lo, v_lo, a_hi, v_hi, prev_y, y0) = state
    # lines anchored at run_start: line(t) = v + a * (t - run_start)
    W = window
    t_i, yt = inp
    S = yt.shape[0]
    dtype = yt.dtype
    t = jnp.broadcast_to(t_i, (S,)).astype(dtype)
    rs = run_start.astype(dtype)
    rel = t - rs

    lo_i, hi_i = yt - eps, yt + eps
    vmax = a_hi * rel + v_hi
    vmin = a_lo * rel + v_lo
    feas2 = (vmax >= lo_i) & (vmin <= hi_i)
    feasible = jnp.where(run_len >= 2, feas2, True)
    cap_hit = run_len >= max_run
    brk = ~feasible | cap_hit

    # Chosen line anchored at the break position (t-1): parameter-space
    # midpoint of the extreme lines (feasible by convexity).
    am = 0.5 * (a_lo + a_hi)
    vm = 0.5 * (v_lo + v_hi) + am * (rel - 1.0)
    a_out = jnp.where(run_len >= 2, am, 0.0)
    v_out = jnp.where(run_len >= 2, vm, prev_y)

    # ---- retightening over the run window -----------------------------
    abs_pos = t_i - 1 - jnp.arange(W)            # absolute positions
    pos = (abs_pos % W).astype(jnp.int32)
    in_run = (abs_pos >= run_start[:, None]) & (abs_pos >= 0)
    yw = jnp.take_along_axis(ybuf, jnp.broadcast_to(pos, (S, W)), axis=1)
    dtw = t[:, None] - abs_pos.astype(dtype)[None, :]
    dtw_safe = jnp.where(in_run, dtw, 1.0)

    need_hi = vmax > hi_i
    slopes_hi = (hi_i[:, None] - (yw - eps[:, None])) / dtw_safe
    slopes_hi = jnp.where(in_run, slopes_hi, _BIG)
    a_hi_new = jnp.min(slopes_hi, axis=1)
    v_hi_new = hi_i - a_hi_new * rel             # value at run_start
    a_hi_u = jnp.where(need_hi, a_hi_new, a_hi)
    v_hi_u = jnp.where(need_hi, v_hi_new, v_hi)

    need_lo = vmin < lo_i
    slopes_lo = (lo_i[:, None] - (yw + eps[:, None])) / dtw_safe
    slopes_lo = jnp.where(in_run, slopes_lo, -_BIG)
    a_lo_new = jnp.max(slopes_lo, axis=1)
    v_lo_new = lo_i - a_lo_new * rel
    a_lo_u = jnp.where(need_lo, a_lo_new, a_lo)
    v_lo_u = jnp.where(need_lo, v_lo_new, v_lo)

    # Second point of a run initializes the extreme lines.
    rel_s = jnp.maximum(rel, 1.0)
    a_hi_2 = (hi_i - (y0 - eps)) / rel_s
    v_hi_2 = y0 - eps
    a_lo_2 = (lo_i - (y0 + eps)) / rel_s
    v_lo_2 = y0 + eps

    second = run_len == 1
    a_hi_n = jnp.where(second, a_hi_2, a_hi_u)
    v_hi_n = jnp.where(second, v_hi_2, v_hi_u)
    a_lo_n = jnp.where(second, a_lo_2, a_lo_u)
    v_lo_n = jnp.where(second, v_lo_2, v_lo_u)

    # ---- commit --------------------------------------------------------
    new_run_start = jnp.where(brk, t_i, run_start)
    new_run_len = jnp.where(brk, 1, run_len + 1)
    ybuf_n = ybuf.at[:, (t_i % W).astype(jnp.int32)].set(yt)
    z = jnp.zeros_like(a_lo_n)
    new_state = (ybuf_n, new_run_start, new_run_len,
                 jnp.where(brk, z, a_lo_n), jnp.where(brk, z, v_lo_n),
                 jnp.where(brk, z, a_hi_n), jnp.where(brk, z, v_hi_n),
                 yt, jnp.where(brk, yt, y0))
    return new_state, (brk, a_out, v_out)


def _disjoint_flush_windowed(carry, t_last):
    (ybuf, run_start, run_len, a_lo, v_lo, a_hi, v_hi, prev_y, y0) = carry
    dtype = prev_y.dtype
    rel = jnp.asarray(t_last).astype(dtype) - run_start.astype(dtype)
    am = 0.5 * (a_lo + a_hi)
    a_f = jnp.where(run_len >= 2, am, 0.0)
    v_f = jnp.where(run_len >= 2, 0.5 * (v_lo + v_hi) + am * rel, prev_y)
    return a_f, v_f


# ---- Disjoint (optimal greedy): amortized hull carry (default) -------------
#
# Carry layout (the "hull carry"): the run's raw values live in one
# (S, W) f32 ring keyed by ``t mod W`` (written at the top of the step,
# before any read — see ``_chain_verts`` for why that ordering is the
# whole perf story), and the two convex chains are (S, W) u8 planes of
# ring-slot indices in chain order — ``hl`` is the *upper* chain of lower
# endpoints (t, y - eps) (the oracle's ``env_lo``, queried for a_hi),
# ``hh`` the *lower* chain of upper endpoints (t, y + eps) (``env_hi``,
# queried for a_lo) — plus int32 lengths and a per-lane windowed-mode
# flag.  Chains only ever pop at the tail, so the vertex prefix stays
# compact, and convex hulls of realistic runs are ~log-sized, so C
# columns suffice; pops and tangent queries are closed-form masked
# reductions over the small chain planes (no data-dependent loops).  A
# lane whose hull outgrows C (pathological near-convex data) flips to
# windowed mode until its next break: its retightening runs the *exact*
# windowed-reference reduction over the full ring inside a ``lax.cond``
# that never fires on benign streams.

def _disjoint_init(y0, eps, max_run, window, t0):
    S = y0.shape[0]
    dtype = y0.dtype
    W = window
    t0 = jnp.asarray(t0, jnp.int32)
    z = jnp.zeros((S,), dtype)
    one = jnp.ones((S,), jnp.int32)
    cdt = _chain_slot_dtype(W)
    slot0 = jnp.mod(t0, W)
    ring = jnp.zeros((S, W), dtype).at[:, slot0].set(y0)
    idx0 = jnp.zeros((S, _chain_cap(W)), cdt).at[:, 0].set(slot0.astype(cdt))
    return (jnp.full((S,), t0, jnp.int32),    # run_start (absolute pos)
            one,                              # run_len
            z, z, z, z,                       # extreme lines (a, v@rs)
            y0, y0,                           # prev_y, y0
            ring, idx0, idx0,                 # value ring + hl/hh chains
            one, one,                         # hl_len, hh_len
            jnp.zeros((S,), bool))            # windowed-mode flag


def _disjoint_step(eps, max_run, window, state, inp):
    (run_start, run_len, a_lo, v_lo, a_hi, v_hi, prev_y, y0,
     ring, hl_idx, hh_idx, hl_len, hh_len, wm) = state
    W = window
    t_i, yt = inp
    S = yt.shape[0]
    dtype = yt.dtype
    slot = jnp.mod(t_i, W)
    ring = _ring_write(ring, slot, yt)  # write FIRST: reads are post-update
    t = jnp.broadcast_to(t_i, (S,)).astype(dtype)
    rs = run_start.astype(dtype)
    rel = t - rs

    lo_i, hi_i = yt - eps, yt + eps
    vmax = a_hi * rel + v_hi
    vmin = a_lo * rel + v_lo
    feas2 = (vmax >= lo_i) & (vmin <= hi_i)
    feasible = jnp.where(run_len >= 2, feas2, True)
    cap_hit = run_len >= max_run
    brk = ~feasible | cap_hit

    # Chosen line anchored at the break position (t-1): parameter-space
    # midpoint of the extreme lines (feasible by convexity).
    am = 0.5 * (a_lo + a_hi)
    vm = 0.5 * (v_lo + v_hi) + am * (rel - 1.0)
    a_out = jnp.where(run_len >= 2, am, 0.0)
    v_out = jnp.where(run_len >= 2, vm, prev_y)

    second = run_len == 1

    # ---- tangent retightening (amortized O(1)) -------------------------
    # Slope expressions match the windowed reference bit-for-bit (chain
    # values store y -+ eps, reconstructed at read time exactly as a
    # push-time store would have).  Windowed-mode lanes (hull overflowed
    # chain capacity) get the exact windowed-reference reduction instead,
    # inside a cond that stays cold on benign data.
    hl_qx, hl_qy = _chain_planes(ring, hl_idx, t_i, W,
                                 lambda yv: yv - eps[:, None])
    hh_qx, hh_qy = _chain_planes(ring, hh_idx, t_i, W,
                                 lambda yv: yv + eps[:, None])

    a_hi_c = _chain_extremum(
        hl_qx, hl_qy, hl_len,
        lambda qx, qy: (hi_i[:, None] - qy) / (t[:, None] - qx),
        minimize=True)
    a_lo_c = _chain_extremum(
        hh_qx, hh_qy, hh_len,
        lambda qx, qy: (lo_i[:, None] - qy) / (t[:, None] - qx),
        minimize=False)

    def _windowed_retighten(_):
        abs_pos = _window_positions(t_i, W)
        pos = (abs_pos % W).astype(jnp.int32)
        in_run = (abs_pos >= run_start[:, None]) & (abs_pos >= 0)
        yw = jnp.take_along_axis(ring, jnp.broadcast_to(pos, (S, W)),
                                 axis=1)
        dtw = t[:, None] - abs_pos.astype(dtype)
        dtw_safe = jnp.where(in_run, dtw, 1.0)
        s_hi = jnp.where(in_run,
                         (hi_i[:, None] - (yw - eps[:, None])) / dtw_safe,
                         _BIG)
        s_lo = jnp.where(in_run,
                         (lo_i[:, None] - (yw + eps[:, None])) / dtw_safe,
                         -_BIG)
        return (jnp.where(wm, jnp.min(s_hi, axis=1), a_hi_c),
                jnp.where(wm, jnp.max(s_lo, axis=1), a_lo_c))

    a_hi_new, a_lo_new = jax.lax.cond(
        jnp.any(wm), _windowed_retighten, lambda _: (a_hi_c, a_lo_c), None)

    need_hi = vmax > hi_i
    act_hi = need_hi & ~second & ~brk
    v_hi_new = hi_i - a_hi_new * rel             # value at run_start
    a_hi_u = jnp.where(act_hi, a_hi_new, a_hi)
    v_hi_u = jnp.where(act_hi, v_hi_new, v_hi)

    need_lo = vmin < lo_i
    act_lo = need_lo & ~second & ~brk
    v_lo_new = lo_i - a_lo_new * rel
    a_lo_u = jnp.where(act_lo, a_lo_new, a_lo)
    v_lo_u = jnp.where(act_lo, v_lo_new, v_lo)

    # Second point of a run initializes the extreme lines.
    rel_s = jnp.maximum(rel, 1.0)
    a_hi_2 = (hi_i - (y0 - eps)) / rel_s
    v_hi_2 = y0 - eps
    a_lo_2 = (lo_i - (y0 + eps)) / rel_s
    v_lo_2 = y0 + eps

    a_hi_n = jnp.where(second, a_hi_2, a_hi_u)
    v_hi_n = jnp.where(second, v_hi_2, v_hi_u)
    a_lo_n = jnp.where(second, a_lo_2, a_lo_u)
    v_lo_n = jnp.where(second, v_lo_2, v_lo_u)

    # ---- commit --------------------------------------------------------
    new_run_start = jnp.where(brk, t_i, run_start)
    new_run_len = jnp.where(brk, 1, run_len + 1)
    keep = ~brk & ~wm
    hl_idx, hl_len, ov_hl = _chain_append(hl_idx, hl_len, keep, t, lo_i,
                                          hl_qx, hl_qy, slot, upper=True)
    hh_idx, hh_len, ov_hh = _chain_append(hh_idx, hh_len, keep, t, hi_i,
                                          hh_qx, hh_qy, slot, upper=False)
    new_wm = ~brk & (wm | ov_hl | ov_hh)
    z = jnp.zeros_like(a_lo_n)
    new_state = (new_run_start, new_run_len,
                 jnp.where(brk, z, a_lo_n), jnp.where(brk, z, v_lo_n),
                 jnp.where(brk, z, a_hi_n), jnp.where(brk, z, v_hi_n),
                 yt, jnp.where(brk, yt, y0),
                 ring, hl_idx, hh_idx, hl_len, hh_len, new_wm)
    return new_state, (brk, a_out, v_out)


def _disjoint_flush(carry, t_last):
    (run_start, run_len, a_lo, v_lo, a_hi, v_hi, prev_y, y0,
     *_rest) = carry
    dtype = prev_y.dtype
    rel = jnp.asarray(t_last).astype(dtype) - run_start.astype(dtype)
    am = 0.5 * (a_lo + a_hi)
    a_f = jnp.where(run_len >= 2, am, 0.0)
    v_f = jnp.where(run_len >= 2, 0.5 * (v_lo + v_hi) + am * rel, prev_y)
    return a_f, v_f


# ---- Linear (best-fit): windowed reference --------------------------------

def _linear_init_windowed(y0, eps, max_run, window, t0):
    S = y0.shape[0]
    dtype = y0.dtype
    W = window
    t0 = jnp.asarray(t0, jnp.int32)
    ybuf0 = jnp.zeros((S, W), dtype).at[:, t0 % W].set(y0)
    return (ybuf0,
            jnp.full((S,), t0, jnp.int32),
            jnp.ones((S,), dtype),                      # n
            jnp.zeros((S,), dtype), y0,                 # means (rel t, y)
            jnp.zeros((S,), dtype), jnp.zeros((S,), dtype),  # stt, sty
            jnp.zeros((S,), dtype), y0)                 # valid fit (0, y0)


def _linear_step_windowed(eps, max_run, window, state, inp):
    (ybuf, run_start, nn, mt, my, stt, sty, va, vv) = state
    # mt = mean of run-relative t; (va, vv) = last valid fit as
    # (slope, value at the previous point) — the break anchor.
    W = window
    t_i, yt = inp
    S = yt.shape[0]
    dtype = yt.dtype
    t = jnp.broadcast_to(t_i, (S,)).astype(dtype)
    rs = run_start.astype(dtype)
    rel = t - rs

    n1 = nn + 1.0
    d_t = rel - mt
    d_y = yt - my
    mt1 = mt + d_t / n1
    my1 = my + d_y / n1
    stt1 = stt + d_t * (rel - mt1)
    sty1 = sty + d_t * (yt - my1)
    a_fit = jnp.where(stt1 > 0, sty1 / jnp.where(stt1 > 0, stt1, 1.0), 0.0)
    b_fit = my1 - a_fit * mt1    # value at rel == 0 (run start)

    # Window revalidation.
    abs_pos = t_i - 1 - jnp.arange(W)
    pos = (abs_pos % W).astype(jnp.int32)
    in_run = (abs_pos >= run_start[:, None]) & (abs_pos >= 0)
    yw = jnp.take_along_axis(ybuf, jnp.broadcast_to(pos, (S, W)), axis=1)
    relw = abs_pos.astype(dtype)[None, :] - rs[:, None]
    res = jnp.abs(yw - (a_fit[:, None] * relw + b_fit[:, None]))
    res = jnp.where(in_run, res, 0.0)
    max_res = jnp.maximum(jnp.max(res, axis=1),
                          jnp.abs(yt - (a_fit * rel + b_fit)))
    tol = eps * (1 + 1e-6) + 1e-12
    valid = max_res <= tol
    cap_hit = nn >= max_run
    brk = ~valid | cap_hit

    a_out, v_out = va, vv  # last valid fit, anchored at t-1

    new_run_start = jnp.where(brk, t_i, run_start)
    new_nn = jnp.where(brk, 1.0, n1)
    new_mt = jnp.where(brk, 0.0, mt1)
    new_my = jnp.where(brk, yt, my1)
    new_stt = jnp.where(brk, 0.0, stt1)
    new_sty = jnp.where(brk, 0.0, sty1)
    new_va = jnp.where(brk, 0.0, a_fit)
    # value of the (new) valid fit at the *current* point t.
    new_vv = jnp.where(brk, yt, a_fit * rel + b_fit)
    ybuf_n = ybuf.at[:, (t_i % W).astype(jnp.int32)].set(yt)
    new_state = (ybuf_n, new_run_start, new_nn, new_mt, new_my,
                 new_stt, new_sty, new_va, new_vv)
    return new_state, (brk, a_out, v_out)


def _linear_flush_windowed(carry, t_last):
    (_, _, _, _, _, _, _, va, vv) = carry
    return va, vv


# ---- Linear (best-fit): hull-carry revalidation (default) ------------------
#
# The Welford accumulators already make the *fit* O(1); only the
# revalidation (max |residual| over the run) scanned the window.  The max
# of ``y - (a*rel + b)`` over the run is attained at a vertex of the upper
# convex chain of the raw points (a linear functional over a convex set),
# the min at a vertex of the lower chain, so the revalidation reduces
# over the small chain planes instead of the W-wide window.  Residuals
# are evaluated with the exact windowed expression
# ``|yw - (a_fit*relw + b_fit)|`` at the chain vertices, so the validity
# decision matches the windowed reference up to fp ulps in the extremum
# choice (same documented pin as disjoint).  Lanes whose hull outgrows
# the chain capacity run the exact windowed reduction inside a cold
# ``lax.cond`` until their next break (see the disjoint layout note).

def _linear_init(y0, eps, max_run, window, t0):
    S = y0.shape[0]
    dtype = y0.dtype
    W = window
    t0 = jnp.asarray(t0, jnp.int32)
    one = jnp.ones((S,), jnp.int32)
    cdt = _chain_slot_dtype(W)
    slot0 = jnp.mod(t0, W)
    ring = jnp.zeros((S, W), dtype).at[:, slot0].set(y0)
    idx0 = jnp.zeros((S, _chain_cap(W)), cdt).at[:, 0].set(slot0.astype(cdt))
    return (jnp.full((S,), t0, jnp.int32),
            jnp.ones((S,), dtype),                      # n
            jnp.zeros((S,), dtype), y0,                 # means (rel t, y)
            jnp.zeros((S,), dtype), jnp.zeros((S,), dtype),  # stt, sty
            jnp.zeros((S,), dtype), y0,                 # valid fit (0, y0)
            ring, idx0, idx0,                 # value ring + uh/lh chains
            one, one,                         # uh_len, lh_len
            jnp.zeros((S,), bool))            # windowed-mode flag


def _linear_step(eps, max_run, window, state, inp):
    (run_start, nn, mt, my, stt, sty, va, vv,
     ring, uh_idx, lh_idx, uh_len, lh_len, wm) = state
    W = window
    t_i, yt = inp
    S = yt.shape[0]
    dtype = yt.dtype
    slot = jnp.mod(t_i, W)
    ring = _ring_write(ring, slot, yt)  # write FIRST: reads are post-update
    t = jnp.broadcast_to(t_i, (S,)).astype(dtype)
    rs = run_start.astype(dtype)
    rel = t - rs

    n1 = nn + 1.0
    d_t = rel - mt
    d_y = yt - my
    mt1 = mt + d_t / n1
    my1 = my + d_y / n1
    stt1 = stt + d_t * (rel - mt1)
    sty1 = sty + d_t * (yt - my1)
    a_fit = jnp.where(stt1 > 0, sty1 / jnp.where(stt1 > 0, stt1, 1.0), 0.0)
    b_fit = my1 - a_fit * mt1    # value at rel == 0 (run start)

    # Hull revalidation: the signed residual is a linear functional of the
    # vertex, so its extrema over the run live on the chains; the max
    # |residual| is the larger magnitude of the two signed extremes.
    uh_qx, uh_qy = _chain_planes(ring, uh_idx, t_i, W, lambda yv: yv)
    lh_qx, lh_qy = _chain_planes(ring, lh_idx, t_i, W, lambda yv: yv)

    def res_at(qx, qy):
        return qy - (a_fit[:, None] * (qx - rs[:, None]) + b_fit[:, None])

    res_u = jnp.abs(_chain_extremum(uh_qx, uh_qy, uh_len, res_at,
                                    minimize=False))
    res_l = jnp.abs(_chain_extremum(lh_qx, lh_qy, lh_len, res_at,
                                    minimize=True))
    mr_c = jnp.maximum(res_u, res_l)

    def _windowed_reval(_):
        abs_pos = _window_positions(t_i, W)
        pos = (abs_pos % W).astype(jnp.int32)
        in_run = (abs_pos >= run_start[:, None]) & (abs_pos >= 0)
        yw = jnp.take_along_axis(ring, jnp.broadcast_to(pos, (S, W)),
                                 axis=1)
        relw = abs_pos.astype(dtype) - rs[:, None]
        res = jnp.abs(yw - (a_fit[:, None] * relw + b_fit[:, None]))
        res = jnp.where(in_run, res, 0.0)
        return jnp.where(wm, jnp.max(res, axis=1), mr_c)

    mr = jax.lax.cond(jnp.any(wm), _windowed_reval, lambda _: mr_c, None)
    max_res = jnp.maximum(mr, jnp.abs(yt - (a_fit * rel + b_fit)))
    tol = eps * (1 + 1e-6) + 1e-12
    valid = max_res <= tol
    cap_hit = nn >= max_run
    brk = ~valid | cap_hit

    a_out, v_out = va, vv  # last valid fit, anchored at t-1

    new_run_start = jnp.where(brk, t_i, run_start)
    new_nn = jnp.where(brk, 1.0, n1)
    new_mt = jnp.where(brk, 0.0, mt1)
    new_my = jnp.where(brk, yt, my1)
    new_stt = jnp.where(brk, 0.0, stt1)
    new_sty = jnp.where(brk, 0.0, sty1)
    new_va = jnp.where(brk, 0.0, a_fit)
    # value of the (new) valid fit at the *current* point t.
    new_vv = jnp.where(brk, yt, a_fit * rel + b_fit)
    keep = ~brk & ~wm
    uh_idx, uh_len, ov_uh = _chain_append(uh_idx, uh_len, keep, t, yt,
                                          uh_qx, uh_qy, slot, upper=True)
    lh_idx, lh_len, ov_lh = _chain_append(lh_idx, lh_len, keep, t, yt,
                                          lh_qx, lh_qy, slot, upper=False)
    new_wm = ~brk & (wm | ov_uh | ov_lh)
    new_state = (new_run_start, new_nn, new_mt, new_my,
                 new_stt, new_sty, new_va, new_vv,
                 ring, uh_idx, lh_idx, uh_len, lh_len, new_wm)
    return new_state, (brk, a_out, v_out)


def _linear_flush(carry, t_last):
    va, vv = carry[6], carry[7]
    return va, vv


# ---- Continuous: connected polyline, gate-deferred knot choice -------------
#
# The sequential reference (methods.run_continuous) keeps a HullFitter over
# a *gate* interval (the feasible-value range inherited from the previous
# segment at its last point) plus the current run's error intervals; at a
# break it fixes the knot at the gate (mid-line evaluation) and only then
# can the *previous* segment's line — through the two bounding knots — be
# emitted.  Events therefore target positions one segment in the past:
# deferred methods emit ``(ev, pos, a, v)`` tuples per step instead of the
# aligned ``(brk, a, v)`` column, and the wrappers scatter them by absolute
# position (see ``_segment_offline_deferred`` / the pending-buffer release
# logic in :func:`step_chunk`).
#
# Carry (per stream): ring of run values, gate (g_pos, glo, ghi), the
# extreme lines of the gate+run fitter anchored at ``g_pos``, the run
# length, a lines-initialized flag, and the last *fixed* knot
# ``(k_pos, k_val)`` (left end of the pending segment).  The convex-hull
# pivot searches become exact masked reductions over the run window with
# the gate as one extra constraint (same argument as the disjoint method:
# the binding extremum over all constraints equals the hull extremum).

def _continuous_init(y0, eps, max_run, window, t0):
    S = y0.shape[0]
    dtype = y0.dtype
    W = window
    t0 = jnp.asarray(t0, jnp.int32)
    ybuf0 = jnp.zeros((S, W), dtype).at[:, t0 % W].set(y0)
    z = jnp.zeros((S,), dtype)
    zi = jnp.zeros((S,), jnp.int32)
    return (ybuf0,
            jnp.full((S,), t0, jnp.int32),    # g_pos (gate position)
            y0 - eps, y0 + eps,               # glo, ghi
            jnp.ones((S,), jnp.int32),        # run_len (sequential i - i0)
            zi,                               # has2: extreme lines valid
            z, z, z, z,                       # a_lo, v_lo, a_hi, v_hi @ g
            zi, jnp.full((S,), t0, jnp.int32), z)  # has_k, k_pos, k_val


def _continuous_step(eps, max_run, window, state, inp):
    (ybuf, g_pos, glo, ghi, rl, has2,
     a_lo, v_lo, a_hi, v_hi, has_k, k_pos, k_val) = state
    W = window
    t_i, yt = inp
    S = yt.shape[0]
    dtype = yt.dtype
    dg = (t_i - g_pos).astype(dtype)          # t - gate position, >= 1

    lo_i, hi_i = yt - eps, yt + eps
    vmax = a_hi * dg + v_hi
    vmin = a_lo * dg + v_lo
    feas = (vmax >= lo_i) & (vmin <= hi_i)
    cap_hit = rl >= max_run
    brk = (has2 == 1) & (~feas | cap_hit)

    # Knot fixed by this break: mid-line evaluation at the gate (both
    # extreme lines are anchored at g_pos, so the parameter-space midpoint
    # evaluates to the plain average there).
    Kv = 0.5 * (v_lo + v_hi)
    dk = (g_pos - k_pos).astype(dtype)
    dk_safe = jnp.where(dk > 0, dk, 1.0)
    ev = brk & (has_k == 1)
    a_ev = jnp.where(ev, (Kv - k_val) / dk_safe, 0.0)
    v_ev = jnp.where(ev, Kv, 0.0)
    pos_ev = jnp.where(ev, g_pos, -1)

    # ---- run window (positions strictly after the gate) ----------------
    abs_pos = t_i - 1 - jnp.arange(W)
    slot = (abs_pos % W).astype(jnp.int32)
    yw = jnp.take_along_axis(ybuf, jnp.broadcast_to(slot, (S, W)), axis=1)
    apf = abs_pos.astype(dtype)[None, :]
    gpf = g_pos.astype(dtype)
    in_run = apf > gpf[:, None]
    dtw = t_i.astype(dtype) - apf
    dtw_safe = jnp.where(in_run, dtw, 1.0)

    # ---- extreme-line retightening (gate is one extra constraint) ------
    need_hi = vmax > hi_i
    s_hi = (hi_i[:, None] - (yw - eps[:, None])) / dtw_safe
    s_hi = jnp.where(in_run, s_hi, _BIG)
    a_hi_new = jnp.minimum(jnp.min(s_hi, axis=1), (hi_i - glo) / dg)
    v_hi_new = hi_i - a_hi_new * dg
    a_hi_u = jnp.where(need_hi, a_hi_new, a_hi)
    v_hi_u = jnp.where(need_hi, v_hi_new, v_hi)

    need_lo = vmin < lo_i
    s_lo = (lo_i[:, None] - (yw + eps[:, None])) / dtw_safe
    s_lo = jnp.where(in_run, s_lo, -_BIG)
    a_lo_new = jnp.maximum(jnp.max(s_lo, axis=1), (lo_i - ghi) / dg)
    v_lo_new = lo_i - a_lo_new * dg
    a_lo_u = jnp.where(need_lo, a_lo_new, a_lo)
    v_lo_u = jnp.where(need_lo, v_lo_new, v_lo)

    # Second constraint (gate + first run point) initializes the lines.
    first = has2 == 0
    a_hi_n = jnp.where(first, (hi_i - glo) / dg, a_hi_u)
    v_hi_n = jnp.where(first, glo, v_hi_u)
    a_lo_n = jnp.where(first, (lo_i - ghi) / dg, a_lo_u)
    v_lo_n = jnp.where(first, ghi, v_lo_u)

    # ---- break: next gate = feasible range of the wedge through K ------
    ds = apf - gpf[:, None]
    ds_safe = jnp.where(in_run, ds, 1.0)
    w1 = jnp.where(in_run, (yw - eps[:, None] - Kv[:, None]) / ds_safe, -_BIG)
    w2 = jnp.where(in_run, (yw + eps[:, None] - Kv[:, None]) / ds_safe, _BIG)
    wslo = jnp.max(w1, axis=1)
    wshi = jnp.min(w2, axis=1)
    dgn = (t_i - 1 - g_pos).astype(dtype)     # distance gate -> new gate
    glo_b = Kv + wslo * dgn
    ghi_b = Kv + wshi * dgn
    # New fitter = gate' + this point's interval (dt == 1 from the gate).
    a_hi_b = hi_i - glo_b
    a_lo_b = lo_i - ghi_b

    # ---- commit --------------------------------------------------------
    new_state = (ybuf.at[:, (t_i % W).astype(jnp.int32)].set(yt),
                 jnp.where(brk, t_i - 1, g_pos),
                 jnp.where(brk, glo_b, glo), jnp.where(brk, ghi_b, ghi),
                 jnp.where(brk, 1, rl + 1),
                 jnp.ones_like(has2),
                 jnp.where(brk, a_lo_b, a_lo_n),
                 jnp.where(brk, ghi_b, v_lo_n),
                 jnp.where(brk, a_hi_b, a_hi_n),
                 jnp.where(brk, glo_b, v_hi_n),
                 jnp.where(brk, 1, has_k),
                 jnp.where(brk, g_pos, k_pos),
                 jnp.where(brk, Kv, k_val))
    return new_state, (ev, pos_ev, a_ev, v_ev)


def _continuous_flush(eps, max_run, window, carry, t_last):
    """Fix the last knot; emit the pending segment + the trailing one.

    Deferred flushes return ``((ev1, pos1, a1, v1), (a2, v2))``: an
    optional event for the still-pending segment plus the trailing
    segment's line (its event always lands at ``t_last``).
    """
    (ybuf, g_pos, glo, ghi, rl, has2,
     a_lo, v_lo, a_hi, v_hi, has_k, k_pos, k_val) = carry
    dtype = glo.dtype
    Kv = jnp.where(has2 == 1, 0.5 * (v_lo + v_hi), 0.5 * (glo + ghi))
    dk = (g_pos - k_pos).astype(dtype)
    dk_safe = jnp.where(dk > 0, dk, 1.0)
    ev1 = has_k == 1
    a1 = jnp.where(ev1, (Kv - k_val) / dk_safe, 0.0)
    v1 = jnp.where(ev1, Kv, 0.0)
    am = jnp.where(has2 == 1, 0.5 * (a_lo + a_hi), 0.0)
    dl = (jnp.asarray(t_last, jnp.int32) - g_pos).astype(dtype)
    # The select keeps ``am * dl`` a separately rounded product: XLA:CPU
    # contracts ``Kv + am * dl`` into an FMA in some fusions and not in
    # others, so the chunked flush (its own jit) and the offline one
    # (fused after the scan) differed by one ulp.
    return (ev1, g_pos, a1, v1), (am, Kv + jnp.where(dl >= 0, am * dl, 0.0))


# ---- MixedPLA: disjoint stage-1 runs + joint-merge stage-2 -----------------
#
# Stage 1 is exactly the disjoint scan (same extreme lines / window
# retightening); stage 2 holds the *previous* finalized run and, when the
# current run breaks, decides joint-vs-disjoint by intersecting the two
# feasible-value ranges at the previous run's last point (Luo et al.'s
# single-segment-lookahead merge, methods.run_mixed).  A join places the
# shared knot at that point and shortens the previous segment by one
# position, so — as with ``continuous`` — events land one run in the past
# and the method is *deferred*.  The ring must retain both runs:
# :func:`mixed_ring` sizes it at ``2 * window + 8``.

def mixed_ring(window: int) -> int:
    """Ring rows for the mixed method: the join decision re-reads both the
    previous run (<= window + 1 points with an absorbed knot) and the
    current run (<= window points)."""
    return 2 * window + 8


def _mixed_init(y0, eps, max_run, window, t0):
    S = y0.shape[0]
    dtype = y0.dtype
    W = window
    t0 = jnp.asarray(t0, jnp.int32)
    ybuf0 = jnp.zeros((S, W), dtype).at[:, t0 % W].set(y0)
    z = jnp.zeros((S,), dtype)
    zi = jnp.zeros((S,), jnp.int32)
    return (ybuf0,
            jnp.full((S,), t0, jnp.int32),    # run_start
            jnp.ones((S,), jnp.int32),        # run_len
            y0, y0,                           # y0, prev_y
            z, z, z, z,                       # a_lo, v_lo, a_hi, v_hi
            zi, zi, zi,                       # p_exists, p_i0, p_i1
            zi, zi, z,                        # p_lk, p_lk_pos, p_lk_val
            z, z, z, z)                       # p_lo, p_hi, p_amid, p_vmid


def _mixed_step(eps, max_run, window, state, inp):
    (ybuf, run_start, rl, y0, prev_y, a_lo, v_lo, a_hi, v_hi,
     p_ex, p_i0, p_i1, p_lk, p_lk_pos, p_lk_val,
     p_lo, p_hi, p_amid, p_vmid) = state
    W = window
    t_i, yt = inp
    S = yt.shape[0]
    dtype = yt.dtype
    rel = (t_i - run_start).astype(dtype)

    # ---- stage 1: disjoint feasibility + retightening (as _disjoint_step)
    lo_i, hi_i = yt - eps, yt + eps
    vmax = a_hi * rel + v_hi
    vmin = a_lo * rel + v_lo
    feas2 = (vmax >= lo_i) & (vmin <= hi_i)
    feasible = jnp.where(rl >= 2, feas2, True)
    cap_hit = rl >= max_run
    brk = ~feasible | cap_hit

    abs_pos = t_i - 1 - jnp.arange(W)
    slot = (abs_pos % W).astype(jnp.int32)
    yw = jnp.take_along_axis(ybuf, jnp.broadcast_to(slot, (S, W)), axis=1)
    apf = abs_pos.astype(dtype)[None, :]
    in_run = (abs_pos[None, :] >= run_start[:, None]) & (abs_pos >= 0)
    dtw_safe = jnp.where(in_run, t_i.astype(dtype) - apf, 1.0)

    need_hi = vmax > hi_i
    s_hi = jnp.where(in_run, (hi_i[:, None] - (yw - eps[:, None]))
                     / dtw_safe, _BIG)
    a_hi_new = jnp.min(s_hi, axis=1)
    a_hi_u = jnp.where(need_hi, a_hi_new, a_hi)
    v_hi_u = jnp.where(need_hi, hi_i - a_hi_new * rel, v_hi)

    need_lo = vmin < lo_i
    s_lo = jnp.where(in_run, (lo_i[:, None] - (yw + eps[:, None]))
                     / dtw_safe, -_BIG)
    a_lo_new = jnp.max(s_lo, axis=1)
    a_lo_u = jnp.where(need_lo, a_lo_new, a_lo)
    v_lo_u = jnp.where(need_lo, lo_i - a_lo_new * rel, v_lo)

    rel_s = jnp.maximum(rel, 1.0)
    second = rl == 1
    a_hi_n = jnp.where(second, (hi_i - (y0 - eps)) / rel_s, a_hi_u)
    v_hi_n = jnp.where(second, y0 - eps, v_hi_u)
    a_lo_n = jnp.where(second, (lo_i - (y0 + eps)) / rel_s, a_lo_u)
    v_lo_n = jnp.where(second, y0 + eps, v_lo_u)

    # ---- stage 2: join decision at the current run's break -------------
    tau = run_start - 1                       # prev run's last point
    tauf = tau.astype(dtype)

    # prev feasible range + mid line when prev carries a left knot:
    # wedge through (p_lk_pos, p_lk_val) over prev's own points.
    lkpf = p_lk_pos.astype(dtype)
    m_prev = (abs_pos[None, :] >= p_i0[:, None]) \
        & (abs_pos[None, :] < p_i1[:, None]) \
        & (abs_pos[None, :] > p_lk_pos[:, None])
    ds = jnp.where(m_prev, apf - lkpf[:, None], 1.0)   # > 0 under mask
    lk_slo = jnp.max(jnp.where(
        m_prev, (yw - eps[:, None] - p_lk_val[:, None]) / ds, -_BIG), axis=1)
    lk_shi = jnp.min(jnp.where(
        m_prev, (yw + eps[:, None] - p_lk_val[:, None]) / ds, _BIG), axis=1)
    dtl = tauf - lkpf
    dtl_safe = jnp.where(dtl > 0, dtl, 1.0)
    lk_lo = p_lk_val + lk_slo * dtl
    lk_hi = p_lk_val + lk_shi * dtl
    lk_amid = 0.5 * (lk_slo + lk_shi)
    lk_vmid = p_lk_val + lk_amid * dtl
    plo = jnp.where(p_lk == 1, lk_lo, p_lo)
    phi = jnp.where(p_lk == 1, lk_hi, p_hi)

    # current run's feasible range at tau (one step before its start).
    cv1 = v_lo - a_lo
    cv2 = v_hi - a_hi
    clo = jnp.where(rl >= 2, jnp.minimum(cv1, cv2), -_BIG)
    chi = jnp.where(rl >= 2, jnp.maximum(cv1, cv2), _BIG)

    jlo = jnp.maximum(plo, clo)
    jhi = jnp.minimum(phi, chi)
    # A join hands the current run prev's last point: a run already at
    # max_run stays disjoint so no segment exceeds max_run points.
    join = brk & ~cap_hit & (p_ex == 1) & (p_i1 - p_i0 >= 2) \
        & (jlo <= jhi)
    vK = 0.5 * (jlo + jhi)

    # Joint emission: prev shortened by one point, line through the knots.
    m_jw = (abs_pos[None, :] >= p_i0[:, None]) \
        & (abs_pos[None, :] < (p_i1 - 1)[:, None])
    ds2 = jnp.where(m_jw, apf - tauf[:, None], 1.0)    # < 0 under mask
    jb1 = (yw - eps[:, None] - vK[:, None]) / ds2
    jb2 = (yw + eps[:, None] - vK[:, None]) / ds2
    jw_slo = jnp.max(jnp.where(m_jw, jb2, -_BIG), axis=1)
    jw_shi = jnp.min(jnp.where(m_jw, jb1, _BIG), axis=1)
    aJ = jnp.where(p_lk == 1, (vK - p_lk_val) / dtl_safe,
                   0.5 * (jw_slo + jw_shi))
    # Disjoint emission: prev's chosen mid line, value at its last point.
    aN = jnp.where(p_lk == 1, lk_amid, p_amid)
    vN = jnp.where(p_lk == 1, lk_vmid, p_vmid)

    ev = brk & (p_ex == 1)
    pos_ev = jnp.where(ev, jnp.where(join, tau - 1, tau), -1)
    a_ev = jnp.where(ev, jnp.where(join, aJ, aN), 0.0)
    v_ev = jnp.where(ev, jnp.where(join, vK - aJ, vN), 0.0)

    # The breaking run becomes prev: cache its free-case range/mid at its
    # last point (t - 1) before stage-1 state resets.
    rel2 = rel - 1.0
    nv1 = v_lo + a_lo * rel2
    nv2 = v_hi + a_hi * rel2
    np_lo = jnp.where(rl >= 2, jnp.minimum(nv1, nv2), prev_y - eps)
    np_hi = jnp.where(rl >= 2, jnp.maximum(nv1, nv2), prev_y + eps)
    np_amid = jnp.where(rl >= 2, 0.5 * (a_lo + a_hi), 0.0)
    np_vmid = jnp.where(rl >= 2, 0.5 * (v_lo + v_hi) + np_amid * rel2,
                        prev_y)

    # ---- commit --------------------------------------------------------
    z = jnp.zeros_like(a_lo)
    new_state = (ybuf.at[:, (t_i % W).astype(jnp.int32)].set(yt),
                 jnp.where(brk, t_i, run_start),
                 jnp.where(brk, 1, rl + 1),
                 jnp.where(brk, yt, y0), yt,
                 jnp.where(brk, z, a_lo_n), jnp.where(brk, z, v_lo_n),
                 jnp.where(brk, z, a_hi_n), jnp.where(brk, z, v_hi_n),
                 jnp.where(brk, 1, p_ex),
                 jnp.where(brk, jnp.where(join, tau, run_start), p_i0),
                 jnp.where(brk, t_i, p_i1),
                 jnp.where(brk, join.astype(jnp.int32), p_lk),
                 jnp.where(brk & join, tau, p_lk_pos),
                 jnp.where(brk & join, vK, p_lk_val),
                 jnp.where(brk, np_lo, p_lo), jnp.where(brk, np_hi, p_hi),
                 jnp.where(brk, np_amid, p_amid),
                 jnp.where(brk, np_vmid, p_vmid))
    return new_state, (ev, pos_ev, a_ev, v_ev)


def _mixed_flush(eps, max_run, window, carry, t_last):
    """Final join decision (prev vs the trailing run) + trailing segment."""
    (ybuf, run_start, rl, y0, prev_y, a_lo, v_lo, a_hi, v_hi,
     p_ex, p_i0, p_i1, p_lk, p_lk_pos, p_lk_val,
     p_lo, p_hi, p_amid, p_vmid) = carry
    S, W = ybuf.shape
    dtype = prev_y.dtype
    t_last = jnp.asarray(t_last, jnp.int32)

    tau = run_start - 1
    tauf = tau.astype(dtype)
    abs_pos = t_last - jnp.arange(W)
    slot = (abs_pos % W).astype(jnp.int32)
    yw = jnp.take_along_axis(ybuf, jnp.broadcast_to(slot, (S, W)), axis=1)
    apf = abs_pos.astype(dtype)[None, :]

    # -- decision between prev and the trailing run (as in _mixed_step) --
    lkpf = p_lk_pos.astype(dtype)
    m_prev = (abs_pos[None, :] >= p_i0[:, None]) \
        & (abs_pos[None, :] < p_i1[:, None]) \
        & (abs_pos[None, :] > p_lk_pos[:, None])
    ds = jnp.where(m_prev, apf - lkpf[:, None], 1.0)
    lk_slo = jnp.max(jnp.where(
        m_prev, (yw - eps[:, None] - p_lk_val[:, None]) / ds, -_BIG), axis=1)
    lk_shi = jnp.min(jnp.where(
        m_prev, (yw + eps[:, None] - p_lk_val[:, None]) / ds, _BIG), axis=1)
    dtl = tauf - lkpf
    dtl_safe = jnp.where(dtl > 0, dtl, 1.0)
    lk_amid = 0.5 * (lk_slo + lk_shi)
    plo = jnp.where(p_lk == 1, p_lk_val + lk_slo * dtl, p_lo)
    phi = jnp.where(p_lk == 1, p_lk_val + lk_shi * dtl, p_hi)

    cv1 = v_lo - a_lo
    cv2 = v_hi - a_hi
    clo = jnp.where(rl >= 2, jnp.minimum(cv1, cv2), -_BIG)
    chi = jnp.where(rl >= 2, jnp.maximum(cv1, cv2), _BIG)
    jlo = jnp.maximum(plo, clo)
    jhi = jnp.minimum(phi, chi)
    join = (rl < max_run) & (p_ex == 1) & (p_i1 - p_i0 >= 2) \
        & (jlo <= jhi)
    vK = 0.5 * (jlo + jhi)

    m_jw = (abs_pos[None, :] >= p_i0[:, None]) \
        & (abs_pos[None, :] < (p_i1 - 1)[:, None])
    ds2 = jnp.where(m_jw, apf - tauf[:, None], 1.0)
    jw_slo = jnp.max(jnp.where(
        m_jw, (yw + eps[:, None] - vK[:, None]) / ds2, -_BIG), axis=1)
    jw_shi = jnp.min(jnp.where(
        m_jw, (yw - eps[:, None] - vK[:, None]) / ds2, _BIG), axis=1)
    aJ = jnp.where(p_lk == 1, (vK - p_lk_val) / dtl_safe,
                   0.5 * (jw_slo + jw_shi))
    aN = jnp.where(p_lk == 1, lk_amid, p_amid)
    vN = jnp.where(p_lk == 1, p_lk_val + lk_amid * dtl, p_vmid)

    ev1 = p_ex == 1
    pos1 = jnp.where(join, tau - 1, tau)
    a1 = jnp.where(ev1, jnp.where(join, aJ, aN), 0.0)
    v1 = jnp.where(ev1, jnp.where(join, vK - aJ, vN), 0.0)

    # -- trailing segment: wedge from the (possibly new) left knot, else
    # the free mid line of the stage-1 fitter ----------------------------
    m_cur = (abs_pos[None, :] > tau[:, None]) \
        & (abs_pos[None, :] <= t_last)
    ds3 = jnp.where(m_cur, apf - tauf[:, None], 1.0)   # > 0 under mask
    cw_slo = jnp.max(jnp.where(
        m_cur, (yw - eps[:, None] - vK[:, None]) / ds3, -_BIG), axis=1)
    cw_shi = jnp.min(jnp.where(
        m_cur, (yw + eps[:, None] - vK[:, None]) / ds3, _BIG), axis=1)
    a2j = 0.5 * (cw_slo + cw_shi)
    dte = (t_last - tau).astype(dtype)
    rel_last = (t_last - run_start).astype(dtype)
    a2n = jnp.where(rl >= 2, 0.5 * (a_lo + a_hi), 0.0)
    v2n = jnp.where(rl >= 2, 0.5 * (v_lo + v_hi) + a2n * rel_last, prev_y)
    a2 = jnp.where(join, a2j, a2n)
    v2 = jnp.where(join, vK + a2j * dte, v2n)
    return (ev1, pos1, a1, v1), (a2, v2)


_METHOD_IMPLS = {
    "angle": _MethodImpl(_angle_init, _angle_step, _angle_flush,
                         int_ts=False, windowed=False),
    "swing": _MethodImpl(_swing_init, _swing_step, _swing_flush,
                         int_ts=False, windowed=False),
    "disjoint": _MethodImpl(_disjoint_init, _disjoint_step, _disjoint_flush,
                            int_ts=True, windowed=True),
    "linear": _MethodImpl(_linear_init, _linear_step, _linear_flush,
                          int_ts=True, windowed=True),
    "continuous": _MethodImpl(_continuous_init, _continuous_step,
                              _continuous_flush, int_ts=True, windowed=True,
                              deferred=True),
    "mixed": _MethodImpl(_mixed_init, _mixed_step, _mixed_flush,
                         int_ts=True, windowed=True, deferred=True),
}

# O(W)-per-point reference steps kept as test oracles for the hull-carry
# fast path (NOT part of the streaming registry — same method names, same
# outputs, different carry).  See disjoint_segment_windowed below.
_WINDOWED_IMPLS = {
    "disjoint": _MethodImpl(_disjoint_init_windowed, _disjoint_step_windowed,
                            _disjoint_flush_windowed,
                            int_ts=True, windowed=True),
    "linear": _MethodImpl(_linear_init_windowed, _linear_step_windowed,
                          _linear_flush_windowed,
                          int_ts=True, windowed=True),
}

STREAMING_METHODS = tuple(_METHOD_IMPLS)

# Methods whose events resolve one segment late: their chunked output has
# data-dependent width (finalized columns are released only once no future
# event can target them) and their scan emits position-tagged events.
DEFERRED_METHODS = tuple(m for m, impl in _METHOD_IMPLS.items()
                         if impl.deferred)


def _ring_size(method: str, max_run: int, window: Optional[int]) -> int:
    """Resolve the ring-buffer row count of a windowed method."""
    W = check_window(max_run, window)
    return mixed_ring(W) if method == "mixed" else W


# ---------------------------------------------------------------------------
# Offline segmenters: one full-length chunk through the shared triple
# ---------------------------------------------------------------------------

def _segment_offline(method, y, eps, max_run, window, impls=None):
    impl = (impls or _METHOD_IMPLS)[method]
    if impl.deferred:
        return _segment_offline_deferred(method, y, eps, max_run, window)
    S, T = y.shape
    dtype = y.dtype
    eps = jnp.broadcast_to(jnp.asarray(eps, dtype), (S,))
    carry = impl.init(y[:, 0], eps, max_run, window, 0)
    ts = jnp.arange(1, T, dtype=jnp.int32 if impl.int_ts else dtype)
    step = functools.partial(impl.step, eps, max_run, window)
    carry, (brk_seq, a_seq, v_seq) = jax.lax.scan(
        step, carry, (ts, y[:, 1:].T), unroll=_scan_unroll(method, T - 1))
    breaks = jnp.zeros((S, T), bool).at[:, :-1].set(brk_seq.T)
    a = jnp.zeros((S, T), dtype).at[:, :-1].set(a_seq.T)
    v = jnp.zeros((S, T), dtype).at[:, :-1].set(v_seq.T)
    # Flush trailing run at T-1 through the shared flush.
    a_f, v_f = impl.flush(carry, T - 1)
    breaks = breaks.at[:, T - 1].set(True)
    a = a.at[:, T - 1].set(a_f)
    v = v.at[:, T - 1].set(v_f)
    return SegmentOutput(breaks, a, v)


def scatter_events(breaks, a, v, ev, pos, ea, ev_v):
    """Scatter position-tagged events into (S, T) event arrays.

    ``ev/pos/ea/ev_v`` are (S, n) batches of deferred events; positions of
    disabled events are redirected past T and dropped.
    """
    S, T = breaks.shape
    rows = jnp.arange(S)[:, None]
    tgt = jnp.where(ev, pos, T)
    breaks = breaks.at[rows, tgt].set(True, mode="drop")
    a = a.at[rows, tgt].set(ea, mode="drop")
    v = v.at[rows, tgt].set(ev_v, mode="drop")
    return breaks, a, v


def assemble_deferred_events(S, T, dtype, ev, pos, ea, ev_v, flush_evs
                             ) -> SegmentOutput:
    """Canonical (S, T) assembly of a deferred segmentation: scatter the
    scan's ``(S, n)`` position-tagged event batch (absolute positions),
    scatter the flush's pending-segment event, and force the trailing
    segment's break at ``T - 1``.  Shared by the jnp offline wrappers and
    the deferred kernel wrappers (``kernels.ops.assemble_deferred``) so
    the two paths cannot drift."""
    breaks = jnp.zeros((S, T), bool)
    a = jnp.zeros((S, T), dtype)
    v = jnp.zeros((S, T), dtype)
    breaks, a, v = scatter_events(breaks, a, v, ev, pos, ea, ev_v)
    (ev1, p1, a1, v1), (a2, v2) = flush_evs
    breaks, a, v = scatter_events(breaks, a, v, ev1[:S, None], p1[:S, None],
                                  a1[:S, None], v1[:S, None])
    breaks = breaks.at[:, T - 1].set(True)
    a = a.at[:, T - 1].set(a2[:S])
    v = v.at[:, T - 1].set(v2[:S])
    return SegmentOutput(breaks, a, v)


def _segment_offline_deferred(method, y, eps, max_run, window):
    impl = _METHOD_IMPLS[method]
    S, T = y.shape
    dtype = y.dtype
    eps = jnp.broadcast_to(jnp.asarray(eps, dtype), (S,))
    carry = impl.init(y[:, 0], eps, max_run, window, 0)
    ts = jnp.arange(1, T, dtype=jnp.int32)
    step = functools.partial(impl.step, eps, max_run, window)
    carry, (ev, pos, ea, ev_v) = jax.lax.scan(
        step, carry, (ts, y[:, 1:].T), unroll=_scan_unroll(method, T - 1))
    flush_evs = impl.flush(eps, max_run, window, carry, T - 1)
    return assemble_deferred_events(S, T, dtype, ev.T, pos.T, ea.T, ev_v.T,
                                    flush_evs)


@functools.partial(jax.jit, static_argnames=("max_run",))
def angle_segment(y: jax.Array, eps: jax.Array, max_run: int = 256
                  ) -> SegmentOutput:
    """Batched Angle method (greedy wedge from the extreme-line crossing).

    ``eps`` may be scalar or per-row ``(S,)``.
    """
    return _segment_offline("angle", y, eps, max_run, None)


@functools.partial(jax.jit, static_argnames=("max_run",))
def swing_segment(y: jax.Array, eps: jax.Array, max_run: int = 256
                  ) -> SegmentOutput:
    """Batched SwingFilter (paper §3.1, Elmeleegy et al.).

    The wedge origin is the chosen end point of the previous segment (the
    joint knot), so consecutive segment lines are connected.  Output uses
    the same (breaks, a, v) form — reconstruction is identical; the joint
    property shows as v[k] continuity across breaks.
    """
    return _segment_offline("swing", y, eps, max_run, None)


def check_window(max_run: int, window: Optional[int]) -> int:
    """Resolve/validate a run-window size (defaults to ``max_run``)."""
    W = window or max_run
    if W < max_run:
        raise ValueError("window must be >= max_run")
    return W


@functools.partial(jax.jit, static_argnames=("max_run", "window"))
def disjoint_segment(y: jax.Array, eps: jax.Array, max_run: int = 256,
                     window: Optional[int] = None) -> SegmentOutput:
    """Batched optimal-disjoint method (ConvexHull / SlideFilter).

    The extreme-slope lines are retightened by a tangent walk over compact
    per-stream convex chains carried in the scan state (amortized O(1) per
    point — the paper's hull algorithm, batched).  Lines are anchored at
    the run start.  ``window`` defaults to ``max_run`` and bounds the
    chain capacity.  ``disjoint_segment_windowed`` is the O(W)-per-point
    reference this is pinned against.
    """
    return _segment_offline("disjoint", y, eps, max_run,
                            check_window(max_run, window))


@functools.partial(jax.jit, static_argnames=("max_run", "window"))
def linear_segment(y: jax.Array, eps: jax.Array, max_run: int = 256,
                   window: Optional[int] = None) -> SegmentOutput:
    """Batched Linear (best-fit) method with hull-carry revalidation.

    The running least-squares fit is kept in Welford form over
    *run-relative* time; the validity check (max |residual| over the run)
    is read off the run's convex chains by a tangent walk instead of an
    O(W) masked reduction.  ``linear_segment_windowed`` is the windowed
    reference this is pinned against.
    """
    return _segment_offline("linear", y, eps, max_run,
                            check_window(max_run, window))


@functools.partial(jax.jit, static_argnames=("max_run", "window"))
def disjoint_segment_windowed(y: jax.Array, eps: jax.Array,
                              max_run: int = 256,
                              window: Optional[int] = None) -> SegmentOutput:
    """O(W)-per-point windowed reference for :func:`disjoint_segment`.

    Retightens by an exact masked reduction over the current run's window
    (all run points), which equals the hull pivot search because the
    binding extremum over the hull equals the extremum over all points
    (DESIGN.md §3).  Kept as the break-position oracle for the amortized
    hull carry; not part of the streaming registry.
    """
    return _segment_offline("disjoint", y, eps, max_run,
                            check_window(max_run, window),
                            impls=_WINDOWED_IMPLS)


@functools.partial(jax.jit, static_argnames=("max_run", "window"))
def linear_segment_windowed(y: jax.Array, eps: jax.Array, max_run: int = 256,
                            window: Optional[int] = None) -> SegmentOutput:
    """O(W)-per-point windowed reference for :func:`linear_segment`."""
    return _segment_offline("linear", y, eps, max_run,
                            check_window(max_run, window),
                            impls=_WINDOWED_IMPLS)


@functools.partial(jax.jit, static_argnames=("max_run", "window"))
def continuous_segment(y: jax.Array, eps: jax.Array, max_run: int = 256,
                       window: Optional[int] = None) -> SegmentOutput:
    """Batched Continuous method (connected polyline, paper §3.3).

    The emitted segmentation is *connected-knot*: consecutive segments
    share their boundary value, i.e. for adjacent breaks ``e < e'`` the
    lines satisfy ``v[e'] - a[e'] * (e' - e) == v[e]`` (up to f32
    rounding), so ``propagate_lines`` reconstructs one polyline.  Knot
    choice is deferred one segment (the paper's extra segment of output
    latency); requires ``max_run >= 2``.
    """
    return _segment_offline("continuous", y, eps, max_run,
                            check_window(max_run, window))


@functools.partial(jax.jit, static_argnames=("max_run", "window"))
def mixed_segment(y: jax.Array, eps: jax.Array, max_run: int = 256,
                  window: Optional[int] = None) -> SegmentOutput:
    """Batched MixedPLA (Luo et al. joint/disjoint trade-off, paper §3.4).

    Stage 1 greedy optimal-disjoint runs; stage 2 merges adjacent runs on
    a joint knot whenever their feasible-value ranges overlap at the
    boundary point.  Breaks followed by a continuity-preserving line are
    joint knots (2 wire fields); the rest are disjoint (3 fields) — see
    ``protocol_engine.protocol_descriptors(knot_kind="mixed")``.
    """
    return _segment_offline("mixed", y, eps, max_run,
                            _ring_size("mixed", max_run, window))


# ---------------------------------------------------------------------------
# Streaming (chunked) API
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SegmenterState:
    """Host-side handle for a chunked segmentation in progress.

    Not a pytree: chunk boundaries are host decisions.  ``carry`` is the
    jitted scan's pytree state (None before the first point / after a
    flush); ``t`` counts consumed points, ``emitted`` counts finalized
    event columns (``emitted == t`` exactly after a flush).
    """

    method: str
    n_streams: int
    max_run: int
    window: Optional[int]
    dtype: Any
    eps: jax.Array            # (S,) in ``dtype``
    t: int = 0
    emitted: int = 0
    carry: Any = None
    # Deferred methods only: host-side buffers of event columns covering
    # absolute positions [emitted, emitted + pend width) that a future
    # event may still target, plus the per-stream determined frontier.
    pend: Any = None          # (brk, a, v) numpy arrays (S, L)
    det: Any = None           # (S,) int64


def init_state(method: str, n_streams: int, eps, *, max_run: int = 256,
               window: Optional[int] = None,
               dtype=jnp.float32) -> SegmenterState:
    """Fresh streaming state for ``n_streams`` rows (no data consumed)."""
    if method not in _METHOD_IMPLS:
        raise ValueError(f"unknown method {method!r}; "
                         f"have {sorted(_METHOD_IMPLS)}")
    if _METHOD_IMPLS[method].windowed:
        W = _ring_size(method, max_run, window)
    elif window is not None:
        raise ValueError(f"method {method!r} takes no window")
    else:
        W = None
    eps = jnp.broadcast_to(jnp.asarray(eps, dtype), (n_streams,))
    return SegmenterState(method=method, n_streams=n_streams,
                          max_run=max_run, window=W, dtype=dtype, eps=eps)


def _chunk_ts(impl, t0, first: int, n: int, dtype):
    ts = t0 + jnp.arange(first, n, dtype=jnp.int32)
    return ts if impl.int_ts else ts.astype(dtype)


def _pow2_pieces(n: int) -> list[int]:
    """Decompose a chunk width into descending powers of two.

    step_chunk feeds each piece through its own jitted launch, so the
    trace set of the streaming scans is bounded by log2 distinct widths
    instead of one trace per odd chunk size.  Pieces are consecutive time
    slices threading the same carry, so outputs are bit-identical to a
    single launch by the carry contract.
    """
    return [1 << i for i in range(n.bit_length() - 1, -1, -1) if n >> i & 1]


@functools.partial(jax.jit, static_argnames=("method", "max_run", "window"))
def _stream_start(method, max_run, window, y_chunk, eps, t0):
    impl = _METHOD_IMPLS[method]
    carry = impl.init(y_chunk[:, 0], eps, max_run, window, t0)
    ts = _chunk_ts(impl, t0, 1, y_chunk.shape[1], y_chunk.dtype)
    step = functools.partial(impl.step, eps, max_run, window)
    carry, (brk, a, v) = jax.lax.scan(
        step, carry, (ts, y_chunk[:, 1:].T),
        unroll=_scan_unroll(method, y_chunk.shape[1] - 1))
    return carry, SegmentOutput(brk.T, a.T, v.T)


@functools.partial(jax.jit, static_argnames=("method", "max_run", "window"))
def _stream_cont(method, max_run, window, carry, y_chunk, eps, t0):
    impl = _METHOD_IMPLS[method]
    ts = _chunk_ts(impl, t0, 0, y_chunk.shape[1], y_chunk.dtype)
    step = functools.partial(impl.step, eps, max_run, window)
    carry, (brk, a, v) = jax.lax.scan(
        step, carry, (ts, y_chunk.T),
        unroll=_scan_unroll(method, y_chunk.shape[1]))
    return carry, SegmentOutput(brk.T, a.T, v.T)


@functools.partial(jax.jit, static_argnames=("method", "max_run", "window"))
def _stream_flush(method, max_run, window, carry, t_last):
    a_f, v_f = _METHOD_IMPLS[method].flush(carry, t_last)
    S = a_f.shape[0]
    return SegmentOutput(jnp.ones((S, 1), bool), a_f[:, None], v_f[:, None])


@functools.partial(jax.jit, static_argnames=("method", "max_run", "window"))
def _dstream_start(method, max_run, window, y_chunk, eps, t0):
    impl = _METHOD_IMPLS[method]
    carry = impl.init(y_chunk[:, 0], eps, max_run, window, t0)
    ts = t0 + jnp.arange(1, y_chunk.shape[1], dtype=jnp.int32)
    step = functools.partial(impl.step, eps, max_run, window)
    carry, evs = jax.lax.scan(
        step, carry, (ts, y_chunk[:, 1:].T),
        unroll=_scan_unroll(method, y_chunk.shape[1] - 1))
    return carry, tuple(e.T for e in evs)


@functools.partial(jax.jit, static_argnames=("method", "max_run", "window"))
def _dstream_cont(method, max_run, window, carry, y_chunk, eps, t0):
    impl = _METHOD_IMPLS[method]
    ts = t0 + jnp.arange(y_chunk.shape[1], dtype=jnp.int32)
    step = functools.partial(impl.step, eps, max_run, window)
    carry, evs = jax.lax.scan(
        step, carry, (ts, y_chunk.T),
        unroll=_scan_unroll(method, y_chunk.shape[1]))
    return carry, tuple(e.T for e in evs)


@functools.partial(jax.jit, static_argnames=("method", "max_run", "window"))
def _dstream_flush(method, max_run, window, carry, eps, t_last):
    return _METHOD_IMPLS[method].flush(eps, max_run, window, carry, t_last)


def release_deferred(pend, det, released: int, t_new: int, batches,
                      flush_tail):
    """Shared pending-buffer engine for deferred-event streaming (used by
    this module's chunked API and by ``kernels.ops.StreamingSegmenter``).

    ``pend`` is the ``(brk, a, v)`` numpy buffer triple covering absolute
    positions ``[released, released + width)``; ``det`` the per-stream
    determined frontier; ``batches`` yields ``(ev, pos, a, v)`` event
    batches with **absolute** positions.  ``flush_tail = (a2, v2)`` forces
    the final column at ``t_new - 1`` and releases everything; otherwise
    only the prefix no future event can target (min frontier) is
    released.  Returns ``(out, pend', det', released')``.
    """
    pend_brk, pend_a, pend_v = pend
    S = pend_brk.shape[0]
    grow = t_new - released - pend_brk.shape[1]
    if grow > 0:
        z = np.zeros((S, grow))
        pend_brk = np.concatenate([pend_brk, z.astype(bool)], axis=1)
        pend_a = np.concatenate([pend_a, z.astype(pend_a.dtype)], axis=1)
        pend_v = np.concatenate([pend_v, z.astype(pend_v.dtype)], axis=1)
    det = det.copy()
    for ev, pos, ea, ev_v in batches:
        ev = np.asarray(ev, bool)
        if ev.size == 0 or not ev.any():
            continue
        pos = np.asarray(pos).astype(np.int64)
        ss, jj = np.nonzero(ev)
        cols = pos[ss, jj] - released
        pend_brk[ss, cols] = True
        pend_a[ss, cols] = np.asarray(ea)[ss, jj]
        pend_v[ss, cols] = np.asarray(ev_v)[ss, jj]
        np.maximum.at(det, ss, pos[ss, jj] + 1)
    if flush_tail is not None:
        a2, v2 = flush_tail
        last = t_new - 1 - released
        pend_brk[:, last] = True
        pend_a[:, last] = np.asarray(a2)[:S]
        pend_v[:, last] = np.asarray(v2)[:S]
        release = t_new - released
        det[:] = t_new
    else:
        release = max(int(det.min()) - released, 0)
    out = SegmentOutput(jnp.asarray(pend_brk[:, :release]),
                        jnp.asarray(pend_a[:, :release]),
                        jnp.asarray(pend_v[:, :release]))
    pend = (pend_brk[:, release:], pend_a[:, release:], pend_v[:, release:])
    return out, pend, det, released + release


def _deferred_release(state: SegmenterState, evs, n_consumed: int,
                      flush_evs=None) -> tuple[SegmenterState, SegmentOutput]:
    """Scatter new events into the pending buffers; release the prefix no
    future event can target (everything on flush)."""
    S = state.n_streams
    t_new = state.t + n_consumed
    if state.pend is None:
        dtype = np.asarray(state.eps).dtype
        pend = (np.zeros((S, 0), bool), np.zeros((S, 0), dtype),
                np.zeros((S, 0), dtype))
        det = np.full((S,), state.emitted, np.int64)
    else:
        pend, det = state.pend, state.det
    batches = list(evs or [])  # jnp-engine events: positions are absolute
    flush_tail = None
    if flush_evs is not None:
        (ev1, p1, a1, v1), flush_tail = flush_evs
        batches.append((np.asarray(ev1)[:, None], np.asarray(p1)[:, None],
                        np.asarray(a1)[:, None], np.asarray(v1)[:, None]))
    out, pend, det, released = release_deferred(pend, det, state.emitted,
                                                 t_new, batches, flush_tail)
    return dataclasses.replace(state, t=t_new, emitted=released,
                               pend=pend, det=det), out


def step_chunk(state: SegmenterState, y_chunk: jax.Array
               ) -> tuple[SegmenterState, SegmentOutput]:
    """Consume ``y_chunk: (S, n)``; return the newly finalized events.

    The returned :class:`SegmentOutput` has width ``n`` (``n - 1`` for the
    first chunk of a stream) and covers the absolute positions
    ``[state.emitted, state.emitted + width)``.  For the deferred methods
    (``DEFERRED_METHODS``) the width is data-dependent (possibly zero):
    only positions no future event can target are released; the coverage
    contract ``[state.emitted, state.emitted + width)`` is unchanged.
    """
    y = jnp.asarray(y_chunk, state.dtype)
    if y.ndim != 2 or y.shape[0] != state.n_streams:
        raise ValueError(f"chunk must be ({state.n_streams}, n); "
                         f"got {y.shape}")
    if y.shape[1] == 0:
        raise ValueError("chunk must contain at least one point")
    if state.t + y.shape[1] > MAX_STREAM_T:
        raise ValueError(
            f"stream would reach {state.t + y.shape[1]} points on this "
            f"SegmenterState, past the 2^24 absolute-time limit of the "
            f"jnp reference segmenters (positions stop being exact in "
            f"float32 and events would silently corrupt).  Start a fresh "
            f"state (init_state) to rebase time — flush() does NOT "
            f"rebase, positions stay absolute for record bookkeeping — "
            f"or use the Pallas kernels "
            f"(repro.kernels.ops.StreamingSegmenter), which renumber "
            f"time per launch and have no such limit.")
    # Feed the chunk as consecutive power-of-two pieces threading the same
    # carry, so odd-sized chunks stop retracing the scans: at most
    # log2(max chunk) traces per variant, and outputs stay bit-identical
    # to a single launch by the carry contract.
    n = y.shape[1]
    deferred = _METHOD_IMPLS[state.method].deferred
    carry = state.carry
    t, lo = state.t, 0
    outs, ev_batches = [], []
    for w in _pow2_pieces(n):
        piece = y[:, lo:lo + w]
        t0 = jnp.asarray(t, jnp.int32)
        if deferred:
            if carry is None:
                carry, evs = _dstream_start(state.method, state.max_run,
                                            state.window, piece, state.eps,
                                            t0)
            else:
                carry, evs = _dstream_cont(state.method, state.max_run,
                                           state.window, carry, piece,
                                           state.eps, t0)
            ev_batches.append(evs)
        else:
            if carry is None:
                carry, out = _stream_start(state.method, state.max_run,
                                           state.window, piece, state.eps,
                                           t0)
            else:
                carry, out = _stream_cont(state.method, state.max_run,
                                          state.window, carry, piece,
                                          state.eps, t0)
            outs.append(out)
        t += w
        lo += w
    if deferred:
        new, out = _deferred_release(state, ev_batches, n)
        return dataclasses.replace(new, carry=carry), out
    if len(outs) == 1:
        out = outs[0]
    else:
        out = SegmentOutput(*(jnp.concatenate(parts, axis=1)
                              for parts in zip(*outs)))
    new = dataclasses.replace(state, t=state.t + n,
                              emitted=state.emitted + out.breaks.shape[1],
                              carry=carry)
    return new, out


def flush(state: SegmenterState) -> tuple[SegmenterState, SegmentOutput]:
    """Close the trailing run: one forced-break event at position t-1.

    The returned state has no carry — the next :func:`step_chunk` starts a
    fresh stream at absolute position ``state.t``.  Deferred methods
    return every still-buffered column plus up to two closing events (the
    pending segment and the trailing one) instead of a single column.
    """
    if state.carry is None:
        raise ValueError("flush with no open run (no data since last flush)")
    if _METHOD_IMPLS[state.method].deferred:
        flush_evs = _dstream_flush(state.method, state.max_run, state.window,
                                   state.carry, state.eps,
                                   jnp.asarray(state.t - 1, jnp.int32))
        new, out = _deferred_release(state, None, 0, flush_evs=flush_evs)
        return dataclasses.replace(new, carry=None), out
    out = _stream_flush(state.method, state.max_run, state.window,
                        state.carry, jnp.asarray(state.t - 1, jnp.int32))
    new = dataclasses.replace(state, carry=None, emitted=state.emitted + 1)
    return new, out


# ---------------------------------------------------------------------------
# Masked streaming: per-row local time over a fixed slot plane
#
# The serving front-end (repro.serving) multiplexes short-lived streams
# onto a fixed (S_pad,) slot batch: every tick pushes one (S, n) plane in
# which row s only has ``lengths[s] <= n`` fresh points, and rows are
# admitted/evicted out of phase.  The lockstep API above cannot express
# that — its scan walks one shared absolute clock.  The masked API gives
# every row its own local clock (``pos``, starting at 0 at admission):
#
# - a column j is a no-op for row s when ``j >= lengths[s]`` (the carry
#   row passes through unchanged, no event, no clock tick);
# - the first valid point of a not-yet-started row routes through
#   ``impl.init`` — a fresh carry row is written over whatever the slot
#   held before, which is what makes slot recycling structurally
#   leak-proof (there is no reset-then-hope: every admission rebuilds the
#   row from its own first point);
# - ``masked_flush_rows`` closes selected rows (eviction) and resets them
#   to zeroed never-started rows.
#
# Bit-identity contract: the per-method steps only consume time through
# differences bounded by the run cap (see the anchored-time note in the
# module docstring), and the masked scan runs at ``unroll=1``, so a row
# admitted mid-flight and fed its points over any tick partition emits
# exactly the events of a fresh lockstep run of that row's own data —
# verified per method in tests/test_serving.py.  Positions in
# ``MaskedEvents.pos`` are row-local (0 = the row's first point since
# admission).  The deferred methods (continuous/mixed) are rejected:
# their release frontier is a global min over rows, which a half-masked
# batch would stall indefinitely.
# ---------------------------------------------------------------------------


class MaskedEvents(NamedTuple):
    ev: jax.Array    # (S, n) bool — finalized event in this column
    pos: jax.Array   # (S, n) int32 — row-local event position (where ev)
    a: jax.Array     # (S, n) — slope, valid where ev
    v: jax.Array     # (S, n) — line value at the event position, where ev


@dataclasses.dataclass
class MaskedSegmenterState:
    """Host-side handle for a masked (per-row-clock) segmentation.

    Unlike :class:`SegmenterState`, ``carry`` is always materialized
    (zero rows before first data) so that admission/eviction never
    changes the jit shape; ``started`` marks rows with >= 1 consumed
    point and ``pos`` counts each row's consumed points since its last
    reset.  ``pos_host`` mirrors ``pos`` on the host — it is fully
    determined by the lengths fed so far, and lets the per-chunk
    ``MAX_STREAM_T`` validation run without materializing the device
    value (which would block on the row's previous launch and serialize
    multi-shard dispatch)."""

    method: str
    n_streams: int
    max_run: int
    window: Optional[int]
    dtype: Any
    eps: jax.Array            # (S,) in ``dtype``
    carry: Any
    started: jax.Array        # (S,) bool
    pos: jax.Array            # (S,) int32
    pos_host: np.ndarray      # (S,) int64, host twin of ``pos``


def _row_mask(mask, leaf):
    """Broadcast an (S,) row mask against an (S, ...) carry leaf."""
    return mask.reshape(mask.shape + (1,) * (leaf.ndim - 1))


def masked_init_state(method: str, n_streams: int, eps, *,
                      max_run: int = 256, window: Optional[int] = None,
                      dtype=jnp.float32) -> MaskedSegmenterState:
    """Fresh masked streaming state: all rows empty, carry materialized."""
    if method not in _METHOD_IMPLS:
        raise ValueError(f"unknown method {method!r}; "
                         f"have {sorted(_METHOD_IMPLS)}")
    impl = _METHOD_IMPLS[method]
    if impl.deferred:
        raise ValueError(
            f"method {method!r} emits deferred events whose release "
            f"frontier is a min over all rows — a masked batch would "
            f"stall it; serve deferred methods on dedicated lockstep "
            f"fleets (SegmenterState) instead")
    if impl.windowed:
        W = _ring_size(method, max_run, window)
    elif window is not None:
        raise ValueError(f"method {method!r} takes no window")
    else:
        W = None
    eps = jnp.broadcast_to(jnp.asarray(eps, dtype), (n_streams,))
    carry = impl.init(jnp.zeros((n_streams,), dtype), eps, max_run, W, 0)
    return MaskedSegmenterState(
        method=method, n_streams=n_streams, max_run=max_run, window=W,
        dtype=dtype, eps=eps, carry=carry,
        started=jnp.zeros((n_streams,), bool),
        pos=jnp.zeros((n_streams,), jnp.int32),
        pos_host=np.zeros((n_streams,), np.int64))


@functools.partial(jax.jit, static_argnames=("method", "max_run", "window"))
def _masked_scan(method, max_run, window, carry, started, pos, eps,
                 y_chunk, lengths):
    impl = _METHOD_IMPLS[method]
    dtype = y_chunk.dtype

    def body(st, inp):
        carry, started, pos = st
        j, y_j = inp
        valid = j < lengths
        t_in = pos if impl.int_ts else pos.astype(dtype)
        stepped, (brk, a, v) = impl.step(eps, max_run, window, carry,
                                         (t_in, y_j))
        use_step = valid & started
        carry = jax.tree_util.tree_map(
            lambda s_, c_: jnp.where(_row_mask(use_step, s_), s_, c_),
            stepped, carry)
        use_init = valid & ~started

        def do_init(c):
            fresh = impl.init(y_j, eps, max_run, window, 0)
            return jax.tree_util.tree_map(
                lambda f_, c_: jnp.where(_row_mask(use_init, f_), f_, c_),
                fresh, c)

        # Admissions are rare (one column per admitted row), so the
        # (S, W)-materializing init stays behind a cond.
        carry = jax.lax.cond(jnp.any(use_init), do_init, lambda c: c, carry)
        ev = use_step & brk
        out = (ev, jnp.where(ev, pos - 1, 0), a, v)
        return (carry, started | valid, pos + valid.astype(pos.dtype)), out

    # unroll=1 unconditionally: cross-step fusion of an unrolled body may
    # shift ulps with the scan length, and masked serving relies on
    # tick-partition bit-transparency (see _SCAN_UNROLL).
    n = y_chunk.shape[1]
    (carry, started, pos), (ev, epos, a, v) = jax.lax.scan(
        body, (carry, started, pos),
        (jnp.arange(n, dtype=jnp.int32), y_chunk.T), unroll=1)
    return carry, started, pos, MaskedEvents(ev.T, epos.T, a.T, v.T)


@functools.partial(jax.jit, static_argnames=("method", "max_run", "window"))
def _masked_flush_rows(method, max_run, window, carry, started, pos, eps,
                       mask):
    impl = _METHOD_IMPLS[method]
    dtype = eps.dtype
    t_last = pos - 1
    a_f, v_f = impl.flush(carry, t_last if impl.int_ts
                          else t_last.astype(dtype))
    ev = mask & started
    epos = jnp.where(ev, pos - 1, 0)
    # Evicted rows reset to zeroed never-started rows — stale geometry is
    # structurally unreachable anyway (the next admission re-inits from
    # its own first point), but zeroing keeps slot dumps inspectable.
    fresh = impl.init(jnp.zeros_like(eps), eps, max_run, window, 0)
    carry = jax.tree_util.tree_map(
        lambda f_, c_: jnp.where(_row_mask(mask, f_), f_, c_), fresh, carry)
    return (carry, started & ~mask, jnp.where(mask, 0, pos),
            (ev, epos, a_f, v_f))


def masked_step_chunk(state: MaskedSegmenterState, y_chunk, lengths
                      ) -> tuple[MaskedSegmenterState, MaskedEvents]:
    """Consume an ``(S, n)`` tick plane with per-row valid prefixes.

    Row ``s`` consumes ``y_chunk[s, :lengths[s]]``; its events come back
    tagged with row-local positions.  Like :func:`step_chunk`, wide
    planes are fed as power-of-two pieces threading one carry, so the
    trace set stays logarithmic in the tick width."""
    y = jnp.asarray(y_chunk, state.dtype)
    if y.ndim != 2 or y.shape[0] != state.n_streams:
        raise ValueError(f"tick plane must be ({state.n_streams}, n); "
                         f"got {y.shape}")
    lengths_np = np.asarray(lengths, np.int64)
    if lengths_np.shape != (state.n_streams,):
        raise ValueError(f"lengths must be ({state.n_streams},); "
                         f"got {lengths_np.shape}")
    n = y.shape[1]
    if lengths_np.min() < 0 or lengths_np.max() > n:
        raise ValueError(f"lengths must lie in [0, {n}]")
    # Validate against the host mirror — np.asarray(state.pos) would
    # synchronize on this shard's previous launch and serialize the
    # caller's multi-shard dispatch loop (SlotManager.step's contract).
    pos_np = state.pos_host
    if (pos_np + lengths_np).max() > MAX_STREAM_T:
        raise ValueError(
            f"a row would reach {(pos_np + lengths_np).max()} points "
            f"since its admission, past the 2^24 local-time limit of the "
            f"jnp segmenters; evict and re-admit the stream to rebase "
            f"its clock")
    if n == 0 or lengths_np.max() == 0:
        z = jnp.zeros((state.n_streams, 0))
        return state, MaskedEvents(z.astype(bool), z.astype(jnp.int32),
                                   z.astype(state.dtype),
                                   z.astype(state.dtype))
    lengths = jnp.asarray(lengths_np, jnp.int32)
    carry, started, pos = state.carry, state.started, state.pos
    outs, lo = [], 0
    for w in _pow2_pieces(n):
        carry, started, pos, out = _masked_scan(
            state.method, state.max_run, state.window, carry, started, pos,
            state.eps, y[:, lo:lo + w],
            jnp.clip(lengths - lo, 0, w))
        outs.append(out)
        lo += w
    if len(outs) > 1:
        out = MaskedEvents(*(jnp.concatenate(parts, axis=1)
                             for parts in zip(*outs)))
    else:
        out = outs[0]
    new = dataclasses.replace(state, carry=carry, started=started, pos=pos,
                              pos_host=pos_np + lengths_np)
    return new, out


def masked_flush_rows(state: MaskedSegmenterState, rows
                      ) -> tuple[MaskedSegmenterState, tuple]:
    """Close the trailing run of the selected rows (eviction).

    ``rows`` is an (S,) bool mask.  Returns the updated state (selected
    rows zeroed and never-started) and one event column ``(ev, pos, a,
    v)``: a forced break at each closed row's last local position (rows
    that never consumed a point emit nothing)."""
    mask_np = np.asarray(rows, bool)
    mask = jnp.asarray(mask_np)
    carry, started, pos, evs = _masked_flush_rows(
        state.method, state.max_run, state.window, state.carry,
        state.started, state.pos, state.eps, mask)
    new = dataclasses.replace(state, carry=carry, started=started, pos=pos,
                              pos_host=np.where(mask_np, 0, state.pos_host))
    return new, evs


def masked_set_eps(state: MaskedSegmenterState, eps) -> MaskedSegmenterState:
    """Swap the per-row ε plane (traced — no recompile)."""
    eps = jnp.broadcast_to(jnp.asarray(eps, state.dtype),
                           (state.n_streams,))
    return dataclasses.replace(state, eps=eps)


# ---------------------------------------------------------------------------
# Reconstruction and record framing
# ---------------------------------------------------------------------------

@jax.jit
def propagate_lines(seg: SegmentOutput) -> jax.Array:
    """Per-point reconstruction: each point uses the line of the segment
    that ends at the next break at-or-after it (reverse scan), evaluated in
    the anchored form ``v + a * (t - t_break)``."""
    breaks, a, v = seg
    S, T = a.shape
    dtype = a.dtype

    def back(carry, inp):
        ca, cv, cd = carry  # slope, value at anchor, distance to anchor
        brk, at, vt = inp
        ca = jnp.where(brk, at, ca)
        cv = jnp.where(brk, vt, cv)
        cd = jnp.where(brk, jnp.zeros_like(cd), cd)
        out = cv - ca * cd
        return (ca, cv, cd + 1.0), out

    init = (a[:, T - 1], v[:, T - 1], jnp.zeros((S,), dtype))
    _, out = jax.lax.scan(back, init,
                          (breaks.T[::-1], a.T[::-1], v.T[::-1]))
    return out[::-1].T


class PLARecords(NamedTuple):
    """Fixed-slot record form for shape-static collectives/storage.

    ``seg_end[s, k]`` = absolute index of the last point of segment k
    (padded by repeating the final segment); lines are anchored there:
    ``y(t) = v[k] + a[k] * (t - seg_end[k])``.  ``count`` = true number of
    segments; ``overflow`` = row had more than K segments (its tail is
    covered by extending slot K-1's line — callers relying on the eps
    guarantee must check/react).
    """

    seg_end: jax.Array  # (S, K) int32
    a: jax.Array        # (S, K)
    v: jax.Array        # (S, K)
    count: jax.Array    # (S,) int32
    overflow: jax.Array  # (S,) bool


def _records_pad(idx, ak, vk, count, k_max, t_len):
    """Canonical padding of :func:`to_records`: slots past the last real
    segment repeat it; overflow rows pin slot K-1 to t-1."""
    kk = jnp.arange(k_max)[None, :]
    last = jnp.clip(count - 1, 0, k_max - 1)[:, None]
    src = jnp.minimum(kk, last).astype(jnp.int32)
    idx = jnp.take_along_axis(idx, src, axis=1)
    ak = jnp.take_along_axis(ak, src, axis=1)
    vk = jnp.take_along_axis(vk, src, axis=1)
    overflow = count > k_max
    idx = idx.at[:, k_max - 1].set(
        jnp.where(overflow, t_len - 1, idx[:, k_max - 1]))
    return PLARecords(idx, ak, vk, jnp.minimum(count, k_max), overflow)


@functools.partial(jax.jit, static_argnames=("k_max",))
def to_records(seg: SegmentOutput, k_max: int) -> PLARecords:
    breaks, a, v = seg
    S, T = a.shape
    count = breaks.sum(axis=1).astype(jnp.int32)

    def row(brk, ar, vr):
        idx = jnp.nonzero(brk, size=k_max, fill_value=T - 1)[0].astype(jnp.int32)
        return idx, ar[idx], vr[idx]

    idx, ak, vk = jax.vmap(row)(breaks, a, v)
    return _records_pad(idx, ak, vk, count, k_max, T)


@functools.partial(jax.jit, static_argnames=("t_len",))
def records_to_events(rec: PLARecords, t_len: int) -> SegmentOutput:
    """Expand canonical fixed-slot records back to (S, T) event arrays.

    The inverse of :func:`to_records` for non-overflowed rows: each valid
    slot scatters a break (and its anchored line) at ``seg_end``.  The
    result feeds the event-form consumers — the Pallas reconstruction
    kernel and the protocol engine — so record buffers can go through the
    same vectorized protocol/metrics/reconstruction paths as fresh
    segmentations.
    Overflowed rows reconstruct their covered prefix exactly; the tail
    extends slot K-1's line (same contract as :func:`decode_records`).
    """
    S, K = rec.seg_end.shape
    rows = jnp.arange(S)[:, None]
    valid = jnp.arange(K)[None, :] < rec.count[:, None]
    slot = jnp.where(valid, rec.seg_end, t_len)  # invalid -> dropped
    breaks = jnp.zeros((S, t_len), bool).at[rows, slot].set(
        True, mode="drop")
    a = jnp.zeros((S, t_len), rec.a.dtype).at[rows, slot].set(
        rec.a, mode="drop")
    v = jnp.zeros((S, t_len), rec.v.dtype).at[rows, slot].set(
        rec.v, mode="drop")
    # Canonical form ends every stream with a break; rows whose last
    # segment ends early (overflow) extend that segment's line.
    last = jnp.clip(rec.count - 1, 0, K - 1)
    last_end = jnp.take_along_axis(rec.seg_end, last[:, None], axis=1)
    last_a = jnp.take_along_axis(rec.a, last[:, None], axis=1)
    last_v = jnp.take_along_axis(rec.v, last[:, None], axis=1)
    open_tail = (last_end < t_len - 1)
    breaks = breaks.at[:, t_len - 1].set(True)
    a = a.at[:, t_len - 1].set(
        jnp.where(open_tail[:, 0], last_a[:, 0], a[:, t_len - 1]))
    v = v.at[:, t_len - 1].set(jnp.where(
        open_tail[:, 0],
        last_v[:, 0] + last_a[:, 0]
        * (t_len - 1 - last_end[:, 0]).astype(rec.v.dtype),
        v[:, t_len - 1]))
    return SegmentOutput(breaks, a, v)


@functools.partial(jax.jit, static_argnames=("t_len",))
def decode_records(rec: PLARecords, t_len: int) -> jax.Array:
    """Reconstruct (S, T) values from fixed-slot records."""
    t = jnp.arange(t_len, dtype=jnp.int32)

    def row(seg_end, a, v):
        j = jnp.searchsorted(seg_end, t, side="left")
        j = jnp.clip(j, 0, seg_end.shape[0] - 1)
        dt = (t - seg_end[j]).astype(a.dtype)   # <= 0, small
        return v[j] + a[j] * dt

    return jax.vmap(row)(rec.seg_end, rec.a, rec.v)

