"""Public jit'd wrappers around the PLA Pallas kernels.

These accept the framework's natural ``(S, T)`` stream layout (float32),
handle padding/transposition at the boundary, and return the same
:class:`repro.core.jax_pla.SegmentOutput` structure as the pure-jnp
reference implementations in :mod:`repro.kernels.ref` — the kernels are
drop-in replacements validated by ``tests/test_kernels.py``.

:class:`StreamingSegmenter` is the chunked front-end to the same kernels:
it owns host-side buffering to time-block multiples, the carry-state
handoff between launches (including the ring-roll / run-start renumbering
of the windowed methods — see the carry contract in
:mod:`repro.kernels.common`), and the trailing-run flush, so a stream can
be pushed in chunks of any size with output bit-identical to the one-shot
offline call.

On non-TPU backends the kernels execute in Pallas ``interpret`` mode
(bit-accurate kernel-body semantics, Python speed) so the whole framework
remains runnable and testable on CPU.
"""

from __future__ import annotations

import functools
from typing import List, Optional

import jax
import jax.numpy as jnp

import numpy as np

from repro.core.jax_pla import (PLARecords, SegmentOutput, _pow2_pieces,
                                check_window, records_to_events,
                                release_deferred, assemble_deferred_events)
from .angle import angle_init_carry, angle_pallas, angle_shift_carry
from .swing import swing_init_carry, swing_pallas, swing_shift_carry
from .common import BLOCK_S, BLOCK_T, assemble_segments, pad_streams
from .continuous import (cont_init_carry, cont_shift_carry,
                         continuous_flush_carry, continuous_pallas)
from .disjoint import (disjoint_init_carry, disjoint_pallas,
                       disjoint_shift_carry)
from .linear import linear_init_carry, linear_pallas, linear_shift_carry
from .mixed import (mixed_flush_carry, mixed_init_carry, mixed_pallas,
                    mixed_shift_carry)
from .reconstruct import reconstruct_error_pallas, reconstruct_pallas

__all__ = ["angle_segment_tpu", "swing_segment_tpu",
           "disjoint_segment_tpu", "linear_segment_tpu",
           "continuous_segment_tpu", "mixed_segment_tpu",
           "reconstruct_tpu", "reconstruct_error_tpu",
           "reconstruct_records_tpu", "KERNEL_SEGMENTERS",
           "DEFERRED_KERNELS", "StreamingSegmenter"]


def _run(kernel_fn, y, eps, max_run, block_s, block_t, **kw):
    y = jnp.asarray(y, jnp.float32)
    yp, S, T = pad_streams(y, block_s, block_t)
    ev_brk, ev_a, ev_b, _ = kernel_fn(yp.T, eps=float(eps), t_real=T,
                                      max_run=max_run, block_s=block_s,
                                      block_t=block_t, **kw)
    return assemble_segments(ev_brk, ev_a, ev_b, S, T)


@functools.partial(jax.jit, static_argnames=("eps", "max_run", "block_s",
                                             "block_t"))
def swing_segment_tpu(y: jax.Array, eps: float, max_run: int = 256,
                      block_s: int = BLOCK_S, block_t: int = BLOCK_T
                      ) -> SegmentOutput:
    """SwingFilter PLA segmentation of (S, T) streams via the Pallas kernel."""
    return _run(swing_pallas, y, eps, max_run, block_s, block_t)


@functools.partial(jax.jit, static_argnames=("eps", "max_run", "block_s",
                                             "block_t"))
def angle_segment_tpu(y: jax.Array, eps: float, max_run: int = 256,
                      block_s: int = BLOCK_S, block_t: int = BLOCK_T
                      ) -> SegmentOutput:
    """Angle PLA segmentation of (S, T) streams via the Pallas kernel."""
    return _run(angle_pallas, y, eps, max_run, block_s, block_t)


@functools.partial(jax.jit, static_argnames=("eps", "max_run", "window",
                                             "block_s", "block_t"))
def disjoint_segment_tpu(y: jax.Array, eps: float, max_run: int = 256,
                         window: Optional[int] = None,
                         block_s: int = BLOCK_S, block_t: int = BLOCK_T
                         ) -> SegmentOutput:
    """Optimal-disjoint PLA segmentation via the Pallas kernel."""
    return _run(disjoint_pallas, y, eps, max_run, block_s, block_t,
                window=window)


@functools.partial(jax.jit, static_argnames=("eps", "max_run", "window",
                                             "block_s", "block_t"))
def linear_segment_tpu(y: jax.Array, eps: float, max_run: int = 256,
                       window: Optional[int] = None,
                       block_s: int = BLOCK_S, block_t: int = BLOCK_T
                       ) -> SegmentOutput:
    """Best-fit (Linear) PLA segmentation via the Pallas kernel."""
    return _run(linear_pallas, y, eps, max_run, block_s, block_t,
                window=window)


def assemble_deferred(ev, pos, ea, ev_v, flush_evs, S: int, T: int
                      ) -> SegmentOutput:
    """Scatter a deferred kernel's position-tagged events (time-major
    ``(Tp, Sp)``, launch-local positions == absolute for the offline call)
    plus the host-flush events into canonical (S, T) SegmentOutput.  Thin
    transposer over the shared ``jax_pla.assemble_deferred_events``."""
    return assemble_deferred_events(S, T, jnp.float32,
                                    ev.T[:S].astype(bool), pos.T[:S],
                                    ea.T[:S], ev_v.T[:S], flush_evs)


def _run_deferred(method, y, eps, max_run, window, block_s, block_t):
    kernel_fn, _, _, flush_fn = DEFERRED_KERNELS[method]
    y = jnp.asarray(y, jnp.float32)
    yp, S, T = pad_streams(y, block_s, block_t)
    W = check_window(max_run, window)
    ev, pos, ea, ev_v, carry = kernel_fn(
        yp.T, eps=float(eps), t_stop=T, max_run=max_run, window=W,
        block_s=block_s, block_t=block_t)
    flush_evs = flush_fn(carry, float(eps), max_run, W, T - 1)
    return assemble_deferred(ev, pos, ea, ev_v, flush_evs, S, T)


@functools.partial(jax.jit, static_argnames=("eps", "max_run", "window",
                                             "block_s", "block_t"))
def continuous_segment_tpu(y: jax.Array, eps: float, max_run: int = 256,
                           window: Optional[int] = None,
                           block_s: int = BLOCK_S, block_t: int = BLOCK_T
                           ) -> SegmentOutput:
    """Continuous (connected-polyline) PLA via the deferred Pallas kernel."""
    return _run_deferred("continuous", y, eps, max_run, window,
                         block_s, block_t)


@functools.partial(jax.jit, static_argnames=("eps", "max_run", "window",
                                             "block_s", "block_t"))
def mixed_segment_tpu(y: jax.Array, eps: float, max_run: int = 256,
                      window: Optional[int] = None,
                      block_s: int = BLOCK_S, block_t: int = BLOCK_T
                      ) -> SegmentOutput:
    """MixedPLA (joint/disjoint merge) via the deferred Pallas kernel."""
    return _run_deferred("mixed", y, eps, max_run, window, block_s, block_t)


@functools.partial(jax.jit, static_argnames=("block_s", "block_t"))
def reconstruct_tpu(seg: SegmentOutput, block_s: int = BLOCK_S,
                    block_t: int = BLOCK_T) -> jax.Array:
    """Per-point reconstruction of (S, T) streams via the Pallas kernel."""
    brk_p, a_p, b_p, S, T, Sp, Tp = _pad_events(seg, block_s, block_t)
    out, _ = reconstruct_pallas(brk_p.T, a_p.T, b_p.T,
                                block_s=block_s, block_t=block_t)
    return out.T[:S, :T]


def _pad_events(seg: SegmentOutput, block_s: int, block_t: int):
    """Pad (S, T) event arrays to the block grid (padded tail: all
    breaks on the zero line, sliced off by the caller)."""
    breaks, a, b = seg
    S, T = a.shape
    Sp = (S + block_s - 1) // block_s * block_s
    Tp = (T + block_t - 1) // block_t * block_t

    def pad(x, fill):
        out = jnp.full((Sp, Tp), fill, x.dtype)
        return out.at[:S, :T].set(x)

    return (pad(breaks.astype(jnp.int32), 1), pad(a.astype(jnp.float32), 0.0),
            pad(b.astype(jnp.float32), 0.0), S, T, Sp, Tp)


@functools.partial(jax.jit, static_argnames=("block_s", "block_t"))
def reconstruct_error_tpu(seg: SegmentOutput, y: jax.Array,
                          block_s: int = BLOCK_S, block_t: int = BLOCK_T
                          ) -> tuple[jax.Array, jax.Array]:
    """Fused per-point reconstruction + |error| of (S, T) streams.

    One kernel pass returns ``(y_hat, |y_hat - y|)`` — the reconstruction
    and the §4.2 approximation-error surface consumed by the batched
    protocol metrics (singleton/burst masking happens protocol-side).
    """
    brk_p, a_p, b_p, S, T, Sp, Tp = _pad_events(seg, block_s, block_t)
    y_p = jnp.zeros((Sp, Tp), jnp.float32).at[:S, :T].set(
        y.astype(jnp.float32))
    out, err, _ = reconstruct_error_pallas(brk_p.T, a_p.T, b_p.T, y_p.T,
                                           block_s=block_s, block_t=block_t)
    return out.T[:S, :T], err.T[:S, :T]


@functools.partial(jax.jit, static_argnames=("t_len", "block_s", "block_t"))
def reconstruct_records_tpu(rec: PLARecords, t_len: int,
                            block_s: int = BLOCK_S, block_t: int = BLOCK_T
                            ) -> jax.Array:
    """Reconstruct (S, t_len) values from fixed-slot records via the
    Pallas kernel (the device-resident alternative to
    :func:`repro.core.jax_pla.decode_records` for serving paths that
    already run the kernels)."""
    return reconstruct_tpu(records_to_events(rec, t_len),
                           block_s=block_s, block_t=block_t)


KERNEL_SEGMENTERS = {
    "swing": swing_segment_tpu,
    "angle": angle_segment_tpu,
    "disjoint": disjoint_segment_tpu,
    "linear": linear_segment_tpu,
    "continuous": continuous_segment_tpu,
    "mixed": mixed_segment_tpu,
}

# Deferred kernels: (kernel fn, init_carry(Sp, W), shift_carry(carry, m),
# flush(carry, eps, max_run, W, t_last)).  Their events carry launch-local
# positions and the trailing flush runs on the host from the carry.
DEFERRED_KERNELS = {
    "continuous": (continuous_pallas, cont_init_carry, cont_shift_carry,
                   lambda carry, eps, max_run, w, t_last:
                   continuous_flush_carry(carry, window=w, t_last=t_last)),
    "mixed": (mixed_pallas, mixed_init_carry, mixed_shift_carry,
              lambda carry, eps, max_run, w, t_last: mixed_flush_carry(
                  carry, eps=eps, max_run=max_run, window=w, t_last=t_last)),
}


# ---------------------------------------------------------------------------
# Chunked streaming front-end
# ---------------------------------------------------------------------------

# method -> (kernel fn, init_carry(Sp, W), shift_carry(carry, m), windowed)
_STREAM_KERNELS = {
    "angle": (angle_pallas, lambda sp, w: angle_init_carry(sp),
              angle_shift_carry, False),
    "swing": (swing_pallas, lambda sp, w: swing_init_carry(sp),
              swing_shift_carry, False),
    "disjoint": (disjoint_pallas, disjoint_init_carry,
                 disjoint_shift_carry, True),
    "linear": (linear_pallas, linear_init_carry,
               linear_shift_carry, True),
    "continuous": (continuous_pallas, cont_init_carry,
                   cont_shift_carry, True),
    "mixed": (mixed_pallas, mixed_init_carry, mixed_shift_carry, True),
}


class StreamingSegmenter:
    """Push ``(S, n)`` chunks through a Pallas segmenter kernel.

    The class owns everything chunking needs around the raw kernel: it
    buffers incoming columns until a whole number of ``block_t`` time
    blocks is available (the kernel must not consume padding mid-stream),
    launches pow2-sized pieces with the packed carry state threaded in
    and out (bounding the kernel trace set by log2 of the widest push
    instead of one trace per odd chunk size), renumbers
    position-dependent carry rows between launches, and finally pads +
    force-breaks the remainder so the trailing run flushes through the
    regular event path.

    ``push`` returns the newly finalized event columns as a
    :class:`SegmentOutput` (possibly width-0 while columns are buffering);
    ``finish`` returns the last columns.  Concatenating every ``push``
    output plus the ``finish`` output is bit-identical to the one-shot
    ``KERNEL_SEGMENTERS[method](y, eps, ...)`` call on the whole stream.

    The deferred kernels (continuous / mixed) emit position-tagged events
    one segment in the past, so their ``push`` output width is
    data-dependent: columns are buffered host-side and released only once
    no future event can target them (``finish`` releases the rest).  The
    trailing flush runs on the host from the carry (the same jitted math
    as the offline wrappers), not through an in-kernel forced break.
    """

    def __init__(self, method: str, n_streams: int, eps: float, *,
                 max_run: int = 256, window: Optional[int] = None,
                 block_s: int = BLOCK_S, block_t: int = BLOCK_T):
        if method not in _STREAM_KERNELS:
            raise ValueError(f"unknown method {method!r}; "
                             f"have {sorted(_STREAM_KERNELS)}")
        kernel_fn, init_carry, shift_carry, windowed = _STREAM_KERNELS[method]
        self.method = method
        self.n_streams = n_streams
        self.eps = float(eps)
        self.max_run = max_run
        self.block_s = block_s
        self.block_t = block_t
        self._sp = (n_streams + block_s - 1) // block_s * block_s
        self._kernel_fn = kernel_fn
        self._shift = shift_carry
        self._kw = {}
        self.window = None
        if windowed:
            self.window = check_window(max_run, window)
            self._kw["window"] = self.window
        elif window is not None:
            raise ValueError(f"method {method!r} takes no window")
        self._carry = init_carry(self._sp, self.window)
        self._pend: List[jax.Array] = []
        self._navail = 0      # buffered, not yet fed to the kernel
        self._t = 0           # columns consumed by the kernel
        self._finished = False
        self._deferred = method in DEFERRED_KERNELS
        if self._deferred:
            self._flush_fn = DEFERRED_KERNELS[method][3]
            self._ev_pend = (np.zeros((n_streams, 0), bool),
                            np.zeros((n_streams, 0), np.float32),
                            np.zeros((n_streams, 0), np.float32))
            self._det = np.zeros((n_streams,), np.int64)
            self._released = 0

    @property
    def pushed(self) -> int:
        """Total stream positions pushed so far."""
        return self._t + self._navail

    def _empty(self) -> SegmentOutput:
        S = self.n_streams
        return SegmentOutput(jnp.zeros((S, 0), bool),
                             jnp.zeros((S, 0), jnp.float32),
                             jnp.zeros((S, 0), jnp.float32))

    def _launch(self, feed: jax.Array, t_real: int):
        """Run one kernel launch on (S, m) columns; returns (Tp, Sp) events."""
        m = feed.shape[1]
        if feed.shape[0] != self._sp:
            feed = jnp.concatenate(
                [feed, jnp.zeros((self._sp - feed.shape[0], m),
                                 jnp.float32)], axis=0)
        if self._deferred:
            # t_real carries the live-column count here (inert past it).
            return self._kernel_fn(
                feed.T, eps=self.eps, t_stop=t_real, max_run=self.max_run,
                block_s=self.block_s, block_t=self.block_t,
                carry=self._carry, **self._kw)
        ev_brk, ev_a, ev_b, carry_out = self._kernel_fn(
            feed.T, eps=self.eps, t_real=t_real, max_run=self.max_run,
            block_s=self.block_s, block_t=self.block_t, carry=self._carry,
            **self._kw)
        return ev_brk, ev_a, ev_b, carry_out

    def _deferred_collect(self, launch_evs, rows: int, consumed: int,
                          flush_evs=None) -> SegmentOutput:
        """Scatter position-tagged events into the host pending buffers;
        release the prefix no future event can target (all on flush).
        The buffer/frontier logic is the shared
        ``jax_pla._release_deferred`` engine; this wrapper only converts
        the kernel's time-major, launch-local events to (S, w) absolute
        batches."""
        S = self.n_streams
        batches = []
        if launch_evs is not None:
            ev, pos, ea, ev_v = launch_evs
            batches.append((np.asarray(ev[:rows, :S]).T,
                            np.asarray(pos[:rows, :S]).T
                            .astype(np.int64) + self._t,
                            np.asarray(ea[:rows, :S]).T,
                            np.asarray(ev_v[:rows, :S]).T))
        flush_tail = None
        if flush_evs is not None:
            (ev1, p1, a1, v1), flush_tail = flush_evs
            batches.append((np.asarray(ev1)[:S, None],
                            np.asarray(p1)[:S, None]
                            .astype(np.int64) + self._t,
                            np.asarray(a1)[:S, None],
                            np.asarray(v1)[:S, None]))
        out, self._ev_pend, self._det, self._released = release_deferred(
            self._ev_pend, self._det, self._released, self._t + consumed,
            batches, flush_tail)
        return out

    def _events_to_out(self, ev_brk, ev_a, ev_b, rows: int) -> SegmentOutput:
        """Event rows [0, rows) -> finalized columns; an event at local row
        j finalizes absolute position t0 + j - 1, so the stream's first
        ever row (position -1) is dropped."""
        lo = 1 if self._t == 0 else 0
        S = self.n_streams
        return SegmentOutput(ev_brk[lo:rows, :S].T.astype(bool),
                             ev_a[lo:rows, :S].T,
                             ev_b[lo:rows, :S].T)

    def push(self, y_chunk: jax.Array) -> SegmentOutput:
        """Feed ``(S, n)`` columns; returns newly finalized event columns."""
        if self._finished:
            raise RuntimeError("push after finish()")
        y = jnp.asarray(y_chunk, jnp.float32)
        if y.ndim != 2 or y.shape[0] != self.n_streams:
            raise ValueError(f"chunk must be ({self.n_streams}, n); "
                             f"got {y.shape}")
        if y.shape[1]:
            self._pend.append(y)
            self._navail += y.shape[1]
        if self._navail < self.block_t:
            return self._empty()
        m = self._navail // self.block_t * self.block_t
        buf = self._pend[0] if len(self._pend) == 1 \
            else jnp.concatenate(self._pend, axis=1)
        feed, rest = buf[:, :m], buf[:, m:]
        self._pend = [rest] if rest.shape[1] else []
        self._navail -= m
        # Launch widths are pow2 multiples of block_t (descending pieces
        # threading the carry, like jax_pla's chunked API), so the kernel
        # trace set stays log-bounded however callers size their pushes.
        outs = []
        lo = 0
        for nb in _pow2_pieces(m // self.block_t):
            w = nb * self.block_t
            piece = feed[:, lo:lo + w]
            lo += w
            if self._deferred:
                ev, pos, ea, ev_v, carry_out = self._launch(piece, t_real=w)
                outs.append(self._deferred_collect((ev, pos, ea, ev_v),
                                                   w, w))
            else:
                ev_brk, ev_a, ev_b, carry_out = self._launch(piece,
                                                             t_real=-1)
                outs.append(self._events_to_out(ev_brk, ev_a, ev_b, w))
            self._carry = self._shift(carry_out, w)
            self._t += w
        if len(outs) == 1:
            return outs[0]
        return SegmentOutput(*(jnp.concatenate(parts, axis=1)
                               for parts in zip(*outs)))

    def finish(self) -> SegmentOutput:
        """Flush the trailing run; returns the final event columns."""
        if self._finished:
            raise RuntimeError("finish() called twice")
        self._finished = True
        r = self._navail
        if self._t == 0 and r == 0:
            return self._empty()
        if self._deferred:
            # Launch any remainder inert-padded (no in-kernel flush), then
            # close the stream from the carry on the host — the same
            # jitted flush as the offline wrapper, hence bit-identical.
            if r:
                buf = self._pend[0] if len(self._pend) == 1 \
                    else jnp.concatenate(self._pend, axis=1)
                pad = jnp.repeat(buf[:, -1:], self.block_t - r, axis=1)
                feed = jnp.concatenate([buf, pad], axis=1)
                ev, pos, ea, ev_v, carry_out = self._launch(feed, t_real=r)
                launch_evs = (ev, pos, ea, ev_v)
            else:
                carry_out = self._carry
                launch_evs = None
            self._pend = []
            self._navail = 0
            flush_evs = self._flush_fn(carry_out, self.eps, self.max_run,
                                       self.window, r - 1)
            out = self._deferred_collect(launch_evs, r, r,
                                         flush_evs=flush_evs)
            self._t += r
            return out
        # Final launch: r real columns + padding (repeat of the last real
        # value) to one time block; the forced break at local row r closes
        # the trailing run, so event rows 0..r finalize positions up to T-1.
        if r:
            buf = self._pend[0] if len(self._pend) == 1 \
                else jnp.concatenate(self._pend, axis=1)
            pad = jnp.repeat(buf[:, -1:], self.block_t - r, axis=1)
            feed = jnp.concatenate([buf, pad], axis=1)
        else:
            feed = jnp.zeros((self.n_streams, self.block_t), jnp.float32)
        self._pend = []
        self._navail = 0
        ev_brk, ev_a, ev_b, _ = self._launch(feed, t_real=r)
        out = self._events_to_out(ev_brk, ev_a, ev_b, r + 1)
        self._t += r
        return out
