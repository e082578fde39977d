"""Shared plumbing for the PLA Pallas TPU kernels.

Layout convention: kernels take the stream batch in **time-major** layout
``y_t: (T, S)`` so that streams ride the TPU lane dimension (128-wide) and
the sequential time walk indexes the sublane dimension, which supports
dynamic row slicing.  The public ops (``repro.kernels.ops``) accept the
framework's natural ``(S, T)`` layout and transpose/pad at the boundary.

Grid convention: ``grid = (S // BS, T // BT)`` with
``dimension_semantics = ("parallel", "arbitrary")`` — stream blocks are
independent; time blocks are walked sequentially with per-stream carry
state living in VMEM scratch.

Carry-state contract (chunked streaming)
----------------------------------------

Every segmenter owns a packed float32 **carry** of shape ``(C, Sp)`` — one
row per scalar of per-stream state (integer rows like run length are
stored as exact small-int floats), ring buffers contributing ``W`` rows.
:func:`launch_segmenter` wires it as one extra *input* (the resumed state)
and one extra *output* (the state after the launch), with a time-invariant
block spec ``(C, block_s) @ (0, si)``: the kernel loads its VMEM scratch
from the carry-in block at the first sequential step (``ti == 0``) and
stores the scratch back to the carry-out block at the last
(``ti == num_programs(1) - 1``).  Row layouts are documented per kernel
module (``*_STATE_ROWS``); host-side initializers (``*_init_carry``) build
the fresh-stream state, and row 0 of every segmenter carry is a
``started`` flag that replaces the old ``t == 0`` special case, so a
resumed launch never re-runs first-point initialization.

Time inside a launch is **local** (``t = ti * block_t + j``, starting at 0
every launch); state that references positions (``run_start``, ring slots)
is kept consistent across launches by the host-side shift helpers
(``*_shift_carry``): after consuming ``m`` columns, absolute-position rows
are decremented by ``m`` and ring rows are rolled by ``-m`` so slot ``r``
again holds the position ``p ≡ r (mod W)`` of the *next* launch's frame.
Because all position arithmetic inside the kernels is difference-based,
the local renumbering is bit-transparent — chunked output is bit-identical
to the offline launch — and, unlike the absolute-time jnp references,
kernels have no 2^24 stream-length limit.

Event semantics: while processing time index ``t`` a kernel may detect that
the current segment *ended at* ``t-1``; it records the event at row ``t``
of its event outputs (no cross-block writes).  A forced break is injected
at ``t == t_real`` (``t_real = -1`` disables it): the offline wrappers and
the final streaming launch use it to flush the trailing run through the
regular event path; intermediate streaming launches disable it.
:func:`assemble_segments` shifts events into the canonical
:class:`repro.core.jax_pla.SegmentOutput` form for the offline wrappers;
:class:`repro.kernels.ops.StreamingSegmenter` does the chunked equivalent
(drop the first event row of a stream, keep rows ``0..t_real`` of the
final launch).

All segmenter kernels (and the reconstructor) launch through the single
:func:`launch_segmenter` helper: block-shape wiring, VMEM scratch
allocation, TPU compiler params, carry in/out specs, and the CPU
interpret-mode fallback live here — the per-algorithm modules contribute
only the kernel body and its scratch/carry layout.  Version-dependent
Pallas attributes are resolved by :mod:`repro.compat.pallas`; kernels
never touch them directly.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.compat.pallas import interpret_mode, tpu_compiler_params, vmem
from repro.core.jax_pla import SegmentOutput

__all__ = ["BLOCK_S", "BLOCK_T", "interpret_mode", "pad_streams",
           "assemble_segments", "launch_segmenter"]

# Default tile sizes: 128 streams on lanes; 128 time steps per block keeps
# (BT, BS) f32 tiles at 64 KiB — far under VMEM even with ring buffers.
BLOCK_S = 128
BLOCK_T = 128

_BIG = jnp.float32(3.4e38)

# Event outputs of every segmenter: break flag, slope, value-at-break.
# The flag is 32-bit: kernels store one (1, block_s) row per time step at
# a dynamic sublane index, which Mosaic lowers only for 32-bit tiles (an
# int8 tile is (32, 128), so a one-row int8 store cannot be proven
# aligned).
SEGMENT_EVENT_DTYPES = (jnp.int32, jnp.float32, jnp.float32)


def pad_streams(y: jax.Array, bs: int, bt: int):
    """Pad (S, T) to multiples of (bs, bt); returns (padded, S, T).

    Time padding *always* adds at least one step (repeating the final
    value): the kernel injects a forced break at ``t == T`` so the trailing
    run flushes through the regular event path (no cross-block writes).
    Stream padding appends zero rows.
    """
    S, T = y.shape
    Sp = (S + bs - 1) // bs * bs
    Tp = (T // bt + 1) * bt
    y = jnp.concatenate([y, jnp.repeat(y[:, -1:], Tp - T, axis=1)], axis=1)
    if Sp != S:
        y = jnp.concatenate([y, jnp.zeros((Sp - S, Tp), y.dtype)], axis=0)
    return y, S, T


def assemble_segments(ev_brk, ev_a, ev_b, S: int, T: int) -> SegmentOutput:
    """Shift kernel events into canonical (S, T) SegmentOutput.

    ``ev_*`` are (Tp, Sp) time-major event arrays; an event at row t means
    "a segment ended at t-1".  The forced break at row T closes the
    trailing run, so rows 1..T cover break positions 0..T-1 completely.
    """
    breaks = ev_brk[1:T + 1, :S].T.astype(bool)
    a = ev_a[1:T + 1, :S].T
    b = ev_b[1:T + 1, :S].T
    return SegmentOutput(breaks, a, b)


def launch_segmenter(kernel, inputs, *,
                     block_s: int = BLOCK_S, block_t: int = BLOCK_T,
                     out_dtypes: Sequence = SEGMENT_EVENT_DTYPES,
                     scratch: Sequence[Tuple[Tuple[int, ...], object]] = (),
                     reverse_time: bool = False,
                     carry: Optional[jax.Array] = None):
    """Launch a PLA segmentation/reconstruction kernel on (Tp, Sp) inputs.

    One place for everything the five kernels used to copy: the
    ``(streams, time)`` grid, the time-major block specs (optionally
    walking time blocks in reverse for the reconstructor), VMEM scratch
    allocation from plain ``(shape, dtype)`` pairs, the
    parallel/arbitrary dimension semantics, and the interpret-mode
    fallback off-TPU.

    ``kernel`` is a Pallas kernel body taking ``len(inputs)`` input refs
    (plus the carry-in ref when ``carry`` is given), ``len(out_dtypes)``
    output refs (plus the carry-out ref), then one scratch ref per
    ``scratch`` entry.  Inputs must share one (Tp, Sp) shape, pre-padded
    to the block grid.

    ``carry`` is the packed per-stream state (see module docstring): a
    ``(C, Sp)`` array appended as the last input and mirrored as the last
    output with a time-invariant ``(C, block_s)`` block spec, so each
    stream block resumes its own state and hands it back after the last
    time block.  Returns the list of (Tp, Sp) output arrays, with the
    (C, Sp) carry-out appended when ``carry`` was given.
    """
    arrs = tuple(inputs) if isinstance(inputs, (tuple, list)) else (inputs,)
    Tp, Sp = arrs[0].shape
    for a in arrs[1:]:
        if a.shape != (Tp, Sp):
            raise ValueError(f"input shapes differ: {a.shape} vs {(Tp, Sp)}")
    if Tp % block_t or Sp % block_s:
        raise ValueError(f"(Tp={Tp}, Sp={Sp}) not padded to "
                         f"({block_t}, {block_s}) blocks")
    nt = Tp // block_t
    grid = (Sp // block_s, nt)
    if reverse_time:
        index_map = lambda si, ti: (nt - 1 - ti, si)  # noqa: E731
    else:
        index_map = lambda si, ti: (ti, si)           # noqa: E731
    spec = pl.BlockSpec((block_t, block_s), index_map)
    in_specs = [spec] * len(arrs)
    out_specs = [spec] * len(out_dtypes)
    out_shape = [jax.ShapeDtypeStruct((Tp, Sp), dt) for dt in out_dtypes]
    if carry is not None:
        if carry.ndim != 2 or carry.shape[1] != Sp:
            raise ValueError(f"carry must be (C, Sp={Sp}); got {carry.shape}")
        cspec = pl.BlockSpec((carry.shape[0], block_s),
                             lambda si, ti: (0, si))
        arrs = arrs + (carry,)
        in_specs.append(cspec)
        out_specs.append(cspec)
        out_shape.append(jax.ShapeDtypeStruct(carry.shape, carry.dtype))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[vmem(shape, dtype) for shape, dtype in scratch],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret_mode(),
    )(*arrs)
