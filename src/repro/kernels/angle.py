"""Pallas TPU kernel: batched Angle PLA segmentation (paper §3.1).

O(1) state per stream: the wedge origin (intersection of the two extreme
lines through the first two error segments) plus the feasible slope
interval.  Streams ride the lane dimension; time is walked sequentially by
the inner grid dimension with carry state in VMEM scratch, resumed from /
handed back through the packed carry operand (kernels/common.py).

All line state is *anchored* (origin kept as an offset from the current
step; outputs are (slope, value-at-break)) so float32 stays exact for
arbitrarily long streams — see repro.core.jax_pla.

Event semantics (see kernels/common.py): processing time ``t`` may emit
"segment ended at t-1" at event row ``t``; a forced break is injected at
``t == t_real`` (disabled with ``t_real=-1``) so the trailing run flushes
without cross-block writes.

Carry rows (ANGLE_STATE_ROWS = 8, all f32; see the carry-state contract in
kernels/common.py): 0 started, 1 phase, 2 p0y, 3 od, 4 oy, 5 slo, 6 shi,
7 run_len.  All state is position-relative, so resuming a launch needs no
host-side shift (``angle_shift_carry`` is the identity).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import BLOCK_S, BLOCK_T, launch_segmenter

_BIG = 3.4e38

ANGLE_STATE_ROWS = 8


def angle_init_carry(sp: int) -> jax.Array:
    """Packed fresh-stream carry (started=0; empty wedge) for Sp lanes."""
    c = jnp.zeros((ANGLE_STATE_ROWS, sp), jnp.float32)
    return c.at[5].set(-_BIG).at[6].set(_BIG)


def angle_shift_carry(carry: jax.Array, m: int) -> jax.Array:
    return carry  # purely relative state


def _angle_kernel(y_ref, cin, brk_ref, a_ref, v_ref, cout,
                  started, phase, p0y, od, oy, slo, shi, runl,
                  *, eps: float, bt: int, t_real: int, max_run: int):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _load():
        started[...] = cin[0:1, :].astype(jnp.int32)
        phase[...] = cin[1:2, :].astype(jnp.int32)
        p0y[...] = cin[2:3, :]
        od[...] = cin[3:4, :]
        oy[...] = cin[4:5, :]
        slo[...] = cin[5:6, :]
        shi[...] = cin[6:7, :]
        runl[...] = cin[7:8, :].astype(jnp.int32)

    def step(j, _):
        t_loc = ti * bt + j   # launch-local time
        yt = y_ref[pl.ds(j, 1), :]  # (1, BS)

        is_first = started[...] == 0
        ph, py = phase[...], p0y[...]
        o_d, o_y, s_lo, s_hi, rl = od[...], oy[...], slo[...], shi[...], runl[...]

        # Phase 0 -> 1: origin from p0=(offset 0) and this point (offset 1).
        amax = (yt + eps) - (py - eps)
        amin = (yt - eps) - (py + eps)
        da = amax - amin
        das = jnp.where(jnp.abs(da) < 1e-30, 1.0, da)
        ox_rel = jnp.where(jnp.abs(da) < 1e-30, 0.5, 2.0 * eps / das)
        oy_new = amax * ox_rel + (py - eps)
        od_new0 = 1.0 - ox_rel

        # Phase 1: wedge update; origin sits o_d steps behind t.
        dts = jnp.where(o_d == 0, 1.0, o_d)
        n1 = (yt - eps - o_y) / dts
        n2 = (yt + eps - o_y) / dts
        nlo = jnp.minimum(n1, n2)
        nhi = jnp.maximum(n1, n2)
        t_slo = jnp.maximum(s_lo, nlo)
        t_shi = jnp.minimum(s_hi, nhi)
        feasible = t_slo <= t_shi
        cap_hit = rl >= max_run
        force = t_loc == t_real
        brk = ((ph == 1) & (~feasible | cap_hit) | force) & ~is_first

        a_out = jnp.where(ph == 1, 0.5 * (s_lo + s_hi), 0.0)
        v_out = jnp.where(ph == 1, o_y + a_out * (o_d - 1.0), py)

        brk_ref[pl.ds(j, 1), :] = brk.astype(brk_ref.dtype)
        a_ref[pl.ds(j, 1), :] = jnp.where(brk, a_out, 0.0)
        v_ref[pl.ds(j, 1), :] = jnp.where(brk, v_out, 0.0)

        # Commit next state.
        go0 = (ph == 0) & ~brk & ~is_first     # origin just built
        phase[...] = jnp.where(brk | is_first, 0, 1).astype(jnp.int32)
        p0y[...] = jnp.where(brk | is_first, yt, py)
        od[...] = jnp.where(go0, od_new0 + 1.0,
                            jnp.where(brk | is_first, 0.0, o_d + 1.0))
        oy[...] = jnp.where(go0, oy_new, o_y)
        slo[...] = jnp.where(go0, amin, jnp.where(brk, -_BIG, t_slo))
        shi[...] = jnp.where(go0, amax, jnp.where(brk, _BIG, t_shi))
        runl[...] = jnp.where(brk | is_first, 1, rl + 1).astype(jnp.int32)
        started[...] = jnp.ones_like(started[...])
        return 0

    jax.lax.fori_loop(0, bt, step, 0)

    @pl.when(ti == pl.num_programs(1) - 1)
    def _store():
        cout[0:1, :] = started[...].astype(jnp.float32)
        cout[1:2, :] = phase[...].astype(jnp.float32)
        cout[2:3, :] = p0y[...]
        cout[3:4, :] = od[...]
        cout[4:5, :] = oy[...]
        cout[5:6, :] = slo[...]
        cout[6:7, :] = shi[...]
        cout[7:8, :] = runl[...].astype(jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("eps", "t_real", "max_run",
                                    "block_s", "block_t"))
def angle_pallas(y_t: jax.Array, *, eps: float, t_real: int, max_run: int = 256,
                 block_s: int = BLOCK_S, block_t: int = BLOCK_T,
                 carry: jax.Array | None = None):
    """Run the Angle kernel on time-major ``y_t: (Tp, Sp)``.

    Returns event arrays ``(brk_i8, a, v)`` of shape (Tp, Sp) plus the
    carry-out state; ``carry=None`` starts fresh streams.
    """
    if carry is None:
        carry = angle_init_carry(y_t.shape[1])
    kernel = functools.partial(_angle_kernel, eps=eps, bt=block_t,
                               t_real=t_real, max_run=max_run)
    scratch = [((1, block_s), jnp.int32),    # started
               ((1, block_s), jnp.int32),    # phase
               ((1, block_s), jnp.float32),  # p0y
               ((1, block_s), jnp.float32),  # od (origin offset)
               ((1, block_s), jnp.float32),  # oy
               ((1, block_s), jnp.float32),  # slo
               ((1, block_s), jnp.float32),  # shi
               ((1, block_s), jnp.int32)]    # run_len
    return launch_segmenter(kernel, y_t, block_s=block_s, block_t=block_t,
                            scratch=scratch, carry=carry)
