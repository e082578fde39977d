"""Pallas TPU kernel: batched SwingFilter PLA segmentation (paper §3.1).

The paper's simplest (and historically first) streaming method: a slope
wedge through a fixed origin = the previous segment's chosen endpoint
(joint knots), O(1) state per stream.  Same lane/scratch/event/carry
layout as the Angle kernel (kernels/angle.py); the origin is carried as a
relative offset so f32 survives arbitrarily long streams.

Carry rows (SWING_STATE_ROWS = 6, all f32; see kernels/common.py):
0 started, 1 od, 2 oy, 3 slo, 4 shi, 5 run_len.  Relative state only —
``swing_shift_carry`` is the identity.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import BLOCK_S, BLOCK_T, launch_segmenter

_BIG = 3.4e38

SWING_STATE_ROWS = 6


def swing_init_carry(sp: int) -> jax.Array:
    c = jnp.zeros((SWING_STATE_ROWS, sp), jnp.float32)
    return c.at[3].set(-_BIG).at[4].set(_BIG)


def swing_shift_carry(carry: jax.Array, m: int) -> jax.Array:
    return carry  # purely relative state


def _swing_kernel(y_ref, cin, brk_ref, a_ref, v_ref, cout,
                  started, od, oy, slo, shi, runl,
                  *, eps: float, bt: int, t_real: int, max_run: int):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _load():
        started[...] = cin[0:1, :].astype(jnp.int32)
        od[...] = cin[1:2, :]
        oy[...] = cin[2:3, :]
        slo[...] = cin[3:4, :]
        shi[...] = cin[4:5, :]
        runl[...] = cin[5:6, :].astype(jnp.int32)

    def step(j, _):
        t_loc = ti * bt + j
        yt = y_ref[pl.ds(j, 1), :]  # (1, BS)
        is_first = started[...] == 0

        o_d, o_y = od[...], oy[...]
        s_lo, s_hi, rl = slo[...], shi[...], runl[...]

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
        brk = (~feasible | cap_hit | force) & ~is_first

        a_out = 0.5 * (s_lo + s_hi)
        v_out = o_y + a_out * (o_d - 1.0)   # knot at t-1 (on the old line)

        brk_ref[pl.ds(j, 1), :] = brk.astype(brk_ref.dtype)
        a_ref[pl.ds(j, 1), :] = jnp.where(brk, a_out, 0.0)
        v_ref[pl.ds(j, 1), :] = jnp.where(brk, v_out, 0.0)

        # Restart from the knot (t-1, v_out); re-add this point (dt == 1).
        b_lo = yt - eps - v_out
        b_hi = yt + eps - v_out
        # od: at the stream's first point the origin IS this point (next
        # step distance 1); on a break the origin is at t-1 (next step
        # distance 2); else +1.
        od[...] = jnp.where(is_first, 1.0, jnp.where(brk, 2.0, o_d + 1.0))
        oy[...] = jnp.where(brk, v_out, jnp.where(is_first, yt, o_y))
        slo[...] = jnp.where(brk, jnp.minimum(b_lo, b_hi),
                             jnp.where(is_first, -_BIG, t_slo))
        shi[...] = jnp.where(brk, jnp.maximum(b_lo, b_hi),
                             jnp.where(is_first, _BIG, t_shi))
        runl[...] = jnp.where(brk | is_first, 1, rl + 1).astype(jnp.int32)
        started[...] = jnp.ones_like(started[...])
        return 0

    jax.lax.fori_loop(0, bt, step, 0)

    @pl.when(ti == pl.num_programs(1) - 1)
    def _store():
        cout[0:1, :] = started[...].astype(jnp.float32)
        cout[1:2, :] = od[...]
        cout[2:3, :] = oy[...]
        cout[3:4, :] = slo[...]
        cout[4:5, :] = shi[...]
        cout[5:6, :] = runl[...].astype(jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("eps", "t_real", "max_run",
                                    "block_s", "block_t"))
def swing_pallas(y_t: jax.Array, *, eps: float, t_real: int,
                 max_run: int = 256,
                 block_s: int = BLOCK_S, block_t: int = BLOCK_T,
                 carry: jax.Array | None = None):
    """Run the Swing kernel on time-major ``y_t: (Tp, Sp)``."""
    if carry is None:
        carry = swing_init_carry(y_t.shape[1])
    kernel = functools.partial(_swing_kernel, eps=eps, bt=block_t,
                               t_real=t_real, max_run=max_run)
    f32 = jnp.float32
    scratch = [((1, block_s), jnp.int32),  # started
               ((1, block_s), f32),      # od
               ((1, block_s), f32),      # oy
               ((1, block_s), f32),      # slo
               ((1, block_s), f32),      # shi
               ((1, block_s), jnp.int32)]
    return launch_segmenter(kernel, y_t, block_s=block_s, block_t=block_t,
                            scratch=scratch, carry=carry)
