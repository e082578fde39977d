"""Pallas TPU kernel: batched best-fit (Linear) PLA segmentation (§3.5).

The least-squares fit itself is incremental: Welford running sums
(rows 2-6) update in O(1) per point, matching the accumulator carry of
``core.jax_pla._linear_step``.  Only the *validity* check reduces over
the run's VMEM ring window — one fused (W, BS) max-residual per point,
already a single op in-kernel (the jnp engine instead revalidates via
capacity-capped residual-extremum chains to cut XLA-CPU dispatch count;
both are exact, since runs are capped so the window covers the run).

Carry rows (linear_state_rows(W) = 9 + W, all f32; see the carry-state
contract in kernels/common.py): 0 started, 1 run_start, 2 n, 3 mt, 4 my,
5 stt, 6 sty, 7 va, 8 vb, then W ring rows.  Same local-time convention as
the disjoint kernel: ``run_start`` may be negative on resume;
``linear_shift_carry`` renumbers and rolls the ring after each launch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.jax_pla import check_window

from .common import BLOCK_S, BLOCK_T, launch_segmenter

_HEAD_ROWS = 9


def linear_state_rows(window: int) -> int:
    return _HEAD_ROWS + window


def linear_init_carry(sp: int, window: int) -> jax.Array:
    return jnp.zeros((linear_state_rows(window), sp), jnp.float32)


def linear_shift_carry(carry: jax.Array, m: int) -> jax.Array:
    carry = carry.at[1:2].add(-float(m))
    return carry.at[_HEAD_ROWS:].set(
        jnp.roll(carry[_HEAD_ROWS:], -m, axis=0))


def _linear_kernel(y_ref, cin, brk_ref, a_ref, b_ref,
                   cout, started, ring, run_start, nn, mt, my, stt, sty,
                   va, vb,
                   *, eps: float, bt: int, t_real: int, max_run: int,
                   window: int):
    ti = pl.program_id(1)
    W = window

    @pl.when(ti == 0)
    def _load():
        started[...] = cin[0:1, :].astype(jnp.int32)
        run_start[...] = cin[1:2, :]
        nn[...] = cin[2:3, :]
        mt[...] = cin[3:4, :]
        my[...] = cin[4:5, :]
        stt[...] = cin[5:6, :]
        sty[...] = cin[6:7, :]
        va[...] = cin[7:8, :]
        vb[...] = cin[8:9, :]
        ring[...] = cin[_HEAD_ROWS:_HEAD_ROWS + W, :]

    slot_iota = jax.lax.broadcasted_iota(
        jnp.int32, (W, 1), 0).astype(jnp.float32)

    def step(j, _):
        t_loc = ti * bt + j
        t = t_loc.astype(jnp.float32)
        yt = y_ref[pl.ds(j, 1), :]  # (1, BS)
        is_first = started[...] == 0

        rs, n0 = run_start[...], nn[...]
        m_t, m_y, s_tt, s_ty = mt[...], my[...], stt[...], sty[...]
        v_a, v_v = va[...], vb[...]
        rel = t - rs  # run-relative time; all fits are anchored at rs

        # Tentative Welford update (over run-relative t).
        n1 = n0 + 1.0
        d_t = rel - m_t
        d_y = yt - m_y
        mt1 = m_t + d_t / n1
        my1 = m_y + d_y / n1
        stt1 = s_tt + d_t * (rel - mt1)
        sty1 = s_ty + d_t * (yt - my1)
        a_fit = jnp.where(stt1 > 0, sty1 / jnp.where(stt1 > 0, stt1, 1.0), 0.0)
        b_fit = my1 - a_fit * mt1    # value at rel == 0 (run start)

        # Window revalidation: residuals of all run points + the new point.
        # Local slot positions may be negative on resume; the run mask is
        # purely relative (see the disjoint kernel).
        tm1 = t - 1.0
        p_r = tm1 - jnp.mod(tm1 - slot_iota, float(W))       # (W, 1)
        in_run = p_r >= rs
        relw = p_r - rs
        yw = ring[...]
        res = jnp.abs(yw - (a_fit * relw + b_fit))
        res = jnp.where(in_run, res, 0.0)
        max_res = jnp.maximum(jnp.max(res, axis=0, keepdims=True),
                              jnp.abs(yt - (a_fit * rel + b_fit)))
        tol = eps * (1 + 1e-6) + 1e-12
        valid = max_res <= tol
        cap_hit = n0 >= max_run
        force = t_loc == t_real
        brk = (~valid | cap_hit | force) & ~is_first

        # (v_a, v_v): last valid fit as (slope, value at previous point) —
        # exactly the anchored output form for a break at t-1.
        brk_ref[pl.ds(j, 1), :] = brk.astype(brk_ref.dtype)
        a_ref[pl.ds(j, 1), :] = jnp.where(brk, v_a, 0.0)
        b_ref[pl.ds(j, 1), :] = jnp.where(brk, v_v, 0.0)

        restart = brk | is_first
        run_start[...] = jnp.where(restart, t, rs)
        nn[...] = jnp.where(restart, 1.0, n1)
        mt[...] = jnp.where(restart, 0.0, mt1)
        my[...] = jnp.where(restart, yt, my1)
        stt[...] = jnp.where(restart, 0.0, stt1)
        sty[...] = jnp.where(restart, 0.0, sty1)
        va[...] = jnp.where(restart, 0.0, a_fit)
        # value of the (new) valid fit at the *current* point t.
        vb[...] = jnp.where(restart, yt, a_fit * rel + b_fit)
        started[...] = jnp.ones_like(started[...])
        ring[pl.ds(jnp.mod(t_loc, W), 1), :] = yt
        return 0

    jax.lax.fori_loop(0, bt, step, 0)

    @pl.when(ti == pl.num_programs(1) - 1)
    def _store():
        cout[0:1, :] = started[...].astype(jnp.float32)
        cout[1:2, :] = run_start[...]
        cout[2:3, :] = nn[...]
        cout[3:4, :] = mt[...]
        cout[4:5, :] = my[...]
        cout[5:6, :] = stt[...]
        cout[6:7, :] = sty[...]
        cout[7:8, :] = va[...]
        cout[8:9, :] = vb[...]
        cout[_HEAD_ROWS:_HEAD_ROWS + W, :] = ring[...]


@functools.partial(jax.jit, static_argnames=("eps", "t_real", "max_run", "window",
                                             "block_s", "block_t"))
def linear_pallas(y_t: jax.Array, *, eps: float, t_real: int,
                  max_run: int = 256, window: int | None = None,
                  block_s: int = BLOCK_S, block_t: int = BLOCK_T,
                  carry: jax.Array | None = None):
    W = check_window(max_run, window)
    if carry is None:
        carry = linear_init_carry(y_t.shape[1], W)
    kernel = functools.partial(_linear_kernel, eps=eps, bt=block_t,
                               t_real=t_real, max_run=max_run, window=W)
    f32 = jnp.float32
    scratch = [((1, block_s), jnp.int32),   # started
               ((W, block_s), f32)] + \
              [((1, block_s), f32) for _ in range(8)]
    return launch_segmenter(kernel, y_t, block_s=block_s, block_t=block_t,
                            scratch=scratch, carry=carry)
