"""Pallas TPU kernel: batched MixedPLA segmentation (paper §3.4).

Stage 1 is the optimal-disjoint scan of kernels/disjoint.py (extreme
lines + exact windowed retightening); stage 2 holds the *previous*
finalized run and, at the current run's break, decides joint-vs-disjoint
by intersecting the two feasible-value ranges at the previous run's last
point (Luo et al.'s single-segment-lookahead merge — see
``repro.core.methods.run_mixed``).  A join shortens the previous segment
by one point and transfers the shared knot to the current run, so events
land one run in the past: like kernels/continuous.py this is a
**deferred** kernel — ``(ev, pos, a, v)`` outputs with launch-local
positions, a static inert-past-``t_stop`` bound instead of an in-kernel
forced break, and a host-side :func:`mixed_flush_carry` shared by the
offline and chunked paths — which is the bit-identity guarantee: chunked
pushes through :class:`repro.kernels.ops.StreamingSegmenter` equal the
one-shot ``mixed_segment_tpu`` output bitwise, and both equal the jnp
reference scan (tests/test_kernels.py, tests/test_streaming.py).

The ring must retain both the previous and the current run
(``jax_pla.mixed_ring(window) = 2 * window + 8`` rows).

Carry rows (mixed_state_rows(W) = 19 + mixed_ring(W), all f32; see the
carry-state contract in kernels/common.py): 0 started, 1 run_start,
2 run_len, 3 y0, 4 prev_y, 5 a_lo, 6 v_lo, 7 a_hi, 8 v_hi, 9 p_exists,
10 p_i0, 11 p_i1, 12 p_lk, 13 p_lk_pos, 14 p_lk_val, 15 p_lo, 16 p_hi,
17 p_amid, 18 p_vmid, then the ring.  ``mixed_shift_carry`` renumbers the
four position rows and rolls the ring between launches.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.jax_pla import check_window, mixed_ring, _mixed_flush

from .common import BLOCK_S, BLOCK_T, launch_segmenter
from .continuous import DEFERRED_EVENT_DTYPES

_BIG = 3.4e38

_HEAD_ROWS = 19


def mixed_state_rows(window: int) -> int:
    return _HEAD_ROWS + mixed_ring(window)


def mixed_init_carry(sp: int, window: int) -> jax.Array:
    return jnp.zeros((mixed_state_rows(window), sp), jnp.float32)


def mixed_shift_carry(carry: jax.Array, m: int) -> jax.Array:
    """Renumber to the next launch's local frame after consuming m cols."""
    for r in (1, 10, 11, 13):       # run_start, p_i0, p_i1, p_lk_pos
        carry = carry.at[r:r + 1].add(-float(m))
    return carry.at[_HEAD_ROWS:].set(
        jnp.roll(carry[_HEAD_ROWS:], -m, axis=0))


def mixed_unpack_carry(carry: jax.Array, window: int):
    """Kernel carry -> the jnp engine's _mixed_* carry tuple (with
    launch-local positions), so the host flush reuses the shared math."""
    W2 = mixed_ring(window)
    i32 = lambda r: carry[r].astype(jnp.int32)  # noqa: E731
    return (carry[_HEAD_ROWS:_HEAD_ROWS + W2].T,
            i32(1), i32(2), carry[3], carry[4],
            carry[5], carry[6], carry[7], carry[8],
            i32(9), i32(10), i32(11), i32(12), i32(13), carry[14],
            carry[15], carry[16], carry[17], carry[18])


@functools.partial(jax.jit, static_argnames=("eps", "max_run", "window",
                                             "t_last"))
def mixed_flush_carry(carry: jax.Array, eps: float, max_run: int,
                      window: int, t_last: int):
    """Close the stream from a carry: the final join decision's event plus
    the trailing segment's line at launch-local ``t_last``."""
    eps_v = jnp.full((carry.shape[1],), eps, jnp.float32)
    return _mixed_flush(eps_v, max_run, mixed_ring(window),
                        mixed_unpack_carry(carry, window), t_last)


def _mixed_kernel(y_ref, cin, ev_ref, pos_ref, a_ref, v_ref, cout,
                  started, ring, run_start, runl, y0s, prev_y,
                  a_lo, v_lo, a_hi, v_hi,
                  p_ex, p_i0, p_i1, p_lk, p_lk_pos, p_lk_val,
                  p_lo, p_hi, p_amid, p_vmid,
                  *, eps: float, bt: int, t_stop: int, max_run: int,
                  window: int):
    ti = pl.program_id(1)
    W2 = mixed_ring(window)

    @pl.when(ti == 0)
    def _load():
        started[...] = cin[0:1, :].astype(jnp.int32)
        run_start[...] = cin[1:2, :]
        runl[...] = cin[2:3, :].astype(jnp.int32)
        y0s[...] = cin[3:4, :]
        prev_y[...] = cin[4:5, :]
        a_lo[...] = cin[5:6, :]
        v_lo[...] = cin[6:7, :]
        a_hi[...] = cin[7:8, :]
        v_hi[...] = cin[8:9, :]
        p_ex[...] = cin[9:10, :].astype(jnp.int32)
        p_i0[...] = cin[10:11, :]
        p_i1[...] = cin[11:12, :]
        p_lk[...] = cin[12:13, :].astype(jnp.int32)
        p_lk_pos[...] = cin[13:14, :]
        p_lk_val[...] = cin[14:15, :]
        p_lo[...] = cin[15:16, :]
        p_hi[...] = cin[16:17, :]
        p_amid[...] = cin[17:18, :]
        p_vmid[...] = cin[18:19, :]
        ring[...] = cin[_HEAD_ROWS:_HEAD_ROWS + W2, :]

    slot_iota = jax.lax.broadcasted_iota(
        jnp.int32, (W2, 1), 0).astype(jnp.float32)

    def step(j, _):
        t_loc = ti * bt + j
        live = t_loc < t_stop
        t = t_loc.astype(jnp.float32)
        yt = y_ref[pl.ds(j, 1), :]  # (1, BS)
        is_first = started[...] == 0

        rs, rl = run_start[...], runl[...]
        y0, py = y0s[...], prev_y[...]
        al, vl, ah, vh = a_lo[...], v_lo[...], a_hi[...], v_hi[...]
        pe, pi0, pi1 = p_ex[...], p_i0[...], p_i1[...]
        plk, lkp, lkv = p_lk[...], p_lk_pos[...], p_lk_val[...]
        plo_c, phi_c = p_lo[...], p_hi[...]
        pam, pvm = p_amid[...], p_vmid[...]
        rel = t - rs

        # ---- stage 1: disjoint feasibility + retightening ---------------
        lo_i, hi_i = yt - eps, yt + eps
        vmax = ah * rel + vh
        vmin = al * rel + vl
        feas2 = (vmax >= lo_i) & (vmin <= hi_i)
        cap_hit = rl >= max_run
        brk = ((rl >= 2) & ~feas2 | cap_hit) & ~is_first & live

        tm1 = t - 1.0
        p_r = tm1 - jnp.mod(tm1 - slot_iota, float(W2))  # (W2, 1)
        in_run = p_r >= rs                               # (W2, BS)
        dtw_safe = jnp.where(in_run, t - p_r, 1.0)
        yw = ring[...]

        need_hi = vmax > hi_i
        s_hi = jnp.where(in_run, (hi_i - (yw - eps)) / dtw_safe, _BIG)
        a_hi_new = jnp.min(s_hi, axis=0, keepdims=True)
        a_hi_u = jnp.where(need_hi, a_hi_new, ah)
        v_hi_u = jnp.where(need_hi, hi_i - a_hi_new * rel, vh)

        need_lo = vmin < lo_i
        s_lo = jnp.where(in_run, (lo_i - (yw + eps)) / dtw_safe, -_BIG)
        a_lo_new = jnp.max(s_lo, axis=0, keepdims=True)
        a_lo_u = jnp.where(need_lo, a_lo_new, al)
        v_lo_u = jnp.where(need_lo, lo_i - a_lo_new * rel, vl)

        rel_s = jnp.maximum(rel, 1.0)
        second = rl == 1
        a_hi_n = jnp.where(second, (hi_i - (y0 - eps)) / rel_s, a_hi_u)
        v_hi_n = jnp.where(second, y0 - eps, v_hi_u)
        a_lo_n = jnp.where(second, (lo_i - (y0 + eps)) / rel_s, a_lo_u)
        v_lo_n = jnp.where(second, y0 + eps, v_lo_u)

        # ---- stage 2: join decision at the break ------------------------
        tau = rs - 1.0

        m_prev = (p_r >= pi0) & (p_r < pi1) & (p_r > lkp)
        ds = jnp.where(m_prev, p_r - lkp, 1.0)           # > 0 under mask
        lk_slo = jnp.max(jnp.where(m_prev, (yw - eps - lkv) / ds, -_BIG),
                         axis=0, keepdims=True)
        lk_shi = jnp.min(jnp.where(m_prev, (yw + eps - lkv) / ds, _BIG),
                         axis=0, keepdims=True)
        dtl = tau - lkp
        dtl_safe = jnp.where(dtl > 0, dtl, 1.0)
        lk_amid = 0.5 * (lk_slo + lk_shi)
        lk_vmid = lkv + lk_amid * dtl
        plo = jnp.where(plk == 1, lkv + lk_slo * dtl, plo_c)
        phi = jnp.where(plk == 1, lkv + lk_shi * dtl, phi_c)

        cv1 = vl - al
        cv2 = vh - ah
        clo = jnp.where(rl >= 2, jnp.minimum(cv1, cv2), -_BIG)
        chi = jnp.where(rl >= 2, jnp.maximum(cv1, cv2), _BIG)
        jlo = jnp.maximum(plo, clo)
        jhi = jnp.minimum(phi, chi)
        # A run already at max_run takes no join (it would hand the
        # run one more point than the cap).
        join = brk & ~cap_hit & (pe == 1) & (pi1 - pi0 >= 2.0) \
            & (jlo <= jhi)
        vK = 0.5 * (jlo + jhi)

        m_jw = (p_r >= pi0) & (p_r < pi1 - 1.0)
        ds2 = jnp.where(m_jw, p_r - tau, 1.0)            # < 0 under mask
        jw_slo = jnp.max(jnp.where(m_jw, (yw + eps - vK) / ds2, -_BIG),
                         axis=0, keepdims=True)
        jw_shi = jnp.min(jnp.where(m_jw, (yw - eps - vK) / ds2, _BIG),
                         axis=0, keepdims=True)
        aJ = jnp.where(plk == 1, (vK - lkv) / dtl_safe,
                       0.5 * (jw_slo + jw_shi))
        aN = jnp.where(plk == 1, lk_amid, pam)
        vN = jnp.where(plk == 1, lk_vmid, pvm)

        evt = brk & (pe == 1)
        ev_ref[pl.ds(j, 1), :] = evt.astype(ev_ref.dtype)
        pos_ref[pl.ds(j, 1), :] = jnp.where(
            evt, jnp.where(join, tau - 1.0, tau), 0.0).astype(jnp.int32)
        a_ref[pl.ds(j, 1), :] = jnp.where(evt, jnp.where(join, aJ, aN),
                                          0.0)
        v_ref[pl.ds(j, 1), :] = jnp.where(evt,
                                          jnp.where(join, vK - aJ, vN), 0.0)

        # The breaking run becomes prev: cache its free-case range/mid at
        # its last point (t - 1) before the stage-1 reset.
        rel2 = rel - 1.0
        nv1 = vl + al * rel2
        nv2 = vh + ah * rel2
        np_lo = jnp.where(rl >= 2, jnp.minimum(nv1, nv2), py - eps)
        np_hi = jnp.where(rl >= 2, jnp.maximum(nv1, nv2), py + eps)
        np_am = jnp.where(rl >= 2, 0.5 * (al + ah), 0.0)
        np_vm = jnp.where(rl >= 2, 0.5 * (vl + vh) + np_am * rel2, py)

        # ---- commit -----------------------------------------------------
        restart = (brk | is_first) & live
        upd = live

        run_start[...] = jnp.where(restart, t, rs)
        runl[...] = jnp.where(restart, 1, jnp.where(upd, rl + 1, rl)) \
            .astype(jnp.int32)
        y0s[...] = jnp.where(restart, yt, y0)
        prev_y[...] = jnp.where(upd, yt, py)
        z = jnp.zeros_like(al)
        a_lo[...] = jnp.where(restart, z, jnp.where(upd, a_lo_n, al))
        v_lo[...] = jnp.where(restart, z, jnp.where(upd, v_lo_n, vl))
        a_hi[...] = jnp.where(restart, z, jnp.where(upd, a_hi_n, ah))
        v_hi[...] = jnp.where(restart, z, jnp.where(upd, v_hi_n, vh))
        p_ex[...] = jnp.where(brk, 1, jnp.where(is_first & live, 0, pe)) \
            .astype(jnp.int32)
        p_i0[...] = jnp.where(brk, jnp.where(join, tau, rs), pi0)
        p_i1[...] = jnp.where(brk, t, pi1)
        p_lk[...] = jnp.where(brk, join.astype(jnp.int32),
                              plk).astype(jnp.int32)
        p_lk_pos[...] = jnp.where(brk & join, tau, lkp)
        p_lk_val[...] = jnp.where(brk & join, vK, lkv)
        p_lo[...] = jnp.where(brk, np_lo, plo_c)
        p_hi[...] = jnp.where(brk, np_hi, phi_c)
        p_amid[...] = jnp.where(brk, np_am, pam)
        p_vmid[...] = jnp.where(brk, np_vm, pvm)
        started[...] = jnp.where(upd, 1, started[...])
        row = pl.ds(jnp.mod(t_loc, W2), 1)
        cur_row = ring[row, :]
        ring[row, :] = jnp.where(live, yt, cur_row)
        return 0

    jax.lax.fori_loop(0, bt, step, 0)

    @pl.when(ti == pl.num_programs(1) - 1)
    def _store():
        cout[0:1, :] = started[...].astype(jnp.float32)
        cout[1:2, :] = run_start[...]
        cout[2:3, :] = runl[...].astype(jnp.float32)
        cout[3:4, :] = y0s[...]
        cout[4:5, :] = prev_y[...]
        cout[5:6, :] = a_lo[...]
        cout[6:7, :] = v_lo[...]
        cout[7:8, :] = a_hi[...]
        cout[8:9, :] = v_hi[...]
        cout[9:10, :] = p_ex[...].astype(jnp.float32)
        cout[10:11, :] = p_i0[...]
        cout[11:12, :] = p_i1[...]
        cout[12:13, :] = p_lk[...].astype(jnp.float32)
        cout[13:14, :] = p_lk_pos[...]
        cout[14:15, :] = p_lk_val[...]
        cout[15:16, :] = p_lo[...]
        cout[16:17, :] = p_hi[...]
        cout[17:18, :] = p_amid[...]
        cout[18:19, :] = p_vmid[...]
        cout[_HEAD_ROWS:_HEAD_ROWS + W2, :] = ring[...]


@functools.partial(jax.jit, static_argnames=("eps", "t_stop", "max_run",
                                             "window", "block_s", "block_t"))
def mixed_pallas(y_t: jax.Array, *, eps: float, t_stop: int,
                 max_run: int = 256, window: int | None = None,
                 block_s: int = BLOCK_S, block_t: int = BLOCK_T,
                 carry: jax.Array | None = None):
    """Run the Mixed kernel on time-major ``y_t: (Tp, Sp)``.

    Returns ``(ev, pos, a, v, carry_out)``; events are position-tagged
    (launch-local) and steps at ``t >= t_stop`` are inert.
    """
    W = check_window(max_run, window)
    if carry is None:
        carry = mixed_init_carry(y_t.shape[1], W)
    kernel = functools.partial(_mixed_kernel, eps=eps, bt=block_t,
                               t_stop=t_stop, max_run=max_run, window=W)
    f32 = jnp.float32
    scratch = [((1, block_s), jnp.int32),     # started
               ((mixed_ring(W), block_s), f32),  # ring
               ((1, block_s), f32),           # run_start
               ((1, block_s), jnp.int32),     # run_len
               ((1, block_s), f32),           # y0
               ((1, block_s), f32),           # prev_y
               ((1, block_s), f32),           # a_lo
               ((1, block_s), f32),           # v_lo
               ((1, block_s), f32),           # a_hi
               ((1, block_s), f32),           # v_hi
               ((1, block_s), jnp.int32),     # p_exists
               ((1, block_s), f32),           # p_i0
               ((1, block_s), f32),           # p_i1
               ((1, block_s), jnp.int32),     # p_lk
               ((1, block_s), f32),           # p_lk_pos
               ((1, block_s), f32),           # p_lk_val
               ((1, block_s), f32),           # p_lo
               ((1, block_s), f32),           # p_hi
               ((1, block_s), f32),           # p_amid
               ((1, block_s), f32)]           # p_vmid
    return launch_segmenter(kernel, y_t, block_s=block_s, block_t=block_t,
                            out_dtypes=DEFERRED_EVENT_DTYPES,
                            scratch=scratch, carry=carry)
