"""Pallas TPU kernel: batched Continuous PLA segmentation (paper §3.3).

The connected-polyline method with gate-deferred knot choice: the fitter
covers a *gate* interval (the feasible-value range inherited from the
previous segment at its last point) plus the current run's error
segments, and a break fixes the knot at the gate — which finally resolves
the *previous* segment's line.  Events therefore carry an explicit
**position** (they land one segment in the past): the kernel's event
outputs are ``(ev, pos, a, v)`` with launch-local positions, and the
wrappers scatter them into the canonical
:class:`repro.core.jax_pla.SegmentOutput` (``assemble_deferred`` in
:mod:`repro.kernels.ops`).

Unlike the aligned-event kernels there is **no in-kernel forced break**:
the flush needs two events (the pending segment and the trailing one),
so the kernel takes a static ``t_stop`` (steps at ``t >= t_stop`` are
inert; offline wrappers pass the real length, streaming pushes pass the
feed width) and the host closes the stream from the carry with
:func:`continuous_flush_carry` — the same jitted math for the offline and
chunked paths, which is what keeps them bit-identical: chunked pushes
through :class:`repro.kernels.ops.StreamingSegmenter` equal the one-shot
``continuous_segment_tpu`` output bitwise, and both equal the jnp
reference scan (tests/test_kernels.py, tests/test_streaming.py).

Carry rows (cont_state_rows(W) = 13 + W, all f32; see the carry-state
contract in kernels/common.py): 0 started, 1 g_pos, 2 glo, 3 ghi,
4 run_len, 5 has2 (extreme lines valid), 6 a_lo, 7 v_lo, 8 a_hi, 9 v_hi,
10 has_k, 11 k_pos, 12 k_val, then W ring rows.  Time is launch-local:
``cont_shift_carry`` renumbers the two position rows and rolls the ring
after each launch; all in-kernel position math is difference-based.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.jax_pla import check_window, _continuous_flush

from .common import BLOCK_S, BLOCK_T, launch_segmenter

_BIG = 3.4e38

_HEAD_ROWS = 13
# ev, pos, a, v — 32-bit flag for the same reason as SEGMENT_EVENT_DTYPES.
DEFERRED_EVENT_DTYPES = (jnp.int32, jnp.int32, jnp.float32, jnp.float32)


def cont_state_rows(window: int) -> int:
    return _HEAD_ROWS + window


def cont_init_carry(sp: int, window: int) -> jax.Array:
    return jnp.zeros((cont_state_rows(window), sp), jnp.float32)


def cont_shift_carry(carry: jax.Array, m: int) -> jax.Array:
    """Renumber to the next launch's local frame after consuming m cols."""
    carry = carry.at[1:2].add(-float(m))      # g_pos
    carry = carry.at[11:12].add(-float(m))    # k_pos
    return carry.at[_HEAD_ROWS:].set(
        jnp.roll(carry[_HEAD_ROWS:], -m, axis=0))


def cont_unpack_carry(carry: jax.Array, window: int):
    """Kernel carry -> the jnp engine's _continuous_* carry tuple (with
    launch-local positions), so the host flush reuses the shared math."""
    f32 = carry
    i32 = lambda r: carry[r].astype(jnp.int32)  # noqa: E731
    return (carry[_HEAD_ROWS:_HEAD_ROWS + window].T,
            i32(1), f32[2], f32[3], i32(4), i32(5),
            f32[6], f32[7], f32[8], f32[9], i32(10), i32(11), f32[12])


@functools.partial(jax.jit, static_argnames=("window", "t_last"))
def continuous_flush_carry(carry: jax.Array, window: int, t_last: int):
    """Close the stream from a carry: the pending-segment event (if any)
    plus the trailing segment's line at launch-local ``t_last``."""
    eps = jnp.zeros((carry.shape[1],), jnp.float32)  # unused by this flush
    return _continuous_flush(eps, None, window,
                             cont_unpack_carry(carry, window), t_last)


def _continuous_kernel(y_ref, cin, ev_ref, pos_ref, a_ref, v_ref, cout,
                       started, ring, g_pos, glo, ghi, runl, has2,
                       a_lo, v_lo, a_hi, v_hi, has_k, k_pos, k_val,
                       *, eps: float, bt: int, t_stop: int, max_run: int,
                       window: int):
    ti = pl.program_id(1)
    W = window

    @pl.when(ti == 0)
    def _load():
        started[...] = cin[0:1, :].astype(jnp.int32)
        g_pos[...] = cin[1:2, :]
        glo[...] = cin[2:3, :]
        ghi[...] = cin[3:4, :]
        runl[...] = cin[4:5, :].astype(jnp.int32)
        has2[...] = cin[5:6, :].astype(jnp.int32)
        a_lo[...] = cin[6:7, :]
        v_lo[...] = cin[7:8, :]
        a_hi[...] = cin[8:9, :]
        v_hi[...] = cin[9:10, :]
        has_k[...] = cin[10:11, :].astype(jnp.int32)
        k_pos[...] = cin[11:12, :]
        k_val[...] = cin[12:13, :]
        ring[...] = cin[_HEAD_ROWS:_HEAD_ROWS + W, :]

    slot_iota = jax.lax.broadcasted_iota(
        jnp.int32, (W, 1), 0).astype(jnp.float32)

    def step(j, _):
        t_loc = ti * bt + j
        live = t_loc < t_stop
        t = t_loc.astype(jnp.float32)
        yt = y_ref[pl.ds(j, 1), :]  # (1, BS)
        is_first = started[...] == 0

        gp, gl, gh = g_pos[...], glo[...], ghi[...]
        rl, h2 = runl[...], has2[...]
        al, vl, ah, vh = a_lo[...], v_lo[...], a_hi[...], v_hi[...]
        hk, kp, kv = has_k[...], k_pos[...], k_val[...]

        dg = t - gp
        lo_i, hi_i = yt - eps, yt + eps
        vmax = ah * dg + vh
        vmin = al * dg + vl
        feas = (vmax >= lo_i) & (vmin <= hi_i)
        cap_hit = rl >= max_run
        brk = (h2 == 1) & (~feas | cap_hit) & ~is_first & live

        # Knot fixed by this break: mid-line value at the gate.
        Kv = 0.5 * (vl + vh)
        dk = gp - kp
        dk_safe = jnp.where(dk > 0, dk, 1.0)
        evt = brk & (hk == 1)
        ev_ref[pl.ds(j, 1), :] = evt.astype(ev_ref.dtype)
        pos_ref[pl.ds(j, 1), :] = jnp.where(evt, gp, 0.0).astype(jnp.int32)
        a_ref[pl.ds(j, 1), :] = jnp.where(evt, (Kv - kv) / dk_safe, 0.0)
        v_ref[pl.ds(j, 1), :] = jnp.where(evt, Kv, 0.0)

        # ---- run window (positions strictly after the gate) -------------
        tm1 = t - 1.0
        p_r = tm1 - jnp.mod(tm1 - slot_iota, float(W))   # (W, 1)
        in_run = p_r > gp                                # (W, BS)
        dtw_safe = jnp.where(in_run, t - p_r, 1.0)
        yw = ring[...]

        # ---- retightening (gate = one extra constraint) -----------------
        need_hi = vmax > hi_i
        s_hi = jnp.where(in_run, (hi_i - (yw - eps)) / dtw_safe, _BIG)
        a_hi_new = jnp.minimum(jnp.min(s_hi, axis=0, keepdims=True),
                               (hi_i - gl) / dg)
        v_hi_new = hi_i - a_hi_new * dg
        a_hi_u = jnp.where(need_hi, a_hi_new, ah)
        v_hi_u = jnp.where(need_hi, v_hi_new, vh)

        need_lo = vmin < lo_i
        s_lo = jnp.where(in_run, (lo_i - (yw + eps)) / dtw_safe, -_BIG)
        a_lo_new = jnp.maximum(jnp.max(s_lo, axis=0, keepdims=True),
                               (lo_i - gh) / dg)
        v_lo_new = lo_i - a_lo_new * dg
        a_lo_u = jnp.where(need_lo, a_lo_new, al)
        v_lo_u = jnp.where(need_lo, v_lo_new, vl)

        first2 = h2 == 0
        a_hi_n = jnp.where(first2, (hi_i - gl) / dg, a_hi_u)
        v_hi_n = jnp.where(first2, gl, v_hi_u)
        a_lo_n = jnp.where(first2, (lo_i - gh) / dg, a_lo_u)
        v_lo_n = jnp.where(first2, gh, v_lo_u)

        # ---- break: next gate = wedge through K over the run ------------
        ds_safe = jnp.where(in_run, p_r - gp, 1.0)
        w1 = jnp.where(in_run, (yw - eps - Kv) / ds_safe, -_BIG)
        w2 = jnp.where(in_run, (yw + eps - Kv) / ds_safe, _BIG)
        wslo = jnp.max(w1, axis=0, keepdims=True)
        wshi = jnp.min(w2, axis=0, keepdims=True)
        dgn = tm1 - gp
        glo_b = Kv + wslo * dgn
        ghi_b = Kv + wshi * dgn
        a_hi_b = hi_i - glo_b
        a_lo_b = lo_i - ghi_b

        # ---- commit -----------------------------------------------------
        def sel(on_first, on_brk, on_add, cur):
            return jnp.where(live,
                             jnp.where(is_first, on_first,
                                       jnp.where(brk, on_brk, on_add)), cur)

        g_pos[...] = sel(t, tm1, gp, gp)
        glo[...] = sel(lo_i, glo_b, gl, gl)
        ghi[...] = sel(hi_i, ghi_b, gh, gh)
        runl[...] = sel(1, 1, rl + 1, rl).astype(jnp.int32)
        has2[...] = sel(0, 1, 1, h2).astype(jnp.int32)
        a_lo[...] = sel(0.0, a_lo_b, a_lo_n, al)
        v_lo[...] = sel(0.0, ghi_b, v_lo_n, vl)
        a_hi[...] = sel(0.0, a_hi_b, a_hi_n, ah)
        v_hi[...] = sel(0.0, glo_b, v_hi_n, vh)
        has_k[...] = sel(0, 1, hk, hk).astype(jnp.int32)
        k_pos[...] = sel(t, gp, kp, kp)
        k_val[...] = sel(0.0, Kv, kv, kv)
        started[...] = jnp.where(live, 1, started[...])
        row = pl.ds(jnp.mod(t_loc, W), 1)
        cur_row = ring[row, :]
        ring[row, :] = jnp.where(live, yt, cur_row)
        return 0

    jax.lax.fori_loop(0, bt, step, 0)

    @pl.when(ti == pl.num_programs(1) - 1)
    def _store():
        cout[0:1, :] = started[...].astype(jnp.float32)
        cout[1:2, :] = g_pos[...]
        cout[2:3, :] = glo[...]
        cout[3:4, :] = ghi[...]
        cout[4:5, :] = runl[...].astype(jnp.float32)
        cout[5:6, :] = has2[...].astype(jnp.float32)
        cout[6:7, :] = a_lo[...]
        cout[7:8, :] = v_lo[...]
        cout[8:9, :] = a_hi[...]
        cout[9:10, :] = v_hi[...]
        cout[10:11, :] = has_k[...].astype(jnp.float32)
        cout[11:12, :] = k_pos[...]
        cout[12:13, :] = k_val[...]
        cout[_HEAD_ROWS:_HEAD_ROWS + W, :] = ring[...]


@functools.partial(jax.jit, static_argnames=("eps", "t_stop", "max_run",
                                             "window", "block_s", "block_t"))
def continuous_pallas(y_t: jax.Array, *, eps: float, t_stop: int,
                      max_run: int = 256, window: int | None = None,
                      block_s: int = BLOCK_S, block_t: int = BLOCK_T,
                      carry: jax.Array | None = None):
    """Run the Continuous kernel on time-major ``y_t: (Tp, Sp)``.

    Returns ``(ev, pos, a, v, carry_out)``; events are position-tagged
    (launch-local) and steps at ``t >= t_stop`` are inert.
    """
    W = check_window(max_run, window)
    if carry is None:
        carry = cont_init_carry(y_t.shape[1], W)
    kernel = functools.partial(_continuous_kernel, eps=eps, bt=block_t,
                               t_stop=t_stop, max_run=max_run, window=W)
    f32 = jnp.float32
    scratch = [((1, block_s), jnp.int32),   # started
               ((W, block_s), f32),         # ring
               ((1, block_s), f32),         # g_pos
               ((1, block_s), f32),         # glo
               ((1, block_s), f32),         # ghi
               ((1, block_s), jnp.int32),   # run_len
               ((1, block_s), jnp.int32),   # has2
               ((1, block_s), f32),         # a_lo
               ((1, block_s), f32),         # v_lo
               ((1, block_s), f32),         # a_hi
               ((1, block_s), f32),         # v_hi
               ((1, block_s), jnp.int32),   # has_k
               ((1, block_s), f32),         # k_pos
               ((1, block_s), f32)]         # k_val
    return launch_segmenter(kernel, y_t, block_s=block_s, block_t=block_t,
                            out_dtypes=DEFERRED_EVENT_DTYPES,
                            scratch=scratch, carry=carry)
