"""Pallas TPU kernel: batched optimal-disjoint PLA segmentation (§3.2).

Mirrors the amortized hull carry of ``core.jax_pla._disjoint_step``: the
convex-hull pivot search runs on two compact per-stream convex chains
carried in VMEM — ``hl`` the *upper* chain of lower endpoints
``(t, y - eps)`` (the oracle's ``env_lo``, queried for ``a_hi``), ``hh``
the *lower* chain of upper endpoints (``env_hi``, queried for ``a_lo``) —
popped with the exact ``hulls._HullChain.add`` cross tests and queried by
a hinted tangent walk (amortized O(1) per point).  The jnp engine keeps
the same chains in capacity-capped ``(S, C)`` planes with closed-form
pops (``core.jax_pla._chain_append`` / ``_chain_extremum``); here VMEM
rows are cheap, so the kernel carries the full-window chains and walks
them sequentially — same hull semantics, different carry shape.

Lines are anchored at the run start (``line(t) = v + a * (t - run_start)``)
so float32 stays exact for arbitrarily long streams.

Per-lane chain indexing uses one-hot masked reductions over the (W, BS)
chain planes (gather) and one-hot selects (scatter) — exact, since adding
zeros and selecting rows do not round.

Carry rows (disjoint_state_rows(W) = 13 + 4W, all f32; see the carry-state
contract in kernels/common.py): 0 started, 1 run_start, 2 run_len, 3 y0,
4 prev_y, 5 a_lo, 6 v_lo, 7 a_hi, 8 v_hi, 9 hl_len, 10 hh_len, 11 hl_c,
12 hh_c, then four W-row blocks hl_pos, hl_val, hh_pos, hh_val.  Time is
launch-local, so ``run_start`` and the chain position rows may be
*negative* on resume (run began in an earlier chunk — never below ``-W``
since runs are capped); ``disjoint_shift_carry`` renumbers them after
each launch.  All uses are differences, so the renumbering is
bit-transparent; lengths and contact hints are counts, not positions, and
shift untouched.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.jax_pla import check_window

from .common import BLOCK_S, BLOCK_T, launch_segmenter

_HEAD_ROWS = 13  # scalar state rows before the chain planes


def disjoint_state_rows(window: int) -> int:
    return _HEAD_ROWS + 4 * window


def disjoint_init_carry(sp: int, window: int) -> jax.Array:
    return jnp.zeros((disjoint_state_rows(window), sp), jnp.float32)


def disjoint_shift_carry(carry: jax.Array, m: int) -> jax.Array:
    """Renumber to the next launch's local frame after consuming m cols."""
    W = (carry.shape[0] - _HEAD_ROWS) // 4
    carry = carry.at[1:2].add(-float(m))                       # run_start
    carry = carry.at[_HEAD_ROWS:_HEAD_ROWS + W].add(-float(m))  # hl_pos
    h2 = _HEAD_ROWS + 2 * W
    return carry.at[h2:h2 + W].add(-float(m))                   # hh_pos


def _gather(buf, idx, slot_iota):
    """One-hot per-lane gather: ``buf (W, BS)``, ``idx (1, BS) -> (1, BS)``.

    Exact — selects a single row and adds zeros, neither of which rounds.
    """
    return jnp.sum(jnp.where(slot_iota == idx, buf, 0.0), axis=0,
                   keepdims=True)


def _chain_pop(pos_ref, val_ref, ln, keep, px, py, slot_iota, upper: bool):
    """Sequential pop + append with ``hulls._HullChain.add``'s cross tests.

    All operands are (1, BS) rows over (W, BS) chain planes; returns the
    length after the append.  ``keep=False`` lanes reset to the single new
    vertex.
    """
    pos, val = pos_ref[...], val_ref[...]

    def g(buf, idx):
        return _gather(buf, idx, slot_iota)

    def flags(ln):
        # int32, not bool: Mosaic cannot carry an i1 vector through a loop.
        can = keep & (ln >= 2)
        ox, oy = g(pos, jnp.maximum(ln - 2, 0)), g(val, jnp.maximum(ln - 2, 0))
        ax, ay = g(pos, jnp.maximum(ln - 1, 0)), g(val, jnp.maximum(ln - 1, 0))
        cr = (ax - ox) * (py - oy) - (ay - oy) * (px - ox)
        return (can & (cr >= 0 if upper else cr <= 0)).astype(jnp.int32)

    def body(st):
        ln, f = st
        ln = jnp.where(f != 0, ln - 1, ln)
        return ln, flags(ln)

    ln, _ = jax.lax.while_loop(lambda st: jnp.any(st[1] != 0), body,
                               (ln, flags(ln)))
    slot = jnp.where(keep, ln, 0)
    pos_ref[...] = jnp.where(slot_iota == slot, px, pos)
    val_ref[...] = jnp.where(slot_iota == slot, py, val)
    return slot + 1


def _chain_walk(pos_ref, val_ref, ln, c0, active, slope_of, slot_iota,
                minimize: bool):
    """Hinted tangent walk: slide the contact index while the slope improves.

    Finds the same chain extremum as ``core.jax_pla._chain_extremum`` but
    amortized O(1) via the carried contact hint instead of a masked
    reduction over the whole chain.
    """
    pos, val = pos_ref[...], val_ref[...]

    def g(buf, idx):
        return _gather(buf, idx, slot_iota)

    better = (lambda a, b: a <= b) if minimize else (lambda a, b: a >= b)
    last = ln - 1
    c = jnp.clip(c0, 0, last)
    s_c = slope_of(g(pos, c), g(val, c))
    cp = jnp.minimum(c + 1, last)
    s_p = slope_of(g(pos, cp), g(val, cp))
    cm = jnp.maximum(c - 1, 0)
    s_m = slope_of(g(pos, cm), g(val, cm))
    fwd = active & (cp != c) & better(s_p, s_c)
    bwd = active & ~fwd & (cm != c) & better(s_m, s_c)
    dirn = jnp.where(fwd, 1, jnp.where(bwd, -1, 0))

    def body(st):
        c, s_c, dirn = st
        cn = jnp.clip(c + dirn, 0, last)
        s_n = slope_of(g(pos, cn), g(val, cn))
        ok = (dirn != 0) & (cn != c) & better(s_n, s_c)
        return (jnp.where(ok, cn, c), jnp.where(ok, s_n, s_c),
                jnp.where(ok, dirn, 0))

    c, s_c, _ = jax.lax.while_loop(lambda st: jnp.any(st[2] != 0), body,
                                   (c, s_c, dirn))
    return c, s_c


def _disjoint_kernel(y_ref, cin, brk_ref, a_ref, v_ref, cout,
                     started, run_start, runl, y0s, prev_y,
                     a_lo, v_lo, a_hi, v_hi,
                     hl_len, hh_len, hl_c, hh_c,
                     hl_pos, hl_val, hh_pos, hh_val,
                     *, eps: float, bt: int, t_real: int, max_run: int,
                     window: int):
    ti = pl.program_id(1)
    W = window

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
        hl_len[...] = cin[9:10, :].astype(jnp.int32)
        hh_len[...] = cin[10:11, :].astype(jnp.int32)
        hl_c[...] = cin[11:12, :].astype(jnp.int32)
        hh_c[...] = cin[12:13, :].astype(jnp.int32)
        hl_pos[...] = cin[_HEAD_ROWS:_HEAD_ROWS + W, :]
        hl_val[...] = cin[_HEAD_ROWS + W:_HEAD_ROWS + 2 * W, :]
        hh_pos[...] = cin[_HEAD_ROWS + 2 * W:_HEAD_ROWS + 3 * W, :]
        hh_val[...] = cin[_HEAD_ROWS + 3 * W:_HEAD_ROWS + 4 * W, :]

    slot_iota = jax.lax.broadcasted_iota(jnp.int32, (W, 1), 0)

    def step(j, _):
        t_loc = ti * bt + j
        t = t_loc.astype(jnp.float32)
        yt = y_ref[pl.ds(j, 1), :]  # (1, BS)
        is_first = started[...] == 0

        rs, rl = run_start[...], runl[...]
        al, vl, ah, vh = a_lo[...], v_lo[...], a_hi[...], v_hi[...]
        y0, py = y0s[...], prev_y[...]
        rel = t - rs

        lo_i, hi_i = yt - eps, yt + eps
        vmax = ah * rel + vh
        vmin = al * rel + vl
        feas2 = (vmax >= lo_i) & (vmin <= hi_i)
        cap_hit = rl >= max_run
        force = t_loc == t_real
        brk = ((rl >= 2) & ~feas2 | cap_hit | force) & ~is_first

        # Chosen line anchored at the break position (t-1): parameter-space
        # midpoint of the extreme lines (feasible by convexity).
        am = 0.5 * (al + ah)
        vm = 0.5 * (vl + vh) + am * (rel - 1.0)
        a_out = jnp.where(rl >= 2, am, 0.0)
        v_out = jnp.where(rl >= 2, vm, py)

        brk_ref[pl.ds(j, 1), :] = brk.astype(brk_ref.dtype)
        a_ref[pl.ds(j, 1), :] = jnp.where(brk, a_out, 0.0)
        v_ref[pl.ds(j, 1), :] = jnp.where(brk, v_out, 0.0)

        # --- tangent retightening over the run's convex chains -----------
        second = rl == 1
        need_hi = vmax > hi_i
        act_hi = need_hi & ~second & ~brk & ~is_first
        c_hi, a_hi_new = _chain_walk(
            hl_pos, hl_val, hl_len[...], hl_c[...], act_hi,
            lambda qx, qy: (hi_i - qy) / (t - qx), slot_iota, minimize=True)
        v_hi_new = hi_i - a_hi_new * rel                     # value at rs
        a_hi_u = jnp.where(act_hi, a_hi_new, ah)
        v_hi_u = jnp.where(act_hi, v_hi_new, vh)

        need_lo = vmin < lo_i
        act_lo = need_lo & ~second & ~brk & ~is_first
        c_lo, a_lo_new = _chain_walk(
            hh_pos, hh_val, hh_len[...], hh_c[...], act_lo,
            lambda qx, qy: (lo_i - qy) / (t - qx), slot_iota, minimize=False)
        v_lo_new = lo_i - a_lo_new * rel
        a_lo_u = jnp.where(act_lo, a_lo_new, al)
        v_lo_u = jnp.where(act_lo, v_lo_new, vl)

        # Second point of a run initializes the extreme lines directly.
        rel_s = jnp.maximum(rel, 1.0)
        a_hi_2 = (hi_i - (y0 - eps)) / rel_s
        a_lo_2 = (lo_i - (y0 + eps)) / rel_s

        a_hi_n = jnp.where(second, a_hi_2, a_hi_u)
        v_hi_n = jnp.where(second, y0 - eps, v_hi_u)
        a_lo_n = jnp.where(second, a_lo_2, a_lo_u)
        v_lo_n = jnp.where(second, y0 + eps, v_lo_u)

        # --- commit --------------------------------------------------------
        restart = brk | is_first
        run_start[...] = jnp.where(restart, t, rs)
        runl[...] = jnp.where(restart, 1, rl + 1).astype(jnp.int32)
        y0s[...] = jnp.where(restart, yt, y0)
        prev_y[...] = yt
        a_lo[...] = jnp.where(restart, 0.0, a_lo_n)
        v_lo[...] = jnp.where(restart, 0.0, v_lo_n)
        a_hi[...] = jnp.where(restart, 0.0, a_hi_n)
        v_hi[...] = jnp.where(restart, 0.0, v_hi_n)
        started[...] = jnp.ones_like(started[...])
        keep = ~restart
        ln_l = _chain_pop(hl_pos, hl_val, hl_len[...], keep, t, lo_i,
                          slot_iota, upper=True)
        ln_h = _chain_pop(hh_pos, hh_val, hh_len[...], keep, t, hi_i,
                          slot_iota, upper=False)
        hl_len[...] = ln_l
        hh_len[...] = ln_h
        hl_c[...] = jnp.where(restart, 0, jnp.minimum(c_hi, ln_l - 1))
        hh_c[...] = jnp.where(restart, 0, jnp.minimum(c_lo, ln_h - 1))
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
        cout[9:10, :] = hl_len[...].astype(jnp.float32)
        cout[10:11, :] = hh_len[...].astype(jnp.float32)
        cout[11:12, :] = hl_c[...].astype(jnp.float32)
        cout[12:13, :] = hh_c[...].astype(jnp.float32)
        cout[_HEAD_ROWS:_HEAD_ROWS + W, :] = hl_pos[...]
        cout[_HEAD_ROWS + W:_HEAD_ROWS + 2 * W, :] = hl_val[...]
        cout[_HEAD_ROWS + 2 * W:_HEAD_ROWS + 3 * W, :] = hh_pos[...]
        cout[_HEAD_ROWS + 3 * W:_HEAD_ROWS + 4 * W, :] = hh_val[...]


@functools.partial(jax.jit, static_argnames=("eps", "t_real", "max_run",
                                             "window", "block_s", "block_t"))
def disjoint_pallas(y_t: jax.Array, *, eps: float, t_real: int,
                    max_run: int = 256, window: int | None = None,
                    block_s: int = BLOCK_S, block_t: int = BLOCK_T,
                    carry: jax.Array | None = None):
    W = check_window(max_run, window)
    if carry is None:
        carry = disjoint_init_carry(y_t.shape[1], W)
    kernel = functools.partial(_disjoint_kernel, eps=eps, bt=block_t,
                               t_real=t_real, max_run=max_run, window=W)
    f32 = jnp.float32
    i32 = jnp.int32
    scratch = [((1, block_s), i32),  # started
               ((1, block_s), f32),  # run_start (local f32 t)
               ((1, block_s), i32),  # run_len
               ((1, block_s), f32),  # y0 (run start value)
               ((1, block_s), f32),  # prev y
               ((1, block_s), f32),  # a_lo
               ((1, block_s), f32),  # v_lo
               ((1, block_s), f32),  # a_hi
               ((1, block_s), f32),  # v_hi
               ((1, block_s), i32),  # hl_len
               ((1, block_s), i32),  # hh_len
               ((1, block_s), i32),  # hl_c
               ((1, block_s), i32),  # hh_c
               ((W, block_s), f32),  # hl_pos
               ((W, block_s), f32),  # hl_val
               ((W, block_s), f32),  # hh_pos
               ((W, block_s), f32)]  # hh_val
    return launch_segmenter(kernel, y_t, block_s=block_s, block_t=block_t,
                            scratch=scratch, carry=carry)
