"""Pallas TPU kernel: segment-stream → dense stream reconstruction.

Reverse time walk: each point takes the line of the segment ending at the
next break at-or-after it.  The grid's sequential dimension maps to time
blocks in *reverse* order via the BlockSpec index map; the (a, b) carry
lives in VMEM scratch and is resumed through the packed carry operand.
Two kernel bodies share the walk: plain reconstruction, and a fused
reconstruct-plus-|error| variant (:func:`reconstruct_error_pallas`) that
feeds the batched §4.2 approximation-error metric in one pass.

Carry rows (RECON_STATE_ROWS = 3, all f32; see kernels/common.py):
0 ca (slope), 1 cv (value at anchor), 2 cd (distance to anchor).  The
carry propagates *backward* in time, so a chunked reconstruction pushes
suffix chunks first: launch the latest (Tp-multiple) slab with a zero
carry, then hand its carry-out to the preceding slab.  ``cd`` is a
distance (frame-free) — no host-side shift is needed between launches.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import BLOCK_S, BLOCK_T, launch_segmenter

RECON_STATE_ROWS = 3


def recon_init_carry(sp: int) -> jax.Array:
    return jnp.zeros((RECON_STATE_ROWS, sp), jnp.float32)


def _recon_kernel(brk_ref, a_ref, v_ref, cin, out_ref, cout, ca, cv, cd,
                  *, bt: int, nt: int):
    ti = pl.program_id(1)  # 0 .. nt-1, mapped to reversed time blocks

    @pl.when(ti == 0)
    def _load():
        ca[...] = cin[0:1, :]
        cv[...] = cin[1:2, :]
        cd[...] = cin[2:3, :]

    def step(k, _):
        j = bt - 1 - k  # walk rows backwards
        brk = brk_ref[pl.ds(j, 1), :] != 0
        at = a_ref[pl.ds(j, 1), :]
        vt = v_ref[pl.ds(j, 1), :]
        # Anchored evaluation: carry (slope, value at anchor, distance to
        # anchor); y(t) = v - a * d.  No absolute-t products — float32 safe
        # at any stream length.
        new_a = jnp.where(brk, at, ca[...])
        new_v = jnp.where(brk, vt, cv[...])
        new_d = jnp.where(brk, jnp.zeros_like(cd[...]), cd[...])
        ca[...] = new_a
        cv[...] = new_v
        cd[...] = new_d + 1.0
        out_ref[pl.ds(j, 1), :] = new_v - new_a * new_d
        return 0

    jax.lax.fori_loop(0, bt, step, 0)

    @pl.when(ti == pl.num_programs(1) - 1)
    def _store():
        cout[0:1, :] = ca[...]
        cout[1:2, :] = cv[...]
        cout[2:3, :] = cd[...]


def _recon_err_kernel(brk_ref, a_ref, v_ref, y_ref, cin, out_ref, err_ref,
                      cout, ca, cv, cd, *, bt: int, nt: int):
    """Fused variant for the §4.2 metrics engine: reconstruct and emit
    ``|y' - y|`` in the same reverse walk (one pass over the stream
    instead of reconstruct-then-subtract on the host)."""
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _load():
        ca[...] = cin[0:1, :]
        cv[...] = cin[1:2, :]
        cd[...] = cin[2:3, :]

    def step(k, _):
        j = bt - 1 - k
        brk = brk_ref[pl.ds(j, 1), :] != 0
        at = a_ref[pl.ds(j, 1), :]
        vt = v_ref[pl.ds(j, 1), :]
        yt = y_ref[pl.ds(j, 1), :]
        new_a = jnp.where(brk, at, ca[...])
        new_v = jnp.where(brk, vt, cv[...])
        new_d = jnp.where(brk, jnp.zeros_like(cd[...]), cd[...])
        ca[...] = new_a
        cv[...] = new_v
        cd[...] = new_d + 1.0
        recon = new_v - new_a * new_d
        out_ref[pl.ds(j, 1), :] = recon
        err_ref[pl.ds(j, 1), :] = jnp.abs(recon - yt)
        return 0

    jax.lax.fori_loop(0, bt, step, 0)

    @pl.when(ti == pl.num_programs(1) - 1)
    def _store():
        cout[0:1, :] = ca[...]
        cout[1:2, :] = cv[...]
        cout[2:3, :] = cd[...]


@functools.partial(jax.jit, static_argnames=("block_s", "block_t"))
def reconstruct_error_pallas(brk_t: jax.Array, a_t: jax.Array,
                             v_t: jax.Array, y_t: jax.Array,
                             block_s: int = BLOCK_S, block_t: int = BLOCK_T,
                             carry: jax.Array | None = None):
    """Time-major (Tp, Sp) events + raw values -> (recon, |err|, carry).

    Same carry contract as :func:`reconstruct_pallas` (reverse-chunked
    streaming); the error output feeds the batched approximation-error
    metric without a second pass over the reconstruction.
    """
    Tp, Sp = a_t.shape
    if carry is None:
        carry = recon_init_carry(Sp)
    nt = Tp // block_t
    kernel = functools.partial(_recon_err_kernel, bt=block_t, nt=nt)
    scratch = [((1, block_s), jnp.float32)] * 3
    out, err, carry_out = launch_segmenter(
        kernel, (brk_t.astype(jnp.int32), a_t, v_t, y_t),
        block_s=block_s, block_t=block_t,
        out_dtypes=(a_t.dtype, a_t.dtype), scratch=scratch,
        reverse_time=True, carry=carry)
    return out, err, carry_out


@functools.partial(jax.jit, static_argnames=("block_s", "block_t"))
def reconstruct_pallas(brk_t: jax.Array, a_t: jax.Array, v_t: jax.Array,
                       block_s: int = BLOCK_S, block_t: int = BLOCK_T,
                       carry: jax.Array | None = None):
    """Time-major (Tp, Sp) breaks/a/v -> (Tp, Sp) reconstructed values.

    Break flags of any integer or bool dtype are read as 32-bit rows
    (the per-step dynamic row load needs a 32-bit tile).  Returns
    ``(out, carry_out)``; pass the carry-out of a later-in-time
    slab as ``carry`` to reconstruct the preceding slab (reverse-chunked
    streaming).  ``carry=None`` starts from the stream tail.
    """
    Tp, Sp = a_t.shape
    if carry is None:
        carry = recon_init_carry(Sp)
    nt = Tp // block_t
    kernel = functools.partial(_recon_kernel, bt=block_t, nt=nt)
    scratch = [((1, block_s), jnp.float32)] * 3
    # Sequential dim walks time blocks in reverse (reverse_time index map).
    out, carry_out = launch_segmenter(kernel,
                                      (brk_t.astype(jnp.int32), a_t, v_t),
                                      block_s=block_s, block_t=block_t,
                                      out_dtypes=(a_t.dtype,),
                                      scratch=scratch,
                                      reverse_time=True, carry=carry)
    return out, carry_out
