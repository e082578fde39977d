"""The TSBS devops ``cpu-only`` series, made on the device from a seed.

TSBS (github.com/timescale/tsbs) generates each host's CPU metrics as a
clamped random walk: every field starts uniform in ``[lo, hi)`` and moves
by a normal step each interval, clamped to ``[lo, hi]``.  Here the walk
runs in one jitted scan per block of samples, so a run makes its data in
a few device calls and copies it to the host once, where the system
under test receives it as a client's samples.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def jax_key(seed: int):
    """A JAX key from any non-negative seed (``jax.random.key`` keeps only
    the low 32 bits)."""
    word = int(np.random.SeedSequence(int(seed)).generate_state(1)[0])
    return jax.random.key(word)


@functools.partial(jax.jit, static_argnames=("n_steps",))
def _walk(key, v, step_sd, lo, hi, n_steps):
    z = jax.random.normal(key, (n_steps, v.shape[0]), jnp.float32)

    def step(v, zt):
        v = jnp.clip(v + step_sd * zt, lo, hi)
        return v, v

    v, ys = jax.lax.scan(step, v, z)
    return v, ys.T


def walk_blocks(seed: int, n_streams: int, block: int, n_blocks: int,
                walk: dict, device) -> list:
    """``n_blocks`` consecutive ``(n_streams, block)`` float32 host arrays
    of one walk per stream, from ``seed``."""
    lo, hi, sd = float(walk["lo"]), float(walk["hi"]), float(walk["step_sd"])
    key = jax_key(seed)
    out = []
    with jax.default_device(device):
        v = jax.random.uniform(jax.random.fold_in(key, 0), (n_streams,),
                               jnp.float32, lo, hi)
        for k in range(n_blocks):
            v, ys = _walk(jax.random.fold_in(key, k + 1), v, sd, lo, hi,
                          n_steps=block)
            out.append(np.asarray(ys))
    return out
