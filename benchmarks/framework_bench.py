"""Framework-side benchmarks: segmenter kernel throughput and the
adaptive-ε regime change."""

from __future__ import annotations

import time
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _time(fn, *args, iters=3) -> float:
    # One warmup call (compile + first run); branch on the held result
    # instead of invoking fn twice.
    res = fn(*args)
    if isinstance(res, tuple):
        res[0].block_until_ready()
    else:
        jax.block_until_ready(res)
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6  # us


def kernel_throughput() -> List[Tuple[str, float, str]]:
    """us/call + points/s of the jitted batched segmenters (CPU numbers;
    TPU kernels are validated in interpret mode and timed on hardware)."""
    from repro.core.jax_pla import (angle_segment, disjoint_segment,
                                    linear_segment, propagate_lines)
    rng = np.random.default_rng(0)
    rows = []
    for S, T in ((256, 256), (1024, 256)):
        y = jnp.asarray(np.cumsum(rng.normal(0, .5, (S, T)), 1), jnp.float32)
        for name, fn in (("angle", angle_segment),
                         ("disjoint", disjoint_segment),
                         ("linear", linear_segment)):
            f = jax.jit(lambda y: fn(y, 1.0, max_run=256))
            us = _time(f, y)
            rows.append((f"jax_pla/{name}/{S}x{T}", us,
                         f"{S*T/us*1e6/1e6:.1f}Mpts/s"))
        f = jax.jit(lambda y: propagate_lines(angle_segment(y, 1.0,
                                                            max_run=256)))
        us = _time(f, y)
        rows.append((f"jax_pla/reconstruct/{S}x{T}", us,
                     f"{S*T/us*1e6/1e6:.1f}Mpts/s"))
    return rows


def adaptive_eps_bench() -> List[Tuple[str, float, str]]:
    """The paper's §8 extension: adaptive ε holding a target ratio across
    a smooth -> noise -> smooth regime change that defeats any fixed ε."""
    from repro.core.adaptive import compare_fixed_vs_adaptive
    rng = np.random.default_rng(0)
    n = 9000
    ts = np.arange(n, dtype=float)
    ys = np.concatenate([
        np.cumsum(rng.normal(0, 0.02, n // 3)),
        10 * rng.normal(0, 1.0, n // 3),
        5 + np.cumsum(rng.normal(0, 0.02, n - 2 * (n // 3)))])
    t0 = time.perf_counter()
    rep = compare_fixed_vs_adaptive(ts, ys, fixed_eps=0.05,
                                    target_ratio=0.15)
    us = (time.perf_counter() - t0) * 1e6
    return [("adaptive_eps/regime_change", us,
             f"fixed={rep['fixed_ratio']:.3f}x "
             f"adaptive={rep['adaptive_ratio']:.3f}x "
             f"eps {rep['adaptive_eps_range'][0]:.3g}.."
             f"{rep['adaptive_eps_range'][1]:.3g}")]
