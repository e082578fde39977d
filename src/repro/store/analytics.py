"""Plato-style closed-form analytics on decoded segment descriptors.

Following Plato (arXiv 1808.04876), every supported aggregate evaluates
*on the segment descriptors* — never on a materialized series.  A
decoded window is a tiling of ``[lo, hi)`` by intervals, each carrying a
grid-form line ``y(i) = Ag * i + Bg`` (exact values ride along as
one-point intervals with ``Ag = 0``), and the aggregates reduce the
closed forms

- ``sum  i            = (lo + hi - 1) n / 2``
- ``sum  i^2          = F(hi-1) - F(lo-1)``,  ``F(m) = m(m+1)(2m+1)/6``

per interval in one batched jit over ``(S, E)`` descriptor arrays (E is
bucketed to a power of two so window sweeps reuse compilations).  The
absolute sum — needed for the correlation error bound — splits each
interval at its line's zero crossing, so it too is exact closed form.

Error bounds (derivation in docs/ARCHITECTURE.md): with ``n_ax`` approx
points in the window and per-stream wire guarantee ``|y - yhat| <= eps``,

- ``SUM``: ``eps * n_ax``            - ``COUNT``: 0
- ``AVG``: ``eps * n_ax / n``        - ``MIN/MAX``: ``eps`` if n_ax else 0
- correlation: interval arithmetic through the moment sums —
  ``|d Sx| <= eps_x n_ax``, ``|d Sxx| <= 2 eps_x sum|x| + n_ax eps_x^2``,
  ``|d Sxy| <= eps_y sum|x| + eps_x sum|y| + min(n_ax, n_ay) eps_x
  eps_y`` — then through covariance / variances / the quotient, clipped
  to ``[-1, 1]``.  A variance interval touching zero yields an infinite
  (still sound) bound.
"""

from __future__ import annotations

import math
from typing import List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.wire_decode import KIND_SEGMENT, WireRecords

__all__ = ["AGG_KINDS", "Cover", "cover_arrays", "window_aggregate",
           "window_correlation"]

AGG_KINDS = ("sum", "avg", "min", "max", "count", "corr")


class Cover(NamedTuple):
    """One stream's window tiling: grid-form lines per interval."""

    s: np.ndarray        # int64 interval start (first position)
    e: np.ndarray        # int64 interval end (exclusive)
    Ag: np.ndarray       # f64 grid slope
    Bg: np.ndarray       # f64 grid intercept
    approx: np.ndarray   # bool: True = eps-approximated segment


def cover_arrays(recs: WireRecords, lo: int, hi: int, t0: float,
                 dt: float) -> Cover:
    """Clip decoded records to ``[lo, hi)`` and gridify their lines.

    Exact records expand to one interval per point (each point its own
    ``Bg``); the result tiles the window exactly or raises.
    """
    st = recs.start
    s_c = np.maximum(st, lo)
    e_c = np.minimum(st + recs.length, hi)
    live = e_c > s_c
    segm = live & (recs.kind == KIND_SEGMENT)
    # y(i) = yref + a * (t0 + dt * i - tref)  ==  (a dt) i + (yref + a (t0 - tref))
    s_parts = [s_c[segm]]
    e_parts = [e_c[segm]]
    ag_parts = [recs.a[segm] * dt]
    bg_parts = [recs.yref[segm] + recs.a[segm] * (t0 - recs.tref[segm])]
    ap_parts = [np.ones(int(segm.sum()), bool)]
    exm = np.flatnonzero(live & (recs.kind != KIND_SEGMENT))
    if exm.size:
        counts = (e_c[exm] - s_c[exm]).astype(np.int64)
        tot = int(counts.sum())
        base = np.repeat(np.cumsum(counts) - counts, counts)
        offs = np.arange(tot, dtype=np.int64) - base
        pts = np.repeat(s_c[exm], counts) + offs
        vstart = recs.vpos[exm] + (s_c[exm] - st[exm])
        vals = recs.values[np.repeat(vstart, counts) + offs]
        s_parts.append(pts)
        e_parts.append(pts + 1)
        ag_parts.append(np.zeros(tot, np.float64))
        bg_parts.append(vals.astype(np.float64))
        ap_parts.append(np.zeros(tot, bool))
    s = np.concatenate(s_parts).astype(np.int64)
    e = np.concatenate(e_parts).astype(np.int64)
    order = np.argsort(s, kind="stable")
    cov = Cover(s[order], e[order],
                np.concatenate(ag_parts)[order].astype(np.float64),
                np.concatenate(bg_parts)[order].astype(np.float64),
                np.concatenate(ap_parts)[order])
    if cov.s.size == 0 or cov.s[0] != lo or cov.e[-1] != hi \
            or not np.array_equal(cov.s[1:], cov.e[:-1]):
        raise ValueError(f"decoded records do not tile [{lo}, {hi})")
    return cov


# ---------------------------------------------------------------------------
# Batched jit cores over padded (S, E) descriptor arrays
# ---------------------------------------------------------------------------

def _bucket(n: int, lo: int = 8) -> int:
    return max(lo, 1 << max(n - 1, 0).bit_length())


def _sum_i(sf, ef):
    """sum of i over [sf, ef) — closed form, f64."""
    n = ef - sf
    return (sf + ef - 1.0) * n * 0.5


def _sum_i2(sf, ef):
    """sum of i^2 over [sf, ef)."""
    def F(m):
        return m * (m + 1.0) * (2.0 * m + 1.0) / 6.0
    return F(ef - 1.0) - F(sf - 1.0)


def _interval_terms(sf, ef, Ag, Bg):
    """Per-interval closed forms: (n, sum, abs_sum, v_first, v_last)."""
    n = ef - sf
    total = Ag * _sum_i(sf, ef) + Bg * n
    v_first = Ag * sf + Bg
    v_last = Ag * (ef - 1.0) + Bg
    # Split at the line's zero crossing: both halves are single-signed,
    # so |sum(left)| + |sum(right)| is exactly sum|y|.
    ratio = jnp.where(Ag != 0.0, -Bg / jnp.where(Ag != 0.0, Ag, 1.0),
                      jnp.inf)
    m = jnp.clip(jnp.floor(ratio) + 1.0, sf, ef)
    n_l = m - sf
    sum_l = Ag * (sf + m - 1.0) * n_l * 0.5 + Bg * n_l
    abs_sum = jnp.abs(sum_l) + jnp.abs(total - sum_l)
    return n, total, abs_sum, v_first, v_last


@jax.jit
def _agg_core(s, e, Ag, Bg, approx):
    """(S, E) padded intervals -> per-stream window statistics."""
    sf = s.astype(jnp.float64)
    ef = e.astype(jnp.float64)
    valid = e > s
    n, total, abs_sum, v_first, v_last = _interval_terms(sf, ef, Ag, Bg)
    vmin_i = jnp.minimum(v_first, v_last)
    vmax_i = jnp.maximum(v_first, v_last)
    return (jnp.sum(n, axis=1),
            jnp.sum(n * approx, axis=1),
            jnp.sum(total, axis=1),
            jnp.sum(abs_sum, axis=1),
            jnp.min(jnp.where(valid, vmin_i, jnp.inf), axis=1),
            jnp.max(jnp.where(valid, vmax_i, -jnp.inf), axis=1))


@jax.jit
def _corr_core(s, e, Ax, Bx, Ay, By, apx, apy):
    """Merged (E,) intervals -> joint moment sums for two streams."""
    sf = s.astype(jnp.float64)
    ef = e.astype(jnp.float64)
    n = ef - sf
    S1 = _sum_i(sf, ef)
    S2 = _sum_i2(sf, ef)
    _, Sx, absx, _, _ = _interval_terms(sf, ef, Ax, Bx)
    _, Sy, absy, _, _ = _interval_terms(sf, ef, Ay, By)
    Sxx = Ax * Ax * S2 + 2.0 * Ax * Bx * S1 + Bx * Bx * n
    Syy = Ay * Ay * S2 + 2.0 * Ay * By * S1 + By * By * n
    Sxy = Ax * Ay * S2 + (Ax * By + Ay * Bx) * S1 + Bx * By * n
    return (jnp.sum(n), jnp.sum(Sx), jnp.sum(Sy), jnp.sum(Sxx),
            jnp.sum(Syy), jnp.sum(Sxy), jnp.sum(absx), jnp.sum(absy),
            jnp.sum(n * apx), jnp.sum(n * apy))


def _pad(a, E, dtype):
    out = np.zeros(E, dtype)
    out[:a.size] = a
    return out


def _pad_stack(covers: Sequence[Cover]):
    E = _bucket(max(c.s.size for c in covers))
    s = np.stack([_pad(c.s, E, np.int64) for c in covers])
    e = np.stack([_pad(c.e, E, np.int64) for c in covers])
    Ag = np.stack([_pad(c.Ag, E, np.float64) for c in covers])
    Bg = np.stack([_pad(c.Bg, E, np.float64) for c in covers])
    ap = np.stack([_pad(c.approx, E, bool) for c in covers])
    return s, e, Ag, Bg, ap


def window_aggregate(kind: str, covers: Sequence[Cover], eps,
                     lo: int, hi: int
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Batched ``(value, error_bound)`` per stream over ``[lo, hi)``."""
    if kind not in ("sum", "avg", "min", "max", "count"):
        raise ValueError(f"unknown aggregate {kind!r}")
    eps = np.asarray(eps, np.float64)
    s, e, Ag, Bg, ap = _pad_stack(covers)
    with jax.enable_x64(True):
        n, n_ax, total, _, vmin, vmax = (
            np.asarray(r) for r in _agg_core(
                jnp.asarray(s), jnp.asarray(e), jnp.asarray(Ag),
                jnp.asarray(Bg), jnp.asarray(ap)))
    if not np.all(n == hi - lo):
        raise ValueError("window cover is incomplete")
    if kind == "count":
        return n.astype(np.float64), np.zeros_like(eps)
    if kind == "sum":
        return total, eps * n_ax
    if kind == "avg":
        return total / n, eps * n_ax / n
    edge = np.where(n_ax > 0, eps, 0.0)
    return (vmin, edge) if kind == "min" else (vmax, edge)


def _merge(cov_x: Cover, cov_y: Cover):
    """Refine two tilings of the same window into one joint tiling."""
    b = np.union1d(cov_x.s, cov_y.s)
    ix = np.searchsorted(cov_x.s, b, "right") - 1
    iy = np.searchsorted(cov_y.s, b, "right") - 1
    e = np.append(b[1:], cov_x.e[-1])
    return (b, e, cov_x.Ag[ix], cov_x.Bg[ix], cov_y.Ag[iy],
            cov_y.Bg[iy], cov_x.approx[ix], cov_y.approx[iy])


def window_correlation(cov_x: Cover, cov_y: Cover, eps_x: float,
                       eps_y: float, lo: int, hi: int
                       ) -> Tuple[float, float]:
    """Pearson correlation over ``[lo, hi)`` with a closed-form bound."""
    b, e, Ax, Bx, Ay, By, apx, apy = _merge(cov_x, cov_y)
    E = _bucket(b.size)
    with jax.enable_x64(True):
        res = _corr_core(
            jnp.asarray(_pad(b, E, np.int64)),
            jnp.asarray(_pad(e, E, np.int64)),
            jnp.asarray(_pad(Ax, E, np.float64)),
            jnp.asarray(_pad(Bx, E, np.float64)),
            jnp.asarray(_pad(Ay, E, np.float64)),
            jnp.asarray(_pad(By, E, np.float64)),
            jnp.asarray(_pad(apx, E, bool)),
            jnp.asarray(_pad(apy, E, bool)))
    n, Sx, Sy, Sxx, Syy, Sxy, absx, absy, n_ax, n_ay = (
        float(v) for v in res)
    if int(n) != hi - lo:
        raise ValueError("window cover is incomplete")
    mx, my = Sx / n, Sy / n
    varx = Sxx / n - mx * mx
    vary = Syy / n - my * my
    cov = Sxy / n - mx * my
    den = math.sqrt(max(varx, 0.0) * max(vary, 0.0))
    r_hat = cov / den if den > 0 else float("nan")
    # Moment-sum deviations from the wire's per-point eps guarantee.
    dSx = eps_x * n_ax
    dSy = eps_y * n_ay
    dSxx = 2.0 * eps_x * absx + n_ax * eps_x * eps_x
    dSyy = 2.0 * eps_y * absy + n_ay * eps_y * eps_y
    dSxy = eps_y * absx + eps_x * absy \
        + min(n_ax, n_ay) * eps_x * eps_y
    mx_lo, mx_hi = (Sx - dSx) / n, (Sx + dSx) / n
    my_lo, my_hi = (Sy - dSy) / n, (Sy + dSy) / n
    prods = (mx_lo * my_lo, mx_lo * my_hi, mx_hi * my_lo, mx_hi * my_hi)
    cov_lo = (Sxy - dSxy) / n - max(prods)
    cov_hi = (Sxy + dSxy) / n - min(prods)

    def _sq(lo_, hi_):
        if lo_ <= 0.0 <= hi_:
            return 0.0, max(lo_ * lo_, hi_ * hi_)
        return min(lo_ * lo_, hi_ * hi_), max(lo_ * lo_, hi_ * hi_)

    mx2_lo, mx2_hi = _sq(mx_lo, mx_hi)
    my2_lo, my2_hi = _sq(my_lo, my_hi)
    varx_lo = max((Sxx - dSxx) / n - mx2_hi, 0.0)
    varx_hi = (Sxx + dSxx) / n - mx2_lo
    vary_lo = max((Syy - dSyy) / n - my2_hi, 0.0)
    vary_hi = (Syy + dSyy) / n - my2_lo
    den_lo = math.sqrt(varx_lo * vary_lo)
    den_hi = math.sqrt(max(varx_hi, 0.0) * max(vary_hi, 0.0))
    if den_lo <= 0.0:
        return r_hat, float("inf")
    r_lo = max(cov_lo / (den_lo if cov_lo < 0 else den_hi), -1.0)
    r_hi = min(cov_hi / (den_lo if cov_hi > 0 else den_hi), 1.0)
    return r_hat, max(r_hat - r_lo, r_hi - r_hat, 0.0)
