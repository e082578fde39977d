"""Batched JAX PLA (core/jax_pla.py) vs. the exact sequential methods."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.jax_pla import (angle_segment, continuous_segment,
                                disjoint_segment, linear_segment,
                                mixed_segment, swing_segment,
                                propagate_lines, to_records,
                                decode_records)
from repro.core.methods import (run_angle, run_continuous, run_disjoint,
                                run_linear, run_mixed, run_swing)

PAIRS = {
    "swing": (swing_segment, run_swing),
    "angle": (angle_segment, run_angle),
    "disjoint": (disjoint_segment, run_disjoint),
    "linear": (linear_segment, run_linear),
    "continuous": (continuous_segment, run_continuous),
    "mixed": (mixed_segment, run_mixed),
}


def _streams(seed=0, S=6, T=250):
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.normal(0, 0.5, (S, T)), axis=1)


@pytest.mark.parametrize("name", list(PAIRS))
@pytest.mark.parametrize("eps", [0.3, 1.0, 4.0])
def test_breaks_match_sequential(name, eps):
    """Batched scan reproduces the sequential oracle's break decisions.

    Run in float64 to avoid spurious decision flips at fp32 boundaries.
    """
    jfn, sfn = PAIRS[name]
    y = _streams()
    S, T = y.shape
    ts = np.arange(T, dtype=float)
    seg = jfn(jnp.asarray(y, jnp.float64), eps, max_run=128)
    for s in range(S):
        out = sfn(ts, y[s], eps, max_run=128)
        seq = np.zeros(T, bool)
        for sg in out.segments:
            seq[sg.i1 - 1] = True
        np.testing.assert_array_equal(np.asarray(seg.breaks[s]), seq,
                                      err_msg=f"{name} row {s}")


@pytest.mark.parametrize("name", list(PAIRS))
def test_reconstruction_within_eps(name):
    jfn, _ = PAIRS[name]
    y = _streams(seed=1, S=16, T=400)
    seg = jfn(jnp.asarray(y, jnp.float32), 1.0, max_run=256)
    recon = propagate_lines(seg)
    assert float(jnp.abs(recon - jnp.asarray(y, jnp.float32)).max()) \
        <= 1.0 * (1 + 1e-4) + 1e-5  # f32: eps + O(ulp(|y|))


def test_records_roundtrip_and_overflow():
    y = _streams(seed=2, S=12, T=300)
    seg = disjoint_segment(jnp.asarray(y, jnp.float32), 1.0, max_run=64)
    rec = to_records(seg, k_max=8)  # deliberately tight budget
    dec = decode_records(rec, 300)
    full = propagate_lines(seg)
    ok = ~np.asarray(rec.overflow)
    if ok.any():
        np.testing.assert_allclose(np.asarray(dec)[ok], np.asarray(full)[ok],
                                   rtol=1e-5, atol=1e-5)
    # Overflow rows still produce finite output (tail extension).
    assert np.isfinite(np.asarray(dec)).all()


# ---------------------------------------------------------------------------
# Golden equality: batched continuous/mixed vs the exact sequential oracles
# (ISSUE 4) — boundaries, knot values, and max-error on the synthetic
# generators, all within the sequential reference's eps guarantee.
# ---------------------------------------------------------------------------

DEFERRED_PAIRS = {
    "continuous": (continuous_segment, run_continuous),
    "mixed": (mixed_segment, run_mixed),
}


def _sequential_events(out, T):
    """Sequential MethodOutput -> (breaks, line-value-at-break) arrays."""
    brk = np.zeros(T, bool)
    val = np.zeros(T)
    for sg in out.segments:
        e = sg.i1 - 1
        brk[e] = True
        val[e] = sg.line(float(e))
    return brk, val


@pytest.mark.parametrize("name", list(DEFERRED_PAIRS))
@pytest.mark.parametrize("dataset", ["gps", "lidar", "urban", "ucr"])
def test_golden_continuous_mixed_on_synthetic(name, dataset):
    """Batched deferred scans vs run_continuous/run_mixed on the paper's
    synthetic surrogates: same segment boundaries, knot values within the
    f32/f64 gap, and reconstruction within the sequential eps guarantee.

    Drives the data/synthetic.py generators with a fixed rng directly:
    make_dataset seeds with hash(name), which is per-process randomized
    (PYTHONHASHSEED) and would make exact-boundary assertions flaky.
    """
    from repro.data.synthetic import _GENS
    jfn, sfn = DEFERRED_PAIRS[name]
    ts, ys = _GENS[dataset](np.random.default_rng(3), 700)
    eps = 0.05 * (np.percentile(ys, 95) - np.percentile(ys, 5)) or 1.0
    y32 = np.asarray(ys, np.float32)[None, :]
    seg = jfn(jnp.asarray(y32), float(eps), max_run=128)
    out = sfn(np.arange(len(ys), dtype=float), ys, float(eps), max_run=128)
    sb, sv = _sequential_events(out, len(ys))
    np.testing.assert_array_equal(np.asarray(seg.breaks[0]), sb,
                                  err_msg=f"{name}/{dataset}")
    # knot values within the f32 engine's rounding of the f64 oracle
    scale = np.abs(sv[sb]).max() + 1.0
    assert np.abs(np.asarray(seg.v[0])[sb] - sv[sb]).max() <= 1e-3 * scale \
        + 0.05 * eps, f"{name}/{dataset}"
    # eps guarantee of the batched reconstruction
    recon = np.asarray(propagate_lines(seg))[0]
    assert np.abs(recon - y32[0]).max() <= eps * (1 + 1e-4) + 1e-5 * scale


def test_continuous_output_is_connected():
    """Adjacent segments share their boundary value (joint knots)."""
    y = jnp.asarray(_streams(seed=9, S=4, T=400), jnp.float32)
    seg = continuous_segment(y, 1.0, max_run=64)
    brk = np.asarray(seg.breaks)
    a = np.asarray(seg.a)
    v = np.asarray(seg.v)
    for s in range(4):
        e = np.flatnonzero(brk[s])
        left = v[s][e[1:]] - a[s][e[1:]] * (e[1:] - e[:-1])
        np.testing.assert_allclose(left, v[s][e[:-1]], rtol=1e-4, atol=1e-4)


def test_mixed_never_worse_than_disjoint():
    """MixedPLA's implicit wire size is never worse than Disjoint's (a
    joint knot replaces a disjoint knot only when feasible)."""
    from repro.core.protocol_engine import protocol_nbytes
    y = jnp.asarray(_streams(seed=10, S=6, T=500), jnp.float32)
    nb_m, _ = protocol_nbytes(mixed_segment(y, 1.0, max_run=256),
                              "implicit", "mixed")
    nb_d, _ = protocol_nbytes(disjoint_segment(y, 1.0, max_run=256),
                              "implicit", "disjoint")
    assert (np.asarray(nb_m) <= np.asarray(nb_d)).all()


def test_per_row_eps():
    """eps may vary per stream row."""
    y = _streams(seed=4, S=4, T=200)
    eps = jnp.asarray([0.1, 0.5, 2.0, 8.0], jnp.float32)
    seg = angle_segment(jnp.asarray(y, jnp.float32), eps, max_run=256)
    recon = propagate_lines(seg)
    err = jnp.abs(recon - jnp.asarray(y, jnp.float32)).max(axis=1)
    assert bool((err <= eps * (1 + 1e-4) + 1e-5).all())
    # Larger eps => no more segments than smaller eps.
    counts = seg.breaks.sum(axis=1)
    assert int(counts[3]) <= int(counts[0])
