"""Amortized hull / least-squares carries vs the windowed references.

The one-shot and chunked disjoint/linear segmenters keep O(1)-amortized
hull carries instead of re-reading a window of points; they must
reproduce the windowed references' output bit-for-bit under arbitrary
chunk splits (hypothesis sweep + deterministic fixed-draw twin, per house
style).
"""

import numpy as np
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # fixed-draw twins below still run
    HAVE_HYPOTHESIS = False

from repro.core import jax_pla
from repro.core.jax_pla import flush, init_state, step_chunk


# ---------------------------------------------------------------------------
# Amortized hull / LSQ carries vs the windowed references
# ---------------------------------------------------------------------------

WINDOWED_REFS = {"disjoint": jax_pla.disjoint_segment_windowed,
                 "linear": jax_pla.linear_segment_windowed}
HULL_EPS, HULL_RUN = 0.8, 24

# (T, splits, seed) — chunk width 1, non-divisor widths, single-chunk,
# final partial chunks (mirrors tests/test_streaming_property.py).
FIXED_SPLITS = (
    (105, (1, 31, 32, 40, 1), 0),
    (97, (50, 47), 1),
    (64, (64,), 2),
    (41, (3, 7, 1, 13, 17), 3),
    (9, tuple([1] * 9), 4),
)


def check_hull_carry_matches_windowed(method, T_, splits, seed):
    """Chunked amortized-carry breaks == windowed-reference breaks."""
    rng = np.random.default_rng(seed)
    y = jnp.asarray(np.cumsum(rng.normal(0, 0.7, (8, T_)), axis=1),
                    jnp.float32)
    ref = WINDOWED_REFS[method](y, HULL_EPS, max_run=HULL_RUN)
    state = init_state(method, 8, HULL_EPS, max_run=HULL_RUN)
    outs, pos = [], 0
    for w in splits:
        state, out = step_chunk(state, y[:, pos:pos + w])
        outs.append(out)
        pos += w
    state, out = flush(state)
    outs.append(out)
    brk = np.concatenate([np.asarray(o.breaks) for o in outs], axis=1)
    label = f"{method}/T={T_}/splits={splits}"
    assert brk.shape == np.asarray(ref.breaks).shape, label
    np.testing.assert_array_equal(brk, np.asarray(ref.breaks),
                                  err_msg=label)


@pytest.mark.parametrize("method", sorted(WINDOWED_REFS))
def test_hull_offline_matches_windowed(method):
    # The one-shot amortized segmenters agree with the windowed references
    # on the full output (breaks, slopes, values), not just positions.
    rng = np.random.default_rng(3)
    y = jnp.asarray(np.cumsum(rng.normal(0, 0.7, (32, 600)), axis=1),
                    jnp.float32)
    fast = {"disjoint": jax_pla.disjoint_segment,
            "linear": jax_pla.linear_segment}[method](y, HULL_EPS,
                                                      max_run=64)
    ref = WINDOWED_REFS[method](y, HULL_EPS, max_run=64)
    np.testing.assert_array_equal(np.asarray(fast.breaks),
                                  np.asarray(ref.breaks))
    np.testing.assert_array_equal(np.asarray(fast.a), np.asarray(ref.a))
    np.testing.assert_array_equal(np.asarray(fast.v), np.asarray(ref.v))


@pytest.mark.parametrize("method", sorted(WINDOWED_REFS))
def test_fixed_hull_carry_matches_windowed(method):
    for T_, splits, seed in FIXED_SPLITS:
        check_hull_carry_matches_windowed(method, T_, splits, seed)


if HAVE_HYPOTHESIS:
    @st.composite
    def _splits_strategy(draw, t_min=2, t_max=140):
        T_ = draw(st.integers(t_min, t_max))
        widths, left = [], T_
        while left:
            w = draw(st.integers(1, left))
            widths.append(w)
            left -= w
        return T_, tuple(widths)

    @settings(max_examples=8, deadline=None)
    @given(data=st.data(), method=st.sampled_from(sorted(WINDOWED_REFS)),
           seed=st.integers(0, 2**16))
    def test_property_hull_carry_matches_windowed(data, method, seed):
        T_, splits = data.draw(_splits_strategy())
        check_hull_carry_matches_windowed(method, T_, splits, seed)
