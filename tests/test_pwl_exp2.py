"""PWL exp2 (paper §3.3 / Fig. 12): correctness + paper-claim reproduction."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.pwl_exp2 import pwl_error_stats, pwl_exp2, segment_table


def test_paper_fig12_8_segments():
    """Paper: 8 segments -> MAE 0.00014, MRE 0.02728 over negative normal fp16."""
    stats = pwl_error_stats(8)
    assert stats["mae"] == pytest.approx(1.4e-4, rel=0.1)
    assert stats["mre"] == pytest.approx(0.02728, rel=0.02)


def test_mae_decreases_mre_stable():
    """Fig. 12 shape: MAE drops with segments, MRE plateaus."""
    s4, s8, s16 = (pwl_error_stats(k) for k in (4, 8, 16))
    assert s4["mae"] > s8["mae"] > s16["mae"]
    assert abs(s8["mre"] - s16["mre"]) < 0.005


def test_intercepts_in_half_open_unit_range():
    """Paper §3.3: all intercepts lie in (0.5, 1] (used to encode k)."""
    for k in (2, 4, 8, 16, 32):
        _, intercept = segment_table(k)
        assert np.all(intercept > 0.5) and np.all(intercept <= 1.0)


def test_exact_at_breakpoints():
    """Chord interpolation is exact at segment breakpoints and at 0."""
    x = jnp.asarray([-0.875, -0.75, -0.5, -0.25, -0.125, 0.0, -1.0, -2.0, -5.0])
    np.testing.assert_allclose(
        np.asarray(pwl_exp2(x)), np.exp2(np.asarray(x)), rtol=1e-6
    )


@settings(max_examples=200, deadline=None)
@given(st.floats(min_value=-100.0, max_value=0.0, allow_nan=False))
def test_relative_error_bound(x):
    """Property: for any x in [-100, 0], PWL rel error < 1% at 8 segments."""
    approx = float(pwl_exp2(jnp.float32(x)))
    exact = float(np.exp2(np.float64(x)))
    if exact > 1e-30:
        assert abs(approx - exact) / exact < 0.01


@settings(max_examples=50, deadline=None)
@given(
    st.integers(min_value=2, max_value=64),
    st.floats(min_value=-30.0, max_value=0.0, allow_nan=False),
)
def test_monotone_in_segments(k, x):
    """More segments never increases the error (chord construction)."""
    e_k = abs(float(pwl_exp2(jnp.float32(x), num_segments=k)) - float(np.exp2(np.float64(x))))
    e_2k = abs(float(pwl_exp2(jnp.float32(x), num_segments=2 * k)) - float(np.exp2(np.float64(x))))
    # Slack of one fp32 ulp of exp2(x): both tables are fp32, so where the
    # two segmentations share a knot (e.g. x = -0.5 for every k) the two
    # errors are only the fp32 rounding of the same chord value.
    ulp = float(np.spacing(np.float32(np.exp2(np.float64(x)))))
    assert e_2k <= e_k + ulp


def test_flush_to_zero():
    assert float(pwl_exp2(jnp.float32(-200.0))) == 0.0


def test_vectorized_shapes_dtypes():
    for dtype in (jnp.float32, jnp.bfloat16, jnp.float16):
        x = -jnp.abs(jax.random.normal(jax.random.PRNGKey(0), (7, 13), jnp.float32)) * 5
        out = pwl_exp2(x.astype(dtype))
        assert out.shape == x.shape and out.dtype == dtype


# -- Pallas kernel properties (interpret mode) -----------------------------
#
# Same claims, checked against the *kernel* (repro.kernels.pwl_exp2) rather
# than the jnp reference: hardware-faithful chord interpolation must stay
# monotone, hit the segment knots exactly, and keep the Fig. 12 relative
# error envelope.

from repro.kernels.pwl_exp2.kernel import pwl_exp2_pallas  # noqa: E402


def _kernel(x, num_segments=8):
    return pwl_exp2_pallas(jnp.asarray(x, jnp.float32), num_segments=num_segments,
                           interpret=True)


@settings(max_examples=25, deadline=None)
@given(
    st.floats(min_value=-30.0, max_value=0.0, allow_nan=False),
    st.floats(min_value=0.0, max_value=30.0, allow_nan=False),
    st.sampled_from([4, 8, 16]),
)
def test_kernel_monotone_nondecreasing(x, delta, k):
    """Property: exp2 is increasing, and each PWL chord has positive slope —
    so the kernel must be monotone for any x <= x + delta."""
    lo, hi = _kernel([x], k), _kernel([min(x + delta, 0.0)], k)
    assert float(lo[0]) <= float(hi[0]) + 1e-7


@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=0, max_value=29), st.sampled_from([4, 8, 16]))
def test_kernel_exact_at_knots(n, k):
    """Property: chord interpolation is exact wherever the fractional part
    lands on a segment breakpoint i/k (and at every integer, i == 0)."""
    for i in range(k + 1):
        x = -(n + i / k)
        got = float(_kernel([x], k)[0])
        want = float(np.exp2(np.float64(x)))
        assert got == pytest.approx(want, rel=1e-6)


@settings(max_examples=100, deadline=None)
@given(st.floats(min_value=-30.0, max_value=0.0, allow_nan=False))
def test_kernel_max_rel_error_within_fig12(x):
    """Property: at 8 segments every input respects the Fig. 12 max
    relative error (MRE 0.02728; small slack for fp32 arithmetic)."""
    approx = float(_kernel([x])[0])
    exact = float(np.exp2(np.float64(x)))
    assert abs(approx - exact) <= 0.0285 * exact + 1e-30
