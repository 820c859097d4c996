"""Piecewise-linear exp2 approximation (paper §3.3, Fig. 5 / Fig. 12).

FSA computes ``exp(x) = exp2(x * log2(e))`` for ``x <= 0`` by splitting the
input into integer and fractional parts::

    x = x_i + x_f,   x_i = ceil(x) integer,   x_f = x - x_i in (-1, 0]
    exp2(x) = 2**x_i * 2**x_f
    2**x_f  ~= slope_k * x_f + intercept_k,   k = segment index

``2**x_f`` lies in (0.5, 1] so a K-segment *uniform* chord interpolation on
(-1, 0] is accurate to ~1e-2 relative error with K = 8 (the paper's choice).
The ``2**x_i`` factor is applied as an exponent-field update (``ldexp``) —
on FSA hardware this only touches the exponent bits of the result.

All intercepts lie in (0.5, 1] (paper §3.3): the chord through
``(a_k, 2**a_k)`` and ``(b_k, 2**b_k)`` extrapolated to ``x_f = 0`` stays in
that range, which is what lets FSA encode the segment index in the intercept
exponent MSBs.  We assert this property in the tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_SEGMENTS = 8

__all__ = [
    "DEFAULT_SEGMENTS",
    "segment_table",
    "pwl_coeffs",
    "pwl_exp2",
    "pwl_exp",
    "exp2_reference",
]


@functools.lru_cache(maxsize=None)
def segment_table(num_segments: int = DEFAULT_SEGMENTS) -> tuple[np.ndarray, np.ndarray]:
    """Chord-interpolation (slope, intercept) tables for 2**x_f on (-1, 0].

    Segment k covers ``[-1 + k/K, -1 + (k+1)/K)``; the chord passes through
    the exact endpoints, so the approximation is continuous and exact at the
    K+1 breakpoints (in particular exp2(0) == 1 exactly).
    """
    k = np.arange(num_segments, dtype=np.float64)
    a = -1.0 + k / num_segments
    b = -1.0 + (k + 1.0) / num_segments
    fa, fb = np.exp2(a), np.exp2(b)
    slope = (fb - fa) * num_segments
    intercept = fa - slope * a
    return slope.astype(np.float32), intercept.astype(np.float32)


def pwl_coeffs(idx: jax.Array, num_segments: int) -> tuple[jax.Array, jax.Array]:
    """(slope, intercept) per element from the segment index.

    A select chain over the K table entries, each a Python scalar.  Pallas
    TPU kernels take scalars as literals (captured constant arrays are
    rejected, and vector gathers do not lower), and the chain keeps every
    intermediate at the shape of ``idx``: a one-hot ``[..., K]`` operand
    pads its K lanes to 128 and overflows VMEM.  Bit-identical to indexing
    the table — each element selects one exact fp32 coefficient.
    """
    slope_t, intercept_t = segment_table(num_segments)
    slope = jnp.full(idx.shape, float(slope_t[0]), jnp.float32)
    intercept = jnp.full(idx.shape, float(intercept_t[0]), jnp.float32)
    for k in range(1, num_segments):
        hit = idx == k
        slope = jnp.where(hit, float(slope_t[k]), slope)
        intercept = jnp.where(hit, float(intercept_t[k]), intercept)
    return slope, intercept


def _split_int_frac(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x = x_i + x_f with x_i integer and x_f in (-1, 0] (requires x <= 0)."""
    x_i = jnp.ceil(x)
    x_f = x - x_i
    return x_i, x_f


def pwl_exp2(x: jax.Array, num_segments: int = DEFAULT_SEGMENTS) -> jax.Array:
    """FSA's piecewise-linear exp2 for non-positive inputs.

    Matches the hardware semantics: computation in fp32 (the MAC accumulates
    in fp32), the 2**x_i factor applied as an exponent shift, inputs below
    the fp32 underflow threshold flushed to zero (the paper flushes
    subnormals, §6.2.1).
    """
    slope_np, intercept_np = segment_table(num_segments)
    slope = jnp.asarray(slope_np)
    intercept = jnp.asarray(intercept_np)

    orig_dtype = x.dtype
    xf32 = x.astype(jnp.float32)
    x_i, x_f = _split_int_frac(xf32)

    # Segment index: uniform split of (-1, 0] into K pieces.
    idx = jnp.clip(
        jnp.floor((x_f + 1.0) * num_segments).astype(jnp.int32), 0, num_segments - 1
    )
    frac_pow = slope[idx] * x_f + intercept[idx]  # one MAC per element

    # 2**x_i via exponent update.  Clamp to avoid ldexp overflow on garbage
    # (positive) inputs; FSA only ever sees x <= 0 here.
    e = jnp.clip(x_i, -150.0, 127.0).astype(jnp.int32)
    out = jnp.ldexp(frac_pow, e)
    # Flush-to-zero below the smallest normal of the *input* precision family,
    # mirroring accelerators that do not produce subnormals (§6.2.1).
    out = jnp.where(x_i < -148, 0.0, out)
    return out.astype(orig_dtype)


LOG2_E = float(np.log2(np.e))


def pwl_exp(x: jax.Array, num_segments: int = DEFAULT_SEGMENTS) -> jax.Array:
    """exp(x) = exp2(x * log2 e) with the PWL exp2 (x <= 0)."""
    return pwl_exp2(x.astype(jnp.float32) * LOG2_E, num_segments=num_segments)


def exp2_reference(x: jax.Array) -> jax.Array:
    """Exact exp2 evaluated in fp64-on-CPU / fp32 elsewhere, for error analysis."""
    return jnp.exp2(x)


def pwl_error_stats(num_segments: int = DEFAULT_SEGMENTS) -> dict[str, float]:
    """Exhaustive error over all negative *normal* fp16 values (paper §6.2.1).

    Returns mean absolute error and mean relative error of the PWL exp2
    against fp64 ground truth; reproduces Fig. 12 (8 segments: MAE ~1.4e-4,
    MRE ~2.7e-2).
    """
    # All negative normal fp16: sign=1, exponent in [1, 30], mantissa 0..1023.
    bits = np.arange(0, 1 << 15, dtype=np.uint16)
    vals = (bits | np.uint16(0x8000)).view(np.float16)
    mask = np.isfinite(vals) & (vals < 0) & (np.abs(vals) >= 2.0 ** -14)
    x = vals[mask].astype(np.float32)

    def _ftz16(v: np.ndarray) -> np.ndarray:
        """Round to fp16 and flush subnormal results to zero (§6.2.1)."""
        h = v.astype(np.float16)
        h[np.abs(h.astype(np.float64)) < 2.0 ** -14] = 0
        return h.astype(np.float64)

    # Accelerator output: fp16 with subnormal results flushed to zero.
    approx = _ftz16(
        np.asarray(pwl_exp2(jnp.asarray(x), num_segments=num_segments), dtype=np.float64)
    )
    # Ground truth: exact exp2 rounded to fp16 *keeping* subnormals (the
    # software reference, e.g. torch fp16).  The mismatch in subnormal
    # handling is exactly why the paper's MRE plateaus near 2.7e-2 while the
    # MAE keeps shrinking with more segments (Fig. 12): outputs in
    # [2^-24, 2^-14) are representable by the reference but flushed by the
    # accelerator, a relative error of 1 independent of the interpolation.
    exact = np.exp2(x.astype(np.float64)).astype(np.float16).astype(np.float64)
    abs_err = np.abs(approx - exact)
    # Per-point relative error, with 0/0 (both sides an exact zero for
    # x <= -25) counted as zero error; the mean runs over all evaluated
    # points, matching the paper's reported MRE = 0.02728 at 8 segments.
    nz = exact > 0
    rel_err = np.zeros_like(abs_err)
    rel_err[nz] = abs_err[nz] / exact[nz]
    return {
        "num_segments": float(num_segments),
        "count": float(x.size),
        "mae": float(abs_err.mean()),
        "mre": float(rel_err.mean()),
        "max_abs": float(abs_err.max()),
    }
