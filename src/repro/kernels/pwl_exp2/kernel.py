"""Standalone Pallas kernel for the FSA piecewise-linear exp2 (paper §3.3).

Elementwise exp2 over a tiled array with the 8-segment chord interpolation:
Split-unit decomposition (x = x_i + x_f), one MAC per element
(slope_k * x_f + intercept_k) and an exponent-field update for 2**x_i.
Blocked into VMEM tiles of (block_rows, 128) — lane-aligned for the VPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.pwl_exp2 import pwl_coeffs

DEFAULT_BLOCK_ROWS = 256
LANES = 128


def _kernel(x_ref, o_ref, *, num_segments: int):
    x = x_ref[...].astype(jnp.float32)
    x_i = jnp.ceil(x)
    x_f = x - x_i
    idx = jnp.clip(
        jnp.floor((x_f + 1.0) * num_segments).astype(jnp.int32), 0, num_segments - 1
    )
    slope, intercept = pwl_coeffs(idx, num_segments)
    frac = slope * x_f + intercept
    e = jnp.clip(x_i, -150.0, 127.0).astype(jnp.int32)
    out = jnp.where(x_i < -148, 0.0, jnp.ldexp(frac, e))
    o_ref[...] = out.astype(o_ref.dtype)


def pwl_exp2_pallas(
    x: jax.Array,
    *,
    num_segments: int = 8,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = False,
) -> jax.Array:
    """PWL exp2 over an arbitrary-shaped array (x <= 0)."""
    orig_shape, orig_dtype = x.shape, x.dtype
    flat = x.reshape(-1)
    n = flat.shape[0]
    per_block = block_rows * LANES
    num_blocks = -(-n // per_block)
    padded = num_blocks * per_block
    if padded != n:
        flat = jnp.pad(flat, (0, padded - n))
    tiled = flat.reshape(num_blocks * block_rows, LANES)

    out = pl.pallas_call(
        functools.partial(_kernel, num_segments=num_segments),
        grid=(num_blocks,),
        in_specs=[pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(tiled.shape, orig_dtype),
        interpret=interpret,
        name="pwl_exp2",
        metadata={"kernel": "pwl_exp2"},
    )(tiled)
    return out.reshape(-1)[:n].reshape(orig_shape)
