"""Public entry point for the fused flash-attention Pallas kernels.

``flash_attention`` is the Pallas forward (``kernel.py``: fused, saves the
base-2 LSE) with a custom VJP into the FlashAttention-2 backward kernels
(``kernel_bwd.py``: dq and dk/dv grids, P recomputed per tile).  It
compiles for TPU; ``interpret=True`` runs it on the CPU for tests.  Off
TPU the model attends with ``repro.core.attention.systolic_attention``,
the same algorithm in jnp, which JAX differentiates directly.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax

from .kernel import DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, flash_attention_fwd
from .kernel_bwd import flash_attention_bwd


@functools.partial(
    jax.custom_vjp,
    nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    scale: Optional[float] = None,
    q_offset: int = 0,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    exp2_impl: str = "exact",
    num_segments: int = 8,
    interpret: bool = False,
    window: Optional[int] = None,
) -> jax.Array:
    """Fused attention, [B,S,H,d] layout, GQA-aware.  Differentiable, but
    for a ``window``: the backward grids take no window, so differentiating
    such a call raises."""
    return flash_attention_fwd(
        q, k, v,
        causal=causal, scale=scale, q_offset=q_offset,
        block_q=block_q, block_k=block_k,
        exp2_impl=exp2_impl, num_segments=num_segments,
        interpret=interpret, window=window,
    )


def _fwd(q, k, v, causal, scale, q_offset, block_q, block_k,
         exp2_impl, num_segments, interpret, window):
    if window is not None:
        raise NotImplementedError("the flash backward grids take no sliding window")
    out, lse = flash_attention_fwd(
        q, k, v,
        causal=causal, scale=scale, q_offset=q_offset,
        block_q=block_q, block_k=block_k,
        exp2_impl=exp2_impl, num_segments=num_segments,
        interpret=interpret, return_lse=True,
    )
    return out, (q, k, v, out, lse)


def _bwd(causal, scale, q_offset, block_q, block_k, exp2_impl,
         num_segments, interpret, window, res, g):
    # FlashAttention-2 backward kernels: P recomputed per VMEM tile from the
    # saved LSE; gradients flow through exact exp2 (the PWL forward is a
    # device-numerics detail, as FSA training would pair with an
    # exact-gradient backward).
    q, k, v, out, lse = res
    return flash_attention_bwd(
        q, k, v, out, lse, g,
        causal=causal, scale=scale, q_offset=q_offset,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )


flash_attention.defvjp(_fwd, _bwd)
