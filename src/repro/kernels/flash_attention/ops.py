"""Public entry point for the fused flash-attention kernel.

``flash_attention`` dispatches between:
  * the Pallas TPU kernels (``impl='pallas'``; ``interpret=True`` on CPU) —
    fused forward (saves base-2 LSE) + FlashAttention-2 backward kernels
    (``kernel_bwd.py``: dq and dk/dv grids, P recomputed per tile);
  * the scan-based pure-jnp SystolicAttention (``impl='jnp'``) — identical
    algorithm, lowers on every backend; used by the multi-pod dry-run; its
    backward is autodiff-of-recompute (same FA2 memory profile).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.attention import systolic_attention
from .kernel import DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, flash_attention_fwd
from .kernel_bwd import flash_attention_bwd


def _jnp_forward(q, k, v, *, causal, scale, q_offset, block_q, block_k,
                 exp2_impl, num_segments, window=None):
    return systolic_attention(
        q, k, v,
        causal=causal, scale=scale, q_offset=q_offset,
        block_q=block_q, block_k=block_k,
        exp2_impl=exp2_impl, num_segments=num_segments, window=window,
    )


@functools.partial(
    jax.custom_vjp,
    nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11, 12),
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
    impl: str = "jnp",
    interpret: bool = False,
    window: Optional[int] = None,
) -> jax.Array:
    """Fused attention, [B,S,H,d] layout, GQA-aware.  Differentiable, but
    for a ``window`` under ``impl='pallas'``: the backward grids take no
    window, so differentiating such a call raises."""
    if impl == "pallas":
        return flash_attention_fwd(
            q, k, v,
            causal=causal, scale=scale, q_offset=q_offset,
            block_q=block_q, block_k=block_k,
            exp2_impl=exp2_impl, num_segments=num_segments,
            interpret=interpret, window=window,
        )
    return _jnp_forward(
        q, k, v,
        causal=causal, scale=scale, q_offset=q_offset,
        block_q=block_q, block_k=block_k,
        exp2_impl=exp2_impl, num_segments=num_segments, window=window,
    )


def _fwd(q, k, v, causal, scale, q_offset, block_q, block_k,
         exp2_impl, num_segments, impl, interpret, window):
    if impl == "pallas" and window is not None:
        raise NotImplementedError("the flash backward grids take no sliding window")
    if impl == "pallas":
        out, lse = flash_attention_fwd(
            q, k, v,
            causal=causal, scale=scale, q_offset=q_offset,
            block_q=block_q, block_k=block_k,
            exp2_impl=exp2_impl, num_segments=num_segments,
            interpret=interpret, return_lse=True,
        )
        return out, (q, k, v, out, lse)
    out = _jnp_forward(
        q, k, v,
        causal=causal, scale=scale, q_offset=q_offset,
        block_q=block_q, block_k=block_k,
        exp2_impl=exp2_impl, num_segments=num_segments, window=window,
    )
    return out, (q, k, v, None, None)


def _bwd(causal, scale, q_offset, block_q, block_k, exp2_impl,
         num_segments, impl, interpret, window, res, g):
    q, k, v, out, lse = res
    if impl == "pallas":
        # FlashAttention-2 backward kernels: P recomputed per VMEM tile
        # from the saved LSE; gradients flow through exact exp2 (the PWL
        # forward is a device-numerics detail, as FSA training would pair
        # with an exact-gradient backward).
        return flash_attention_bwd(
            q, k, v, out, lse, g,
            causal=causal, scale=scale, q_offset=q_offset,
            block_q=block_q, block_k=block_k, interpret=interpret,
        )
    # jnp path: differentiate the tiled forward (recompute; XLA fuses).
    f = functools.partial(
        _jnp_forward,
        causal=causal, scale=scale, q_offset=q_offset,
        block_q=block_q, block_k=block_k,
        exp2_impl="exact", num_segments=num_segments, window=window,
    )
    _, vjp = jax.vjp(f, q, k, v)
    return vjp(g)


flash_attention.defvjp(_fwd, _bwd)
