"""SystolicAttention — the paper's Algorithm 1 as a pure-JAX function.

This is the *paper-faithful* reference implementation of the technique:
FlashAttention-2/3 forward with

  * the exact floating-point operation order of Algorithm 1 (rowmax on the
    **unscaled** scores, 1/sqrt(d) folded into the exp2 argument),
  * exp implemented as ``exp2(log2(e)/sqrt(d) * x)``,
  * optionally the FSA 8-segment piecewise-linear exp2 (paper §3.3),
  * fp32 accumulation regardless of input dtype (FlashAttention-2/3 and the
    FSA accumulator both accumulate in fp32).

It is written with `jax.lax.scan` over key/value tiles so it lowers to clean
HLO on any backend: it is the model's attention off TPU (tests, smoke runs,
the multi-pod dry-run cells; a Pallas TPU kernel does not lower for the CPU
host platform), and JAX differentiates it directly.  The Pallas TPU kernel
in ``repro.kernels.flash_attention`` implements the same schedule with
explicit VMEM BlockSpecs and is validated against this function and
``naive_attention``, the materialized-softmax oracle of the tests.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .pwl_exp2 import DEFAULT_SEGMENTS, LOG2_E, pwl_exp2

__all__ = ["systolic_attention", "naive_attention"]

NEG_INF = -1e30  # finite stand-in for -inf; keeps PWL split well-defined


def _exp2_fn(impl: str, num_segments: int) -> Callable[[jax.Array], jax.Array]:
    if impl == "exact":
        return jnp.exp2
    if impl == "pwl":
        return functools.partial(pwl_exp2, num_segments=num_segments)
    raise ValueError(f"unknown exp2 impl: {impl!r} (want 'exact' or 'pwl')")


def _attend_single(
    q: jax.Array,  # [Sq, d]
    k: jax.Array,  # [Sk, d]
    v: jax.Array,  # [Sk, dv]
    *,
    causal: bool,
    block_q: int,
    block_k: int,
    exp2: Callable,
    scale: float,
    q_offset: int | jax.Array = 0,
    bias: Optional[jax.Array] = None,  # [Sq, Sk]
    unroll: bool = False,
    window: Optional[int] = None,
) -> jax.Array:
    """One (batch, head) slice of Algorithm 1.  fp32 state, tiled KV scan."""
    sq, d = q.shape
    sk, dv = v.shape[0], v.shape[1]
    n_q = -(-sq // block_q)
    n_k = -(-sk // block_k)

    c = scale * LOG2_E  # log2(e)/sqrt(d): folded into the exp2 argument

    q32 = q.astype(jnp.float32)
    k32 = k.astype(jnp.float32)
    v32 = v.astype(jnp.float32)
    # Pad ragged edges up to whole tiles; padded *keys* are masked below.
    pad_q, pad_k = n_q * block_q - sq, n_k * block_k - sk
    if pad_q:
        q32 = jnp.pad(q32, ((0, pad_q), (0, 0)))
    if pad_k:
        k32 = jnp.pad(k32, ((0, pad_k), (0, 0)))
        v32 = jnp.pad(v32, ((0, pad_k), (0, 0)))
    if bias is not None and (pad_q or pad_k):
        bias = jnp.pad(bias, ((0, pad_q), (0, pad_k)))

    def outer(_, i):
        q_i = jax.lax.dynamic_slice_in_dim(q32, i * block_q, block_q, axis=0)

        def inner(carry, j):
            old_m, old_l, old_o = carry
            k_j = jax.lax.dynamic_slice_in_dim(k32, j * block_k, block_k, axis=0)
            v_j = jax.lax.dynamic_slice_in_dim(v32, j * block_k, block_k, axis=0)

            # line 6: S = Q_i K_j^T  (unscaled, as in Algorithm 1)
            s = q_i @ k_j.T  # [Bq, Bk]

            if bias is not None:
                b_ij = jax.lax.dynamic_slice(
                    bias, (i * block_q, j * block_k), (block_q, block_k)
                ).astype(jnp.float32)
                s = s + b_ij / scale  # bias enters pre-scale score space
            # Masks enter as an additive [Bq, Bk] bias shared across
            # batch/heads (a pred broadcast to [B, H, Bq, Bk] gets hoisted
            # out of the layer loop as a multi-GiB constant).
            cols = j * block_k + jnp.arange(block_k)[None, :]
            if pad_k:
                s = s + jnp.where(cols < sk, 0.0, NEG_INF)
            if causal:
                rows = i * block_q + q_offset + jnp.arange(block_q)[:, None]
                s = s + jnp.where(rows >= cols, 0.0, NEG_INF)
                if window is not None:
                    s = s + jnp.where(rows - cols < window, 0.0, NEG_INF)

            # lines 7-9: rowmax, running max, a = old_m - new_m
            local_m = jnp.max(s, axis=-1)
            new_m = jnp.maximum(local_m, old_m)
            a = old_m - new_m
            # line 10: b = exp2(log2e/sqrt(d) * a)
            b = exp2(c * a)
            # lines 11-12: N = S - new_m ; P = exp2(log2e/sqrt(d) * N)
            n = s - new_m[:, None]
            p = exp2(c * n)
            # lines 13-16
            local_l = jnp.sum(p, axis=-1)
            new_l = old_l * b + local_l
            local_o = p @ v_j
            new_o = b[:, None] * old_o + local_o
            return (new_m, new_l, new_o), None

        init = (
            jnp.full((block_q,), NEG_INF, jnp.float32),
            jnp.zeros((block_q,), jnp.float32),
            jnp.zeros((block_q, dv), jnp.float32),
        )
        (m, l, o), _ = jax.lax.scan(
            inner, init, jnp.arange(n_k), unroll=n_k if unroll else 1
        )
        # line 21: O_i = diag(l)^-1 O   (guard fully-masked rows)
        safe_l = jnp.where(l == 0.0, 1.0, l)
        return (), o / safe_l[:, None]

    _, o_blocks = jax.lax.scan(
        outer, (), jnp.arange(n_q), unroll=n_q if unroll else 1
    )
    return o_blocks.reshape(n_q * block_q, dv)[:sq]


def systolic_attention(
    q: jax.Array,  # [B, Sq, H, d]
    k: jax.Array,  # [B, Sk, Hkv, d]
    v: jax.Array,  # [B, Sk, Hkv, dv]
    *,
    causal: bool = False,
    block_q: int = 128,
    block_k: int = 128,
    exp2_impl: str = "exact",
    num_segments: int = DEFAULT_SEGMENTS,
    scale: Optional[float] = None,
    q_offset: int | jax.Array = 0,
    bias: Optional[jax.Array] = None,
    unroll: bool = False,
    window: Optional[int] = None,
) -> jax.Array:
    """Batched multi-head SystolicAttention (GQA-aware).

    Args:
      q/k/v: [batch, seq, heads, head_dim]; kv heads may be a divisor of q
        heads (GQA — kv heads are repeated logically, not materialized
        per-q-head in HBM; the repeat happens on the fly).
      exp2_impl: "exact" (native exp2; the fast mode) or "pwl" (the paper's
        8-segment interpolation; numerics-faithful mode).
      q_offset: absolute position of q[0] (for decode/chunked prefill
        causal masking against a longer KV).
      bias: optional additive attention bias broadcastable to [Sq, Sk].
      window: with ``causal``, query row r (q_offset included) sees only
        keys r - window < c <= r (a sliding window of ``window`` keys).
    """
    b_, sq, h, d = q.shape
    hkv = k.shape[2]
    assert h % hkv == 0, (h, hkv)
    rep = h // hkv
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    exp2 = _exp2_fn(exp2_impl, num_segments)

    bq = min(block_q, sq)
    bk = min(block_k, k.shape[1])

    fn = functools.partial(
        _attend_single,
        causal=causal,
        block_q=bq,
        block_k=bk,
        exp2=exp2,
        scale=scale,
        q_offset=q_offset,
        bias=bias,
        unroll=unroll,
        window=window,
    )
    # GQA without materializing repeated KV: vmap q's rep dim with KV
    # broadcast (in_axes=None), then over kv-heads, then batch.
    fn = jax.vmap(fn, in_axes=(0, None, None))  # rep (q heads per kv head)
    fn = jax.vmap(fn, in_axes=(0, 0, 0))        # kv heads
    fn = jax.vmap(fn, in_axes=(0, 0, 0))        # batch
    qg = jnp.transpose(q, (0, 2, 1, 3)).reshape(b_, hkv, rep, sq, d)
    out = fn(
        qg,
        jnp.transpose(k, (0, 2, 1, 3)),
        jnp.transpose(v, (0, 2, 1, 3)),
    )  # [B, Hkv, rep, Sq, dv]
    out = out.reshape(b_, h, sq, v.shape[-1])
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def naive_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    q_offset: int | jax.Array = 0,
    bias: Optional[jax.Array] = None,
    dtype: jnp.dtype = jnp.float32,
    window: Optional[int] = None,
) -> jax.Array:
    """Materialized-softmax oracle (the ref implementation for all kernels);
    ``window`` as in ``systolic_attention``."""
    b_, sq, h, d = q.shape
    hkv = k.shape[2]
    rep = h // hkv
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    kr = jnp.repeat(k, rep, axis=2) if rep > 1 else k
    vr = jnp.repeat(v, rep, axis=2) if rep > 1 else v
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(dtype), kr.astype(dtype)) * scale
    if bias is not None:
        s = s + bias.astype(dtype)
    if causal:
        rows = q_offset + jnp.arange(sq)[:, None]
        cols = jnp.arange(k.shape[1])[None, :]
        s = jnp.where(rows >= cols, s, -jnp.inf)
        if window is not None:
            s = jnp.where(rows - cols < window, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, vr.astype(dtype))
    return o.astype(q.dtype)
