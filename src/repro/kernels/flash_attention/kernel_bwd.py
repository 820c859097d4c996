"""FlashAttention-2 backward pass as Pallas TPU kernels.

Same VMEM-blocked structure as the forward (DESIGN.md §2): the forward
saves the base-2 log-sum-exp row statistics L (so P = exp2(c·S − L) is
recomputed per tile, never stored), and the backward runs two grid-clean
kernels:

  * dq kernel — grid (B·H, i, j), KV innermost, dq accumulates in VMEM
    scratch (mirror of the forward);
  * dkv kernel — grid (B·H, j, i), Q innermost, dk/dv accumulate in VMEM
    scratch; GQA partials over the rep q-heads are summed outside (one
    cheap reshape-sum) so no grid step ever writes another step's block.

All matmul work uses fp32 accumulation; masks are additive [Bq, Bk]
biases as in the forward.  As in the forward, causal steps whose block
pair lies wholly above the diagonal do no work and fetch nothing: the dq
grid meets them at the end of each KV sweep and names the row's last live
KV block, the dkv grid at the start of each q sweep and names the column's
first live q block.  The skip is exact: there P = exp2(-huge - LSE) = 0,
so dS = 0 and every accumulator would gain exact zeros.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.pwl_exp2 import LOG2_E
from .kernel import (
    LANES, first_live_q_block, kv_block_index, last_live_kv_block, q_block_index,
)

NEG_INF = -1e30


def _mask_bias(i, j, block_q, block_k, causal, q_offset, seq_k, pad_k):
    cols = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    bias = jnp.zeros((block_q, block_k), jnp.float32)
    if pad_k:
        bias = bias + jnp.where(cols < seq_k, 0.0, NEG_INF)
    if causal:
        rows = (
            i * block_q + q_offset
            + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        )
        bias = bias + jnp.where(rows >= cols, 0.0, NEG_INF)
    return bias


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc,
               *, num_k_blocks, block_q, block_k, causal, sm_scale, q_offset,
               seq_k, pad_k):
    j = pl.program_id(2)
    i = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    c = sm_scale * LOG2_E
    live = j <= last_live_kv_block(i, block_q, block_k, q_offset) if causal else True

    @pl.when(live)
    def _():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]      # [bq, 1] (stored lane-broadcast)
        delta = delta_ref[0][:, :1]  # [bq, 1] = rowsum(dO * O)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s + _mask_bias(i, j, block_q, block_k, causal, q_offset, seq_k, pad_k)
        p = jnp.exp2(c * s - lse)  # recompute (never stored)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        acc[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(j == num_k_blocks - 1)
    def _():
        dq_ref[0, :, :] = acc[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc,
                *, num_q_blocks, block_q, block_k, causal, sm_scale, q_offset,
                seq_k, pad_k):
    i = pl.program_id(2)  # q innermost
    j = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    c = sm_scale * LOG2_E
    live = i >= first_live_q_block(j, block_q, block_k, q_offset) if causal else True

    @pl.when(live)
    def _():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s + _mask_bias(i, j, block_q, block_k, causal, q_offset, seq_k, pad_k)
        p = jnp.exp2(c * s - lse)  # [bq, bk]
        dv_acc[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale  # [bq, bk]
        dk_acc[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)

    @pl.when(i == num_q_blocks - 1)
    def _():
        dk_ref[0, :, :] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, :, :] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd(
    q: jax.Array,   # [B, Sq, H, d]
    k: jax.Array,   # [B, Sk, Hkv, d]
    v: jax.Array,   # [B, Sk, Hkv, d]
    out: jax.Array,  # [B, Sq, H, d] forward output
    lse: jax.Array,  # [B*H, padded_Sq, LANES] base-2 LSE from the forward
    do: jax.Array,  # [B, Sq, H, d]
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
):
    batch, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    rep = h // hkv
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    num_q = -(-sq // block_q)
    num_k = -(-sk // block_k)
    pad_q = num_q * block_q - sq
    pad_k = num_k * block_k - sk

    def headmajor(x, heads):
        x = x.transpose(0, 2, 1, 3).reshape(batch * heads, x.shape[1], d)
        return x

    qh, doh, oh = headmajor(q, h), headmajor(do, h), headmajor(out, h)
    kh, vh = headmajor(k, hkv), headmajor(v, hkv)
    if pad_q:
        qh = jnp.pad(qh, ((0, 0), (0, pad_q), (0, 0)))
        doh = jnp.pad(doh, ((0, 0), (0, pad_q), (0, 0)))
        oh = jnp.pad(oh, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kh = jnp.pad(kh, ((0, 0), (0, pad_k), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, pad_k), (0, 0)))

    # delta = rowsum(dO * O) (the FA2 preprocess; cheap, done in XLA),
    # lane-broadcast like the LSE so its blocks are (8, 128)-tiled.
    delta = jnp.sum(doh.astype(jnp.float32) * oh.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], lse.shape)

    common = dict(block_q=block_q, block_k=block_k, causal=causal,
                  sm_scale=float(scale), q_offset=q_offset, seq_k=sk,
                  pad_k=pad_k)

    kv_block = kv_block_index(causal, block_q, block_k, q_offset)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, num_k_blocks=num_k, **common),
        grid=(batch * h, num_q, num_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, i, j, rep=rep: (bh // rep, kv_block(i, j), 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, i, j, rep=rep: (bh // rep, kv_block(i, j), 0)),
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda bh, i, j: (bh, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((batch * h, num_q * block_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_dq",
        metadata={"kernel": "flash_dq"},
    )(qh, kh, vh, doh, lse, delta)

    # dk/dv at q-head granularity; sum the rep partials afterwards.
    q_block = q_block_index(causal, block_q, block_k, q_offset, num_q)
    dk_p, dv_p = pl.pallas_call(
        functools.partial(_dkv_kernel, num_q_blocks=num_q, **common),
        grid=(batch * h, num_k, num_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, j, i: (bh, q_block(j, i), 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, j, i, rep=rep: (bh // rep, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, j, i, rep=rep: (bh // rep, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh, j, i: (bh, q_block(j, i), 0)),
            pl.BlockSpec((1, block_q, LANES), lambda bh, j, i: (bh, q_block(j, i), 0)),
            pl.BlockSpec((1, block_q, LANES), lambda bh, j, i: (bh, q_block(j, i), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, j, i: (bh, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, j, i: (bh, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch * h, num_k * block_k, d), k.dtype),
            jax.ShapeDtypeStruct((batch * h, num_k * block_k, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_dkv",
        metadata={"kernel": "flash_dkv"},
    )(qh, kh, vh, doh, lse, delta)

    def unhead(x, heads, s):
        return x[:, :s, :].reshape(batch, heads, s, d).transpose(0, 2, 1, 3)

    dq = unhead(dq, h, sq)
    # Sum GQA partials: [B*H, Sk, d] -> [B, Hkv, rep, Sk, d] -> sum rep.
    dk = dk_p[:, :sk, :].reshape(batch, hkv, rep, sk, d).sum(axis=2).transpose(0, 2, 1, 3)
    dv = dv_p[:, :sk, :].reshape(batch, hkv, rep, sk, d).sum(axis=2).transpose(0, 2, 1, 3)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)
