"""GQA attention layer: params, forward (train/prefill), decode with KV cache.

The paper's technique enters here.  Full-sequence attention (training and
prefill) runs

  * ``pallas``   — the fused Pallas TPU kernel (``repro.kernels``), compiled;
    the default on TPU;
  * ``systolic`` — the Algorithm-1-faithful tiled jnp implementation
    (``repro.core.attention``), lowers on all backends; the default
    elsewhere (tests, the CPU dry-run) and the f32 reference;
  * ``naive``    — materialized softmax (oracle).

``cfg.attention_impl`` forces one; None picks from the platform.

Per the paper §8.3, decode (seq_q == 1, memory-bound) never uses the FSA
path: a 1-token query would waste a 128x128 tile.  ``decode_attention``
is a plain einsum over the KV cache.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.attention import naive_attention, systolic_attention
from repro.dist.collectives import map_heads
from repro.kernels.flash_attention.ops import flash_attention
from repro.quant import dequantize_kv, get_quant, quantize_kv
from .layers import apply_mrope, apply_rope, dense_init, rms_norm


class KVCache(NamedTuple):
    k: jax.Array  # [B, max_len, Hkv, d]
    v: jax.Array  # [B, max_len, Hkv, d]
    lengths: jax.Array  # [B] int32: tokens cached per batch slot


class QuantKVCache(NamedTuple):
    """int8 KV storage (repro.quant): payloads + per-token/head scales.

    Field order keeps ``lengths`` last and batch at dim 0 of every array
    leaf, preserving the ``insert_cache`` / ``cache_shardings`` invariants
    of the float cache.  Scales are fp32 [B, max_len, Hkv] — 4 bytes per
    cached vector next to ``head_dim`` int8 payload bytes.
    """

    k: jax.Array  # int8 [B, max_len, Hkv, d]
    v: jax.Array  # int8 [B, max_len, Hkv, d]
    k_scale: jax.Array  # f32 [B, max_len, Hkv]
    v_scale: jax.Array  # f32 [B, max_len, Hkv]
    lengths: jax.Array  # [B] int32: tokens cached per batch slot


def attention_params(key, cfg: ModelConfig, dtype) -> dict:
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    keys = jax.random.split(key, 6)
    p = {
        "wq": dense_init(keys[0], d, cfg.num_heads * hd, dtype),
        "wk": dense_init(keys[1], d, cfg.num_kv_heads * hd, dtype),
        "wv": dense_init(keys[2], d, cfg.num_kv_heads * hd, dtype),
        "wo": dense_init(keys[3], cfg.num_heads * hd, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.num_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
    if cfg.qk_norm:  # qwen3-style per-head q/k RMSNorm
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _project_qkv(x, params, cfg: ModelConfig, positions):
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    quant = get_quant(cfg)
    q = quant.dot(x, params["wq"], "attention")
    k = quant.dot(x, params["wk"], "attention")
    v = quant.dot(x, params["wv"], "attention")
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(b, s, cfg.num_heads, hd)
    k = k.reshape(b, s, cfg.num_kv_heads, hd)
    v = v.reshape(b, s, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"])
        k = rms_norm(k, params["k_norm"])
    if cfg.mrope_sections is not None:
        q = apply_mrope(q, positions, cfg.mrope_sections, cfg.rope_theta)
        k = apply_mrope(k, positions, cfg.mrope_sections, cfg.rope_theta)
    else:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_yarn)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_yarn)
    return q, k, v


def _window(cfg: ModelConfig):
    """The layer's sliding window (None: full causal).  Attention runs one
    layer kind at a time: a patterned config is split by ``layer_config``."""
    if cfg.layer_pattern is not None:
        raise ValueError("attention takes one layer's config (ModelConfig.layer_config)")
    return cfg.sliding_window


def _impl_attention(q, k, v, cfg: ModelConfig, q_offset: int = 0) -> jax.Array:
    """Dispatch full-sequence attention to the configured implementation.

    Shared by training/prefill (``attention_forward``) and the chunked
    flash prefill (``prefill_attention``) so both paths produce identical
    numerics for the same (q, k, v) — the token-equivalence contract of
    the serving engine depends on this.
    """
    impl = cfg.attention_impl
    window = _window(cfg)
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "systolic"
    if impl == "naive":
        return naive_attention(q, k, v, causal=cfg.causal, q_offset=q_offset, window=window)
    if impl == "pallas":
        def kernel(q, k, v):
            return flash_attention(
                q, k, v, cfg.causal, None, q_offset,
                cfg.attn_block_q, cfg.attn_block_k, cfg.exp2_impl, 8, "pallas",
                False, window,
            )

        return map_heads(kernel, q, k, v)
    # systolic (paper-faithful jnp; dry-run / CPU path)
    return systolic_attention(
        q, k, v,
        causal=cfg.causal,
        q_offset=q_offset,
        block_q=cfg.attn_block_q,
        block_k=cfg.attn_block_k,
        exp2_impl=cfg.exp2_impl,
        unroll=cfg.attn_unroll,
        window=window,
    )


def attention_forward(
    x: jax.Array,  # [B, S, d_model]
    params: dict,
    cfg: ModelConfig,
    positions: jax.Array,  # [B, S] (or [B, S, 3] for M-RoPE)
) -> jax.Array:
    """Full-sequence attention (training / prefill)."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(x, params, cfg, positions)
    o = _impl_attention(q, k, v, cfg)
    o = o.reshape(b, s, cfg.num_heads * cfg.resolved_head_dim)
    return get_quant(cfg).dot(o, params["wo"], "attention")


def prefill_attention(
    x: jax.Array,  # [B, C, d_model] — one prefill chunk
    params: dict,
    cfg: ModelConfig,
    cache: KVCache,  # seq capacity >= start + C
    positions: jax.Array,  # [B, C] (or [B, C, 3]) absolute positions
    start: int,  # static chunk offset: tokens [0, start) are already cached
) -> tuple[jax.Array, KVCache]:
    """Chunked flash prefill: write the chunk's K/V straight into the cache
    and attend the chunk's queries over everything cached so far.

    One flash-attention call per chunk (no per-token loop): causality
    against the earlier chunks comes from ``q_offset=start``.  ``start`` is
    a Python int (the chunk schedule is unrolled inside jit), so the K/V
    span ``[:start+C]`` is a static slice.  ``cache.lengths`` is left for
    the caller to set once the full prompt is in.
    """
    b, c, _ = x.shape
    q, k_new, v_new = _project_qkv(x, params, cfg, positions)
    if get_quant(cfg).quantized_kv:
        # Quantize on insert: each token/head vector gets its own scale, so
        # the chunk write is byte-identical to what a per-token decode
        # scatter-write would have produced.
        kq, ks = quantize_kv(k_new)
        vq, vs = quantize_kv(v_new)
        dus = jax.lax.dynamic_update_slice_in_dim
        new_cache = QuantKVCache(
            k=dus(cache.k, kq, start, axis=1),
            v=dus(cache.v, vq, start, axis=1),
            k_scale=dus(cache.k_scale, ks, start, axis=1),
            v_scale=dus(cache.v_scale, vs, start, axis=1),
            lengths=cache.lengths,
        )
        span = slice(None, start + c)
        k = dequantize_kv(new_cache.k[:, span], new_cache.k_scale[:, span], x.dtype)
        v = dequantize_kv(new_cache.v[:, span], new_cache.v_scale[:, span], x.dtype)
        o = _impl_attention(q, k, v, cfg, q_offset=start)
    else:
        k = jax.lax.dynamic_update_slice_in_dim(
            cache.k, k_new.astype(cache.k.dtype), start, axis=1
        )
        v = jax.lax.dynamic_update_slice_in_dim(
            cache.v, v_new.astype(cache.v.dtype), start, axis=1
        )
        new_cache = KVCache(k=k, v=v, lengths=cache.lengths)
        o = _impl_attention(
            q, k[:, : start + c], v[:, : start + c], cfg, q_offset=start
        )
    o = o.reshape(b, c, cfg.num_heads * cfg.resolved_head_dim)
    return get_quant(cfg).dot(o, params["wo"], "attention"), new_cache


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    hd = cfg.resolved_head_dim
    hkv = cfg.num_kv_heads
    if get_quant(cfg).quantized_kv:
        return QuantKVCache(
            k=jnp.zeros((batch, max_len, hkv, hd), jnp.int8),
            v=jnp.zeros((batch, max_len, hkv, hd), jnp.int8),
            k_scale=jnp.zeros((batch, max_len, hkv), jnp.float32),
            v_scale=jnp.zeros((batch, max_len, hkv), jnp.float32),
            lengths=jnp.zeros((batch,), jnp.int32),
        )
    return KVCache(
        k=jnp.zeros((batch, max_len, hkv, hd), dtype),
        v=jnp.zeros((batch, max_len, hkv, hd), dtype),
        lengths=jnp.zeros((batch,), jnp.int32),
    )


def _pv(p: jax.Array, v: jax.Array) -> jax.Array:
    """P·V of the cached-decode einsums with P kept in fp32.  TPU's default
    f32 matmul rounds P to bf16; the prefill kernel does not, and decode
    should agree with prefill as closely as the activation dtype allows."""
    return jnp.einsum(
        "bhrqk,bkhd->bqhrd", p, v.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )


def verify_attention(
    x: jax.Array,  # [B, S, d_model] — S teacher-forced tokens per slot
    params: dict,
    cfg: ModelConfig,
    cache: KVCache,
    positions: jax.Array,  # [B, S] (or [B, S, 3]) absolute positions
    write_pos: jax.Array,  # [B] int32: first write row per slot
) -> tuple[jax.Array, KVCache]:
    """Batched speculative-verify attention: score S tokens per slot in one
    pass against the *live* decode cache.

    The spec-decoding core (repro.spec): S = K+1 proposed tokens enter as
    one wide teacher-forced chunk — the consecutive-large-matmul shape the
    paper's FSA scheduling thrives on, instead of K memory-bound 1-token
    decode steps.  Slot i's rows are scattered at ``write_pos[i] + j`` (its
    own decode depth, unlike ``prefill_attention``'s batch-static ``start``)
    and query j attends keys at absolute positions ``<= write_pos[i] + j``.
    Row j therefore sees exactly the cache a sequential ``decode_attention``
    step would have seen, so greedy acceptance is lossless.

    ``cache.lengths`` is left untouched: acceptance (and the rollback that
    truncates rejected suffixes) is decided by the caller once the verify
    logits are known — see ``repro.spec.verify``.
    """
    b, s_new, _ = x.shape
    hd = cfg.resolved_head_dim
    q, k_new, v_new = _project_qkv(x, params, cfg, positions)

    slot = jnp.arange(b)[:, None]  # [B, 1]
    rows = write_pos[:, None] + jnp.arange(s_new)[None, :]  # [B, S]
    if get_quant(cfg).quantized_kv:
        # Same per-token/head quantize-on-write as the decode scatter, so
        # accepted rows are byte-identical to sequential decode's writes.
        kq, ks = quantize_kv(k_new)
        vq, vs = quantize_kv(v_new)
        new_cache = QuantKVCache(
            k=cache.k.at[slot, rows].set(kq, mode="drop"),
            v=cache.v.at[slot, rows].set(vq, mode="drop"),
            k_scale=cache.k_scale.at[slot, rows].set(ks, mode="drop"),
            v_scale=cache.v_scale.at[slot, rows].set(vs, mode="drop"),
            lengths=cache.lengths,
        )
        k = dequantize_kv(new_cache.k, new_cache.k_scale)
        v = dequantize_kv(new_cache.v, new_cache.v_scale)
    else:
        k = cache.k.at[slot, rows].set(k_new.astype(cache.k.dtype), mode="drop")
        v = cache.v.at[slot, rows].set(v_new.astype(cache.v.dtype), mode="drop")
        new_cache = KVCache(k=k, v=v, lengths=cache.lengths)

    # Same grouped-einsum formulation (and fp32 softmax) as
    # ``decode_attention``, widened from 1 query to S — the mask reduces to
    # decode's ``key <= lengths`` row by row, which is what keeps verify
    # argmax-identical to the sequential decode it replaces.
    rep = cfg.num_heads // cfg.num_kv_heads
    qg = q.reshape(b, s_new, cfg.num_kv_heads, rep, hd).astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    s = jnp.einsum("bqhrd,bkhd->bhrqk", qg, k.astype(jnp.float32)) * scale
    keys = jnp.arange(k.shape[1])[None, None, None, None, :]
    valid = keys <= rows[:, None, None, :, None]
    if _window(cfg) is not None:
        valid &= keys > rows[:, None, None, :, None] - cfg.sliding_window
    s = jnp.where(valid, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = _pv(p, v).astype(x.dtype)
    o = o.reshape(b, s_new, cfg.num_heads * hd)
    return get_quant(cfg).dot(o, params["wo"], "attention"), new_cache


def decode_attention(
    x: jax.Array,  # [B, 1, d_model]
    params: dict,
    cfg: ModelConfig,
    cache: KVCache,
    positions: jax.Array,  # [B, 1] (or [B, 1, 3])
) -> tuple[jax.Array, KVCache]:
    """Single-token decode against the KV cache (paper §8.3: never FSA).

    Per-slot positions: slot i's new K/V is scattered at ``lengths[i]``, so
    requests at arbitrary decode depths share one batched step (continuous
    batching).  Slots whose length has reached capacity drop their write.
    """
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    q, k_new, v_new = _project_qkv(x, params, cfg, positions)

    slot = jnp.arange(b)
    if get_quant(cfg).quantized_kv:
        # Quantize on the decode scatter-write; attention below runs over
        # the dequantized cache (identical values to the prefill path).
        kq, ks = quantize_kv(k_new[:, 0])
        vq, vs = quantize_kv(v_new[:, 0])
        new_cache = QuantKVCache(
            k=cache.k.at[slot, cache.lengths].set(kq, mode="drop"),
            v=cache.v.at[slot, cache.lengths].set(vq, mode="drop"),
            k_scale=cache.k_scale.at[slot, cache.lengths].set(ks, mode="drop"),
            v_scale=cache.v_scale.at[slot, cache.lengths].set(vs, mode="drop"),
            lengths=cache.lengths + 1,
        )
        k = dequantize_kv(new_cache.k, new_cache.k_scale)
        v = dequantize_kv(new_cache.v, new_cache.v_scale)
    else:
        k = cache.k.at[slot, cache.lengths].set(
            k_new[:, 0].astype(cache.k.dtype), mode="drop"
        )
        v = cache.v.at[slot, cache.lengths].set(
            v_new[:, 0].astype(cache.v.dtype), mode="drop"
        )
        new_cache = KVCache(k=k, v=v, lengths=cache.lengths + 1)

    # GQA via grouped einsum — materializing jnp.repeat(k, rep) would blow
    # the cache up rep x (16x for qwen3) and force GSPMD to reshard it every
    # step (measured: the dominant decode collective cost).
    rep = cfg.num_heads // cfg.num_kv_heads
    qg = q.reshape(b, 1, cfg.num_kv_heads, rep, hd).astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    s = jnp.einsum("bqhrd,bkhd->bhrqk", qg, k.astype(jnp.float32)) * scale
    # Mask positions beyond each slot's (updated) cache length, and in a
    # sliding layer those older than its window; the cache stays dense.
    keys = jnp.arange(k.shape[1])[None, None, None, None, :]
    pos = cache.lengths[:, None, None, None, None]
    valid = keys <= pos
    if _window(cfg) is not None:
        valid &= keys > pos - cfg.sliding_window
    s = jnp.where(valid, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = _pv(p, v).astype(x.dtype)
    o = o.reshape(b, 1, cfg.num_heads * hd)
    return get_quant(cfg).dot(o, params["wo"], "attention"), new_cache
