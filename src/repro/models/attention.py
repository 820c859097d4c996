"""GQA attention layer: params, and one attention path per phase.

The paper's technique enters here.  Each phase has one function:

  * training and ``forward`` — ``attention_forward``: full-sequence
    attention;
  * prefill — ``prefill_attention``: one chunk's K/V written into the
    cache at a static ``start``, its queries attending ``[0, start+C)``;
  * decode and speculative verify — ``cached_attention``: S new tokens per
    slot (decode is S = 1 at ``write_pos = cache.lengths``) scattered into
    the live cache and attended with a grouped einsum.

The first two attend through ``_impl_attention``, which runs

  * ``pallas``   — the fused Pallas TPU kernel (``repro.kernels``), the
    default on TPU;
  * ``systolic`` — the Algorithm-1-faithful tiled jnp implementation
    (``repro.core.attention``), which lowers on every backend: the default
    elsewhere, and the f32 reference.

``cfg.attention_impl`` forces one; None picks from the platform.  Per the
paper §8.3, cached attention (a few queries, memory-bound) never uses the
FSA path.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.attention import systolic_attention
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

    Shared by training (``attention_forward``) and the chunked flash
    prefill (``prefill_attention``) so both paths produce identical
    numerics for the same (q, k, v) — the token-equivalence contract of
    the serving engine depends on this.
    """
    impl = cfg.attention_impl
    window = _window(cfg)
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "systolic"
    if impl == "pallas":
        def kernel(q, k, v):
            return flash_attention(
                q, k, v, causal=cfg.causal, q_offset=q_offset,
                block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
                exp2_impl=cfg.exp2_impl, window=window,
            )

        return map_heads(kernel, q, k, v)
    if impl != "systolic":
        raise ValueError(f"attention_impl {impl!r}: expected None, 'pallas' or 'systolic'")
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
    """Full-sequence attention (training, and ``forward``)."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(x, params, cfg, positions)
    o = _impl_attention(q, k, v, cfg)
    o = o.reshape(b, s, cfg.num_heads * cfg.resolved_head_dim)
    return get_quant(cfg).dot(o, params["wo"], "attention")


def _store_kv(cfg: ModelConfig, cache, k_new, v_new, write, read_len, dtype):
    """Store new K/V rows in ``cache`` -> (new cache, K, V to attend over).

    ``write(buffer, rows)`` puts one buffer's new rows in place.  An int8
    cache quantizes on write, one scale per token/head vector, so a row is
    byte-identical whichever phase wrote it, and is read back dequantized
    to ``dtype``; a float cache casts to its own dtype.  The K/V read back
    cover rows ``[0, read_len)`` (None: all).  ``lengths`` is left as it is.
    """
    def read(a):
        return a if read_len is None else a[:, :read_len]

    if get_quant(cfg).quantized_kv:
        kq, ks = quantize_kv(k_new)
        vq, vs = quantize_kv(v_new)
        new = QuantKVCache(
            k=write(cache.k, kq),
            v=write(cache.v, vq),
            k_scale=write(cache.k_scale, ks),
            v_scale=write(cache.v_scale, vs),
            lengths=cache.lengths,
        )
        return (new, dequantize_kv(read(new.k), read(new.k_scale), dtype),
                dequantize_kv(read(new.v), read(new.v_scale), dtype))
    new = KVCache(
        k=write(cache.k, k_new.astype(cache.k.dtype)),
        v=write(cache.v, v_new.astype(cache.v.dtype)),
        lengths=cache.lengths,
    )
    return new, read(new.k), read(new.v)


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

    def write(buf, rows):
        return jax.lax.dynamic_update_slice_in_dim(buf, rows, start, axis=1)

    new_cache, k, v = _store_kv(cfg, cache, k_new, v_new, write, start + c, x.dtype)
    o = _impl_attention(q, k, v, cfg, q_offset=start)
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


def cached_attention(
    x: jax.Array,  # [B, S, d_model] — S new tokens per slot
    params: dict,
    cfg: ModelConfig,
    cache: KVCache,
    positions: jax.Array,  # [B, S] (or [B, S, 3]) absolute positions
    write_pos: jax.Array,  # [B] int32: first write row per slot
) -> tuple[jax.Array, KVCache]:
    """S new tokens per slot against the live cache: decode (S = 1,
    ``write_pos = cache.lengths``) and speculative verify (S = K+1).

    Paper §8.3: never the FSA path — a 1-token query would waste a 128x128
    tile.  Slot i's rows are scattered at ``write_pos[i] + j`` (its own
    depth, so requests at arbitrary depths share one batched step; rows past
    capacity drop their write) and query j attends keys at absolute
    positions ``<= write_pos[i] + j`` (and, in a sliding layer, within its
    window).  Verify's row j therefore sees exactly the cache a sequential
    decode step would have seen, so greedy acceptance is lossless.

    ``cache.lengths`` is left untouched: decode advances it, and verify's
    caller decides how many rows survive (``repro.spec.verify``).
    """
    b, s_new, _ = x.shape
    hd = cfg.resolved_head_dim
    q, k_new, v_new = _project_qkv(x, params, cfg, positions)

    slot = jnp.arange(b)[:, None]  # [B, 1]
    rows = write_pos[:, None] + jnp.arange(s_new)[None, :]  # [B, S]

    def write(buf, new):
        return buf.at[slot, rows].set(new, mode="drop")

    new_cache, k, v = _store_kv(cfg, cache, k_new, v_new, write, None, jnp.float32)

    # GQA via grouped einsum — materializing jnp.repeat(k, rep) would blow
    # the cache up rep x (16x for qwen3) and force GSPMD to reshard it every
    # step (measured: the dominant decode collective cost).  Softmax in
    # fp32; the cache stays dense and the mask hides rows past each query.
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
