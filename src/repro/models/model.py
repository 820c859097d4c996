"""Model assembly: init / forward / decode for every architecture family.

Families:
  dense | moe | vlm | encoder — transformer stacks (scan-over-layers, remat)
  hybrid — zamba2: Mamba2 backbone + one *shared* attention(+MLP) block
           applied every ``cfg.attn_every`` layers (weights shared, KV caches
           per application)
  ssm    — xlstm: alternating mLSTM / sLSTM blocks (attention-free)

All params are plain nested dicts; layer params are stacked along a leading
axis and consumed by ``jax.lax.scan`` so the per-layer HLO is compiled once
(critical for 94-layer dry-run compiles).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.collectives import constrain
from repro.quant import get_quant
from .attention import (
    KVCache,
    QuantKVCache,
    attention_forward,
    attention_params,
    cached_attention,
    init_kv_cache,
    prefill_attention,
)
from .layers import apply_norm, embed_init, mlp_forward, mlp_params, norm_params
from .moe import grouped_dropless, moe_forward, moe_params
from .ssm import MambaCache, init_mamba_cache, mamba_decode, mamba_forward, mamba_params
from .xlstm import (
    MLSTMState,
    SLSTMState,
    init_mlstm_state,
    init_slstm_state,
    mlstm_decode,
    mlstm_forward,
    mlstm_params,
    slstm_decode,
    slstm_forward,
    slstm_params,
)

# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _transformer_layer_params(key, cfg: ModelConfig, dtype) -> dict:
    keys = jax.random.split(key, 5)
    p = {
        "attn_norm": norm_params(keys[0], cfg.d_model, cfg.norm_type, dtype),
        "attn": attention_params(keys[1], cfg, dtype),
        "mlp_norm": norm_params(keys[2], cfg.d_model, cfg.norm_type, dtype),
    }
    if cfg.moe is not None:
        p["moe"] = moe_params(keys[3], cfg, dtype)
        if cfg.moe.dense_residual:
            p["dense_mlp"] = mlp_params(keys[4], cfg.d_model, cfg.d_ff, cfg.mlp_type, dtype)
    else:
        p["mlp"] = mlp_params(keys[3], cfg.d_model, cfg.d_ff, cfg.mlp_type, dtype)
    return p


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    dtype = cfg.activation_dtype
    keys = jax.random.split(key, 8)
    params: dict[str, Any] = {}
    params["embed"] = embed_init(keys[0], cfg.vocab_size, cfg.d_model, dtype)
    params["final_norm"] = norm_params(keys[1], cfg.d_model, cfg.norm_type, dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = embed_init(keys[2], cfg.vocab_size, cfg.d_model, dtype).T

    if cfg.family in ("dense", "moe", "vlm", "encoder"):
        layer_keys = jax.random.split(keys[3], cfg.num_layers)
        params["layers"] = jax.vmap(
            lambda k: _transformer_layer_params(k, cfg, dtype)
        )(layer_keys)
    elif cfg.family == "hybrid":
        layer_keys = jax.random.split(keys[3], cfg.num_layers)
        params["mamba_layers"] = jax.vmap(
            lambda k: {
                "norm": norm_params(None, cfg.d_model, cfg.norm_type, dtype),
                "mamba": mamba_params(k, cfg, dtype),
            }
        )(layer_keys)
        params["shared_attn"] = _transformer_layer_params(keys[4], cfg, dtype)
    elif cfg.family == "ssm":
        n_blocks = cfg.num_layers // 2  # one (mLSTM, sLSTM) pair per block
        block_keys = jax.random.split(keys[3], n_blocks)
        params["blocks"] = jax.vmap(
            lambda k: {
                "mlstm_norm": norm_params(None, cfg.d_model, cfg.norm_type, dtype),
                "mlstm": mlstm_params(jax.random.fold_in(k, 0), cfg, dtype),
                "slstm_norm": norm_params(None, cfg.d_model, cfg.norm_type, dtype),
                "slstm": slstm_params(jax.random.fold_in(k, 1), cfg, dtype),
            }
        )(block_keys)
    else:
        raise ValueError(cfg.family)
    return params


def param_shapes(cfg: ModelConfig) -> dict:
    """Abstract params (ShapeDtypeStructs) — no allocation; dry-run input."""
    return jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _default_positions(cfg: ModelConfig, batch: int, seq: int, offset=0):
    pos = offset + jnp.arange(seq, dtype=jnp.int32)[None, :]
    pos = jnp.broadcast_to(pos, (batch, seq))
    if cfg.mrope_sections is not None:
        pos = jnp.broadcast_to(pos[..., None], (batch, seq, 3))
    return pos


def _sp(x, cfg: ModelConfig):
    """Sequence-parallel residual sharding (Megatron-SP): the layer-scan
    carry — which remat checkpoints per layer — lives sharded over
    (data x model) instead of (data x replicated).  GSPMD inserts the
    all-gather before attention/MLP and the reduce-scatter after, halving
    TP collective volume and dividing checkpointed activation memory by
    the model-axis size.  No-op without an ambient mesh.

    Under dp_only the batch dim spans every axis and the carry is simply
    batch-sharded."""
    if cfg.parallelism == "dp_only":
        return constrain(x, ("pod", "data", "model"), None, None)
    return constrain(x, ("pod", "data"), "model", None)


class _Phase(NamedTuple):
    """What one phase gives ``_transformer_block``: its attention sub-block
    ``attend(h, attn_params, cfg, kv) -> (a, kv)``, its MoE routing
    (``dropless``), and whether the residual is sharded by sequence
    (``_sp``)."""

    attend: Callable
    dropless: bool
    seq_parallel: bool


def _full_phase(positions) -> _Phase:
    """Training and ``forward``: full-sequence attention, no cache, and
    capacity routing."""
    def attend(h, p, cfg, kv):
        return attention_forward(h, p, cfg, positions), kv

    return _Phase(attend, dropless=False, seq_parallel=True)


def _prefill_phase(cfg: ModelConfig, positions, start: int) -> _Phase:
    """Prefill: the chunk's K/V written into the cache at ``start``.
    Prefill into a cache is serving: it routes dropless, as decode does,
    where the experts run grouped.  Under EP or an int8 "moe" policy
    dropless pads every expert to the token pool, so prefill keeps
    capacity routing there."""
    def attend(h, p, lcfg, kv):
        return prefill_attention(h, p, lcfg, kv, positions, start)

    return _Phase(attend, dropless=grouped_dropless(cfg), seq_parallel=True)


def _cached_phase(positions, write_pos=None) -> _Phase:
    """Decode and verify: S new rows per slot at ``write_pos`` (None: each
    layer's own ``cache.lengths``, where decode writes).  Dropless: a
    token's routing must not depend on its lane-mates (dead slots, other
    slots' depths), so verify row j equals the decode step it replaces.
    The residual of a few rows per slot stays whole."""
    def attend(h, p, cfg, kv):
        at = kv.lengths if write_pos is None else write_pos
        return cached_attention(h, p, cfg, kv, positions, at)

    return _Phase(attend, dropless=True, seq_parallel=False)


def _transformer_block(x, layer, cfg: ModelConfig, phase: _Phase, kv=None):
    """One transformer block -> ``(x, kv)``, for every phase: ``phase``
    supplies the attention over ``kv`` (a per-layer cache, or None), the MoE
    routing and the residual sharding.  The norms, residuals and MLP are
    shared, so prefill-into-cache and training forward are numerically
    identical."""
    def shard(x):
        return _sp(x, cfg) if phase.seq_parallel else x

    x = shard(x)
    h = apply_norm(x, layer["attn_norm"], cfg.norm_type)
    a, kv = phase.attend(h, layer["attn"], cfg, kv)
    x = x + a
    x = shard(x)
    h = apply_norm(x, layer["mlp_norm"], cfg.norm_type)
    quant = get_quant(cfg)
    if cfg.moe is not None:
        y = moe_forward(h, layer["moe"], cfg, dropless=phase.dropless)
        if cfg.moe.dense_residual:
            y = y + mlp_forward(h, layer["dense_mlp"], cfg.mlp_type, quant)
    else:
        y = mlp_forward(h, layer["mlp"], cfg.mlp_type, quant)
    return shard(x + y), kv


def _head(params: dict, cfg: ModelConfig, x) -> jax.Array:
    """Final norm and LM head -> logits."""
    x = apply_norm(x, params["final_norm"], cfg.norm_type)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return x @ head


def _scan_layers(x, stacked, body, remat: bool, unroll: int = 1):
    fn = jax.checkpoint(body) if remat else body

    def step(carry, layer):
        return fn(carry, layer), None

    out, _ = jax.lax.scan(step, x, stacked, unroll=unroll)
    return out


def _scan_periods(cfg: ModelConfig, x, layers, body, cache=None, remat: bool = False):
    """The layer scan of a patterned config: each scan step runs one period
    of ``p`` layers in order, each under its kind's static config (window,
    rope), so that flash grids and rope tables stay static.

    Without ``cache``, ``body(layer_cfg, h, layer) -> h``.  With it,
    ``body(layer_cfg, h, (layer, layer_cache)) -> (h, layer_cache)``, and
    the stacked cache rides in the carry, each layer's slice updated in
    place.  Every layer's params and cache are indexed from the stacked
    ``[L, ...]`` arrays one layer at a time: a period's slice of them as a
    scan input is materialized whole (3.2 GB of experts for Mellum2's
    four layers).  Returns ``(h, cache)``.  With ``remat`` each layer is
    checkpointed, as ``_scan_layers`` does."""
    kinds = cfg.layer_pattern
    p = len(kinds)
    if cfg.num_layers % p:
        raise ValueError(f"{cfg.num_layers} layers are not whole periods of {kinds}")
    lcfgs = [cfg.layer_config(k) for k in kinds]

    def at(tree, j):
        return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, j, keepdims=False), tree)

    def period(carry, i):
        h, c = carry
        for k, lcfg in enumerate(lcfgs):
            fn = functools.partial(body, lcfg)
            if remat:
                fn = jax.checkpoint(fn)
            j = i * p + k
            if c is None:
                h = fn(h, at(layers, j))
                continue
            h, kv = fn(h, (at(layers, j), at(c, j)))
            c = jax.tree.map(lambda a, u: jax.lax.dynamic_update_index_in_dim(a, u, j, 0), c, kv)
        return (h, c), None

    (h, cache), _ = jax.lax.scan(period, (x, cache), jnp.arange(cfg.num_layers // p),
                                 unroll=cfg.scan_unroll)
    return h, cache


def _scan_cached(cfg: ModelConfig, x, layers, cache, phase: _Phase):
    """The layer walk of decode, verify and chunked prefill: every layer a
    ``_transformer_block`` with its own cache.  A patterned config scans by
    period (``_scan_periods``); any other keeps one ``lax.scan`` over the
    stacked layers and cache.  Returns ``(h, cache)``."""
    def body(lcfg, h, inp):
        layer, kv = inp
        return _transformer_block(h, layer, lcfg, phase, kv)

    if cfg.layer_pattern is not None:
        return _scan_periods(cfg, x, layers, body, cache)
    return jax.lax.scan(functools.partial(body, cfg), x, (layers, cache),
                        unroll=cfg.scan_unroll)


def forward(
    params: dict,
    cfg: ModelConfig,
    *,
    tokens: Optional[jax.Array] = None,  # [B, S] int32
    embeds: Optional[jax.Array] = None,  # [B, S, d] (frontend-stub archs)
    positions: Optional[jax.Array] = None,
) -> jax.Array:
    """Full-sequence forward -> logits [B, S, V]."""
    if embeds is not None:
        x = embeds.astype(cfg.activation_dtype)
    else:
        x = params["embed"][tokens]
    b, s = x.shape[:2]
    if positions is None:
        positions = _default_positions(cfg, b, s)

    phase = _full_phase(positions)
    if cfg.family in ("dense", "moe", "vlm", "encoder") and cfg.layer_pattern is not None:
        def period_body(lcfg, h, layer):
            return _transformer_block(h, layer, lcfg, phase)[0]

        x, _ = _scan_periods(cfg, x, params["layers"], period_body, remat=cfg.remat)
    elif cfg.family in ("dense", "moe", "vlm", "encoder"):
        def body(h, layer):
            return _transformer_block(h, layer, cfg, phase)[0]

        x = _scan_layers(x, params["layers"], body, cfg.remat, cfg.scan_unroll)
    elif cfg.family == "hybrid":
        shared = params["shared_attn"]
        every = max(cfg.attn_every, 1)

        def hybrid_body(carry, inp):
            h, = carry
            layer, idx = inp

            def with_attn(h):
                return _transformer_block(h, shared, cfg, phase)[0]

            h = jax.lax.cond(idx % every == 0, with_attn, lambda h: h, h)
            hn = apply_norm(h, layer["norm"], cfg.norm_type)
            h = h + mamba_forward(hn, layer["mamba"], cfg)
            return (h,), None

        body_fn = jax.checkpoint(hybrid_body) if cfg.remat else hybrid_body
        (x,), _ = jax.lax.scan(
            body_fn,
            (x,),
            (params["mamba_layers"], jnp.arange(cfg.num_layers)),
            unroll=cfg.scan_unroll,
        )
    elif cfg.family == "ssm":
        def ssm_body(h, block):
            hn = apply_norm(h, block["mlstm_norm"], cfg.norm_type)
            h = h + mlstm_forward(hn, block["mlstm"], cfg)
            hn = apply_norm(h, block["slstm_norm"], cfg.norm_type)
            h = h + slstm_forward(hn, block["slstm"], cfg)
            return h

        x = _scan_layers(x, params["blocks"], ssm_body, cfg.remat, cfg.scan_unroll)
    else:
        raise ValueError(cfg.family)

    return _head(params, cfg, x)


# ---------------------------------------------------------------------------
# decode (single new token against caches)
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Any:
    dtype = cfg.activation_dtype
    if cfg.family in ("dense", "moe", "vlm"):
        def one(_):
            return init_kv_cache(cfg, batch, max_len, dtype)

        return jax.vmap(one)(jnp.arange(cfg.num_layers))
    if cfg.family == "hybrid":
        every = max(cfg.attn_every, 1)
        n_attn = (cfg.num_layers + every - 1) // every
        return {
            "attn": jax.vmap(lambda _: init_kv_cache(cfg, batch, max_len, dtype))(
                jnp.arange(n_attn)
            ),
            "mamba": jax.vmap(lambda _: init_mamba_cache(cfg, batch, dtype))(
                jnp.arange(cfg.num_layers)
            ),
        }
    if cfg.family == "ssm":
        n_blocks = cfg.num_layers // 2
        return {
            "mlstm": jax.vmap(lambda _: init_mlstm_state(cfg, batch))(
                jnp.arange(n_blocks)
            ),
            "slstm": jax.vmap(lambda _: init_slstm_state(cfg, batch))(
                jnp.arange(n_blocks)
            ),
        }
    raise ValueError(f"{cfg.family} has no decode step (encoder-only)")


def decode_step(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,  # [B, 1] int32
    cache: Any,
    position: jax.Array,  # scalar or [B] int32: absolute position per slot
) -> tuple[jax.Array, Any]:
    """One decode step -> (logits [B, 1, V], new cache).

    ``position`` may be a scalar (all slots at the same depth — the
    static-batch path) or a per-slot ``[B]`` vector (continuous batching:
    each slot decodes at its own depth)."""
    x = params["embed"][tokens]
    b = x.shape[0]
    position = jnp.asarray(position, jnp.int32)
    pos = jnp.broadcast_to(position.reshape(-1, 1), (b, 1))
    if cfg.mrope_sections is not None:
        pos = jnp.broadcast_to(pos[..., None], (b, 1, 3))

    # Every attention layer's cache takes one row per slot at its length;
    # the lengths advance here, once per step.
    phase = _cached_phase(pos)
    if cfg.family in ("dense", "moe", "vlm"):
        x, new_cache = _scan_cached(cfg, x, params["layers"], cache, phase)
        new_cache = new_cache._replace(lengths=new_cache.lengths + 1)
    elif cfg.family == "hybrid":
        shared = params["shared_attn"]
        every = max(cfg.attn_every, 1)
        n_attn = (cfg.num_layers + every - 1) // every

        def hybrid_body(carry, inp):
            h = carry
            layer, mamba_cache, idx = inp

            def with_attn(args):
                h, kv = args
                return _transformer_block(h, shared, cfg, phase, kv)

            attn_slot = idx // every
            kv = jax.tree.map(lambda c: c[attn_slot], cache["attn"])
            h, kv_new = jax.lax.cond(
                idx % every == 0, with_attn, lambda a: (a[0], a[1]), (h, kv)
            )
            hn = apply_norm(h, layer["norm"], cfg.norm_type)
            m, mc_new = mamba_decode(hn, layer["mamba"], cfg, mamba_cache)
            # Non-attention layers must not write their (stale) slot echo:
            # route their scatter index out of bounds (dropped below).
            write_idx = jnp.where(idx % every == 0, attn_slot, n_attn)
            return h + m, (kv_new, write_idx, mc_new)

        x, (kvs, slots, mcs) = jax.lax.scan(
            hybrid_body,
            x,
            (params["mamba_layers"], cache["mamba"], jnp.arange(cfg.num_layers)),
            unroll=cfg.scan_unroll,
        )
        # Scatter updated attention caches back: exactly one layer per slot
        # carries a valid index; all others were routed out of bounds and
        # are dropped by the scatter.
        new_attn = jax.tree.map(
            lambda stacked, upd: stacked.at[slots].set(upd, mode="drop"),
            cache["attn"],
            kvs,
        )
        new_attn = new_attn._replace(lengths=new_attn.lengths + 1)
        new_cache = {"attn": new_attn, "mamba": mcs}
    elif cfg.family == "ssm":
        def ssm_body(h, inp):
            block, ms, ss = inp
            hn = apply_norm(h, block["mlstm_norm"], cfg.norm_type)
            y, ms_new = mlstm_decode(hn, block["mlstm"], cfg, ms)
            h = h + y
            hn = apply_norm(h, block["slstm_norm"], cfg.norm_type)
            y, ss_new = slstm_decode(hn, block["slstm"], cfg, ss)
            return h + y, (ms_new, ss_new)

        x, (ms_all, ss_all) = jax.lax.scan(
            ssm_body,
            x,
            (params["blocks"], cache["mlstm"], cache["slstm"]),
            unroll=cfg.scan_unroll,
        )
        new_cache = {"mlstm": ms_all, "slstm": ss_all}
    else:
        raise ValueError(cfg.family)
    return _head(params, cfg, x), new_cache


# ---------------------------------------------------------------------------
# speculative verify (K+1 teacher-forced tokens against the live cache)
# ---------------------------------------------------------------------------


def verify_step(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,  # [B, S] int32: [last sampled token, K draft tokens]
    cache: Any,  # the *live* decode cache (batch B, capacity max_len)
    positions: jax.Array,  # [B] int32: per-slot first write position
) -> tuple[jax.Array, Any]:
    """Score S teacher-forced tokens per slot in one batched forward.

    The speculative-decoding verify pass (repro.spec): slot i's tokens
    occupy absolute positions ``positions[i] + [0, S)``; their K/V are
    written straight into the live decode cache at those per-slot rows and
    every token attends exactly the prefix a sequential ``decode_step``
    would have seen, so ``argmax(logits[:, j])`` equals the vanilla greedy
    token given the prefix plus ``tokens[:, :j+1]``.

    ``cache.lengths`` is *not* advanced here — the caller decides how many
    proposed tokens survive and truncates via ``rollback_cache``.  Only
    attention families support this (recurrent Mamba/xLSTM state cannot be
    rolled back by length truncation).
    """
    if cfg.family not in ("dense", "moe", "vlm"):
        raise ValueError(
            f"verify_step requires an attention-family cache (KV rollback); "
            f"{cfg.family!r} carries recurrent state"
        )
    x = params["embed"][tokens]
    b, s = tokens.shape
    positions = jnp.asarray(positions, jnp.int32)
    pos = positions[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]  # [B, S]
    if cfg.mrope_sections is not None:
        pos = jnp.broadcast_to(pos[..., None], (b, s, 3))
    x, new_cache = _scan_cached(cfg, x, params["layers"], cache,
                                _cached_phase(pos, positions))
    return _head(params, cfg, x), new_cache


def rollback_cache(cache: Any, new_lengths: jax.Array) -> Any:
    """Truncate every slot's cached length to ``new_lengths`` [B].

    Speculative-decoding rejection rollback: rejected suffix rows stay in
    the buffers but become invisible — every attention read masks keys
    beyond ``lengths`` and every subsequent write scatters at ``lengths``,
    so stale rows are never read and are overwritten in place.  Works for
    both fp32 ``KVCache`` and int8 ``QuantKVCache`` (stacked ``[L, B, ...]``
    leaves with ``lengths [L, B]``); recurrent-state caches cannot roll
    back this way and are rejected.
    """
    if not isinstance(cache, (KVCache, QuantKVCache)):
        raise ValueError(
            "rollback_cache requires a KVCache/QuantKVCache (attention "
            "families); recurrent state has no length-truncation rollback"
        )
    new_lengths = jnp.asarray(new_lengths, jnp.int32)
    return cache._replace(
        lengths=jnp.broadcast_to(new_lengths[None, :], cache.lengths.shape)
    )


# ---------------------------------------------------------------------------
# prefill (whole prompt into the cache) + slot insert
# ---------------------------------------------------------------------------


def _prefill_chunk(params: dict, cfg: ModelConfig, tokens_c, cache, start: int):
    """One prefill chunk through the transformer stack: each layer writes
    its K/V into the cache and flash-attends over [0, start+C)."""
    x = params["embed"][tokens_c]
    b, c = tokens_c.shape
    positions = _default_positions(cfg, b, c, offset=start)

    x, new_cache = _scan_cached(cfg, x, params["layers"], cache,
                                _prefill_phase(cfg, positions, start))
    return _head(params, cfg, x), new_cache


def _prefill_by_scan(params: dict, cfg: ModelConfig, tokens, cache, lengths):
    """Family-agnostic prefill fallback: teacher-force the prompt through
    ``decode_step`` under one ``lax.scan`` (a single jit invocation, not an
    O(prompt_len) Python loop).  Per-slot state updates are frozen once the
    scan passes a slot's true length, so right-padded prompts don't pollute
    recurrent (Mamba/xLSTM) states with pad tokens."""
    b, s = tokens.shape

    def body(c, inp):
        tok, pos = inp
        logits, new_c = decode_step(params, cfg, tok[:, None], c, pos)
        keep = pos < lengths  # [B]

        def sel(n, o):
            return jnp.where(keep.reshape((1, b) + (1,) * (n.ndim - 2)), n, o)

        return jax.tree.map(sel, new_c, c), logits[:, 0]

    cache, logits = jax.lax.scan(
        body, cache, (tokens.T, jnp.arange(s, dtype=jnp.int32))
    )
    return jnp.moveaxis(logits, 0, 1), cache


def prefill_step(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,  # [B, S] int32, right-padded to the bucket length
    cache: Any,  # from ``init_cache(cfg, B, S')`` with S' >= S
    lengths: jax.Array,  # [B] int32: true prompt length per row
    *,
    chunk_size: Optional[int] = None,
) -> tuple[jax.Array, Any]:
    """Prefill a (padded) prompt batch into ``cache`` -> (logits [B,S,V], cache).

    Attention families run the chunked flash path — ``flash_attention`` is
    called once per chunk of ``chunk_size`` tokens (default: the whole
    prompt in one call) and K/V are written straight into the cache, no
    per-token loop and no second pass.  Recurrent families (hybrid/ssm)
    teacher-force through ``decode_step`` under a single ``lax.scan``.
    """
    b, s = tokens.shape
    lengths = jnp.asarray(lengths, jnp.int32).reshape(b)
    if cfg.family in ("dense", "moe", "vlm"):
        chunk = min(int(chunk_size), s) if chunk_size else s
        logits = []
        for start in range(0, s, chunk):
            lg, cache = _prefill_chunk(
                params, cfg, tokens[:, start : start + chunk], cache, start
            )
            logits.append(lg)
        out = logits[0] if len(logits) == 1 else jnp.concatenate(logits, axis=1)
        cache = cache._replace(
            lengths=jnp.broadcast_to(lengths[None, :], cache.lengths.shape)
        )
        return out, cache
    if cfg.family == "encoder":
        raise ValueError("encoder archs have no decode cache to prefill")
    return _prefill_by_scan(params, cfg, tokens, cache, lengths)


def insert_cache(cache: Any, prefix: Any, slot: jax.Array) -> Any:
    """Copy a prefilled cache (batch dim 1, seq capacity <= max_len) into
    batch slot ``slot`` of a decode cache.  Family-agnostic: every stacked
    cache leaf is [L, B, ...] with batch at dim 1 (KV tensors, per-slot
    lengths, Mamba/xLSTM states alike), so one dynamic_update_slice per
    leaf moves the whole request."""

    def one(dst, src):
        start = (0, slot) + (0,) * (dst.ndim - 2)
        return jax.lax.dynamic_update_slice(dst, src.astype(dst.dtype), start)

    return jax.tree.map(one, cache, prefix)


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------


def lm_loss(
    params: dict,
    cfg: ModelConfig,
    batch: dict,
) -> jax.Array:
    """Next-token (or frame-label) cross entropy; labels < 0 are masked."""
    logits = forward(
        params,
        cfg,
        tokens=batch.get("tokens"),
        embeds=batch.get("embeds"),
        positions=batch.get("positions"),
    )
    labels = batch["labels"]
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    mask = (labels >= 0).astype(jnp.float32)
    safe = jnp.maximum(labels, 0)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
