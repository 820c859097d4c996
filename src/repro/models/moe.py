"""Mixture-of-Experts layer: top-k token-choice routing with capacity and
**explicit expert parallelism via shard_map**.

Why shard_map and not plain pjit: the dispatch scatter/gather pattern of
token-choice MoE defeats GSPMD's scatter partitioner — it replicates the
[T*k, d] token copies at global size (we measured ~128 GiB/device buffers
on the qwen3-235B dry-run).  Under shard_map every rank works on its local
tokens only and the layout is explicit:

  * tokens are sharded over the data axes and *replicated* over 'model';
  * expert weights are sharded over 'model' (num_experts / 16 per rank);
  * each rank routes its local tokens, keeps only pairs that hit its local
    experts, and builds a capacity-bounded [E_local, C, d] buffer via an
    index-inversion gather (token_for_slot) — the [T*k, d] all-pairs tensor
    never exists;
  * partial outputs are combined with one psum over 'model' — the same
    collective a Megatron row-parallel MLP pays, and the EP analogue of
    the all-to-all+combine in DeepSpeed-MoE.

Capacity dropping (capacity_factor, default 1.25) happens per rank over
its local token pool, matching per-device capacity semantics of real EP
systems.  No [T, E, C] one-hot is ever built.

Serving routes dropless.  On one shard that is a grouped matmul: the T*k
(token, expert) pairs sorted by expert run through ``jax.lax.ragged_dot``
over the experts' group sizes (``_grouped_moe``), so no expert is padded to
the token pool.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.dist.collectives import _ambient_axis_names
from repro.quant import get_quant
from .layers import dense_init, mlp_forward

DATA_AXES = ("pod", "data")


def moe_params(key, cfg: ModelConfig, dtype) -> dict:
    moe = cfg.moe
    d, e, ff = cfg.d_model, moe.num_experts, moe.d_ff_expert
    keys = jax.random.split(key, 4)

    def experts(k, in_d, out_d):
        ks = jax.random.split(k, e)
        return jax.vmap(lambda kk: dense_init(kk, in_d, out_d, dtype))(ks)

    return {
        "router": dense_init(keys[0], d, e, jnp.float32),
        "gate": experts(keys[1], d, ff),
        "up": experts(keys[2], d, ff),
        "down": experts(keys[3], ff, d),
    }


def _route(xf, router, top_k):
    """Softmax over the router's logits (float32), the top-k experts and
    their probabilities renormalised to sum to 1: ([T, k], [T, k])."""
    probs = jax.nn.softmax(xf.astype(jnp.float32) @ router, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)
    return top_p / jnp.sum(top_p, axis=-1, keepdims=True), top_e


def _moe_block(x, router, gate, up, down, cfg: ModelConfig,
               expert_offset, total_tokens_hint=None, dropless=False):
    """MoE over a local token block with a local expert slice.

    x: [B_loc, S, d]; gate/up/down: [E_loc, ...]; expert_offset: first
    global expert id owned by this rank.  Returns this rank's partial
    output (sum over ranks = full MoE output).

    ``dropless`` sets capacity to the whole token pool, so no copy is ever
    dropped and each token's output depends only on its own routing — the
    decode/verify contract (see ``moe_forward``).
    """
    moe = cfg.moe
    b, s, d = x.shape
    t = b * s
    k = moe.top_k
    e = moe.num_experts
    e_loc = gate.shape[0]
    capacity = t if dropless else max(int(t * k * moe.capacity_factor / e), 1)

    xf = x.reshape(t, d)
    top_p, top_e = _route(xf, router, k)  # [T, k]; router is replicated

    flat_e = top_e.reshape(-1)  # [T*k] int
    flat_p = top_p.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(t), k)
    order = jnp.argsort(flat_e)
    e_s, p_s, t_s = flat_e[order], flat_p[order], flat_t[order]
    counts = jnp.bincount(e_s, length=e)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(t * k) - starts[e_s]

    local_e = e_s - expert_offset
    keep = (pos < capacity) & (local_e >= 0) & (local_e < e_loc)
    # Index inversion: which token fills (local_expert, slot)?  Index
    # arrays are [E_loc, C] int32 — tiny; the [T*k, d] all-pairs tensor
    # never materializes.
    slot_flat = jnp.where(keep, local_e * capacity + pos, e_loc * capacity)
    token_for_slot = (
        jnp.full((e_loc * capacity + 1,), t, jnp.int32)
        .at[slot_flat]
        .set(t_s.astype(jnp.int32), mode="drop")[: e_loc * capacity]
    )
    weight_for_slot = (
        jnp.zeros((e_loc * capacity + 1,), jnp.float32)
        .at[slot_flat]
        .set(p_s, mode="drop")[: e_loc * capacity]
    )

    # Gather tokens into the expert buffer (sentinel t -> zero row).
    xf_pad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)
    buf = xf_pad[token_for_slot].reshape(e_loc, capacity, d)

    # Expert matmuls via the quant policy: [E_loc, C, d] x [E_loc, d, f]
    # batched dots run int8 with int32 accumulation when cfg.quant covers
    # the "moe" class (per-row token scales, per-expert-channel weight
    # scales); otherwise the plain einsum.
    quant = get_quant(cfg)
    h = jax.nn.silu(quant.dot_batched(buf, gate, "moe"))
    h = h * quant.dot_batched(buf, up, "moe")
    out_buf = quant.dot_batched(h, down, "moe")  # [E_loc, C, d]

    # Combine: weight rows and scatter-add back to tokens (one scatter of
    # [E_loc*C, d]; sentinel rows drop).
    weighted = out_buf.reshape(e_loc * capacity, d) * weight_for_slot[:, None].astype(
        x.dtype
    )
    y = (
        jnp.zeros((t + 1, d), x.dtype)
        .at[token_for_slot]
        .add(weighted, mode="drop")[:t]
    )
    return y.reshape(b, s, d)


def _grouped_moe(x, router, gate, up, down, cfg: ModelConfig):
    """Dropless MoE over every expert as grouped matmuls: the T*k (token,
    expert) pairs sorted by expert, gate, up and down as ``ragged_dot`` over
    the sorted rows and the experts' group sizes, and each row scattered
    back to its token weighted by its renormalised probability (summed in
    float32)."""
    b, s, d = x.shape
    t, k, e = b * s, cfg.moe.top_k, cfg.moe.num_experts
    xf = x.reshape(t, d)
    top_p, top_e = _route(xf, router, k)
    flat_e = top_e.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    tok = order // k  # the token of each sorted pair
    sizes = jnp.bincount(flat_e, length=e).astype(jnp.int32)
    rows = xf[tok]
    h = jax.nn.silu(jax.lax.ragged_dot(rows, gate, sizes))
    h = h * jax.lax.ragged_dot(rows, up, sizes)
    out = jax.lax.ragged_dot(h, down, sizes, preferred_element_type=jnp.float32)
    w = top_p.reshape(-1)[order]
    y = jnp.zeros((t, d), jnp.float32).at[tok].add(out * w[:, None])
    return y.astype(x.dtype).reshape(b, s, d)


def grouped_dropless(cfg: ModelConfig) -> bool:
    """Whether dropless routing runs here as grouped matmuls: on one shard
    (no 'model' axis) and without an int8 policy for the "moe" class.
    Elsewhere it pads every expert to the token pool."""
    return "model" not in _ambient_axis_names() and not get_quant(cfg).active("moe")


def moe_forward(
    x: jax.Array, params: dict, cfg: ModelConfig, dropless: bool = False
) -> jax.Array:
    """x: [B, S, d] -> [B, S, d].

    ``dropless=True`` is the decode-side mode (single-token decode and the
    speculative verify pass): expert capacity equals the token pool, so no
    token is ever dropped and routing is per-token independent.  That
    independence is what makes a token's logits identical whether it runs
    in a [B, 1] decode step or a [B, K+1] verify chunk, whatever its
    lane-mates are — the engine's token-equivalence contract for MoE.
    Serving's prefill routes dropless too where that runs as grouped
    matmuls (``grouped_dropless``, ``_grouped_moe``); training, and prefill
    under EP or an int8 policy for the "moe" class, keep the
    capacity-bounded EP semantics (the drop is the compute-efficiency
    feature there).
    """
    moe = cfg.moe
    names = _ambient_axis_names()
    if dropless and grouped_dropless(cfg):
        return _grouped_moe(
            x, params["router"], params["gate"], params["up"], params["down"], cfg
        )
    if "model" not in names:
        # Single-shard path (unit tests / CPU smoke): all experts local.
        return _moe_block(
            x, params["router"], params["gate"], params["up"], params["down"],
            cfg, expert_offset=0, dropless=dropless,
        ).astype(x.dtype)

    daxes = tuple(a for a in DATA_AXES if a in names)
    e = moe.num_experts
    model_size = 1
    mesh = jax.sharding.get_abstract_mesh()
    model_size = mesh.shape["model"]
    assert e % model_size == 0, (e, model_size)
    e_loc = e // model_size

    # FSDP/ZeRO-3 for the expert weights: at rest each leaf is sharded over
    # 'model' (experts, EP) *and* 'data' (the ff dim) — 1/256th per device —
    # and all-gathered over 'data' just-in-time inside the block (the
    # gather's transpose is the reduce-scatter of the expert grads).
    fsdp = "data" in names and (moe.d_ff_expert % mesh.shape["data"] == 0)

    def block(x_b, router_b, gate_b, up_b, down_b):
        rank = jax.lax.axis_index("model")
        if fsdp:
            gate_b = jax.lax.all_gather(gate_b, "data", axis=2, tiled=True)
            up_b = jax.lax.all_gather(up_b, "data", axis=2, tiled=True)
            down_b = jax.lax.all_gather(down_b, "data", axis=1, tiled=True)
        y = _moe_block(
            x_b, router_b, gate_b, up_b, down_b, cfg,
            expert_offset=rank * e_loc, dropless=dropless,
        )
        # Sum partial expert contributions across EP ranks (row-parallel
        # combine; tokens are replicated over 'model').
        return jax.lax.psum(y, "model")

    ffd = "data" if fsdp else None
    sm = jax.shard_map(
        block,
        in_specs=(
            P(daxes, None, None),       # x: tokens over data, repl. over model
            P(None, None),              # router: replicated
            P("model", None, ffd),      # experts: EP (+ ZeRO-3 over ff)
            P("model", None, ffd),
            P("model", ffd, None),
        ),
        out_specs=P(daxes, None, None),
    )
    return sm(x, params["router"], params["gate"], params["up"], params["down"]).astype(
        x.dtype
    )


def moe_with_dense_residual(
    x: jax.Array, params: dict, dense_params: dict, cfg: ModelConfig
) -> jax.Array:
    """Arctic: dense FFN running in parallel with the MoE branch."""
    return moe_forward(x, params, cfg) + mlp_forward(
        x, dense_params, cfg.mlp_type, get_quant(cfg)
    )
