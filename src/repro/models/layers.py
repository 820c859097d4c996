"""Shared neural-net building blocks (pure functions over param pytrees)."""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.quant import Quant

_FP = Quant()  # no-op policy for call sites without a config


# -- initializers ---------------------------------------------------------------

def dense_init(key, in_dim: int, out_dim: int, dtype) -> jax.Array:
    scale = 1.0 / np.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32) * scale).astype(dtype)


def embed_init(key, vocab: int, dim: int, dtype) -> jax.Array:
    return (jax.random.normal(key, (vocab, dim), jnp.float32) * 0.02).astype(dtype)


# -- norms -----------------------------------------------------------------------

def rms_norm(x: jax.Array, weight: Optional[jax.Array], eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    if weight is not None:
        x = x * weight.astype(jnp.float32)
    return x.astype(dtype)


def layer_norm(
    x: jax.Array,
    weight: Optional[jax.Array],
    bias: Optional[jax.Array],
    eps: float = 1e-5,
) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    if weight is not None:
        x = x * weight.astype(jnp.float32)
    if bias is not None:
        x = x + bias.astype(jnp.float32)
    return x.astype(dtype)


def apply_norm(x: jax.Array, params: Optional[dict], norm_type: str) -> jax.Array:
    if norm_type == "rmsnorm":
        return rms_norm(x, params["scale"] if params else None)
    if norm_type == "layernorm":
        return layer_norm(x, params["scale"], params["bias"])
    if norm_type == "non_parametric":  # OLMo: LN without learnable params
        return layer_norm(x, None, None)
    raise ValueError(norm_type)


def norm_params(key, d: int, norm_type: str, dtype) -> Optional[dict]:
    if norm_type == "rmsnorm":
        return {"scale": jnp.ones((d,), dtype)}
    if norm_type == "layernorm":
        return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
    if norm_type == "non_parametric":
        return None
    raise ValueError(norm_type)


# -- rotary embeddings -------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))


def yarn_frequencies(head_dim: int, theta: float, yarn) -> np.ndarray:
    """YaRN inverse frequencies (arXiv:2309.00071), as transformers'
    ``_compute_yarn_parameters`` computes them: dimensions that turn fewer
    than ``beta_slow`` times over the original context are interpolated
    (divided by ``factor``), those that turn more than ``beta_fast`` times
    keep their frequency, and a linear ramp blends the range between (its
    ends rounded out to whole dimensions, as the published configs have it)."""
    def correction_dim(rotations):
        return (head_dim * math.log(yarn.original_max_position_embeddings
                                    / (rotations * 2 * math.pi))) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    inv = rope_frequencies(head_dim, theta)
    keep = 1.0 - ramp  # 1: extrapolated (kept), 0: interpolated
    return inv / yarn.factor * (1.0 - keep) + inv * keep


def apply_rope(
    x: jax.Array,  # [B, S, H, d]
    positions: jax.Array,  # [B, S]
    theta: float = 10000.0,
    yarn=None,  # Optional[configs.base.YarnConfig]
) -> jax.Array:
    d = x.shape[-1]
    if yarn is None:
        inv, mscale = rope_frequencies(d, theta), 1.0
    else:
        inv, mscale = yarn_frequencies(d, theta, yarn), yarn.attention_factor
    freqs = jnp.asarray(inv, jnp.float32)  # [d/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, d/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if yarn is not None:
        cos, sin = cos * mscale, sin * mscale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jax.Array,  # [B, S, H, d]
    positions: jax.Array,  # [B, S, 3] (t, h, w) — qwen2-vl M-RoPE
    sections: tuple[int, int, int],
    theta: float = 1_000_000.0,
) -> jax.Array:
    """Multimodal RoPE: the head_dim/2 frequency slots are partitioned into
    (temporal, height, width) sections, each rotated by its own position id.
    For pure-text tokens all three ids coincide and M-RoPE reduces to RoPE."""
    d = x.shape[-1]
    half = d // 2
    assert sum(sections) == half, (sections, half)
    freqs = jnp.asarray(rope_frequencies(d, theta), jnp.float32)  # [half]
    # Build a per-slot position by selecting the section's position id.
    sec_id = jnp.asarray(
        np.repeat(np.arange(3), np.asarray(sections)), jnp.int32
    )  # [half]
    pos = positions.astype(jnp.float32)[:, :, sec_id]  # [B, S, half]
    angles = pos * freqs
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# -- MLPs --------------------------------------------------------------------------

def mlp_params(key, d: int, d_ff: int, mlp_type: str, dtype) -> dict:
    keys = jax.random.split(key, 3)
    if mlp_type == "swiglu":
        return {
            "gate": dense_init(keys[0], d, d_ff, dtype),
            "up": dense_init(keys[1], d, d_ff, dtype),
            "down": dense_init(keys[2], d_ff, d, dtype),
        }
    return {
        "up": dense_init(keys[0], d, d_ff, dtype),
        "down": dense_init(keys[1], d_ff, d, dtype),
    }


def mlp_forward(
    x: jax.Array, params: dict, mlp_type: str, quant: Quant = _FP
) -> jax.Array:
    dot = lambda a, w: quant.dot(a, w, "mlp")  # noqa: E731
    if mlp_type == "swiglu":
        h = jax.nn.silu(dot(x, params["gate"])) * dot(x, params["up"])
    elif mlp_type == "squared_relu":  # nemotron-4
        h = jnp.square(jax.nn.relu(dot(x, params["up"])))
    elif mlp_type == "gelu":
        h = jax.nn.gelu(dot(x, params["up"]))
    else:
        raise ValueError(mlp_type)
    return dot(h, params["down"])
