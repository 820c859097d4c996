"""The serving reference of a model with sliding-window and full attention
layers and a sparse expert layer in every layer (Mellum2): the
full-forward logits of one sequence, in float32 at ``highest`` matmul
precision, layer by layer, with no kernel, cache or batching.  It imports
nothing of the program; the norm and the matmuls are ``reference.py``'s.

Per layer: RMSNorm, GQA attention under the layer's own mask (causal, and
in a sliding layer a window of ``window`` keys, the query's own included)
and rope (plain at ``rope_theta`` in sliding layers; YaRN in full ones, as
transformers' ``_compute_yarn_parameters`` computes it), then RMSNorm and
the experts: softmax over the router's logits, the top ``top_k``
renormalised, and every expert computed on every row, the unselected ones
weighted by zero, in blocks of rows so that it fits.

``mode="fp8"`` is the control, as in ``reference.py``: every matmul operand
rounded to float8 e4m3.  ``mode="bf16"`` rounds every matmul operand to
bfloat16 (products summed in float32): the precision the configuration
states, computed by the reference, for the witness of what the program's
gap is made of.  ``routes`` sets each layer's expert set from another pass
of the reference (a witness at bf16 routed as float32 is); the program's
own routing is never read.

Beside the logits, ``logits`` counts the rows (token, layer) of the
sequence whose top-k expert set changes when the router's input is rounded
to bfloat16, as the program's is: how many near-ties between the k-th and
the next expert the comparison meets; and it returns each layer's expert
sets.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import reference
from .counts_swa_moe import KINDS
from .reference import _f32, _norm, mm

# Sequences are padded at the end to a multiple of this, so a run compiles
# a few shapes, not one per length; rows of logits are read in groups of
# ROWS; queries attend in blocks of QBLOCK; experts run in blocks of
# EBLOCK rows.
PAD = 1024
ROWS = 32
QBLOCK = 512
EBLOCK = 512


def arch_of(conf: dict) -> dict:
    """``reference.arch_of`` and what this reference adds: each layer's
    kind, the window, the experts and the two rope groups, from the
    configuration file."""
    a = reference.arch_of(conf)
    rope = conf["rope_parameters"]
    yarn = rope["full_attention"]
    if rope["sliding_attention"]["rope_type"] != "default" or yarn["rope_type"] != "yarn":
        raise ValueError(f"no reference for rope groups {rope}")
    if not conf["norm_topk_prob"] or set(conf["mlp_layer_types"]) != {"sparse"}:
        raise ValueError("the reference computes renormalised top-k experts in every layer")
    a.update(
        kinds=tuple(KINDS[k] for k in conf["layer_types"][: a["layers"]]),
        window=int(conf["sliding_window"]),
        experts=int(conf["num_experts"]),
        top_k=int(conf["num_experts_per_tok"]),
        d_expert=int(conf["moe_intermediate_size"]),
        sliding_theta=float(rope["sliding_attention"]["rope_theta"]),
        yarn=tuple(float(yarn[k]) for k in (
            "rope_theta", "factor", "original_max_position_embeddings", "beta_fast",
            "beta_slow", "attention_factor")),
    )
    return a


def _inv_freq(d: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))


def yarn_inv_freq(d: int, yarn: tuple) -> tuple[np.ndarray, float]:
    """YaRN's inverse frequencies and cos/sin scale (arXiv:2309.00071, as
    transformers computes them, with truncation)."""
    theta, factor, orig, fast, slow, scale = yarn

    def corr(rot):
        return d * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low, high = max(math.floor(corr(fast)), 0), min(math.ceil(corr(slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    inv = _inv_freq(d, theta)
    return inv / factor * ramp + inv * (1 - ramp), scale


def _rope(x, inv, scale):
    """Rotary embedding, rotate-half form; x [S, H, d], position = row."""
    s, _, d = x.shape
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :] * scale, jnp.sin(ang)[:, None, :] * scale
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mm(mode, spec, a, b):
    if mode == "bf16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return mm(mode, spec, a, b)


def _attention(arch, mode, q, k, v, window):
    """Causal GQA attention (within ``window`` keys where it is set),
    softmax in float32, in blocks of QBLOCK queries."""
    s, h, d = q.shape
    g = arch["kv_heads"]
    q = q.reshape(s, g, h // g, d) / math.sqrt(d)
    blk = QBLOCK if s % QBLOCK == 0 else s

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, 0)
        sc = _mm(mode, "qgrd,kgd->grqk", qb, k)
        rows = i * blk + jnp.arange(blk)[:, None]
        cols = jnp.arange(s)[None, :]
        seen = cols <= rows
        if window is not None:
            seen &= rows - cols < window
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return _mm(mode, "grqk,kgd->qgrd", p, v)

    return jax.lax.map(block, jnp.arange(s // blk)).reshape(s, h * d)


def _top(h, router, arch, mode, route=None):
    """The renormalised probabilities of the top-k experts, and the experts;
    of the experts ``route`` names where it is given."""
    probs = jax.nn.softmax(_mm(mode, "sd,de->se", h, router), axis=-1)
    if route is None:
        top_p, top_e = jax.lax.top_k(probs, arch["top_k"])
    else:
        top_p, top_e = jnp.take_along_axis(probs, route, -1), route
    return top_p / jnp.sum(top_p, -1, keepdims=True), top_e


def _experts(arch, mode, h, m, route=None):
    """Every expert on every row, weighted by the renormalised top-k
    probabilities (zero for the unselected), in blocks of EBLOCK rows."""
    s, d = h.shape
    top_p, top_e = _top(h, m["router"], arch, mode, route)
    w = jnp.zeros((s, arch["experts"]), jnp.float32).at[
        jnp.arange(s)[:, None], top_e].set(top_p)
    blk = EBLOCK if s % EBLOCK == 0 else s

    def block(i):
        hb = jax.lax.dynamic_slice_in_dim(h, i * blk, blk, 0)
        wb = jax.lax.dynamic_slice_in_dim(w, i * blk, blk, 0)
        g = jax.nn.silu(_mm(mode, "rd,edf->ref", hb, m["gate"]))
        g = g * _mm(mode, "rd,edf->ref", hb, m["up"])
        return _mm(mode, "ref,efd->rd", g * wb[:, :, None], m["down"])

    return jax.lax.map(block, jnp.arange(s // blk)).reshape(s, d), top_e


def layer(arch, kind, mode, x, lp, n, route=None):
    """One pre-norm block of ``kind``; returns the new rows, how many of
    the first ``n`` rows pick another expert set from bf16-rounded input,
    and the rows' expert sets (``route`` where it is given)."""
    s = x.shape[0]
    a, hd = lp["attn"], arch["head_dim"]
    h = _norm(arch, x, lp["attn_norm"])
    q = _mm(mode, "sd,dk->sk", h, a["wq"]).reshape(s, arch["heads"], hd)
    k = _mm(mode, "sd,dk->sk", h, a["wk"]).reshape(s, arch["kv_heads"], hd)
    v = _mm(mode, "sd,dk->sk", h, a["wv"]).reshape(s, arch["kv_heads"], hd)
    if kind == "full":
        inv, scale = yarn_inv_freq(hd, arch["yarn"])
        window = None
    else:
        inv, scale, window = _inv_freq(hd, arch["sliding_theta"]), 1.0, arch["window"]
    q, k = _rope(q, inv, scale), _rope(k, inv, scale)
    x = x + _mm(mode, "sk,kd->sd", _attention(arch, mode, q, k, v, window), a["wo"])
    h = _norm(arch, x, lp["mlp_norm"])
    y, top_e = _experts(arch, mode, h, lp["moe"], route)
    _, top_b = _top(h.astype(jnp.bfloat16).astype(jnp.float32), lp["moe"]["router"], arch, mode)
    differs = jnp.any(jnp.sort(top_e, -1) != jnp.sort(top_b, -1), -1)
    x = x + y
    if mode == "bf16":  # the residual stream is held in bfloat16 too
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
    return x, jnp.sum(differs & (jnp.arange(s) < n)), top_e


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _layer(arch_items, kind, mode, x, layers, i, n, route):
    lp = _f32(jax.tree.map(lambda a: a[i], layers))
    return layer(dict(arch_items), kind, mode, x, lp, n, route)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _logits(arch_items, mode, x, rows, final_norm, head):
    h = _norm(dict(arch_items), x[rows], _f32(final_norm))
    return _mm(mode, "sd,dv->sv", h, head.astype(jnp.float32))


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(jnp.float32)


def logits(arch: dict, params, tokens, rows, mode: str = "fp32",
           routes: list | None = None) -> tuple[np.ndarray, int, list]:
    """Logits [len(rows), vocab] (float32) at positions ``rows`` of the
    sequence ``tokens`` (position = index from 0), the routing rows that
    bf16 input would flip, and each layer's expert sets (``routes``, from
    another pass over the same sequence, where it is given)."""
    n = len(tokens)
    toks = np.zeros(-(-n // PAD) * PAD, np.int32)
    toks[:n] = tokens
    padded = np.zeros(-(-max(len(rows), 1) // ROWS) * ROWS, np.int32)
    padded[:len(rows)] = rows
    items = tuple(sorted(arch.items()))
    flips, tops = [], []
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(toks))
        for i, kind in enumerate(arch["kinds"]):
            x, f, top = _layer(items, kind, mode, x, params["layers"], jnp.int32(i),
                               jnp.int32(n), None if routes is None else routes[i])
            flips.append(f)
            tops.append(top)
        head = params["lm_head"] if "lm_head" in params else params["embed"].T
        out = _logits(items, mode, x, jnp.asarray(padded), params["final_norm"], head)
    return np.asarray(out, np.float32)[:len(rows)], int(sum(int(f) for f in flips)), tops
