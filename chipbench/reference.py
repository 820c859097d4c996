"""The plain reference: a decoder transformer in float32 ``jax.numpy``, at
``highest`` matmul precision, with no kernel, cache or batching, and Adafactor
as its own few lines.  It imports nothing of the program.  It reads the
weights that the benchmark made (``weights.make``) by their names in the
parameter tree and upcasts them to float32.

``mode="fp8"`` is the control: the same mathematics with every matmul
operand rounded to float8 e4m3 (scaled per tensor), the nearest precision
below the bfloat16 that the configurations state.  The benchmark's own runs
never compute it; the limits were set between the two.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


# (MLP form, hidden_act) of a configuration file -> the MLP computed here.
MLPS = {("gated", "silu"): "swiglu"}
NORMS = ("layernorm_nonparametric", "rmsnorm")


def arch_of(conf: dict) -> dict:
    """Widths and forms of a configuration file, under the names used here.
    A form that the reference does not compute is an error, never a
    default."""
    heads = conf["num_attention_heads"]
    mlp = MLPS.get((conf["mlp"], conf["hidden_act"]))
    if mlp is None or conf["norm"] not in NORMS:
        raise ValueError(f"no reference for mlp {conf['mlp']!r} with {conf['hidden_act']!r}, "
                         f"norm {conf['norm']!r}")
    return {
        "layers": conf["num_hidden_layers"],
        "d_model": conf["hidden_size"],
        "heads": heads,
        "kv_heads": conf.get("num_key_value_heads", heads),
        "head_dim": conf.get("head_dim") or conf["hidden_size"] // heads,
        "d_ff": conf["intermediate_size"],
        "vocab": conf["vocab_size"],
        "tied": bool(conf.get("tie_word_embeddings", False)),
        "rope_theta": float(conf["rope_theta"]),
        "norm": conf["norm"],
        "mlp": mlp,
        "eps": float(conf.get("rms_norm_eps") or conf["assumed"]["layer_norm_eps"]),
    }


# -- matmuls ------------------------------------------------------------------

def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum8(spec, a, b):
    return jnp.einsum(spec, _q8(a), _q8(b), precision=HIGHEST)


def _einsum8_fwd(spec, a, b):
    qa, qb = _q8(a), _q8(b)
    return jnp.einsum(spec, qa, qb, precision=HIGHEST), (qa, qb)


def _einsum8_bwd(spec, res, g):
    qa, qb = res
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST), qa, qb)
    return vjp(_q8(g))


_einsum8.defvjp(_einsum8_fwd, _einsum8_bwd)


def mm(mode: str, spec: str, a, b):
    if mode == "fp8":
        return _einsum8(spec, a, b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


# -- layers -------------------------------------------------------------------

def _norm(arch, x, p):
    if arch["norm"] == "rmsnorm":
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + arch["eps"])
        return x * p["scale"]
    if arch["norm"] == "layernorm_nonparametric":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + arch["eps"])
    raise ValueError(arch["norm"])


def _rope(x, theta):
    """Rotary embedding, rotate-half form; x [S, H, d], position = row."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(arch, mode, q, k, v):
    """Causal GQA attention, softmax in float32, in blocks of 512 queries."""
    s, h, d = q.shape
    g = arch["kv_heads"]
    q = q.reshape(s, g, h // g, d) / math.sqrt(d)
    blk = 512 if s % 512 == 0 else s

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, 0)
        sc = mm(mode, "qgrd,kgd->grqk", qb, k)
        rows = i * blk + jnp.arange(blk)[:, None]
        sc = jnp.where(jnp.arange(s)[None, :] <= rows, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return mm(mode, "grqk,kgd->qgrd", p, v)

    out = jax.lax.map(block, jnp.arange(s // blk))
    return out.reshape(s, h * d)


def layer(arch, mode, x, lp):
    """One pre-norm block: x + attn(norm(x)), then + swiglu(norm(x))."""
    s = x.shape[0]
    a = lp["attn"]
    h = _norm(arch, x, lp.get("attn_norm"))
    q = mm(mode, "sd,dk->sk", h, a["wq"]).reshape(s, arch["heads"], arch["head_dim"])
    k = mm(mode, "sd,dk->sk", h, a["wk"]).reshape(s, arch["kv_heads"], arch["head_dim"])
    v = mm(mode, "sd,dk->sk", h, a["wv"]).reshape(s, arch["kv_heads"], arch["head_dim"])
    q, k = _rope(q, arch["rope_theta"]), _rope(k, arch["rope_theta"])
    x = x + mm(mode, "sk,kd->sd", _attention(arch, mode, q, k, v), a["wo"])
    m = lp["mlp"]
    h = _norm(arch, x, lp.get("mlp_norm"))
    y = jax.nn.silu(mm(mode, "sd,df->sf", h, m["gate"])) * mm(mode, "sd,df->sf", h, m["up"])
    return x + mm(mode, "sf,fd->sd", y, m["down"])


def _head(params):
    return params["embed"].T if "lm_head" not in params else params["lm_head"]


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


# -- training: three steps of Adafactor over rows ---------------------------------

def _row_loss(arch, mode, params, tokens, labels):
    x = params["embed"][tokens]

    def body(h, lp):
        return jax.checkpoint(lambda h, lp: layer(arch, mode, h, lp))(h, lp), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    h = _norm(arch, x, params.get("final_norm"))
    lg = mm(mode, "sd,dv->sv", h, _head(params))
    logp = jax.nn.log_softmax(lg, -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1))


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(3,))
def _accumulate(arch_items, mode, params, acc, tokens, labels):
    loss, g = jax.value_and_grad(functools.partial(_row_loss, dict(arch_items), mode))(
        params, tokens, labels)
    return loss, jax.tree.map(jnp.add, acc, g)


def lr_at(opt: dict, step: int) -> float:
    """Cosine schedule with linear warm-up, ending at a tenth of the peak."""
    peak, warm, total = opt["peak_lr"], opt["warmup_steps"], opt["schedule_steps"]
    if step < warm:
        return peak * step / max(warm, 1)
    prog = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return peak * (0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * prog)))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adafactor(params, grad_sums, stats, step, lr, inv_rows):
    """Adafactor on the mean gradient (``grad_sums * inv_rows``): factored
    second moments for matrices, decay t^-0.8, epsilon 1e-30, update clipped
    to RMS 1, no weight decay."""
    eps, t = 1e-30, step.astype(jnp.float32)
    beta2 = 1.0 - t ** -0.8

    def one(p, g, s):
        g = g * inv_rows
        g2 = g * g + eps
        if g.ndim >= 2:
            r = beta2 * s["r"] + (1 - beta2) * jnp.mean(g2, -1)
            c = beta2 * s["c"] + (1 - beta2) * jnp.mean(g2, -2)
            rn = r / jnp.maximum(jnp.mean(r, -1, keepdims=True), eps)
            u = g / jnp.sqrt(rn[..., None] * c[..., None, :] + eps)
            s = {"r": r, "c": c}
        else:
            v = beta2 * s["v"] + (1 - beta2) * g2
            u, s = g / jnp.sqrt(v + eps), {"v": v}
        u = u / jnp.maximum(1.0, jnp.sqrt(jnp.mean(u * u) + eps))
        return p - lr * u, s

    out = jax.tree.map(one, params, grad_sums, stats)
    is_pair = lambda x: isinstance(x, tuple)  # noqa: E731
    return (jax.tree.map(lambda o: o[0], out, is_leaf=is_pair),
            jax.tree.map(lambda o: o[1], out, is_leaf=is_pair))


def _stats0(params):
    def one(p):
        if p.ndim >= 2:
            return {"r": jnp.zeros(p.shape[:-1]), "c": jnp.zeros(p.shape[:-2] + p.shape[-1:])}
        return {"v": jnp.zeros(p.shape)}
    return jax.tree.map(one, params)


@jax.jit
def leaf_change_norms(new, old):
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32))))
            for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old))]


def first_step(stats, start) -> dict:
    """Readings of the first Adafactor step, taken from its statistics alike
    for the program and the reference.  At step 1 beta2 is 0, so the
    factored statistics hold the first gradient's mean squares over each
    row (``r``) and each column (``c``).  Per leaf of ``start`` (the
    weights): ``rms``, the square roots of those (row RMS, then column RMS),
    and ``grad``, the gradient's norm."""
    rms, grad = [], []
    for st, p in zip(jax.tree.structure(start).flatten_up_to(stats), jax.tree.leaves(start)):
        if "r" in st:
            r, c = (np.asarray(st[k], np.float64).ravel() for k in ("r", "c"))
            rms.append(np.sqrt(np.concatenate([r, c])))
            grad.append(math.sqrt(r.sum() * p.shape[-1]))
        else:
            v = np.asarray(st["v"], np.float64).ravel()
            rms.append(np.sqrt(v))
            grad.append(math.sqrt(v.sum()))
    return {"rms": rms, "grad": grad}


def train_steps(arch: dict, make_weights, rows, opt: dict, steps: int = 3, mode: str = "fp32"):
    """Follow ``steps`` optimizer steps from the benchmark's weights
    (``make_weights()`` makes them anew) over the rows of each step
    (``rows[s] = (tokens [B, S], labels [B, S])``), one row at a time.
    Returns the readings that ``train.compare`` takes: the mean loss of each
    step, ``first_step``'s readings, and the norm of each leaf's change
    after the steps.  The bf16 weights are not kept while the steps run:
    float32 weights, the gradient sum and one row's gradient fill the chip."""
    items = tuple(sorted(arch.items()))
    with jax.default_matmul_precision("highest"):
        params = _f32(make_weights())
        stats = _stats0(params)
        losses, out = [], {}
        for step in range(steps):
            tokens, labels = rows[step]
            acc = jax.tree.map(jnp.zeros_like, params)
            total = 0.0
            for b in range(tokens.shape[0]):
                loss, acc = _accumulate(items, mode, params, acc,
                                        jnp.asarray(tokens[b]), jnp.asarray(labels[b]))
                total += float(loss)
            inv = 1.0 / tokens.shape[0]
            losses.append(total * inv)
            params, stats = _adafactor(params, acc, stats, jnp.int32(step + 1),
                                       jnp.float32(lr_at(opt, step + 1)), jnp.float32(inv))
            if step == 0:
                out = first_step(stats, params)
        del stats
        out["change"] = [float(x) for x in leaf_change_norms(params, make_weights())]
    out["losses"] = losses
    return out
