"""Sliding-window attention, YaRN rope, the layer scan by period and the
dropless grouped experts of a Mellum2-style model, on the CPU:

* the windowed Pallas forward (interpret mode) against the materialized
  oracle with the same window, at a chunk start as at 0, for windows that
  are and are not block multiples, at GQA 8:1, and a window as long as the
  sequence equal to plain causal attention;
* a windowed call under ``jax.grad`` refused, never run unwindowed;
* a call without a window traced to the kernel it had before the window;
* the grouped dropless experts against the dense top-k mix, with routing
  skewed so that one expert takes most rows;
* YaRN's inverse frequencies against a float64 table at Mellum's values;
* a decode past the window blind to a key older than the window;
* the smoke config's decode, prefill and forward in agreement.
"""

import dataclasses
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, MoEConfig, YarnConfig
from repro.configs.registry import get_config, get_smoke_config
from repro.core.attention import naive_attention, systolic_attention
from repro.kernels.flash_attention.kernel import flash_attention_fwd, window_kv_steps
from repro.kernels.flash_attention.ops import flash_attention
from repro.models import decode_step, forward, init_cache, init_params, prefill_step
from repro.models.attention import cached_attention, init_kv_cache
from repro.models.layers import yarn_frequencies
from repro.models.moe import moe_forward, moe_params

ARCH = "mellum2-12b-a2.5b"


def _qkv(key, s_q, s_k, h=8, hkv=1, d=32):
    kq, kk, kv = jax.random.split(key, 3)
    return (jax.random.normal(kq, (1, s_q, h, d), jnp.float32),
            jax.random.normal(kk, (1, s_k, hkv, d), jnp.float32),
            jax.random.normal(kv, (1, s_k, hkv, d), jnp.float32))


@pytest.mark.parametrize("window", [16, 24, 40])
@pytest.mark.parametrize("q_offset", [0, 32])
def test_windowed_flash_forward_matches_the_oracle(window, q_offset):
    """q_offset 32 is the second chunk of a prefill in chunks of 32."""
    q, k, v = _qkv(jax.random.PRNGKey(window + q_offset), 64 - q_offset, 64)
    got = flash_attention_fwd(q, k, v, causal=True, q_offset=q_offset, block_q=16,
                              block_k=16, interpret=True, window=window)
    want = naive_attention(q, k, v, causal=True, q_offset=q_offset, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)
    scan = systolic_attention(q, k, v, causal=True, q_offset=q_offset, block_q=16,
                              block_k=16, window=window)
    np.testing.assert_allclose(np.asarray(scan), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_a_window_as_long_as_the_sequence_is_plain_causal():
    q, k, v = _qkv(jax.random.PRNGKey(5), 48, 48)
    kw = dict(causal=True, block_q=16, block_k=16, interpret=True)
    windowed = flash_attention_fwd(q, k, v, window=48, **kw)
    causal = flash_attention_fwd(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(windowed), np.asarray(causal), atol=1e-6)


def test_the_windowed_grid_iterates_only_the_band():
    # Mellum's window at 8192 tokens in blocks of 128: 10 of 64 KV steps.
    assert window_kv_steps(1024, 128, 128, 64) == 10
    assert window_kv_steps(40, 16, 16, 4) == 4  # never more than the blocks


def test_a_windowed_flash_call_is_not_differentiated():
    q, k, v = _qkv(jax.random.PRNGKey(6), 32, 32)

    def loss(q):
        return flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                               interpret=True, window=16).sum()

    with pytest.raises(NotImplementedError):
        jax.grad(loss)(q)


# The unwindowed forward's kernel, with lane-broadcast row statistics and
# bf16 MXU operands: sha256 (first 16 hex digits) of its jaxpr (the kernel
# body, name and tag) with the grid and every block's index map.  Yi-9B's
# prefill at 4096 (GQA 32:4), OLMo-1B's training forward with LSE (batch
# 12 x 2048, 16 heads), and a prefill at 1024.  A change to the windowed
# path leaves these as they are; a change to the kernel body repins them.
UNWINDOWED = {
    (1, 4096, 32, 4, False): "48c10c63257f16e6",
    (12, 2048, 16, 16, True): "fc4a1c40dc5d78e8",
    (1, 1024, 32, 4, False): "a5a59931414fecbb",
}


@pytest.mark.parametrize("shape", sorted(UNWINDOWED))
def test_an_unwindowed_call_keeps_the_kernel_it_had(shape):
    """Without a window the forward keeps its grid, index maps, body and
    ``flash_fwd`` tag, so the cells that run it compile the same kernel."""
    b, s, h, hkv, lse = shape
    q = jax.ShapeDtypeStruct((b, s, h, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((b, s, hkv, 128), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda q, k, v: flash_attention_fwd(q, k, v, causal=True, return_lse=lse))(q, kv, kv)
    parts = [str(jaxpr)]
    for eqn in jaxpr.jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grid = eqn.params["grid_mapping"]
            parts += [str(grid.grid)] + [str(m.index_map_jaxpr) for m in grid.block_mappings]
    assert len(parts) > 1
    assert hashlib.sha256("\n".join(parts).encode()).hexdigest()[:16] == UNWINDOWED[shape]


def _moe_cfg(e=8, k=2, d=16, ff=32):
    return ModelConfig(
        name="m", family="moe", num_layers=1, d_model=d, num_heads=2, num_kv_heads=2,
        d_ff=ff, vocab_size=64, dtype="float32", remat=False,
        moe=MoEConfig(num_experts=e, top_k=k, d_ff_expert=ff),
    )


def test_grouped_dropless_experts_match_the_dense_topk_mix():
    cfg = _moe_cfg()
    p = moe_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    # Skew the routing: inputs share an offset that expert 3's router
    # column reads, so expert 3 is in nearly every row's top 2.
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, cfg.d_model)) + 1.0
    p["router"] = p["router"].at[:, 3].add(0.5)
    out = moe_forward(x, p, cfg, dropless=True)

    xf = x.reshape(-1, cfg.d_model)
    probs = jax.nn.softmax(xf @ p["router"], axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg.moe.top_k)
    assert np.mean(np.any(np.asarray(top_e) == 3, -1)) > 0.9
    w = jnp.zeros_like(probs).at[jnp.arange(xf.shape[0])[:, None], top_e].set(
        top_p / top_p.sum(-1, keepdims=True))
    h = jax.nn.silu(jnp.einsum("td,edf->tef", xf, p["gate"]))
    h = h * jnp.einsum("td,edf->tef", xf, p["up"])
    want = jnp.einsum("tef,efd,te->td", h, p["down"], w)
    np.testing.assert_allclose(np.asarray(out.reshape(-1, 16)), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_yarn_frequencies_match_a_float64_table():
    """transformers' YaRN at Mellum's full-layer values, written out."""
    cfg = get_config(ARCH)
    y, dim, base = cfg.rope_yarn, cfg.resolved_head_dim, cfg.rope_theta
    assert (y.factor, y.original_max_position_embeddings, y.beta_fast, y.beta_slow) == (
        16.0, 8192, 32.0, 1.0)

    def corr(rot):
        return dim * math.log(8192 / (rot * 2 * math.pi)) / (2 * math.log(base))

    low, high = max(math.floor(corr(32.0)), 0), min(math.ceil(corr(1.0)), dim - 1)
    pos = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    want = (1 / (16.0 * pos)) * ramp + (1 / pos) * (1 - ramp)
    got = yarn_frequencies(dim, base, y)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    assert (low, high) == (18, 35)
    assert got[0] == 1.0 and got[-1] == pytest.approx(1 / (16.0 * pos[-1]))


def test_a_decode_past_the_window_ignores_older_keys():
    cfg = get_smoke_config(ARCH).layer_config("sliding")
    w, pos = cfg.sliding_window, 20
    key = jax.random.PRNGKey(7)
    p = init_params(dataclasses.replace(cfg, num_layers=1), key)
    attn = jax.tree.map(lambda a: a[0], p["layers"]["attn"])
    cache = init_kv_cache(cfg, 1, 32, jnp.float32)
    k0 = jax.random.normal(key, cache.k.shape)
    cache = cache._replace(k=k0, v=jax.random.normal(jax.random.PRNGKey(8), cache.v.shape),
                           lengths=jnp.array([pos], jnp.int32))
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 1, cfg.d_model))
    at = jnp.array([[pos]], jnp.int32)
    def decode(cache):  # decode is cached attention at the cache's length
        return cached_attention(x, attn, cfg, cache, at, cache.lengths)[0]

    base = decode(cache)
    old = pos - w  # the newest key the window leaves out
    moved = decode(cache._replace(k=k0.at[0, old].add(5.0)))
    np.testing.assert_array_equal(np.asarray(base), np.asarray(moved))
    seen = decode(cache._replace(k=k0.at[0, old + 1].add(5.0)))
    assert not np.allclose(np.asarray(base), np.asarray(seen))


def test_smoke_config_keeps_the_published_form():
    full, smoke = get_config(ARCH), get_smoke_config(ARCH)
    assert full.param_count() == pytest.approx(12.15e9, rel=1e-3)
    assert full.active_param_count() == pytest.approx(2.43e9, rel=5e-3)
    assert smoke.layer_pattern == full.layer_pattern
    assert smoke.num_layers % len(smoke.layer_pattern) == 0
    assert smoke.moe.num_experts >= 8 and smoke.moe.top_k >= 2
    assert isinstance(smoke.rope_yarn, YarnConfig) and smoke.sliding_window < 32


def test_smoke_decode_and_prefill_agree_with_the_forward():
    """Past the window, token by token and in one prefill, as the period
    scan runs them.  Forward routes with capacity, so its experts get a
    capacity no token fills."""
    cfg = get_smoke_config(ARCH)
    params = init_params(cfg, jax.random.PRNGKey(3))
    s = 28
    tokens = jax.random.randint(jax.random.PRNGKey(4), (1, s), 0, cfg.vocab_size)
    roomy = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=64.0))
    full = forward(params, roomy, tokens=tokens)
    cache = init_cache(cfg, 1, 32)
    outs = []
    for i in range(s):
        lg, cache = decode_step(params, cfg, tokens[:, i:i + 1], cache, jnp.int32(i))
        outs.append(lg[:, 0])
    np.testing.assert_allclose(np.asarray(jnp.stack(outs, 1)), np.asarray(full), atol=2e-4)
    pre, _ = prefill_step(params, cfg, tokens, init_cache(cfg, 1, 32),
                          jnp.array([s], jnp.int32), chunk_size=16)
    np.testing.assert_allclose(np.asarray(pre), np.asarray(full), atol=2e-4)
