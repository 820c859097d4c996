"""Per-architecture smoke tests (reduced configs): one forward + one train
step on CPU, shape and finiteness assertions; decode consistency checks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.models import decode_step, forward, init_cache, init_params, lm_loss
from repro.optim import AdamW


def _smoke_batch(cfg, key, b=2, s=32):
    if cfg.embedding_inputs:
        batch = {
            "embeds": jax.random.normal(key, (b, s, cfg.d_model), jnp.float32),
            "labels": jax.random.randint(key, (b, s), 0, cfg.vocab_size),
        }
        if cfg.mrope_sections is not None:
            batch["positions"] = jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32)[None, :, None], (b, s, 3)
            )
        return batch
    return {
        "tokens": jax.random.randint(key, (b, s), 0, cfg.vocab_size),
        "labels": jax.random.randint(key, (b, s), 0, cfg.vocab_size),
    }


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_shapes_and_finite(arch):
    cfg = get_smoke_config(arch)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    batch = _smoke_batch(cfg, key)
    logits = forward(
        params, cfg,
        tokens=batch.get("tokens"), embeds=batch.get("embeds"),
        positions=batch.get("positions"),
    )
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step_reduces_loss(arch):
    """A few steps on a fixed batch must reduce the loss (overfit check)."""
    cfg = get_smoke_config(arch)
    key = jax.random.PRNGKey(1)
    params = init_params(cfg, key)
    batch = _smoke_batch(cfg, key)
    opt = AdamW(lr=1e-3, weight_decay=0.0)
    state = opt.init(params)

    @jax.jit
    def step(params, state):
        loss, g = jax.value_and_grad(lambda p: lm_loss(p, cfg, batch))(params)
        params, state = opt.update(g, state, params)
        return params, state, loss

    losses = []
    for _ in range(5):
        params, state, loss = step(params, state)
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


@pytest.mark.parametrize(
    "arch", [a for a in ARCH_IDS if get_config(a).family != "encoder"]
)
def test_decode_runs_and_is_finite(arch):
    cfg = get_smoke_config(arch)
    key = jax.random.PRNGKey(2)
    params = init_params(cfg, key)
    cache = init_cache(cfg, 2, 16)
    tok = jnp.zeros((2, 1), jnp.int32)
    for i in range(3):
        logits, cache = decode_step(params, cfg, tok, cache, jnp.asarray(i, jnp.int32))
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())


@pytest.mark.parametrize("arch", ["yi-9b", "olmo-1b", "qwen2.5-32b"])
def test_decode_matches_forward(arch):
    """Token-by-token decode logits == full forward logits (same positions).

    Dense transformer KV-cache correctness: run S tokens through decode and
    compare each step's logits against the teacher-forced forward pass.
    """
    cfg = get_smoke_config(arch)
    key = jax.random.PRNGKey(3)
    params = init_params(cfg, key)
    s = 8
    tokens = jax.random.randint(key, (1, s), 0, cfg.vocab_size)
    full = forward(params, cfg, tokens=tokens)  # [1, S, V]

    cache = init_cache(cfg, 1, s)
    outs = []
    for i in range(s):
        logits, cache = decode_step(
            params, cfg, tokens[:, i : i + 1], cache, jnp.asarray(i, jnp.int32)
        )
        outs.append(logits[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(dec, np.float32), np.asarray(full, np.float32), atol=2e-3
    )


def test_zamba2_decode_matches_forward():
    """Hybrid (Mamba2 + shared attention) cache correctness, incl. the
    shared-attention KV slot scatter."""
    cfg = get_smoke_config("zamba2-1.2b")
    key = jax.random.PRNGKey(4)
    params = init_params(cfg, key)
    s = 8
    tokens = jax.random.randint(key, (1, s), 0, cfg.vocab_size)
    full = forward(params, cfg, tokens=tokens)
    cache = init_cache(cfg, 1, s)
    outs = []
    for i in range(s):
        logits, cache = decode_step(
            params, cfg, tokens[:, i : i + 1], cache, jnp.asarray(i, jnp.int32)
        )
        outs.append(logits[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(dec, np.float32), np.asarray(full, np.float32), atol=5e-3
    )


def test_xlstm_decode_matches_forward():
    """Recurrent-state decode == scan forward for the attention-free arch."""
    cfg = get_smoke_config("xlstm-125m")
    key = jax.random.PRNGKey(5)
    params = init_params(cfg, key)
    s = 8
    tokens = jax.random.randint(key, (1, s), 0, cfg.vocab_size)
    full = forward(params, cfg, tokens=tokens)
    cache = init_cache(cfg, 1, s)
    outs = []
    for i in range(s):
        logits, cache = decode_step(
            params, cfg, tokens[:, i : i + 1], cache, jnp.asarray(i, jnp.int32)
        )
        outs.append(logits[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(dec, np.float32), np.asarray(full, np.float32), atol=5e-3
    )


def test_pwl_mode_end_to_end():
    """The paper-faithful numerics mode runs through a whole model."""
    import dataclasses

    cfg = dataclasses.replace(get_smoke_config("yi-9b"), exp2_impl="pwl")
    key = jax.random.PRNGKey(6)
    params = init_params(cfg, key)
    batch = _smoke_batch(cfg, key)
    loss = lm_loss(params, cfg, batch)
    assert bool(jnp.isfinite(loss))


@pytest.mark.parametrize("impl", ["naive", "jnp"])
def test_unknown_attention_impl_raises(impl):
    """attention_impl is None, "pallas" or "systolic"; any other name fails
    where attention reads it instead of falling through to the scan."""
    import dataclasses

    cfg = dataclasses.replace(get_smoke_config("olmo-1b"), attention_impl=impl)
    params = init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="attention_impl"):
        forward(params, cfg, tokens=jnp.zeros((1, 8), jnp.int32))


def test_full_configs_match_assignment():
    """The full configs carry the exact assigned hyperparameters."""
    expect = {
        "qwen3-moe-235b-a22b": (94, 4096, 64, 4, 1536, 151936),
        "arctic-480b": (35, 7168, 56, 8, 4864, 32000),
        "hubert-xlarge": (48, 1280, 16, 16, 5120, 504),
        "olmo-1b": (16, 2048, 16, 16, 8192, 50304),
        "nemotron-4-15b": (32, 6144, 48, 8, 24576, 256000),
        "qwen2.5-32b": (64, 5120, 40, 8, 27648, 152064),
        "yi-9b": (48, 4096, 32, 4, 11008, 64000),
        "qwen2-vl-7b": (28, 3584, 28, 4, 18944, 152064),
        "zamba2-1.2b": (38, 2048, 32, 32, 8192, 32000),
        "xlstm-125m": (12, 768, 4, 4, 0, 50304),
    }
    for arch, (L, d, h, kv, ff, v) in expect.items():
        cfg = get_config(arch)
        assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                cfg.d_ff, cfg.vocab_size) == (L, d, h, kv, ff, v), arch
    assert get_config("qwen3-moe-235b-a22b").moe.num_experts == 128
    assert get_config("qwen3-moe-235b-a22b").moe.top_k == 8
    assert get_config("arctic-480b").moe.top_k == 2
    assert get_config("arctic-480b").moe.dense_residual
    assert get_config("zamba2-1.2b").ssm.state_dim == 64
