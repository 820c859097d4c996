"""Substrate tests: optimizers, schedules, data pipeline determinism,
checkpoint atomicity/elasticity, fault tolerance, gradient compression."""

import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint import CheckpointManager
from repro.configs import SHAPES, get_smoke_config
from repro.data import DataConfig, PrefetchIterator, SyntheticLM, make_source
from repro.dist.fault import (
    PreemptionHandler,
    StepWatchdog,
    StragglerDetected,
    run_with_restarts,
)
from repro.optim import (
    Adafactor,
    AdamW,
    compress_with_feedback,
    cosine_with_warmup,
    dequantize_int8,
    init_residual,
    quantize_int8,
)


# -- optimizers ----------------------------------------------------------------

def _quadratic_problem():
    target = jnp.asarray([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros((3,))}

    def loss(p):
        return jnp.sum(jnp.square(p["w"] - target))

    return params, loss, target


@pytest.mark.parametrize(
    "opt,atol",
    [
        (AdamW(lr=0.1, weight_decay=0.0), 0.1),
        # Adafactor's RMS update clipping makes it hover within ~lr/2 of the
        # optimum on this toy problem without an lr decay — test the basin.
        (Adafactor(lr=0.5), 0.3),
    ],
)
def test_optimizer_converges(opt, atol):
    params, loss, target = _quadratic_problem()
    state = opt.init(params)
    start = float(loss(params))
    for _ in range(300):
        g = jax.grad(loss)(params)
        params, state = opt.update(g, state, params)
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(target), atol=atol)
    assert float(loss(params)) < 0.05 * start


def test_adafactor_memory_is_factored():
    p = {"big": jnp.zeros((64, 128))}
    st_ = Adafactor().init(p)
    r, c = st_.stats["big"]["r"], st_.stats["big"]["c"]
    assert r.shape == (64,) and c.shape == (128,)  # O(n+m), not O(n*m)


def test_cosine_schedule_shape():
    sched = cosine_with_warmup(1e-3, 10, 100)
    assert float(sched(jnp.asarray(0))) == 0.0
    assert float(sched(jnp.asarray(10))) == pytest.approx(1e-3)
    assert float(sched(jnp.asarray(100))) == pytest.approx(1e-4, rel=0.01)


# -- data pipeline ---------------------------------------------------------------

def test_data_deterministic_and_host_invariant():
    cfg = get_smoke_config("olmo-1b")
    shape = SHAPES["train_4k"]
    import dataclasses

    shape = dataclasses.replace(shape, seq_len=16, global_batch=8)
    one_host = SyntheticLM(cfg, shape, DataConfig(seed=7, num_hosts=1, host_id=0))
    full = one_host.batch(3)
    # Two-host layout must produce exactly the same global batch, split.
    h0 = SyntheticLM(cfg, shape, DataConfig(seed=7, num_hosts=2, host_id=0)).batch(3)
    h1 = SyntheticLM(cfg, shape, DataConfig(seed=7, num_hosts=2, host_id=1)).batch(3)
    np.testing.assert_array_equal(
        np.concatenate([h0["tokens"], h1["tokens"]]), full["tokens"]
    )
    # Restart reproducibility.
    again = SyntheticLM(cfg, shape, DataConfig(seed=7)).batch(3)
    np.testing.assert_array_equal(again["tokens"], full["tokens"])


def test_labels_are_shifted_tokens():
    cfg = get_smoke_config("olmo-1b")
    import dataclasses

    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=16, global_batch=2)
    b = SyntheticLM(cfg, shape, DataConfig()).batch(0)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


def test_embeds_source_for_frontend_stubs():
    cfg = get_smoke_config("qwen2-vl-7b")
    import dataclasses

    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=8, global_batch=2)
    b = make_source(cfg, shape, DataConfig()).batch(0)
    assert b["embeds"].shape == (2, 8, cfg.d_model)
    assert b["positions"].shape == (2, 8, 3)


def test_prefetch_iterator():
    cfg = get_smoke_config("olmo-1b")
    import dataclasses

    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=8, global_batch=2)
    src = SyntheticLM(cfg, shape, DataConfig(seed=1))
    it = PrefetchIterator(src, start_step=0, prefetch=2)
    try:
        b0, b1 = next(it), next(it)
        np.testing.assert_array_equal(b0["tokens"], src.batch(0)["tokens"])
        np.testing.assert_array_equal(b1["tokens"], src.batch(1)["tokens"])
    finally:
        it.close()


# -- checkpointing ----------------------------------------------------------------

def _tree():
    return {
        "layers": {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4)},
        "step": jnp.asarray(7, jnp.int32),
    }


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    tree = _tree()
    mgr.save(5, tree)
    assert mgr.latest_step() == 5
    out = mgr.restore(5, jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree))
    np.testing.assert_array_equal(np.asarray(out["layers"]["w"]), np.asarray(tree["layers"]["w"]))
    assert int(out["step"]) == 7


def test_checkpoint_atomic_no_partial_on_crash(tmp_path):
    """A .tmp directory must never be visible as a restorable step."""
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _tree())
    os.makedirs(tmp_path / "step_0000000002.tmp")  # simulated crash mid-save
    assert mgr.all_steps() == [1]


def test_checkpoint_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, _tree())
    assert mgr.all_steps() == [3, 4]


def test_checkpoint_async(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save_async(9, _tree())
    mgr.wait()
    assert mgr.latest_step() == 9


def test_checkpoint_mismatch_detected(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _tree())
    bad_target = {"other": jax.ShapeDtypeStruct((3, 4), jnp.float32)}
    with pytest.raises(ValueError):
        mgr.restore(1, bad_target)


def test_checkpoint_elastic_reshard(tmp_path):
    """Restore with explicit shardings onto a (1-device) mesh — the elastic
    resume path (same API re-shards onto any mesh shape)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = jax.make_mesh((1,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
    mgr = CheckpointManager(str(tmp_path))
    tree = _tree()
    mgr.save(2, tree)
    target = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    shardings = jax.tree.map(lambda _: NamedSharding(mesh, P()), target)
    out = mgr.restore(2, target, shardings=shardings)
    assert out["layers"]["w"].sharding == NamedSharding(mesh, P())


# -- fault tolerance ----------------------------------------------------------------

def test_watchdog_detects_straggler():
    wd = StepWatchdog(timeout_factor=3.0, warmup_steps=2)
    for _ in range(5):
        wd.durations.append(0.1)
    with pytest.raises(StragglerDetected):
        wd.check(1.0)


def test_watchdog_tolerates_normal_jitter():
    wd = StepWatchdog(timeout_factor=3.0, warmup_steps=2)
    for _ in range(5):
        wd.durations.append(0.1)
    wd.check(0.25)  # 2.5x median: fine


def test_preemption_flag():
    h = PreemptionHandler(install=False)
    assert not h.requested
    h.trigger()
    assert h.requested


def test_run_with_restarts_recovers_from_crash(tmp_path):
    """Simulated node failure mid-training: restart resumes from the latest
    checkpoint and completes."""
    mgr = CheckpointManager(str(tmp_path))
    crashed = {"yet": False}

    def make_state():
        step = mgr.latest_step()
        if step is None:
            return {"x": jnp.zeros(()), "step": 0}
        t = mgr.restore(step, {"x": jax.ShapeDtypeStruct((), jnp.float32)})
        return {"x": t["x"], "step": step}

    def run_steps(state, n):
        x, step = state["x"], state["step"]
        while step < n:
            x = x + 1.0
            step += 1
            mgr.save(step, {"x": x})
            if step == 4 and not crashed["yet"]:
                crashed["yet"] = True
                raise RuntimeError("injected node failure")
        return {"x": x, "step": step}

    state, restarts = run_with_restarts(make_state, run_steps, steps_per_attempt=8)
    assert restarts == 1
    assert state["step"] == 8 and float(state["x"]) == 8.0


# -- gradient compression ---------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(st.integers(min_value=0, max_value=2**31 - 1))
def test_quantize_roundtrip_bounded_error(seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (64,)) * 3.0
    q, s = quantize_int8(x)
    err = jnp.abs(dequantize_int8(q, s) - x)
    assert float(err.max()) <= float(s) * 0.5 + 1e-7


def test_error_feedback_preserves_signal():
    """With error feedback, the *accumulated* compressed signal converges to
    the true accumulated gradient (bias-free compression)."""
    g = {"w": jnp.asarray([0.001, -0.02, 0.3])}
    residual = init_residual(g)
    total = jnp.zeros(3)
    for _ in range(100):
        q, s, residual = compress_with_feedback(g, residual)
        total = total + dequantize_int8(q["w"], s["w"])
    np.testing.assert_allclose(np.asarray(total / 100), np.asarray(g["w"]), rtol=0.02)


@pytest.mark.parametrize("env_set", [False, True])
def test_compile_cache_dir(env_set, tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the cache goes to
    one fixed directory at the root of the checkout."""
    from repro.launch import compile_cache

    assert (compile_cache.CHECKOUT_CACHE_DIR.parent / "chip_smoke.py").is_file()
    was = jax.config.jax_compilation_cache_dir
    was_regex = jax.config.jax_hlo_source_file_canonicalization_regex
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.enable_compile_cache()
        if env_set:
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == was  # set nothing
        else:
            assert got == str(compile_cache.CHECKOUT_CACHE_DIR)
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        jax.config.update("jax_hlo_source_file_canonicalization_regex", was_regex)


def test_compile_cache_key_has_no_checkout_path():
    """The Pallas kernel reaches the compiler as serialized Mosaic code with
    its source file in it.  After ``enable_compile_cache`` that file name is
    relative to ``src/``, so two checkouts at different paths share cache
    entries.  (Lowered for TPU through ``jax.export``; needs no chip.)"""
    import base64
    import re

    from repro.kernels.flash_attention.kernel import flash_attention_fwd
    from repro.launch import compile_cache

    def mosaic_body():
        q = jax.ShapeDtypeStruct((1, 128, 1, 128), jnp.bfloat16)
        f = jax.jit(lambda q: flash_attention_fwd(q, q, q))
        text = jax.export.export(f, platforms=["tpu"])(q).mlir_module()
        body = re.search(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)', text).group(1)
        return base64.b64decode(body)

    src = str(compile_cache.SRC_DIR).encode()
    was = jax.config.jax_hlo_source_file_canonicalization_regex
    was_dir = jax.config.jax_compilation_cache_dir
    try:
        assert src in mosaic_body()
        compile_cache.enable_compile_cache()
        body = mosaic_body()
        assert src not in body
        assert b"repro/kernels/flash_attention/kernel.py" in body
    finally:
        jax.config.update("jax_hlo_source_file_canonicalization_regex", was)
        jax.config.update("jax_compilation_cache_dir", was_dir)
