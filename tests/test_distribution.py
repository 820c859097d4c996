"""Distribution tests on a CPU debug mesh: sharding rules, shard_map MoE,
sequence parallelism, pipeline parallelism, compressed gradient reduction.

conftest.py sets xla_force_host_platform_device_count=8 for this module
only via an env marker — see conftest.
"""

import os

import pytest

# These tests need >1 CPU device; they are collected only when the test
# process was started with the device-count flag (tests/conftest.py spawns
# nothing — run `pytest tests/test_distribution.py` standalone or rely on
# the session flag below).
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    )

import dataclasses  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import SHAPES, get_config, get_smoke_config  # noqa: E402
from repro.configs.registry import ARCH_IDS, runnable_cells, skipped_cells  # noqa: E402
from repro.dist.pipeline import pipelined_apply  # noqa: E402
from repro.dist.sharding import param_pspec  # noqa: E402
from repro.launch.cells import input_specs, lower_cell  # noqa: E402
from repro.launch.mesh import make_debug_mesh  # noqa: E402
from repro.models import forward, init_params, param_shapes  # noqa: E402

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 host-platform devices"
)


def test_cell_registry_counts():
    cells = runnable_cells()
    skips = skipped_cells()
    assert len(cells) + len(skips) == 40  # 10 archs x 4 shapes
    assert len(cells) == 31
    # hubert skips all decode shapes; full-attention archs skip long_500k
    assert ("hubert-xlarge", "decode_32k") in [(a, s) for a, s, _ in skips]
    assert ("yi-9b", "long_500k") in [(a, s) for a, s, _ in skips]
    assert ("zamba2-1.2b", "long_500k") in cells
    assert ("xlstm-125m", "long_500k") in cells


def test_tp_divisibility_of_sharded_dims():
    """Every dim the rules shard by 'model' must divide 16 for all archs."""
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        shapes = param_shapes(cfg)
        flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
        for path, leaf in flat:
            pstr = "/".join(str(getattr(p, "key", p)) for p in path)
            spec = param_pspec(pstr, tuple(leaf.shape), cfg, 16, 16)
            for dim, ax in enumerate(spec):
                if ax is None:
                    continue
                axes = ax if isinstance(ax, tuple) else (ax,)
                div = {"model": 16, "data": 16, "pod": 2}
                total = int(np.prod([div[a] for a in axes]))
                assert leaf.shape[dim] % total == 0, (arch, pstr, dim, spec)


def test_sharded_forward_matches_unsharded():
    """yi-9b smoke forward: TP+DP+SP sharded == single-device result."""
    cfg = get_smoke_config("yi-9b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)
    ref = forward(params, cfg, tokens=toks)
    mesh = make_debug_mesh(2, 4)
    with jax.set_mesh(mesh):
        out = jax.jit(lambda p, t: forward(p, cfg, tokens=t))(params, toks)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-3
    )


@pytest.mark.parametrize("heads,kv_heads", [(8, 4), (8, 2)])
def test_map_heads_matches_unsharded(heads, kv_heads):
    """Attention run per device shard under a 2x4 mesh == whole-array
    attention.  (8, 2): "model" does not divide the KV heads, which then
    stay whole on every device."""
    from repro.core.attention import naive_attention
    from repro.dist.collectives import map_heads

    def attn(q, k, v):
        return naive_attention(q, k, v, causal=True)

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (4, 32, heads, 16))
    k = jax.random.normal(ks[1], (4, 32, kv_heads, 16))
    v = jax.random.normal(ks[2], (4, 32, kv_heads, 16))
    with jax.set_mesh(make_debug_mesh(2, 4)):
        out = jax.jit(lambda q, k, v: map_heads(attn, q, k, v))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(attn(q, k, v)), atol=1e-5)


def test_params_created_sharded_equal_unsharded_init():
    """``init_params`` jitted with ``out_shardings`` (how the launchers create
    a model too large for one device) gives the same values as on one
    device, laid out per the TP rules."""
    from repro.dist.sharding import param_shardings

    cfg = get_smoke_config("yi-9b")
    mesh = make_debug_mesh(2, 4)
    sh = param_shardings(param_shapes(cfg), cfg, mesh)
    sharded = jax.jit(init_params, static_argnums=0, out_shardings=sh)(
        cfg, jax.random.PRNGKey(0))
    ref = jax.jit(init_params, static_argnums=0)(cfg, jax.random.PRNGKey(0))
    for a, b, s in zip(jax.tree.leaves(sharded), jax.tree.leaves(ref),
                       jax.tree.leaves(sh)):
        assert a.sharding == s
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_moe_shard_map_matches_local_no_drop():
    cfg = get_smoke_config("qwen3-moe-235b-a22b")
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=16.0)
    )
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)
    ref = forward(params, cfg, tokens=toks)
    mesh = make_debug_mesh(2, 4)
    with jax.set_mesh(mesh):
        out = jax.jit(lambda p, t: forward(p, cfg, tokens=t))(params, toks)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-3
    )


def test_moe_prefill_under_a_mesh_keeps_capacity_routing():
    """Serving's prefill routes dropless only where the experts run
    grouped; under EP each rank's expert buffer keeps the capacity rows
    ([E_loc, T_loc*k*1.25/E, d]) and is never padded to its token pool."""
    from repro.models import init_cache, prefill_step

    cfg = get_smoke_config("qwen3-moe-235b-a22b")
    b, s = 4, 16  # no other array of the module is [E_loc, T_loc, d]
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.zeros((b, s), jnp.int32)
    cache = init_cache(cfg, b, s)
    mesh = make_debug_mesh(2, 4)
    with jax.set_mesh(mesh):
        text = jax.jit(lambda p, t, c: prefill_step(p, cfg, t, c, jnp.full((b,), s))).lower(
            params, toks, cache).as_text()
    e_loc, t_loc, d = cfg.moe.num_experts // 4, b // 2 * s, cfg.d_model
    capacity = int(t_loc * cfg.moe.top_k * cfg.moe.capacity_factor / cfg.moe.num_experts)
    assert capacity < t_loc
    assert f"tensor<{e_loc}x{capacity}x{d}x" in text
    assert f"tensor<{e_loc}x{t_loc}x{d}x" not in text


def test_debug_mesh_lower_and_compile_cells():
    """Miniature dry-run: smoke configs x {train, decode} compile on a
    2x4 debug mesh with the same lowering code path as production."""
    import repro.launch.cells as cells_mod

    mesh = make_debug_mesh(2, 4)
    for arch in ("olmo-1b", "zamba2-1.2b"):
        smoke = get_smoke_config(arch)
        shape = dataclasses.replace(SHAPES["train_4k"], seq_len=32, global_batch=4)
        cfg = dataclasses.replace(smoke)
        cell = lower_cell(
            arch, "train_4k", mesh,
            cfg_override=dataclasses.replace(cfg, remat=True),
        )
        # NOTE lower_cell reads SHAPES[...]: full shapes are too big for 8
        # CPU devices, so just check it LOWERS (no allocation happens).
        assert cell.lowered is not None


def test_pipeline_parallel_matches_sequential():
    """GPipe over a 4-stage pipeline == sequential layer application."""
    mesh = jax.make_mesh(
        (4,), ("pod",), axis_types=(jax.sharding.AxisType.Auto,)
    )
    k = jax.random.PRNGKey(0)
    stages, width = 4, 16
    ws = jax.random.normal(k, (stages, width, width)) * 0.3

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    x = jax.random.normal(jax.random.fold_in(k, 1), (8, width))
    seq = x
    for i in range(stages):
        seq = stage_fn(ws[i], seq)
    with jax.set_mesh(mesh):
        out = jax.jit(
            lambda ws, x: pipelined_apply(
                stage_fn, ws, x, num_stages=stages, num_microbatches=4
            )
        )(ws, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(seq), atol=1e-5)


def test_input_specs_cover_all_cells():
    for arch, shape_name in runnable_cells():
        cfg = get_config(arch)
        specs = input_specs(cfg, SHAPES[shape_name])
        assert specs, (arch, shape_name)
        if SHAPES[shape_name].kind == "decode":
            assert "cache" in specs
        else:
            leaves = jax.tree.leaves(specs["batch"])
            assert all(hasattr(l, "shape") for l in leaves)
