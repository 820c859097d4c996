"""The Pallas kernels compile for a TPU v5e at olmo-1b attention width.

Interpret mode (tests/test_kernels.py) checks numerics but not what the
chip's compiler accepts: block tiling, VMEM use, partitioning.  These tests
compile for a described v5e:2x2 topology, which needs the TPU compiler but
no chip, at q/k/v ``[1, 2048, 16, 128]`` bf16 (and fp32 for one forward),
causal.  Nothing runs.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.dist.collectives import map_heads, mesh_context
from repro.kernels.flash_attention.kernel import LANES, flash_attention_fwd
from repro.kernels.flash_attention.kernel_bwd import flash_attention_bwd
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.pwl_exp2.kernel import pwl_exp2_pallas

B, S, H, D = 1, 2048, 16, 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args, mesh=None):
    with mesh_context(mesh):
        text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text  # the Mosaic kernel is in the program
    return text


def _qkv(sharding, sq=S, sk=S):
    q = jax.ShapeDtypeStruct((B, sq, H, D), jnp.bfloat16, sharding=sharding)
    kv = jax.ShapeDtypeStruct((B, sk, H, D), jnp.bfloat16, sharding=sharding)
    return q, kv, kv


# Each case: (name, build(sharding) -> (fn, args)).
CASES = {
    "fwd": lambda sh: (
        lambda q, k, v: flash_attention_fwd(q, k, v, causal=True), _qkv(sh)),
    "fwd_lse": lambda sh: (
        lambda q, k, v: flash_attention_fwd(q, k, v, causal=True, return_lse=True),
        _qkv(sh)),
    # fp32 inputs: both products stay fp32 HIGHEST dots.
    "fwd_f32": lambda sh: (
        lambda q, k, v: flash_attention_fwd(q, k, v, causal=True),
        [x.update(dtype=jnp.float32) for x in _qkv(sh)]),
    "fwd_pwl": lambda sh: (
        lambda q, k, v: flash_attention_fwd(q, k, v, causal=True, exp2_impl="pwl"),
        _qkv(sh)),
    # Chunked prefill: the second 512-token chunk against 1024 cached keys.
    "fwd_q_offset": lambda sh: (
        lambda q, k, v: flash_attention_fwd(q, k, v, causal=True, q_offset=512),
        _qkv(sh, sq=512, sk=1024)),
    # dq grid and dkv grid, with the lane-broadcast LSE the forward stores.
    "bwd_dq_dkv": lambda sh: (
        lambda q, k, v, o, lse, do: flash_attention_bwd(q, k, v, o, lse, do, causal=True),
        (*_qkv(sh), _qkv(sh)[0],
         jax.ShapeDtypeStruct((B * H, S, LANES), jnp.float32, sharding=sh),
         _qkv(sh)[0])),
    # The training path: custom_vjp forward with LSE, then both grids.
    "train_grad": lambda sh: (
        jax.grad(lambda q, k, v: flash_attention(
            q, k, v, True, None, 0, 128, 128, "exact", 8, "pallas"
        ).astype(jnp.float32).sum(), argnums=(0, 1, 2)),
        _qkv(sh)),
    "pwl_exp2": lambda sh: (
        pwl_exp2_pallas,
        (jax.ShapeDtypeStruct((1024, 1024), jnp.float32, sharding=sh),)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, no_compile_cache):
    fn, args = CASES[case](one_chip)
    _compile(fn, *args)


# The kernel's tag in a custom call's frontend attributes; the compiler
# prints the metadata over several lines.
TAG = re.compile(r'kernel_metadata=\{\s*"kernel"\s*:\s*"(\w+)"\s*\}')


# The shapes of a custom call's first two operands, as its layout
# constraints print them.
FIRST_TWO_OPERANDS = re.compile(
    r"operand_layout_constraints=\{(\w+\[[\d,]*\])\{[^}]*\}, (\w+\[[\d,]*\])")


def _mosaic_calls(one_chip):
    """The training gradient's Mosaic call lines, one instruction each once
    the tags are on one line each; the tuple elements of a call carry its
    attributes too."""
    fn, args = CASES["train_grad"](one_chip)
    text = _compile(fn, *args)
    lines = TAG.sub(lambda m: f'kernel_metadata={{"kernel":"{m[1]}"}}', text).splitlines()
    return [line for line in lines if 'custom_call_target="tpu_custom_call"' in line]


def test_training_gradient_carries_the_kernel_tags(one_chip, no_compile_cache):
    """Each Mosaic call of the training gradient names its kernel, so a
    device trace can tell them apart without guessing from shapes."""
    tags = [TAG.search(line)[1] for line in _mosaic_calls(one_chip)]
    assert sorted(tags) == ["flash_dkv", "flash_dq", "flash_fwd"]


def test_flash_calls_take_head_major_q_and_k_first(one_chip, no_compile_cache):
    """Every flash call of the training gradient takes q and k head-major,
    ``[heads x batch, seq, head_dim]``, as its first two operands: a device
    trace reads each call's shapes from them."""
    head_major = f"bf16[{H * B},{S},{D}]"
    for line in _mosaic_calls(one_chip):
        assert FIRST_TWO_OPERANDS.search(line).groups() == (head_major, head_major), line


def test_kernel_under_mesh_compiles_for_four_chips(topo, no_compile_cache):
    """Under a 1x4 (data x model) mesh the kernel runs per head shard inside
    shard_map (``map_heads``); GSPMD alone cannot partition it."""
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    sh = NamedSharding(mesh, P(None, None, "model", None))
    text = _compile(
        lambda q, k, v: map_heads(
            lambda q, k, v: flash_attention_fwd(q, k, v, causal=True), q, k, v),
        *_qkv(sh), mesh=mesh,
    )
    assert "all-gather" not in text  # each chip attends its own heads


def test_windowed_forward_compiles_at_mellum_width(one_chip, no_compile_cache):
    """Mellum2's sliding layers at a prefill of 8192: GQA 32:4 of 128, a
    window of 1024 keys; the call carries its own tag."""
    q = jax.ShapeDtypeStruct((1, 8192, 32, D), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8192, 4, D), jnp.bfloat16, sharding=one_chip)
    text = _compile(lambda q, k, v: flash_attention_fwd(q, k, v, causal=True, window=1024),
                    q, kv, kv)
    lines = TAG.sub(lambda m: f'kernel_metadata={{"kernel":"{m[1]}"}}', text).splitlines()
    tags = {TAG.search(x)[1] for x in lines if 'custom_call_target="tpu_custom_call"' in x}
    assert tags == {"flash_fwd_window"}


def test_grouped_experts_lower_to_the_native_ragged_dot(one_chip, no_compile_cache):
    """The dropless experts' grouped matmul at Mellum2's widths (64 experts
    of 2304 x 896) is the chip's ragged-dot kernel, not a dense fallback."""
    rows = jax.ShapeDtypeStruct((4096, 2304), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((64, 2304, 896), jnp.bfloat16, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((64,), jnp.int32, sharding=one_chip)
    text = _compile(jax.lax.ragged_dot, rows, w, sizes)
    assert "ragged-dot" in text and " dot(" not in text
