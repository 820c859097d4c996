"""Pallas kernel validation (interpret mode): shape/dtype sweeps vs ref.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.attention import systolic_attention
from repro.core.pwl_exp2 import pwl_exp2 as pwl_exp2_jnp
from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_reference
from repro.kernels.pwl_exp2.kernel import pwl_exp2_pallas


def _rand(shape, key, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32).astype(dtype)


SHAPE_SWEEP = [
    # (B, Sq, Sk, H, Hkv, d, causal)
    (1, 128, 128, 1, 1, 64, False),
    (2, 256, 256, 4, 2, 64, True),
    (1, 256, 512, 4, 1, 128, True),
    (1, 100, 200, 4, 4, 32, True),   # ragged
    (2, 64, 64, 8, 2, 16, False),
]


@pytest.mark.parametrize("case", SHAPE_SWEEP)
def test_flash_fwd_matches_ref(case):
    b, sq, sk, h, hkv, d, causal = case
    q = _rand((b, sq, h, d), 0)
    k = _rand((b, sk, hkv, d), 1)
    v = _rand((b, sk, hkv, d), 2)
    qo = sk - sq if causal else 0
    ref = attention_reference(q, k, v, causal=causal, q_offset=qo)
    out = flash_attention_fwd(
        q, k, v, causal=causal, q_offset=qo, block_q=64, block_k=64, interpret=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_fwd_dtypes(dtype):
    q = _rand((1, 128, 2, 64), 0, dtype)
    k = _rand((1, 128, 2, 64), 1, dtype)
    v = _rand((1, 128, 2, 64), 2, dtype)
    ref = attention_reference(q, k, v, causal=True)
    out = flash_attention_fwd(q, k, v, causal=True, interpret=True)
    assert out.dtype == dtype
    tol = 3e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol
    )


def test_flash_pwl_matches_table2_envelope():
    """Paper Table 2 distribution: N(0,1) + N(0,100)*Bernoulli(0.001)."""
    rng = np.random.default_rng(0)
    shape = (1, 512, 2, 128)

    def draw(s):
        x = rng.standard_normal(s) + rng.standard_normal(s) * 10.0 * (
            rng.random(s) < 0.001
        )
        return jnp.asarray(x, jnp.float32)

    q, k, v = draw(shape), draw(shape), draw(shape)
    ref = attention_reference(q, k, v)
    out = flash_attention_fwd(q, k, v, exp2_impl="pwl", interpret=True)
    mae = float(jnp.abs(out - ref).mean())
    assert mae < 2e-2  # Table 2 reports MAE 8e-3..3.4e-2 over 2k..16k


def test_flash_custom_vjp_matches_autodiff_of_ref():
    q = _rand((1, 128, 2, 32), 0)
    k = _rand((1, 128, 1, 32), 1)
    v = _rand((1, 128, 1, 32), 2)

    def f_kernel(q, k, v):
        return (systolic_attention(q, k, v, causal=True) * 0.1).sum()

    def f_ref(q, k, v):
        return (attention_reference(q, k, v, causal=True) * 0.1).sum()

    g1 = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


@pytest.mark.parametrize("shape", [(8,), (1000, 37), (3, 5, 7), (128, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pwl_exp2_kernel_sweep(shape, dtype):
    x = -jnp.abs(_rand(shape, 0)) * 8.0
    x = x.astype(dtype)
    out = pwl_exp2_pallas(x, interpret=True)
    ref = pwl_exp2_jnp(x)
    assert out.shape == ref.shape and out.dtype == ref.dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-3
    )


def test_pwl_exp2_kernel_segment_counts():
    x = -jnp.abs(_rand((256,), 1)) * 4.0
    for k in (4, 8, 16):
        out = pwl_exp2_pallas(x, num_segments=k, interpret=True)
        ref = pwl_exp2_jnp(x, num_segments=k)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


# -- Cross-check against jax.nn.dot_product_attention ----------------------
#
# ref.py shares code style (and potential blind spots) with the kernels; the
# XLA attention is an independent oracle.  Sequence lengths are deliberately
# not multiples of the 64-token blocks so the padded-tail masking is load-
# bearing in every case.


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_fwd_matches_jax_nn(causal, dtype):
    b, s, h, hkv, d = 2, 100, 4, 2, 32  # GQA, ragged vs block_q/block_k=64
    q = _rand((b, s, h, d), 0, dtype)
    k = _rand((b, s, hkv, d), 1, dtype)
    v = _rand((b, s, hkv, d), 2, dtype)
    out = flash_attention_fwd(
        q, k, v, causal=causal, block_q=64, block_k=64, interpret=True
    )
    ref = jax.nn.dot_product_attention(q, k, v, is_causal=causal)
    tol = 3e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol
    )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_bwd_matches_jax_nn_autodiff(causal):
    b, s, h, hkv, d = 1, 100, 2, 1, 32
    q = _rand((b, s, h, d), 0)
    k = _rand((b, s, hkv, d), 1)
    v = _rand((b, s, hkv, d), 2)
    do = _rand((b, s, h, d), 3)

    def f_kernel(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                            interpret=True)
        return (o * do).sum()

    def f_xla(q, k, v):
        return (jax.nn.dot_product_attention(q, k, v, is_causal=causal) * do).sum()

    g1 = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_xla, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=5e-5)


def test_flash_fwd_bf16_ragged_gqa_vs_ref():
    """bf16 + ragged Sq != Sk + causal offset in one case (the decode-cache
    prefill shape class the serving engine emits)."""
    q = _rand((1, 100, 4, 32), 0, jnp.bfloat16)
    k = _rand((1, 200, 2, 32), 1, jnp.bfloat16)
    v = _rand((1, 200, 2, 32), 2, jnp.bfloat16)
    out = flash_attention_fwd(
        q, k, v, causal=True, q_offset=100, block_q=64, block_k=64, interpret=True
    )
    ref = attention_reference(q, k, v, causal=True, q_offset=100)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-2
    )


# -- Pallas backward kernels (FlashAttention-2 dq / dkv) -------------------

from repro.kernels.flash_attention.kernel_bwd import flash_attention_bwd  # noqa: E402


@pytest.mark.parametrize("case", [
    (1, 128, 128, 2, 1, 32, True),
    (2, 256, 192, 4, 2, 64, False),
    (1, 100, 200, 4, 1, 32, True),   # ragged + GQA + causal offset
])
def test_pallas_bwd_matches_autodiff(case):
    b, sq, sk, h, hkv, d, causal = case
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = _rand((b, sq, h, d), 0)
    k = _rand((b, sk, hkv, d), 1)
    v = _rand((b, sk, hkv, d), 2)
    do = _rand((b, sq, h, d), 3)
    qo = sk - sq if causal else 0
    out, lse = flash_attention_fwd(
        q, k, v, causal=causal, q_offset=qo, block_q=64, block_k=64,
        interpret=True, return_lse=True,
    )
    dq, dk, dv = flash_attention_bwd(
        q, k, v, out, lse, do, causal=causal, q_offset=qo,
        block_q=64, block_k=64, interpret=True,
    )
    f = lambda q, k, v: (  # noqa: E731
        attention_reference(q, k, v, causal=causal, q_offset=qo) * do
    ).sum()
    rq, rk, rv = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(rq), atol=3e-5)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(rk), atol=3e-5)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(rv), atol=3e-5)


def test_pallas_custom_vjp_end_to_end():
    """flash_attention trains: full kernel fwd+bwd path."""
    q = _rand((1, 128, 2, 32), 0)
    k = _rand((1, 128, 1, 32), 1)
    v = _rand((1, 128, 1, 32), 2)

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                            interpret=True)
        return (o * o).sum()

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    def loss_ref(q, k, v):
        o = attention_reference(q, k, v, causal=True)
        return (o * o).sum()

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def test_pallas_path_without_interpret_raises_off_tpu():
    """Off TPU the compiled kernel path must fail loudly, never fall back
    to interpret mode or to the scan."""
    if jax.default_backend() == "tpu":
        pytest.skip("compiled kernels run on TPU")
    q = _rand((1, 128, 2, 32), 0)
    with pytest.raises(ValueError, match="interpret"):
        flash_attention(q, q, q, causal=True, block_q=64, block_k=64)


# -- Causal block skip -------------------------------------------------------
#
# A causal grid step whose (q block, KV block) pair lies wholly above the
# diagonal does no work and reads nothing new.  NaN planted in inputs that
# only such steps read shows it: a step that still ran would turn its exact
# zero contribution into NaN (0 * NaN), so the outputs that only dead steps
# touch stay finite, and equal the clean call's bit for bit.  Without the
# causal mask every step is live and the poison must reach them.

from repro.kernels.flash_attention.kernel import causal_grid_steps  # noqa: E402

SKIP_BLOCK = 32
SKIP_CASES = {
    # (B, Sq, Sk, H, Hkv, d, q_offset)
    "square": (1, 128, 128, 2, 2, 16, 0),
    "ragged_offset": (1, 72, 128, 2, 2, 16, 40),
    "padded_k": (1, 100, 100, 2, 2, 16, 0),
    "gqa": (1, 128, 128, 4, 1, 16, 0),
    "kv_past_queries": (1, 64, 128, 2, 2, 16, 0),
}


def _skip_inputs(case):
    b, sq, sk, h, hkv, d, _ = case
    return (_rand((b, sq, h, d), 0), _rand((b, sk, hkv, d), 1),
            _rand((b, sk, hkv, d), 2), _rand((b, sq, h, d), 3))


def _block_rows(blocks, size, n):
    """Row indices below ``n`` of the given blocks of ``size`` rows."""
    rows = np.concatenate([np.arange(i * size, (i + 1) * size) for i in blocks])
    return rows[rows < n]


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("name", sorted(SKIP_CASES))
@pytest.mark.parametrize("grid", ["fwd", "dq", "dkv"])
def test_causal_dead_blocks_are_skipped(grid, name, causal):
    case = SKIP_CASES[name]
    _, sq, sk, _, _, _, qo = case
    bq = bk = SKIP_BLOCK
    num_q, num_k = -(-sq // bq), -(-sk // bk)
    q, k, v, do = _skip_inputs(case)
    kw = dict(causal=causal, q_offset=qo, block_q=bq, block_k=bk, interpret=True)

    if grid in ("fwd", "dq"):
        # Poison the last KV block; check the q blocks whose causal rows
        # all end before it.
        poisoned = slice((num_k - 1) * bk, sk)
        blocks = [i for i in range(num_q) if (i * bq + qo + bq - 1) // bk < num_k - 1]
        rows = _block_rows(blocks, bq, sq)
    else:
        # Poison the first q block; check the KV blocks past its last row.
        poisoned = slice(0, bq)
        blocks = [j for j in range(num_k) if j * bk > qo + bq - 1]
        rows = _block_rows(blocks, bk, sk)
    assert rows.size, "the case must have outputs that only dead steps touch"

    def run(q, k, v, do):
        if grid == "fwd":
            return (flash_attention_fwd(q, k, v, **kw),)
        o, lse = flash_attention_fwd(*_skip_inputs(case)[:3], **kw, return_lse=True)
        dq, dk, dv = flash_attention_bwd(q, k, v, o, lse, do, **kw)
        return (dq,) if grid == "dq" else (dk, dv)

    if grid == "fwd":
        v = v.at[:, poisoned].set(jnp.nan)
    elif grid == "dq":
        k = k.at[:, poisoned].set(jnp.nan)
        v = v.at[:, poisoned].set(jnp.nan)
    else:
        q = q.at[:, poisoned].set(jnp.nan)
        do = do.at[:, poisoned].set(jnp.nan)
    got = run(q, k, v, do)
    clean = run(*_skip_inputs(case))

    for g, c in zip(got, clean):
        g = np.take(np.asarray(g), rows, axis=1)
        c = np.take(np.asarray(c), rows, axis=1)
        if causal:
            assert np.isfinite(g).all()
            assert g.tobytes() == c.tobytes()
        else:
            assert np.isnan(g).all()


def test_causal_grid_steps_matches_brute_force():
    def brute(sq, sk, bq, bk, qo, causal):
        bq, bk = min(bq, sq), min(bk, sk)
        num_q, num_k = -(-sq // bq), -(-sk // bk)
        live = 0
        for i in range(num_q):
            rows = i * bq + qo + np.arange(bq)
            for j in range(num_k):
                cols = j * bk + np.arange(bk)
                live += (not causal) or bool((rows[:, None] >= cols[None, :]).any())
        return live, num_q * num_k

    for sq, sk in [(128, 128), (100, 100), (72, 128), (64, 256), (256, 96), (1, 17)]:
        for bq, bk in [(32, 32), (32, 64), (64, 16)]:
            for qo in (0, 5, 40, 128):
                for causal in (True, False):
                    args = (sq, sk, bq, bk, qo, causal)
                    assert causal_grid_steps(*args) == brute(*args), args

    assert causal_grid_steps(2048, 2048, 128, 128, 0, True) == (136, 256)
    assert causal_grid_steps(2048, 2048, 128, 128, 0, False) == (256, 256)
