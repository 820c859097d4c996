"""The flash forward's live step in interpret mode: which MXU operands it
takes for which input dtype, and that its bf16 path forms what the fp32
path forms from the same values.

* bf16 q, k and v against the same values upcast to fp32: the output
  within one bf16 ulp of the fp32 call's, the base-2 LSE within 2e-6.
  The ulp is taken at the row's sum of |P| |V| (the same call on |V|),
  the scale at which a sum of products rounds: an output near zero is a
  cancellation of terms of that size.  All but one element in a thousand
  equal the fp32 output rounded to bf16 (P rounded to one bf16 moves about
  a third of them);
* the kernel's own jaxpr: for bf16 inputs no ``HIGHEST`` dot and only bf16
  dot operands, P entering as three bf16 slices; for fp32 inputs both dots
  at ``HIGHEST``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import flash_attention_fwd

# (Sq, Sk, H, Hkv, d, block, flash keywords)
CASES = {
    "causal": (256, 256, 2, 2, 128, 128, dict(causal=True)),
    "not_causal": (256, 256, 2, 2, 128, 128, dict(causal=False)),
    "q_offset": (128, 384, 2, 2, 128, 128, dict(causal=True, q_offset=256)),
    "gqa": (256, 256, 8, 2, 128, 128, dict(causal=True)),
    "ragged": (200, 200, 2, 1, 64, 64, dict(causal=True)),
    "window": (384, 384, 4, 1, 128, 128, dict(causal=True, window=160)),
    "pwl": (256, 256, 2, 2, 128, 64, dict(causal=True, exp2_impl="pwl")),
}


def _inputs(case, dtype):
    sq, sk, h, hkv, d, _, _ = CASES[case]
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 3)
    shapes = ((1, sq, h, d), (1, sk, hkv, d), (1, sk, hkv, d))
    return [jax.random.normal(key, shape, jnp.float32).astype(dtype)
            for key, shape in zip(keys, shapes)]


def _bf16_ulp(x):
    """Spacing of bf16 (8 significant bits) at |x|."""
    _, e = np.frexp(np.maximum(np.abs(x), np.finfo(np.float32).tiny))
    return np.ldexp(1.0, e - 8)


@pytest.mark.parametrize("case", sorted(CASES))
def test_bf16_forward_matches_the_same_values_in_fp32(case):
    *_, block, kw = CASES[case]
    kw = dict(kw, block_q=block, block_k=block, interpret=True, return_lse=True)
    q, k, v = _inputs(case, jnp.bfloat16)
    o, lse = flash_attention_fwd(q, k, v, **kw)
    o32, lse32 = flash_attention_fwd(*(x.astype(jnp.float32) for x in (q, k, v)), **kw)
    assert o.dtype == jnp.bfloat16 and o32.dtype == jnp.float32
    scale = flash_attention_fwd(q.astype(jnp.float32), k.astype(jnp.float32),
                                jnp.abs(v.astype(jnp.float32)), **kw)[0]
    got, want = np.asarray(o, np.float32), np.asarray(o32)
    assert (np.abs(got - want) <= _bf16_ulp(np.asarray(scale))).all()
    assert np.mean(got != np.asarray(o32.astype(jnp.bfloat16), np.float32)) < 1e-3
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse32), rtol=0, atol=2e-6)


def _kernel_dots(dtype, **kw):
    """(precision, operand dtypes) of every dot_general in the forward's
    kernel body, nested jaxprs (the ``pl.when`` branches) included."""
    shape = jax.ShapeDtypeStruct((1, 256, 2, 128), dtype)
    jaxpr = jax.make_jaxpr(
        lambda q, k, v: flash_attention_fwd(q, k, v, interpret=True, **kw))(shape, shape, shape)
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    dots = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "dot_general":
                dots.append((eqn.params["precision"],
                             tuple(str(x.aval.dtype) for x in eqn.invars)))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(calls[0].params["jaxpr"])
    return dots


HIGHEST = (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)


@pytest.mark.parametrize("kw", [dict(causal=True), dict(causal=True, window=96)],
                         ids=["full", "window"])
def test_bf16_inputs_reach_the_mxu_as_bf16(kw):
    dots = _kernel_dots(jnp.bfloat16, **kw)
    # q.kT once, then P's three bf16 slices against V.
    assert len(dots) == 4
    for precision, operands in dots:
        assert precision != HIGHEST
        assert operands == ("bfloat16", "bfloat16")


@pytest.mark.parametrize("kw", [dict(causal=True), dict(causal=True, window=96)],
                         ids=["full", "window"])
def test_fp32_inputs_keep_two_highest_dots(kw):
    dots = _kernel_dots(jnp.float32, **kw)
    assert dots == [(HIGHEST, ("float32", "float32"))] * 2
