"""Token-equivalence harness for the continuous-batching ServeEngine.

The contract under test: whatever mix of prompt lengths, arrival times,
slot evictions and prefill chunking the engine sees, every request's
output tokens must equal an obviously-correct baseline — batch-1,
teacher-forced, one-token-at-a-time greedy decode
(``sequential_greedy_decode``).  This holds exactly (not approximately)
because chunked flash prefill and per-token decode share one attention
dispatch (``repro.models.attention._impl_attention``) and padded lanes
contribute exact zeros to the softmax.

Also pinned here: jit executables are reused across requests — the
generate step compiles once, prefill once per length bucket, and a second
wave of differently-sized prompts compiles nothing new.
"""

import os

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    )

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from repro.configs import get_smoke_config  # noqa: E402
from repro.configs.base import ModelConfig  # noqa: E402
from repro.models import init_params  # noqa: E402
from repro.serve import (  # noqa: E402
    Request,
    SamplingConfig,
    ServeEngine,
    sequential_greedy_decode,
)

TINY = ModelConfig(
    name="tiny",
    family="dense",
    num_layers=2,
    d_model=64,
    num_heads=4,
    num_kv_heads=2,
    head_dim=16,
    d_ff=128,
    vocab_size=128,
    mlp_type="swiglu",
    dtype="float32",
    remat=False,
)

MAX_LEN = 64


@pytest.fixture(scope="module")
def params():
    return init_params(TINY, jax.random.PRNGKey(0))


def _prompts(spec, seed=0):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(1, TINY.vocab_size, size=plen).astype(np.int32)
        for plen, _ in spec
    ]


def _reference(params, prompts, spec, eos_id=-1):
    return {
        i: sequential_greedy_decode(
            TINY, params, p, spec[i][1], eos_id=eos_id, max_len=MAX_LEN
        )
        for i, p in enumerate(prompts)
    }


# Three mixed-length schedules: (batch_size, buckets, prefill_chunk,
# [(prompt_len, max_new_tokens), ...]).  Each has more requests than slots
# (forcing retirement + back-fill), prompts spanning several buckets, and
# lengths that are not multiples of the chunk/bucket sizes.
SCHEDULES = [
    (2, (8, 16, 32), None, [(5, 6), (13, 4), (24, 5), (9, 3), (17, 6)]),
    (3, (8, 32), 8, [(3, 8), (30, 2), (11, 5), (8, 4), (21, 7), (4, 1)]),
    (4, (16,), 4, [(16, 5), (2, 5), (7, 5), (12, 5), (1, 5)]),
]


@pytest.mark.parametrize("batch,buckets,chunk,spec", SCHEDULES)
def test_token_equivalence_mixed_schedules(params, batch, buckets, chunk, spec):
    prompts = _prompts(spec, seed=hash((batch, chunk)) % 1000)
    ref = _reference(params, prompts, spec)

    eng = ServeEngine(
        TINY, params, batch_size=batch, max_len=MAX_LEN,
        prefill_chunk=chunk, prefill_buckets=buckets,
    )
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=spec[i][1]))
    done = eng.run()

    assert len(done) == len(spec)
    for r in done:
        assert r.output == ref[r.rid], f"rid {r.rid} diverged"
    # Every request prefilled exactly once, into a reused slot pool.
    assert eng.stats["prefill_calls"] == len(spec)
    assert eng.stats["insert_calls"] == len(spec)


def test_mid_stream_insertion(params):
    """Requests arriving while others are mid-decode join the running batch
    without perturbing anyone's tokens."""
    spec = [(12, 8), (6, 8), (20, 6), (9, 6)]
    prompts = _prompts(spec, seed=42)
    ref = _reference(params, prompts, spec)

    eng = ServeEngine(
        TINY, params, batch_size=2, max_len=MAX_LEN, prefill_buckets=(8, 16, 32)
    )
    for i in (0, 1):
        eng.submit(Request(rid=i, prompt=prompts[i], max_new_tokens=spec[i][1]))
    for _ in range(3):  # partially decode the first wave
        eng.step()
    for i in (2, 3):  # late arrivals
        eng.submit(Request(rid=i, prompt=prompts[i], max_new_tokens=spec[i][1]))
    done = eng.run()

    assert len(done) == 4
    for r in done:
        assert r.output == ref[r.rid], f"rid {r.rid} diverged"


def test_slot_eviction_and_backfill(params):
    """A slot whose request hits max_new_tokens retires and is re-used by
    the next queued request within the same step."""
    spec = [(4, 2), (4, 2), (4, 2), (4, 2), (4, 2)]
    prompts = _prompts(spec, seed=7)
    ref = _reference(params, prompts, spec)

    eng = ServeEngine(TINY, params, batch_size=2, max_len=MAX_LEN,
                      prefill_buckets=(8,))
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=2))
    done = eng.run()
    assert len(done) == 5
    for r in done:
        assert r.output == ref[r.rid]
    # 5 requests through 2 slots: at least one slot served >= 3 requests,
    # so the cache was overwritten in place (not grown).
    assert eng.stats["prefill_calls"] == 5
    assert eng.batch == 2


def test_eos_truncates_and_matches_reference(params):
    prompt = _prompts([(10, 8)], seed=3)[0]
    base = sequential_greedy_decode(TINY, params, prompt, 8, max_len=MAX_LEN)
    eos = base[3]  # force a mid-stream EOS
    ref = sequential_greedy_decode(
        TINY, params, prompt, 8, eos_id=eos, max_len=MAX_LEN
    )
    assert len(ref) < len(base)

    eng = ServeEngine(TINY, params, batch_size=2, max_len=MAX_LEN,
                      prefill_buckets=(16,))
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=8, eos_id=eos))
    (r,) = eng.run()
    assert r.output == ref


@pytest.mark.parametrize("mesh_shape", [None, (2, 4)])
def test_generate_compiles_once_per_bucket(params, jit_recompiles, mesh_shape):
    """Prefill compiles once per bucket, generate exactly once; a second
    wave of new prompt lengths (same buckets) compiles nothing.  Under a
    mesh too: the cache keeps its layout through insert and decode."""
    from repro.launch.mesh import make_debug_mesh

    mesh = make_debug_mesh(*mesh_shape) if mesh_shape else None
    eng = ServeEngine(TINY, params, batch_size=2, max_len=MAX_LEN,
                      prefill_buckets=(8, 16), mesh=mesh)
    wave1 = [(5, 3), (8, 3), (12, 3), (16, 3)]  # both buckets, both edges
    for i, p in enumerate(_prompts(wave1, seed=1)):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=3))
    eng.run()
    counts = eng.compile_counts()
    assert counts["prefill"] == 2  # == number of buckets touched
    assert counts["insert"] == 2  # one per distinct prefix shape
    assert counts["generate"] == 1  # shared by every slot state

    jit_recompiles.reset()
    wave2 = [(7, 4), (3, 2), (13, 5), (9, 3)]  # new lengths, same buckets
    for i, p in enumerate(_prompts(wave2, seed=2)):
        eng.submit(Request(rid=10 + i, prompt=p, max_new_tokens=wave2[i][1]))
    done = eng.run()
    assert len(done) == 4
    assert jit_recompiles.count == 0, "second wave must reuse all executables"
    assert eng.compile_counts() == counts


def test_chunked_prefill_matches_unchunked(params):
    spec = [(24, 6), (17, 6)]
    prompts = _prompts(spec, seed=11)

    outs = []
    for chunk in (None, 8):
        eng = ServeEngine(TINY, params, batch_size=2, max_len=MAX_LEN,
                          prefill_chunk=chunk, prefill_buckets=(32,))
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=6))
        outs.append(sorted((r.rid, tuple(r.output)) for r in eng.run()))
    assert outs[0] == outs[1]


def test_hybrid_family_scan_prefill(params):
    """Recurrent-state families can't chunk flash prefill; they teacher-force
    under one lax.scan — still one jit call per request, still
    token-equivalent (per-slot state freeze keeps pad tokens out of the
    recurrence)."""
    cfg = get_smoke_config("zamba2-1.2b")
    assert cfg.family == "hybrid"
    hparams = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    spec = [(4, 5), (11, 4), (7, 5)]
    prompts = [
        rng.integers(1, cfg.vocab_size, size=n).astype(np.int32) for n, _ in spec
    ]
    ref = {
        i: sequential_greedy_decode(cfg, hparams, p, spec[i][1], max_len=32)
        for i, p in enumerate(prompts)
    }
    eng = ServeEngine(cfg, hparams, batch_size=2, max_len=32,
                      prefill_buckets=(8, 16))
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=spec[i][1]))
    done = eng.run()
    assert len(done) == 3
    for r in done:
        assert r.output == ref[r.rid]
    assert eng.stats["prefill_calls"] == 3


def _run_sampled(params, prompts, sampling):
    eng = ServeEngine(TINY, params, batch_size=2, max_len=MAX_LEN,
                      prefill_buckets=(16,), sampling=sampling)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=5))
    return sorted((r.rid, tuple(r.output)) for r in eng.run())


def test_sampling_deterministic_per_seed(params):
    prompts = _prompts([(6, 5), (12, 5)], seed=9)
    a = _run_sampled(params, prompts, SamplingConfig(temperature=0.8, top_k=5, seed=7))
    b = _run_sampled(params, prompts, SamplingConfig(temperature=0.8, top_k=5, seed=7))
    c = _run_sampled(params, prompts, SamplingConfig(temperature=0.8, top_k=5, seed=8))
    assert a == b  # same seed, same tokens
    assert a != c  # seed actually threads through


def test_top_k_one_equals_greedy(params):
    prompts = _prompts([(6, 5), (12, 5)], seed=9)
    greedy = _run_sampled(params, prompts, SamplingConfig())
    k1 = _run_sampled(params, prompts, SamplingConfig(temperature=0.5, top_k=1))
    assert greedy == k1


def test_top_p_tiny_equals_greedy(params):
    prompts = _prompts([(6, 5), (12, 5)], seed=9)
    greedy = _run_sampled(params, prompts, SamplingConfig())
    p_tiny = _run_sampled(
        params, prompts, SamplingConfig(temperature=0.7, top_p=1e-6)
    )
    assert greedy == p_tiny  # nucleus keeps at least the argmax token


def test_overlong_prompt_rejected(params):
    eng = ServeEngine(TINY, params, batch_size=2, max_len=32,
                      prefill_buckets=(8, 16))
    with pytest.raises(ValueError, match="exceeds the largest prefill bucket"):
        eng.submit(Request(rid=0, prompt=np.zeros(17, np.int32)))


def test_encoder_family_rejected():
    cfg = get_smoke_config("hubert-xlarge")
    with pytest.raises(AssertionError, match="no decode phase"):
        ServeEngine(cfg, params=None)
