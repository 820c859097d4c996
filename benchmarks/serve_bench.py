"""Serving throughput: continuous-batching decode tokens/s + latency tails.

First point on the repo's bench trajectory (ROADMAP "Benchmark
trajectory"): a CPU-runnable tiny-model measurement of the engine's
steady-state generate step — full slot pool, executables warm, one batched
decode per step — written to ``BENCH_serve.json`` so CI archives a
comparable number per commit.

Since PR 10 the latency distribution comes from the engine's own
``repro.obs`` registry: TTFT and TPOT percentiles (TTFT — and TPOT's p99 —
include the jit compile, deliberately: that *is* the first-request
experience) and mean batch utilization ride along in the JSON;
``benchmarks/summarize.py`` folds the ``latency.*`` keys into the CI step
summary.
"""

from __future__ import annotations

import json
import time

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import init_params
from repro.serve import Request, ServeEngine

BATCH = 4
PROMPT_LEN = 24
TIMED_STEPS = 40

CFG = ModelConfig(
    name="serve-bench-tiny",
    family="dense",
    num_layers=4,
    d_model=128,
    num_heads=8,
    num_kv_heads=4,
    head_dim=16,
    d_ff=256,
    vocab_size=512,
    mlp_type="swiglu",
    dtype="float32",
    remat=False,
)


def run(csv_rows: list) -> dict:
    params = init_params(CFG, jax.random.PRNGKey(0))
    engine = ServeEngine(
        CFG, params, batch_size=BATCH, max_len=128, prefill_buckets=(32,)
    )
    rng = np.random.default_rng(0)
    # max_new_tokens large enough that no slot retires inside the timed
    # window — every timed step decodes exactly BATCH tokens.
    for i in range(BATCH):
        engine.submit(Request(
            rid=i,
            prompt=rng.integers(0, CFG.vocab_size, size=PROMPT_LEN).astype(np.int32),
            max_new_tokens=TIMED_STEPS + 8,
        ))
    for _ in range(3):  # warmup: prefill + insert + generate all compile
        engine.step()

    t0 = time.perf_counter()
    for _ in range(TIMED_STEPS):
        engine.step()
    jax.block_until_ready(engine.cache)
    dt = time.perf_counter() - t0

    toks = TIMED_STEPS * BATCH
    tok_s = toks / dt
    us_per_step = dt / TIMED_STEPS * 1e6
    csv_rows.append(
        ("serve_decode", us_per_step, f"decode_tok_s={tok_s:.1f};batch={BATCH}")
    )

    ttft = engine.registry.get("serve_ttft_seconds")
    tpot = engine.registry.get("serve_tpot_seconds")
    butil = engine.registry.get("serve_batch_utilization")
    mfu = engine.registry.get("mfu")
    result = {
        "benchmark": "serve_decode",
        "decode_tokens_per_s": round(tok_s, 1),
        "us_per_generate_step": round(us_per_step, 1),
        "batch_size": BATCH,
        "prompt_len": PROMPT_LEN,
        "timed_steps": TIMED_STEPS,
        "latency": {
            "ttft_p50_ms": round(ttft.percentile(50) * 1e3, 3),
            "ttft_p99_ms": round(ttft.percentile(99) * 1e3, 3),
            "tpot_p50_ms": round(tpot.percentile(50) * 1e3, 3),
            "tpot_p99_ms": round(tpot.percentile(99) * 1e3, 3),
            "batch_utilization_mean": round(butil.sum / max(butil.count, 1), 4),
        },
        # None on a device with no known peak (repro.obs.peaks).
        "mfu_decode": mfu.labels(phase="decode").value if mfu else None,
        "model": {
            "family": CFG.family,
            "num_layers": CFG.num_layers,
            "d_model": CFG.d_model,
            "num_heads": CFG.num_heads,
        },
        "stats": dict(engine.stats),
        "compiles": engine.compile_counts(),
    }
    with open("BENCH_serve.json", "w") as f:
        json.dump(result, f, indent=2)
    return result
