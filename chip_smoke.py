#!/usr/bin/env python3
"""Bring-up smoke run on TPU: the serve and train paths at full published
width, with attention through the compiled Pallas flash kernel.

  python chip_smoke.py             # one chip: olmo-1b serve phase, then train phase
  python chip_smoke.py --chips 4   # four chips: yi-9b served over a 1x4 mesh,
                                   # then sharded vs unsharded at reduced depth

Weights are random, made from ``--seed``; no weight files are needed.  Each
phase prints one line (model and widths, compile and steady seconds, its
deviation from a reference, the device kind).  The last line of standard
output is exactly ``{"ok": true, "device": {...}}``, and only when every
phase passed.  Without a TPU, or when any phase fails, the script exits
non-zero and prints no ``ok`` line.  One process, no children.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import tempfile
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# Serve phase (one chip): olmo-1b CONFIG, 8 slots over a 2048-token cache.
# Prompt lengths are drawn in three ranges so they land in the 256, 512 and
# 1024 prefill buckets.
SERVE_BATCH, SERVE_MAX_LEN, SERVE_NEW_TOKENS = 8, 2048, 32
SERVE_LEN_RANGES = ((200, 256), (257, 512), (513, 1000))
# Train phase (one chip): batch 1 x 2048 tokens.  AdamW's fp32 moments do
# not fit one v5e at this width next to the step's activations; Adafactor's
# factored statistics do.
TRAIN_SEQ, TRAIN_STEPS = 2048, 3
# Four-chip phase: yi-9b CONFIG (about 18 GB of bf16 weights, more than one
# chip holds) on a 1x4 (data x model) mesh, then the same widths cut to
# SHARD_CHECK_LAYERS of 48 layers (6.6 GB), small enough to hold unsharded
# on one chip beside its own sharded copy.  One prefill bucket (256) keeps
# the four-chip compile count down.
MESH_BATCH, MESH_MAX_LEN, MESH_NEW_TOKENS = 4, 512, 16
MESH_LEN_RANGE = (200, 256)
SHARD_CHECK_LAYERS = 16

# Tolerances, fixed before the chip runs.  The chip path keeps activations
# in bf16 (unit roundoff 2**-9 ~ 2e-3) and rounds the logits themselves to
# bf16 (one ulp is 2**-6 ~ 0.016 at |logit| in [2, 4)); the fp32 reference
# does neither.  Through 16 residual layers the bf16 roundings compound:
# at reduced width on CPU the largest logit error is 1-2% of the largest
# logit, between bf16 and fp32 and even between two bf16 paths (prefill vs
# token-by-token decode).  A wrong kernel (a mask, a scale, a missing
# block) moves logits by the size of the logits themselves.
LOGIT_TOL = 0.05  # max |chip - fp32| over the logits / max |fp32 logit|
# Cross-entropy averages 2048 per-token terms, so the bf16 error largely
# cancels; a wrong forward moves the loss of a random-init model (about
# ln(vocab) = 10.8) by far more than this.
LOSS_TOL = 0.02  # |first-step loss - fp32 loss|, absolute


def _draw_prompts(rng, len_ranges, n, vocab):
    lens = [int(rng.integers(*len_ranges[i % len(len_ranges)], endpoint=True))
            for i in range(n)]
    return [rng.integers(0, vocab, size=n_).astype("int32") for n_ in lens]


def _emitted_logits(cfg, params, prompts, outputs):
    """Prefill logits at the positions that predicted each emitted token:
    prompt + output[:-1] through ``prefill_step`` in one call per request,
    rows ``len(prompt) - 1`` onwards.  Row 0 is the last prompt position."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import init_cache, prefill_step

    n = len(outputs[0])
    pad = -(-max(len(p) + n for p in prompts) // 128) * 128

    @jax.jit
    def rows(params, tokens, start):
        logits, _ = prefill_step(params, cfg, tokens, init_cache(cfg, 1, pad),
                                 jnp.full((1,), pad, jnp.int32))
        return jax.lax.dynamic_slice_in_dim(logits[0], start, n).astype(jnp.float32)

    out = []
    for p, o in zip(prompts, outputs):
        seq = np.concatenate([p, np.asarray(o[:-1], np.int32)])
        toks = np.zeros((1, pad), np.int32)
        toks[0, : len(seq)] = seq
        out.append(np.asarray(rows(params, jnp.asarray(toks), jnp.int32(len(p) - 1))))
    return np.stack(out)  # [requests, n, vocab]


def _deviation(got, ref):
    import numpy as np

    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


def _check_tokens_exact(engine_out, refs, prompts, what):
    bad = [i for i, r in enumerate(refs) if engine_out.get(i) != r]
    for i in bad:
        got, want = engine_out.get(i, []), refs[i]
        at = next((j for j, (a, b) in enumerate(zip(got, want)) if a != b),
                  min(len(got), len(want)))
        print(f"  {what}: request {i} (prompt {len(prompts[i])}) differs from "
              f"token {at}: {got[at:at + 4]} vs {want[at:at + 4]}", flush=True)
    if bad:
        raise AssertionError(f"{what}: {len(bad)}/{len(prompts)} requests differ "
                             "from sequential greedy decode")


def serve_check(cfg, params, *, batch, max_len, prompts, new_tokens, mesh=None):
    """Serve ``prompts`` through ``ServeEngine`` on ``cfg`` and check it three
    ways.  Returns the record for the phase line and the compiled prefill
    HLO of the largest bucket.

    1. The engine serves the requests twice: the first pass compiles (every
       prefill bucket, insert, generate), the second is steady and must
       emit the same tokens.
    2. Prefill logits over prompt + outputs against the fp32 reference (the
       same parameters cast to fp32, scan attention, highest matmul
       precision): every logit within ``LOGIT_TOL``, and every emitted token
       a greedy choice of the reference up to that error (its reference
       logit within ``2 * LOGIT_TOL * max|logit|`` of the reference's max).
       Greedy tokens of the bf16 path cannot be held to exact equality:
       with random weights the top two logits are often closer than bf16
       noise, so two bf16 paths (engine and token-by-token decode) part
       ways within a few tokens.  How many requests still agree with bf16
       sequential decode is reported, not checked.
    3. Exact equality where it is well-posed: the engine on the same widths
       in fp32 at highest precision (still through the Pallas kernel, with
       half the slots, so requests also wait for and back-fill freed slots)
       emits exactly the tokens of ``sequential_greedy_decode`` on the same
       devices.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.dist.collectives import mesh_context
    from repro.serve import Request, ServeEngine, sequential_greedy_decode

    def run(engine):
        for i, p in enumerate(prompts):
            engine.submit(Request(rid=i, prompt=p, max_new_tokens=new_tokens))
        t0 = time.perf_counter()
        done = engine.run()
        return {r.rid: r.output for r in done}, time.perf_counter() - t0

    engine = ServeEngine(cfg, params, batch_size=batch, max_len=max_len, mesh=mesh)
    first, t_first = run(engine)
    second, t_steady = run(engine)
    if first != second:
        raise AssertionError("a second serve pass of the same requests differed")
    bucket = max(engine._bucket_for(len(p)) for p in prompts)
    with mesh_context(mesh):
        hlo = engine._prefill_jit.lower(  # the engine's own prefill executable
            params, jnp.zeros((1, bucket), jnp.int32), jnp.int32(bucket),
            jax.random.PRNGKey(0),
        ).compile().as_text()
    rec = {
        "requests": len(prompts),
        "prompt_lens": sorted(len(p) for p in prompts),
        "new_tokens": new_tokens,
        "compile_s": round(t_first - t_steady, 3),
        "steady_s": round(t_steady, 3),
        "compiles": engine.compile_counts(),
    }
    del engine
    with mesh_context(mesh):
        seq16 = [sequential_greedy_decode(cfg, params, p, new_tokens, max_len=max_len)
                 for p in prompts]
    rec["bf16_requests_equal_sequential"] = sum(
        first[i] == r for i, r in enumerate(seq16))

    outputs = [first[i] for i in range(len(prompts))]
    with mesh_context(mesh):
        got = _emitted_logits(cfg, params, prompts, outputs)
    ref_cfg = dataclasses.replace(cfg, dtype="float32")
    ref_params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    del params
    with mesh_context(mesh), jax.default_matmul_precision("highest"):
        ref = _emitted_logits(dataclasses.replace(ref_cfg, attention_impl="systolic"),
                              ref_params, prompts, outputs)
    scale = float(np.max(np.abs(ref)))
    picked = np.take_along_axis(ref, np.asarray(outputs)[..., None], -1)[..., 0]
    gap = (ref.max(-1) - picked) / scale
    rec["logit_dev"] = _deviation(got, ref)
    rec["last_prompt_logit_dev"] = _deviation(got[:, 0], ref[:, 0])
    rec["max_token_gap"] = float(gap.max())
    rec["greedy_agree"] = float(np.mean(gap == 0))
    rec["logit_tol"] = LOGIT_TOL
    if not rec["logit_dev"] <= LOGIT_TOL:
        raise AssertionError(f"prefill logits deviate {rec['logit_dev']:.4g} from "
                             f"the fp32 reference (tolerance {LOGIT_TOL})")
    if not rec["max_token_gap"] <= 2 * LOGIT_TOL:
        raise AssertionError(f"an emitted token is {rec['max_token_gap']:.4g} below "
                             "the fp32 reference's greedy choice")

    with mesh_context(mesh), jax.default_matmul_precision("highest"):
        # Half the slots: an fp32 cache of full size would leave the decode
        # step (old and new cache both live) too little room on one chip.
        engine = ServeEngine(ref_cfg, ref_params, batch_size=max(batch // 2, 1),
                             max_len=max_len, mesh=mesh)
        out32, _ = run(engine)
        del engine
        refs = [sequential_greedy_decode(ref_cfg, ref_params, p, new_tokens,
                                         max_len=max_len) for p in prompts]
    _check_tokens_exact(out32, refs, prompts, "fp32 engine")
    rec["fp32_tokens_equal_sequential"] = True
    return rec, hlo


def serve_phase(cfg, seed, *, batch=SERVE_BATCH, max_len=SERVE_MAX_LEN,
                len_ranges=SERVE_LEN_RANGES, new_tokens=SERVE_NEW_TOKENS):
    """One chip: ``serve_check`` on ``cfg``."""
    import jax
    import numpy as np

    from repro.models import init_params

    prompts = _draw_prompts(np.random.default_rng(seed), len_ranges, batch,
                            cfg.vocab_size)
    # Passed without a name here, so serve_check can free the bf16
    # parameters once their fp32 copy exists.
    return serve_check(
        cfg, jax.jit(init_params, static_argnums=0)(cfg, jax.random.PRNGKey(seed)),
        batch=batch, max_len=max_len, prompts=prompts, new_tokens=new_tokens,
    )


def train_phase(cfg, seed, *, seq=TRAIN_SEQ, steps=TRAIN_STEPS):
    """One chip: ``steps`` Trainer steps at batch 1 x ``seq`` with Adafactor;
    the first-step loss is compared with the fp32 loss on the same batch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import ShapeConfig
    from repro.models import lm_loss
    from repro.train.trainer import Trainer, TrainerConfig

    with tempfile.TemporaryDirectory() as ckpt_dir:
        tcfg = TrainerConfig(
            total_steps=steps, ckpt_every=steps + 1, ckpt_dir=ckpt_dir,
            optimizer="adafactor", warmup_steps=1, log_every=steps + 1, seed=seed,
        )
        trainer = Trainer(cfg, ShapeConfig("chip_smoke", seq, 1, "train"), tcfg)
        state = trainer.init_state()
        batch = {k: jnp.asarray(v) for k, v in trainer.data.batch(0).items()}

        ref_cfg = dataclasses.replace(cfg, dtype="float32", attention_impl="systolic")
        ref_params = jax.tree.map(lambda a: a.astype(jnp.float32), state["params"])
        with jax.default_matmul_precision("highest"):
            ref_loss = float(jax.jit(lm_loss, static_argnums=1)(ref_params, ref_cfg, batch))
        del ref_params

        t0 = time.perf_counter()
        hlo = trainer.step_fn.lower(state["params"], state["opt"], batch).compile().as_text()
        compile_s = time.perf_counter() - t0
        state = trainer.run(state)
    losses = state["losses"]
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"non-finite training loss: {losses}")
    dev = abs(losses[0] - ref_loss)
    rec = {
        "steps": len(losses),
        "tokens_per_step": seq,
        "optimizer": "adafactor",
        "compile_s": round(compile_s, 3),
        "steady_s": round(trainer.registry.get("train_step_seconds").percentile(50), 4),
        "losses": [round(x, 5) for x in losses],
        "ref_loss": round(ref_loss, 5),
        "loss_dev": dev,
        "loss_tol": LOSS_TOL,
    }
    if not dev <= LOSS_TOL:
        raise AssertionError(f"first-step loss {losses[0]:.5f} deviates {dev:.4g} "
                             f"from the fp32 loss {ref_loss:.5f} (tolerance {LOSS_TOL})")
    return rec, hlo


def mesh_phase(cfg, seed, mesh, *, batch=MESH_BATCH, max_len=MESH_MAX_LEN,
               len_range=MESH_LEN_RANGE, new_tokens=MESH_NEW_TOKENS,
               check_layers=SHARD_CHECK_LAYERS):
    """Four chips: ``serve_check`` on ``cfg`` over ``mesh`` with params and
    cache created sharded, then sharded vs one-chip prefill logits at
    ``check_layers`` layers."""
    import jax
    import numpy as np

    from repro.dist.sharding import param_shardings
    from repro.models import init_params, param_shapes

    def sharded_params(c):
        sh = param_shardings(param_shapes(c), c, mesh)
        return jax.jit(init_params, static_argnums=0, out_shardings=sh)(
            c, jax.random.PRNGKey(seed))

    prompts = _draw_prompts(np.random.default_rng(seed), (len_range,), batch,
                            cfg.vocab_size)
    rec, hlo = serve_check(cfg, sharded_params(cfg), batch=batch, max_len=max_len,
                           prompts=prompts, new_tokens=new_tokens, mesh=mesh)
    # Peak bytes per device (TPU reports them): even across the four chips
    # when nothing was ever placed whole on one of them.
    rec["peak_gib_per_device"] = [
        round((d.memory_stats() or {}).get("peak_bytes_in_use", 0) / 2**30, 2)
        for d in mesh.devices.flat
    ]
    gc.collect()

    cut = dataclasses.replace(cfg, num_layers=check_layers)
    outputs = [[0] for _ in prompts]  # last prompt position only
    with jax.set_mesh(mesh):
        got = _emitted_logits(cut, sharded_params(cut), prompts, outputs)
    one = jax.jit(init_params, static_argnums=0,
                  out_shardings=jax.sharding.SingleDeviceSharding(mesh.devices.flat[0]))
    ref = _emitted_logits(cut, one(cut, jax.random.PRNGKey(seed)), prompts, outputs)
    rec["check_layers"] = check_layers
    rec["sharded_vs_one_chip_logit_dev"] = _deviation(got, ref)
    if not rec["sharded_vs_one_chip_logit_dev"] <= LOGIT_TOL:
        raise AssertionError("sharded prefill logits deviate "
                             f"{rec['sharded_vs_one_chip_logit_dev']:.4g} from one chip")
    return rec, hlo


def _widths(cfg):
    return {
        "layers": cfg.num_layers, "d_model": cfg.d_model, "heads": cfg.num_heads,
        "kv_heads": cfg.num_kv_heads, "head_dim": cfg.resolved_head_dim,
        "d_ff": cfg.d_ff, "vocab": cfg.vocab_size, "dtype": cfg.dtype,
    }


def _report(name, cfg, device_kind, rec, hlo):
    rec = {"phase": name, "model": cfg.name, "widths": _widths(cfg),
           "device_kind": device_kind, **rec,
           "tpu_custom_call": "tpu_custom_call" in hlo}
    print(json.dumps(rec), flush=True)
    if not rec["tpu_custom_call"]:
        raise AssertionError(f"{name}: no tpu_custom_call in the compiled HLO; "
                             "attention did not go through the Pallas kernel")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no {SRC / 'repro'}; run this script from a checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    from repro.configs.registry import get_config
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found {len(devices)}",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    kind = devices[0].device_kind
    print(f"chip_smoke: {len(devices)} x {kind}, jax {jax.__version__}, "
          f"compile cache {cache_dir}", flush=True)

    if args.chips == 4:
        from repro.launch.mesh import make_debug_mesh

        cfg = get_config("yi-9b")
        rec, hlo = mesh_phase(cfg, args.seed, make_debug_mesh(1, 4))
        _report("serve_1x4", cfg, kind, rec, hlo)
    else:
        cfg = get_config("olmo-1b")
        rec, hlo = serve_phase(cfg, args.seed)
        _report("serve", cfg, kind, rec, hlo)
        del rec, hlo
        gc.collect()
        live = sum(a.nbytes for a in jax.live_arrays())
        print(f"chip_smoke: {live / 2**30:.3f} GiB live after the serve phase",
              flush=True)
        rec, hlo = train_phase(cfg, args.seed)
        _report("train", cfg, kind, rec, hlo)

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
