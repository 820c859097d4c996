"""Serving launcher: continuous batching against a registry model.

  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --smoke --requests 8

Without ``--smoke`` the arch's full published config is served (random
init, seeded); ``--smoke`` selects its reduced config (CPU-runnable).

Requests get mixed prompt lengths (the engine buckets them for prefill),
arrive all at once, and drain through a fixed slot pool — so this drives
prefill bucketing, slot eviction and back-fill even in a smoke run.

  --temperature/--top-k/--top-p  sampling policy (default greedy)
  --chunk N                      chunked flash prefill (N tokens per call)
  --mesh DxM                     shard params + decode cache over a debug
                                 mesh (data x model), e.g. --mesh 2x4; both
                                 are created sharded
  --quant int8                   int8 projections + int8 KV cache
                                 (repro.quant; greedy outputs stay
                                 token-identical to sequential decode,
                                 so --check still applies)
  --spec-draft self|ARCH         speculative decoding (repro.spec): 'self'
                                 drafts with the target itself (lossless
                                 sanity mode, acceptance = 1.0); an arch id
                                 drafts with that smoke config (random
                                 init in this launcher; the vocabularies
                                 must match, so pair it with --smoke)
  --spec-k N                     lookahead: draft tokens verified per round
  --spec-quant int8              int8 policy on the *draft* only (the
                                 near-free draft / exact target split)
  --check                        verify every greedy output token-for-token
                                 against sequential single-request decode
  --metrics-out PATH             dump the engine's metrics registry as
                                 Prometheus text at exit (TTFT/TPOT/queue
                                 histograms, occupancy + MFU gauges, jit
                                 compile counters)
  --trace-out PATH               save a Chrome-trace/Perfetto JSON of the
                                 run (open at ui.perfetto.dev)
"""

from __future__ import annotations

import argparse
import contextlib
import time

import jax
import numpy as np

from repro.configs.registry import get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params, param_shapes
from repro.obs import Tracer, set_tracer, watch_jit_compiles
from repro.quant.config import QUANT_FLAGS
from repro.serve import Request, SamplingConfig, ServeEngine, sequential_greedy_decode


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--quant", default="none", choices=QUANT_FLAGS,
                    help="int8 policy: projections + int8 KV cache "
                         "(int8-kv-only / int8-no-kv select one half)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=12,
                    help="max prompt length; actual lengths are mixed in [2, N]")
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=None)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default=None, help="debug mesh DxM, e.g. 2x4")
    ap.add_argument("--spec-draft", default=None,
                    help="speculative decoding draft: 'self' or an arch id")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="speculative lookahead (draft tokens per round)")
    ap.add_argument("--spec-quant", default="none", choices=QUANT_FLAGS,
                    help="int8 policy applied to the draft model only")
    ap.add_argument("--check", action="store_true",
                    help="compare against sequential single-request decode")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write Prometheus text exposition here at exit")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Perfetto-loadable Chrome trace here")
    args = ap.parse_args()

    cfg = (get_smoke_config if args.smoke else get_config)(args.arch, args.quant)
    if cfg.family == "encoder":
        raise SystemExit("encoder-only arch: no decode phase (DESIGN.md §5)")
    enable_compile_cache()

    mesh = None
    key = jax.random.PRNGKey(0)
    if args.mesh:
        from repro.dist.sharding import param_shardings
        from repro.launch.mesh import make_debug_mesh

        data, model = (int(x) for x in args.mesh.split("x"))
        mesh = make_debug_mesh(data, model)
        # Created sharded: no device ever holds the whole model.
        sh = param_shardings(param_shapes(cfg), cfg, mesh)
        params = jax.jit(init_params, static_argnums=0, out_shardings=sh)(cfg, key)
    else:
        params = init_params(cfg, key)

    sampling = SamplingConfig(
        temperature=args.temperature, top_k=args.top_k, top_p=args.top_p,
        seed=args.seed,
    )

    spec = draft_params = None
    if args.spec_draft:
        from repro.spec import SpecConfig, resolve_draft_config

        spec = SpecConfig(
            draft_arch=None if args.spec_draft == "self" else args.spec_draft,
            draft_quant=args.spec_quant if args.spec_quant != "none" else None,
            lookahead=args.spec_k,
        )
        if spec.draft_arch is not None:
            # No trained weights in this launcher: a random-init draft still
            # exercises the full draft->verify->rollback path (outputs stay
            # lossless; only the acceptance rate suffers).
            draft_params = init_params(
                resolve_draft_config(spec, cfg), jax.random.PRNGKey(1)
            )

    tracer = None
    if args.trace_out:
        tracer = Tracer(process_name=f"serve {args.arch}")
        set_tracer(tracer)

    engine = ServeEngine(
        cfg, params, batch_size=args.batch, max_len=args.max_len,
        prefill_chunk=args.chunk, sampling=sampling, mesh=mesh,
        spec=spec, draft_params=draft_params, tracer=tracer,
    )

    rng = np.random.default_rng(0)
    prompts = {}
    for i in range(args.requests):
        plen = int(rng.integers(2, max(3, args.prompt_len + 1)))
        prompts[i] = rng.integers(0, cfg.vocab_size, size=plen).astype(np.int32)
        engine.submit(Request(rid=i, prompt=prompts[i], max_new_tokens=args.max_new))

    # With a metrics sink requested, also count XLA executable builds into
    # the registry (jax's compile log fires once per build).
    compile_watch = (
        watch_jit_compiles(
            engine.registry.counter(
                "jit_compiles_total", "XLA executable builds observed"
            )
        )
        if args.metrics_out else contextlib.nullcontext()
    )
    t0 = time.perf_counter()
    with compile_watch:
        done = engine.run()
    dt = time.perf_counter() - t0

    for r in sorted(done, key=lambda r: r.rid):
        print(f"req {r.rid}: prompt[{len(prompts[r.rid])}] -> {r.output}")
    toks = sum(len(r.output) for r in done)
    print(
        f"completed {len(done)}/{args.requests}: {toks} tokens in {dt:.2f}s "
        f"({toks / dt:.1f} tok/s) | stats {engine.stats} "
        f"| compiles {engine.compile_counts()}"
    )
    if spec is not None:
        print(
            f"spec: acceptance {engine.acceptance_rate():.3f} | "
            f"{engine.stats['verify_steps']} verify steps for {toks} tokens "
            f"({toks / max(engine.stats['verify_steps'], 1):.2f} tok/verify)"
        )

    ttft = engine.registry.get("serve_ttft_seconds")
    tpot = engine.registry.get("serve_tpot_seconds")
    print(
        f"latency: ttft p50 {ttft.percentile(50) * 1e3:.1f} ms "
        f"p99 {ttft.percentile(99) * 1e3:.1f} ms | "
        f"tpot p50 {tpot.percentile(50) * 1e3:.1f} ms "
        f"p99 {tpot.percentile(99) * 1e3:.1f} ms"
    )
    if args.metrics_out:
        engine.registry.dump(args.metrics_out)
        print(f"metrics -> {args.metrics_out}")
    if tracer is not None:
        tracer.save(args.trace_out)
        print(f"trace ({len(tracer.events)} events) -> {args.trace_out}")

    if args.check:
        if not sampling.greedy:
            raise SystemExit("--check requires greedy decoding (temperature 0)")
        bad = 0
        for r in sorted(done, key=lambda r: r.rid):
            ref = sequential_greedy_decode(
                cfg, params, prompts[r.rid], args.max_new, max_len=args.max_len
            )
            if r.output != ref:
                bad += 1
                print(f"MISMATCH req {r.rid}: engine {r.output} != ref {ref}")
        if bad:
            raise SystemExit(f"{bad}/{len(done)} requests diverged")
        print(f"check OK: all {len(done)} outputs match sequential decode")


if __name__ == "__main__":
    main()
