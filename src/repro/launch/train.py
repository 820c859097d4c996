"""Production training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --steps 50 \
      --batch 8 --seq 256 [--smoke] [--ckpt-dir DIR] [--resume]

``--smoke`` uses the arch's reduced config (CPU-runnable); the full config
is what the multi-pod dry-run lowers.  On a real TPU slice this same entry
point runs under the production mesh with the sharding rules from
repro.dist.sharding; ``--mesh DxM`` stands one up from the local devices.

  --quant int8         int8 projections (quantization-aware: the backward
                       is straight-through against fp operands)
  --compress-grads     int8 DP gradient reduction with error feedback
  --mesh DxM           debug mesh (data x model), e.g. --mesh 2x1
  --metrics-out PATH   Prometheus text dump at exit (loss/gnorm gauges,
                       step-latency histogram, MFU, watchdog heartbeats);
                       additionally streams one JSON record per step to
                       PATH.jsonl (scrape_log's fast path)
  --trace-out PATH     Chrome-trace/Perfetto JSON of the per-step spans
"""

from __future__ import annotations

import argparse
import dataclasses

from repro.configs.base import ShapeConfig
from repro.configs.registry import get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import Tracer, set_tracer
from repro.quant.config import QUANT_FLAGS
from repro.train.trainer import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--optimizer", default="adamw", choices=("adamw", "adafactor"))
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--token-file", default=None)
    ap.add_argument("--quant", default="none", choices=QUANT_FLAGS)
    ap.add_argument("--compress-grads", action="store_true",
                    help="int8-compressed DP gradient reduction")
    ap.add_argument("--mesh", default=None, help="debug mesh DxM, e.g. 2x1")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="Prometheus dump at exit + per-step PATH.jsonl stream")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Perfetto-loadable Chrome trace here")
    args = ap.parse_args()

    cfg = (get_smoke_config if args.smoke else get_config)(args.arch, args.quant)
    if cfg.family == "encoder" and not cfg.embedding_inputs:
        raise SystemExit("encoder archs train on frame embeddings")
    enable_compile_cache()
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    tcfg = TrainerConfig(
        total_steps=args.steps,
        ckpt_every=args.ckpt_every,
        ckpt_dir=args.ckpt_dir,
        optimizer=args.optimizer,
        peak_lr=args.lr,
        num_microbatches=args.microbatches,
        log_every=max(args.steps // 10, 1),
        compress_grads=args.compress_grads,
        metrics_jsonl=args.metrics_out + ".jsonl" if args.metrics_out else None,
    )
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_debug_mesh

        data, model = (int(x) for x in args.mesh.split("x"))
        mesh = make_debug_mesh(data, model)
    tracer = None
    if args.trace_out:
        tracer = Tracer(process_name=f"train {args.arch}")
        set_tracer(tracer)
    trainer = Trainer(
        cfg, shape, tcfg, token_file=args.token_file, mesh=mesh, tracer=tracer
    )
    state = trainer.run()
    print(f"done at step {state['step']}; "
          f"loss {state['losses'][0]:.4f} -> {state['losses'][-1]:.4f}")
    mfu = trainer.registry.get("mfu")
    if mfu is not None:
        print(f"mfu (train, vs the devices' bf16 peak): "
              f"{mfu.labels(phase='train').value:.4f}")
    if args.metrics_out:
        trainer.registry.dump(args.metrics_out)
        print(f"metrics -> {args.metrics_out} (+ {tcfg.metrics_jsonl})")
    if tracer is not None:
        tracer.save(args.trace_out)
        print(f"trace ({len(tracer.events)} events) -> {args.trace_out}")


if __name__ == "__main__":
    main()
