"""Training loop with checkpoint/restart, preemption handling, straggler
watchdog, async checkpointing, and deterministic data — the glue layer that
makes the framework runnable unattended.

Single-process on this container; every policy (atomic checkpoints, resume
from latest, watchdog thresholds, preemption drain) is the multi-host one.

Telemetry (``repro.obs``): each step lands in the trainer's metrics
registry (``train_steps_total``/``train_tokens_total`` counters,
``train_step_seconds`` histogram, loss/grad-norm gauges, per-step MFU
against the devices' bf16 peak; ``flash_live_block_share``, set once, is
the share of the flash grids' steps that the causal skip leaves live at
the training length) and, when ``TrainerConfig.metrics_jsonl``
is set, as one structured JSONL record per step — the stream
``launch/scrape_log.py`` now parses without regexes.  The human log line
is kept.  Spans go to the ambient tracer (``--trace-out`` installs one):
each step is ``train_step.data`` (the batch read and copied to the
device), ``train_step`` (dispatch until the loss is ready) and
``train_step.readback`` (loss and grad norm read back, metrics, JSONL
line, hooks).  A real ``Tracer`` passes them to the profiler; the default
``NullTracer`` makes no call.
"""

from __future__ import annotations

import dataclasses
import json
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs.base import ModelConfig, ShapeConfig
from repro.data import DataConfig, make_source
from repro.dist.collectives import mesh_context
from repro.dist.fault import PreemptionHandler, StepWatchdog
from repro.kernels.flash_attention.kernel import causal_grid_steps
from repro.models import init_params, lm_loss
from repro.obs import MFUMeter, Registry, get_tracer
from repro.optim import make_optimizer
from repro.optim.grad_compress import init_residual
from repro.optim.schedules import cosine_with_warmup
from .train_step import make_train_step


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int = 100
    ckpt_every: int = 20
    ckpt_dir: str = "/tmp/repro_ckpt"
    keep: int = 3
    optimizer: str = "adamw"
    peak_lr: float = 3e-4
    warmup_steps: int = 10
    num_microbatches: int = 1
    log_every: int = 10
    seed: int = 0
    watchdog_factor: float = 10.0
    # int8-compressed DP gradient reduction with error feedback
    # (repro.optim.grad_compress); adds a residual pytree to the state.
    compress_grads: bool = False
    # One JSON object per step appended to this path (None: no stream);
    # the structured twin of the stdout log line — scrape_log's fast path.
    metrics_jsonl: Optional[str] = None


class Trainer:
    def __init__(
        self,
        cfg: ModelConfig,
        shape: ShapeConfig,
        tcfg: TrainerConfig,
        *,
        token_file: Optional[str] = None,
        hooks: Optional[dict[str, Callable]] = None,
        mesh=None,
        registry: Optional[Registry] = None,  # repro.obs metrics sink
        tracer=None,  # repro.obs Tracer (default: ambient, usually Null)
    ):
        self.cfg, self.shape, self.tcfg = cfg, shape, tcfg
        self.data = make_source(cfg, shape, DataConfig(seed=tcfg.seed), token_file)
        self.ckpt = CheckpointManager(tcfg.ckpt_dir, keep=tcfg.keep)
        self.registry = registry if registry is not None else Registry()
        self.tracer = tracer if tracer is not None else get_tracer()
        self.watchdog = StepWatchdog(
            timeout_factor=tcfg.watchdog_factor, registry=self.registry
        )
        self.preempt = PreemptionHandler(install=False, registry=self.registry)
        self.hooks = hooks or {}
        self.mesh = mesh
        self.mfu = MFUMeter(
            cfg, self.registry, chips=mesh.devices.size if mesh is not None else 1
        )
        self._steps_total = self.registry.counter(
            "train_steps_total", "optimizer steps completed"
        )
        self._tokens_total = self.registry.counter(
            "train_tokens_total", "tokens consumed"
        )
        self._h_step = self.registry.histogram(
            "train_step_seconds", "wall time per optimizer step"
        )
        self._g_loss = self.registry.gauge("train_loss", "last step loss")
        self._g_gnorm = self.registry.gauge(
            "train_grad_norm", "last step gradient norm"
        )
        self._g_tok_s = self.registry.gauge(
            "train_tokens_per_s", "throughput of the last step"
        )
        live, total = causal_grid_steps(
            shape.seq_len, shape.seq_len, cfg.attn_block_q, cfg.attn_block_k,
            0, cfg.causal,
        )
        self.registry.gauge(
            "flash_live_block_share",
            "share of flash grid steps that do work at the training length",
        ).set(live / total)

        sched = cosine_with_warmup(tcfg.peak_lr, tcfg.warmup_steps, tcfg.total_steps)
        self.optimizer = make_optimizer(tcfg.optimizer, lr=sched)
        step = make_train_step(
            cfg,
            self.optimizer,
            num_microbatches=tcfg.num_microbatches,
            compress_grads=tcfg.compress_grads,
        )
        self.step_fn = jax.jit(step)

    # -- state ------------------------------------------------------------

    def _make_state(self) -> dict:
        params = init_params(self.cfg, jax.random.PRNGKey(self.tcfg.seed))
        state = {"params": params, "opt": self.optimizer.init(params)}
        if self.tcfg.compress_grads:
            state["residual"] = init_residual(params)
        return state

    def _state_shardings(self, shapes: dict) -> dict:
        """TP layout for params (and the compression residual), ZeRO-1 for
        the optimizer state."""
        from repro.dist.sharding import param_shardings, zero1_shardings

        sh = param_shardings(shapes["params"], self.cfg, self.mesh)
        out = {"params": sh, "opt": zero1_shardings(shapes["opt"], self.cfg, self.mesh)}
        if "residual" in shapes:
            out["residual"] = sh
        return out

    def init_state(self) -> dict:
        """Fresh state.  With a mesh it is created sharded: each device
        materializes only its own shards, never the whole state first."""
        if self.mesh is None:
            state = self._make_state()
        else:
            shapes = jax.eval_shape(self._make_state)
            state = jax.jit(
                self._make_state, out_shardings=self._state_shardings(shapes)
            )()
        state["step"] = 0
        return state

    def restore_or_init(self) -> dict:
        latest = self.ckpt.latest_step()
        if latest is None:
            return self.init_state()
        template = jax.eval_shape(self._make_state)
        shardings = self._state_shardings(template) if self.mesh else None
        restored = self.ckpt.restore(latest, template, shardings)
        restored["step"] = latest
        return restored

    # -- loop --------------------------------------------------------------

    def run(self, state: Optional[dict] = None) -> dict:
        state = state or self.restore_or_init()
        ckpt_keys = ("params", "opt") + (
            ("residual",) if self.tcfg.compress_grads else ()
        )
        losses = []
        tokens_per_batch = self.shape.global_batch * self.shape.seq_len
        jsonl = (
            open(self.tcfg.metrics_jsonl, "a")
            if self.tcfg.metrics_jsonl else None
        )
        while state["step"] < self.tcfg.total_steps:
            if self.preempt.requested:
                self.ckpt.save(state["step"], {k: state[k] for k in ckpt_keys})
                break
            step = state["step"]
            with self.tracer.span("train_step.data", cat="train", tid=0):
                batch = {
                    k: jnp.asarray(v) for k, v in self.data.batch(step).items()
                }
            self.watchdog.start_step()
            with mesh_context(self.mesh), self.tracer.span(
                "train_step", cat="train", tid=0, args={"step": step}
            ):
                if self.tcfg.compress_grads:
                    params, opt, residual, metrics = self.step_fn(
                        state["params"], state["opt"], batch, state["residual"]
                    )
                    new_state = {
                        "params": params, "opt": opt,
                        "residual": residual, "step": step + 1,
                    }
                else:
                    params, opt, metrics = self.step_fn(
                        state["params"], state["opt"], batch
                    )
                    new_state = {"params": params, "opt": opt, "step": step + 1}
                jax.block_until_ready(metrics["loss"])
            dur = self.watchdog.end_step()
            state = new_state
            with self.tracer.span("train_step.readback", cat="train", tid=0):
                loss = float(metrics["loss"])
                gnorm = float(metrics["grad_norm"])
                losses.append(loss)
                self._steps_total.inc()
                self._tokens_total.inc(tokens_per_batch)
                self._h_step.observe(dur)
                self._g_loss.set(loss)
                self._g_gnorm.set(gnorm)
                self._g_tok_s.set(tokens_per_batch / dur)
                mfu_rec = self.mfu.train_step(
                    self.shape.global_batch, self.shape.seq_len, dur
                )
                if jsonl is not None:
                    jsonl.write(json.dumps({
                        "event": "train_step",
                        "step": step + 1,
                        "loss": loss,
                        "grad_norm": gnorm,
                        "step_s": dur,
                        "tokens_per_s": tokens_per_batch / dur,
                        "mfu": mfu_rec["mfu"],
                        "model_flops_per_s": mfu_rec["flops_per_s"],
                    }) + "\n")
                    jsonl.flush()
                if "on_step" in self.hooks:
                    self.hooks["on_step"](state, metrics)
            if (step + 1) % self.tcfg.log_every == 0:
                print(
                    f"step {step + 1} loss {loss:.4f} "
                    f"gnorm {gnorm:.3f} {dur * 1e3:.0f} ms"
                )
            if (step + 1) % self.tcfg.ckpt_every == 0:
                self.ckpt.save_async(step + 1, {k: state[k] for k in ckpt_keys})
        if jsonl is not None:
            jsonl.close()
        self.ckpt.wait()
        state["losses"] = losses
        return state
