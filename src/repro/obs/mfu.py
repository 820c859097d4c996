"""Model-FLOPs-utilization (MFU) accounting against the device's peak.

  * closed-form model FLOPs per phase: 2 FLOPs per matmul parameter per
    token forward (the output head counts, tied or not; the embedding
    lookup does not), 3x the forward for training, plus the causal
    attention term ``4 * head_dim * heads`` per layer for each query-key
    pair on or below the diagonal; recomputation under remat is not
    counted;
  * ``MFUMeter`` divides achieved FLOPs/s by the bf16 peak of the device
    (``repro.obs.peaks``, keyed by ``device_kind``) and keeps per-phase
    gauges (``model_flops_per_s``, ``mfu``) and a cumulative FLOPs
    counter.  On a device with no known peak it keeps no ``mfu`` gauge.

The paper's FSA array stays here as a reference for the paper
reproduction: ``paper_ideal_flops_per_s`` is what FSA achieves on an
attention shape per Fig. 11 (``core.systolic_model``).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import systolic_model
from .peaks import device_peak

__all__ = [
    "ArrayConfig",
    "PAPER_ARRAY",
    "matmul_param_count",
    "train_step_flops",
    "prefill_flops",
    "decode_flops",
    "verify_flops",
    "paper_ideal_flops_per_s",
    "MFUMeter",
]


@dataclasses.dataclass(frozen=True)
class ArrayConfig:
    """The paper's systolic array, the reference of
    ``paper_ideal_flops_per_s`` (Table 1: N = 128 at 1.5 GHz;
    ``tune.DesignPoint`` uses the same defaults)."""

    array_n: int = 128
    freq_ghz: float = 1.5
    single_direction: bool = False

    @property
    def peak_flops_per_s(self) -> float:
        """2 * N^2 MACs-as-FLOPs per cycle at the synthesis clock."""
        return 2.0 * self.array_n * self.array_n * self.freq_ghz * 1e9


PAPER_ARRAY = ArrayConfig()


# ---------------------------------------------------------------------------
# Model-FLOPs closed forms
# ---------------------------------------------------------------------------


def matmul_param_count(cfg: ModelConfig) -> int:
    """Active parameters that enter a matmul for each token: all but the
    embedding lookup.  A tied table is still the output head's matmul."""
    lookup = 0 if cfg.tie_embeddings else cfg.vocab_size * cfg.d_model
    return cfg.active_param_count() - lookup


def _attn_flops(cfg: ModelConfig, pairs: float) -> float:
    """Score and value matmul FLOPs over ``pairs`` query-key pairs:
    2 * (QK^T) + 2 * (PV) per head per layer."""
    return 4.0 * pairs * cfg.resolved_head_dim * cfg.num_heads * cfg.num_layers


def _causal_pairs(n: float) -> float:
    return n * (n + 1) / 2.0


def train_step_flops(cfg: ModelConfig, batch: int, seq_len: int) -> float:
    """One optimizer step over ``batch`` sequences of ``seq_len`` tokens:
    3x the causal forward (forward 1 + backward 2)."""
    return 3.0 * batch * prefill_flops(cfg, seq_len)


def prefill_flops(cfg: ModelConfig, prompt_len: int) -> float:
    """Forward over one prompt (causal: token i attends to i+1 keys)."""
    param = 2.0 * matmul_param_count(cfg) * prompt_len
    return param + _attn_flops(cfg, _causal_pairs(prompt_len))


def decode_flops(cfg: ModelConfig, contexts) -> float:
    """One batched decode step; ``contexts`` = per-live-slot KV lengths."""
    contexts = np.asarray(contexts, dtype=np.float64)
    param = 2.0 * matmul_param_count(cfg) * float(contexts.size)
    return param + _attn_flops(cfg, float(np.sum(contexts + 1.0)))


def verify_flops(cfg: ModelConfig, contexts, k: int) -> float:
    """One speculative verify: K+1 teacher-forced tokens per slot, each
    attending over its (growing) context."""
    contexts = np.asarray(contexts, dtype=np.float64)
    pairs = float(np.sum(contexts)) * (k + 1) + contexts.size * _causal_pairs(k + 1)
    param = 2.0 * matmul_param_count(cfg) * float(contexts.size) * (k + 1)
    return param + _attn_flops(cfg, pairs)


def paper_ideal_flops_per_s(
    seq_len: int,
    head_dim: int = 128,
    array: ArrayConfig = PAPER_ARRAY,
) -> float:
    """FLOPs/s FSA achieves on this attention shape per Fig. 11: the
    ``systolic_model`` closed-form utilization times the array peak."""
    util = systolic_model.fsa_utilization(
        seq_len, head_dim, array.array_n,
        single_direction=array.single_direction,
    )
    return util * array.peak_flops_per_s


class MFUMeter:
    """Per-phase MFU gauges on a ``repro.obs`` registry.

    ``record(phase, flops, seconds)`` computes achieved FLOPs/s and divides
    it by the peak of ``chips`` devices: ``peak_flops_per_s`` per device
    when given, else the bf16 peak of the first device's kind.  Where
    neither is known the meter counts FLOPs but sets no ``mfu`` gauge and
    records ``mfu`` as None.  Returns the computed record as a plain dict."""

    def __init__(self, cfg: ModelConfig, registry, *,
                 peak_flops_per_s: Optional[float] = None, chips: int = 1,
                 prefix: str = ""):
        if peak_flops_per_s is None:
            peak = device_peak(jax.devices()[0].device_kind)
            peak_flops_per_s = peak["bf16_flops_per_s"] if peak else None
        self.cfg = cfg
        self.peak_flops_per_s = (
            peak_flops_per_s * chips if peak_flops_per_s else None
        )
        p = prefix
        self.registry = registry
        self._flops_total = registry.counter(
            p + "model_flops_total", "cumulative model FLOPs", ("phase",)
        )
        self._flops_per_s = registry.gauge(
            p + "model_flops_per_s", "achieved model FLOPs/s (last call)",
            ("phase",),
        )
        self._mfu = registry.gauge(
            p + "mfu",
            "model FLOPs utilization vs the devices' bf16 peak "
            f"({self.peak_flops_per_s / 1e12:.3f} TFLOP/s)",
            ("phase",),
        ) if self.peak_flops_per_s else None

    def record(self, phase: str, flops: float, seconds: float) -> dict:
        seconds = max(float(seconds), 1e-12)
        fps = flops / seconds
        self._flops_total.labels(phase=phase).inc(flops)
        self._flops_per_s.labels(phase=phase).set(fps)
        mfu = None
        if self._mfu is not None:
            mfu = fps / self.peak_flops_per_s
            self._mfu.labels(phase=phase).set(mfu)
        return {"phase": phase, "flops": flops, "flops_per_s": fps, "mfu": mfu}

    # -- phase-specific conveniences ---------------------------------------

    def train_step(self, batch: int, seq_len: int, seconds: float) -> dict:
        return self.record("train", train_step_flops(self.cfg, batch, seq_len), seconds)

    def prefill(self, prompt_len: int, seconds: float) -> dict:
        return self.record("prefill", prefill_flops(self.cfg, prompt_len), seconds)

    def decode(self, contexts, seconds: float) -> dict:
        return self.record("decode", decode_flops(self.cfg, contexts), seconds)

    def verify(self, contexts, k: int, seconds: float) -> dict:
        return self.record("verify", verify_flops(self.cfg, contexts, k), seconds)
