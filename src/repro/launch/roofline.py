"""Roofline-term extraction from compiled dry-run artifacts.

Per the brief (TPU v5e targets):
  compute term    = HLO_FLOPs / (chips x 197e12 FLOP/s)
  memory term     = HLO_bytes / (chips x 819e9 B/s)
  collective term = collective operand bytes / (chips x 50e9 B/s per link)

HLO_FLOPs / bytes come from ``compiled.cost_analysis()``.  Collective bytes
are NOT in cost_analysis: we parse the optimized HLO text and sum the
operand sizes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute op.
"""

from __future__ import annotations

import dataclasses
import re

from repro.obs.peaks import PEAKS

_V5E = PEAKS["TPU v5 lite"]
PEAK_FLOPS = _V5E["bf16_flops_per_s"]  # per chip
HBM_BW = _V5E["hbm_bytes_per_s"]  # per chip
LINK_BW = 50e9  # bytes/s per ICI link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVE_RE = re.compile(
    r"^\s*(?:%\S+\s*=\s*)?"
    r"(?:\(?[a-z0-9\[\]{}, ـ/_.\-]*\)?\s*)?"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
    re.IGNORECASE,
)

_SHAPE_RE = re.compile(r"(pred|[suf]\d+|bf16|f8e4m3fn|f8e5m2|c64|c128)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum *output* shape bytes per collective kind from optimized HLO.

    Each collective line looks like::

        %ag = bf16[16,4096]{...} all-gather(bf16[1,4096]{...} %x), ...

    We count the result shape (the data volume that crosses links, up to a
    kind-dependent constant) and report per-kind totals.
    """
    out: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = re.search(
            r"=\s*([^=]*?)\s*(all-gather|all-reduce|reduce-scatter|all-to-all|"
            r"collective-permute)(?:-start)?\(",
            line,
        )
        if not m:
            continue
        kind = m.group(2).lower()
        result_shape = m.group(1)
        b = _shape_bytes(result_shape)
        if b:
            out[kind] = out.get(kind, 0) + b
    return out


@dataclasses.dataclass
class RooflineTerms:
    flops: float
    bytes_accessed: float
    coll_bytes: float
    coll_breakdown: dict
    chips: int
    out_bytes_per_device: float

    @property
    def t_compute(self) -> float:
        return self.flops / (self.chips * PEAK_FLOPS)

    @property
    def t_memory(self) -> float:
        return self.bytes_accessed / (self.chips * HBM_BW)

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / (self.chips * LINK_BW)

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def step_time(self) -> float:
        """Lower-bound step time: max of the three overlappable terms."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    def roofline_fraction(self) -> float:
        """Fraction of the compute roofline this step achieves if the
        dominant term were perfectly overlapped: t_compute / step_time."""
        return self.t_compute / max(self.step_time, 1e-30)


def analyze_compiled(compiled, chips: int) -> RooflineTerms:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):  # some backends return [dict]
        cost = cost[0]
    # cost_analysis() reports the *per-device* SPMD module (verified on the
    # CPU backend: an 8-way sharded matmul reports dense_flops/8).  Scale to
    # global so the brief's global/(chips*peak) formulas apply.
    flops = float(cost.get("flops", 0.0)) * chips
    byts = float(cost.get("bytes accessed", 0.0)) * chips
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = ""
    coll = collective_bytes(hlo)
    # Collective result shapes in the per-device HLO approximate the bytes
    # crossing each device's links; x chips = whole-system volume.
    coll_total = float(sum(coll.values()))
    try:
        mem = compiled.memory_analysis()
        out_bytes = float(getattr(mem, "output_size_in_bytes", 0))
    except Exception:
        out_bytes = 0.0
    return RooflineTerms(
        flops=flops,
        bytes_accessed=byts,
        coll_bytes=coll_total * chips,  # scale to whole-system volume
        coll_breakdown=coll,
        chips=chips,
        out_bytes_per_device=out_bytes,
    )


def model_flops(active_params: int, tokens: int, kind: str) -> float:
    """6·N·D for training, 2·N·D for inference forward."""
    mult = 6.0 if kind == "train" else 2.0
    return mult * active_params * tokens
