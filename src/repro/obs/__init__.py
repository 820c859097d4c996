"""repro.obs — unified telemetry: metrics registry, tracing, MFU accounting.

Zero-dependency observability substrate (ISSUE 10).  Three pieces:

  * :mod:`repro.obs.metrics` — labeled Counter/Gauge/Histogram registry
    with Prometheus-text and JSON exposition, percentile summaries, a
    global off switch whose no-op path costs ~a guarded return, and the
    XLA compile-event watcher.
  * :mod:`repro.obs.trace` — Chrome-trace/Perfetto span + event tracer
    (``{"ph": "X", "ts": ...}``) with ``jax.profiler.TraceAnnotation``
    pass-through; ``NullTracer`` is the free disabled twin.
  * :mod:`repro.obs.mfu` — model-FLOPs-utilization accounting against the
    device's bf16 peak (:mod:`repro.obs.peaks`, keyed by ``device_kind``),
    with the paper's FSA array kept as the Fig. 11 paper-ideal reference.

The serve engine, trainer, and fault-tolerance layer all report through
this package; ``launch/serve.py --metrics-out m.prom --trace-out t.json``
(and the train launcher) dump the exposition files at exit.
"""

from .metrics import (
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    JitCompileWatcher,
    Registry,
    default_registry,
    enabled,
    set_enabled,
    watch_jit_compiles,
)
from .mfu import (
    PAPER_ARRAY,
    ArrayConfig,
    MFUMeter,
    decode_flops,
    matmul_param_count,
    paper_ideal_flops_per_s,
    prefill_flops,
    train_step_flops,
    verify_flops,
)
from .peaks import PEAKS, device_peak
from .trace import NullTracer, Tracer, get_tracer, set_tracer

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "Registry",
    "DEFAULT_BUCKETS",
    "default_registry",
    "enabled",
    "set_enabled",
    "JitCompileWatcher",
    "watch_jit_compiles",
    "Tracer",
    "NullTracer",
    "get_tracer",
    "set_tracer",
    "ArrayConfig",
    "PAPER_ARRAY",
    "MFUMeter",
    "PEAKS",
    "device_peak",
    "matmul_param_count",
    "train_step_flops",
    "prefill_flops",
    "decode_flops",
    "verify_flops",
    "paper_ideal_flops_per_s",
]
