"""repro.dist — the distributed-execution substrate.

Layers (each usable on its own):

  * ``collectives`` — mesh-aware logical sharding constraints (``constrain``)
    and ambient-mesh introspection used by the model code;
  * ``sharding``    — path-based TP/DP/SP partition rules over the
    ("pod", "data", "model") mesh: params, optimizer state (ZeRO-1),
    batches and KV caches;
  * ``pipeline``    — GPipe-style microbatched stage execution over the
    "pod" axis (``pipelined_apply``);
  * ``elastic``     — checkpoint-portable mesh rescale plans
    (``rescale_plan`` / ``apply_rescale``) with divisibility validation;
  * ``fault``       — step watchdog, preemption drain and restart loop
    (``StepWatchdog``, ``PreemptionHandler``, ``run_with_restarts``).

The mesh convention everywhere: axis "model" carries tensor parallelism,
"data" carries data parallelism (plus ZeRO-1 optimizer-state partitioning
and MoE expert-weight ZeRO-3), "pod" carries either pipeline stages
(``pipeline``) or an extra data-parallel dimension (it folds into DP in
``sharding``'s batch rules).
"""

from .collectives import constrain, mesh_context  # noqa: F401
from .elastic import RescalePlan, apply_rescale, rescale_plan  # noqa: F401
from .fault import (  # noqa: F401
    PreemptionHandler,
    StepWatchdog,
    StragglerDetected,
    run_with_restarts,
)
from .pipeline import pipelined_apply  # noqa: F401
from .sharding import (  # noqa: F401
    batch_pspec,
    cache_shardings,
    param_pspec,
    param_shardings,
    zero1_shardings,
)

__all__ = [
    "constrain",
    "RescalePlan", "apply_rescale", "rescale_plan",
    "PreemptionHandler", "StepWatchdog", "StragglerDetected",
    "run_with_restarts",
    "pipelined_apply",
    "batch_pspec", "cache_shardings", "param_pspec", "param_shardings",
    "zero1_shardings",
]
