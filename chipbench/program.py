"""The system under test, as the benchmark builds it: the program's model
configuration from a configuration file, and its parameter layout.  Only
this module and the cells (`train.py`, `serve.py`) import the program."""

from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

SRC = Path(__file__).resolve().parent.parent / "src"

_NORMS = {"layernorm_nonparametric": "non_parametric", "rmsnorm": "rmsnorm"}
# The MLP of ``reference.arch_of`` -> the program's ``mlp_type``.
_MLPS = {"swiglu": "swiglu"}


def import_program():
    """Put the checkout's ``src`` on the path; raises ImportError where the
    checkout has no program."""
    if not (SRC / "repro").is_dir():
        raise ImportError(f"no program at {SRC / 'repro'}")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    import repro  # noqa: F401


def model_config(conf: dict, arch: dict):
    """The program's ModelConfig for a configuration file: the registry
    architecture named by ``arch``, with every width and form set from the
    file (``arch`` is ``reference.arch_of(conf)``, which refuses a form that
    the reference does not compute)."""
    from repro.configs.registry import get_config

    return dataclasses.replace(
        get_config(conf["arch"]),
        num_layers=arch["layers"], d_model=arch["d_model"], num_heads=arch["heads"],
        num_kv_heads=arch["kv_heads"], head_dim=arch["head_dim"], d_ff=arch["d_ff"],
        vocab_size=arch["vocab"], tie_embeddings=arch["tied"],
        rope_theta=arch["rope_theta"], norm_type=_NORMS[arch["norm"]],
        mlp_type=_MLPS[arch["mlp"]], dtype=conf["dtype"],
    )


def param_shapes(cfg):
    from repro.models import param_shapes as shapes

    return shapes(cfg)
