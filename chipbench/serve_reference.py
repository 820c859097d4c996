"""The serving reference: the full-forward logits of one sequence, in
float32 at ``highest`` matmul precision, layer by layer, with no kernel,
cache or batching.  It takes its layer from ``reference.py`` and imports
nothing of the program.  The bf16 weights stay as the benchmark made them;
one layer at a time is upcast inside the jitted layer, so the reference fits
beside them.

``mode="fp8"`` is the control, as in ``reference.py``: every matmul operand
rounded to float8 e4m3.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .reference import _f32, _head, _norm, layer, mm

# Sequences are padded at the end to a multiple of this (causal attention
# leaves the rows before the padding as they were), so a run compiles a few
# shapes, not one per length.
PAD = 1024
# Rows of logits are read in groups of this many.
ROWS = 32


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(arch_items, mode, x, layers, i):
    lp = _f32(jax.tree.map(lambda a: a[i], layers))
    return layer(dict(arch_items), mode, x, lp)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _logits(arch_items, mode, x, rows, final_norm, head):
    h = _norm(dict(arch_items), x[rows], _f32(final_norm))
    return mm(mode, "sd,dv->sv", h, head.astype(jnp.float32))


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(jnp.float32)


def logits(arch: dict, params, tokens, rows, mode: str = "fp32") -> np.ndarray:
    """Logits [len(rows), vocab] (float32) at positions ``rows`` of the
    sequence ``tokens``, position = index from 0."""
    n = len(tokens)
    toks = np.zeros(-(-n // PAD) * PAD, np.int32)
    toks[:n] = tokens
    padded = np.zeros(-(-max(len(rows), 1) // ROWS) * ROWS, np.int32)
    padded[:len(rows)] = rows
    items = tuple(sorted(arch.items()))
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(toks))
        for i in range(arch["layers"]):
            x = _layer(items, mode, x, params["layers"], jnp.int32(i))
        out = _logits(items, mode, x, jnp.asarray(padded), params.get("final_norm"),
                      _head(params))
    return np.asarray(out, np.float32)[:len(rows)]
