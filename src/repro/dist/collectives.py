"""Mesh-aware logical constraint helpers.

``constrain`` is the one entry point model code uses to express layout
intent (Megatron-SP residual sharding, dp_only batch spans, ...).  It is a
*logical* annotation: axis names that don't exist on the ambient mesh are
dropped, dims whose size doesn't divide the named axes are left
unconstrained, and with no ambient mesh at all it is the identity — so the
same model code runs unmodified on a laptop CPU and on a multi-pod slice.
"""

from __future__ import annotations

import contextlib
import math
from typing import Sequence, Union

import jax
from jax.sharding import PartitionSpec as P

AxisSpec = Union[None, str, Sequence[str]]


def _ambient_mesh():
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def mesh_context(mesh):
    """``jax.set_mesh(mesh)``; a null context when ``mesh`` is None."""
    return jax.set_mesh(mesh) if mesh is not None else contextlib.nullcontext()


def _ambient_axis_names() -> tuple[str, ...]:
    """Axis names of the mesh currently in scope (() when unsharded)."""
    mesh = _ambient_mesh()
    return tuple(mesh.axis_names) if mesh is not None else ()


def _resolve_entry(entry: AxisSpec, dim_size: int, mesh) -> AxisSpec:
    """Filter one PartitionSpec entry against a concrete mesh."""
    if entry is None:
        return None
    axes = (entry,) if isinstance(entry, str) else tuple(entry)
    axes = tuple(a for a in axes if a in mesh.axis_names)
    if not axes:
        return None
    total = 1
    for a in axes:
        total *= int(mesh.shape[a])
    if total == 1 or dim_size % total != 0:
        return None
    return axes[0] if len(axes) == 1 else axes


def constrain(x: jax.Array, *spec: AxisSpec) -> jax.Array:
    """``with_sharding_constraint`` against the ambient mesh, forgivingly.

    ``spec`` gives one entry per dim of ``x``: an axis name, a tuple of
    axis names (the dim is sharded over their product), or None.  Missing
    trailing entries mean unconstrained.  No-op without an ambient mesh.
    """
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    entries = [
        _resolve_entry(spec[d] if d < len(spec) else None, x.shape[d], mesh)
        for d in range(x.ndim)
    ]
    if all(e is None for e in entries):
        return x
    return jax.lax.with_sharding_constraint(x, P(*entries))


def map_heads(f, q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Run attention ``f`` on each device's block of ``[B, S, H, d]`` q/k/v.

    GSPMD cannot partition a Mosaic (Pallas TPU) kernel, so under an
    ambient mesh the kernel runs inside ``shard_map``: batch over the data
    axes, heads over "model" (the layout the column-parallel QKV
    projections produce).  Heads stay whole unless "model" divides both the
    query and the KV head counts, so every shard keeps its GQA groups.
    Identity wrapper without a mesh.
    """
    mesh = _ambient_mesh()
    if mesh is None:
        return f(q, k, v)
    heads = math.gcd(q.shape[2], k.shape[2])
    spec = P(
        _resolve_entry(("pod", "data"), q.shape[0], mesh),
        None,
        _resolve_entry("model", heads, mesh),
        None,
    )
    return jax.shard_map(
        f, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False
    )(q, k, v)


def psum_mean(x: jax.Array, axis_name: str) -> jax.Array:
    """Mean-reduce across one mesh axis (shard_map bodies only)."""
    return jax.lax.psum(x, axis_name) / jax.lax.psum(
        jax.numpy.ones((), x.dtype), axis_name
    )
