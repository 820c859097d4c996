"""The program's Pallas kernels by the tag they carry.

Each ``pallas_call`` of the program is given ``metadata={"kernel": <tag>}``,
which the compiler writes into the custom call's frontend attributes as
``kernel_metadata``; the device trace prints it in each call's event name,
over several lines:

    frontend_attributes={kernel_metadata={
    "kernel":"flash_fwd"
    }}

Readers key on the tag alone: the instruction name is not stable (``name=``
sets it, and transforms such as remat can rename the call).  A Mosaic call
without a tag, as is every call of a program older than its tags, is left
out.
"""

from __future__ import annotations

import collections
import re

from chipbench import counts, trace

_TAG = re.compile(r'kernel_metadata=\{[^{}]*?"kernel"\s*:\s*"([^"]*)"')


def tag_of(text: str):
    """The tag of a Mosaic call's event text, or None (no Mosaic call, or
    a call without a tag)."""
    if trace.custom_call(text) is None:
        return None
    m = _TAG.search(text)
    return m[1] if m else None


def tagged(tr: trace.Trace) -> dict[str, list[trace.Event]]:
    """The window's tagged Mosaic calls by tag."""
    out = collections.defaultdict(list)
    for ev in tr.ops:
        tag = tag_of(ev.name)
        if tag:
            out[tag].append(ev)
    return out


def in_steps(run, *tags: str) -> list[list[trace.Event]]:
    """For each tag, its calls inside the training steps that ran wholly in
    the traced window."""
    steps = trace.module_events(run.trace, r"^jit_train_step\(")
    by_tag = tagged(run.trace)
    return [trace.inside(by_tag.get(t, []), steps) for t in tags]


def flash_shape(run, ev: trace.Event):
    """(batch, sq, sk, heads, kv_heads, head_dim) of a flash call, read from
    its head-major q and k operands ``[heads x batch, seq, head_dim]``;
    raises where they do not fit the model."""
    q, k = ([o[1] if o else () for o in trace.custom_call(ev.name)[1]] + [(), ()])[:2]
    a = run.arch
    if (len(q) != 3 or len(k) != 3 or q[2] != a["head_dim"] or k[2] != a["head_dim"]
            or q[0] % a["heads"] or q[0] * a["kv_heads"] != k[0] * a["heads"]):
        raise ValueError(f"flash call shapes {q}, {k} do not fit the model")
    return q[0] // a["heads"], q[1], k[1], a["heads"], a["kv_heads"], a["head_dim"]


def device_s(events: list[trace.Event]) -> float:
    return sum(e.dur for e in events) / 1e9


def fwd_roofline_s(run, ev: trace.Event) -> float:
    """Roofline seconds of one causal forward call at its own shapes (with
    the LSE where it returns two arrays)."""
    results, _ = trace.custom_call(ev.name)
    flops, nbytes = counts.flash_fwd(*flash_shape(run, ev), with_lse=len(results) == 2)
    return counts.roofline_s(flops, nbytes, run.peak)[0]


def bwd_roofline_s(run, ev: trace.Event) -> float:
    """Roofline seconds of one causal backward (dq, dk and dv together) at
    the shapes of its dq call."""
    return counts.roofline_s(*counts.flash_bwd(*flash_shape(run, ev)), run.peak)[0]
