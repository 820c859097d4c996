"""Reduction of one profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy and idle time, each operation's self time, the
flash kernels' events, the program executions, and the host span that was
open in each idle gap.

Device and host events are on one clock (nanoseconds from the start of the
trace).  The traced window is the host span ``chipbench.window`` that the
harness opens around the traced part of the run.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

WINDOW_SPAN = "chipbench.window"
# Host spans that name what the host was doing: the harness's own, and the
# program's (repro.obs Tracer spans pass through to the profiler): the
# trainer's step phases and the serving engine's live phases.
HOST_SPANS = ("chipbench.", "train_step", "prefill", "generate")



@dataclasses.dataclass
class Event:
    name: str
    start: float  # ns
    end: float  # ns

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    window: tuple[float, float]
    ops: list[Event]  # device "XLA Ops", nested events included
    modules: list[Event]  # device "XLA Modules": one per program execution
    host: list[Event]  # host spans named by HOST_SPANS
    devices: int

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


def options():
    """Profiler options: host annotations kept, Python call tracing off."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[0])
    return from_planes(pd.planes)


def from_planes(planes) -> Trace:
    """Build a Trace from xplane planes (anything with ``.name``, ``.lines``;
    lines with ``.name``, ``.events``; events with ``.name``, ``.start_ns``,
    ``.duration_ns``)."""
    ops, modules, host, devices = [], [], [], 0
    first_device = True
    for plane in planes:
        if plane.name.startswith("/device:TPU:") or plane.name.startswith("/device:GPU:"):
            devices += 1
            if not first_device:  # busy time is averaged over chips by the caller
                continue
            first_device = False
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [Event(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
                elif line.name == "XLA Modules":
                    modules = [Event(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_SPANS):
                        host.append(Event(e.name, e.start_ns, e.start_ns + e.duration_ns))
    wins = [h for h in host if h.name == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    win = (wins[0].start, wins[0].end)
    clip = lambda evs: [e for e in evs if e.end > win[0] and e.start < win[1]]  # noqa: E731
    return Trace(win, clip(ops), clip(modules), [h for h in host if h.name != WINDOW_SPAN],
                 devices)


def merged(intervals, lo, hi) -> list[tuple[float, float]]:
    """Union of intervals, clipped to [lo, hi], as sorted disjoint pieces."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def busy_s(tr: Trace) -> float:
    lo, hi = tr.window
    return sum(e - s for s, e in merged(((o.start, o.end) for o in tr.ops), lo, hi)) / 1e9


def idle_gaps(tr: Trace) -> list[tuple[float, float]]:
    lo, hi = tr.window
    gaps, t = [], lo
    for s, e in merged(((o.start, o.end) for o in tr.ops), lo, hi):
        if s > t:
            gaps.append((t, s))
        t = e
    if hi > t:
        gaps.append((t, hi))
    return gaps


def attribute_gaps(tr: Trace) -> list[list]:
    """Idle seconds by the innermost host span open at each gap's middle
    (``host: no span`` where none was), largest first."""
    total = collections.defaultdict(float)
    for s, e in idle_gaps(tr):
        mid = (s + e) / 2
        open_ = [h for h in tr.host if h.start <= mid < h.end]
        label = min(open_, key=lambda h: h.dur).name if open_ else "host: no span"
        total[label] += (e - s) / 1e9
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])]


def op_name(text: str) -> str:
    """Short name of an op from its HLO text: the instruction name, or the
    flash kernel it runs."""
    kind = kernel_kind(text)
    name = text.split(" = ", 1)[0].lstrip("%")
    if kind == "pallas":
        return f"pallas {name}"
    return kind or name


def _top_level(text: str, start: int) -> tuple[list[str], int]:
    """Split the parenthesised list opening at ``text[start]`` into its
    top-level items; returns them and the index after the closing one."""
    depth, items, cur = 0, [], []
    for i in range(start, len(text)):
        ch = text[i]
        if ch in "([{":
            depth += 1
            if depth == 1:
                continue
        elif ch in ")]}":
            depth -= 1
            if depth == 0:
                items.append("".join(cur).strip())
                return [x for x in items if x], i + 1
        elif ch == "," and depth == 1:
            items.append("".join(cur).strip())
            cur = []
            continue
        cur.append(ch)
    return [x for x in items if x], len(text)


_SHAPE = re.compile(r"^\(?\s*([a-z0-9]+)\[([0-9,]*)\]")


def _shape(item: str):
    """(dtype, dims) of an HLO operand or result such as
    ``bf16[32,2048,128]{2,1,0:T(8,128)(2,1)} %q``."""
    m = _SHAPE.match(item.strip())
    if not m:
        return None
    return m.group(1), tuple(int(x) for x in m.group(2).split(",") if x)


def custom_call(text: str):
    """(result shapes, operand shapes) of a Mosaic (Pallas TPU) custom
    call, or None for any other op."""
    if "custom-call(" not in text or 'custom_call_target="tpu_custom_call"' not in text:
        return None
    text = re.sub(r"/\*.*?\*/", "", text)
    head, _, _ = text.partition(" custom-call(")
    result = head.split(" = ", 1)[-1].strip()
    results = _top_level(result, 0)[0] if result.startswith("(") else [result]
    # Operands are written with their shapes in some dumps and by name alone
    # in others; the layout constraints list every operand's shape in order.
    constraints = text.find("operand_layout_constraints={")
    if constraints >= 0:
        operands, _ = _top_level(text, constraints + len("operand_layout_constraints="))
    else:
        operands, _ = _top_level(text, text.index(" custom-call(") + len(" custom-call"))
    return [_shape(r) for r in results], [_shape(o) for o in operands]


def kernel_kind(text: str):
    """Which flash kernel a device op runs: ``flash_fwd``, ``flash_dq`` or
    ``flash_dkv``; ``pallas`` for any other Mosaic kernel; None for an op
    that is not a Mosaic kernel.  The Mosaic calls carry no kernel name in
    the trace, so the flash calls are told apart by their signature, on
    head-major arrays ``[heads x batch, seq, head_dim]``: q, k, v first
    (k and v alike, q's heads a multiple of k's); the forward takes those
    three and returns o (and the LSE); dq and dkv take q, k, v, dO, the LSE
    and delta, and return dq, or dk and dv."""
    call = custom_call(text)
    if call is None:
        return None
    results, operands = call
    shapes = [x[1] if x else None for x in operands]
    outs = [x[1] if x else None for x in results]
    if len(shapes) < 3 or any(s is None or len(s) != 3 for s in shapes[:3]):
        return "pallas"
    q, k, v = shapes[:3]
    if k != v or q[2] != k[2] or q[0] % k[0]:
        return "pallas"
    if len(shapes) == 3 and outs and outs[0] == q and len(outs) <= 2:
        return "flash_fwd"
    if len(shapes) == 6 and shapes[3] == q:
        if outs == [q]:
            return "flash_dq"
        if len(outs) == 2 and outs[0] == outs[1] and outs[0][::2] == q[::2]:
            return "flash_dkv"
    return "pallas"


def self_times(tr: Trace) -> dict[str, float]:
    """Seconds of each op's own time in the window: its duration less the
    ops nested inside it (a loop's body runs inside the loop's event)."""
    lo, hi = tr.window
    out = collections.defaultdict(float)
    stack: list[list] = []  # [event, child ns]

    def close(item):
        ev, child = item
        dur = min(ev.end, hi) - max(ev.start, lo)
        out[op_name(ev.name)] += max(dur - child, 0.0) / 1e9
        if stack:
            stack[-1][1] += dur

    for ev in sorted(tr.ops, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0].end <= ev.start:
            close(stack.pop())
        stack.append([ev, 0.0])
    while stack:
        close(stack.pop())
    return out


def kernel_events(tr: Trace) -> dict[str, list[Event]]:
    """The window's Mosaic kernel events by ``kernel_kind``."""
    out = collections.defaultdict(list)
    for ev in tr.ops:
        kind = kernel_kind(ev.name)
        if kind:
            out[kind].append(ev)
    return out


def module_events(tr: Trace, pattern: str) -> list[Event]:
    """Program executions whose name (``jit_<function>(<id>)``) matches and
    that ran wholly inside the traced window."""
    rx = re.compile(pattern)
    lo, hi = tr.window
    return [m for m in tr.modules if rx.search(m.name) and m.start >= lo and m.end <= hi]


def inside(events: list[Event], spans: list[Event]) -> list[Event]:
    """The events that lie wholly inside one of ``spans``."""
    return [e for e in events if any(s.start <= e.start and e.end <= s.end for s in spans)]


def breakdown(tr: Trace) -> dict:
    top = sorted(self_times(tr).items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": attribute_gaps(tr)[:10]}
