"""Model FLOP utilization of serving: model operations of the prefill
programs and decode steps that ran wholly in the traced window, over the
window and the chip's bf16 peak.  A prefill counts at the request's true
prompt length (``counts.prefill_flops``), a decode step one token per live
slot at that slot's length (``counts.decode_flops``).  Programs are matched
in order to the prefills and decode calls that the cell recorded.

The programs' names come from the engine's private ``_prefill`` and
``serve_step`` functions: where the cell recorded prefills or decode calls
in the traced steps and no program of that name ran, the reader raises
rather than go quiet."""

from chipbench import counts, trace

PREFILL, DECODE = r"^jit__prefill\(", r"^jit_serve_step\("


def read(run):
    pre = trace.module_events(run.trace, PREFILL)
    dec = trace.module_events(run.trace, DECODE)
    cell, a = run.cell, run.arch
    recorded = cell.traced(run.trace)
    for progs, kind, pattern in ((pre, "prefills", PREFILL), (dec, "decodes", DECODE)):
        if recorded[kind] and not progs:
            raise ValueError(f"{len(recorded[kind])} {kind} recorded in the traced steps, "
                             f"no program matches {pattern!r}")
    if not (pre or dec):
        return None
    if len(pre) > len(cell.prefills) or len(dec) > len(cell.decodes):
        raise ValueError(f"traced {len(pre)} prefills and {len(dec)} decode steps, recorded "
                         f"{len(cell.prefills)} and {len(cell.decodes)}")
    flops = sum(counts.prefill_flops(a, n) for n, _ in cell.prefills[:len(pre)])
    flops += sum(counts.decode_flops(a, int(k)) for keys in cell.decodes[:len(dec)] for k in keys)
    return 100.0 * flops / (run.trace.window_s * run.peak["bf16_flops_per_s"])
