"""Model FLOP utilization of serving a model with windowed and full
attention and sparse experts: model operations of the prefill programs and
decode steps that ran wholly in the traced window, over the window and the
chip's bf16 peak.  A prefill counts at the request's true prompt length, a
decode step one token per live slot at that slot's length, both with the
active parameters and each layer's own attention pairs
(``counts_swa_moe``).  Programs are matched in order to the prefills and
decode calls that the cell recorded.

Where the cell recorded prefills or decode calls in the traced steps and
no program of the name ran, the reader raises rather than go quiet."""

from chipbench import counts_swa_moe, trace

PREFILL, DECODE = r"^jit__prefill\(", r"^jit_serve_step\("


def read(run):
    pre = trace.module_events(run.trace, PREFILL)
    dec = trace.module_events(run.trace, DECODE)
    cell = run.cell
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
    s = counts_swa_moe.shape_of(cell.conf)
    flops = sum(counts_swa_moe.prefill_flops(s, n) for n, _ in cell.prefills[:len(pre)])
    flops += sum(counts_swa_moe.decode_flops(s, int(k))
                 for keys in cell.decodes[:len(dec)] for k in keys)
    return 100.0 * flops / (run.trace.window_s * run.peak["bf16_flops_per_s"])
