"""Roofline share of the windowed flash forward in serving's prefill: for
the prefill programs (``jit__prefill``) that ran wholly in the traced
window, the roofline time of one windowed forward per sliding layer at each
request's true prompt length (``counts_swa_moe.flash_fwd_window``), over
the device time of the ``flash_fwd_window``-tagged calls inside those
programs.  Programs are matched in order to the prefills that the cell
recorded; a program whose calls read another bucket than the recorded one
is an error.  A program without the tag (one older than the windowed
kernel) reads nothing.

Where the cell recorded prefills in the traced steps and no program of
that name ran, the reader raises rather than go quiet."""

from chipbench import counts, counts_swa_moe, trace
from chipbench import kernel_tags as kt

PREFILL = r"^jit__prefill\("


def read(run):
    progs = trace.module_events(run.trace, PREFILL)
    in_steps = run.cell.traced(run.trace)["prefills"]
    if not progs:
        if in_steps:
            raise ValueError(f"{len(in_steps)} prefills recorded in the traced steps, "
                             f"no program matches {PREFILL!r}")
        return None
    fwd = kt.tagged(run.trace).get("flash_fwd_window", [])
    if not fwd:
        return None
    recorded = run.cell.prefills
    if len(progs) > len(recorded):
        raise ValueError(f"{len(progs)} prefill programs traced, {len(recorded)} recorded")
    s = counts_swa_moe.shape_of(run.cell.conf)
    sliding = s["kinds"].count("sliding")
    need = took = 0.0
    for prog, (n, bucket) in zip(sorted(progs, key=lambda e: e.start), recorded):
        calls = trace.inside(fwd, [prog])
        if not calls:
            raise ValueError(f"a prefill program without flash_fwd_window calls at {prog.start}")
        if max(kt.flash_shape(run, e)[2] for e in calls) != bucket:
            raise ValueError(f"prefill of {n} tokens: windowed calls do not read bucket {bucket}")
        flops, nbytes = counts_swa_moe.flash_fwd_window(
            1, n, s["heads"], s["kv_heads"], s["head_dim"], s["window"])
        need += sliding * counts.roofline_s(flops, nbytes, run.peak)[0]
        took += kt.device_s(calls)
    return 100.0 * need / took
