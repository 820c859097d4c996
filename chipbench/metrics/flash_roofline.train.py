"""Roofline share of the flash kernels in training.  The work is the
model's: in each training step that ran wholly inside the traced window,
one causal forward (with its LSE) and one backward (dq, dk, dv) per layer
at the step's shapes, so a remat recompute, or a kernel split into more
calls, adds time and no work.  The time is the device time of the flash
kernels' events inside those steps.  A Mosaic kernel in the window that
does not have the flash kernel's signature is an error: its time could be
neither counted nor left out safely."""

from chipbench import counts, trace


def read(run):
    steps = trace.module_events(run.trace, r"^jit_train_step\(")
    ev = trace.kernel_events(run.trace)
    if ev.get("pallas"):
        raise ValueError("a Mosaic kernel without the flash kernel's signature ran: "
                         + trace.op_name(ev["pallas"][0].name))
    a, m = run.arch, run.mix
    flash = trace.inside(ev.get("flash_fwd", []) + ev.get("flash_dq", [])
                         + ev.get("flash_dkv", []), steps)
    for e in flash:
        _, operands = trace.custom_call(e.name)
        q, k = operands[0][1], operands[1][1]
        if q[2] != a["head_dim"] or q[0] * a["kv_heads"] != k[0] * a["heads"]:
            raise ValueError(f"flash call shapes {q}, {k} do not fit the model")
    if not (steps and flash):
        return None
    shape = (m["batch"], m["seq_len"], m["seq_len"], a["heads"], a["kv_heads"], a["head_dim"])
    t_fwd, _ = counts.roofline_s(*counts.flash_fwd(*shape, with_lse=True), run.peak)
    t_bwd, _ = counts.roofline_s(*counts.flash_bwd(*shape), run.peak)
    needed = len(steps) * a["layers"] * (t_fwd + t_bwd)
    return 100.0 * needed / (sum(e.dur for e in flash) / 1e9)
