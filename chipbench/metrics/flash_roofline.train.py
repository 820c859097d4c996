"""Roofline share of the flash kernels in training.  The work is the
model's: in each training step that ran wholly inside the traced window,
one causal forward (with its LSE) and one backward (dq, dk, dv) per layer
at the step's shapes, so a remat recompute, or a kernel split into more
calls, adds time and no work.  The time is the device time of the calls
tagged ``flash_fwd``, ``flash_dq`` and ``flash_dkv`` inside those steps;
untagged Mosaic calls, and calls with other tags, are left out.  A tagged
call that does not fit the model is an error."""

from chipbench import counts, trace
from chipbench import kernel_tags as kt


def read(run):
    flash = [e for calls in kt.in_steps(run, "flash_fwd", "flash_dq", "flash_dkv")
             for e in calls]
    for e in flash:
        kt.flash_shape(run, e)
    steps = trace.module_events(run.trace, r"^jit_train_step\(")
    if not (steps and flash):
        return None
    a, m = run.arch, run.mix
    shape = (m["batch"], m["seq_len"], m["seq_len"], a["heads"], a["kv_heads"], a["head_dim"])
    t_fwd, _ = counts.roofline_s(*counts.flash_fwd(*shape, with_lse=True), run.peak)
    t_bwd, _ = counts.roofline_s(*counts.flash_bwd(*shape), run.peak)
    needed = len(steps) * a["layers"] * (t_fwd + t_bwd)
    return 100.0 * needed / kt.device_s(flash)
