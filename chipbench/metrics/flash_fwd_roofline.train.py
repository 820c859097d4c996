"""Roofline share of the flash forward kernel in training: the roofline
time of each ``flash_fwd``-tagged call inside the training steps that ran
wholly in the traced window, at that call's own shapes (causal, with the
LSE where it returns two arrays), over those calls' device time.  A remat
recompute counts as work here, since the kernel did it.  Untagged Mosaic
calls are left out; a program without the tags reads nothing."""

from chipbench import kernel_tags as kt


def read(run):
    (fwd,) = kt.in_steps(run, "flash_fwd")
    if not fwd:
        return None
    return 100.0 * sum(kt.fwd_roofline_s(run, e) for e in fwd) / kt.device_s(fwd)
