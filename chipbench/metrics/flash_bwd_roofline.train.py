"""Roofline share of the flash backward kernels in training: the roofline
time of one causal backward (dq, dk and dv together) at the shapes of each
``flash_dq``-tagged call inside the training steps that ran wholly in the
traced window, over the device time of those steps' ``flash_dq`` and
``flash_dkv`` calls.  Unequal numbers of dq and dkv calls are an error.
Untagged Mosaic calls are left out; a program without the tags reads
nothing."""

from chipbench import kernel_tags as kt


def read(run):
    dq, dkv = kt.in_steps(run, "flash_dq", "flash_dkv")
    if not (dq or dkv):
        return None
    if len(dq) != len(dkv):
        raise ValueError(f"{len(dq)} flash_dq calls but {len(dkv)} flash_dkv calls")
    for e in dkv:
        kt.flash_shape(run, e)
    return 100.0 * sum(kt.bwd_roofline_s(run, e) for e in dq) / kt.device_s(dq + dkv)
