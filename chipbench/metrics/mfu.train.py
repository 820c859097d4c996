"""Model FLOP utilization of training: model operations of the training
steps that ran wholly inside the traced window (forward and backward of the
matmul parameters and causal attention; remat not counted), over the
window and the chip's bf16 peak."""

from chipbench import counts, trace


def read(run):
    n = len(trace.module_events(run.trace, r"^jit_train_step\("))
    if not n:
        return None
    flops = n * run.mix["batch"] * counts.train_flops(run.arch, run.mix["seq_len"])
    return 100.0 * flops / (run.trace.window_s * run.peak["bf16_flops_per_s"])
