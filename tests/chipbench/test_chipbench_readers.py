"""The training cell's per-layer readers on a synthetic trace: the flash
roofline takes its work from the model and the steps in the window, and
only its time from the kernels' events; a Mosaic kernel that is not the
flash kernel, or flash calls at shapes that do not fit the model, are
errors; with no step in the window a reader reads nothing."""

import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import counts, peaks, spec, trace  # noqa: E402

ARCH = dict(layers=2, d_model=256, heads=4, kv_heads=2, head_dim=64, d_ff=512, vocab=1000)
MIX = {"batch": 2, "seq_len": 256}
BH, BKV = MIX["batch"] * ARCH["heads"], MIX["batch"] * ARCH["kv_heads"]
Q, KV = f"bf16[{BH},256,64]{{2,1,0}}", f"bf16[{BKV},256,64]{{2,1,0}}"
LSE = f"f32[{BH},256,128]{{2,1,0}}"


def call(results, operands):
    ops = ", ".join(f"{o} %x{i}" for i, o in enumerate(operands))
    return f'%k = {results} custom-call({ops}), custom_call_target="tpu_custom_call"'


FWD, DQ = call(f"({Q}, {LSE})", [Q, KV, KV]), call(Q, [Q, KV, KV, Q, LSE, LSE])
DKV = call(f"({Q}, {Q})", [Q, KV, KV, Q, LSE, LSE])


def ev(name, start, end):
    return NS(name=name, start_ns=float(start), duration_ns=float(end - start))


def run_of(ops, steps=((0, 1000), (1000, 2000))):
    planes = [
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Modules", events=[ev("jit_train_step(1)", s, e) for s, e in steps]),
            NS(name="XLA Ops", events=ops)]),
        NS(name="/host:CPU", lines=[NS(name="py", events=[ev(trace.WINDOW_SPAN, 0, 2500)])]),
    ]
    return NS(trace=trace.from_planes(planes), arch=ARCH, mix=MIX,
              peak=peaks.peaks("TPU v5 lite"))


def step_ops(t0):
    # Per layer: a forward, its remat recompute, dq and dkv, 100 ns each.
    return [ev(k, t0 + 100 * i, t0 + 100 * i + 100)
            for i, k in enumerate([FWD, FWD, DQ, DKV] * ARCH["layers"])]


def test_flash_roofline_counts_the_model_work_and_the_kernels_time():
    read = spec.reader("flash_roofline.train")
    got = read(run_of(step_ops(0) + step_ops(1000)))
    shape = (2, 256, 256, 4, 2, 64)
    t_fwd, _ = counts.roofline_s(*counts.flash_fwd(*shape, with_lse=True), peaks.peaks("TPU v5 lite"))
    t_bwd, _ = counts.roofline_s(*counts.flash_bwd(*shape), peaks.peaks("TPU v5 lite"))
    # Two steps of two layers of work, over 16 kernel events of 100 ns:
    # the recompute adds time, not work.
    assert got == pytest.approx(100 * 2 * 2 * (t_fwd + t_bwd) / 1.6e-6)


def test_flash_roofline_refuses_another_mosaic_kernel():
    other = ev(call("bf16[16,128]{1,0}", ["bf16[16,128]{1,0}"]), 900, 950)
    with pytest.raises(ValueError, match="signature"):
        spec.reader("flash_roofline.train")(run_of(step_ops(0) + [other]))


def test_flash_roofline_refuses_calls_that_do_not_fit_the_model():
    q8 = f"bf16[{BH},256,128]{{2,1,0}}"
    k8 = f"bf16[{BKV},256,128]{{2,1,0}}"
    with pytest.raises(ValueError, match="fit"):
        spec.reader("flash_roofline.train")(run_of([ev(call(q8, [q8, k8, k8]), 0, 100)]))


@pytest.mark.parametrize("metric", ["flash_roofline.train", "mfu.train"])
def test_no_step_in_the_window_reads_nothing(metric):
    assert spec.reader(metric)(run_of(step_ops(0), steps=((0, 3000),))) is None


def test_mfu_counts_whole_steps_over_the_window():
    got = spec.reader("mfu.train")(run_of(step_ops(0)))
    flops = 2 * MIX["batch"] * counts.train_flops(ARCH, MIX["seq_len"])
    assert got == pytest.approx(100 * flops / (2500e-9 * 197e12))
