"""The training cell's per-layer readers on a synthetic trace: the flash
roofline takes its work from the model and the steps in the window, and
only its time from the kernels' tagged events; a Mosaic kernel that is not
a flash kernel is left out, and flash calls at shapes that do not fit the
model are errors; with no step in the window a reader reads nothing.  The
tags give the same calls, and so the same number, as the signatures that
the reader matched before the kernels were tagged."""

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


def call(results, operands, tag=None):
    ops = ", ".join(f"{o} %x{i}" for i, o in enumerate(operands))
    meta = f'{{\n"kernel":"{tag}"\n}}' if tag else "{}"
    return (f'%k = {results} custom-call({ops}), custom_call_target="tpu_custom_call", '
            f"frontend_attributes={{kernel_metadata={meta}}}")


FWD = call(f"({Q}, {LSE})", [Q, KV, KV], "flash_fwd")
DQ = call(Q, [Q, KV, KV, Q, LSE, LSE], "flash_dq")
DKV = call(f"({Q}, {Q})", [Q, KV, KV, Q, LSE, LSE], "flash_dkv")


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
    """Another Mosaic kernel, tagged or not, is no longer refused: it is
    left out, and the reading is the one without it."""
    read = spec.reader("flash_roofline.train")
    want = read(run_of(step_ops(0)))
    for tag in (None, "pwl_exp2"):
        other = ev(call("bf16[16,128]{1,0}", ["bf16[16,128]{1,0}"], tag), 900, 950)
        assert read(run_of(step_ops(0) + [other])) == pytest.approx(want)


def test_flash_roofline_refuses_calls_that_do_not_fit_the_model():
    q8 = f"bf16[{BH},256,128]{{2,1,0}}"
    k8 = f"bf16[{BKV},256,128]{{2,1,0}}"
    with pytest.raises(ValueError, match="fit"):
        spec.reader("flash_roofline.train")(
            run_of([ev(call(q8, [q8, k8, k8], "flash_fwd"), 0, 100)]))


def _by_signature(run):
    """The reading as it was taken before the kernels were tagged: the
    flash calls found by their signature (``trace.kernel_kind``)."""
    steps = trace.module_events(run.trace, r"^jit_train_step\(")
    ev_ = trace.kernel_events(run.trace)
    flash = trace.inside(ev_.get("flash_fwd", []) + ev_.get("flash_dq", [])
                         + ev_.get("flash_dkv", []), steps)
    shape = (MIX["batch"], MIX["seq_len"], MIX["seq_len"], 4, 2, 64)
    p = peaks.peaks("TPU v5 lite")
    t_fwd, _ = counts.roofline_s(*counts.flash_fwd(*shape, with_lse=True), p)
    t_bwd, _ = counts.roofline_s(*counts.flash_bwd(*shape), p)
    return 100.0 * len(steps) * ARCH["layers"] * (t_fwd + t_bwd) / (sum(e.dur for e in flash) / 1e9)


def test_tags_and_signatures_find_the_same_calls():
    # Two steps of unequal call times, and a call outside every step.
    ops = step_ops(0) + [ev(k.name, k.start_ns + 1000, k.start_ns + 1000 + 37 * (i + 1))
                         for i, k in enumerate(step_ops(0))] + [ev(FWD, 2100, 2400)]
    run = run_of(ops)
    assert spec.reader("flash_roofline.train")(run) == pytest.approx(_by_signature(run), rel=1e-12)


@pytest.mark.parametrize("metric", ["flash_roofline.train", "mfu.train"])
def test_no_step_in_the_window_reads_nothing(metric):
    assert spec.reader(metric)(run_of(step_ops(0), steps=((0, 3000),))) is None


def test_mfu_counts_whole_steps_over_the_window():
    got = spec.reader("mfu.train")(run_of(step_ops(0)))
    flops = 2 * MIX["batch"] * counts.train_flops(ARCH, MIX["seq_len"])
    assert got == pytest.approx(100 * flops / (2500e-9 * 197e12))
