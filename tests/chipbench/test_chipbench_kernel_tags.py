"""The readers that find the program's kernels by their tags and its step
phases by their spans, on synthetic traces in the text form that the
device trace prints: the forward's and the backward's roofline shares at
each call's own shapes, untagged Mosaic calls left out, calls that do not
fit the model refused, the data wait from ``train_step.data`` spans, and
a program without tags or spans read as nothing."""

import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import counts, kernel_tags, peaks, spec, trace  # noqa: E402

ARCH = dict(layers=2, d_model=256, heads=4, kv_heads=2, head_dim=64, d_ff=512, vocab=1000)
MIX = {"batch": 2, "seq_len": 256}
PEAK = peaks.peaks("TPU v5 lite")
BH, BKV = MIX["batch"] * ARCH["heads"], MIX["batch"] * ARCH["kv_heads"]
Q, KV = f"bf16[{BH},256,64]{{2,1,0}}", f"bf16[{BKV},256,64]{{2,1,0}}"
LSE = f"f32[{BH},256,128]{{2,1,0}}"


def call(results, operands, tag=None):
    """A Mosaic call's event text; the tag as the compiler prints it, over
    three lines."""
    ops = ", ".join(f"{o} %x{i}" for i, o in enumerate(operands))
    meta = f'{{\n"kernel":"{tag}"\n}}' if tag else "{}"
    return (f"%k = {results} custom-call({ops}), custom_call_target=\"tpu_custom_call\", "
            f"frontend_attributes={{kernel_metadata={meta}}}")


def fwd(tag="flash_fwd", q=Q, kv=KV, lse=True):
    return call(f"({q}, {LSE})" if lse else q, [q, kv, kv], tag)


def dq(tag="flash_dq"):
    return call(Q, [Q, KV, KV, Q, LSE, LSE], tag)


def dkv(tag="flash_dkv"):
    return call(f"({Q}, {Q})", [Q, KV, KV, Q, LSE, LSE], tag)


def ev(name, start, end):
    return NS(name=name, start_ns=float(start), duration_ns=float(end - start))


def run_of(ops, steps=((0, 1000), (1000, 2000)), host=()):
    planes = [
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Modules", events=[ev("jit_train_step(1)", s, e) for s, e in steps]),
            NS(name="XLA Ops", events=ops)]),
        NS(name="/host:CPU", lines=[NS(name="py", events=[ev(trace.WINDOW_SPAN, 0, 2500),
                                                          *host])]),
    ]
    return NS(trace=trace.from_planes(planes), arch=ARCH, mix=MIX, peak=PEAK)


FWD_NS, DQ_NS, DKV_NS = 150, 60, 90


def step_ops(t0, texts=None):
    """One step: per layer a forward, its remat recompute, dq and dkv."""
    texts = texts or (fwd(), fwd(), dq(), dkv())
    out, t = [], t0
    for _ in range(ARCH["layers"]):
        for text, dur in zip(texts, (FWD_NS, FWD_NS, DQ_NS, DKV_NS)):
            out.append(ev(text, t, t + dur))
            t += dur
    return out


SHAPE = (MIX["batch"], 256, 256, ARCH["heads"], ARCH["kv_heads"], ARCH["head_dim"])
T_FWD = counts.roofline_s(*counts.flash_fwd(*SHAPE, with_lse=True), PEAK)[0]
T_BWD = counts.roofline_s(*counts.flash_bwd(*SHAPE), PEAK)[0]
TWO_STEPS = step_ops(0) + step_ops(1000)
CALLS = 2 * ARCH["layers"]  # (step, layer) pairs in TWO_STEPS


@pytest.mark.parametrize("text,kind", [
    (fwd(), "flash_fwd"), (fwd(lse=False), "flash_fwd"), (dq(), "flash_dq"),
    (dkv(), "flash_dkv"), (call("bf16[16,128]{1,0}", ["bf16[16,128]{1,0}"], "pwl_exp2"), "pallas"),
])
def test_signature_matching_reads_tagged_text_as_before(text, kind):
    assert trace.kernel_kind(text) == kind
    untagged = text.replace(text[text.index("kernel_metadata="):], "kernel_metadata={}}")
    assert trace.kernel_kind(untagged) == kind


@pytest.mark.parametrize("text,tag", [
    (fwd(), "flash_fwd"), (dq(), "flash_dq"), (dkv(), "flash_dkv"),
    (fwd().replace('"kernel":"flash_fwd"', '"kernel" : "flash_fwd"'), "flash_fwd"),
    (fwd(tag=None), None),  # a program older than its tags
    # The call's tuple elements carry its attributes, but are no calls.
    ('%pallas_call.9 = f32[8,256,128]{2,1,0} get-tuple-element(%k), index=1, '
     'frontend_attributes={kernel_metadata={\n"kernel":"flash_fwd"\n}}', None),
    ("%fusion.6 = bf16[16]{0} fusion(bf16[16] %a)", None),
])
def test_tag_of(text, tag):
    assert kernel_tags.tag_of(text) == tag


def test_forward_roofline_counts_each_call_at_its_own_shapes():
    got = spec.reader("flash_fwd_roofline.train")(run_of(TWO_STEPS))
    # Both forwards of each layer are work, the recompute included.
    assert got == pytest.approx(100 * 2 * CALLS * T_FWD / (2 * CALLS * FWD_NS * 1e-9))


def test_forward_roofline_without_lse_and_at_other_lengths():
    q, kv = f"bf16[{BH},128,64]{{2,1,0}}", f"bf16[{BKV},384,64]{{2,1,0}}"
    ops = [ev(fwd(q=q, kv=kv, lse=False), 0, 100)]
    want = counts.roofline_s(*counts.flash_fwd(2, 128, 384, 4, 2, 64), PEAK)[0]
    got = spec.reader("flash_fwd_roofline.train")(run_of(ops))
    assert got == pytest.approx(100 * want / 100e-9)


def test_backward_roofline_counts_one_backward_per_dq_call():
    got = spec.reader("flash_bwd_roofline.train")(run_of(TWO_STEPS))
    assert got == pytest.approx(100 * CALLS * T_BWD / (CALLS * (DQ_NS + DKV_NS) * 1e-9))


def test_whole_flash_share_is_the_two_shares_weighted_by_time():
    """With two forward calls per layer, the model's flash work over all
    flash time is (fwd share x T_fwd / 2 + bwd share x T_bwd) / (T_fwd + T_bwd)."""
    run = run_of(TWO_STEPS)
    f = spec.reader("flash_fwd_roofline.train")(run)
    b = spec.reader("flash_bwd_roofline.train")(run)
    t_fwd, t_bwd = 2 * CALLS * FWD_NS, CALLS * (DQ_NS + DKV_NS)
    whole = spec.reader("flash_roofline.train")(run)
    assert whole == pytest.approx((f * t_fwd / 2 + b * t_bwd) / (t_fwd + t_bwd))


@pytest.mark.parametrize("metric", ["flash_fwd_roofline.train", "flash_bwd_roofline.train"])
def test_untagged_mosaic_calls_are_left_out(metric):
    other = call("bf16[16,128]{1,0}", ["bf16[16,128]{1,0}"])
    untagged_flash = fwd(tag=None)
    extra = [ev(other, 100, 200), ev(untagged_flash, 1100, 1200)]
    read = spec.reader(metric)
    assert read(run_of(TWO_STEPS + extra)) == pytest.approx(read(run_of(TWO_STEPS)))


@pytest.mark.parametrize("metric,bad", [
    ("flash_fwd_roofline.train", fwd(q=f"bf16[{BH},256,128]{{2,1,0}}",
                                     kv=f"bf16[{BKV},256,128]{{2,1,0}}")),
    ("flash_fwd_roofline.train", fwd(kv=f"bf16[{BH},256,64]{{2,1,0}}")),  # MHA k for GQA
    ("flash_bwd_roofline.train", call(Q, [f"bf16[6,256,64]{{2,1,0}}", KV, KV, Q, LSE, LSE],
                                      "flash_dq")),
])
def test_a_tagged_call_that_does_not_fit_the_model_is_refused(metric, bad):
    ops = step_ops(0)
    ops[0 if "fwd" in metric else 2] = ev(bad, ops[0].start_ns, ops[0].start_ns + 10)
    with pytest.raises(ValueError, match="fit"):
        spec.reader(metric)(run_of(ops))


def test_unequal_dq_and_dkv_calls_are_refused():
    with pytest.raises(ValueError, match="dkv"):
        spec.reader("flash_bwd_roofline.train")(run_of(step_ops(0) + [ev(dq(), 900, 950)]))


@pytest.mark.parametrize("metric", ["flash_fwd_roofline.train", "flash_bwd_roofline.train",
                                    "data_wait.train"])
def test_a_program_without_tags_or_spans_reads_nothing(metric):
    untagged = step_ops(0, (fwd(tag=None), fwd(tag=None), dq(None), dkv(None)))
    host = [ev("chipbench.step", 0, 1000), ev("train_step", 10, 990)]
    assert spec.reader(metric)(run_of(untagged, host=host)) is None


def test_data_wait_credits_only_gaps_in_the_data_span():
    # Device busy [100, 900] and [1100, 1900] in a window of 2500 ns: idle
    # [0, 100] (midpoint 50), [900, 1100] (1000) and [1900, 2500] (2200).
    ops = [ev("%fusion.1 = f", 100, 900), ev("%fusion.2 = f", 1100, 1900)]
    host = [ev("chipbench.step", 0, 990), ev("train_step.data", 0, 120),
            ev("train_step", 120, 880), ev("train_step.readback", 880, 990),
            ev("chipbench.step", 990, 2000),
            # Open in the middle gap, but closed before its midpoint.
            ev("train_step.data", 990, 995), ev("train_step", 995, 1950)]
    run = run_of(ops, host=host)
    assert spec.reader("data_wait.train")(run) == pytest.approx(100 * 100 / 2500)
    gaps = dict(trace.attribute_gaps(run.trace))
    assert gaps == pytest.approx({"train_step.data": 100e-9, "train_step": 200e-9,
                                  "host: no span": 600e-9})
