"""The serving cell's per-layer readers on a synthetic trace: the flash
forward's roofline share in prefill, read at each request's true length
while its programs ran at padded buckets; the MFU of prefills and decode
steps; the device's idle share; a program without tags, or with no prefill
in the window, read as nothing; a traced program that does not match what
the cell recorded refused, and so is recorded work that no program of the
expected name ran."""

import sys
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import counts, peaks, serve, spec, trace  # noqa: E402

ARCH = dict(layers=2, d_model=256, heads=4, kv_heads=2, head_dim=64, d_ff=512, vocab=1000)
PEAK = peaks.peaks("TPU v5 lite")


def flash(bucket, tag="flash_fwd"):
    q, kv = f"bf16[4,{bucket},64]{{2,1,0}}", f"bf16[2,{bucket},64]{{2,1,0}}"
    meta = f'{{\n"kernel":"{tag}"\n}}' if tag else "{}"
    return (f"%k = {q} custom-call({q} %q, {kv} %k, {kv} %v), "
            f'custom_call_target="tpu_custom_call", frontend_attributes={{kernel_metadata={meta}}}')


def ev(name, start, end):
    return NS(name=name, start_ns=float(start), duration_ns=float(end - start))


# Two prefills (true lengths 100 and 300, buckets 128 and 512), each a
# program with one flash call per layer, then two decode steps.
PREFILLS = [(100, 128), (300, 512)]
DECODES = [np.array([101, 7]), np.array([102, 8, 301])]
FLASH_NS = {128: 40, 512: 200}


def serve_run(prefills=PREFILLS, tag="flash_fwd", window=(0, 5000),
              prefill_module="jit__prefill(77)", decode_module="jit_serve_step(9)"):
    modules, ops, t = [], [], 100
    for n, bucket in prefills:
        start = t
        for _ in range(ARCH["layers"]):
            ops.append(ev(flash(bucket, tag), t + 10, t + 10 + FLASH_NS[bucket]))
            t += 10 + FLASH_NS[bucket]
        ops.append(ev("%fusion.1 = head", t, t + 50))
        t += 60
        modules.append(ev(prefill_module, start, t))
        t += 100
    for _ in DECODES:
        modules.append(ev(decode_module, t, t + 300))
        ops.append(ev("%fusion.2 = decode", t, t + 300))
        t += 400
    planes = [
        NS(name="/device:TPU:0", lines=[NS(name="XLA Modules", events=modules),
                                        NS(name="XLA Ops", events=ops)]),
        NS(name="/host:CPU", lines=[NS(name="py", events=[
            ev(trace.WINDOW_SPAN, *window), ev("chipbench.step", 0, 3000),
            ev("prefill", 90, 900), ev("generate", 1000, 1500)])]),
    ]
    # The cell's record, on the host's clock: one window step (the
    # ``chipbench.step`` span) that held both prefills and both decodes.
    cell = NS(prefills=list(PREFILLS), prefill_times=[10.0, 11.0], step_ends=[12.0],
              decodes=list(DECODES), decode_steps=[0] * len(DECODES))
    cell.traced = lambda tr: serve.ServeCell.traced(cell, tr)
    return NS(trace=trace.from_planes(planes), arch=ARCH, peak=PEAK, cell=cell, mix={})


def test_flash_roofline_reads_true_lengths_not_buckets():
    got = spec.reader("flash_fwd_roofline.prefill")(serve_run())
    need = sum(counts.roofline_s(*counts.flash_fwd(1, n, n, 4, 2, 64), PEAK)[0]
               for n, _ in PREFILLS) * ARCH["layers"]
    took = ARCH["layers"] * sum(FLASH_NS.values()) * 1e-9
    assert got == pytest.approx(100 * need / took)
    # At the buckets the same calls would read more: padding is not work.
    at_buckets = sum(counts.roofline_s(*counts.flash_fwd(1, b, b, 4, 2, 64), PEAK)[0]
                     for _, b in PREFILLS) * ARCH["layers"]
    assert got < 100 * at_buckets / took


def test_mfu_counts_prefills_at_true_length_and_each_live_decode_token():
    run = serve_run()
    got = spec.reader("mfu.prefill")(run)
    flops = sum(counts.prefill_flops(ARCH, n) for n, _ in PREFILLS)
    flops += sum(counts.decode_flops(ARCH, int(k)) for d in DECODES for k in d)
    assert got == pytest.approx(100 * flops / (5000e-9 * PEAK["bf16_flops_per_s"]))


def test_idle_share_and_the_serving_spans_in_the_breakdown():
    run = serve_run()
    idle = spec.reader("device_idle.prefill")(run)
    assert idle == pytest.approx(100 * (1 - trace.busy_s(run.trace) / 5000e-9))
    labels = {k for k, _ in trace.breakdown(run.trace)["idle_gaps"]}
    assert {"prefill", "generate", "chipbench.step"} <= labels


def test_untagged_programs_and_empty_windows_read_nothing():
    assert spec.reader("flash_fwd_roofline.prefill")(serve_run(tag=None)) is None
    # A window that closes before the first prefill program ends.
    for metric in ("flash_fwd_roofline.prefill", "mfu.prefill"):
        assert spec.reader(metric)(serve_run(window=(0, 150))) is None


def test_a_program_that_does_not_match_the_record_is_refused():
    run = serve_run()
    run.cell.prefills = [(100, 128), (300, 1024)]
    with pytest.raises(ValueError, match="bucket"):
        spec.reader("flash_fwd_roofline.prefill")(run)
    run.cell.prefills = PREFILLS[:1]
    for metric in ("flash_fwd_roofline.prefill", "mfu.prefill"):
        with pytest.raises(ValueError, match="recorded"):
            spec.reader(metric)(run)


@pytest.mark.parametrize("renamed", ["prefill", "decode"])
def test_recorded_work_with_no_program_of_its_name_is_refused(renamed):
    """A later program that renames or fuses the engine's jitted functions
    makes the readers raise, not go quiet."""
    names = {"prefill_module": "jit_prefill_and_insert(77)"} if renamed == "prefill" else \
        {"decode_module": "jit_decode_fused(9)"}
    run = serve_run(**names)
    with pytest.raises(ValueError, match="no program matches"):
        spec.reader("mfu.prefill")(run)
    if renamed == "prefill":
        with pytest.raises(ValueError, match="no program matches"):
            spec.reader("flash_fwd_roofline.prefill")(run)
    else:
        assert spec.reader("flash_fwd_roofline.prefill")(run) > 0
