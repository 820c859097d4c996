"""The trace reducer: busy and idle time, self time per op, the flash
kernels' events and the host span that was open in each idle gap."""

import json
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import trace  # noqa: E402

Q, KV, LSE = "bf16[32,2048,128]{2,1,0:T(8,128)(2,1)}", "bf16[8,2048,128]{2,1,0}", "f32[32,2048,128]{2,1,0}"


def call(results, operands):
    return (f"%c.1 = {results} custom-call({', '.join(f'{o} %x{i}' for i, o in enumerate(operands))}), "
            'custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}')


FWD = call(Q, [Q, KV, KV])


def ev(name, start, end):
    return NS(name=name, start_ns=float(start), duration_ns=float(end - start))


def planes(ops, modules, host):
    return [
        NS(name="/device:TPU:0", lines=[NS(name="XLA Modules", events=modules),
                                        NS(name="XLA Ops", events=ops)]),
        NS(name="/host:CPU", lines=[NS(name="python", events=host)]),
    ]


def synthetic():
    ops = [ev("%while.1 = loop", 100, 300), ev("%fusion.2 = f", 150, 200), ev(FWD, 500, 600),
           ev("%fusion.9 = outside the window", 1100, 1200)]
    modules = [ev("jit_train_step(123)", 100, 300), ev("jit_train_step(123)", 500, 600),
               ev("jit_train_step(123)", 900, 1100)]
    host = [ev(trace.WINDOW_SPAN, 0, 1000), ev("chipbench.step", 50, 700),
            ev("train_step", 80, 320), ev("chipbench.other", 700, 1000),
            ev("$python frame", 0, 1000)]
    return trace.from_planes(planes(ops, modules, host))


def test_busy_and_idle():
    tr = synthetic()
    assert tr.window == (0.0, 1000.0) and tr.devices == 1
    assert trace.busy_s(tr) == pytest.approx(300e-9)
    assert trace.idle_gaps(tr) == [(0.0, 100.0), (300.0, 500.0), (600.0, 1000.0)]


def test_self_time_subtracts_nested_ops():
    st = trace.self_times(synthetic())
    assert st["while.1"] == pytest.approx(150e-9)
    assert st["fusion.2"] == pytest.approx(50e-9)
    assert st["flash_fwd"] == pytest.approx(100e-9)
    assert "fusion.9" not in st


def test_kernels_and_modules():
    tr = synthetic()
    k = trace.kernel_events(tr)
    assert [e.dur for e in k["flash_fwd"]] == [100.0] and not k.get("flash_dq")
    # The third step runs past the window's end, so it is not counted.
    steps = trace.module_events(tr, r"^jit_train_step\(")
    assert [(m.start, m.end) for m in steps] == [(100.0, 300.0), (500.0, 600.0)]
    assert trace.inside(tr.ops, steps[1:]) == k["flash_fwd"]


def test_gaps_are_attributed_to_the_innermost_open_span():
    gaps = dict(trace.attribute_gaps(synthetic()))
    assert gaps["chipbench.step"] == pytest.approx(300e-9)  # [0,100] and [300,500]
    assert gaps["chipbench.other"] == pytest.approx(400e-9)
    b = trace.breakdown(synthetic())
    assert b["device_ops"][0] == ["while.1", pytest.approx(150e-9)]
    assert len(b["idle_gaps"]) == 2


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        trace.from_planes(planes([], [], [ev("chipbench.step", 0, 10)]))


RECORDED = Path(__file__).parent / "data" / "trace_small.json"


def test_recorded_chip_trace():
    """A trace recorded on a TPU v5e (one prefill of the prefill cell and
    the steps around it), cut to its events.  The expected values were
    worked out when it was cut, by a plain union of the op intervals and a
    count of the Mosaic custom calls and prefill programs."""
    rec = json.loads(RECORDED.read_text())
    pl = [NS(name=p["name"], lines=[NS(name=l["name"], events=[ev(*e) for e in l["events"]])
                                    for l in p["lines"]]) for p in rec["planes"]]
    tr = trace.from_planes(pl)
    exp = rec["expected"]
    assert trace.busy_s(tr) == pytest.approx(exp["busy_s"], rel=1e-9)
    assert {k: len(v) for k, v in trace.kernel_events(tr).items()} == exp["kernel_events"]
    assert len(trace.module_events(tr, exp["module_pattern"])) == exp["modules"]
    assert trace.busy_s(tr) <= tr.window_s
    assert sum(v for _, v in trace.attribute_gaps(tr)) == pytest.approx(
        tr.window_s - trace.busy_s(tr), rel=1e-9)


@pytest.mark.parametrize("text,kind", [
    (call(f"({Q}, {LSE})", [Q, KV, KV]), "flash_fwd"),
    (call(Q, [Q, KV, KV]), "flash_fwd"),
    (call(Q, [Q, KV, KV, Q, LSE, LSE]), "flash_dq"),
    (call(f"({Q}, {Q})", [Q, KV, KV, Q, LSE, LSE]), "flash_dkv"),
    # Operands by name, with their shapes in the layout constraints (as the
    # compiled training step writes dq).
    ("%checkpoint.23 = bf16[32,2048,128]{2,1,0:T(8,128)(2,1)} custom-call(%b.1, %b.2, %b.3, "
     "%b.4, %pallas_call.60, /*index=5*/%broadcast.231), custom_call_target=\"tpu_custom_call\", "
     f"operand_layout_constraints={{{Q}, {Q}, {Q}, {Q}, {LSE}, {LSE}}}", "flash_dq"),
    # Another Mosaic kernel: operands that are not q, k, v.
    (call("bf16[16,128]{1,0}", ["bf16[16,128]{1,0}", "bf16[128]{0}"]), "pallas"),
    (call(Q, ["bf16[32,2048,64]{2,1,0}", KV, KV]), "pallas"),
    (call(Q, [Q, KV, "bf16[8,1024,128]{2,1,0}"]), "pallas"),
    ('%c.5 = bf16[16]{0} custom-call(), custom_call_target="AllocateBuffer"', None),
    ("%fusion.6 = bf16[16]{0} fusion(bf16[16] %a)", None),
])
def test_kernels_are_told_apart_by_signature(text, kind):
    assert trace.kernel_kind(text) == kind


def test_another_mosaic_kernel_keeps_its_name_in_the_breakdown():
    text = call("bf16[16,128]{1,0}", ["bf16[16,128]{1,0}"])
    assert trace.op_name(text) == "pallas c.1"
