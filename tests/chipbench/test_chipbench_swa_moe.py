"""The ``mellum2-8l-serve-repo8k`` cell's parts on the CPU:

* the program's smoke configuration of Mellum2 (8 layers in two periods,
  a window of 12 keys under prompts of 16-100, 8 experts top-2, YaRN)
  served through ``ServeEngine`` (prefill, insert, decode) agrees with the
  reference's logits, and the fp8 control and each planted fault make
  ``correct`` false; the control's run reads the witness, and the median
  row gap is the middle row's;
* a program whose configuration differs from the file is refused;
* the reference's YaRN, window and experts against the program's;
* the window counts against brute force, and the readers on a synthetic
  trace: the windowed kernel's roofline share at true lengths, the MFU of
  prefills and decode steps, and nothing read from a program without the
  windowed kernel's tag.
"""

import sys
import time
from pathlib import Path
from types import SimpleNamespace as NS

import jax
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import (counts, counts_swa_moe, peaks, program, run, serve, spec,  # noqa: E402
                       swa_moe_reference, trace)

CELL = "mellum2-8l-serve-repo8k"
ARCH = "mellum2-12b-a2.5b"
SEED = 2**31 + 4243
PEAK = peaks.peaks("TPU v5 lite")


def tiny_conf():
    """The configuration file at the program's smoke widths for Mellum2."""
    conf = spec.config("mellum2-8l")
    rope = conf["rope_parameters"]
    conf.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                vocab_size=256, num_experts=8, num_experts_per_tok=2,
                moe_intermediate_size=32, sliding_window=12, dtype="float32",
                rope_parameters=dict(rope, full_attention=dict(
                    rope["full_attention"], original_max_position_embeddings=32)))
    return conf


@pytest.fixture
def smoke_program(monkeypatch):
    program.import_program()
    from repro.configs.registry import get_smoke_config

    monkeypatch.setattr(program, "model_config", lambda conf, arch: get_smoke_config(ARCH))


def run_tiny(plant="none", conf=None):
    bench = spec.benchmark()
    w = spec.workload(CELL, bench)
    mix = spec.traffic(w["traffic"])
    mix.update(slots=4, max_len=128, backlog=48, block=8, check_requests=4,
               prompt=dict(mix["prompt"], median=40, min=16, max=100),
               output=dict(mix["output"], min=3, max=6))
    return run.run_cell(bench, w, conf or tiny_conf(), mix, spec.limits(CELL), seed=SEED,
                        seconds=2.0, trace=0, device=jax.devices()[0], peak=PEAK,
                        plant=plant, t_start=time.perf_counter())


def test_smoke_serving_agrees_with_the_reference(smoke_program):
    res = run_tiny()
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 4 and res["failed"] == 0
    assert res["check_readings"]["compared"] >= 8
    assert res["check_readings"]["route_flips"] >= 0
    assert res["checks"]["median_logit_gap"]["value"] <= res["checks"]["logit_gap"]["value"]
    assert res["metrics"]["prefill_tokens_per_s"]["value"] > 0


@pytest.mark.parametrize("plant", ["control", "unchanged", "half_batch", "token"])
def test_lower_precision_and_broken_serving_are_caught(smoke_program, plant):
    res = run_tiny(plant)
    assert res["correct"] is False, res["checks"]


def test_the_control_run_reads_the_witness(smoke_program):
    """The control's run keeps the program's readings and reads the
    witness: the reference at bf16 routed by itself and as float32."""
    got = run_tiny("control")["check_readings"]
    for k in ("program_median_logit_gap", "witness_program_logit_gap",
              "witness_bf16_logit_gap", "witness_bf16_routed_logit_gap"):
        assert np.isfinite(got[k]), k
    assert got["witness_bf16_logit_gap"] > 0


def test_the_median_gap_reads_the_middle_row_and_not_the_largest():
    """One row far off (an expert flipped) sets ``logit_gap`` and leaves
    the median; every row a little off (a lower precision) moves both."""
    from chipbench import serve_swa_moe

    ref = [np.ones((5, 8)), np.ones((4, 8))]
    got = [r[1:] + 0.01 for r in ref]
    got[0][2, 3] += 1.0
    gaps = serve_swa_moe.row_gaps(got, ref)
    assert len(gaps) == 7 and gaps.max() == pytest.approx(1.01)
    assert np.median(gaps) == pytest.approx(0.01)
    assert np.median(serve_swa_moe.row_gaps([None, got[1] + 0.5], ref)) == pytest.approx(0.51)


def test_a_program_unlike_the_file_is_refused(smoke_program):
    with pytest.raises(ValueError, match="not the file's"):
        run_tiny(conf=dict(tiny_conf(), sliding_window=16))


def test_the_reference_rope_and_window_match_the_program():
    program.import_program()
    from repro.configs.registry import get_config
    from repro.core.attention import naive_attention
    from repro.models.layers import yarn_frequencies

    cfg = get_config(ARCH)
    a = swa_moe_reference.arch_of(spec.config("mellum2-8l"))
    assert a["kinds"] == ("sliding",) * 3 + ("full",) + ("sliding",) * 3 + ("full",)
    inv, scale = swa_moe_reference.yarn_inv_freq(128, a["yarn"])
    np.testing.assert_allclose(inv, yarn_frequencies(128, cfg.rope_theta, cfg.rope_yarn))
    assert scale == cfg.rope_yarn.attention_factor
    key = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(key[0], (64, 8, 16))
    k, v = (jax.random.normal(kk, (64, 2, 16)) for kk in key[1:])
    ref = swa_moe_reference._attention({"kv_heads": 2}, "fp32", q, k, v, 10)
    want = naive_attention(q[None], k[None], v[None], causal=True, window=10)[0]
    np.testing.assert_allclose(np.asarray(ref), np.asarray(want).reshape(64, -1), atol=1e-5)


@pytest.mark.parametrize("n", [1, 5, 12, 13, 40])
def test_window_pairs_count_every_pair_once(n):
    w = 12
    brute = sum(1 for r in range(n) for c in range(n) if r - w < c <= r)
    assert counts_swa_moe.window_pairs(n, w) == brute


def test_counts_at_the_published_shape():
    s = counts_swa_moe.shape_of(spec.config("mellum2-8l"))
    assert s["kinds"].count("sliding") == 6 and s["window"] == 1024
    layers, head = counts_swa_moe.active_params(s)
    # Per layer: attention 21.2 M, router 0.15 M, 8 experts 49.5 M.
    assert layers == 8 * (21_233_664 + 147_456 + 8 * 3 * 2304 * 896)
    # Decode keys past the window count the window in sliding layers.
    per = 4 * 128 * 32
    assert (counts_swa_moe.decode_flops(s, 5000) - counts_swa_moe.decode_flops(s, 4000)
            == 2 * per * 1000)
    n = 4000
    assert counts_swa_moe.attention_flops(s, n) == per * (
        2 * counts.causal_pairs(n, n) + 6 * counts_swa_moe.window_pairs(n, 1024))


# -- readers on a synthetic trace -----------------------------------------------

HEADS, KV, HD = 32, 4, 128
PREFILLS = [(1500, 2048), (3000, 4096)]
DECODES = [np.array([1501, 20]), np.array([1502, 21, 3001])]
WIN_NS = {2048: 400, 4096: 900}


def flash(bucket, tag):
    q, kv = f"bf16[{HEADS},{bucket},{HD}]{{2,1,0}}", f"bf16[{KV},{bucket},{HD}]{{2,1,0}}"
    meta = f'{{\n"kernel":"{tag}"\n}}' if tag else "{}"
    return (f"%k = {q} custom-call({q} %q, {kv} %k, {kv} %v), "
            f'custom_call_target="tpu_custom_call", frontend_attributes={{kernel_metadata={meta}}}')


def ev(name, start, end):
    return NS(name=name, start_ns=float(start), duration_ns=float(end - start))


def swa_run(window_tag="flash_fwd_window"):
    conf = spec.config("mellum2-8l")
    kinds = counts_swa_moe.shape_of(conf)["kinds"]
    modules, ops, t = [], [], 100
    for n, bucket in PREFILLS:
        start = t
        for kind in kinds:
            tag, dur = ((window_tag, WIN_NS[bucket]) if kind == "sliding"
                        else ("flash_fwd", 2 * WIN_NS[bucket]))
            ops.append(ev(flash(bucket, tag), t + 10, t + 10 + dur))
            t += 10 + dur
        modules.append(ev("jit__prefill(3)", start, t + 50))
        t += 150
    for _ in DECODES:
        modules.append(ev("jit_serve_step(4)", t, t + 300))
        ops.append(ev("%fusion.2 = decode", t, t + 300))
        t += 400
    planes = [
        NS(name="/device:TPU:0", lines=[NS(name="XLA Modules", events=modules),
                                        NS(name="XLA Ops", events=ops)]),
        NS(name="/host:CPU", lines=[NS(name="py", events=[
            ev(trace.WINDOW_SPAN, 0, 20000), ev("chipbench.step", 0, 15000)])]),
    ]
    cell = NS(conf=conf, prefills=list(PREFILLS), prefill_times=[10.0, 11.0], step_ends=[12.0],
              decodes=list(DECODES), decode_steps=[0] * len(DECODES))
    cell.traced = lambda tr: serve.ServeCell.traced(cell, tr)
    arch = dict(layers=8, d_model=2304, heads=HEADS, kv_heads=KV, head_dim=HD, vocab=98304)
    return NS(trace=trace.from_planes(planes), arch=arch, peak=PEAK, cell=cell, mix={})


def test_window_roofline_reads_window_pairs_at_true_lengths():
    got = spec.reader("flash_window_roofline.prefill")(swa_run())
    need = 6 * sum(counts.roofline_s(*counts_swa_moe.flash_fwd_window(
        1, n, HEADS, KV, HD, 1024), PEAK)[0] for n, _ in PREFILLS)
    took = 6 * sum(WIN_NS.values()) * 1e-9
    assert got == pytest.approx(100 * need / took)


def test_mfu_counts_prefills_and_capped_decode_keys():
    r = swa_run()
    s = counts_swa_moe.shape_of(r.cell.conf)
    flops = sum(counts_swa_moe.prefill_flops(s, n) for n, _ in PREFILLS)
    flops += sum(counts_swa_moe.decode_flops(s, int(k)) for d in DECODES for k in d)
    got = spec.reader("mfu.prefill_moe")(r)
    assert got == pytest.approx(100 * flops / (r.trace.window_s * PEAK["bf16_flops_per_s"]))


def test_a_program_without_the_windowed_tag_reads_nothing():
    assert spec.reader("flash_window_roofline.prefill")(swa_run(window_tag=None)) is None


def test_a_prefill_at_another_bucket_is_refused():
    r = swa_run()
    r.cell.prefills = [(1500, 4096), (3000, 4096)]
    with pytest.raises(ValueError, match="bucket"):
        spec.reader("flash_window_roofline.prefill")(r)


def test_the_cell_is_found_by_its_mix_kind():
    conf = spec.config("mellum2-8l")
    cell, arch = run.make_cell(conf, spec.traffic("serve-repo-completion-8k"))
    assert type(cell).__name__ == "SwaMoeCell" and arch["layers"] == 8
