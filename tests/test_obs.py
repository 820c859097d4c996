"""Tests for the ``repro.obs`` telemetry layer (ISSUE 10).

Covers: histogram/percentile math vs numpy, Prometheus/JSON exposition
golden output, Chrome-trace schema validity, the disabled-mode no-op
overhead guard, MFU against the device peak (and the paper-ideal
reference against ``core.systolic_model``), engine TTFT/TPOT plausibility,
the library compile counter vs the ``jit_recompiles`` fixture, fault-layer
counters, trainer metrics, step-phase spans + the JSONL stream round-trip
through ``launch/scrape_log``.
"""

import os

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    )

import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from repro.configs.base import ModelConfig, ShapeConfig  # noqa: E402
from repro.core import systolic_model  # noqa: E402
from repro.dist.fault import PreemptionHandler, StepWatchdog  # noqa: E402
from repro.launch.scrape_log import scrape, scrape_dryrun  # noqa: E402
from repro.models import init_params  # noqa: E402
from repro.obs import (  # noqa: E402
    PAPER_ARRAY,
    PEAKS,
    MFUMeter,
    NullTracer,
    Registry,
    Tracer,
    decode_flops,
    matmul_param_count,
    paper_ideal_flops_per_s,
    prefill_flops,
    set_enabled,
    train_step_flops,
    verify_flops,
    watch_jit_compiles,
)
from repro.serve.engine import Request, ServeEngine  # noqa: E402
from repro.train.trainer import Trainer, TrainerConfig  # noqa: E402

TINY = ModelConfig(
    name="tiny-obs",
    family="dense",
    num_layers=2,
    d_model=64,
    num_heads=4,
    num_kv_heads=2,
    head_dim=16,
    d_ff=128,
    vocab_size=128,
    mlp_type="swiglu",
    dtype="float32",
    remat=False,
)

TEST_PEAK = 1e12


@pytest.fixture
def cpu_peak(monkeypatch):
    """The CPU has no entry in the peak table: tests that read an MFU gauge
    of the engine or the trainer give it one."""
    monkeypatch.setitem(PEAKS, jax.devices()[0].device_kind,
                        {"bf16_flops_per_s": TEST_PEAK, "hbm_bytes_per_s": 1e11})


@pytest.fixture(autouse=True)
def _metrics_enabled():
    """Every test starts (and leaves the process) with metrics on."""
    set_enabled(True)
    yield
    set_enabled(True)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_counter_gauge_basics_and_labels():
    reg = Registry()
    c = reg.counter("reqs_total", "requests", ("phase",))
    c.labels(phase="prefill").inc()
    c.labels(phase="prefill").inc(2)
    c.labels(phase="decode").inc()
    assert c.labels(phase="prefill").value == 3
    assert c.labels(phase="decode").value == 1
    with pytest.raises(ValueError):
        c.labels(phase="x").inc(-1)  # counters only go up

    g = reg.gauge("occupancy", "live fraction")
    g.set(0.75)
    g.inc(0.25)
    assert g.value == 1.0
    # Re-registering the same name returns the same family; kind clashes
    # are errors.
    assert reg.counter("reqs_total") is c
    with pytest.raises(ValueError):
        reg.gauge("reqs_total")


def test_histogram_percentiles_match_numpy():
    reg = Registry()
    h = reg.histogram("lat", "latency")
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=-3.0, sigma=1.0, size=1000)
    for v in vals:
        h.observe(v)
    for q in (50, 90, 99):
        assert h.percentile(q) == pytest.approx(np.percentile(vals, q), rel=1e-9)
    assert h.count == 1000
    assert h.sum == pytest.approx(vals.sum())
    s = h.summary()
    assert s["p50"] == pytest.approx(np.percentile(vals, 50))


def test_histogram_bucket_counts_cumulative():
    reg = Registry()
    h = reg.histogram("d", "", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    rows = h._default().cumulative_buckets()
    assert [(le, n) for le, n in rows] == [
        (0.1, 1), (1.0, 3), (10.0, 4), (float("inf"), 5)
    ]


def test_prometheus_exposition_golden():
    reg = Registry()
    reg.counter("steps_total", "steps done").inc(3)
    reg.gauge("loss", "last loss").set(2.5)
    h = reg.histogram("lat_seconds", "latency", ("phase",), buckets=(0.1, 1.0))
    h.labels(phase="decode").observe(0.05)
    h.labels(phase="decode").observe(0.5)
    expected = "\n".join([
        "# HELP lat_seconds latency",
        "# TYPE lat_seconds histogram",
        'lat_seconds_bucket{phase="decode",le="0.1"} 1',
        'lat_seconds_bucket{phase="decode",le="1"} 2',
        'lat_seconds_bucket{phase="decode",le="+Inf"} 2',
        'lat_seconds_sum{phase="decode"} 0.55',
        'lat_seconds_count{phase="decode"} 2',
        "# HELP loss last loss",
        "# TYPE loss gauge",
        "loss 2.5",
        "# HELP steps_total steps done",
        "# TYPE steps_total counter",
        "steps_total 3",
    ]) + "\n"
    assert reg.to_prometheus() == expected


def test_json_exposition_round_trips_snapshot():
    reg = Registry()
    reg.counter("c", "", ("k",)).labels(k="a").inc(2)
    reg.histogram("h", "").observe(0.2)
    snap = json.loads(reg.to_json())
    assert snap["counters"]["c"] == {'{k="a"}': 2.0}
    assert snap["histograms"]["h"][""]["count"] == 1
    assert snap == json.loads(json.dumps(reg.snapshot(), sort_keys=True))


def test_disabled_mode_is_noop_and_near_free():
    reg = Registry()
    c = reg.counter("c", "")
    h = reg.histogram("h", "")
    set_enabled(False)
    c.inc()
    h.observe(1.0)
    assert c.value == 0 and h.count == 0  # true no-op

    n = 100_000
    t0 = time.perf_counter()
    for _ in range(n):
        c.inc()
        h.observe(1.0)
    disabled = time.perf_counter() - t0
    # Guarded-early-return cost: generous CI bound, ~50x slack over the
    # observed per-call time.
    assert disabled / (2 * n) < 5e-6, f"disabled path too slow: {disabled:.3f}s"
    set_enabled(True)
    c.inc()
    assert c.value == 1


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------


def test_chrome_trace_schema_valid(tmp_path):
    tr = Tracer(process_name="test")
    with tr.span("outer", cat="t", tid=1, args={"k": 1}):
        with tr.span("inner", cat="t", tid=1):
            pass
    tr.instant("marker", tid=1, args={"rid": 7})
    tr.complete("retro", 0.001, 0.002, tid=2)
    tr.thread_name(1, "slot 1")
    path = tr.save(str(tmp_path / "trace.json"))

    with open(path) as f:
        doc = json.load(f)  # loadable JSON — what Perfetto requires
    evs = doc["traceEvents"]
    assert isinstance(evs, list) and len(evs) >= 5
    for ev in evs:
        assert {"ph", "name", "pid", "tid"} <= set(ev)
    spans = [e for e in evs if e["ph"] == "X"]
    assert len(spans) == 3
    for s in spans:
        assert s["dur"] >= 0 and s["ts"] >= 0
    inner = next(e for e in spans if e["name"] == "inner")
    outer = next(e for e in spans if e["name"] == "outer")
    # Nesting: inner lies within outer on the same lane.
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    (instant,) = [e for e in evs if e["ph"] == "i"]
    assert instant["args"]["rid"] == 7


# ---------------------------------------------------------------------------
# MFU against the device peak; the paper-ideal reference vs systolic_model
# ---------------------------------------------------------------------------


def test_paper_ideal_matches_systolic_model():
    # peak: 2 * 128^2 MACs/cycle at 1.5 GHz
    assert PAPER_ARRAY.peak_flops_per_s == pytest.approx(49.152e12)
    for seq in systolic_model.PAPER_SEQLENS:
        util = systolic_model.fsa_utilization(seq, 128)
        assert paper_ideal_flops_per_s(seq) == pytest.approx(
            util * PAPER_ARRAY.peak_flops_per_s
        )


def test_mfu_meter_achieving_ideal_reads_one():
    """MFU = flops / (seconds x peak): a phase that achieves exactly the
    given peak reads 1, half of it 0.5, and the gauge keeps the last."""
    reg = Registry()
    meter = MFUMeter(TINY, reg, peak_flops_per_s=TEST_PEAK)
    flops = 3e12
    rec = meter.record("prefill", flops, flops / TEST_PEAK)
    assert rec["mfu"] == pytest.approx(1.0)
    rec = meter.record("prefill", flops, 2 * flops / TEST_PEAK)
    assert rec["mfu"] == pytest.approx(0.5)
    assert rec["flops_per_s"] == pytest.approx(TEST_PEAK / 2)
    assert reg.get("mfu").labels(phase="prefill").value == pytest.approx(0.5)
    # Over a mesh the peak is the devices' together.
    four = MFUMeter(TINY, Registry(), peak_flops_per_s=TEST_PEAK, chips=4)
    assert four.record("train", flops, flops / TEST_PEAK)["mfu"] == pytest.approx(0.25)


def test_mfu_meter_on_a_device_without_a_peak_sets_no_gauge():
    """The CPU is not in the peak table: FLOPs are counted, no MFU."""
    assert jax.devices()[0].device_kind not in PEAKS
    reg = Registry()
    rec = MFUMeter(TINY, reg).record("train", 1e9, 1.0)
    assert rec["mfu"] is None and rec["flops_per_s"] == pytest.approx(1e9)
    assert reg.get("mfu") is None
    assert reg.get("model_flops_total").labels(phase="train").value == 1e9


def test_one_peak_table_for_mfu_and_the_dry_run_roofline():
    from repro.launch import roofline

    v5e = PEAKS["TPU v5 lite"]
    assert v5e == {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert roofline.PEAK_FLOPS == v5e["bf16_flops_per_s"]
    assert roofline.HBM_BW == v5e["hbm_bytes_per_s"]


def test_flops_closed_forms_scale_sanely():
    # Param term dominates at tiny context; attention term grows with ctx.
    m = matmul_param_count(TINY)
    assert prefill_flops(TINY, 8) > 2.0 * m * 8
    assert decode_flops(TINY, [16, 16]) > decode_flops(TINY, [4, 4])
    # Train: 3x the causal forward of each sequence (remat not counted).
    assert train_step_flops(TINY, 2, 32) == pytest.approx(2 * 3 * prefill_flops(TINY, 32))
    # A verify of K+1 tokens is K+1 decode steps at growing contexts.
    ctx = np.array([5, 9])
    assert verify_flops(TINY, ctx, 2) == pytest.approx(
        sum(decode_flops(TINY, ctx + j) for j in range(3)))


def test_train_flops_count_matmul_parameters_and_causal_pairs():
    """Matmul parameters (the tied head yes, the embedding lookup no),
    causal pairs, 3x the forward."""
    d, L, v, ff, h, kv, hd, seq = 64, 2, 128, 128, 4, 2, 16, 32
    attn = d * h * hd * 2 + d * kv * hd * 2
    layers = L * (attn + 3 * d * ff)
    assert matmul_param_count(TINY) == layers + v * d  # untied: lookup left out
    tied = dataclasses.replace(TINY, tie_embeddings=True)
    assert matmul_param_count(tied) == layers + v * d  # the head's matmul
    want = 3 * (2 * (layers + v * d) * seq + 4 * hd * h * L * seq * (seq + 1) // 2)
    assert train_step_flops(TINY, 1, seq) == pytest.approx(want)


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_params():
    return init_params(TINY, jax.random.PRNGKey(0))


def _run_wave(params, n_requests=5, max_new=4, tracer=None):
    eng = ServeEngine(
        TINY, params, batch_size=2, max_len=32, prefill_buckets=(16,),
        tracer=tracer,
    )
    rng = np.random.default_rng(0)
    for i in range(n_requests):
        eng.submit(Request(
            rid=i,
            prompt=rng.integers(0, TINY.vocab_size, size=6 + i).astype(np.int32),
            max_new_tokens=max_new,
        ))
    done = eng.run()
    assert len(done) == n_requests
    return eng, done


def test_engine_ttft_tpot_plausible(tiny_params, cpu_peak):
    eng, done = _run_wave(tiny_params)
    ttft = eng.registry.get("serve_ttft_seconds")
    tpot = eng.registry.get("serve_tpot_seconds")
    queue = eng.registry.get("serve_queue_wait_seconds")
    # One TTFT + one queue-wait observation per request.
    assert ttft.count == len(done)
    assert queue.count == len(done)
    # One TPOT observation per batched decode step.
    assert tpot.count == eng.stats["decode_steps"]
    # Plausibility: positive, ordered, sub-minute on a tiny model.
    assert 0 < tpot.percentile(50) <= tpot.percentile(99) < 60
    assert 0 < ttft.percentile(50) <= ttft.percentile(99) < 60
    # Queue wait <= TTFT (TTFT includes it) for the median request.
    assert queue.percentile(50) <= ttft.percentile(50)
    # Tokens: every request emitted max_new tokens.
    assert eng.registry.get("serve_tokens_total").value == sum(
        len(r.output) for r in done
    )
    assert eng.registry.get("serve_requests_completed_total").value == len(done)
    # MFU gauges populated for both phases.
    for phase in ("prefill", "decode"):
        assert eng.registry.get("mfu").labels(phase=phase).value > 0
    # Occupancy/batch-utilization within [0, 1].
    assert 0 <= eng.registry.get("serve_slot_occupancy").value <= 1
    butil = eng.registry.get("serve_batch_utilization")
    assert 0 < butil.sum / butil.count <= 1


def test_engine_stats_property_backwards_compatible(tiny_params):
    eng, done = _run_wave(tiny_params, n_requests=3)
    stats = eng.stats
    assert isinstance(stats, dict)
    assert stats["prefill_calls"] == 3
    assert stats["insert_calls"] == 3
    assert stats["decode_steps"] > 0
    # Snapshot semantics: mutating the returned dict is harmless.
    before = dict(eng.stats)
    stats["prefill_calls"] = 999
    assert eng.stats == before


def test_engine_prometheus_dump_has_required_series(tiny_params, cpu_peak):
    eng, _ = _run_wave(tiny_params, n_requests=3)
    eng.compile_counts()
    prom = eng.registry.to_prometheus()
    for needle in (
        "serve_ttft_seconds_bucket",
        "serve_tpot_seconds_bucket",
        "serve_queue_wait_seconds_bucket",
        "serve_slot_occupancy",
        'mfu{phase="decode"}',
        'serve_jit_executables{phase="generate"}',
    ):
        assert needle in prom, f"missing {needle}"


def test_engine_trace_lifecycle_spans(tiny_params, tmp_path):
    tr = Tracer()
    eng, done = _run_wave(tiny_params, n_requests=3, tracer=tr)
    doc = json.load(open(tr.save(str(tmp_path / "t.json"))))
    names = [e.get("name") for e in doc["traceEvents"]]
    for phase in ("prefill", "generate", "queued", "decode", "retire"):
        assert phase in names, f"no {phase} events in trace"
    # One retroactive queued+decode span pair per retired request.
    assert names.count("queued") == len(done)
    assert names.count("decode") == len(done)


def test_compile_counter_matches_fixture(tiny_params, jit_recompiles):
    """The library watcher (wired into a registry counter) and the test
    fixture count the same log records — their totals must agree."""
    reg = Registry()
    counter = reg.counter("jit_compiles_total", "")
    with watch_jit_compiles(counter) as lib_watcher:
        _run_wave(tiny_params, n_requests=2)
    assert counter.value == lib_watcher.count == jit_recompiles.count
    assert counter.value > 0  # the wave does compile something


def test_engine_token_equivalence_with_tracer_enabled(tiny_params):
    """Instrumentation must not perturb outputs: the same wave with and
    without a live tracer yields identical tokens."""
    _, plain = _run_wave(tiny_params, n_requests=4)
    _, traced = _run_wave(tiny_params, n_requests=4, tracer=Tracer())
    for a, b in zip(
        sorted(plain, key=lambda r: r.rid), sorted(traced, key=lambda r: r.rid)
    ):
        assert a.output == b.output


# ---------------------------------------------------------------------------
# fault-layer + trainer metrics, JSONL round trip
# ---------------------------------------------------------------------------


def test_fault_counters():
    reg = Registry()
    wd = StepWatchdog(timeout_factor=2.0, warmup_steps=1, registry=reg)
    for _ in range(3):
        wd.start_step()
        wd.end_step()
    assert reg.get("watchdog_heartbeats_total").value == 3
    with pytest.raises(Exception):
        wd.check(1e9)
    assert reg.get("watchdog_stragglers_total").value == 1

    ph = PreemptionHandler(install=False, registry=reg)
    ph.trigger()
    assert ph.requested
    assert reg.get("preemptions_total").value == 1


@pytest.mark.parametrize("causal, share", [(True, 136 / 256), (False, 1.0)])
def test_trainer_sets_flash_live_block_share(tmp_path, causal, share):
    """Set once at construction from the training length and the flash
    blocks: 136 of 16 x 16 block pairs are live under a causal mask."""
    cfg = dataclasses.replace(TINY, causal=causal)
    tcfg = TrainerConfig(total_steps=1, ckpt_dir=str(tmp_path))
    t = Trainer(cfg, ShapeConfig("t", 2048, 1, "train"), tcfg)
    assert t.registry.get("flash_live_block_share").value == share


def test_trainer_metrics_and_jsonl_roundtrip(tmp_path, cpu_peak):
    jsonl = tmp_path / "train.metrics.jsonl"
    tcfg = TrainerConfig(
        total_steps=4, ckpt_every=100, ckpt_dir=str(tmp_path / "ckpt"),
        log_every=100, metrics_jsonl=str(jsonl),
    )
    t = Trainer(TINY, ShapeConfig("t", 32, 4, "train"), tcfg)
    state = t.run()
    assert state["step"] == 4

    # Registry: counters/gauges/histograms landed.
    reg = t.registry
    assert reg.get("train_steps_total").value == 4
    assert reg.get("train_tokens_total").value == 4 * 32 * 4
    assert reg.get("train_step_seconds").count == 4
    assert np.isfinite(reg.get("train_loss").value)
    assert reg.get("watchdog_heartbeats_total").value == 4
    assert reg.get("mfu").labels(phase="train").value > 0

    # JSONL stream: one record per step; scrape()'s fast path returns them.
    text = jsonl.read_text()
    records = scrape(text)
    assert len(records) == 4
    assert [r["step"] for r in records] == [1, 2, 3, 4]
    assert records[-1]["loss"] == pytest.approx(state["losses"][-1])
    for r in records:
        assert r["event"] == "train_step"
        assert r["mfu"] > 0 and r["step_s"] > 0

    # Interleaved human log lines don't confuse the fast path.
    noisy = "step 1 loss 5.0 gnorm 1.0 3 ms\n" + text + "not json {\n"
    assert scrape(noisy) == records


def _annotations(monkeypatch):
    """Names passed to the profiler's TraceAnnotation, in order of entry."""
    from repro.obs import trace as trace_mod

    entered = []

    class Annotation:
        def __init__(self, name):
            entered.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(trace_mod.jax.profiler, "TraceAnnotation", Annotation)
    return entered


def test_trainer_step_phase_spans(tmp_path, monkeypatch):
    """With a Tracer each step is data, step, readback, in that order, on
    the Tracer and the profiler alike; with the NullTracer the trainer
    makes no profiler call."""
    entered = _annotations(monkeypatch)
    phases = ["train_step.data", "train_step", "train_step.readback"]

    def trainer(tracer, sub):
        tcfg = TrainerConfig(total_steps=2, ckpt_every=100,
                             ckpt_dir=str(tmp_path / sub), log_every=100)
        return Trainer(TINY, ShapeConfig("t", 16, 2, "train"), tcfg, tracer=tracer)

    trainer(NullTracer(), "null").run()
    assert entered == []

    tr = Tracer()
    trainer(tr, "traced").run()
    assert entered == phases * 2
    spans = sorted((e for e in tr.events if e["ph"] == "X"), key=lambda e: e["ts"])
    assert [e["name"] for e in spans] == phases * 2
    for a, b in zip(spans, spans[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1e-3  # one after another


def test_scrape_regex_fallback_still_works():
    log = (
        "== yi-9b x train_4k on 8x4 (32 chips) ==\n"
        "lower 1.5s compile 12.0s\n"
        "per-device bytes: 3.25 GiB\n"
    )
    (rec,) = scrape(log)
    assert rec["arch"] == "yi-9b" and rec["chips"] == 32
    assert rec["compile_s"] == 12.0
    assert scrape_dryrun(log) == [rec]
