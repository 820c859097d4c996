"""A cell of BENCHMARK.json cut to a size that the CPU runs in seconds, for
the tests of the check.  Widths, depth and traffic shrink; the harness,
the program's timed path and the reference are the ones a chip run uses."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import peaks, program, run, spec  # noqa: E402

SEED = 2**31 + 4242
CELL = "olmo1b-train-2k"
SERVE_CELL = "yi9b16l-serve-prefill"


def tiny_cell(name: str = CELL):
    bench = spec.benchmark()
    w = spec.workload(name, bench)
    conf, mix = spec.config(w["config"]), spec.traffic(w["traffic"])
    conf.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                num_key_value_heads=4, num_hidden_layers=2, vocab_size=256,
                eos_token_id=255)
    # At width 64 a weight's bf16 spacing is near the update that the cell's
    # learning rate makes; ten times the rate keeps bf16 rounding of the
    # weights a small share of each step's change, as it is at the
    # published width.
    mix.update(seq_len=64, batch=4, corpus_steps=32, peak_lr=10 * mix["peak_lr"],
               document=dict(mix["document"], median=40))
    return bench, w, conf, mix, spec.limits(name)


def tiny_serve_cell(name: str = SERVE_CELL):
    """The serving cell at the program's smoke widths for yi-9b (d 64, 4
    heads over 2 KV heads of 16, d_ff 128, vocab 256, 2 layers), 4 slots of
    128 and prompts of 5-100 tokens."""
    bench = spec.benchmark()
    w = spec.workload(name, bench)
    conf, mix = spec.config(w["config"]), spec.traffic(w["traffic"])
    conf.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                num_key_value_heads=2, head_dim=16, num_hidden_layers=2, vocab_size=256)
    mix.update(slots=4, max_len=128, backlog=48, block=8, check_requests=4,
               prompt=dict(mix["prompt"], median=24, min=5, max=100),
               output=dict(mix["output"], min=2, max=6))
    return bench, w, conf, mix, spec.limits(name)


def run_tiny(plant: str = "none", seconds: float = 2.0, trace: int = 0, cell=tiny_cell):
    import time

    program.import_program()
    import jax

    bench, w, conf, mix, limits = cell()
    return run.run_cell(bench, w, conf, mix, limits, seed=SEED, seconds=seconds,
                        trace=trace, device=jax.devices()[0],
                        peak=peaks.peaks("TPU v5 lite"), plant=plant,
                        t_start=time.perf_counter())
