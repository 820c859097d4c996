"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and asserts the paper's
headline numbers (Fig. 11 speedups, Fig. 12 PWL errors, Table 2 accuracy
envelope, Table 3 area overhead, §3.5 cycle counts).

Also emits machine-readable ``BENCH_*.json`` files into the working
directory — ``BENCH_serve.json`` (continuous-batching decode tokens/s),
``BENCH_quant.json`` (int8 decode throughput, KV-cache footprint and
greedy fidelity), ``BENCH_spec.json`` (speculative decoding acceptance
rate and target-step reduction), ``BENCH_train.json`` (train-step
steps/s and tokens/s) and ``BENCH_tune.json`` (design-space autotune
Pareto frontier + paper cross-checks, with ``tune_report.md``) — CI
uploads them as workflow artifacts so throughput is tracked per commit.

``--only NAME`` (repeatable) runs a subset, e.g.
``python -m benchmarks.run --only tune``.

Roofline terms per (arch x mesh) come from the compiled dry-run
(launch/dryrun.py + launch/roofline.py), not from here — this harness is
CPU-runnable paper-claim reproduction.
"""

from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    from . import (
        fig1_active_time,
        fig11_utilization,
        fig12_pwl_error,
        quant_bench,
        section35_cycles,
        serve_bench,
        spec_bench,
        table2_accuracy,
        table3_area,
        train_bench,
        tune_bench,
    )

    modules = [
        ("fig1", fig1_active_time),
        ("fig11", fig11_utilization),
        ("fig12", fig12_pwl_error),
        ("table2", table2_accuracy),
        ("table3", table3_area),
        ("sec35", section35_cycles),
        ("serve", serve_bench),
        ("quant", quant_bench),
        ("spec", spec_bench),
        ("train", train_bench),
        ("tune", tune_bench),
    ]

    ap = argparse.ArgumentParser(description="paper-claim benchmark harness")
    ap.add_argument(
        "--only",
        action="append",
        metavar="NAME",
        choices=[name for name, _ in modules],
        help="run only the named benchmark(s); repeatable",
    )
    args = ap.parse_args()
    if args.only:
        modules = [(name, mod) for name, mod in modules if name in args.only]

    csv_rows: list[tuple[str, float, str]] = []
    failed = []
    for name, mod in modules:
        try:
            mod.run(csv_rows)
        except Exception:
            traceback.print_exc()
            failed.append(name)

    print("name,us_per_call,derived")
    for row in csv_rows:
        print(f"{row[0]},{row[1]:.1f},{row[2]}")
    if failed:
        print(f"FAILED benchmarks: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
