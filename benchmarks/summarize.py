"""Aggregate all ``BENCH_*.json`` files into one markdown table.

Run after ``python -m benchmarks.run``:

  PYTHONPATH=src python -m benchmarks.summarize

Prints the table to stdout and, when ``GITHUB_STEP_SUMMARY`` is set
(inside a GitHub Actions step), appends it there too — so every CI run
shows serve/quant/spec/train throughput on the run page without
downloading the artifact.
"""

from __future__ import annotations

import glob
import json
import os
import sys


def _flatten(obj, prefix=""):
    """Nested dict -> dotted-key scalar rows, insertion-ordered."""
    rows = []
    for k, v in obj.items():
        key = f"{prefix}.{k}" if prefix else k
        if isinstance(v, dict):
            rows.extend(_flatten(v, key))
        elif isinstance(v, (int, float, bool, str)):
            rows.append((key, v))
        elif isinstance(v, list):
            # Lists are detail payloads (e.g. the BENCH_tune.json Pareto
            # frontier); summarize their size, not their contents.
            rows.append((f"{key}.n", len(v)))
    return rows


def summarize(paths: list[str]) -> str:
    lines = ["# Benchmark summary", ""]
    if not paths:
        lines.append("_no BENCH_*.json files found_")
        return "\n".join(lines) + "\n"
    latency_rows = []  # (file, metric, value): surfaced in their own table
    lines += ["| file | metric | value |", "|---|---|---|"]
    for path in sorted(paths):
        with open(path) as f:
            data = json.load(f)
        name = os.path.basename(path)
        for key, val in _flatten(data):
            if key.startswith("model."):  # config echo, not a metric
                continue
            if key.startswith("latency."):
                latency_rows.append((name, key.removeprefix("latency."), val))
                continue
            lines.append(f"| {name} | {key} | {val} |")
    if latency_rows:
        lines += [
            "",
            "## Latency percentiles (repro.obs)",
            "",
            "| file | metric | value |",
            "|---|---|---|",
        ]
        lines += [f"| {n} | {k} | {v} |" for n, k, v in latency_rows]
    return "\n".join(lines) + "\n"


def main() -> None:
    paths = sys.argv[1:] or glob.glob("BENCH_*.json")
    table = summarize(paths)
    print(table)
    step_summary = os.environ.get("GITHUB_STEP_SUMMARY")
    if step_summary:
        with open(step_summary, "a") as f:
            f.write(table)


if __name__ == "__main__":
    main()
