"""Run one cell of ``BENCHMARK.json`` and print one JSON result line last.

  python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: it builds the program, makes the weights and the traffic from
the seed, warms every shape the traffic uses (set-up, ``setup_s``), measures
for ``--seconds``, then checks what the timed path produced against the
plain reference.  ``--trace 1`` takes a profiler trace of the first seconds
of the window and reports the per-layer metrics instead of the end-to-end
ones.  Without a TPU, or with fewer chips than the cell asks for, it exits
with code 2 and prints no result.

``--plant`` is for measuring the check itself and is never used by a
benchmark run: ``control`` puts the fp8 control's readings in the program's
place, and ``unchanged``, ``half_batch`` and ``token`` break the timed path
(each cell says how, and refuses a plant that it does not implement).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

from . import peaks, program, reference, spec  # noqa: E402

# The profiler traces the window's first steps: at least this long, and at
# least this many steps.
TRACE_SECONDS = 8.0
TRACE_STEPS = 2


class Hooks:
    """Called by a cell after each step of its window (and once before the
    first): starts the profiler at the window's start and stops it once
    ``trace_seconds`` and ``TRACE_STEPS`` steps have passed, or when the
    window closes."""

    def __init__(self, trace_dir, trace_seconds, device):
        self.trace_dir, self.trace_seconds, self.device = trace_dir, trace_seconds, device
        self.t0 = None
        self.ticks = 0
        self._ann = None

    def tick(self, now, closing=False):
        import jax

        if self.t0 is None:
            self.t0 = now
            if self.trace_dir:
                from . import trace

                jax.profiler.start_trace(self.trace_dir, profiler_options=trace.options())
                self._ann = jax.profiler.TraceAnnotation("chipbench.window")
                self._ann.__enter__()
            return
        self.ticks += 1
        traced_enough = now - self.t0 >= self.trace_seconds and self.ticks >= TRACE_STEPS
        if self._ann is not None and (traced_enough or closing):
            self._ann.__exit__(None, None, None)
            self._ann = None
            jax.profiler.stop_trace()

    def memory_peak(self) -> int:
        stats = self.device.memory_stats() or {}
        return int(stats.get("peak_bytes_in_use", 0))


class Run:
    """What a per-layer metric reader gets."""

    def __init__(self, cell, tr, peak, arch, mix):
        self.cell, self.trace = cell, tr
        self.peak, self.arch, self.mix = peak, arch, mix


def make_cell(conf, mix, *, tracing=False, plant="none"):
    """The cell for a mix: the ``Cell`` of module ``chipbench.<kind>``."""
    arch = reference.arch_of(conf)
    Cell = importlib.import_module(f".{mix['kind']}", __package__).Cell
    return Cell(conf, arch, mix, tracing=tracing, plant=plant), arch


def run_cell(bench, workload, conf, mix, limits, *, seed, seconds, trace, device,
             peak, plant="none", t_start=None) -> dict:
    """Set up, measure and check one cell on ``device``; returns the result."""
    t_start = T_START if t_start is None else t_start
    cell, arch = make_cell(conf, mix, tracing=bool(trace), plant=plant)
    tmp = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        cell.setup(seed, seconds)
        setup_s = time.perf_counter() - t_start
        hooks = Hooks(tmp, min(TRACE_SECONDS, seconds), device)
        e2e = cell.window(seconds, hooks)
        out_metrics, dev_extra, breakdown = {}, {}, None
        name = workload["name"]
        if trace:
            from . import trace as tracemod

            tr = tracemod.load(tmp)
            run = Run(cell, tr, peak, arch, mix)
            for m in spec.metrics_for(name, bench, "per_layer"):
                value = spec.reader(m["name"])(run)
                if value is not None:
                    out_metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            busy = tracemod.busy_s(tr)
            dev_extra = {"busy_s": busy, "window_s": tr.window_s}
            breakdown = tracemod.breakdown(tr)
        else:
            e2e["setup_s"] = setup_s
            for m in spec.metrics_for(name, bench, "end_to_end"):
                if e2e.get(m["name"]) is not None:
                    out_metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
        checked = cell.check(seed, control=(plant == "control"))
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)
        if hasattr(cell, "close"):
            cell.close()
    checks = {k: {"value": checked[k], "limit": lim} for k, lim in limits["limits"].items()}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values())
    correct = correct and e2e["failed"] == 0
    result = {
        "correct": bool(correct),
        "attempted": int(e2e["attempted"]),
        "failed": int(e2e["failed"]),
        "metrics": out_metrics,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": workload["chips"], "memory_peak_bytes": cell.memory_peak,
                   **dev_extra},
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    extra = {k: v for k, v in checked.items() if k not in checks}
    if extra:
        result["check_readings"] = extra
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--plant", default="none",
                    choices=("none", "control", "unchanged", "half_batch", "token"))
    args = ap.parse_args(argv)

    try:
        bench = spec.benchmark()
        workload = spec.workload(args.workload, bench)
        conf = spec.config(workload["config"])
        mix = spec.traffic(workload["traffic"])
        limits = spec.limits(workload["name"])
        program.import_program()
    except (OSError, KeyError, ImportError, ValueError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: needs a TPU, JAX found {devices[0].platform}", file=sys.stderr)
        return 2
    if len(devices) < workload["chips"]:
        print(f"chipbench: {workload['name']} needs {workload['chips']} chips, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    try:
        peak = peaks.peaks(devices[0].device_kind)
    except ValueError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    with contextlib.redirect_stdout(sys.stderr):  # the program's prints go to stderr
        result = run_cell(bench, workload, conf, mix, limits, seed=args.seed,
                          seconds=args.seconds, trace=args.trace, device=devices[0],
                          peak=peak, plant=args.plant)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
