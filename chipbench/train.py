"""Training cells: the program's ``Trainer`` built once, driven from the
benchmark's weights through its first steps in set-up, and handed on to the
window, which calls ``Trainer.run`` one step at a time until the window's
time is up.  The batches come from a corpus that set-up writes to a token
file, so the program's own data path reads them."""

from __future__ import annotations

import gc
import os
import shutil
import tempfile
import time

import numpy as np

from . import program, reference, traffic, weights

CHECK_STEPS = 3
PLANTS = ("none", "control", "unchanged", "half_batch")


class TrainCell:
    def __init__(self, conf: dict, arch: dict, mix: dict, *, tracing: bool = False,
                 plant: str = "none"):
        if plant not in PLANTS:
            raise ValueError(f"the training cell plants none of {plant!r}")
        self.conf, self.arch, self.mix = conf, arch, mix
        self.tracing, self.plant = tracing, plant
        self.tmp = tempfile.mkdtemp(prefix="chipbench-train-")

    def close(self) -> None:
        shutil.rmtree(self.tmp, ignore_errors=True)

    def setup(self, seed: int, seconds: float) -> None:
        import jax

        from repro.configs.base import ShapeConfig
        from repro.obs import Tracer
        from repro.train.trainer import Trainer, TrainerConfig

        m = self.mix
        self.cfg = cfg = program.model_config(self.conf, self.arch)
        self.seed = seed
        self.corpus = traffic.corpus(m, seed, self.arch["vocab"], self.conf["eos_token_id"])
        path = os.path.join(self.tmp, "corpus.npy")
        np.save(path, self.corpus)
        self.tcfg = TrainerConfig(
            total_steps=m["schedule_steps"], ckpt_every=10**9,
            ckpt_dir=os.path.join(self.tmp, "ckpt"), optimizer=m["optimizer"],
            peak_lr=m["peak_lr"], warmup_steps=m["warmup_steps"], log_every=10**9,
            seed=0)
        self.trainer = Trainer(cfg, ShapeConfig("chipbench", m["seq_len"], m["batch"], "train"),
                               self.tcfg, token_file=path,
                               tracer=Tracer() if self.tracing else None)
        if self.plant in ("unchanged", "half_batch"):
            self._plant_step()
        self.shapes = program.param_shapes(cfg)
        params = weights.make(self.shapes, seed)
        self.state = {"params": params, "opt": self.trainer.optimizer.init(params), "step": 0}
        del params
        # The first steps go through the window's own call and feed.
        losses = self._run_to(1)
        first = reference.first_step(self.state["opt"].stats, self.shapes)
        losses += self._run_to(CHECK_STEPS)
        start = weights.make(self.shapes, seed)
        change = [float(x) for x in reference.leaf_change_norms(self.state["params"], start)]
        self.readings = {"losses": losses, **first, "change": change}
        del start
        jax.block_until_ready(self.state["params"])

    def _plant_step(self) -> None:
        """Faults for the tests of the check, planted in the step itself."""
        orig = self.trainer.step_fn
        half = self.mix["batch"] // 2

        if self.plant == "unchanged":
            def step(params, opt, batch):
                _, _, metrics = orig(params, opt, batch)
                return params, opt, metrics
        else:
            def step(params, opt, batch):
                return orig(params, opt, {k: v[:half] for k, v in batch.items()})

        self.trainer.step_fn = step

    def _run_to(self, step: int) -> list[float]:
        self.tcfg.total_steps = step
        self.state = self.trainer.run(self.state)
        return list(self.state.pop("losses"))

    def window(self, seconds: float, hooks) -> dict:
        import jax

        m = self.mix
        t0 = time.perf_counter()
        hooks.tick(t0)
        steps = 0
        while time.perf_counter() - t0 < seconds:
            with jax.profiler.TraceAnnotation("chipbench.step"):
                self._run_to(self.state["step"] + 1)
            steps += 1
            hooks.tick(time.perf_counter())
        t_end = time.perf_counter()
        hooks.tick(t_end, closing=True)
        self.memory_peak = hooks.memory_peak()
        return {
            "attempted": steps, "failed": 0, "window_s": t_end - t0,
            "train_tokens_per_s": steps * m["batch"] * m["seq_len"] / (t_end - t0),
            "t0": t0, "t_close": t_end,
        }

    def rows(self, step: int):
        """Rows of one step, as the token file's windows are laid out:
        window w is tokens [w*S, w*S + S] and step s takes windows
        s*B .. s*B + B - 1."""
        s, b = self.mix["seq_len"], self.mix["batch"]
        w = [self.corpus[i * s: i * s + s + 1] for i in range(step * b, step * b + b)]
        toks = np.stack(w).astype(np.int32)
        return toks[:, :-1], toks[:, 1:]

    def check(self, seed: int, control: bool = False) -> dict:
        """The readings of the first steps against the reference that
        follows the same steps.  With ``control`` the fp8 control's readings
        take the program's place, and the program's are kept beside them
        under ``program_``."""
        self.state = None
        self.trainer = None
        gc.collect()
        opt = {k: self.mix[k] for k in ("peak_lr", "warmup_steps", "schedule_steps")}
        rows = [self.rows(i) for i in range(CHECK_STEPS)]
        start = lambda: weights.make(self.shapes, seed)  # noqa: E731
        ref = reference.train_steps(self.arch, start, rows, opt, CHECK_STEPS)
        if not control:
            return compare(self.readings, ref)
        low = reference.train_steps(self.arch, start, rows, opt, CHECK_STEPS, mode="fp8")
        out = compare(low, ref)
        out.update({f"program_{k}": v for k, v in compare(self.readings, ref).items()})
        return out



Cell = TrainCell


def compare(got: dict, want: dict) -> dict:
    """The numbers that decide ``correct``, each by the worst leaf:

    * ``loss_gap``: the largest |got - want| of the step losses;
    * ``grad_norm_gap``: each leaf's first-gradient norm;
    * ``change_norm_gap``: each leaf's change after the steps;
    * ``row_gap``: the first gradient's row and column RMS, each against
      the reference's, over the larger of the reference's and the leaf's
      median, averaged over the leaf.

    A gap of norms (the first three) is second order in a rounding error
    that is random, so a lower precision can pass it; ``row_gap`` is first
    order.  Leaves whose reference gradient is under a thousandth of the
    median leaf's are left out of every leaf number."""
    grad = np.asarray(want["grad"], np.float64)
    keep = grad >= 1e-3 * float(np.median(grad))

    def norm_gap(key):
        g, w = np.asarray(got[key], np.float64), np.asarray(want[key], np.float64)
        return float(np.max((np.abs(g - w) / np.maximum(w, np.median(w)))[keep]))

    rows = [float(np.mean(np.abs(g - w) / np.maximum(w, np.median(w))))
            for g, w, k in zip(got["rms"], want["rms"], keep) if k]
    return {
        "loss_gap": float(np.max(np.abs(np.asarray(got["losses"]) - np.asarray(want["losses"])))),
        "grad_norm_gap": norm_gap("grad"),
        "change_norm_gap": norm_gap("change"),
        "row_gap": max(rows),
        "leaves_left_out": int((~keep).sum()),
        "losses": [float(x) for x in got["losses"]],
        "reference_losses": [float(x) for x in want["losses"]],
    }
