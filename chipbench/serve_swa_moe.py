"""The serving cell of a model with sliding-window and full attention layers
and sparse experts in every layer (Mellum2): ``serve.ServeCell``, with the
program's configuration held to the file's layer kinds, window, experts and
rope, and the check computed by ``swa_moe_reference``.

Set-up refuses a program whose configuration's layer kinds, window,
experts or rope differ from the file's (``model_config``).  The check runs
the sample again through the window's programs, as ``ServeCell``
does, then the reference.  Beside ``serve.compare``'s numbers it reads
``median_logit_gap``, the median over the decode positions of the gap
whose largest is ``logit_gap``: bf16 arithmetic flips the choice between
near-tied experts in a few rows, and the jump that follows sets the
largest gap, while the middle row shows the precision of the rest
(``PERF.md`` section 4).  It also reads ``route_flips`` (the rows whose
expert set bf16 input would change, counted by the reference) and
``check_s``, the check's wall time.  With the control it reads the witness
of what the largest gap is made of, over the first ``WITNESS`` requests:
the program's largest gap there, and the reference's own at bf16, routed
by itself and routed as its float32 pass.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from . import program, serve, swa_moe_reference, weights

WITNESS = 4


def row_gaps(got: list, ref: list) -> np.ndarray:
    """Each decode position's largest |got - reference| over the RMS of the
    reference's logits there, over the requests (``got[i]`` holds one row
    per served token after the first, or None; ``ref[i]`` a row per served
    token): ``serve.compare``'s ``logit_gap`` before its maximum."""
    gaps = [np.max(np.abs(g.astype(np.float64) - r[1:]), -1)
            / np.sqrt(np.mean(np.square(r[1:], dtype=np.float64), -1))
            for g, r in zip(got, ref) if g is not None]
    return np.concatenate(gaps) if gaps else np.full(1, np.nan)


class SwaMoeCell(serve.ServeCell):
    def __init__(self, conf, arch, mix, **kw):
        super().__init__(conf, arch, mix, **kw)
        self.ref_arch = swa_moe_reference.arch_of(conf)

    def model_config(self):
        """``program.model_config`` (the registry's architecture at the
        file's widths), refused where its layer kinds, window, experts or
        rope differ from the file's."""
        cfg = program.model_config(self.conf, self.arch)
        a, y, pattern = self.ref_arch, cfg.rope_yarn, cfg.layer_pattern or ("full",)
        got = {
            "kinds": tuple(pattern[i % len(pattern)] for i in range(cfg.num_layers)),
            "window": cfg.sliding_window,
            "experts": cfg.moe and (cfg.moe.num_experts, cfg.moe.top_k, cfg.moe.d_ff_expert),
            "sliding_theta": cfg.rope_theta,
            "yarn": y and (cfg.rope_theta, y.factor, y.original_max_position_embeddings,
                           y.beta_fast, y.beta_slow, y.attention_factor),
        }
        want = dict(kinds=a["kinds"], window=a["window"],
                    experts=(a["experts"], a["top_k"], a["d_expert"]),
                    sliding_theta=a["sliding_theta"], yarn=a["yarn"])
        if got != want:
            raise ValueError(f"the program's configuration {got} is not the file's {want}")
        return cfg

    def setup(self, seed: int, seconds: float) -> None:
        self.model_config()  # ServeCell.setup builds the same configuration
        super().setup(seed, seconds)

    def check(self, seed: int, control: bool = False) -> dict:
        """As ``ServeCell.check``, with ``swa_moe_reference`` as the
        reference."""
        t0 = time.perf_counter()
        sample = self.sample(seed)
        if not sample:
            return {"token_gap": float("nan"), "logit_gap": float("nan"),
                    "served_gap": float("nan"), "median_logit_gap": float("nan"),
                    "compared": 0}
        got = self.rerun(sample)
        self.engine = None
        gc.collect()
        params = weights.make(self.shapes, seed)
        seqs = [np.concatenate([r.prompt, np.asarray(r.output[:-1], np.int32)]) for r in sample]
        rows = [np.arange(len(r.prompt) - 1, len(s)) for r, s in zip(sample, seqs)]
        ref, flips, routes = zip(*[swa_moe_reference.logits(self.ref_arch, params, s, w)
                                   for s, w in zip(seqs, rows)])
        served = [np.asarray(r.output) for r in sample]
        out = serve.compare(served, got, list(ref))
        out["median_logit_gap"] = float(np.median(row_gaps(got["logits"], ref)))
        out["route_flips"] = float(sum(flips))
        if control:
            low = [self._ref_logits(params, s, w, "fp8") for s, w in zip(seqs, rows)]
            fp8 = {"first": [int(np.argmax(x[0])) for x in low],
                   "logits": [x[1:] if len(x) > 1 else None for x in low]}
            program_out = out
            out = serve.compare(served, fp8, list(ref), picked=[np.argmax(x, -1) for x in low])
            out["median_logit_gap"] = float(np.median(row_gaps(fp8["logits"], ref)))
            out.update({f"program_{k}": v for k, v in program_out.items()})
            k = WITNESS
            out["witness_program_logit_gap"] = float(np.max(row_gaps(got["logits"][:k], ref[:k])))
            for name, route in (("bf16", [None] * k), ("bf16_routed", routes[:k])):
                lg = [self._ref_logits(params, s, w, "bf16", r)[1:]
                      for s, w, r in zip(seqs[:k], rows[:k], route)]
                out[f"witness_{name}_logit_gap"] = float(np.max(row_gaps(lg, ref[:k])))
        out["check_s"] = time.perf_counter() - t0
        return out

    def _ref_logits(self, params, seq, rows, mode, routes=None):
        return swa_moe_reference.logits(self.ref_arch, params, seq, rows, mode, routes)[0]


Cell = SwaMoeCell
