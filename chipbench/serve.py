"""Serving cells: the program's ``ServeEngine``, built as its launcher
(``repro.launch.serve``) builds it, and driven through ``submit`` and
``step``.  Set-up makes the weights, builds the engine, warms every prefill
bucket that the traffic uses, the insert of each and the decode step,
submits the backlog and steps once, so that every slot is live.  The window
calls ``engine.step()`` until its time is up.

The check runs after the window.  It takes a sample of the requests that
the window finished, drawn from the seed with the longest among them, and
runs them again through the compiled programs that the window ran, at the
window's shapes: each prompt through ``_prefill_jit`` and ``_insert_jit``
into its own slot of the slots' cache, then ``_decode_jit`` over all slots
at once, fed the tokens that the window served.  The reference then
computes the full forward of each prompt with its served tokens
(``serve_reference``), and ``compare`` reads the gaps.
"""

from __future__ import annotations

import gc
import sys
import time

import numpy as np

from . import program, serve_reference, traffic, weights

STEP_SPAN = "chipbench.step"
PLANTS = ("none", "control", "unchanged", "half_batch", "token")


class ServeCell:
    def __init__(self, conf: dict, arch: dict, mix: dict, *, tracing: bool = False,
                 plant: str = "none"):
        if plant not in PLANTS:
            raise ValueError(f"the serving cell plants none of {plant!r}")
        self.conf, self.arch, self.mix = conf, arch, mix
        self.tracing, self.plant = tracing, plant

    def setup(self, seed: int, seconds: float) -> None:
        from repro.obs import Tracer
        from repro.serve import Request, SamplingConfig, ServeEngine

        m = self.mix
        if m["sampling"] != "greedy":
            raise ValueError("the check compares greedy tokens only")
        cfg = program.model_config(self.conf, self.arch)
        self.shapes = program.param_shapes(cfg)
        params = weights.make(self.shapes, seed)
        self.engine = eng = ServeEngine(
            cfg, params, batch_size=m["slots"], max_len=m["max_len"],
            prefill_chunk=m["prefill_chunk"], sampling=SamplingConfig(),
            tracer=Tracer(process_name="chipbench") if self.tracing else None)
        del params
        self._plant_faults()
        self.decodes: list[np.ndarray] = []
        self.decode_steps: list[int] = []
        self.step_ends: list[float] = []
        if self.tracing:
            self._log_decodes()

        # One request per prefill bucket that the traffic uses, at the
        # shortest of its lengths in that bucket, through prefill, insert
        # and one decode step.
        lengths = traffic.quantiles(m["prompt"], m["block"])
        warm = {}
        for n in lengths:
            warm.setdefault(eng._bucket_for(int(n)), int(n))
        for i, n in enumerate(warm.values()):
            eng.submit(Request(rid=-1 - i, prompt=np.zeros(n, np.int32), max_new_tokens=2,
                               eos_id=m["eos_id"]))
        while eng.step():
            pass

        self.requests = [
            Request(rid=i, prompt=p, max_new_tokens=o, eos_id=m["eos_id"])
            for i, (p, o) in enumerate(traffic.requests(m, seed, self.arch["vocab"],
                                                        self.conf["eos_token_id"]))]
        for r in self.requests:
            eng.submit(r)
        eng.step()  # every slot live; these prefills are not counted
        if any(s is None for s in eng.slots):
            raise ValueError("the backlog does not fill every slot")
        self.decodes.clear()
        self.decode_steps.clear()

    def _plant_faults(self) -> None:
        """Faults for the tests of the check, planted in the engine's
        compiled calls: ``unchanged`` skips the insert, so decoding reads a
        slot that the prefill never reached; ``half_batch`` prefills each
        prompt from its second half only; ``token`` alters every decoded
        token where the decode step returns it."""
        import jax.numpy as jnp

        eng = self.engine
        if self.plant == "unchanged":
            eng._insert_jit = lambda cache, prefix, slot: cache
        elif self.plant == "half_batch":
            prefill = eng._prefill_jit

            def half(params, toks, true_len, key):
                n = int(true_len)
                t = np.asarray(toks).copy()
                t[0, : n - n // 2] = t[0, n // 2: n]
                t[0, n - n // 2:] = 0
                return prefill(params, jnp.asarray(t), jnp.asarray(n - n // 2, jnp.int32), key)

            eng._prefill_jit = half
        elif self.plant == "token":
            decode, vocab = eng._decode_jit, self.arch["vocab"]

            def altered(*args):
                nt, logits, cache = decode(*args)
                return (nt + 1) % vocab, logits, cache

            eng._decode_jit = altered

    def _log_decodes(self) -> None:
        """Record, at each decode call, the keys that each live slot's token
        attends to (its position + 1) and the window's step it came in, for
        the readers of traced runs."""
        eng = self.engine
        decode = eng._decode_jit

        def logged(*args):
            live = [i for i, r in enumerate(eng.slots) if r is not None]
            self.decodes.append(eng._positions[live] + 1)
            self.decode_steps.append(len(self.step_ends))
            return decode(*args)

        eng._decode_jit = logged

    def window(self, seconds: float, hooks) -> dict:
        import jax

        eng = self.engine
        steps = []  # per step: start, wall s, main thread's CPU s, collector's s
        gc_s = [0.0, None]

        def collector(phase, info):
            if phase == "start":
                gc_s[1] = time.perf_counter()
            elif gc_s[1] is not None:
                gc_s[0] += time.perf_counter() - gc_s[1]

        gc.callbacks.append(collector)
        t0 = time.perf_counter()
        hooks.tick(t0)
        try:
            while time.perf_counter() - t0 < seconds:
                t, cpu, col = time.perf_counter(), time.thread_time(), gc_s[0]
                with jax.profiler.TraceAnnotation(STEP_SPAN):
                    eng.step()
                now = time.perf_counter()
                self.step_ends.append(now)
                steps.append((t, now - t, time.thread_time() - cpu, gc_s[0] - col))
                hooks.tick(now)
        finally:
            gc.callbacks.remove(collector)
        t_end = time.perf_counter()
        hooks.tick(t_end, closing=True)
        self.memory_peak = hooks.memory_peak()
        served = sorted((r for r in self.requests
                         if r.t_first_token is not None and t0 <= r.t_prefill <= t_end),
                        key=lambda r: r.t_prefill)
        # The order, lengths and times of the window's prefills, for the
        # readers.
        self.prefills = [(len(r.prompt), eng._bucket_for(len(r.prompt))) for r in served]
        self.prefill_times = [r.t_prefill for r in served]
        self.finished = [r for r in self.requests if r.done]
        short = [r for r in self.finished if len(r.output) < r.max_new_tokens]
        rate = sum(len(r.prompt) for r in served) / (t_end - t0)
        # Where a run reads low, the slowest steps say whether the host was
        # busy (its CPU time near the wall time) or waited, and on which
        # prefill buckets.
        slow = []
        for t, wall, cpu, col in sorted(steps, key=lambda x: -x[1])[:3]:
            buckets = [b for (_, b), tp in zip(self.prefills, self.prefill_times)
                       if t <= tp <= t + wall]
            slow.append(f"{wall:.4f} s (CPU {cpu:.4f}, collector {col:.4f}, "
                        f"prefill buckets {buckets})")
        print(f"chipbench: window {t_end - t0:.3f} s, {len(steps)} steps, {len(served)} "
              f"prefills, {rate!r} prompt tokens/s; slowest steps: {'; '.join(slow)}",
              file=sys.stderr)
        return {
            "attempted": len(served), "failed": len(short), "window_s": t_end - t0,
            "prefill_tokens_per_s": rate,
            "t0": t0, "t_close": t_end,
        }

    def traced(self, tr) -> dict:
        """What the cell recorded in the steps that the trace ``tr`` holds
        whole (its ``chipbench.step`` spans, which begin with the window's
        first step): the prefills, as (true length, bucket), and the decode
        calls, as the keys of each live slot."""
        lo, hi = tr.window
        k = sum(1 for h in tr.host
                if h.name == STEP_SPAN and h.start >= lo and h.end <= hi)
        until = self.step_ends[k - 1] if k else float("-inf")
        return {"prefills": [p for p, t in zip(self.prefills, self.prefill_times)
                             if t <= until],
                "decodes": [d for d, s in zip(self.decodes, self.decode_steps) if s < k]}

    def sample(self, seed: int) -> list:
        """The requests that the check compares: the finished request with
        the most tokens (prompt and output), then others drawn from the
        seed, ``check_requests`` in all."""
        done = self.finished
        if not done:
            return []
        longest = max(range(len(done)), key=lambda i: len(done[i].prompt) + len(done[i].output))
        rest = [i for i in range(len(done)) if i != longest]
        rng = traffic.rng_for(seed, "check")
        k = min(self.mix["check_requests"], len(done)) - 1
        return [done[longest]] + [done[i] for i in rng.choice(rest, size=k, replace=False)]

    def rerun(self, sample: list) -> dict:
        """The sample through the window's compiled programs: each prompt
        prefilled and inserted into slot i, then every slot decoded at once,
        fed the tokens that the window served.  Returns the first tokens and,
        per request, the decode logits (float32, one row per served token
        after the first)."""
        import jax.numpy as jnp

        eng = self.engine
        first = []
        for i, r in enumerate(sample):
            n = len(r.prompt)
            toks = np.zeros((1, eng._bucket_for(n)), np.int32)
            toks[0, :n] = r.prompt
            tok0, prefix = eng._prefill_jit(eng.params, jnp.asarray(toks),
                                            jnp.asarray(n, jnp.int32), eng._base_key)
            eng.cache = eng._insert_jit(eng.cache, prefix, jnp.asarray(i, jnp.int32))
            first.append(int(tok0))
        pos = np.zeros(eng.batch, np.int32)
        feed = np.zeros(eng.batch, np.int32)
        pos[: len(sample)] = [len(r.prompt) for r in sample]
        feed[: len(sample)] = [r.output[0] for r in sample]
        rows = [[] for _ in sample]
        for j in range(max(len(r.output) for r in sample) - 1):
            _, logits, eng.cache = eng._decode_jit(eng.params, eng.cache,
                                                   jnp.asarray(feed[:, None]), jnp.asarray(pos))
            got = np.asarray(logits)[:, 0].astype(np.float32)
            for i, r in enumerate(sample):
                if j + 1 < len(r.output):
                    rows[i].append(got[i])
                    feed[i] = r.output[j + 1]
            pos += 1
        return {"first": first, "logits": [np.stack(x) if x else None for x in rows]}

    def check(self, seed: int, control: bool = False) -> dict:
        """The sample's served tokens and re-run logits against the
        reference.  With ``control`` the fp8 control's readings take the
        program's place, and the program's are kept beside them under
        ``program_``."""
        sample = self.sample(seed)
        if not sample:
            return {"token_gap": float("nan"), "logit_gap": float("nan"),
                    "served_gap": float("nan"), "compared": 0}
        got = self.rerun(sample)
        self.engine = None
        gc.collect()
        params = weights.make(self.shapes, seed)
        seqs = [np.concatenate([r.prompt, np.asarray(r.output[:-1], np.int32)]) for r in sample]
        rows = [np.arange(len(r.prompt) - 1, len(s)) for r, s in zip(sample, seqs)]
        ref = [serve_reference.logits(self.arch, params, s, w) for s, w in zip(seqs, rows)]
        served = [np.asarray(r.output) for r in sample]
        out = compare(served, got, ref)
        if control:
            low = [serve_reference.logits(self.arch, params, s, w, mode="fp8")
                   for s, w in zip(seqs, rows)]
            fp8 = {"first": [int(np.argmax(x[0])) for x in low],
                   "logits": [x[1:] if len(x) > 1 else None for x in low]}
            program_out = out
            out = compare(served, fp8, ref, picked=[np.argmax(x, -1) for x in low])
            out.update({f"program_{k}": v for k, v in program_out.items()})
        return out


Cell = ServeCell


def compare(served: list, got: dict, ref: list, picked: list | None = None) -> dict:
    """The numbers that decide ``correct``, over the sampled requests:

    * ``token_gap``: 1 if a served token is not the one that ``got`` puts
      first at its position (its first token, then the argmax of its decode
      logits), else 0;
    * ``logit_gap``: the largest |got - reference| of a decode position's
      logits over the RMS of the reference's logits there;
    * ``served_gap``: the largest gap by which the reference's logit of a
      picked token lies below the reference's best at its position, over
      that RMS.  The picked tokens are the served ones, or ``picked`` where
      something else stands in the program's place (the control's own).

    ``served[i]`` are request i's served tokens, ``got["first"][i]`` its
    first token and ``got["logits"][i]`` its decode logits (one row per
    served token after the first, or None); ``ref[i]`` the reference's
    logits at every served token's position."""
    token, logit, gap, n = 0, 0.0, 0.0, 0
    picked = served if picked is None else picked
    for toks, pick, first, lg, r in zip(served, picked, got["first"], got["logits"], ref):
        rms = np.sqrt(np.mean(np.square(r, dtype=np.float64), axis=-1))
        top = r.max(-1)
        gap = max(gap, float(np.max((top - r[np.arange(len(pick)), pick]) / rms)))
        mine = [first] + (list(np.argmax(lg, -1)) if lg is not None else [])
        token = max(token, int(np.any(np.asarray(mine) != toks)))
        if lg is not None:
            diff = np.max(np.abs(lg.astype(np.float64) - r[1:]), axis=-1)
            logit = max(logit, float(np.max(diff / rms[1:])))
        n += len(toks)
    return {"token_gap": float(token), "logit_gap": logit, "served_gap": gap,
            "compared": n}
