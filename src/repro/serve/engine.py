"""Continuous-batching serving engine (JetStream/MaxText-style).

Requests flow through three separated phases, each a reused jit executable:

  * **prefill** — the whole (padded) prompt in one jit call: chunked flash
    attention writes K/V straight into a single-request cache
    (``repro.models.prefill_step``; the compute-bound phase the paper
    targets), and the first token is sampled from the last true position's
    logits.  Prompts are padded to a small set of power-of-two *buckets* so
    the executable is compiled once per bucket, never per prompt length.
  * **insert** — the prefilled single-request cache is copied into a free
    batch slot of the shared decode cache (``repro.models.insert_cache``).
  * **generate** — one batched decode step advances *every* live slot by
    one token.  The cache keeps per-slot lengths, so requests with
    different prompt lengths and decode depths coexist in one batch; slots
    retire at EOS/max_tokens/capacity and are back-filled from the queue
    every step.

The engine is family-agnostic (dense/MoE/VLM use the flash prefill path;
hybrid/SSM teacher-force under one ``lax.scan``) and optionally shards the
decode cache over an ambient mesh via ``repro.dist.sharding``.

With ``spec=SpecConfig(...)`` (repro.spec) the generate phase runs
speculatively: a draft model proposes K greedy tokens per slot, the target
verifies all of them in one wide teacher-forced forward against the live
cache, and rejected suffixes roll back by per-slot length truncation.
Greedy outputs stay token-identical to vanilla decode — only the step
count changes.

Telemetry (``repro.obs``): every engine owns a metrics ``Registry`` —
request-lifecycle histograms (``serve_ttft_seconds``,
``serve_tpot_seconds``, ``serve_queue_wait_seconds``), slot-occupancy /
batch-utilization / queue-depth gauges, per-phase jit-executable gauges,
spec acceptance, and per-phase MFU gauges against the devices' bf16 peak
(``repro.obs.mfu``).  The legacy ``stats`` dict is now a property over the
registry counters.  With a real ``Tracer`` installed (``--trace-out``),
phases emit live spans and each retired request leaves queued/prefill/
decode spans on its slot's lane.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.dist.collectives import mesh_context
from repro.models import decode_step, init_cache, insert_cache, prefill_step
from repro.obs import MFUMeter, Registry, get_tracer
from .serve_step import SamplingConfig, make_decode_step, sample_logits


@dataclasses.dataclass(eq=False)
class Request:
    # eq=False: the generated __eq__ would compare the ndarray `prompt`
    # field, making `r in wave` membership raise ("truth value of an array
    # is ambiguous") for distinct same-length prompts.  Requests are
    # identity-equal; `rid` is the stable external key.
    rid: int
    prompt: np.ndarray  # [len] int32 (lists/other int dtypes are coerced)
    max_new_tokens: int = 16
    eos_id: int = -1  # -1: never
    output: list = dataclasses.field(default_factory=list)
    done: bool = False
    # Lifecycle timestamps (engine-clock seconds), filled in by the engine:
    # enqueue -> prefill start -> first token -> last token.  They back the
    # TTFT/TPOT/queue-wait histograms and the per-request trace spans.
    t_submit: Optional[float] = None
    t_prefill: Optional[float] = None
    t_first_token: Optional[float] = None
    t_last_token: Optional[float] = None

    def __post_init__(self):
        # Callers naturally pass Python lists; everything downstream
        # (shape-based bucketing, pad copies) needs ndarray semantics.
        self.prompt = np.asarray(self.prompt, dtype=np.int32)


def default_buckets(max_len: int, lo: int = 16) -> tuple[int, ...]:
    """Power-of-two prefill buckets up to (and excluding padding past)
    ``max_len``: the largest bucket equals the cache capacity."""
    buckets = []
    b = lo
    while b < max_len:
        buckets.append(b)
        b *= 2
    buckets.append(max_len)
    return tuple(buckets)


class ServeEngine:
    """Continuous-batching engine with per-slot cache state."""

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        batch_size: int = 4,
        max_len: int = 256,
        prefill_chunk: Optional[int] = None,
        prefill_buckets: Optional[tuple[int, ...]] = None,
        sampling: Optional[SamplingConfig] = None,
        mesh=None,
        spec=None,  # Optional[repro.spec.SpecConfig]: speculative decoding
        draft_params=None,  # draft model params (self-draft reuses `params`)
        registry: Optional[Registry] = None,  # repro.obs metrics sink
        tracer=None,  # repro.obs Tracer (default: ambient, usually Null)
    ):
        assert cfg.family != "encoder", "encoder archs have no decode phase"
        self.cfg, self.params = cfg, params
        self.batch, self.max_len = batch_size, max_len
        self.prefill_chunk = prefill_chunk
        self.sampling = sampling or SamplingConfig()
        self.mesh = mesh
        self.buckets = tuple(sorted(prefill_buckets or default_buckets(max_len)))
        assert self.buckets[-1] <= max_len, "bucket exceeds cache capacity"

        self.queue: deque[Request] = deque()
        self.slots: list[Optional[Request]] = [None] * batch_size
        self.cache = None
        # Host-side per-slot decode state: the position the next token will
        # be written at (== tokens cached), and the last sampled token that
        # the next generate step consumes.
        self._positions = np.zeros(batch_size, np.int32)
        self._next_tok = np.zeros(batch_size, np.int32)
        self._done: list[Request] = []
        self._step_idx = 0
        self._prefill_idx = 0
        self._base_key = jax.random.PRNGKey(self.sampling.seed)

        # -- telemetry (repro.obs): engine-scoped registry so concurrent
        # engines (e.g. spec target + vanilla baseline in one bench) never
        # share counters; the tracer defaults to the ambient one, which is
        # the free NullTracer unless a launcher installed a real Tracer.
        self.registry = registry if registry is not None else Registry()
        self.tracer = tracer if tracer is not None else get_tracer()
        self.mfu = MFUMeter(
            cfg, self.registry, chips=mesh.devices.size if mesh is not None else 1
        )
        self._stat_keys = ["prefill_calls", "insert_calls", "decode_steps"]
        self._counters = {
            k: self.registry.counter(f"serve_{k}_total", h)
            for k, h in [
                ("prefill_calls", "prefill jit invocations"),
                ("insert_calls", "cache-insert jit invocations"),
                ("decode_steps", "batched generate steps"),
            ]
        }
        self._tokens_total = self.registry.counter(
            "serve_tokens_total", "tokens emitted across all requests"
        )
        self._requests_total = self.registry.counter(
            "serve_requests_completed_total", "requests retired"
        )
        self._h_ttft = self.registry.histogram(
            "serve_ttft_seconds", "submit -> first token"
        )
        self._h_tpot = self.registry.histogram(
            "serve_tpot_seconds", "per-token latency of batched decode steps"
        )
        self._h_queue = self.registry.histogram(
            "serve_queue_wait_seconds", "submit -> prefill start"
        )
        self._h_prefill = self.registry.histogram(
            "serve_prefill_seconds", "prefill + insert wall time"
        )
        self._h_batch_util = self.registry.histogram(
            "serve_batch_utilization", "live slots / batch per generate step",
            buckets=tuple(np.round(np.arange(0.05, 1.05, 0.05), 2)),
        )
        self._g_occupancy = self.registry.gauge(
            "serve_slot_occupancy", "fraction of decode slots live"
        )
        self._g_queue_depth = self.registry.gauge(
            "serve_queue_depth", "requests waiting for a slot"
        )
        self._g_compiled = self.registry.gauge(
            "serve_jit_executables", "compiled executables per engine phase",
            ("phase",),
        )

        # -- speculative decoding (repro.spec): draft worker + verify jit --
        self.spec = spec
        self.draft = None
        if spec is not None:
            # Imported lazily: repro.spec pulls in repro.serve.serve_step,
            # so a module-level import here would be circular.
            from repro.spec import DraftWorker, make_spec_verify, resolve_draft_config

            if not self.sampling.greedy:
                raise ValueError(
                    "speculative decoding requires greedy sampling "
                    "(lossless greedy acceptance)"
                )
            self.draft_cfg = resolve_draft_config(spec, cfg)
            if draft_params is None:
                if spec.draft_arch is not None:
                    raise ValueError(
                        "draft_params is required when draft_arch names a "
                        "distinct model"
                    )
                draft_params = params  # self-draft
            self.draft = DraftWorker(
                self.draft_cfg, draft_params,
                batch_size=batch_size, max_len=max_len,
                prefill_chunk=prefill_chunk,
            )
            self._verify_jit = jax.jit(make_spec_verify(cfg))
            spec_keys = [
                ("verify_steps", "wide verify forwards"),
                ("draft_steps", "draft decode steps"),
                ("proposed_tokens", "draft tokens proposed"),
                ("accepted_tokens", "draft tokens the target accepted"),
            ]
            self._stat_keys += [k for k, _ in spec_keys]
            self._counters.update(
                {k: self.registry.counter(f"serve_{k}_total", h)
                 for k, h in spec_keys}
            )
            self._g_acceptance = self.registry.gauge(
                "spec_acceptance_rate",
                "cumulative fraction of proposed draft tokens accepted",
            )

        scfg = self.sampling

        def _prefill(params, tokens, true_len, key):
            # tokens [1, bucket]; a fresh single-request cache sized to the
            # bucket (not max_len) keeps prefill memory and the insert copy
            # proportional to the prompt, MaxText-style.
            bucket = tokens.shape[1]
            cache = init_cache(cfg, 1, bucket)
            logits, cache = prefill_step(
                params, cfg, tokens, cache,
                jnp.reshape(true_len, (1,)),
                chunk_size=self.prefill_chunk,
            )
            last = jnp.take(logits[0], true_len - 1, axis=0)  # [V]
            return sample_logits(last, key, scfg), cache

        # One jitted callable each; distinct buckets become distinct cache
        # entries of the same executable family (``_cache_size()`` counts
        # them — the recompile tests pin it to the bucket count).
        def _insert(cache, prefix, slot):
            # Closure (not `jax.jit(insert_cache)` directly): pjit caches on
            # function identity, so jitting the shared module-level function
            # would pool executables across engines and make per-engine
            # compile_counts() meaningless.
            return insert_cache(cache, prefix, slot)

        # Under a mesh the cache keeps the layout it is created with through
        # every insert and decode step, so each compiles once.
        self._cache_sh = None
        if mesh is not None:
            from repro.dist.sharding import cache_shardings

            self._cache_sh = cache_shardings(
                jax.eval_shape(self._new_cache), cfg, mesh
            )
        self._prefill_jit = jax.jit(_prefill)
        self._insert_jit = jax.jit(_insert, out_shardings=self._cache_sh)
        self._decode_jit = jax.jit(
            make_decode_step(cfg, sampling=scfg),
            out_shardings=(None, None, self._cache_sh),
        )

    # -- introspection ------------------------------------------------------

    @property
    def stats(self) -> dict:
        """Legacy raw-counter view, now backed by the ``repro.obs``
        registry (``serve_*_total`` counters).  Returns a fresh plain dict
        each access, so ``dict(engine.stats)`` / delta-subtraction idioms
        from existing tests and benchmarks keep working."""
        return {k: int(self._counters[k].value) for k in self._stat_keys}

    def compile_counts(self) -> dict:
        """Executables compiled so far, per phase (also exported as the
        ``serve_jit_executables`` gauge)."""
        counts = {
            "prefill": self._prefill_jit._cache_size(),
            "insert": self._insert_jit._cache_size(),
            "generate": self._decode_jit._cache_size(),
        }
        if self.draft is not None:
            counts["verify"] = self._verify_jit._cache_size()
            counts.update(self.draft.compile_counts())
        for phase, n in counts.items():
            self._g_compiled.labels(phase=phase).set(n)
        return counts

    def acceptance_rate(self) -> float:
        """Fraction of proposed draft tokens the target accepted."""
        proposed = self._counters["proposed_tokens"].value if self.draft else 0
        return self._counters["accepted_tokens"].value / proposed if proposed else 0.0

    # -- request intake -----------------------------------------------------

    def submit(self, req: Request) -> None:
        if len(req.prompt) > self.buckets[-1]:
            raise ValueError(
                f"prompt length {len(req.prompt)} exceeds the largest prefill "
                f"bucket {self.buckets[-1]}"
            )
        req.t_submit = time.perf_counter()
        self.queue.append(req)
        self._g_queue_depth.set(len(self.queue))

    def _bucket_for(self, plen: int) -> int:
        for b in self.buckets:
            if plen <= b:
                return b
        raise ValueError(plen)  # unreachable: submit() validates

    # -- engine phases ------------------------------------------------------

    def _new_cache(self):
        return init_cache(self.cfg, self.batch, self.max_len)

    def _ensure_cache(self) -> None:
        if self.cache is not None:
            return
        if self.mesh is None:
            self.cache = self._new_cache()
        else:
            # Created sharded: each device allocates only its own shards.
            self.cache = jax.jit(self._new_cache, out_shardings=self._cache_sh)()

    def _prefill_into_slot(self, req: Request, slot: int) -> int:
        """Prefill ``req`` (one jit call) and insert it into ``slot``."""
        plen = len(req.prompt)
        bucket = self._bucket_for(plen)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :plen] = req.prompt
        key = jax.random.fold_in(self._base_key, self._prefill_idx)
        self._prefill_idx += 1
        req.t_prefill = t0 = time.perf_counter()
        with mesh_context(self.mesh), self.tracer.span(
            "prefill", cat="serve", tid=slot,
            args={"rid": req.rid, "len": plen, "bucket": bucket},
        ):
            tok0, prefix = self._prefill_jit(
                self.params, jnp.asarray(toks), jnp.asarray(plen, jnp.int32), key
            )
            self.cache = self._insert_jit(
                self.cache, prefix, jnp.asarray(slot, jnp.int32)
            )
            tok0 = int(tok0)  # blocks: the first token is now on the host
        # The first token is sampled inside prefill, so TTFT == queue wait
        # plus the prefill span.
        req.t_first_token = req.t_last_token = now = time.perf_counter()
        self._counters["prefill_calls"].inc()
        self._counters["insert_calls"].inc()
        self._tokens_total.inc()
        self._h_prefill.observe(now - t0)
        self._h_queue.observe(t0 - req.t_submit)
        self._h_ttft.observe(now - req.t_submit)
        self.mfu.prefill(plen, now - t0)
        self._g_queue_depth.set(len(self.queue))
        self._positions[slot] = plen
        self._next_tok[slot] = tok0
        return tok0

    def _retire(self, slot: int) -> None:
        req = self.slots[slot]
        req.done = True
        self._done.append(req)
        self.slots[slot] = None
        self._finish(req, slot)

    def _finish(self, req: Request, slot: int) -> None:
        """Close out a request's telemetry: completion counter plus the
        retroactive per-request lifecycle spans (queue-wait -> prefill ->
        decode) on the slot's trace lane."""
        self._requests_total.inc()
        tr = self.tracer
        if req.t_submit is not None and req.t_prefill is not None:
            tr.complete_abs(
                "queued", req.t_submit, req.t_prefill, cat="request",
                tid=slot, args={"rid": req.rid},
            )
        if req.t_first_token is not None and req.t_last_token is not None:
            n = len(req.output)
            tr.complete_abs(
                "decode", req.t_first_token, req.t_last_token, cat="request",
                tid=slot, args={"rid": req.rid, "tokens": n},
            )
            tr.instant("retire", tid=slot, args={"rid": req.rid, "tokens": n})

    def step(self) -> bool:
        """Back-fill free slots, then advance every live slot one token.

        Returns True while work remains (live slots or queued requests).
        """
        self._ensure_cache()
        # Insert phase: fill every free slot from the queue.  A request
        # that completes at prefill (max_new_tokens == 1 or immediate EOS)
        # retires without occupying the slot.
        for i in range(self.batch):
            while self.slots[i] is None and self.queue:
                req = self.queue.popleft()
                tok0 = self._prefill_into_slot(req, i)
                req.output.append(tok0)
                if tok0 == req.eos_id or req.max_new_tokens <= 1:
                    req.done = True
                    self._done.append(req)
                    self._finish(req, i)
                else:
                    self.slots[i] = req
                    if self.draft is not None:
                        # Mirror the insert into the draft's slot pool so
                        # its context matches the target's from round one.
                        self.draft.prefill_into_slot(
                            req.prompt, i, self._bucket_for(len(req.prompt))
                        )

        live = [i for i in range(self.batch) if self.slots[i] is not None]
        self._g_occupancy.set(len(live) / self.batch)
        self._g_queue_depth.set(len(self.queue))
        if not live:
            return bool(self.queue)
        self._h_batch_util.observe(len(live) / self.batch)

        if self.draft is not None:
            self._spec_generate(live)
        else:
            self._generate(live)
        return bool(self.queue or any(r is not None for r in self.slots))

    def _generate(self, live: list) -> None:
        """Vanilla generate: one batched decode step, one token per slot."""
        args = (
            self.params,
            self.cache,
            jnp.asarray(self._next_tok[:, None]),
            jnp.asarray(self._positions),
        )
        t0 = time.perf_counter()
        with mesh_context(self.mesh), self.tracer.span(
            "generate", cat="serve", tid=0,
            args={"live": len(live), "step": self._step_idx},
        ):
            if self.sampling.greedy:
                nt, _logits, self.cache = self._decode_jit(*args)
            else:
                key = jax.random.fold_in(self._base_key, 2**20 + self._step_idx)
                nt, _logits, self.cache = self._decode_jit(*args, key)
            nt = np.asarray(nt)[:, 0]  # blocks on the decode result
        now = time.perf_counter()
        self._counters["decode_steps"].inc()
        self._tokens_total.inc(len(live))
        # One batched step emits one token per live slot, so the step wall
        # time *is* each slot's per-token latency this round.
        self._h_tpot.observe(now - t0)
        self.mfu.decode(self._positions[live], now - t0)
        self._step_idx += 1

        self._positions[live] += 1
        for i in live:
            req = self.slots[i]
            req.t_last_token = now
            tok = int(nt[i])
            req.output.append(tok)
            if (
                tok == req.eos_id
                or len(req.output) >= req.max_new_tokens
                or self._positions[i] >= self.max_len  # cache slot exhausted
            ):
                self._retire(i)
            else:
                self._next_tok[i] = tok

    def _spec_generate(self, live: list) -> None:
        """Speculative generate: K draft steps + one wide verify pass.

        Emits between 1 and K+1 tokens per live slot per round.  The
        emitted tokens are always the target's own greedy continuation
        (``repro.spec.verify``), so the output stream is token-identical
        to ``_generate``'s — speculation changes step count, never tokens.
        """
        k = self.spec.lookahead
        t0 = time.perf_counter()
        with self.tracer.span("draft", cat="serve", tid=0, args={"k": k}):
            drafts = self.draft.propose(self._next_tok, k)  # [B, K]
        tokens = np.concatenate(
            [self._next_tok[:, None], drafts], axis=1
        ).astype(np.int32)
        t1 = time.perf_counter()
        with mesh_context(self.mesh), self.tracer.span(
            "verify", cat="serve", tid=0, args={"live": len(live), "k": k}
        ):
            greedy, accepted, self.cache = self._verify_jit(
                self.params, self.cache,
                jnp.asarray(tokens), jnp.asarray(self._positions),
            )
            greedy, accepted = np.asarray(greedy), np.asarray(accepted)
        now = time.perf_counter()
        self._counters["verify_steps"].inc()
        self._counters["draft_steps"].inc(k + 1)
        self.mfu.verify(self._positions[live], k, now - t1)
        # Per-token latency of the round: the full draft+verify wall time
        # amortized over the tokens it emitted (upper bound: early
        # retirement can drop a few of them).
        emitted = int(np.sum(accepted[live] + 1))
        self._h_tpot.observe((now - t0) / max(emitted, 1))
        self._step_idx += 1

        # Post-verify lengths (the in-jit rollback already clamped
        # ``accepted`` to cache capacity); the draft mirrors them so both
        # caches hold exactly the accepted prefix next round.
        new_lengths = self._positions + accepted + 1

        for i in live:
            req = self.slots[i]
            req.t_last_token = now
            pos0 = int(self._positions[i])
            n = int(accepted[i])
            self._counters["proposed_tokens"].inc(k)
            self._counters["accepted_tokens"].inc(n)
            # Consume the emitted run token by token, applying the same
            # retirement rules (EOS / max_new_tokens / capacity) at the
            # same points vanilla decode would.
            for j in range(n + 1):
                tok = int(greedy[i, j])
                req.output.append(tok)
                self._tokens_total.inc()
                self._positions[i] = pos0 + j + 1
                if (
                    tok == req.eos_id
                    or len(req.output) >= req.max_new_tokens
                    or pos0 + j + 1 >= self.max_len
                ):
                    self._retire(i)
                    break
            else:
                self._next_tok[i] = int(greedy[i, n])
        self._g_acceptance.set(self.acceptance_rate())
        self.draft.rollback(new_lengths)

    def run(self, max_steps: int = 100_000) -> list[Request]:
        """Drain the queue; returns completed requests."""
        steps = 0
        while steps < max_steps:
            steps += 1
            if not self.step():
                break
        self.compile_counts()  # refresh the serve_jit_executables gauges
        done, self._done = self._done, []
        return done


def sequential_greedy_decode(
    cfg: ModelConfig,
    params,
    prompt: np.ndarray,
    max_new_tokens: int,
    *,
    eos_id: int = -1,
    max_len: Optional[int] = None,
) -> list[int]:
    """Obviously-correct single-request baseline: teacher-forced per-token
    prefill plus greedy decode, batch 1, one jit dispatch per token.  The
    engine's token-equivalence harness checks continuous batching against
    exactly this."""
    plen = len(prompt)
    max_len = max_len or plen + max_new_tokens
    step = jax.jit(decode_step, static_argnums=1)
    cache = init_cache(cfg, 1, max_len)
    logits = None
    for i in range(plen):
        t = jnp.asarray([[int(prompt[i])]], jnp.int32)
        logits, cache = step(params, cfg, t, cache, jnp.asarray(i, jnp.int32))
    out = [int(jnp.argmax(logits[0, -1]))]
    pos = plen
    while len(out) < max_new_tokens and out[-1] != eos_id and pos < max_len:
        t = jnp.asarray([[out[-1]]], jnp.int32)
        logits, cache = step(params, cfg, t, cache, jnp.asarray(pos, jnp.int32))
        out.append(int(jnp.argmax(logits[0, -1])))
        pos += 1
    return out
