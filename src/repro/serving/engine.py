"""Serving engines with MobiRNN-style runtime policies.

The paper mechanisms are first-class here:
  * preallocated state pools (core/state.StatePool) — decode caches are
    built once and reset in place through donated jits; no allocation on the
    serving path, pool exhaustion = explicit backpressure;
  * load-aware dispatch (core/scheduler.Scheduler) — multiple decode plans
    are registered and the predicted-fastest under current load runs each
    tick (paper Fig 7);
  * fixed-shape batching — the decode step has one shape for the life of
    the engine.

Two engines share that substrate:

``Engine`` — the coarse WAVE engine: requests are packed into lockstep
waves of ``batch_size``; every request pads to the longest prompt and the
longest ``max_new_tokens`` in its wave.  Short waves are padded with
zero-length dummy requests (an inactive lane, not a duplicated real
request).  Kept as the baseline the benchmarks compare against.

``SlotEngine`` — slot-resident CONTINUOUS batching (serving/slots.py): the
batch axis is B independent slots over one preallocated cache; requests are
admitted from a bounded queue into free slots at step granularity, decode
runs one fused masked step across all lanes per tick, and retirement resets
just that lane and immediately admits the next request.  Tokens stream out
per tick (``stream``/``on_token``) instead of arriving all at once.  This
is the engine the ROADMAP's heavy-traffic north star builds on.

Both engines are modality-generic: they serve any registry.Model whose
config family is text-like (dense/moe/ssm/hybrid/vlm/audio all decode
token ids).
"""
from __future__ import annotations

import collections
import dataclasses
import time
import warnings
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.scheduler import Plan, Scheduler, SyntheticLoadSensor
from repro.core.state import StatePool, make_buffer
from repro.obs import trace as trace_lib
from repro.obs.metrics import Metrics
from repro.models.registry import Model
from repro.partitioning import split
from repro.serving import faults as faults_lib
from repro.serving.slots import (FinishReason, PrefillLane, QueueFull,
                                 Request, RequestQueue, Result, SlotManager,
                                 TokenEvent, chunk_schedule)
from repro import steps as steps_lib


@dataclasses.dataclass
class EngineConfig:
    """The consolidated construction surface for both engines — every
    queue/retry/ladder/fault/chunk knob in one dataclass instead of
    sprawled across ``Engine``/``SlotEngine`` kwargs.  Engines take
    ``config=EngineConfig(...)``; the old per-engine kwargs remain as
    deprecated aliases (DeprecationWarning) so downstream callers migrate
    at their own pace.  Unused knobs are simply ignored by the engine that
    does not implement them (``pool_capacity`` is a wave knob — the slot
    engine always runs ONE resident cache; ``queue_capacity``/retry/
    ladder/chunk knobs are slot knobs).

    Chunked prefill (``prefill_chunk_len``):
      * ``None`` (default) keeps whole-prompt admission — one B=1 prefill
        dispatch per request, one compiled executable per DISTINCT prompt
        length, and one long prompt stalls every resident lane's decode
        tick for its whole prefill;
      * an int enables chunk-interleaved admission: prompts prefill
        through up-to-``prefill_lanes`` PrefillLane state machines, at
        most ONE fixed-shape chunk between decode ticks, and admit into a
        slot only when fully prefilled.  Greedy outputs are token-
        identical to whole-prompt prefill — chunking changes scheduling,
        not math.
    """
    n_slots: int = 4
    max_seq: int = 128
    queue_capacity: int = 16
    pool_capacity: int = 2
    #: admission-prefill chunk length (None = whole-prompt admission)
    prefill_chunk_len: int | None = None
    #: concurrent partially-prefilled requests (chunked mode only)
    prefill_lanes: int = 2
    retry_budget: int = 0
    retry_backoff_s: float = 0.0
    tick_slo_s: float | None = None
    slo_breach_ticks: int = 3
    slo_recover_ticks: int = 8
    shed_margin: float = 1.0
    ladder: list[str] | None = None
    faults: faults_lib.FaultPlan | None = None

    @property
    def batch_size(self) -> int:
        """Wave-engine naming for the batch axis (== ``n_slots``)."""
        return self.n_slots


#: deprecated per-engine kwarg -> EngineConfig field
_WAVE_ALIASES = {"batch_size": "n_slots", "max_seq": "max_seq",
                 "pool_capacity": "pool_capacity"}
_SLOT_ALIASES = {k: k for k in (
    "n_slots", "max_seq", "queue_capacity", "faults", "retry_budget",
    "retry_backoff_s", "tick_slo_s", "slo_breach_ticks",
    "slo_recover_ticks", "shed_margin", "ladder")}


def _resolve_config(cls_name: str, config: EngineConfig | None,
                    legacy: dict, aliases: dict) -> EngineConfig:
    """Fold an engine's deprecated construction kwargs into EngineConfig.

    Exactly one spelling per call: legacy kwargs warn (DeprecationWarning
    pointing at the caller) and build a fresh config through the alias
    map; mixing them with an explicit ``config`` is ambiguous and raises."""
    if not legacy:
        return config if config is not None else EngineConfig()
    unknown = sorted(set(legacy) - set(aliases))
    if unknown:
        raise TypeError(
            f"{cls_name}: unexpected keyword argument(s) {unknown}")
    if config is not None:
        raise ValueError(
            f"{cls_name}: pass config=EngineConfig(...) OR the deprecated "
            f"kwargs {sorted(legacy)}, not both")
    warnings.warn(
        f"{cls_name}({', '.join(sorted(legacy))}) kwargs are deprecated; "
        "pass config=EngineConfig(...)", DeprecationWarning, stacklevel=3)
    return EngineConfig(**{aliases[k]: v for k, v in legacy.items()})


class _EngineBase:
    """Shared substrate: cache pool, prefill jit, decode-plan scheduler."""

    def __init__(self, model: Model, params: Any, *, batch_size: int,
                 max_seq: int, pool_capacity: int, sensor,
                 extra_plans: dict[str, Callable] | None, per_lane_pos: bool):
        self.model = model
        self.cfg = model.cfg
        self.params = params
        self.batch_size = batch_size
        self.max_seq = max_seq

        cache_annot = jax.eval_shape(
            lambda: model.init_cache(batch_size, max_seq))
        cache_abs, _ = split(cache_annot)
        if per_lane_pos:
            # continuous batching: each lane decodes at its own position
            cache_abs = dict(cache_abs, pos=jax.ShapeDtypeStruct(
                (batch_size,), jnp.int32))
        self.pool = StatePool(cache_abs, capacity=pool_capacity)

        # shape-polymorphic: the same jit serves (B, S) wave prefills and
        # (1, S) per-slot admission prefills (one compile per shape)
        self._prefill = jax.jit(
            lambda p, c, b: steps_lib.prefill_step(self.cfg, p, c, b),
            donate_argnums=(1,))

        self.scheduler = Scheduler(sensor or SyntheticLoadSensor(0.0))
        for name, fn in self._decode_plans(extra_plans or {}).items():
            self.scheduler.register(
                Plan(name, jax.jit(fn, donate_argnums=(1,)), shared=True))

        # serving metrics are ALWAYS on: obs.metrics instruments are plain
        # host ints/deques, so they cannot violate the zero-allocation
        # serving invariant (tests assert buffers_built stays at capacity
        # with metrics enabled); tracing stays opt-in via obs.trace
        self.metrics = Metrics()

    def _decode_plans(self, extra: dict[str, Callable]
                      ) -> dict[str, Callable]:
        raise NotImplementedError

    def _prefill_batch(self, toks: np.ndarray) -> dict:
        batch = {"tokens": jnp.asarray(toks)}
        if self.cfg.n_vis_tokens:
            batch["vis_embeds"] = jnp.zeros(
                (toks.shape[0], self.cfg.n_vis_tokens, self.cfg.vis_dim),
                jnp.dtype(self.cfg.dtype))
        return batch


# ---------------------------------------------------------------------------
# Wave engine (baseline)
# ---------------------------------------------------------------------------
class Engine(_EngineBase):
    """Lockstep wave engine — the coarse-batching baseline."""

    def __init__(self, model: Model, params: Any, *,
                 config: EngineConfig | None = None, sensor=None,
                 extra_plans: dict[str, Callable] | None = None, **legacy):
        config = _resolve_config("Engine", config, legacy, _WAVE_ALIASES)
        self.config = config
        super().__init__(model, params, batch_size=config.n_slots,
                         max_seq=config.max_seq,
                         pool_capacity=config.pool_capacity,
                         sensor=sensor, extra_plans=extra_plans,
                         per_lane_pos=False)

    def _decode_plans(self, extra: dict[str, Callable]
                      ) -> dict[str, Callable]:
        plans = {"decode/base":
                 lambda p, c, b: steps_lib.decode_step(self.cfg, p, c, b)}
        plans.update(extra)
        return plans

    # ------------------------------------------------------------------
    def _dummy_request(self) -> Request:
        """Zero-length, zero-token filler for ragged wave tails — an
        inactive lane, NOT a duplicate of a real request."""
        shape = ((self.cfg.n_codebooks, 0) if self.cfg.n_codebooks
                 else (0,))
        return Request(uid=-1, prompt=np.zeros(shape, np.int32),
                       max_new_tokens=0)

    def _pad_prompts(self, reqs: list[Request]) -> tuple[np.ndarray, int]:
        lens = [r.prompt.shape[-1] for r in reqs]
        s = max(lens)
        shape = ((self.batch_size, self.cfg.n_codebooks, s)
                 if self.cfg.n_codebooks else (self.batch_size, s))
        toks = np.zeros(shape, np.int32)
        for i, r in enumerate(reqs):
            toks[i, ..., s - r.prompt.shape[-1]:] = r.prompt  # left-pad
        return toks, s

    def serve(self, requests: list[Request]) -> list[Result]:
        """Serve all requests in fixed-shape waves of `batch_size`."""
        results: list[Result] = []
        for i in range(0, len(requests), self.batch_size):
            wave = requests[i:i + self.batch_size]
            pad = self.batch_size - len(wave)
            wave_padded = wave + [self._dummy_request()] * pad
            results.extend(self._serve_wave(wave_padded)[: len(wave)])
        return results

    def _serve_wave(self, reqs: list[Request]) -> list[Result]:
        cache = self.pool.checkout()
        toks, _ = self._pad_prompts(reqs)
        batch = self._prefill_batch(toks)

        t0 = time.perf_counter()
        logits, cache = jax.block_until_ready(
            self._prefill(self.params, cache, batch))
        t_prefill = time.perf_counter() - t0

        max_new = max(r.max_new_tokens for r in reqs)
        outs = []
        decisions = []
        tracer = trace_lib.get_tracer()
        wave_span = (tracer.span("serve/wave", n_reqs=len(reqs),
                                 max_new=max_new, prefill_s=t_prefill)
                     if tracer.enabled else trace_lib.NULL_SPAN)
        # prefill logits keep a singleton seq axis before the vocab dim
        tok = steps_lib.greedy_sample(logits)[..., 0]
        t0 = time.perf_counter()
        with wave_span:
            for _ in range(max_new):
                outs.append(np.asarray(tok))
                d = self.scheduler.choose()
                decisions.append(d.plan)
                plan = self.scheduler.plans[d.plan]
                t1 = time.perf_counter()
                logits, cache = jax.block_until_ready(
                    plan.fn(self.params, cache, {"tokens": tok}))
                plan.observe(time.perf_counter() - t1, d.load)
                tok = steps_lib.greedy_sample(logits)
            t_decode = time.perf_counter() - t0
            wave_span.set(decode_s=t_decode)
        self.pool.give_back(cache)

        # (B, [K,] max_new); toks[..., :0] covers an all-zero-budget wave
        gen = (np.stack(outs, axis=-1) if outs else toks[..., :0])
        return [Result(r.uid, gen[j, ..., :r.max_new_tokens], t_prefill,
                       t_decode, decisions)
                for j, r in enumerate(reqs)]


# ---------------------------------------------------------------------------
# Slot engine (continuous batching)
# ---------------------------------------------------------------------------
class SlotEngine(_EngineBase):
    """Slot-resident continuous batching (see serving/slots.py docstring).

    Greedy outputs are token-identical to an unpadded per-request reference
    (the wave engine at batch_size=1): admission prefills each prompt at
    its exact length through a B=1 scratch cache, and lanes never interact
    — per-lane positions keep attention exact, and rwkv/mamba/MoE-decode
    paths are lane-independent by construction.  Distinct prompt lengths
    compile distinct prefill executables (bucket upstream if that matters).

    With ``EngineConfig.prefill_chunk_len`` set, admission prefill is
    CHUNK-INTERLEAVED instead: up to ``prefill_lanes`` PrefillLane state
    machines each prefill one prompt through fixed-shape segments
    (slots.chunk_schedule), the tick loop advances at most ONE chunk
    between decode ticks (round-robin across lanes), and a lane admits
    into a slot only when fully prefilled.  Resident lanes therefore
    never stall for more than one chunk on a long-prompt admission — the
    lockstep pathology whole-prompt admission readmits — while greedy
    outputs stay token-identical to whole-prompt prefill and the compiled
    prefill shapes collapse from one-per-prompt-length to one per segment
    length ({chunk_len} plus descending powers of two for remainders).
    """

    #: smoothing for the observed tick-latency EMA the shed predicate and
    #: watchdog read (matches core.scheduler.Plan.ema)
    TICK_EMA = 0.3

    def __init__(self, model: Model, params: Any, *,
                 config: EngineConfig | None = None, sensor=None,
                 extra_plans: dict[str, Callable] | None = None,
                 clock: Callable[[], float] = None, **legacy):
        """All queue/retry/ladder/fault/chunk knobs live on ``config``
        (EngineConfig, see its docstring); the old per-engine kwargs are
        accepted as deprecated aliases.  ``sensor``/``extra_plans``/
        ``clock`` stay real kwargs — they are collaborator objects, not
        configuration."""
        config = _resolve_config("SlotEngine", config, legacy, _SLOT_ALIASES)
        self.config = config
        n_slots, max_seq = config.n_slots, config.max_seq
        super().__init__(model, params, batch_size=n_slots, max_seq=max_seq,
                         pool_capacity=1, sensor=sensor,
                         extra_plans=extra_plans, per_lane_pos=True)
        self.n_slots = n_slots
        self.clock = clock or time.monotonic
        self.queue = RequestQueue(config.queue_capacity, clock=self.clock)
        # completed Results land here until the caller consumes them with
        # take_finished() — long-running submit()/stream() users must drain
        # it, or host memory grows with every retired request
        self.finished: dict[int, Result] = {}
        # uid -> the moment the engine took the request, on the tracer's
        # clock; dropped at its first token or when it ends without one
        self._submitted: dict[int, float] = {}

        # -- chunked prefill (admission interleaving) -----------------------
        w = self.cfg.sliding_window or 0
        #: longest prompt the CHUNKED path serves token-identically: a
        #: windowed KV ring starts evicting once the prompt outruns the
        #: cache seq axis, and mid-chunk queries then see less in-window
        #: history than whole-prompt flash attention would give them.
        #: Longer windowed prompts fall back to whole-prompt admission.
        self._chunk_safe_len = min(max_seq, w) if w else max_seq
        self._chunk_len = config.prefill_chunk_len
        self.prefill_lanes = config.prefill_lanes
        chunked = self._chunk_len is not None
        if chunked:
            if self.cfg.n_vis_tokens:
                raise ValueError(
                    "chunked prefill cannot serve vis-token prompts (the "
                    "vision prefix is not sliceable); keep "
                    "prefill_chunk_len=None")
            if not 0 < self._chunk_len <= self._chunk_safe_len:
                raise ValueError(
                    f"prefill_chunk_len {self._chunk_len} outside (0, "
                    f"{self._chunk_safe_len}] — chunks longer than the "
                    "cache seq axis would scatter duplicate ring slots")
            if self.prefill_lanes < 1:
                raise ValueError(
                    f"prefill_lanes {self.prefill_lanes} must be >= 1")

        # B=1 scratch the admission prefill runs through (donated per
        # dispatch).  Whole-prompt mode keeps ONE permanently checked-out
        # buffer; chunked mode pools ``prefill_lanes`` of them (one per
        # concurrent PrefillLane, checked out at lane start and returned —
        # zeroed through the pool's donated reset — at admission, abort or
        # failure), plus the persistent whole-prompt buffer when windowed
        # fallbacks are possible.  Either way the pool is built ONCE:
        # ``buffers_built`` stays at capacity for the life of the engine.
        scratch_abs, _ = split(jax.eval_shape(
            lambda: model.init_cache(1, max_seq)))
        self._scratch_abs = scratch_abs
        self._fallback = chunked and bool(w) and self._chunk_safe_len < max_seq
        self._scratch_pool = StatePool(
            scratch_abs, capacity=(self.prefill_lanes + int(self._fallback)
                                   if chunked else 1))
        self._scratch = (self._scratch_pool.checkout()
                         if not chunked or self._fallback else None)

        def prefill_sample(p, c, b):
            # zero the donated scratch first — rwkv/mamba prefill consumes
            # the cache as its initial state, so a previous occupant's
            # state must not leak into the next prompt — then sample the
            # prompt's first greedy token, all in one dispatch
            c = jax.tree.map(lambda a: a * 0, c)
            logits, c = steps_lib.prefill_step(self.cfg, p, c, b)
            return steps_lib.greedy_sample(logits)[..., 0], c

        def prefill_chunk_sample(p, c, b, first):
            # ``first`` is a TRACED scalar bool, so chunk 0 (zero the
            # scratch, prefill_sample's reset) and continuation chunks
            # share ONE executable per segment length — the one-shape-per-
            # (chunk_len,) contract
            c = jax.tree.map(lambda a: jnp.where(first, a * 0, a), c)
            logits, c = steps_lib.chunked_prefill_step(self.cfg, p, c, b)
            return steps_lib.greedy_sample(logits)[..., 0], c

        # pre-create the serving instruments so metrics snapshots (and the
        # end-of-stream serve/metrics trace event) always carry the full
        # schema, zero-valued counters included
        for name in ("serving/ticks", "serving/tokens", "serving/retired",
                     "serving/deadline_miss", "serving/quarantined",
                     "serving/retries", "serving/shed"):
            self.metrics.counter(name)
        self.metrics.histogram("serving/ttft_s")
        self.metrics.histogram("serving/tbt_s")
        if chunked:
            self.metrics.histogram("serving/prefill_chunk_s")

        token_tail = ((self.cfg.n_codebooks,) if self.cfg.n_codebooks
                      else ())
        self._prefill_sample = jax.jit(prefill_sample, donate_argnums=(1,))
        self._prefill_chunk = jax.jit(prefill_chunk_sample,
                                      donate_argnums=(1,))
        # device-resident chunk-0 flags, uploaded once and reused — the
        # chunked path keeps the no-per-dispatch-upload property
        self._first_true = jnp.asarray(True)
        self._first_false = jnp.asarray(False)
        self._lanes: list[PrefillLane] = []
        self._rr = 0                 # round-robin cursor over live lanes
        self.manager = SlotManager(
            self.pool.checkout(), n_slots, token_tail=token_tail,
            clock=self.clock)

        # -- fault tolerance ------------------------------------------------
        ladder = config.ladder
        unknown = set(ladder or []) - set(self.scheduler.plans)
        if unknown:
            raise ValueError(
                f"ladder names unregistered plans: {sorted(unknown)}")
        self.scheduler.ladder = list(ladder or [])
        self.retry_budget = config.retry_budget
        self.retry_backoff_s = config.retry_backoff_s
        self.tick_slo_s = config.tick_slo_s
        self.slo_breach_ticks = config.slo_breach_ticks
        self.slo_recover_ticks = config.slo_recover_ticks
        self.shed_margin = config.shed_margin
        faults = config.faults
        self.injector = None if faults is None else faults_lib.FaultInjector(
            faults, n_slots, vocab=self.cfg.vocab, max_seq=max_seq,
            token_tail=token_tail)
        # the all-False poison mask is uploaded ONCE and reused every
        # healthy tick, so the guard keeps the no-per-tick-upload property;
        # a real mask is uploaded only on the fault ticks themselves
        self._no_poison = jnp.zeros((n_slots,), bool)
        self._attempts: dict[int, int] = {}   # uid -> retries consumed
        self._retry_backlog: list[tuple[float, Request]] = []
        self._tick_ema: float | None = None
        self._breach_ticks = 0
        self._healthy_ticks = 0

    def _decode_plans(self, extra: dict[str, Callable]
                      ) -> dict[str, Callable]:
        # every plan is wrapped with the active mask (free/finished lanes
        # keep their state untouched; the plan receives batch['active'] and
        # must honour it), the per-lane finite guard and greedy sampling,
        # so one dispatch per tick yields (sampled tokens, lane_ok, cache)
        # directly
        def masked(fn=None):
            def plan(p, c, b):
                step = None if fn is None else (
                    lambda _cfg, p_, c_, b_: fn(p_, c_, b_))
                logits, lane_ok, cache = steps_lib.guarded_decode_step(
                    self.cfg, p, c, b, step_fn=step)
                return steps_lib.greedy_sample(logits), lane_ok, cache
            return plan

        plans = {"decode/base": masked()}
        plans.update({n: masked(fn) for n, fn in extra.items()})
        return plans

    # ------------------------------------------------------------------
    def _validate(self, req: Request) -> None:
        """Reject requests that cannot fit their lane BEFORE they queue —
        decode writes token ``i`` at position prompt_len + i, and an
        out-of-range lane scatter would be silently dropped, not clamped."""
        if req.max_new_tokens <= 0:
            return                        # completes without touching a lane
        s = np.asarray(req.prompt).shape[-1]
        if not 0 < s <= self.max_seq:
            raise ValueError(
                f"request {req.uid}: prompt length {s} outside (0, "
                f"{self.max_seq}]")
        if s + req.max_new_tokens - 1 > self.max_seq:
            raise ValueError(
                f"request {req.uid}: prompt {s} + max_new_tokens "
                f"{req.max_new_tokens} - 1 exceeds max_seq {self.max_seq}")

    def submit(self, req: Request) -> bool:
        """Queue one request; raises QueueFull (backpressure) when bounded
        queue capacity is reached, ValueError when it cannot fit a lane.
        Returns False — with an immediate ``finish_reason='deadline'``
        Result published to ``finished`` — when the request is dead on
        arrival (its deadline already passed)."""
        self._validate(req)
        if not self.queue.submit(req):
            self.metrics.counter("serving/deadline_miss").inc()
            self._terminal(req, FinishReason.DEADLINE)
            return False
        self._stamp(req)
        return True

    def _stamp(self, req: Request) -> None:
        """Note when the engine took ``req`` (its TTFT starts here); a
        retry that re-queues a request keeps its first stamp."""
        self._submitted.setdefault(req.uid, trace_lib.get_tracer().clock())

    def _first_token(self, req: Request, t_start: float, prompt_len: int,
                     chunks: int) -> tuple[float, float]:
        """Close ``req``'s stamp now, as its first token is handed to the
        client after a prefill that started at ``t_start`` (tracer's
        clock).  Returns (ttft_s, queue_s) and records ``serve/request``."""
        tracer = trace_lib.get_tracer()
        t_first = tracer.clock()
        t_sub = self._submitted.pop(req.uid, t_start)
        ttft_s, queue_s = t_first - t_sub, t_start - t_sub
        self.metrics.histogram("serving/ttft_s").observe(ttft_s)
        if tracer.enabled:
            tracer.interval("serve/request", t_sub, t_first, uid=req.uid,
                            queue_s=queue_s, lane_s=t_first - t_start,
                            prompt_len=prompt_len, chunks=chunks)
        return ttft_s, queue_s

    def _admit_one(self, index: int, req: Request) -> TokenEvent:
        prompt = np.asarray(req.prompt, np.int32)
        tracer = trace_lib.get_tracer()
        t_start = tracer.clock()
        t0 = time.perf_counter()
        tok0, self._scratch = self._prefill_sample(
            self.params, self._scratch,
            self._prefill_batch(prompt.reshape((1,) + prompt.shape)))
        tok0 = tok0[0]                       # () or (K,), device array
        prefill_s = time.perf_counter() - t0
        tok0_np = np.asarray(tok0, np.int32)  # blocks: token host-visible
        slot = self.manager.admit(index, req, self._scratch, tok0, prefill_s)
        slot.ttft_s, slot.queue_s = self._first_token(
            req, t_start, int(prompt.shape[-1]), chunks=1)
        if tracer.enabled:
            tracer.event("serve/admit", uid=req.uid, slot=index,
                         prompt_len=int(prompt.shape[-1]),
                         prefill_s=prefill_s, ttft_s=slot.ttft_s)
        return TokenEvent(req.uid, tok0_np, 0,
                          done=(req.max_new_tokens <= 1))

    # -- fault-tolerance plumbing --------------------------------------
    def _terminal(self, req: Request, reason: str) -> TokenEvent:
        """Publish a tokenless terminal Result (queue expiry, dead-on-
        arrival deadline, shed, failure out of retries) and return its
        stream event."""
        self._attempts.pop(req.uid, None)
        self._submitted.pop(req.uid, None)
        self.finished[req.uid] = Result(req.uid, self.manager.empty_tokens(),
                                        0.0, 0.0, [], finish_reason=reason)
        return TokenEvent(req.uid, None, 0, done=True, finish_reason=reason)

    def _zero_budget(self, req: Request) -> TokenEvent:
        """Complete a request with no tokens owed, without a lane."""
        self._submitted.pop(req.uid, None)
        self.finished[req.uid] = Result(
            req.uid, self.manager.empty_tokens(), 0.0, 0.0, [])
        return TokenEvent(req.uid, None, 0, done=True,
                          finish_reason=FinishReason.LENGTH)

    def _finish(self, res: Result) -> None:
        """Adopt a retired lane's Result — the one place lane retirement
        updates the metrics and retry bookkeeping."""
        self.metrics.counter("serving/retired").inc()
        self._attempts.pop(res.uid, None)
        self.finished[res.uid] = res

    def _fail_or_retry(self, req: Request, now: float) -> str | None:
        """Shared quarantine / prefill-failure disposition.  Consumes one
        unit of ``retry_budget`` when available: the request re-enters the
        queue after exponential backoff (``retry_backoff_s * 2**attempt``)
        and restarts FROM PREFILL — a retried greedy request therefore
        still produces exactly its fault-free tokens.  Returns None on
        retry, otherwise the terminal finish_reason (ERROR with no budget,
        RETRIES_EXHAUSTED once the budget is spent)."""
        attempts = self._attempts.get(req.uid, 0)
        if attempts < self.retry_budget:
            self._attempts[req.uid] = attempts + 1
            self.metrics.counter("serving/retries").inc()
            ready = now + self.retry_backoff_s * (2.0 ** attempts)
            self._retry_backlog.append((ready, req))
            return None
        return (FinishReason.RETRIES_EXHAUSTED if self.retry_budget > 0
                else FinishReason.ERROR)

    def _prefill_failed(self, req: Request, now: float, err: Exception
                        ) -> Iterator[TokenEvent]:
        """Containment for an admission prefill that raised: emit the
        serve/fault event, then retry or terminate the request."""
        injected = isinstance(err, faults_lib.InjectedFault)
        if not injected and self._scratch is not None and any(
                getattr(a, "is_deleted", lambda: False)()
                for a in jax.tree.leaves(self._scratch)):
            # a REAL prefill exception may have consumed the donated
            # scratch mid-dispatch; rebuild it so the next admission still
            # works.  Injected faults raise before the dispatch and never
            # take this path, so chaos runs stay zero-allocation.
            self._scratch = make_buffer(self._scratch_abs)
        tracer = trace_lib.get_tracer()
        if tracer.enabled:
            tracer.event("serve/fault", kind="prefill", uid=req.uid,
                         injected=injected, error=repr(err))
        reason = self._fail_or_retry(req, now)
        if reason is not None:
            yield self._terminal(req, reason)

    # -- chunked admission (the tentpole) ------------------------------
    def _lane_failed(self, lane: PrefillLane, now: float, err: Exception
                     ) -> Iterator[TokenEvent]:
        """Containment for a chunked-prefill attempt that raised: the
        lane's PARTIAL state is discarded (its scratch returns to the pool
        through the donated zeroing reset), so a retry restarts from chunk
        0 with a clean cache — token-identical to an unfaulted admission."""
        injected = isinstance(err, faults_lib.InjectedFault)
        cache = lane.cache
        if not injected and any(
                getattr(a, "is_deleted", lambda: False)()
                for a in jax.tree.leaves(cache)):
            # same rebuild rule as _prefill_failed: only a REAL exception
            # can strand a consumed donated buffer
            cache = make_buffer(self._scratch_abs)
        self._scratch_pool.give_back(cache)
        tracer = trace_lib.get_tracer()
        if tracer.enabled:
            tracer.event("serve/fault", kind="prefill",
                         uid=lane.request.uid, injected=injected,
                         chunk=lane.chunks_done, error=repr(err))
        reason = self._fail_or_retry(lane.request, now)
        if reason is not None:
            yield self._terminal(lane.request, reason)

    def _advance_lane(self, lane: PrefillLane, now: float
                      ) -> Iterator[TokenEvent]:
        """Run ONE prefill chunk for ``lane``; on the final chunk, admit
        the fully-prefilled request into a free slot (the invariant
        ``len(self._lanes) <= free slots`` guarantees one exists — slots
        are only ever occupied BY lane admission while lanes are live)."""
        mgr = self.manager
        req = lane.request
        inj = self.injector
        tracer = trace_lib.get_tracer()
        seg = lane.schedule[0]
        try:
            if inj is not None and inj.take_prefill_fault(
                    req.uid, lane.chunks_done):
                # raised BEFORE the dispatch: the lane cache is untouched
                raise faults_lib.InjectedFault(
                    f"injected prefill fault, uid={req.uid}, "
                    f"chunk={lane.chunks_done}")
            toks = lane.prompt[..., lane.filled:lane.filled + seg]
            first = (self._first_true if lane.chunks_done == 0
                     else self._first_false)
            on = tracer.enabled
            null = trace_lib.NULL_SPAN
            t0 = time.perf_counter()
            with (tracer.span("serve/chunk", uid=req.uid,
                              chunk=lane.chunks_done, seg_len=seg)
                  if on else null):
                batch = self._prefill_batch(toks.reshape((1,) + toks.shape))
                with tracer.span("serve/chunk/dispatch") if on else null:
                    tok, lane.cache = self._prefill_chunk(
                        self.params, lane.cache, batch, first)
                with tracer.span("serve/chunk/sync") if on else null:
                    tok = jax.block_until_ready(tok)
                chunk_s = time.perf_counter() - t0
                # the chunk's last device work, so inside its span
                last_tok = tok[0]              # () or (K,), device array
        except Exception as err:      # containment: never escapes
            self._lanes.remove(lane)
            yield from self._lane_failed(lane, now, err)
            return
        lane.schedule.pop(0)
        lane.filled += seg
        lane.chunks_done += 1
        lane.prefill_s += chunk_s
        lane.last_tok = last_tok
        self.metrics.histogram("serving/prefill_chunk_s").observe(chunk_s)
        if tracer.enabled:
            tracer.event("serve/prefill_chunk", uid=req.uid,
                         chunk=lane.chunks_done - 1, seg_len=seg,
                         filled=lane.filled, chunk_s=chunk_s)
        if not lane.done:
            return
        # fully prefilled: admit into a free slot and release the scratch
        self._lanes.remove(lane)
        idx = mgr.free_indices()[0]
        tok0_np = np.asarray(lane.last_tok, np.int32)
        slot = mgr.admit(idx, req, lane.cache, lane.last_tok, lane.prefill_s)
        self._scratch_pool.give_back(lane.cache)
        slot.ttft_s, slot.queue_s = self._first_token(
            req, lane.t_start, int(lane.prompt.shape[-1]),
            chunks=lane.chunks_done)
        if tracer.enabled:
            tracer.event("serve/admit", uid=req.uid, slot=idx,
                         prompt_len=int(lane.prompt.shape[-1]),
                         prefill_s=lane.prefill_s, ttft_s=slot.ttft_s,
                         chunks=lane.chunks_done)
        ev = TokenEvent(req.uid, tok0_np, 0, done=(req.max_new_tokens <= 1))
        yield ev
        if ev.done:
            self._finish(mgr.retire(idx))

    def _admit_chunked(self, now: float, refill) -> Iterator[TokenEvent]:
        """One scheduling round of chunk-interleaved admission: abort
        deadline-expired lanes, start new lanes while scratch buffers AND
        target slots are both free, then advance at most ONE chunk total
        (round-robin across live lanes) before the decode tick runs."""
        mgr = self.manager
        metrics = self.metrics
        inj = self.injector
        tracer = trace_lib.get_tracer()

        # partially-prefilled requests past their deadline abort here —
        # the partial state is discarded and buffers_built is untouched
        for lane in [ln for ln in self._lanes
                     if ln.request.deadline_s is not None
                     and ln.request.deadline_s <= now]:
            self._lanes.remove(lane)
            self._scratch_pool.give_back(lane.cache)
            metrics.counter("serving/deadline_miss").inc()
            yield self._terminal(lane.request, FinishReason.DEADLINE)

        # start lanes: never more live lanes than prefill_lanes OR free
        # slots — every lane must have a slot to land in when it finishes
        while (len(self._lanes) < self.prefill_lanes
               and len(self._lanes) < len(mgr.free_indices())):
            yield from refill()
            req = self.queue.pop()
            if req is None:
                break
            if req.max_new_tokens <= 0:
                # zero-budget request: complete without touching a lane
                yield self._zero_budget(req)
                continue
            prompt = np.asarray(req.prompt, np.int32)
            if prompt.shape[-1] > self._chunk_safe_len:
                # windowed prompt past the cache seq axis: chunked replay
                # through the ring is not token-identical, so this one
                # admission takes the legacy whole-prompt path (and eats
                # the full stall — the documented trade)
                idx = mgr.free_indices()[0]
                try:
                    if inj is not None and inj.take_prefill_fault(req.uid):
                        raise faults_lib.InjectedFault(
                            f"injected prefill fault, uid={req.uid}")
                    ev = self._admit_one(idx, req)
                except Exception as err:
                    yield from self._prefill_failed(req, now, err)
                    continue
                yield ev
                if ev.done:
                    self._finish(mgr.retire(idx))
                continue
            self._lanes.append(PrefillLane(
                request=req, cache=self._scratch_pool.checkout(),
                schedule=chunk_schedule(prompt.shape[-1], self._chunk_len),
                prompt=prompt, t_start=tracer.clock()))
            if tracer.enabled:
                tracer.event("serve/prefill_start", uid=req.uid,
                             prompt_len=int(prompt.shape[-1]),
                             n_chunks=len(self._lanes[-1].schedule))

        # the chunk budget: ONE fixed-shape prefill dispatch per tick-loop
        # iteration, shared round-robin — a short prompt behind a long
        # adversary waits O(its own chunks), not the adversary's prefill
        if self._lanes:
            self._rr += 1
            yield from self._advance_lane(
                self._lanes[self._rr % len(self._lanes)], now)

    def _watchdog(self, observed_s: float, tick: int) -> None:
        """Tick-latency watchdog driving the degradation ladder: after
        ``slo_breach_ticks`` consecutive ticks over ``tick_slo_s`` the
        scheduler steps one rung down (sched/degrade in the trace); after
        ``slo_recover_ticks`` consecutive healthy ticks it steps back up."""
        ema = self._tick_ema
        self._tick_ema = (observed_s if ema is None else
                          (1 - self.TICK_EMA) * ema
                          + self.TICK_EMA * observed_s)
        if self.tick_slo_s is None:
            return
        if observed_s > self.tick_slo_s:
            self._breach_ticks += 1
            self._healthy_ticks = 0
            if self._breach_ticks >= self.slo_breach_ticks:
                self.scheduler.degrade(reason=f"tick_slo@{tick}")
                self._breach_ticks = 0
        else:
            self._breach_ticks = 0
            self._healthy_ticks += 1
            if (self.scheduler.level > 0
                    and self._healthy_ticks >= self.slo_recover_ticks):
                self.scheduler.recover()
                self._healthy_ticks = 0

    def stream(self, requests: list[Request] | None = None
               ) -> Iterator[TokenEvent]:
        """Run the continuous-batching loop, yielding one TokenEvent per
        generated token (plus terminal events), until queue and slots
        drain.  ``requests`` are fed into the bounded queue as space frees
        — external callers use ``submit`` and get backpressure instead.

        Results are published through ``self.finished`` as slots retire.
        """
        for req in requests or []:
            self._validate(req)          # fail fast, not mid-stream
            self._stamp(req)
        pending = collections.deque(requests or [])
        mgr = self.manager
        metrics = self.metrics
        inj = self.injector
        tick = 0
        while (pending or len(self.queue) or mgr.any_occupied
               or self._retry_backlog or self._lanes):
            now = self.clock()
            tracer = trace_lib.get_tracer()

            # injected queue floods land first: synthetic dead weight
            # competing with real work for bounded queue space.  A flood
            # bouncing off a full queue is the defined behaviour
            # (backpressure), same as a rejected client — dropped, not
            # tracked.
            if inj is not None:
                for req in inj.flood_requests(tick, now):
                    if tracer.enabled:
                        tracer.event("serve/fault", kind="flood", tick=tick,
                                     uid=req.uid)
                    try:
                        if self.queue.submit(req, now=now):
                            self._stamp(req)
                        else:
                            metrics.counter("serving/deadline_miss").inc()
                            yield self._terminal(req, FinishReason.DEADLINE)
                    except QueueFull:
                        pass

            # quarantined requests whose backoff elapsed re-enter the
            # queue (ahead of fresh `pending` work — they were admitted
            # once already)
            if self._retry_backlog:
                still: list[tuple[float, Request]] = []
                for ready_t, req in self._retry_backlog:
                    if ready_t > now or self.queue.full:
                        still.append((ready_t, req))
                    elif self.queue.submit(req, now=now):
                        self._stamp(req)
                    else:
                        metrics.counter("serving/deadline_miss").inc()
                        yield self._terminal(req, FinishReason.DEADLINE)
                self._retry_backlog = still

            def refill_and_expire():
                """Top the queue up from `pending`, then drop anything whose
                deadline already passed — every pop below sees an expired-
                free queue, including mid-admission refills.  A pending
                request dead on arrival terminates immediately without
                queueing."""
                while pending and not self.queue.full:
                    req = pending.popleft()
                    if not self.queue.submit(req, now=now):
                        metrics.counter("serving/deadline_miss").inc()
                        yield self._terminal(req, FinishReason.DEADLINE)
                for req in self.queue.expire(now):
                    metrics.counter("serving/deadline_miss").inc()
                    yield self._terminal(req, FinishReason.DEADLINE)

            yield from refill_and_expire()
            # resident lanes past their deadline retire with what they have
            for idx in mgr.expired_indices(now):
                res = mgr.retire(idx, finish_reason=FinishReason.DEADLINE)
                metrics.counter("serving/deadline_miss").inc()
                self._finish(res)
                yield TokenEvent(res.uid, None, res.tokens.shape[-1],
                                 done=True,
                                 finish_reason=FinishReason.DEADLINE)

            # degradation ladder, shed half: once degraded, queued requests
            # whose deadlines are provably unmeetable under the observed
            # tick latency are dropped now instead of wasting lane time
            # before expiring anyway
            if self.scheduler.level > 0 and self._tick_ema is not None:
                horizon = now + self.shed_margin * self._tick_ema
                for req in self.queue.shed(
                        lambda r: r.deadline_s is not None
                        and r.deadline_s <= horizon):
                    metrics.counter("serving/shed").inc()
                    if tracer.enabled:
                        tracer.event("serve/shed", uid=req.uid, tick=tick,
                                     deadline_s=req.deadline_s,
                                     tick_ema_s=self._tick_ema)
                    yield self._terminal(req, FinishReason.SHED)

            # step-granular admission — chunk-interleaved (at most one
            # prefill chunk before the decode tick) or whole-prompt
            if self._chunk_len is not None:
                yield from self._admit_chunked(now, refill_and_expire)
            else:
                for idx in mgr.free_indices():
                    yield from refill_and_expire()
                    req = self.queue.pop()
                    if req is None:
                        break
                    if req.max_new_tokens <= 0:
                        # zero-budget request: complete without a lane
                        yield self._zero_budget(req)
                        continue
                    try:
                        if (inj is not None
                                and inj.take_prefill_fault(req.uid)):
                            # raised BEFORE the dispatch: the donated
                            # scratch is untouched, exactly the guarantee
                            # InjectedFault documents
                            raise faults_lib.InjectedFault(
                                f"injected prefill fault, uid={req.uid}")
                        ev = self._admit_one(idx, req)
                    except Exception as err:  # containment: never escapes
                        yield from self._prefill_failed(req, now, err)
                        continue
                    yield ev
                    if ev.done:
                        self._finish(mgr.retire(idx))

            queue_depth = len(self.queue)
            occupied = sum(1 for s in mgr.slots if s.occupied)
            metrics.gauge("serving/queue_depth").set(float(queue_depth))
            metrics.gauge("serving/occupancy").set(occupied / mgr.n_slots)

            n_active = int(mgr.active_mask().sum())
            if not n_active:
                if (pending or len(self.queue) or self._retry_backlog
                        or self._lanes):
                    # only expiries/zero-token admissions/backoffs/partial
                    # prefills left; keep looping — lanes advance one
                    # chunk per iteration even with no decode to interleave
                    continue
                break

            # ONE fused masked decode tick across all lanes, in three
            # spans: prepare (choose — its sched/choose event nests here —
            # batch, poison mask), the asynchronous dispatch, and the
            # blocking host copy of its outputs
            on = tracer.enabled
            null = trace_lib.NULL_SPAN
            span = (tracer.span("serve/tick", tick=tick,
                                queue_depth=queue_depth, occupied=occupied,
                                active=n_active)
                    if on else null)
            with span:
                with tracer.span("serve/tick/prepare") if on else null:
                    d = self.scheduler.choose()
                    plan = self.scheduler.plans[d.plan]
                    batch = mgr.tick_batch()
                    lanes = inj.poison_lanes(tick) if inj is not None else ()
                    if lanes:
                        mask = np.zeros((self.n_slots,), bool)
                        mask[list(lanes)] = True
                        batch["poison"] = jnp.asarray(mask)
                        if on:
                            for lane in lanes:
                                tracer.event("serve/fault", kind="poison",
                                             tick=tick, lane=lane)
                    else:
                        batch["poison"] = self._no_poison
                t0 = time.perf_counter()
                with tracer.span("serve/tick/dispatch") if on else null:
                    sampled_dev, lane_ok_dev, mgr.cache = plan.fn(
                        self.params, mgr.cache, batch)
                mgr.set_sampled(sampled_dev)
                with tracer.span("serve/tick/sync") if on else null:
                    sampled = np.asarray(sampled_dev)  # blocks; 1 per tick
                    lane_ok = np.asarray(lane_ok_dev)
                tick_s = time.perf_counter() - t0
                extra_s = inj.slow_s(tick) if inj is not None else 0.0
                if extra_s and on:
                    tracer.event("serve/fault", kind="slow", tick=tick,
                                 extra_s=extra_s)
                observed_s = tick_s + extra_s
                plan.observe(observed_s, d.load)
                span.set(plan=d.plan, load=d.load, tick_s=tick_s,
                         observed_s=observed_s)
            metrics.counter("serving/ticks").inc()
            self._watchdog(observed_s, tick)

            # quarantine: any ACTIVE lane whose finite guard tripped
            # retires NOW, before its poisoned token could be recorded —
            # the donated lane reset inside retire() zeroes just that
            # lane, so its neighbours and the zero-allocation invariant
            # are untouched
            for s in [s for s in mgr.slots
                      if s.occupied and not lane_ok[s.index]]:
                req = s.request
                metrics.counter("serving/quarantined").inc()
                reason = self._fail_or_retry(req, now)
                res = mgr.retire(s.index,
                                 finish_reason=reason or FinishReason.ERROR)
                if tracer.enabled:
                    tracer.event("serve/quarantine", uid=req.uid,
                                 slot=s.index, tick=tick,
                                 action="retry" if reason is None
                                 else reason)
                if reason is None:
                    # retry path: partial output discarded — the retry
                    # restarts from prefill and regenerates the same
                    # greedy tokens
                    continue
                self._finish(res)
                yield TokenEvent(req.uid, None, res.tokens.shape[-1],
                                 done=True, finish_reason=reason)
            tick += 1

            just_active = [s.index for s in mgr.slots
                           if s.occupied and s.remaining > 0]
            done_idx = set(mgr.record(sampled, d.plan))
            metrics.counter("serving/tokens").inc(len(just_active))
            token_t = time.perf_counter()
            tbt = metrics.histogram("serving/tbt_s")
            for idx in just_active:
                s = mgr.slots[idx]
                tbt.observe(token_t - s.last_token_t)
                s.last_token_t = token_t
                yield TokenEvent(s.request.uid, np.asarray(sampled[idx],
                                                           np.int32),
                                 len(s.tokens) - 1, done=idx in done_idx)
            for idx in done_idx:
                self._finish(mgr.retire(idx))

        # one summary record per drained stream: every counter (including
        # zero-valued deadline_miss), gauge and histogram summary
        tracer = trace_lib.get_tracer()
        if tracer.enabled:
            tracer.event("serve/metrics", **metrics.snapshot())

    def take_finished(self) -> dict[int, Result]:
        """Pop and return every completed Result (uid -> Result).  The
        consumption half of the streaming API: call it periodically from a
        long-running submit()/stream() loop to keep host memory bounded."""
        out, self.finished = self.finished, {}
        return out

    def serve(self, requests: list[Request],
              on_token: Callable[[TokenEvent], None] | None = None
              ) -> list[Result]:
        """Convenience wrapper: stream everything, return per-request
        Results in submission order."""
        self.finished = {}
        for ev in self.stream(requests):
            if on_token is not None:
                on_token(ev)
        done = self.take_finished()
        return [done[r.uid] for r in requests]
