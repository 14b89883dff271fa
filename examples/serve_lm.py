"""Serving example: a reduced qwen2 under the MobiRNN runtime policies —
preallocated cache pools, load-aware plan dispatch (paper Fig 7, but for
LLM decode) — through ``SlotEngine``: slot-resident continuous batching,
per-lane admission/retirement over one preallocated cache, tokens streamed
per tick.

  PYTHONPATH=src python examples/serve_lm.py
  PYTHONPATH=src python examples/serve_lm.py --trace serve_trace.jsonl
  PYTHONPATH=src python examples/serve_lm.py --chaos

With ``--trace`` the whole run is recorded as structured JSONL (per-tick
serve/tick spans with the chosen plan, serve/admit events with per-request
TTFT, nested sched/choose decisions, and a final serve/metrics summary —
see ROADMAP §Observability for the schema).

With ``--chaos`` the slot engine runs under a seeded FaultPlan
(ROADMAP §Robustness): the client submits through the bounded queue with
EXPONENTIAL BACKOFF on QueueFull (the intended reaction to backpressure),
the engine quarantines poisoned lanes / retries failed prefills / steps
its degradation ladder, and the run ends with the per-reason retirement
breakdown over the closed finish_reason set.
"""
import argparse
import collections
import time

import jax
import numpy as np

from repro.configs import get_arch
from repro.core.scheduler import SyntheticLoadSensor
from repro.models import registry
from repro.partitioning import split
from repro.serving import (EngineConfig, FaultPlan, QueueFull, Request,
                           SlotEngine)


def make_requests(cfg, rng):
    # ragged on purpose: mixed prompt lengths, 8x max_new spread — the
    # workload where continuous batching beats lockstep batches
    lens = [8, 12, 6, 16, 8, 12, 6, 16, 8, 12, 6, 16]
    news = [2, 16, 4, 8, 16, 2, 8, 4, 16, 2, 4, 8]
    return [Request(i, rng.integers(0, cfg.vocab, (l,)).astype(np.int32),
                    max_new_tokens=n)
            for i, (l, n) in enumerate(zip(lens, news))]


def run_chaos(cfg, model, params) -> None:
    from repro import steps as steps_lib

    rng = np.random.default_rng(1)
    reqs = make_requests(cfg, rng)
    plan = FaultPlan.seeded(
        0, n_slots=2, ticks=16, uids=tuple(r.uid for r in reqs),
        n_poison=2, n_prefill=1, n_slow_burst=1, slow_extra_s=1e6,
        n_flood=1, flood_n=2)
    kinds = collections.Counter(type(f).__name__ for f in plan.faults)
    print(f"chaos: seed={plan.seed} schedule="
          + " ".join(f"{k}x{n}" for k, n in sorted(kinds.items())))

    # small queue ON PURPOSE: the client below must hit QueueFull and
    # back off, which is the intended reaction to engine backpressure
    engine = SlotEngine(
        model, params,
        config=EngineConfig(
            n_slots=2, max_seq=64, queue_capacity=3,
            faults=plan, retry_budget=1, retry_backoff_s=0.005,
            tick_slo_s=50.0, slo_breach_ticks=3, slo_recover_ticks=8,
            ladder=["decode/base"]),
        extra_plans={"decode/fallback":
                     lambda p, c, b: steps_lib.decode_step(cfg, p, c, b)})

    pending = collections.deque(reqs)
    backoff_s, backoffs = 0.005, 0

    def pump() -> None:
        # exponential backoff on QueueFull: sleep, double the delay, and
        # yield control back to the stream so the engine can drain lanes;
        # any accepted submit resets the delay to its floor
        nonlocal backoff_s, backoffs
        while pending:
            try:
                engine.submit(pending[0])
            except QueueFull:
                backoffs += 1
                time.sleep(backoff_s)
                backoff_s = min(backoff_s * 2, 0.08)
                return
            pending.popleft()            # queued (or retired dead-on-arrival)
            backoff_s = 0.005

    n_tokens = 0
    while pending:
        pump()
        for ev in engine.stream():
            n_tokens += ev.token is not None
            if pending:
                pump()

    results = engine.take_finished()
    breakdown = collections.Counter(r.finish_reason for r in results.values())
    print(f"chaos: {len(results)} retired ({n_tokens} tokens streamed), "
          "breakdown: "
          + " ".join(f"{k}={n}" for k, n in sorted(breakdown.items())))
    m = engine.metrics
    print(f"chaos: client QueueFull backoffs={backoffs}; engine "
          f"quarantined={m.counter('serving/quarantined').value} "
          f"retries={m.counter('serving/retries').value} "
          f"shed={m.counter('serving/shed').value} "
          f"deadline_miss={m.counter('serving/deadline_miss').value}")
    print(f"chaos: ladder level={engine.scheduler.level} "
          f"(0 = recovered); resident pool: {engine.pool.stats}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="write a structured JSONL trace of the run")
    ap.add_argument("--chaos", action="store_true",
                    help="run the slot engine under a seeded FaultPlan "
                         "with client-side backoff on QueueFull")
    args = ap.parse_args()
    if args.trace:
        from repro.obs import trace as trace_lib

        trace_lib.configure(path=args.trace)

    cfg = get_arch("qwen2-0.5b").reduced()
    model = registry.build(cfg)
    params, _ = split(model.init(jax.random.PRNGKey(0)))
    print(f"serving {cfg.name}: vocab={cfg.vocab} layers={cfg.n_layers}")

    if args.chaos:
        run_chaos(cfg, model, params)
        if args.trace:
            from repro.obs import trace as trace_lib

            trace_lib.get_tracer().close()
            print(f"wrote trace to {args.trace}")
        return

    rng = np.random.default_rng(0)
    reqs = make_requests(cfg, rng)
    n_tok = sum(r.max_new_tokens for r in reqs)

    sensor = SyntheticLoadSensor(0.0)
    slot = SlotEngine(model, params, sensor=sensor, config=EngineConfig(
        n_slots=4, max_seq=64, queue_capacity=8))
    slot.serve(reqs)        # compile once so the printed rows are steady
    for load in (0.0, 0.85):
        sensor.value = load
        t0 = time.time()
        results = slot.serve(reqs)
        wall = time.time() - t0
        plans = {p for r in results for p in r.plan_decisions}
        print(f"load={load:.0%}: {len(results)} requests, "
              f"{n_tok} tokens, {n_tok / wall:.1f} tok/s, "
              f"plans used: {plans}")

    # streaming: tokens surface per tick, not when the whole batch drains;
    # TTFT is measured by the engine itself (submit -> first token on
    # host, its queue part on Result.queue_s) and surfaced both per
    # request on Result.ttft_s and as a p50/p99 histogram in the engine's
    # always-on serving metrics
    results = slot.serve(reqs)
    for r in sorted(results, key=lambda r: r.ttft_s)[:3]:
        print(f"  uid={r.uid}: ttft={r.ttft_s * 1e3:.1f}ms "
              f"queue={r.queue_s * 1e3:.1f}ms "
              f"decode={r.decode_s * 1e3:.1f}ms "
              f"tokens={r.tokens.shape[-1]}")
    ttft = slot.metrics.histogram("serving/ttft_s").summary()
    tbt = slot.metrics.histogram("serving/tbt_s").summary()
    print(f"slot streaming: ttft p50={ttft['p50'] * 1e3:.1f}ms "
          f"p99={ttft['p99'] * 1e3:.1f}ms; "
          f"tbt p50={tbt['p50'] * 1e3:.2f}ms p99={tbt['p99'] * 1e3:.2f}ms")
    print("resident pool:", slot.pool.stats)
    if args.trace:
        from repro.obs import trace as trace_lib

        trace_lib.get_tracer().close()
        print(f"wrote trace to {args.trace}")


if __name__ == "__main__":
    main()
