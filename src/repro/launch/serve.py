"""Serving driver: batched requests through the MobiRNN-policy engine.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
      --requests 8 --prompt-len 16 --max-new 8

Weights are random, drawn from ``--seed``.  Exits 1 when, with no
``FaultPlan`` configured, any request ends in ``error`` or
``retries_exhausted``: the engine contains those failures per request, so
without this check a broken device path would still exit 0.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import numpy as np

from repro import compile_cache
from repro.configs import get_arch
from repro.core.scheduler import SyntheticLoadSensor
from repro.models import registry
from repro.partitioning import split
from repro.serving import Engine, EngineConfig, Request, SlotEngine
from repro.serving.slots import FinishReason

#: terminal reasons that mean the engine failed a request (vs. ran it out)
FAILED = (FinishReason.ERROR, FinishReason.RETRIES_EXHAUSTED)


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--engine", choices=("wave", "slot"), default="slot",
                    help="wave = lockstep batches; slot = slot-resident "
                         "continuous batching (default)")
    ap.add_argument("--load", type=float, default=0.0,
                    help="injected accelerator load in [0,1] (paper Fig 7)")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def init_params(model: registry.Model, seed: int):
    """Random weights from ``seed``, initialised in ONE jitted program so
    each f32 draw fuses into its cast to the parameter dtype — eager init
    keeps every f32 temporary and every per-layer copy alive until the
    layers are stacked, about twice the weights' bytes at full width."""
    init = jax.jit(lambda key: split(model.init(key))[0])
    return init(jax.random.PRNGKey(seed))


def make_requests(cfg, args: argparse.Namespace) -> list[Request]:
    rng = np.random.default_rng(args.seed)
    shape = ((cfg.n_codebooks, args.prompt_len) if cfg.n_codebooks
             else (args.prompt_len,))
    return [Request(i, rng.integers(0, cfg.vocab, shape).astype(np.int32),
                    max_new_tokens=args.max_new)
            for i in range(args.requests)]


def make_engine(model: registry.Model, params, args: argparse.Namespace):
    config = EngineConfig(n_slots=args.batch_size,
                          max_seq=args.prompt_len + args.max_new + 1,
                          queue_capacity=max(args.requests, 1))
    engine_cls = SlotEngine if args.engine == "slot" else Engine
    return engine_cls(model, params, config=config,
                      sensor=SyntheticLoadSensor(args.load))


def failed(engine, results) -> list:
    """Failed requests that count against the run: any ``error`` or
    ``retries_exhausted`` finish when no FaultPlan injected it."""
    if engine.config.faults is not None:
        return []
    return [r for r in results if r.finish_reason in FAILED]


def main(argv: list[str] | None = None) -> int:
    args = parse_args(argv)
    cfg = get_arch(args.arch + ("-reduced" if args.reduced else ""))
    model = registry.build(cfg)
    params = init_params(model, args.seed)
    engine = make_engine(model, params, args)

    t0 = time.time()
    results = engine.serve(make_requests(cfg, args))
    wall = time.time() - t0
    n_tok = sum(r.tokens.shape[-1] for r in results)
    print(f"arch={cfg.name} served={len(results)} new_tokens={n_tok} "
          f"wall={wall:.2f}s tok/s={n_tok / wall:.1f}")
    for r in results[:4]:
        print(f"  req {r.uid}: prefill={r.prefill_s * 1e3:.1f}ms "
              f"decode={r.decode_s * 1e3:.1f}ms plans={set(r.plan_decisions)}")
    print("pool:", engine.pool.stats)
    bad = failed(engine, results)
    if bad:
        print(f"FAILED: {len(bad)} request(s) ended in "
              f"{sorted({r.finish_reason for r in bad})} with no FaultPlan "
              f"set (uids {[r.uid for r in bad]})", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    compile_cache.enable()
    sys.exit(main())
