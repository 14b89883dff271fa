"""Training driver.

Runs real training on the local device(s) for any registered architecture
(typically a ``--reduced`` variant on CPU) against the synthetic LM pipeline,
with sharded params (logical-axis rules on the host mesh), checkpointing and
metric logging.  The same step function lowers against the production mesh
in the dry-run — this driver is the single-host instantiation.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --reduced \
      --steps 50 --batch 8 --seq 64
"""
from __future__ import annotations

import argparse
import functools
import json
import time

import jax
import numpy as np

from repro import compile_cache, steps as steps_lib
from repro.checkpoint import ckpt
from repro.configs import get_arch
from repro.data.lm import SyntheticLM
from repro.launch.mesh import make_host_mesh
from repro.models import registry
from repro.optim import AdamW, warmup_cosine
from repro.partitioning import (make_rules, param_count, split,
                                tree_shardings, use_rules)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_arch(args.arch + ("-reduced" if args.reduced else ""))
    model = registry.build(cfg)
    mesh = make_host_mesh()
    rules = make_rules(mesh)

    params_annot = model.init(jax.random.PRNGKey(args.seed))
    params, axes = split(params_annot)
    print(f"arch={cfg.name} params={param_count(params):,} "
          f"mesh={dict(mesh.shape)}")

    optimizer = AdamW(lr=warmup_cosine(args.lr, args.steps // 10,
                                       args.steps))
    opt_state = optimizer.init(params)

    p_shard = tree_shardings(axes, params, rules)
    params = jax.device_put(params, p_shard)

    step_fn = jax.jit(
        functools.partial(steps_lib.train_step, optimizer, cfg),
        donate_argnums=(0, 1))

    data = SyntheticLM(cfg.vocab, seed=args.seed)
    it = data.batches(args.batch, args.seq)

    history = []
    log_every = max(args.log_every, 1)   # --log-every 0 means "every step"
    t0 = time.time()
    with mesh, use_rules(rules):
        for step in range(1, args.steps + 1):
            batch = {k: jax.numpy.asarray(v) for k, v in next(it).items()}
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            if step % log_every == 0 or step == 1:
                m = {k: float(v) for k, v in metrics.items()}
                m["step"] = step
                m["wall_s"] = round(time.time() - t0, 1)
                history.append(m)
                print(json.dumps({k: (round(v, 4) if isinstance(v, float)
                                      else v) for k, v in m.items()}))
            if args.ckpt_dir and step % args.ckpt_every == 0:
                ckpt.save(args.ckpt_dir, step, params,
                          {"arch": cfg.name})
    if not history:                      # --steps 0: nothing ran, no summary
        print("no training steps run")
        return
    first, last = history[0]["loss"], history[-1]["loss"]
    print(f"loss {first:.3f} -> {last:.3f} "
          f"({'improved' if last < first else 'NO IMPROVEMENT'})")


if __name__ == "__main__":
    compile_cache.enable()
    main()
