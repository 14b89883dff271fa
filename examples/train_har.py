"""End-to-end driver: train the paper's activity-recognition LSTM to
convergence (a few hundred steps) and reproduce the §4 evaluation protocol
(latency over 100 test cases, per-plan).

Training runs under any of the registered execution plans
(core/lstm.FORWARD_PLANS) via ``--plan`` — ``fused_seq_q8`` trains
quantization-aware (int8 forward, straight-through grads to f32 masters);
with either fused-seq plan the whole
``value_and_grad`` lowers to TWO Pallas dispatches (one trajectory-emitting
forward + one reverse-sweep BPTT kernel), and the latency table sweeps ALL
registered plans so the Fig 4 comparison covers the Pallas plans too.

  PYTHONPATH=src python examples/train_har.py --steps 300 --hidden 32 \
      --layers 2 --plan fused_seq
"""
import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.configs.mobirnn_lstm import LSTMConfig
from repro.core import lstm
from repro.data import har
from repro.optim import AdamW, warmup_cosine


def train(cfg: LSTMConfig, forward, data: har.HARData, *, steps: int,
          batch: int, lr: float, test: har.HARData | None = None,
          seed: int = 0, log=print):
    """AdamW training of the HAR classifier under one execution plan.
    Returns ``(params, losses, step_s)``: the trained params, the loss of
    every step and its wall seconds (the first includes the compile)."""
    params = lstm.init_params(jax.random.PRNGKey(seed), cfg)
    opt = AdamW(lr=warmup_cosine(lr, steps // 10, steps), weight_decay=0.0)
    state = opt.init(params)

    @jax.jit
    def step(params, state, x, y):
        loss, grads = jax.value_and_grad(lstm.loss_fn)(params, x, y, cfg,
                                                       forward=forward)
        params, state, m = opt.update(grads, state, params)
        return params, state, loss, m["grad_norm"]

    # a batch larger than the train set would make har.batches yield nothing
    it = har.batches(data, min(batch, len(data.y)), seed=seed)
    t0 = time.time()
    losses, step_s = [], []
    for i in range(1, steps + 1):
        bx, by = next(it)
        t_step = time.perf_counter()
        params, state, loss, _ = step(params, state, jnp.asarray(bx),
                                      jnp.asarray(by))
        losses.append(float(loss))            # blocks on the step
        step_s.append(time.perf_counter() - t_step)
        if test is not None and (i % 50 == 0 or i == 1):
            n_eval = min(512, len(test.y))
            acc = lstm.accuracy(params, jnp.asarray(test.x[:n_eval]),
                                jnp.asarray(test.y[:n_eval]), cfg,
                                forward=forward)
            log(f"step {i:4d} loss {losses[-1]:.4f} "
                f"test_acc {float(acc):.1%} "
                f"({time.time() - t0:.0f}s)")
    return params, losses, step_s


def latency_protocol(fns: dict, cases: jax.Array
                     ) -> tuple[dict[str, float], dict[str, jax.Array]]:
    """Paper §4.1: classify each test case alone (batch 1), one after the
    other.  ``fns`` maps a plan name to ``x -> logits``; returns the total
    seconds per plan (warm-up call and compile excluded) and the logits of
    every case."""
    totals, logits = {}, {}
    for name, fn in fns.items():
        jax.block_until_ready(fn(cases[:1]))            # compile / warm up
        outs = []
        t0 = time.perf_counter()
        for j in range(cases.shape[0]):
            outs.append(jax.block_until_ready(fn(cases[j:j + 1])))
        totals[name] = time.perf_counter() - t0
        logits[name] = jnp.concatenate(outs)
    return totals, logits


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--hidden", type=int, default=32)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--plan", default="sequential",
                    choices=sorted(lstm.FORWARD_PLANS),
                    help="execution plan for the TRAINING step "
                         "(core/lstm.FORWARD_PLANS; fused_seq is the "
                         "single-dispatch MobiRNN fast path, forward and "
                         "backward; fused_seq_q8 is its int8-weight QAT "
                         "variant — equivalent within the int8 error band, "
                         "the rest exactly)")
    ap.add_argument("--latency-cases", type=int, default=100,
                    help="cases for the paper §4.1 latency protocol "
                         "(0 skips it — the CI smoke setting)")
    ap.add_argument("--n-train", type=int, default=7352,
                    help="synthetic train windows (UCI HAR protocol size)")
    ap.add_argument("--n-test", type=int, default=2947)
    args = ap.parse_args()

    forward = lstm.FORWARD_PLANS[args.plan]
    cfg = LSTMConfig().with_complexity(args.hidden, args.layers)
    print(f"config: {cfg.name} ({cfg.n_layers}L x {cfg.hidden}H) "
          f"plan={args.plan}")
    train_data, test = har.make_har(args.n_train, args.n_test)
    print(f"data: {len(train_data.y)} train / {len(test.y)} test windows "
          f"(UCI HAR protocol)")

    params, _, _ = train(cfg, forward, train_data, steps=args.steps,
                         batch=args.batch, lr=args.lr, test=test)

    acc = lstm.accuracy(params, jnp.asarray(test.x), jnp.asarray(test.y),
                        cfg, forward=forward)
    print(f"\nfinal test accuracy: {float(acc):.2%}")

    # --- paper §4.1 protocol: latency over N random test cases, for EVERY
    # registered execution plan (Fig 4 covers the Pallas plans too) --------
    n_cases = min(args.latency_cases, len(test.y))
    if n_cases <= 0:
        return
    idx = np.random.default_rng(0).choice(len(test.y), n_cases,
                                          replace=False)
    cases = jnp.asarray(test.x[idx])
    print(f"\nlatency for {n_cases} test cases (paper Fig 4 protocol):")
    fns = {name: functools.partial(
        jax.jit(lambda p, x, fwd=fwd: fwd(p, x, cfg)), params)
        for name, fwd in lstm.FORWARD_PLANS.items()}
    totals, _ = latency_protocol(fns, cases)
    for name, dt in totals.items():
        print(f"  {name:12s} {dt * 1e3:8.1f} ms total "
              f"({dt * 1e3 / n_cases:.2f} ms/case)")


if __name__ == "__main__":
    compile_cache.enable()
    main()
