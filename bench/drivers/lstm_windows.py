"""On-device recurrent inference: sensor windows classified one at a time
by the stacked LSTM through the plan the configuration names (the paper's
§4.1 protocol), in a closed loop.

Set-up draws the weights on the device and a pool of windows from the
seed, and runs the window shape until it is compiled.  In the measured
window each classification is issued when the last one's logits reached
the host; the end-to-end metric is the window's length over the windows
classified.  Afterwards every answer is compared with the plain
reference's logits for its window.
"""
from __future__ import annotations

import functools
import gc
import math
import time

import numpy as np

from harness import manifest, traffic, xtrace
from harness.device import describe


def build(cfgf: dict, seed: int, dtype: str | None = None):
    """(reference, weights, program forward, windows pool).  The forward
    runs at the configuration's matmul precision ("highest" for float32:
    the chip's default would round every f32 operand to bf16), or, with
    ``dtype`` given (the control), in that dtype at default precision."""
    import jax
    import jax.numpy as jnp
    from repro.configs.mobirnn_lstm import LSTMConfig
    from repro.core import lstm as lstm_lib
    from repro.partitioning import Annot

    m = dict(cfgf["model"])
    ref = manifest.reference(cfgf)
    w = jax.jit(functools.partial(ref.make_weights, m))(traffic.key_for(seed))
    lcfg = LSTMConfig(n_layers=m["n_layers"], hidden=m["hidden"],
                      input_dim=m["input_dim"], seq_len=m["seq_len"],
                      n_classes=m["n_classes"], dtype=dtype or m["dtype"])
    cast = jnp.dtype(lcfg.dtype)
    params = jax.tree.map(lambda a: Annot(a.astype(cast), (None,) * a.ndim),
                          w)
    plan = lstm_lib.FORWARD_PLANS[cfgf["plan"]]
    precision = "default" if dtype else cfgf["precision"]

    def forward(p, x):
        with jax.default_matmul_precision(precision):
            return plan(p, x.astype(cast), lcfg)

    fwd = jax.jit(forward)
    pool = ref.windows(traffic.rng_for(seed, 40), int(cfgf["pool"]),
                       m["seq_len"], m["input_dim"])
    return ref, w, functools.partial(fwd, params), pool


def classify(fwd, pool: np.ndarray, order: np.ndarray, until: float,
             batch: int, start: int = 0):
    """Closed loop until ``until``, taking windows in ``order`` (round and
    round) from its ``start``-th entry: returns (pool index, logits,
    t_issue, t_done) per window classified."""
    idx, outs, t_in, t_out = [], [], [], []
    views = [pool[i:i + batch] for i in range(len(pool) - batch + 1)]
    k = 0
    while True:
        t0 = time.perf_counter()
        if t0 >= until:
            break
        i = int(order[(start + k) % len(order)])
        y = np.asarray(fwd(views[i]))
        t1 = time.perf_counter()
        idx.append(i)
        outs.append(y)
        t_in.append(t0)
        t_out.append(t1)
        k += 1
    return idx, outs, t_in, t_out


def run(cell, seed: int, seconds: float, trace: bool, devices,
        t_start: float, log_dir: str) -> dict:
    cfgf, mix = cell.config, cell.traffic
    batch = int(mix["batch"])
    ref, w, fwd, pool = build(cfgf, seed)
    n_views = len(pool) - batch + 1
    order = traffic.rng_for(seed, 41).permutation(n_views)
    for i in range(int(mix["warmup_windows"])):
        np.asarray(fwd(pool[i % n_views:i % n_views + batch]))
    w0 = time.perf_counter()
    setup_s = w0 - t_start
    cap = None
    idx, outs, t_in, t_out = classify(
        fwd, pool, order,
        w0 + (float(mix["trace_offset_s"]) if trace else seconds), batch)
    if trace:
        with xtrace.Capture(log_dir) as cap:
            more = classify(fwd, pool, order,
                            cap.t0 + float(mix["trace_s"]), batch,
                            len(idx))
        traced = (len(idx), len(idx) + len(more[0]))
        for a, b in zip((idx, outs, t_in, t_out), more):
            a.extend(b)
        rest = classify(fwd, pool, order, w0 + seconds, batch, len(idx))
        for a, b in zip((idx, outs, t_in, t_out), rest):
            a.extend(b)
        cap.load()
    w1 = w0 + seconds
    done = [i for i, t in enumerate(t_out) if t <= w1]
    device = describe(devices)
    del fwd
    gc.collect()

    expect = np.asarray(ref.logits(w, pool[:n_views + batch - 1]))
    got = np.stack([o for o in outs])                   # (n, batch, C)
    want = np.stack([expect[i:i + batch] for i in idx])
    err = float(np.max(np.abs(got - want))) if len(idx) else math.inf
    limit = float(cfgf["check"]["max_abs_logit_err"])
    failed = sum(1 for o in outs if not np.all(np.isfinite(o)))
    out = {
        "correct": bool(len(idx)) and err <= limit and failed == 0,
        "attempted": len(idx), "failed": failed,
        "e2e": {"window_ms": (w1 - w0) * 1e3 / max(len(done), 1),
                "setup_s": setup_s},
        "device": device,
        "checks": [["max_abs_logit_err", err, limit],
                   ["failed_windows", failed, 0]],
        "notes": {"windows": len(idx), "windows_in_window": len(done)},
        "window": (w0, w1),
    }
    if trace and cap is not None and cap.trace is not None:
        spans = [("har/window", cap.to_ns(t_in[i]), cap.to_ns(t_out[i]))
                 for i in range(*traced)]
        out["trace"] = {"trace": cap.trace, "capture": cap,
                        "windows": traced[1] - traced[0], "batch": batch,
                        "spans": spans, "model": cfgf["model"]}
    return out

