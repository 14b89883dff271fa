#!/usr/bin/env python3
"""Chip smoke test: MobiRNN's main paths, once each, on one TPU.

  python chip_smoke.py          # from the checkout root, on a TPU host

Phase ``har`` — the paper's model (configs/mobirnn_lstm.py: 2 layers x 32
hidden, 9 sensor channels, T=128, 6 classes) on seeded synthetic HAR windows
(data/har.py):
  * AdamW steps at batch 64 through ``examples/train_har.py``'s ``train``
    with the ``fused_seq`` plan; the loss on a fixed batch must be finite
    and fall;
  * the paper §4.1 protocol (each test case alone, batch 1) for
    ``sequential``, ``fused_cell``, ``fused_seq`` and ``fused_seq_q8``;
  * forward and ``jax.grad`` of ``fused_seq`` and ``fused_seq_q8`` at the
    paper's width and at Fig 5/6's largest (3 x 256), batch 1 and 64.
  Every Pallas plan must compile to a ``tpu_custom_call``, no
  ``plan/dispatch`` event may show a fallback, and logits and gradients
  must match the float32 oracles of kernels/ref.py (run at "highest"
  matmul precision) within the bounds below.

Phase ``serve`` — rwkv6-3b at its published width (configs/rwkv6_3b.py: 32
layers, d_model 2560, vocab 65536) with seeded random weights, through
launch/serve.py's engine (SlotEngine): 4 slots, 8 requests of 128 prompt
tokens and 16 new tokens, twice: in the published bf16, then in float32
with "highest"-precision matmuls (the same draws, unrounded).  Each time
every request must finish with ``length`` (none sets a stop token), no
lane may be quarantined or retried, a request served alone and beside
busy lanes must yield the same tokens, and each first token must be the
argmax of a batch-1 ``model.forward`` over its prompt.  Every greedy token
is compared with the argmax of ``model.forward`` over the prompt plus the
tokens before it; a mismatch is a counted near-tie when the reference
prefers its argmax by no more than its own rounding envelope.  In float32
every mismatch must be a near-tie and nearly all tokens must agree.  In
bf16 the random-weight 32-layer model is chaotic under rounding (two
equivalent evaluations of the reference disagree with each other), so
there the later tokens are held only to a share of the reference's
agreement with itself.

Prints one JSON line per phase, then, only when every phase passed, the
last line ``{"ok": true, "device": {...}}``.  There is no CPU mode: without
a TPU the script exits 2 before it runs anything.  Everything runs in this
one process (a chip belongs to one process at a time).
"""
from __future__ import annotations

import json
import math
import os
import statistics
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

#: |logits - oracle| bound for every LSTM plan.  Mosaic lowers the
#: kernels' f32 dots at default precision — fewer MXU passes than the
#: oracle's "highest" — while XLA's ``sequential`` plan stays within 2.4e-7
#: of it.  The Pallas plans read 1.6e-3 at most on a v5e (two chip runs,
#: identical readings); 1e-2 leaves a factor of 6 for other seeds.
LOGIT_ATOL = 1e-2
#: ||g - g_oracle|| / ||g_oracle|| bound, per parameter leaf (the worst
#: leaf is reported), so a wrong small leaf cannot hide under the weight
#: gradients.  Same cause as LOGIT_ATOL, through the reverse sweep's dots;
#: the worst leaf reads 7.0e-3 on a v5e (a bias leaf, 2 x 32 at B=64),
#: so 2e-2 leaves a factor of about 3.
GRAD_RTOL = 2e-2
#: serve: a greedy token may differ from the reference argmax only where
#: the reference prefers its argmax by no more than ENVELOPE_FACTOR times
#: the rounding envelope — how far that same logit gap moves between two
#: equivalent evaluations of the reference (two wkv chunkings); the engine
#: is a third such evaluation, so its distance from the reference can
#: exceed the one sampled distance — and never by less than NEAR_TIE_ULPS
#: ulps of the top logit in the run's dtype (the logits come out of a
#: matmul in that dtype, so closer values are one rounding apart).  First
#: tokens are held to the ulps alone: there the reference runs the
#: prefill's own shape.
ENVELOPE_FACTOR = 2
NEAR_TIE_ULPS = 2
#: serve, float32: share of tokens that must equal the reference argmax
MIN_AGREE_F32 = 0.95
#: serve, bf16: the engine's agreement with the reference must reach this
#: share of the reference's agreement with its own second evaluation — a
#: broken decode path agrees with almost nothing, rounding chaos with
#: about as much as the reference agrees with itself
BF16_MIN_AGREE_OF_SELF = 0.5

PALLAS_PLANS = ("fused_cell", "fused_seq", "fused_seq_q8")
PROTOCOL_PLANS = ("sequential",) + PALLAS_PLANS


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _require_tpu():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's first device is {dev.platform!r} "
              f"({dev.device_kind}); this script runs only on a TPU",
              file=sys.stderr)
        sys.exit(2)
    return dev


# ---------------------------------------------------------------------------
# phase har
# ---------------------------------------------------------------------------
def _oracle_forward(quantized: bool):
    """(params, x, cfg) -> logits through kernels/ref.py's float32 stacked
    LSTM (the q8 plan's oracle dequantizes with straight-through grads)."""
    from repro.kernels import lstm_seq as seq_lib
    from repro.kernels import ref
    from repro.partitioning import split

    def forward(params, x, cfg):
        p, _ = split(params)
        w, b, p_width = seq_lib.stack_params(p["layers"], cfg.hidden)
        if quantized:
            w = ref.quantize_dequantize_ste(w)
        _, h = ref.lstm_seq(w, b, seq_lib.pad_input(x, p_width))
        return h[-1] @ p["head"]["w"] + p["head"]["b"]
    return forward


def _highest(fn):
    import jax

    def run(*args):
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*args)
    return run


def _compile(fn, *args):
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, time.perf_counter() - t0


def _grad_rel_err(g, g_ref) -> tuple[float, str]:
    """Worst relative error over the parameter leaves, and its leaf."""
    import jax
    import jax.numpy as jnp

    errs = {jax.tree_util.keystr(path): float(
        jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-30))
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g),
                                jax.tree.leaves(g_ref))}
    leaf = max(errs, key=errs.get)
    return errs[leaf], leaf


def phase_har(out: dict, *, hidden: int = 32, layers: int = 2,
              big_hidden: int = 256, big_layers: int = 3, batch: int = 64,
              steps: int = 30, n_cases: int = 100, seed: int = 0) -> None:
    import jax
    import jax.numpy as jnp

    import train_har
    from repro.configs.mobirnn_lstm import LSTMConfig
    from repro.core import lstm
    from repro.data import har
    from repro.kernels.lstm_seq import ORACLE_BWD
    from repro.obs import trace

    cfg = LSTMConfig().with_complexity(hidden, layers)
    big = LSTMConfig().with_complexity(big_hidden, big_layers)
    train_data, test = har.make_har(batch * steps, max(n_cases, batch),
                                    seed=seed)
    sink = trace.ListSink()
    previous = trace.set_tracer(trace.Tracer(sink))
    out["shape"] = {"layers": layers, "hidden": hidden,
                    "seq_len": cfg.seq_len, "channels": cfg.input_dim,
                    "classes": cfg.n_classes}
    compile_s = 0.0
    try:
        # -- training through examples/train_har.py -----------------------
        ex, ey = jnp.asarray(test.x[:batch]), jnp.asarray(test.y[:batch])
        oracle_loss = _highest(lambda p, x, y: lstm.loss_fn(
            p, x, y, cfg, forward=_oracle_forward(False)))
        p0 = lstm.init_params(jax.random.PRNGKey(seed), cfg)
        before = float(oracle_loss(p0, ex, ey))
        params, losses, step_s = train_har.train(
            cfg, lstm.FORWARD_PLANS["fused_seq"], train_data, steps=steps,
            batch=batch, lr=3e-3, seed=seed, log=lambda *_: None)
        after = float(oracle_loss(params, ex, ey))
        check(all(map(math.isfinite, losses)), f"non-finite loss {losses}")
        check(after < before, f"eval loss did not fall: {before} -> {after}")
        out["train"] = {"plan": "fused_seq", "batch": batch, "steps": steps,
                        "eval_loss_before": before, "eval_loss_after": after,
                        "first_step_s": step_s[0],
                        "median_step_s": statistics.median(step_s[1:]
                                                           or step_s)}

        # -- paper §4.1 protocol at batch 1 -------------------------------
        cases = jnp.asarray(test.x[:n_cases])
        fns, proto = {}, {}
        out["protocol"] = {"batch": 1, "cases": n_cases, "plans": proto}
        for name in PROTOCOL_PLANS:
            fwd = lstm.FORWARD_PLANS[name]
            compiled, secs = _compile(lambda p, x, fwd=fwd: fwd(p, x, cfg),
                                      params, cases[:1])
            compile_s += secs
            kernels = compiled.as_text().count("tpu_custom_call")
            fns[name] = lambda x, c=compiled: c(params, x)
            proto[name] = {"compile_s": secs, "kernels": kernels}
        totals, logits = train_har.latency_protocol(fns, cases)
        ref_logits = {
            q: _highest(lambda p, x, q=q: _oracle_forward(q)(p, x, cfg))(
                params, cases) for q in (False, True)}
        for name in PROTOCOL_PLANS:
            proto[name].update(
                logit_err=float(jnp.max(jnp.abs(
                    logits[name] - ref_logits[name == "fused_seq_q8"]))),
                ms_per_case=totals[name] * 1e3 / n_cases)

        # -- forward + jax.grad of the fused plans at both widths ---------
        sweep = out["fwd_grad"] = {}
        for tag, c in (("paper", cfg), ("fig5_6", big)):
            pc = lstm.init_params(jax.random.PRNGKey(seed + 1), c)
            for b in (1, batch):
                x, y = (jnp.asarray(test.x[:b]), jnp.asarray(test.y[:b]))
                for name in ("fused_seq", "fused_seq_q8"):
                    fwd = lstm.FORWARD_PLANS[name]
                    oracle = _oracle_forward(name == "fused_seq_q8")
                    f_c, f_s = _compile(lambda p, x, fwd=fwd, c=c:
                                        fwd(p, x, c), pc, x)
                    g_c, g_s = _compile(jax.grad(
                        lambda p, x, y, fwd=fwd, c=c:
                        lstm.loss_fn(p, x, y, c, forward=fwd)), pc, x, y)
                    compile_s += f_s + g_s
                    l_err = float(jnp.max(jnp.abs(
                        f_c(pc, x) - _highest(lambda p, x, c=c, o=oracle:
                                              o(p, x, c))(pc, x))))
                    g_ref = _highest(jax.grad(
                        lambda p, x, y, c=c, o=oracle:
                        lstm.loss_fn(p, x, y, c, forward=o)))(pc, x, y)
                    g_err, g_leaf = _grad_rel_err(g_c(pc, x, y), g_ref)
                    sweep[f"{name}/{tag}/B{b}"] = {
                        "layers": c.n_layers, "hidden": c.hidden,
                        "kernels": [f_c.as_text().count("tpu_custom_call"),
                                    g_c.as_text().count("tpu_custom_call")],
                        "logit_err": l_err, "grad_rel_err": g_err,
                        "grad_worst_leaf": g_leaf, "compile_s": f_s + g_s}
    finally:
        trace.set_tracer(previous)

    out["compile_s"] = compile_s
    dispatch = [r["attrs"] for r in sink.records
                if r["name"] == "plan/dispatch"
                and r["attrs"].get("family") == "lstm"]
    out["dispatch_events"] = len(dispatch)
    # every bound is checked after every reading is in ``out``, so a
    # failing run still prints them all
    for name, p in proto.items():
        check(name not in PALLAS_PLANS or p["kernels"] > 0,
              f"{name}: no tpu_custom_call compiled")
        check(p["logit_err"] <= LOGIT_ATOL,
              f"{name}: |logits - oracle| {p['logit_err']} > {LOGIT_ATOL}")
    for key, s in sweep.items():
        check(min(s["kernels"]) > 0, f"{key}: no tpu_custom_call")
        check(s["logit_err"] <= LOGIT_ATOL,
              f"{key}: |logits - oracle| {s['logit_err']} > {LOGIT_ATOL}")
        check(s["grad_rel_err"] <= GRAD_RTOL,
              f"{key}: grad rel err {s['grad_rel_err']} at "
              f"{s['grad_worst_leaf']} > {GRAD_RTOL}")
    check(dispatch, "no plan/dispatch events recorded")
    bad = [a for a in dispatch
           if "fallback" in a or a.get("bwd_block_b") == ORACLE_BWD]
    check(not bad, f"fused plan fell back: {bad}")


# ---------------------------------------------------------------------------
# phase serve
# ---------------------------------------------------------------------------
def _ulp(x, dtype: str):
    """Spacing of ``dtype`` at |x| (bf16 has 8 significand bits, f32 24)."""
    import numpy as np

    bits = {"bfloat16": 8, "float32": 24}[dtype]
    return 2.0 ** (np.floor(np.log2(np.maximum(np.abs(x), 1e-30)))
                   - (bits - 1))


def _serve_and_compare(cfg, args, out: dict) -> dict:
    """Serve ``args.requests`` requests of ``cfg`` through launch/serve.py's
    engine, check the serving contract, and compare every greedy token
    with ``model.forward``.  Fills ``out``; returns the token counts the
    caller's gate needs."""
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import serve
    from repro.models import registry
    from repro.partitioning import param_count
    from repro.serving import Request
    from repro.serving.slots import FinishReason

    n, prompt_len, max_new = args.requests, args.prompt_len, args.max_new
    model = registry.build(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(serve.init_params(model, args.seed))
    out["init_s"] = time.perf_counter() - t0
    out["params"] = param_count(params)
    engine = serve.make_engine(model, params, args)
    reqs = serve.make_requests(cfg, args)

    # one warm-up request compiles the admission prefill and the decode tick
    t0 = time.perf_counter()
    warm = engine.serve([Request(n, reqs[0].prompt, max_new_tokens=max_new)])
    out["warmup_request_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    results = engine.serve(reqs)
    out["serve_s"] = time.perf_counter() - t0

    # no request sets a stop token, so every one must run out its budget
    reasons = [r.finish_reason for r in warm + results]
    out["finish_reasons"] = sorted(set(reasons))
    check(all(r == FinishReason.LENGTH for r in reasons),
          f"finish reasons {reasons}")
    check(not serve.failed(engine, results), "failed requests")
    counters = engine.metrics.snapshot()["counters"]
    for name in ("serving/quarantined", "serving/retries"):
        out[name.split("/")[1]] = counters[name]
        check(counters[name] == 0, f"{name} = {counters[name]}")
    check(all(r.tokens.shape == (max_new,) for r in results),
          f"token counts {[r.tokens.shape for r in results]}")
    # lanes never interact: the warm-up ran request 0's prompt alone, the
    # batch ran it beside three busy lanes — the tokens must be identical
    check(np.array_equal(warm[0].tokens, results[0].tokens),
          f"lane isolation: {warm[0].tokens} vs {results[0].tokens}")
    out["lane_isolation"] = True
    got = np.stack([r.tokens for r in results])             # (n, max_new)
    prompts = np.stack([q.prompt for q in reqs])             # (n, prompt)
    # the engine's jitted closures hold it in a reference cycle: collect
    # it now, so its state pool leaves the device with the weights
    del engine, warm, results
    gc.collect()

    # first tokens: model.forward at batch 1 over exactly the prompt — the
    # shape the admission prefill runs — so the two programs differ only in
    # the prefill's cache writes and last-row head
    first = jax.jit(lambda p, t: model.forward(
        p, {"tokens": t}, inference=True)[0][0, -1])
    t0 = time.perf_counter()
    ref0 = np.stack([np.asarray(first(params, row[None])) for row in prompts])
    out["first_ref_s"] = time.perf_counter() - t0
    rows = np.arange(n)
    best0 = ref0.argmax(-1)
    gap0 = ref0[rows, best0] - ref0[rows, got[:, 0]]
    tie0 = gap0 <= NEAR_TIE_ULPS * _ulp(ref0[rows, best0], cfg.dtype)
    out["first_tokens_agree"] = int(np.sum(got[:, 0] == best0))
    out["first_token_near_ties"] = int(np.sum((got[:, 0] != best0) & tie0))
    check(np.all((got[:, 0] == best0) | tie0),
          f"first tokens {got[:, 0].tolist()} vs batch-1 model.forward "
          f"argmax {best0.tolist()} (gaps {gap0.tolist()})")

    # every token: model.forward over the prompt plus the tokens before it
    # (teacher-forced), run twice.  Run A pads to a multiple of the wkv
    # chunk, run B reads exactly prompt + tokens[:-1], so the two chunk
    # the sequence differently.  Causality makes them the same function at
    # the checked positions: how far the compared logit gap moves between
    # them is the rounding envelope of this model at this depth.
    chunk = cfg.ssm.chunk if cfg.ssm is not None else 1
    seqs = np.zeros((n, -(-(prompt_len + max_new) // chunk) * chunk),
                    np.int32)
    seqs[:, :prompt_len] = prompts
    seqs[:, prompt_len:prompt_len + max_new] = got

    def teacher_forced(p, toks_a, toks_b, got):
        def logits_at(toks):
            logits, _ = model.forward(p, {"tokens": toks}, inference=True)
            return logits[:, prompt_len - 1:prompt_len - 1 + max_new]

        a, b = logits_at(toks_a), logits_at(toks_b)
        best = jnp.argmax(a, axis=-1)
        # the entry the gap is taken to: the engine's token where it
        # differs, else the reference's runner-up (its top-2 gap)
        alt = jnp.where(got == best, jax.lax.top_k(a, 2)[1][..., 1], got)

        def at(logits, idx):
            return jnp.take_along_axis(logits, idx[..., None], -1)[..., 0]
        return (best, jnp.argmax(b, axis=-1), at(a, best),
                at(a, best) - at(a, alt), at(b, best) - at(b, alt))

    ref_args = (params, seqs, seqs[:, :prompt_len + max_new - 1], got)
    ref_c, out["ref_compile_s"] = _compile(teacher_forced, *ref_args)
    best, best_b, top, gap_a, gap_b = jax.device_get(ref_c(*ref_args))
    envelope = np.abs(gap_a - gap_b)
    mism = got != best
    tie = gap_a <= np.maximum(ENVELOPE_FACTOR * envelope,
                              NEAR_TIE_ULPS * _ulp(top, cfg.dtype))
    counts = {"checked": int(got.size), "agree": int(np.sum(~mism)),
              "near_ties": int(np.sum(mism & tie)),
              "beyond_envelope": int(np.sum(mism & ~tie)),
              "reference_self_agree": int(np.sum(best == best_b))}
    out.update({f"tokens_{k}": v for k, v in counts.items()})
    out["envelope_median"] = float(np.median(envelope))
    out["top2_gap_median"] = float(np.median(gap_a[~mism])) if np.any(
        ~mism) else None
    out["beyond_envelope_gaps"] = gap_a[mism & ~tie].tolist()[:16]
    stats = jax.devices()[0].memory_stats() or {}
    out["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    out["bytes_limit"] = stats.get("bytes_limit")
    return counts


def phase_serve(out: dict, *, arch: str = "rwkv6-3b", requests: int = 8,
                prompt_len: int = 128, max_new: int = 16, slots: int = 4,
                seed: int = 0) -> None:
    import dataclasses
    import gc

    import jax

    from repro.configs import get_arch
    from repro.launch import serve

    args = serve.parse_args([
        "--arch", arch, "--requests", str(requests),
        "--prompt-len", str(prompt_len), "--max-new", str(max_new),
        "--batch-size", str(slots), "--seed", str(seed)])
    cfg = get_arch(arch)
    out.update(arch=cfg.name, shape={"layers": cfg.n_layers,
                                     "d_model": cfg.d_model,
                                     "vocab": cfg.vocab},
               requests=requests, slots=slots, prompt_len=prompt_len,
               max_new=max_new)

    # the published configuration, bf16: first tokens sharp, later tokens
    # held only against the reference's own disagreement with itself
    published = out[cfg.dtype] = {}
    counts = _serve_and_compare(cfg, args, published)
    floor = BF16_MIN_AGREE_OF_SELF * counts["reference_self_agree"]
    check(counts["agree"] >= floor,
          f"{cfg.dtype}: {counts['agree']} of {counts['checked']} tokens "
          f"equal the reference argmax, below {floor} "
          f"({BF16_MIN_AGREE_OF_SELF} x the reference's self-agreement)")
    gc.collect()

    # the same weights' draw in float32 with "highest"-precision matmuls:
    # not chaotic, so every token is held to the reference argmax
    sharp = out["float32"] = {}
    sharp["bytes_in_use_before"] = (
        jax.devices()[0].memory_stats() or {}).get("bytes_in_use")
    with jax.default_matmul_precision("highest"):
        counts = _serve_and_compare(
            dataclasses.replace(cfg, dtype="float32"), args, sharp)
    check(counts["beyond_envelope"] == 0,
          f"float32: {counts['beyond_envelope']} greedy tokens disagree with "
          f"the reference beyond its rounding envelope")
    check(counts["agree"] >= MIN_AGREE_F32 * counts["checked"],
          f"float32: {counts['agree']} of {counts['checked']} tokens equal "
          f"the reference argmax, below {MIN_AGREE_F32}")


PHASES = (("har", phase_har), ("serve", phase_serve))


def main() -> int:
    dev = _require_tpu()
    sys.path[:0] = [os.path.join(ROOT, "src"), os.path.join(ROOT, "examples")]
    import jax

    from repro import compile_cache
    compile_cache.enable()
    ok = True
    for name, phase in PHASES:
        t0 = time.perf_counter()
        info: dict = {}
        try:
            phase(info)
            line = {"phase": name, "ok": True}
        except Exception as err:           # every phase runs; any fails all
            traceback.print_exc()
            line = {"phase": name, "ok": False,
                    "error": f"{type(err).__name__}: {err}"[:4000]}
            ok = False
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps({**line, **info}), flush=True)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
