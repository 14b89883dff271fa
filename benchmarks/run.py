"""Benchmark harness — one benchmark per paper figure, plus framework-level
kernel/scan benchmarks.  Prints ``name,us_per_call,derived`` CSV rows.

Paper figures (MobiRNN, EMDL'17) and their analogues here:
  Fig 2/3  work-unit factorization: fine (per-column) vs packed vs fused —
           empirical wall time on this host + the calibrated device model
           (core/factorization) that reproduces the paper's mobile-GPU
           numbers.
  Fig 4    GPU-vs-CPU speedup for the default 2x32 model (device model) +
           empirical fused-vs-fine speedup.
  Fig 5    speedup vs model complexity (hidden units / layers sweep).
  Fig 6    multi-threaded CPU vs GPU (device model: >= 70% claim).
  Fig 7    latency vs load + dispatch crossover (scheduler, synthetic load).

Framework benches: Pallas kernels (interpret), rwkv chunk-size sweep (the
work-unit-coarseness knob measured empirically), MoE capacity-factor sweep.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import MOBIRNN_LSTM
from repro.core import cell as cell_lib
from repro.core import factorization as fz
from repro.core import lstm
from repro.core.scheduler import Plan, Scheduler, SyntheticLoadSensor

ROWS: list[tuple[str, float, str]] = []


def row(name: str, us: float, derived: str = "") -> None:
    ROWS.append((name, us, derived))
    print(f"{name},{us:.1f},{derived}")


def timeit(fn, *args, repeats: int = 5, **kw) -> float:
    fn(*args, **kw)  # compile
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kw))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


# ---------------------------------------------------------------------------
def bench_fig3_factorization() -> None:
    cfg = MOBIRNN_LSTM
    params = lstm.init_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, cfg.seq_len,
                                                  cfg.input_dim))

    def make(cell_fn):
        return jax.jit(lambda p, x: lstm.forward_sequential(p, x, cfg,
                                                            cell_fn=cell_fn))

    t_fine1 = timeit(make(lambda p, i, c, h: cell_lib.lstm_cell_fine(
        p, i, c, h, unit_cols=1)), params, x)
    t_fine10 = timeit(make(lambda p, i, c, h: cell_lib.lstm_cell_fine(
        p, i, c, h, unit_cols=10)), params, x)
    t_fused = timeit(make(cell_lib.lstm_cell_fused), params, x)
    row("fig3/fine_per_column", t_fine1, f"slowdown_vs_fused="
        f"{t_fine1 / t_fused:.2f}x")
    row("fig3/packed_10col", t_fine10,
        f"slowdown_vs_fused={t_fine10 / t_fused:.2f}x")
    row("fig3/fused", t_fused, "MobiRNN plan")
    # device-model reproduction of the paper's Fig 3 (4x slower on GPU)
    in_dim = cfg.input_dim + cfg.hidden
    t_gpu_fine = fz.factorize_gate(fz.MOBILE_GPU, in_dim, 4 * cfg.hidden, 1)
    t_cpu = fz.factorize_gate(fz.MOBILE_CPU1, in_dim, 4 * cfg.hidden,
                              4 * cfg.hidden)
    row("fig3/model_mobile_gpu_fine_vs_cpu", t_gpu_fine * 1e6,
        f"gpu_fine/cpu={t_gpu_fine / t_cpu:.2f}x (paper: ~4x slower)")


#: Mobile-class VMEM budget for the streamed fig2 family: whole-T residency
#: falls off it by T=256 (fwd) and at every T (bwd) at the seed config, so
#: the rows demonstrate the time-chunked pipeline keeping the plan fused where
#: it previously fell back.  Shared with the acceptance tests via
#: core/factorization so everything asserts one viability surface.
STREAM_BUDGET = fz.MOBILE_VMEM_BUDGET


def bench_fig2_dispatch_counts() -> None:
    """Fig 2/3's real lever, measured at the jaxpr level: kernel dispatches
    per forward AND per training step.  The per-cell fused plan launches one
    pallas_call per cell per step (O(T*L), and its VJP unrolls to O(T*L)
    again); the sequence-resident plan (kernels/lstm_seq.py +
    lstm_seq_bwd.py) launches exactly ONE forward and, under
    ``value_and_grad``, one forward + one reverse-sweep — O(1) in T both
    ways.  The ``stream_*`` rows repeat the count under the mobile-class
    STREAM_BUDGET: whole-T residency no longer fits there at long T, but
    the time-chunked double-buffered kernels keep the counts flat out to
    T=2048 — the ``nochunk`` note shows where the pre-streaming decision
    table (allow_chunk=False) would have fallen off the cliff."""
    from repro.analysis import count_kernel_dispatches, count_train_dispatches
    from repro.kernels import lstm_seq as seq_lib

    cfg = MOBIRNN_LSTM
    p_width = max(cfg.input_dim, cfg.hidden)
    for T in (32, 128, 512, 2048):
        params = lstm.init_params(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, T, cfg.input_dim))
        labels = jnp.zeros((2,), jnp.int32)
        n_cell = count_kernel_dispatches(jax.make_jaxpr(
            lambda p, x: lstm.forward_fused_kernel(p, x, cfg))(params, x))
        n_seq = count_kernel_dispatches(jax.make_jaxpr(
            lambda p, x: lstm.forward_fused_seq(p, x, cfg))(params, x))
        row(f"fig2/dispatch_fused_cell_T{T}", float(n_cell),
            f"pallas_calls={n_cell} (O(T*L))")
        row(f"fig2/dispatch_fused_seq_T{T}", float(n_seq),
            f"pallas_calls={n_seq} (O(1) in T)")
        t_cell = count_train_dispatches(
            lambda p: lstm.loss_fn(p, x, labels, cfg,
                                   forward=lstm.forward_fused_kernel),
            params)
        t_seq = count_train_dispatches(
            lambda p: lstm.loss_fn(p, x, labels, cfg,
                                   forward=lstm.forward_fused_seq),
            params)
        row(f"fig2/train_dispatch_fused_cell_T{T}", float(t_cell),
            f"pallas_calls={t_cell} (fwd+bwd, O(T*L))")
        row(f"fig2/train_dispatch_fused_seq_T{T}", float(t_seq),
            f"pallas_calls={t_seq} (1 fwd + 1 bwd, O(1) in T)")

        # the same counts under the mobile-class budget: streamed kernels
        n_stream = count_kernel_dispatches(jax.make_jaxpr(
            lambda p, x: lstm.forward_fused_seq(
                p, x, cfg, vmem_budget=STREAM_BUDGET))(params, x))
        t_stream = count_train_dispatches(
            lambda p: lstm.loss_fn(
                p, x, labels, cfg,
                forward=lambda p, x, cfg: lstm.forward_fused_seq(
                    p, x, cfg, vmem_budget=STREAM_BUDGET)),
            params)
        blocks = seq_lib.choose_batch_block(
            2, T, cfg.n_layers, p_width, cfg.hidden,
            vmem_budget=STREAM_BUDGET)
        nochunk = seq_lib.choose_batch_block(
            2, T, cfg.n_layers, p_width, cfg.hidden,
            vmem_budget=STREAM_BUDGET, allow_chunk=False)
        row(f"fig2/stream_dispatch_fused_seq_T{T}", float(n_stream),
            f"pallas_calls={n_stream},blocks={tuple(blocks) if blocks else None},"
            f"nochunk={'fused_seq' if nochunk else 'fused_cell-fallback'}")
        bwd_blocks = seq_lib.choose_batch_block(
            2, T, cfg.n_layers, p_width, cfg.hidden,
            vmem_budget=STREAM_BUDGET, mode="bwd")
        bwd_nochunk = seq_lib.choose_batch_block(
            2, T, cfg.n_layers, p_width, cfg.hidden,
            vmem_budget=STREAM_BUDGET, mode="bwd", allow_chunk=False)
        row(f"fig2/stream_train_dispatch_fused_seq_T{T}", float(t_stream),
            f"pallas_calls={t_stream},"
            f"bwd_blocks={tuple(bwd_blocks) if bwd_blocks else None},"
            f"nochunk={'fused-bwd' if bwd_nochunk else 'oracle-fallback'}")

    # wall time of the two kernel plans at the paper's default shape
    params = lstm.init_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.input_dim))
    t_cell = timeit(jax.jit(lambda p, x: lstm.forward_fused_kernel(
        p, x, cfg)), params, x, repeats=2)
    t_seq = timeit(jax.jit(lambda p, x: lstm.forward_fused_seq(
        p, x, cfg)), params, x, repeats=2)
    row("fig2/time_fused_cell_T32", t_cell, "interpret-mode wall time")
    row("fig2/time_fused_seq_T32", t_seq,
        f"speedup_vs_percell={t_cell / t_seq:.2f}x")


def bench_chunk_sweep() -> None:
    """fig2/chunk_sweep: latency + dispatch count vs ``time_chunk`` at fixed
    T.  Dispatch count is flat at 1 by construction (the chunk loop lives
    INSIDE the kernel); wall time shows the streaming overhead curve — on
    real TPU the double buffer hides the DMA behind compute, in interpret
    mode the rows still pin down the shape of the overhead and that
    chunking never changes results (the kernels are bit-identical, asserted
    in tests)."""
    from repro.analysis import count_kernel_dispatches
    from repro.kernels import lstm_seq as seq_lib
    from repro.partitioning import split

    cfg = MOBIRNN_LSTM
    B, T = 4, 256
    params = lstm.init_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, cfg.input_dim))
    values, _ = split(params)
    w_stack, b_stack, p_width = seq_lib.stack_params(values["layers"],
                                                     cfg.hidden)
    xp = seq_lib.pad_input(x, p_width)
    base = None
    for tc in (None, 128, 32, 8):
        fn = jax.jit(lambda w, b, xp, tc=tc: seq_lib.lstm_seq(
            w, b, xp, block_b=B, time_chunk=tc))
        t = timeit(fn, w_stack, b_stack, xp, repeats=2)
        n = count_kernel_dispatches(jax.make_jaxpr(
            lambda w, b, xp, tc=tc: seq_lib.lstm_seq(
                w, b, xp, block_b=B, time_chunk=tc))(w_stack, b_stack, xp))
        base = base or t
        label = "resident" if tc is None else f"tc{tc}"
        row(f"fig2/chunk_sweep_{label}", t,
            f"pallas_calls={n},vs_resident={base / t:.2f}x,T={T}")


def bench_stream_smoke() -> None:
    """CI smoke (fast job): at a T whose whole-T-resident working set
    exceeds the (constrained) budget, the fused plan must NOT fall back —
    forward stays 1 dispatch, value_and_grad stays 2, and the executed
    streamed kernels agree with the sequential oracle."""
    import numpy as np

    from repro.analysis import count_kernel_dispatches, count_train_dispatches
    from repro.kernels import lstm_seq as seq_lib

    cfg = MOBIRNN_LSTM
    B, T = 2, 512
    p_width = max(cfg.input_dim, cfg.hidden)
    # the pre-streaming table would fall back at this (T, budget)...
    assert seq_lib.choose_batch_block(
        B, T, cfg.n_layers, p_width, cfg.hidden,
        vmem_budget=STREAM_BUDGET, mode="bwd", allow_chunk=False) is None
    # ...the chunked table must not
    bwd_blocks = seq_lib.choose_batch_block(
        B, T, cfg.n_layers, p_width, cfg.hidden,
        vmem_budget=STREAM_BUDGET, mode="bwd")
    assert bwd_blocks is not None and bwd_blocks.time_chunk is not None, \
        bwd_blocks

    params = lstm.init_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, cfg.input_dim))
    labels = jnp.zeros((B,), jnp.int32)

    def fwd(p, x, cfg):
        return lstm.forward_fused_seq(p, x, cfg, vmem_budget=STREAM_BUDGET)

    n_fwd = count_kernel_dispatches(jax.make_jaxpr(
        lambda p, x: fwd(p, x, cfg))(params, x))
    n_train = count_train_dispatches(
        lambda p: lstm.loss_fn(p, x, labels, cfg, forward=fwd), params)
    assert n_fwd == 1, f"streamed forward fell back: {n_fwd} dispatches"
    assert n_train == 2, f"streamed backward fell back: {n_train} dispatches"

    want = lstm.forward_sequential(params, x, cfg)
    got = fwd(params, x, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    _, grads = jax.value_and_grad(
        lambda p: lstm.loss_fn(p, x, labels, cfg, forward=fwd))(params)
    assert all(bool(jnp.all(jnp.isfinite(g)))
               for g in jax.tree.leaves(grads))
    row("stream_smoke/long_T_fused", float(T),
        f"fwd_dispatches={n_fwd},train_dispatches={n_train},"
        f"bwd_blocks={tuple(bwd_blocks)},budget={STREAM_BUDGET}")


def bench_quant_rows() -> None:
    """quant/* rows: what int8 weights buy on the (T, 320K-budget) surface.

    For each T, fwd and bwd: the f32 vs q8 ``(block_b, time_chunk)`` choice
    under STREAM_BUDGET (the widened whole-T-resident window shows as
    ``tc=None`` where f32 already streams, and as coarser chunks past
    that), the streamed HBM bytes of the chosen tiling (the quartered
    weight term), and the q8 plan's dispatch counts — still 1 fwd / 2 train
    at every T (quantization happens in jnp outside the kernels).
    """
    from repro.analysis import (count_kernel_dispatches,
                                count_train_dispatches,
                                lstm_seq_stream_costs)
    from repro.kernels import lstm_seq as seq_lib

    cfg = MOBIRNN_LSTM
    B = 2
    p_width = max(cfg.input_dim, cfg.hidden)
    params = lstm.init_params(jax.random.PRNGKey(0), cfg)
    for T in (128, 512, 1024, 2048):
        x = jax.random.normal(jax.random.PRNGKey(1), (B, T, cfg.input_dim))
        labels = jnp.zeros((B,), jnp.int32)
        n_fwd = count_kernel_dispatches(jax.make_jaxpr(
            lambda p, x: lstm.forward_fused_seq_q8(
                p, x, cfg, vmem_budget=STREAM_BUDGET))(params, x))
        n_train = count_train_dispatches(
            lambda p: lstm.loss_fn(
                p, x, labels, cfg,
                forward=lambda p, x, cfg: lstm.forward_fused_seq_q8(
                    p, x, cfg, vmem_budget=STREAM_BUDGET)),
            params)
        row(f"quant/dispatch_fused_seq_q8_T{T}", float(n_fwd),
            f"pallas_calls={n_fwd} (O(1) in T)")
        row(f"quant/train_dispatch_fused_seq_q8_T{T}", float(n_train),
            f"pallas_calls={n_train} (1 fwd + 1 bwd, O(1) in T)")
        for mode in ("fwd", "bwd"):
            f32 = seq_lib.choose_batch_block(
                B, T, cfg.n_layers, p_width, cfg.hidden,
                vmem_budget=STREAM_BUDGET, mode=mode)
            q8 = seq_lib.choose_batch_block(
                B, T, cfg.n_layers, p_width, cfg.hidden,
                vmem_budget=STREAM_BUDGET, mode=mode, quantized=True)
            parts = [f"f32_blocks={tuple(f32) if f32 else None}",
                     f"q8_blocks={tuple(q8) if q8 else None}"]
            if f32 is not None and q8 is not None:
                cf = lstm_seq_stream_costs(
                    T, cfg.n_layers, p_width, cfg.hidden, B, f32.block_b,
                    f32.time_chunk, mode=mode)
                cq = lstm_seq_stream_costs(
                    T, cfg.n_layers, p_width, cfg.hidden, B, q8.block_b,
                    q8.time_chunk, mode=mode, quantized=True)
                parts.append(f"streamed_f32={cf['hbm_bytes']:.0f}B")
                parts.append(f"streamed_q8={cq['hbm_bytes']:.0f}B"
                             f"({cq['hbm_bytes'] / cf['hbm_bytes']:.2f}x)")
                saved = float(cq["hbm_bytes"])
            else:
                saved = 0.0
            row(f"quant/budget_{mode}_T{T}", saved, ",".join(parts))


def bench_quant_smoke() -> None:
    """CI smoke (fast job): the q8 acceptance criteria, executed.

    Asserts (a) the quantization-aware table returns a strictly-no-finer
    tiling than f32 at the mobile-class budget, (b) the q8 plan is 1 fwd /
    2 train dispatches at a long T, (c) the executed kernels agree with the
    dequantize oracle within fp rounding and with the f32 sequential plan
    within the documented int8 error band, and (d) straight-through
    training grads are finite.
    """
    import numpy as np

    from repro.analysis import count_kernel_dispatches, count_train_dispatches
    from repro.kernels import lstm_seq as seq_lib
    from repro.kernels import ref
    from repro.partitioning import split

    cfg = MOBIRNN_LSTM
    B, T = 2, 512
    p_width = max(cfg.input_dim, cfg.hidden)
    # no-finer-tiling acceptance across the fig2 T sweep, both modes
    for T_chk in (32, 128, 512, 1024, 2048):
        for mode in ("fwd", "bwd"):
            f32 = seq_lib.choose_batch_block(
                B, T_chk, cfg.n_layers, p_width, cfg.hidden,
                vmem_budget=STREAM_BUDGET, mode=mode)
            q8 = seq_lib.choose_batch_block(
                B, T_chk, cfg.n_layers, p_width, cfg.hidden,
                vmem_budget=STREAM_BUDGET, mode=mode, quantized=True)
            assert q8 is not None, (T_chk, mode)
            if f32 is not None:
                assert q8.block_b >= f32.block_b, (T_chk, mode, f32, q8)
                assert q8.time_chunk is None or (
                    f32.time_chunk is not None
                    and q8.time_chunk >= f32.time_chunk), (T_chk, mode,
                                                          f32, q8)

    params = lstm.init_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, cfg.input_dim))
    labels = jnp.zeros((B,), jnp.int32)

    def fwd(p, x, cfg):
        return lstm.forward_fused_seq_q8(p, x, cfg,
                                         vmem_budget=STREAM_BUDGET)

    n_fwd = count_kernel_dispatches(jax.make_jaxpr(
        lambda p, x: fwd(p, x, cfg))(params, x))
    n_train = count_train_dispatches(
        lambda p: lstm.loss_fn(p, x, labels, cfg, forward=fwd), params)
    assert n_fwd == 1, f"q8 forward fell back: {n_fwd} dispatches"
    assert n_train == 2, f"q8 backward fell back: {n_train} dispatches"

    # executed kernels vs the dequantize oracle (fp-rounding band) ...
    values, _ = split(params)
    w_stack, b_stack, pw = seq_lib.stack_params(values["layers"], cfg.hidden)
    xp = seq_lib.pad_input(x, pw)
    wq, scales = ref.quantize_q8(w_stack)
    want_c, want_h = ref.lstm_seq_q8(wq, scales, b_stack, xp)
    got_c, got_h = seq_lib.lstm_seq_q8(w_stack, b_stack, xp)
    np.testing.assert_allclose(np.asarray(got_h), np.asarray(want_h),
                               rtol=1e-4, atol=1e-5)
    # ... and the full plan vs the f32 sequential within the int8 band
    want = lstm.forward_sequential(params, x, cfg)
    got = fwd(params, x, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-2, atol=5e-2)
    _, grads = jax.value_and_grad(
        lambda p: lstm.loss_fn(p, x, labels, cfg, forward=fwd))(params)
    assert all(bool(jnp.all(jnp.isfinite(g)))
               for g in jax.tree.leaves(grads))
    row("quant_smoke/long_T_q8", float(T),
        f"fwd_dispatches={n_fwd},train_dispatches={n_train},"
        f"budget={STREAM_BUDGET}")


def bench_rwkv_rows() -> None:
    """rwkv/* rows: the rwkv6 family's chunked_scan plan holds its
    registered dispatch contract on the fig2 T sweep — 1 forward / 2 train
    Pallas dispatches at every T (the names contain "dispatch", so the
    regression guard fails CI on any silent oracle-replay fallback), plus
    the O(T/C) grid-step rows (count_pallas_grid_steps: BH * ceil(T/C),
    the sequential work a dispatch count cannot see) and the chunk the
    VMEM table picks at the mobile-class budget."""
    import math

    from repro.analysis import (count_kernel_dispatches,
                                count_pallas_grid_steps,
                                count_train_dispatches)
    from repro.core import plans
    from repro.kernels import wkv6 as wkv6_lib

    B, H, dk, dv, chunk = 2, 2, 8, 8, 32
    fam = plans.get_family("rwkv6")
    for T in (128, 512, 2048):
        case = plans.Case(f"bench_T{T}", (B, T, H, dk, dv, chunk))
        args, _ = fam.make_inputs(case, "float32")
        jx = jax.make_jaxpr(
            lambda *a: plans.RWKV_PLANS["chunked_scan"](*a, chunk=chunk))(
                *args)
        n_fwd = count_kernel_dispatches(jx)
        steps = count_pallas_grid_steps(jx)

        def loss(*a):
            out, s = plans.RWKV_PLANS["chunked_scan"](*a, chunk=chunk)
            return jnp.sum(out) + jnp.sum(s)

        n_train = count_train_dispatches(loss, *args)
        jx2 = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0,)))(*args)
        t_steps = count_pallas_grid_steps(jx2)
        want = B * H * math.ceil(T / chunk)
        row(f"rwkv/dispatch_chunked_scan_T{T}", float(n_fwd),
            f"pallas_calls={n_fwd} (O(1) in T)")
        row(f"rwkv/train_dispatch_chunked_scan_T{T}", float(n_train),
            f"pallas_calls={n_train} (1 traj fwd + 1 reverse sweep)")
        row(f"rwkv/grid_dispatch_steps_T{T}", float(steps),
            f"grid_steps={steps} (BH*ceil(T/C)={want})")
        row(f"rwkv/train_grid_dispatch_steps_T{T}", float(t_steps),
            f"grid_steps={t_steps} (2x fwd)")
        for mode in ("fwd", "bwd"):
            blocks = wkv6_lib.choose_blocks(
                1, T, dk, dv, target=chunk, vmem_budget=STREAM_BUDGET,
                mode=mode)
            row(f"rwkv/chunk_{mode}_T{T}",
                float(blocks.chunk if blocks else 0),
                f"chosen={tuple(blocks) if blocks else None}"
                f",budget={STREAM_BUDGET}")


def bench_rwkv_smoke() -> None:
    """CI smoke (fast job): the rwkv6 registry acceptance, executed.

    Asserts (a) the chunked_scan plan agrees with the stepwise oracle —
    values AND gradients — at a dividing and a NON-dividing T, (b) its
    dispatch counts match the PlanSpec (1 fwd / 2 train: no silent
    oracle-replay backward), (c) the chunk table is viable at the
    mobile-class budget and halves rather than vanishing under pressure,
    and (d) the double-buffered streamed windows are exact: a bh-tiled
    run (bh_tile > 1, non-dividing BH tail included) is bit-identical to
    the bh_tile=1 sweep, and the joint (chunk, bh_tile) table picks a
    real point at the mobile-class budget.
    """
    import functools

    import numpy as np

    from repro.analysis import count_kernel_dispatches, count_train_dispatches
    from repro.core import plans
    from repro.kernels import wkv6 as wkv6_lib

    fam = plans.get_family("rwkv6")
    spec = fam.plans["chunked_scan"]
    for label, T in (("div", 64), ("nondiv", 61)):
        case = plans.Case(f"smoke_{label}", (2, T, 2, 8, 8, 16))
        inputs = fam.make_inputs(case, "float32")
        got = fam.apply("chunked_scan", inputs)
        want = fam.apply(fam.oracle, inputs)
        for a, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                       **fam.tol("chunked_scan", "float32"))
        gg = fam.grads("chunked_scan", inputs)
        gw = fam.grads(fam.oracle, inputs)
        for a, w in zip(jax.tree.leaves(gg), jax.tree.leaves(gw)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(w),
                **fam.grad_tol("chunked_scan", "float32"))
        (args, chunk) = inputs
        n_fwd = count_kernel_dispatches(jax.make_jaxpr(
            lambda *a: plans.RWKV_PLANS["chunked_scan"](*a, chunk=chunk))(
                *args))

        def loss(*a):
            out, s = plans.RWKV_PLANS["chunked_scan"](*a, chunk=chunk)
            return jnp.sum(out) + jnp.sum(s)

        n_train = count_train_dispatches(loss, *args)
        assert n_fwd == spec.fwd_dispatches, \
            f"rwkv forward fell back at T={T}: {n_fwd} dispatches"
        assert n_train == spec.train_dispatches, \
            f"rwkv backward fell back at T={T}: {n_train} dispatches"

    assert plans.rwkv_viability(2048, 64, 64,
                                vmem_budget=STREAM_BUDGET)("chunked_scan")
    full = wkv6_lib.choose_blocks(1, 2048, 64, 64, target=32,
                                  vmem_budget=STREAM_BUDGET)
    assert full is not None
    tight = wkv6_lib.choose_blocks(
        1, 2048, 64, 64, target=32,
        vmem_budget=wkv6_lib.working_set_bytes(2048, 64, 64, full.chunk) - 1)
    assert tight is not None
    assert tight.chunk < full.chunk, (full, tight)   # halves, not vanishes
    row("rwkv_smoke/chunked_scan", float(full.chunk),
        f"fwd_dispatches=1,train_dispatches=2,chunk={full.chunk},"
        f"budget={STREAM_BUDGET}")

    # (d) streamed windows: bh-tiled sweep (non-dividing BH=B*H=3, tail
    # row masked against the shared f32 state scratch) is bit-identical
    # to the bh_tile=1 sweep of the same jitted kernel
    case = plans.Case("smoke_bh", (1, 23, 3, 8, 8, 8))    # BH=3, T=23
    (args, chunk) = fam.make_inputs(case, "float32")
    run = jax.jit(functools.partial(
        plans.RWKV_PLANS["chunked_scan"], chunk=chunk),
        static_argnames=("bh_tile",))
    base_out, base_s = run(*args, bh_tile=1)
    for bt in (2, 3):
        out, s = run(*args, bh_tile=bt)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(base_out))
        np.testing.assert_array_equal(np.asarray(s), np.asarray(base_s))
    joint = wkv6_lib.choose_blocks(8, 2048, 64, 64, target=32,
                                   vmem_budget=STREAM_BUDGET)
    assert joint is not None and joint.bh_tile >= 1
    row("rwkv_smoke/streamed_windows", float(joint.bh_tile),
        f"bitwise_bh_tiles=(1,2,3),BH=3,T=23,joint={tuple(joint)},"
        f"budget={STREAM_BUDGET}")


def bench_mamba_rows() -> None:
    """mamba/* rows: the mamba family's fused_scan plan holds its
    registered dispatch contract on the fig2 T sweep — 1 forward / 2
    train Pallas dispatches at every T (the names contain "dispatch", so
    the regression guard fails CI on any silent scan-oracle fallback),
    plus the O(T/C) grid-step rows and the (block_b, chunk) the VMEM
    table picks at the mobile-class budget."""
    import math

    from repro.analysis import (count_kernel_dispatches,
                                count_pallas_grid_steps,
                                count_train_dispatches)
    from repro.core import plans
    from repro.kernels import mamba_scan as ms_lib

    B, di, ds, chunk, bm = 2, 8, 4, 32, 2
    fam = plans.get_family("mamba")
    for T in (128, 512, 2048):
        case = plans.Case(f"bench_T{T}", (B, T, di, ds, chunk, bm))
        args, _, _ = fam.make_inputs(case, "float32")
        jx = jax.make_jaxpr(
            lambda *a: plans.MAMBA_PLANS["fused_scan"](
                *a, chunk=chunk, block_b=bm))(*args)
        n_fwd = count_kernel_dispatches(jx)
        steps = count_pallas_grid_steps(jx)

        def loss(*a):
            y, h = plans.MAMBA_PLANS["fused_scan"](*a, chunk=chunk,
                                                   block_b=bm)
            return jnp.sum(y) + jnp.sum(h)

        n_train = count_train_dispatches(loss, *args)
        jx2 = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0,)))(*args)
        t_steps = count_pallas_grid_steps(jx2)
        want = math.ceil(B / bm) * math.ceil(T / chunk)
        row(f"mamba/dispatch_fused_scan_T{T}", float(n_fwd),
            f"pallas_calls={n_fwd} (O(1) in T)")
        row(f"mamba/train_dispatch_fused_scan_T{T}", float(n_train),
            f"pallas_calls={n_train} (1 traj fwd + 1 reverse sweep)")
        row(f"mamba/grid_dispatch_steps_T{T}", float(steps),
            f"grid_steps={steps} (ceil(B/bm)*ceil(T/C)={want})")
        row(f"mamba/train_grid_dispatch_steps_T{T}", float(t_steps),
            f"grid_steps={t_steps} (2x fwd)")
        for mode in ("fwd", "bwd"):
            blocks = ms_lib.choose_blocks(
                B, T, di, ds, vmem_budget=STREAM_BUDGET, mode=mode)
            row(f"mamba/blocks_{mode}_T{T}",
                float(blocks.chunk if blocks else 0),
                f"chosen={tuple(blocks) if blocks else None}"
                f",budget={STREAM_BUDGET}")


def bench_mamba_smoke() -> None:
    """CI smoke (fast job): the mamba registry acceptance, executed.

    Asserts (a) the fused_scan plan agrees with the lax.scan oracle —
    values AND gradients — at a dividing and a NON-dividing T (identity
    zero-pad) and a non-dividing batch tile, (b) its dispatch counts
    match the PlanSpec (1 fwd / 2 train: no silent scan-replay
    backward), and (c) the joint (block_b, chunk) table is viable at the
    mobile-class budget and refines rather than vanishing under pressure.
    """
    import numpy as np

    from repro.analysis import count_kernel_dispatches, count_train_dispatches
    from repro.core import plans
    from repro.kernels import mamba_scan as ms_lib

    fam = plans.get_family("mamba")
    spec = fam.plans["fused_scan"]
    for label, (B, T, bm) in (("div", (2, 64, 2)), ("nondiv", (3, 61, 2))):
        case = plans.Case(f"smoke_{label}", (B, T, 8, 4, 16, bm))
        inputs = fam.make_inputs(case, "float32")
        got = fam.apply("fused_scan", inputs)
        want = fam.apply(fam.oracle, inputs)
        for a, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                       **fam.tol("fused_scan", "float32"))
        gg = fam.grads("fused_scan", inputs)
        gw = fam.grads(fam.oracle, inputs)
        for a, w in zip(jax.tree.leaves(gg), jax.tree.leaves(gw)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(w),
                **fam.grad_tol("fused_scan", "float32"))
        (args, chunk, block_b) = inputs
        n_fwd = count_kernel_dispatches(jax.make_jaxpr(
            lambda *a: plans.MAMBA_PLANS["fused_scan"](
                *a, chunk=chunk, block_b=block_b))(*args))

        def loss(*a):
            y, h = plans.MAMBA_PLANS["fused_scan"](*a, chunk=chunk,
                                                   block_b=block_b)
            return jnp.sum(y) + jnp.sum(h)

        n_train = count_train_dispatches(loss, *args)
        assert n_fwd == spec.fwd_dispatches, \
            f"mamba forward fell back at T={T}: {n_fwd} dispatches"
        assert n_train == spec.train_dispatches, \
            f"mamba backward fell back at T={T}: {n_train} dispatches"

    assert plans.mamba_viability(4, 2048, 64, 16,
                                 vmem_budget=STREAM_BUDGET)("fused_scan")
    full = ms_lib.choose_blocks(4, 2048, 64, 16,
                                vmem_budget=STREAM_BUDGET)
    assert full is not None
    ws = ms_lib.working_set_bytes(2048, 64, 16, full.block_b, full.chunk)
    tight = ms_lib.choose_blocks(4, 2048, 64, 16, vmem_budget=ws - 1)
    assert tight is not None
    assert tuple(tight) != tuple(full), (full, tight)  # refines, not gone
    row("mamba_smoke/fused_scan", float(full.chunk),
        f"fwd_dispatches=1,train_dispatches=2,blocks={tuple(full)},"
        f"budget={STREAM_BUDGET}")


def bench_fig4_speedup() -> None:
    cfg = MOBIRNN_LSTM
    in_dim = cfg.input_dim + cfg.hidden
    best = fz.best_cols_per_unit(fz.MOBILE_GPU, in_dim, 4 * cfg.hidden)
    t_gpu = fz.factorize_gate(fz.MOBILE_GPU, in_dim, 4 * cfg.hidden, best)
    t_cpu = fz.factorize_gate(fz.MOBILE_CPU1, in_dim, 4 * cfg.hidden,
                              4 * cfg.hidden)
    row("fig4/model_mobirnn_speedup", t_gpu * 1e6,
        f"cpu/gpu={t_cpu / t_gpu:.2f}x (paper: 3.93x on Nexus5)")


def bench_fig5_complexity() -> None:
    for hidden in (32, 64, 128, 256):
        for layers in (1, 2, 3):
            cfg = MOBIRNN_LSTM.with_complexity(hidden, layers)
            in_dim = cfg.input_dim + hidden
            best = fz.best_cols_per_unit(fz.MOBILE_GPU, in_dim, 4 * hidden)
            t_gpu = layers * fz.factorize_gate(fz.MOBILE_GPU, in_dim,
                                               4 * hidden, best)
            t_cpu = layers * fz.factorize_gate(fz.MOBILE_CPU1, in_dim,
                                               4 * hidden, 4 * hidden)
            row(f"fig5/model_h{hidden}_l{layers}", t_gpu * 1e6,
                f"speedup={t_cpu / t_gpu:.2f}x")


def bench_fig6_multithread() -> None:
    cfg = MOBIRNN_LSTM
    in_dim = cfg.input_dim + cfg.hidden
    best_gpu = fz.best_cols_per_unit(fz.MOBILE_GPU, in_dim, 4 * cfg.hidden)
    t_gpu = fz.factorize_gate(fz.MOBILE_GPU, in_dim, 4 * cfg.hidden,
                              best_gpu)
    best_cpu = fz.best_cols_per_unit(fz.MOBILE_CPU4, in_dim, 4 * cfg.hidden)
    t_mt = fz.factorize_gate(fz.MOBILE_CPU4, in_dim, 4 * cfg.hidden,
                             best_cpu)
    row("fig6/model_multithread_cpu", t_mt * 1e6,
        f"mt_cpu_gets={t_gpu / t_mt:.0%} of gpu perf (paper: >=70%)")


def bench_train_step() -> None:
    """Train-step wall time per execution plan — the training story the
    fused backward kernel unlocks: with ``fused_seq`` the whole
    ``value_and_grad`` is 2 Pallas dispatches instead of an O(T*L) oracle
    replay.  Viability of the fused plan's BACKWARD working set is checked
    via plan_viability(train=True) and noted in the derived column."""
    from repro.optim import AdamW

    cfg = MOBIRNN_LSTM.with_complexity(32, 2)
    B, T = 8, 32
    params = lstm.init_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, cfg.input_dim))
    labels = jnp.zeros((B,), jnp.int32)
    opt = AdamW(lr=1e-3)
    viable = lstm.plan_viability(cfg, B, T, train=True)
    base = None
    for name, fwd in lstm.FORWARD_PLANS.items():
        state = opt.init(params)

        @jax.jit
        def step(p, s, fwd=fwd):
            loss, grads = jax.value_and_grad(lstm.loss_fn)(
                p, x, labels, cfg, forward=fwd)
            p, s, _ = opt.update(grads, s, p)
            return p, s, loss

        t = timeit(step, params, state, repeats=2)
        base = base or t
        note = f"speedup_vs_sequential={base / t:.2f}x"
        if name in ("fused_seq", "fused_seq_q8"):
            note += f",bwd_viable={viable(name)}"
        row(f"train/step_{name}_B{B}_T{T}", t, note)


def bench_fig7_load() -> None:
    cfg = MOBIRNN_LSTM
    params = lstm.init_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, cfg.seq_len,
                                                  cfg.input_dim))
    accel = jax.jit(lambda p, x: lstm.forward_wavefront(p, x, cfg))
    accel_seq = jax.jit(lambda p, x: lstm.forward_fused_seq(p, x, cfg))
    accel_seq_q8 = jax.jit(lambda p, x: lstm.forward_fused_seq_q8(p, x, cfg))
    cpu = jax.jit(lambda p, x: lstm.forward_sequential(p, x, cfg))
    sensor = SyntheticLoadSensor(0.0)
    # VMEM-model viability: never calibrate/choose the sequence-resident
    # plan when choose_batch_block says it cannot fit (it would silently
    # benchmark its fused_cell fallback under the wrong name).  This is the
    # INFERENCE dispatch bench, so the forward working set (train=False) is
    # the right gate; a train-time scheduler passes train=True to size the
    # ~3x backward working set instead (see bench_train_step).  The q8 plan
    # is gated by the quantization-aware table (4x smaller weight term), so
    # the per-tick choice keeps a fused option under budgets that filter
    # the f32 plan out.
    sched = Scheduler(sensor, viable=lstm.plan_viability(
        cfg, 1, cfg.seq_len, seq_plan_names=("accel_seq",),
        q8_plan_names=("accel_seq_q8",), train=False))
    sched.register(Plan("accel", accel, shared=True, sensitivity=1.0))
    sched.register(Plan("accel_seq", accel_seq, shared=True,
                        sensitivity=1.0))
    sched.register(Plan("accel_seq_q8", accel_seq_q8, shared=True,
                        sensitivity=1.0))
    sched.register(Plan("cpu", cpu, shared=False))
    sched.calibrate(params, x)
    for load in (0.1, 0.3, 0.5, 0.7, 0.9):
        sensor.value = load
        d = sched.choose()
        pred = d.predicted_s[d.plan]
        row(f"fig7/load_{load:.1f}", pred * 1e6,
            f"dispatch={d.plan}")
    crossings = [d.plan for d in sched.decisions]
    row("fig7/crossover", 0.0, f"sequence={'>'.join(crossings)}")


# ---------------------------------------------------------------------------
def bench_serving() -> None:
    """Wave vs slot engine on a RAGGED workload: mixed prompt lengths and an
    8x ``max_new_tokens`` spread.  The wave engine pads every request in a
    wave to the longest prompt and the longest token budget, so short
    requests burn dead ticks; the slot engine retires each lane the step it
    finishes and admits the next queued request — same model, same plans,
    higher tokens/sec.  Also asserts the slot engine's zero-allocation
    invariant (StatePool stats) after warmup."""
    import dataclasses

    from repro.configs import get_arch
    from repro.models import registry
    from repro.partitioning import split
    from repro.serving import Engine, EngineConfig, Request, SlotEngine

    cfg = dataclasses.replace(
        get_arch("qwen2-0.5b").reduced(), n_layers=2, d_model=64,
        n_heads=2, n_kv_heads=1, head_dim=16, d_ff=128, vocab=256)
    model = registry.build(cfg)
    params, _ = split(model.init(jax.random.PRNGKey(0)))

    rng = np.random.default_rng(0)
    lens = [4, 12, 6, 16, 8, 4, 12, 6, 16, 8, 4, 12]
    news = [2, 32, 4, 24, 32, 2, 24, 4, 32, 2, 4, 24]    # 16x spread
    prompts = [rng.integers(0, cfg.vocab, (l,)).astype(np.int32)
               for l in lens]

    def reqs():
        return [Request(i, p, max_new_tokens=n)
                for i, (p, n) in enumerate(zip(prompts, news))]

    n_tok = sum(news)
    wave = Engine(model, params, config=EngineConfig(
        n_slots=4, max_seq=64, pool_capacity=1))
    wave.serve(reqs())                                   # compile/warmup
    t0 = time.perf_counter()
    wave.serve(reqs())
    t_wave = time.perf_counter() - t0
    row("serving/wave_ragged", t_wave * 1e6 / n_tok,
        f"tok_per_s={n_tok / t_wave:.1f}")

    slot = SlotEngine(model, params, config=EngineConfig(
        n_slots=4, max_seq=64, queue_capacity=8))
    slot.serve(reqs())                                   # compile/warmup
    import gc

    gc.collect()
    live0 = len(jax.live_arrays())
    t0 = time.perf_counter()
    slot.serve(reqs())
    t_slot = time.perf_counter() - t0
    gc.collect()
    live1 = len(jax.live_arrays())
    # the REAL zero-allocation invariant: a warm serve leaves the live
    # device-buffer population unchanged (pool buffers reset in place via
    # donation; pool stats corroborate that none were rebuilt)
    assert live1 <= live0, (live0, live1)
    assert (slot.pool.stats.buffers_built,
            slot._scratch_pool.stats.buffers_built) == (1, 1), \
        "slot engine rebuilt pool buffers on the serving path"
    row("serving/slot_ragged", t_slot * 1e6 / n_tok,
        f"tok_per_s={n_tok / t_slot:.1f},speedup_vs_wave="
        f"{t_wave / t_slot:.2f}x,live_buffers_delta={live1 - live0}")

    # per-request latency distributions from the engine's always-on obs
    # metrics (accumulated over warmup + timed serves): TTFT is
    # submit->first-token-on-host, TBT the per-lane gap between decode
    # tokens — the serving numbers MobiRNN-style tuning should move
    ttft = slot.metrics.histogram("serving/ttft_s").summary()
    tbt = slot.metrics.histogram("serving/tbt_s").summary()
    row("serving/slot_ttft_p50", ttft["p50"] * 1e6,
        f"p99_us={ttft['p99'] * 1e6:.1f},n={ttft['count']}")
    row("serving/slot_tbt_p50", tbt["p50"] * 1e6,
        f"p99_us={tbt['p99'] * 1e6:.1f},n={tbt['count']}")

    # TTFT under contention (ISSUE 10 headline): short requests queued
    # behind long-prompt adversaries.  Whole-prompt admission stalls the
    # tick loop for each adversary's full prefill; chunked admission
    # interleaves, bounding any single stall at ~one chunk.  NOTE the
    # wall-clock rows track a tradeoff, not a one-way win: on this tiny
    # model a whole 48-token prefill is ONE sub-ms dispatch, so the
    # per-chunk dispatch overhead chunking adds can exceed the stall it
    # removes — the granularity bound itself is asserted structurally in
    # --prefill-smoke, where it is model-size-independent.
    adv_lens = [48, 4, 48, 4, 48, 4, 4, 4]               # adversary, short, ...
    adv_news = [4, 8, 4, 8, 4, 8, 8, 8]
    adv_prompts = [rng.integers(0, cfg.vocab, (l,)).astype(np.int32)
                   for l in adv_lens]

    def adv_reqs():
        return [Request(i, p, max_new_tokens=n)
                for i, (p, n) in enumerate(zip(adv_prompts, adv_news))]

    short_uids = {i for i, l in enumerate(adv_lens) if l == 4}
    for label, config in (
            ("whole", EngineConfig(n_slots=2, max_seq=64, queue_capacity=8)),
            ("chunked", EngineConfig(n_slots=2, max_seq=64, queue_capacity=8,
                                     prefill_chunk_len=8, prefill_lanes=2))):
        eng = SlotEngine(model, params, config=config)
        eng.serve(adv_reqs())                            # compile/warmup
        first_tok: dict[int, float] = {}

        def on_token(ev, first_tok=first_tok):
            if ev.token is not None and ev.uid not in first_tok:
                first_tok[ev.uid] = time.perf_counter()

        t0 = time.perf_counter()
        eng.serve(adv_reqs(), on_token=on_token)
        # submit-to-first-token for the SHORT requests: includes the queue
        # wait behind adversary prefills, the number chunking improves
        short_ttfts = sorted(first_tok[u] - t0 for u in short_uids)
        p50 = short_ttfts[len(short_ttfts) // 2]
        row(f"serving/adversary_short_ttft_p50_{label}", p50 * 1e6,
            f"p99_us={short_ttfts[-1] * 1e6:.1f},n={len(short_ttfts)},"
            f"adversary_prompt=48")


def bench_prefill_smoke() -> None:
    """CI smoke (fast job): the ISSUE 10 chunked-prefill acceptance,
    executed.

    Asserts (a) chunked admission is greedy-token-identical to
    whole-prompt admission on a tiny dense AND a tiny rwkv model; (b) the
    compiled-shape contract — exactly ONE prefill-chunk executable per
    distinct segment length used (the schedule's shape set is {C} plus
    descending powers of two for the remainder); (c) the TTFT-adversary
    headline, structurally: short requests queued alongside a long-prompt
    adversary produce their first tokens BEFORE the adversary's first —
    chunked admission stalls the tick loop by at most one chunk, never an
    entire foreign prefill; and (d) the zero-allocation invariant through
    chunked admission (scratch pool built once at lane capacity, no lane
    leaks).
    """
    import dataclasses

    from repro.configs import get_arch
    from repro.models import registry
    from repro.obs import trace as trace_lib
    from repro.partitioning import split
    from repro.serving import (EngineConfig, Request, SlotEngine,
                               chunk_schedule)

    rng = np.random.default_rng(0)
    lens, news = [5, 13, 3, 9], [4, 3, 5, 2]
    dense = None
    for arch in ("qwen2-0.5b", "rwkv6-3b"):
        cfg = get_arch(arch).reduced()
        if arch == "qwen2-0.5b":
            cfg = dataclasses.replace(
                cfg, n_layers=2, d_model=64, n_heads=2, n_kv_heads=1,
                head_dim=16, d_ff=128, vocab=128)
        model = registry.build(cfg)
        params, _ = split(model.init(jax.random.PRNGKey(0)))
        if arch == "qwen2-0.5b":
            dense = (cfg, model, params)
        prompts = [rng.integers(0, cfg.vocab, (l,)).astype(np.int32)
                   for l in lens]

        def reqs():
            return [Request(i, p, max_new_tokens=n)
                    for i, (p, n) in enumerate(zip(prompts, news))]

        whole = SlotEngine(model, params, config=EngineConfig(
            n_slots=2, max_seq=32)).serve(reqs())
        eng = SlotEngine(model, params, config=EngineConfig(
            n_slots=2, max_seq=32, prefill_chunk_len=4, prefill_lanes=2))
        chunked = eng.serve(reqs())
        for w, g in zip(whole, chunked):
            assert np.array_equal(w.tokens, g.tokens), \
                f"{arch} uid {w.uid}: chunked != whole-prompt tokens"
        segs = set()
        for l in lens:
            segs.update(chunk_schedule(l, 4))
        n_exec = eng._prefill_chunk._cache_size()
        assert n_exec == len(segs), \
            f"{arch}: {n_exec} prefill executables for shapes {sorted(segs)}"
        sp = eng._scratch_pool.stats
        assert sp.buffers_built == sp.capacity == 2 and sp.outstanding == 0, \
            f"{arch}: scratch pool leaked through chunked admission: {sp}"
        row(f"prefill_smoke/{arch}", float(n_exec),
            f"chunk_shapes={sorted(segs)},identity=ok,"
            f"buffers_built={sp.buffers_built}")

    # (c) TTFT under an adversary, deterministic/structural: a 24-token
    # prompt (6 chunks of 4) competes with short 4-token prompts.  Every
    # short request's FIRST token must land before the adversary's first
    # — whole-prompt admission would stall the loop for the full foreign
    # prefill instead.  The trace corroborates: one serve/prefill_chunk
    # per scheduled segment.
    cfg, model, params = dense
    short_prompts = [rng.integers(0, cfg.vocab, (4,)).astype(np.int32)
                     for _ in range(2)]
    adversary = rng.integers(0, cfg.vocab, (24,)).astype(np.int32)

    def adv_reqs():
        return [Request(0, short_prompts[0], max_new_tokens=8),
                Request(1, adversary, max_new_tokens=2),
                Request(2, short_prompts[1], max_new_tokens=8)]

    eng = SlotEngine(model, params, config=EngineConfig(
        n_slots=3, max_seq=32, queue_capacity=4,
        prefill_chunk_len=4, prefill_lanes=2))
    sink = trace_lib.ListSink()
    old = trace_lib.set_tracer(trace_lib.Tracer(sink))
    try:
        events = []
        eng.serve(adv_reqs(), on_token=events.append)
    finally:
        trace_lib.set_tracer(old)
    uids = [ev.uid for ev in events if ev.token is not None]
    first_adv = uids.index(1)
    for short_uid in (0, 2):
        assert short_uid in uids[:first_adv], \
            f"short request {short_uid} starved behind the adversary prefill"
    n_chunk_events = sum(r["name"] == "serve/prefill_chunk"
                         for r in sink.records)
    want_chunks = (len(chunk_schedule(24, 4))
                   + 2 * len(chunk_schedule(4, 4)))
    assert n_chunk_events == want_chunks, (n_chunk_events, want_chunks)
    short_before = uids[:first_adv].count(0) + uids[:first_adv].count(2)
    row("prefill_smoke/adversary", float(short_before),
        f"short_tokens_before_adversary_first={short_before},"
        f"prefill_chunk_events={n_chunk_events}")


def bench_obs_smoke(trace_path: str = "BENCH_ci_obs_trace.jsonl",
                    profile_path: str = "BENCH_ci_obs_profile.json") -> None:
    """CI smoke (fast job): the ISSUE 7 observability acceptance, executed.

    Asserts (a) a traced SlotEngine run produces well-formed JSONL with
    per-tick spans (plan + tick latency), per-request TTFT admit events,
    nested sched/choose decisions, and the end-of-stream metrics summary
    (queue depth gauge, deadline-miss counter); (b) tracing changes NO
    tokens and keeps the zero-allocation invariant; (c) the measured
    profiler sweeps >= 2 viable tiling points for ALL THREE registered
    families (lstm's (block_b, time_chunk) surface, rwkv6's widened
    (bh_tile, chunk) surface, mamba's (block_b, chunk) surface), the
    profile round-trips through save/load, ``Scheduler.calibrate`` seeds
    base latencies from it, and the model-vs-measured report carries a
    finite ratio per point.  The trace and profile files are uploaded as
    CI artifacts next to the BENCH_ci_*.json rows.
    """
    import dataclasses

    from repro.configs import get_arch
    from repro.models import registry
    from repro.obs import profile as profile_lib
    from repro.obs import trace as trace_lib
    from repro.partitioning import split
    from repro.serving import EngineConfig, Request, SlotEngine

    cfg = dataclasses.replace(
        get_arch("qwen2-0.5b").reduced(), n_layers=2, d_model=64,
        n_heads=2, n_kv_heads=1, head_dim=16, d_ff=128, vocab=128)
    model = registry.build(cfg)
    params, _ = split(model.init(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, (l,)).astype(np.int32)
               for l in (5, 3, 7, 4, 6, 3)]
    news = [6, 4, 5, 6, 3, 4]

    def reqs():
        return [Request(i, p, max_new_tokens=n)
                for i, (p, n) in enumerate(zip(prompts, news))]

    # --- traced vs untraced serving: token-identical, zero-alloc --------
    plain = SlotEngine(model, params, config=EngineConfig(
        n_slots=2, max_seq=32))
    base = {r.uid: r.tokens.tolist() for r in plain.serve(reqs())}
    old = trace_lib.set_tracer(trace_lib.Tracer(trace_lib.JsonlSink(
        trace_path)))
    try:
        traced_eng = SlotEngine(model, params, config=EngineConfig(
            n_slots=2, max_seq=32))
        traced = {r.uid: r.tokens.tolist()
                  for r in traced_eng.serve(reqs())}
    finally:
        trace_lib.get_tracer().close()
        trace_lib.set_tracer(old)
    assert traced == base, "tracing changed greedy outputs"
    assert traced_eng.pool.stats.buffers_built == 1, \
        "traced serving run rebuilt pool buffers"

    events = trace_lib.read_jsonl(trace_path)
    assert events, "empty trace"
    ticks = [e for e in events if e["name"] == "serve/tick"]
    admits = [e for e in events if e["name"] == "serve/admit"]
    chooses = [e for e in events if e["name"] == "sched/choose"]
    summaries = [e for e in events if e["name"] == "serve/metrics"]
    assert ticks and all("plan" in e["attrs"] and "tick_s" in e["attrs"]
                         for e in ticks), "malformed serve/tick spans"
    assert len(admits) == len(news) and all(
        e["attrs"]["ttft_s"] > 0 for e in admits), "missing TTFT events"
    tick_ids = {e["span"] for e in ticks}
    prepare = {e["span"]: e["parent"] for e in events
               if e["name"] == "serve/tick/prepare"}
    assert chooses and all(prepare.get(e["parent"]) in tick_ids
                           for e in chooses), \
        "sched/choose not nested under serve/tick/prepare"
    assert summaries and "serving/deadline_miss" in \
        summaries[-1]["attrs"]["counters"], "missing metrics summary"
    row("obs_smoke/trace", float(len(events)),
        f"ticks={len(ticks)},admits={len(admits)},file={trace_path}")

    # --- measured profiler: all three families, save/load, calibrate ----
    prof = profile_lib.profile_families(
        ("lstm", "rwkv6", "mamba"), vmem_budget=STREAM_BUDGET, repeats=1,
        warmup=1, max_points=2,
        hook_kwargs={"lstm": {"batch": 2, "seq_len": 16},
                     "rwkv6": {"seq_len": 32, "n_bh": 2, "target": 8},
                     "mamba": {"batch": 2, "seq_len": 16, "d_inner": 8,
                               "d_state": 4}})
    for fam in ("lstm", "rwkv6", "mamba"):
        n = sum(p.family == fam for p in prof.points)
        assert n >= 2, f"profiler swept {n} < 2 points for {fam}"
    # the widened rwkv6 surface exposes the bh-tile axis, not just chunk
    rwkv_tiles = {p.point.get("bh_tile") for p in prof.points
                  if p.family == "rwkv6"}
    assert len(rwkv_tiles) >= 2, \
        f"rwkv6 profile points collapsed to one bh_tile: {rwkv_tiles}"
    prof.save(profile_path)
    prof2 = profile_lib.DeviceProfile.load(profile_path)
    assert prof2.to_json() == prof.to_json(), "profile did not round-trip"

    sched = Scheduler(SyntheticLoadSensor(0.0))
    sched.register(Plan("fused_seq", lambda: None))
    sched.register(Plan("chunked_scan", lambda: None))
    sched.register(Plan("fused_scan", lambda: None))
    sched.calibrate(profile=prof2.best_latencies())
    assert all(np.isfinite(p.base_latency_s)
               for p in sched.plans.values()), "profile seeding failed"

    report = profile_lib.model_vs_measured(prof2, threshold=3.0)
    assert len(report) == len(prof.points) and all(
        r["finite"] for r in report), "non-finite model-vs-measured ratio"
    worst = max(r["ratio"] for r in report)
    row("obs_smoke/profile", float(len(prof.points)),
        f"families=3,key={prof.key},max_ratio={worst:.3g},"
        f"file={profile_path}")


def bench_chaos_smoke(trace_path: str = "BENCH_ci_chaos_trace.jsonl",
                      faults_path: str = "BENCH_ci_chaos_faults.json"
                      ) -> None:
    """CI smoke (fast job): the ISSUE 9 fault-tolerance acceptance, executed.

    Drives a seeded FaultPlan (a NaN-poisoned lane, a failed prefill, a
    3-tick slow burst) through a SlotEngine with a retry budget and a
    degradation ladder, and asserts (a) every request terminates with a
    finish_reason from the closed set; (b) every request that finishes
    'length' — including the quarantined-then-retried and the
    prefill-faulted ones — carries tokens bit-identical to a fault-free
    run; (c) the zero-allocation invariant holds through quarantine and
    re-admission (pool and scratch buffers_built stay at capacity); (d)
    the watchdog's plan downshift and the shed decisions are visible in
    the JSONL trace (serve/fault, serve/quarantine, serve/shed,
    sched/degrade; post-degrade sched/choose picks the fallback plan).
    The fault schedule and the trace are written next to the other
    BENCH_ci_* artifacts so any failure replays exactly.
    """
    import dataclasses

    from repro.configs import get_arch
    from repro.models import registry
    from repro.obs import trace as trace_lib
    from repro.partitioning import split
    from repro.serving import (FINISH_REASONS, EngineConfig, FaultPlan,
                               FinishReason, LanePoison, PrefillFault,
                               Request, SlotEngine, SlowTick)
    from repro import steps as steps_lib

    cfg = dataclasses.replace(
        get_arch("qwen2-0.5b").reduced(), n_layers=2, d_model=64,
        n_heads=2, n_kv_heads=1, head_dim=16, d_ff=128, vocab=128)
    model = registry.build(cfg)
    params, _ = split(model.init(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(0)
    lens, news = (5, 9, 3, 7, 4, 6), (12, 12, 6, 12, 4, 4)
    prompts = [rng.integers(0, cfg.vocab, (l,)).astype(np.int32)
               for l in lens]

    def reqs(deadline=None):
        # uids 4-5 carry deadlines ~1000s out: trivially meetable on a
        # healthy engine, provably unmeetable once the slow burst drives
        # the tick EMA to ~1e6 s — the shed sweep's targets
        return [Request(i, p, max_new_tokens=n,
                        deadline_s=(None if deadline is None or i < 4
                                    else deadline + i))
                for i, (p, n) in enumerate(zip(prompts, news))]

    faults = FaultPlan(seed=0, faults=(
        LanePoison(tick=1, lane=0),
        PrefillFault(uid=2),
        SlowTick(tick=4, extra_s=1e6),
        SlowTick(tick=5, extra_s=1e6),
        SlowTick(tick=6, extra_s=1e6)))
    faults.save(faults_path)

    # fault-free reference: what every 'length' finisher must reproduce
    base_eng = SlotEngine(model, params, config=EngineConfig(
        n_slots=2, max_seq=64, queue_capacity=4))
    base = {r.uid: r.tokens.tolist()
            for r in base_eng.serve(reqs(base_eng.clock() + 1000.0))}

    old = trace_lib.set_tracer(trace_lib.Tracer(trace_lib.JsonlSink(
        trace_path)))
    try:
        eng = SlotEngine(
            model, params,
            config=EngineConfig(
                n_slots=2, max_seq=64, queue_capacity=4,
                faults=faults, retry_budget=1, tick_slo_s=50.0,
                slo_breach_ticks=3, slo_recover_ticks=99,
                ladder=["decode/base"]),
            extra_plans={"decode/fallback":
                         lambda p, c, b: steps_lib.decode_step(cfg, p, c, b)})
        chaos = {r.uid: r for r in eng.serve(reqs(eng.clock() + 1000.0))}
    finally:
        trace_lib.get_tracer().close()
        trace_lib.set_tracer(old)

    # (a) all terminate, closed set; (b) healthy-lane bit-identity
    assert set(chaos) == set(range(6)), sorted(chaos)
    assert all(r.finish_reason in FINISH_REASONS for r in chaos.values())
    reasons = {u: r.finish_reason for u, r in chaos.items()}
    for uid in (0, 1, 2, 3):
        assert reasons[uid] == FinishReason.LENGTH, reasons
        assert chaos[uid].tokens.tolist() == base[uid], \
            f"uid {uid} diverged from the fault-free run"
    for uid in (4, 5):
        assert reasons[uid] == FinishReason.SHED, reasons
    # (c) zero-alloc through quarantine + re-admission
    assert eng.pool.stats.buffers_built == 1
    assert eng._scratch_pool.stats.buffers_built == 1
    q = eng.metrics.counter("serving/quarantined").value
    rt = eng.metrics.counter("serving/retries").value
    sh = eng.metrics.counter("serving/shed").value
    assert q >= 1 and rt >= 1 and sh >= 1, (q, rt, sh)
    assert eng.scheduler.level == 1     # degraded, recovery disabled

    # (d) the chaos story is visible in the trace
    events = trace_lib.read_jsonl(trace_path)
    kinds = {e["attrs"]["kind"] for e in events
             if e["name"] == "serve/fault"}
    assert {"poison", "prefill", "slow"} <= kinds, kinds
    assert any(e["name"] == "serve/quarantine" for e in events)
    assert any(e["name"] == "serve/shed" for e in events)
    degrades = [e for e in events if e["name"] == "sched/degrade"]
    assert degrades, "watchdog never stepped the ladder"
    post = [e["attrs"]["plan"] for e in events
            if e["name"] == "sched/choose" and e["seq"] > degrades[0]["seq"]]
    assert post and set(post) == {"decode/fallback"}, \
        f"no downshift after sched/degrade: {post[:5]}"
    row("chaos_smoke/seeded_faults", float(len(events)),
        f"quarantined={q},retries={rt},shed={sh},reasons="
        f"{'|'.join(sorted(set(reasons.values())))},files={faults_path}"
        f"+{trace_path}")


def bench_kernels() -> None:
    from repro.kernels import ops, ref

    B, D, H = 8, 64, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    w = jax.random.normal(ks[0], (D + H, 4 * H)) * 0.1
    b = jnp.zeros((4 * H,))
    x, c, h = (jax.random.normal(k, (B, d)) for k, d in
               zip(ks[1:], (D, H, H)))
    row("kernel/lstm_cell_interpret",
        timeit(lambda: ops.lstm_cell(w, b, x, c, h), repeats=3), "")
    row("kernel/lstm_cell_ref",
        timeit(lambda: jax.jit(ref.lstm_cell)(w, b, x, c, h)), "oracle")

    BH, T, dk = 4, 128, 32
    r, k2, v = (jax.random.normal(kk, (BH, T, dk)) for kk in ks[:3])
    logw = -jnp.exp(jax.random.normal(ks[3], (BH, T, dk)))
    u = jax.random.normal(ks[4], (BH, dk))
    s0 = jnp.zeros((BH, dk, dk))
    row("kernel/wkv6_interpret",
        timeit(lambda: ops.wkv6(r, k2, v, logw, u, s0, chunk=32),
               repeats=2), "")

    B2, Hq, Hkv, S, dh = 4, 8, 2, 512, 64
    q = jax.random.normal(ks[0], (B2, Hq, dh))
    kc = jax.random.normal(ks[1], (B2, S, Hkv, dh))
    vc = jax.random.normal(ks[2], (B2, S, Hkv, dh))
    lens = jnp.full((B2,), S, jnp.int32)
    row("kernel/decode_attn_interpret",
        timeit(lambda: ops.decode_attn(q, kc, vc, lens), repeats=2), "")


def bench_wkv_chunks() -> None:
    """Empirical work-unit coarseness curve: the paper's Fig 2/3 effect
    measured on real hardware for the rwkv scan (chunk = unit size)."""
    from repro.models.rwkv import wkv_chunked

    B, S, Hh, dk = 2, 256, 4, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    r, k, v = (jax.random.normal(kk, (B, S, Hh, dk)) for kk in ks[:3])
    logw = -jnp.exp(jax.random.normal(ks[3], (B, S, Hh, dk)))
    u = jax.random.normal(ks[4], (Hh, dk))
    s0 = jnp.zeros((B, Hh, dk, dk))
    base = None
    for chunk in (1, 4, 16, 64):
        fn = jax.jit(lambda r, k, v, w, u, s, c=chunk: wkv_chunked(
            r, k, v, w, u, s, c))
        t = timeit(fn, r, k, v, logw, u, s0, repeats=3)
        base = base or t
        row(f"scan/wkv_chunk_{chunk}", t, f"speedup_vs_chunk1="
            f"{base / t:.2f}x")


def bench_moe_capacity() -> None:
    import dataclasses

    from repro.configs import get_arch
    from repro.models import moe as moe_lib
    from repro.partitioning import split

    base_cfg = get_arch("olmoe-1b-7b").reduced()
    x = jax.random.normal(jax.random.PRNGKey(1), (256, base_cfg.d_model))
    for cf in (0.5, 1.0, 1.25, 2.0):
        cfg = dataclasses.replace(
            base_cfg, moe=dataclasses.replace(base_cfg.moe,
                                              capacity_factor=cf))
        p, _ = split(moe_lib.init_moe(jax.random.PRNGKey(0), cfg,
                                      jnp.float32))
        fn = jax.jit(lambda p, x, c=cfg: moe_lib.apply_moe(p, x, c))
        t = timeit(fn, p, x, repeats=3)
        _, aux = fn(p, x)
        row(f"moe/capacity_{cf}", t,
            f"drop_frac={float(aux['moe_drop_frac']):.3f}")


def write_json(path: str) -> None:
    """Machine-readable benchmark rows (fig2 fwd+bwd dispatch counts,
    train-step wall time per plan, serving tokens/sec live in `derived`) so
    the perf trajectory is diffable across PRs."""
    import json

    with open(path, "w") as fh:
        json.dump([{"name": n, "us_per_call": us, "derived": d}
                   for n, us, d in ROWS], fh, indent=1)
    print(f"wrote {len(ROWS)} rows to {path}")


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--serving", action="store_true",
                    help="run only the serving throughput benchmark "
                         "(wave vs slot engine; the CI smoke invocation)")
    ap.add_argument("--train", action="store_true",
                    help="run only the per-plan train-step benchmark")
    ap.add_argument("--stream-smoke", action="store_true",
                    help="run only the long-T streaming smoke (asserts the "
                         "fused plan does NOT fall back past the "
                         "whole-T-resident budget; the CI fast-job "
                         "invocation)")
    ap.add_argument("--quant-smoke", action="store_true",
                    help="run only the int8-plan smoke (asserts 1 fwd / 2 "
                         "train dispatches for fused_seq_q8, oracle "
                         "agreement within the int8 error band, and the "
                         "no-finer q8 tiling at the mobile budget; the CI "
                         "fast-job invocation)")
    ap.add_argument("--rwkv-smoke", action="store_true",
                    help="run only the rwkv6 chunked-scan smoke (asserts "
                         "registry equivalence vs the stepwise oracle — "
                         "values and gradients, dividing and non-dividing "
                         "T — plus the 1 fwd / 2 train dispatch contract "
                         "and chunk-table viability at the mobile budget; "
                         "the CI fast-job invocation)")
    ap.add_argument("--mamba-smoke", action="store_true",
                    help="run only the mamba fused-scan smoke (asserts "
                         "registry equivalence vs the lax.scan oracle — "
                         "values and gradients, dividing and non-dividing "
                         "T and batch tile — plus the 1 fwd / 2 train "
                         "dispatch contract and (block_b, chunk) table "
                         "viability at the mobile budget; the CI fast-job "
                         "invocation)")
    ap.add_argument("--obs-smoke", action="store_true",
                    help="run only the observability smoke (traced serving "
                         "run: per-tick spans, TTFT, token identity, "
                         "zero-alloc; measured 2-point profiler sweep for "
                         "both families with save/load round-trip, "
                         "calibrate seeding and a finite model-vs-measured "
                         "ratio; the CI fast-job invocation — writes "
                         "BENCH_ci_obs_trace.jsonl + "
                         "BENCH_ci_obs_profile.json)")
    ap.add_argument("--prefill-smoke", action="store_true",
                    help="run only the chunked-prefill smoke (asserts "
                         "chunked-vs-whole-prompt greedy token identity on "
                         "dense AND rwkv, one compiled executable per "
                         "chunk segment length, short-request tokens "
                         "landing before a long-prompt adversary's first, "
                         "and the zero-alloc scratch-pool invariant; the "
                         "CI fast-job invocation)")
    ap.add_argument("--chaos-smoke", action="store_true",
                    help="run only the fault-tolerance smoke (seeded "
                         "FaultPlan through the SlotEngine: every request "
                         "terminates inside the closed finish_reason set, "
                         "healthy lanes bit-identical to the fault-free "
                         "run, zero-alloc through quarantine/re-admission, "
                         "ladder downshift + shed visible in the trace; "
                         "the CI fast-job invocation — writes "
                         "BENCH_ci_chaos_trace.jsonl + "
                         "BENCH_ci_chaos_faults.json)")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="enable structured tracing for the whole run and "
                         "write JSONL records (spans/events; see "
                         "ROADMAP §Observability) to PATH")
    ap.add_argument("--fig2", action="store_true",
                    help="run only the fig2 dispatch-count rows + the "
                         "quant/*, rwkv/* and mamba/* rows (the CI "
                         "dispatch-regression guard input — see "
                         "benchmarks/check_dispatch_regression.py)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write the rows as JSON (e.g. BENCH_PR4.json) "
                         "for cross-PR perf tracking")
    args = ap.parse_args()

    if args.trace:
        from repro.obs import trace as trace_lib

        trace_lib.configure(path=args.trace)

    print("name,us_per_call,derived")
    if args.serving:
        bench_serving()
    elif args.train:
        bench_train_step()
    elif args.stream_smoke:
        bench_stream_smoke()
    elif args.quant_smoke:
        bench_quant_smoke()
    elif args.rwkv_smoke:
        bench_rwkv_smoke()
    elif args.mamba_smoke:
        bench_mamba_smoke()
    elif args.obs_smoke:
        bench_obs_smoke()
    elif args.prefill_smoke:
        bench_prefill_smoke()
    elif args.chaos_smoke:
        bench_chaos_smoke()
    elif args.fig2:
        bench_fig2_dispatch_counts()
        bench_quant_rows()
        bench_rwkv_rows()
        bench_mamba_rows()
    else:
        bench_fig2_dispatch_counts()
        bench_quant_rows()
        bench_rwkv_rows()
        bench_chunk_sweep()
        bench_stream_smoke()
        bench_quant_smoke()
        bench_rwkv_smoke()
        bench_fig3_factorization()
        bench_fig4_speedup()
        bench_fig5_complexity()
        bench_fig6_multithread()
        bench_train_step()
        bench_fig7_load()
        bench_serving()
        bench_kernels()
        bench_wkv_chunks()
        bench_moe_capacity()
    print(f"\n{len(ROWS)} benchmarks complete")
    if args.json:
        write_json(args.json)
    if args.trace:
        from repro.obs import trace as trace_lib

        trace_lib.get_tracer().close()
        print(f"wrote trace to {args.trace}")


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    main()
