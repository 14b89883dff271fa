"""Every plan of every registered family is the SAME function (or sits
inside its documented error band) — with the sweep GENERATED from the
family-generic plan registry (core/plans.py).

``plans.value_sweep()`` / ``plans.grad_sweep()`` enumerate plans x dtypes x
deliberately awkward shapes per family (odd batch, short prime-ish T,
hidden sizes that do not divide the Pallas block sizes; for rwkv6: C=1,
C=T, non-dividing T, chunk > T), each compared leaf-wise against the
family's oracle under the plan's registered equivalence policy.  Adding a
family to the registry adds it to this sweep — nothing here is
LSTM-specific anymore.

``fused_seq_q8`` carries a band policy with no oracle-gradient contract:
its training guarantee is the ERROR-BAND Q8 section below — tight
agreement with the dequantize oracle (fp rounding of the folded
per-channel scale), int8-band agreement with the f32 plans, and
straight-through gradients that match the STE reference
(ref.quantize_dequantize_ste) exactly-math.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.mobirnn_lstm import LSTMConfig
from repro.core import lstm, plans

#: the LSTM family's shapes, re-exported for the Q8/streaming sections
SHAPES = [c.shape for c in plans.get_family("lstm").cases]

#: exact-equivalence plans of the lstm family (the historical constant;
#: the jit/Q8 sections still iterate it)
EXACT_PLANS = [n for n, s in plans.get_family("lstm").plans.items()
               if s.policy.kind == "exact" and n != "sequential"]

Q8_BAND = plans.Q8_BAND
#: kernel-vs-dequant-oracle agreement is far tighter than the int8 band
#: (fp rounding of the folded per-channel scale only)
Q8_ORACLE_TOL = dict(rtol=1e-4, atol=1e-5)


def _setup(shape, dtype):
    b, t, h, d, n_layers = shape
    cfg = dataclasses.replace(LSTMConfig(), hidden=h, input_dim=d,
                              n_layers=n_layers, seq_len=t, dtype=dtype)
    params = lstm.init_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (b, t, d),
                          jnp.dtype(dtype))
    return cfg, params, x


def _sweep_params(sweep):
    return [pytest.param(sc, id=sc.id,
                         marks=[pytest.mark.slow] if sc.heavy else [])
            for sc in sweep]


def test_registry_preserves_forward_plans():
    """Acceptance: the registry SERVES core/lstm.FORWARD_PLANS — same
    names, same functions — rather than forking them."""
    fam = plans.get_family("lstm")
    assert list(fam.plans) == list(lstm.FORWARD_PLANS)
    for name, spec in fam.plans.items():
        assert spec.fn is lstm.FORWARD_PLANS[name]
    assert fam.oracle == "sequential"


@pytest.mark.parametrize("sc", _sweep_params(plans.value_sweep()))
def test_plan_matches_oracle(sc):
    """Registry-generated value sweep: every comparable plan of every
    family, against that family's oracle, at the registered tolerance."""
    fam = plans.get_family(sc.family)
    inputs = fam.make_inputs(sc.case, sc.dtype)
    got = fam.apply(sc.plan, inputs)
    want = fam.apply(fam.oracle, inputs)
    for a, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == w.shape and a.dtype == w.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(w, np.float32),
                                   **fam.tol(sc.plan, sc.dtype),
                                   err_msg=sc.id)


def test_plans_agree_under_jit_and_grad():
    """The plans stay equivalent through jit and as loss_fn backends."""
    cfg, params, x = _setup(SHAPES[0], "float32")
    labels = jnp.array([0, 3, 5])
    grads = []
    for plan in ("sequential", "fused_seq"):
        fwd = lstm.FORWARD_PLANS[plan]
        loss, g = jax.jit(jax.value_and_grad(
            lambda p: lstm.loss_fn(p, x, labels, cfg, forward=fwd)))(params)
        grads.append((loss, g))
    np.testing.assert_allclose(grads[0][0], grads[1][0], rtol=1e-5,
                               atol=1e-5)
    for a, b in zip(jax.tree.leaves(grads[0][1]),
                    jax.tree.leaves(grads[1][1])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# GRADIENT equivalence: every plan is the same function under jax.grad too
# (fused_seq via the fused reverse-sweep kernel, rwkv6 chunked_scan via the
# reverse-sweep wkv kernel, fused_cell via the per-cell oracle VJP,
# wavefront via plain autodiff) — the training-story guarantee, generated
# from the registry: only (plan, dtype) pairs whose policy registers a
# grad_tol participate (the q8 plan's gradient contract is the STE test).
# ---------------------------------------------------------------------------
def _grads(plan, cfg, params, x, labels):
    fwd = lstm.FORWARD_PLANS[plan]
    _, g = jax.value_and_grad(
        lambda p: lstm.loss_fn(p, x, labels, cfg, forward=fwd))(params)
    return g


@pytest.mark.parametrize("sc", _sweep_params(plans.grad_sweep()))
def test_grad_matches_oracle(sc):
    fam = plans.get_family(sc.family)
    inputs = fam.make_inputs(sc.case, sc.dtype)
    got = fam.grads(sc.plan, inputs)
    want = fam.grads(fam.oracle, inputs)
    for a, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == w.dtype and a.shape == w.shape
        assert bool(jnp.all(jnp.isfinite(a.astype(jnp.float32))))
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(w, np.float32),
                                   **fam.grad_tol(sc.plan, sc.dtype),
                                   err_msg=sc.id)


def test_value_and_grad_dispatches_O1_in_T():
    """The fused-seq training step is O(1) Pallas dispatches in T: exactly
    one trajectory-emitting forward + one reverse-sweep backward, at every
    sequence length — vs the per-cell plan's O(T*L) forward replay."""
    from repro.analysis import count_train_dispatches

    counts = []
    for t in (3, 12, 48):
        cfg, params, x = _setup((2, t, 16, 9, 2), "float32")
        labels = jnp.array([0, 1])
        counts.append(count_train_dispatches(
            lambda p: lstm.loss_fn(p, x, labels, cfg,
                                   forward=lstm.FORWARD_PLANS["fused_seq"]),
            params))
    assert counts == [2, 2, 2], counts

    # contrast: the per-cell plan's training step scales with T*L (pallas
    # dispatches all sit in the forward; its VJP replays the jnp oracle)
    cfg, params, x = _setup((2, 6, 16, 9, 2), "float32")
    labels = jnp.array([0, 1])
    n_cell = count_train_dispatches(
        lambda p: lstm.loss_fn(p, x, labels, cfg,
                               forward=lstm.FORWARD_PLANS["fused_cell"]),
        params)
    assert n_cell == 6 * 2, n_cell


# ---------------------------------------------------------------------------
# RWKV6 dispatch counts (ISSUE 6): the chunked_scan plan honours its
# registered dispatch expectations — ONE forward dispatch, TWO per
# value_and_grad (trajectory-emitting forward + one reverse-sweep
# backward), at every T — and its sequential grid work is O(T/C), pinned by
# the family-aware grid-step counter.
# ---------------------------------------------------------------------------
def _rwkv_case(T, chunk, B=2, H=2, dk=8, dv=8):
    case = plans.Case(f"T{T}c{chunk}", (B, T, H, dk, dv, chunk))
    return plans.get_family("rwkv6").make_inputs(case, "float32")


def _rwkv_loss(args, chunk, plan="chunked_scan"):
    def loss(*a):
        out, s = plans.RWKV_PLANS[plan](*a, chunk=chunk)
        return (jnp.sum(out.astype(jnp.float32))
                + jnp.sum(s.astype(jnp.float32)))
    return loss, args


def test_rwkv_chunked_scan_dispatches_match_registry():
    """fwd_dispatches/train_dispatches registered on the PlanSpec hold at
    every T, dividing or not — a silent oracle-replay backward would show
    up as extra forward dispatches here."""
    from repro.analysis import count_kernel_dispatches, count_train_dispatches

    spec = plans.get_family("rwkv6").plans["chunked_scan"]
    for T in (8, 24, 23):
        args, chunk = _rwkv_case(T, 8)
        n_fwd = count_kernel_dispatches(jax.make_jaxpr(
            lambda *a: plans.RWKV_PLANS["chunked_scan"](*a, chunk=chunk))(
                *args))
        loss, a = _rwkv_loss(args, chunk)
        n_train = count_train_dispatches(loss, *a)
        assert n_fwd == spec.fwd_dispatches, (T, n_fwd)
        assert n_train == spec.train_dispatches, (T, n_train)


def test_rwkv_grid_steps_O_T_over_C():
    """count_pallas_grid_steps sees the O(T/C) sequential structure the
    dispatch count cannot: BH * ceil(T/C) forward grid steps, twice that
    for value_and_grad, and halving the chunk doubles both."""
    from repro.analysis import count_pallas_grid_steps

    B, H = 2, 2
    for T, chunk in ((24, 8), (23, 8), (24, 4)):
        args, _ = _rwkv_case(T, chunk, B=B, H=H)
        want = B * H * math.ceil(T / chunk)
        jx = jax.make_jaxpr(
            lambda *a: plans.RWKV_PLANS["chunked_scan"](*a, chunk=chunk))(
                *args)
        assert count_pallas_grid_steps(jx) == want, (T, chunk)
        loss, a = _rwkv_loss(args, chunk)
        jx2 = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0,)))(*a)
        assert count_pallas_grid_steps(jx2) == 2 * want, (T, chunk)


def test_rwkv_oracle_bwd_fallback_keeps_single_forward():
    """bwd=ORACLE_BWD (the past-budget fallback) still runs the fused
    forward kernel once; only the backward replays the jnp oracle — the
    shape plan_viability(train=True) routes to past the bwd budget."""
    from repro.analysis import count_kernel_dispatches, count_train_dispatches
    from repro.kernels import wkv6 as wkv6_lib

    args, chunk = _rwkv_case(16, 8)

    def plan(*a):
        return plans.RWKV_PLANS["chunked_scan"](
            *a, chunk=chunk, bwd=wkv6_lib.ORACLE_BWD)

    n_fwd = count_kernel_dispatches(jax.make_jaxpr(plan)(*args))

    def loss(*a):
        out, s = plan(*a)
        return jnp.sum(out) + jnp.sum(s)

    n_train = count_train_dispatches(loss, *args)
    assert (n_fwd, n_train) == (1, 1), (n_fwd, n_train)


# ---------------------------------------------------------------------------
# Long-T time streaming (ISSUE 4 acceptance): past the whole-T-resident VMEM
# budget the plan STREAMS the time axis instead of falling back — no
# fused_cell reroute, no oracle-VJP backward.
# ---------------------------------------------------------------------------
#: The mobile-class budget where the seed config's whole-T-resident working
#: set falls off by T=256 (fwd) / at every T (bwd) while the chunked table
#: stays viable — same constant the CI smoke (benchmarks/run.py
#: --stream-smoke) runs at.
from repro.core.factorization import MOBILE_VMEM_BUDGET as _STREAM_BUDGET


def test_long_T_budget_table_streams_instead_of_falling_back():
    """Pure budget math: at (T, budget) pairs where whole-T residency does
    not fit even at batch tile 1, ``choose_batch_block`` returns a viable
    ``(block_b, time_chunk)`` — and keeps the batch tile coarse."""
    from repro.kernels import lstm_seq as seq_lib

    cfg = LSTMConfig()
    p_width = max(cfg.input_dim, cfg.hidden)
    for T, mode in ((512, "bwd"), (2048, "fwd"), (2048, "bwd")):
        nochunk = seq_lib.choose_batch_block(
            2, T, cfg.n_layers, p_width, cfg.hidden,
            vmem_budget=_STREAM_BUDGET, mode=mode, allow_chunk=False)
        assert nochunk is None, (T, mode, nochunk)   # the old cliff
        blocks = seq_lib.choose_batch_block(
            2, T, cfg.n_layers, p_width, cfg.hidden,
            vmem_budget=_STREAM_BUDGET, mode=mode)
        assert blocks is not None and blocks.time_chunk is not None, (T, mode)
        assert blocks.block_b == 2, blocks            # batch stays coarse
        assert seq_lib.working_set_bytes(
            T, cfg.n_layers, p_width, cfg.hidden, blocks.block_b,
            mode=mode, time_chunk=blocks.time_chunk) <= _STREAM_BUDGET


@pytest.mark.slow
def test_long_T_streamed_plan_matches_sequential():
    """Executed acceptance: at T=512 under the mobile-class budget — where
    the pre-streaming table dropped the backward to the oracle VJP — the
    plan stays fused_seq end-to-end (1 fwd dispatch, 2 train dispatches)
    and fwd + gradients match the sequential oracle."""
    from repro.analysis import count_kernel_dispatches, count_train_dispatches

    cfg, params, x = _setup((2, 512, 32, 9, 2), "float32")
    labels = jnp.array([0, 1])

    def fwd(p, x, cfg):
        return lstm.forward_fused_seq(p, x, cfg,
                                      vmem_budget=_STREAM_BUDGET)

    n_fwd = count_kernel_dispatches(jax.make_jaxpr(
        lambda p, x: fwd(p, x, cfg))(params, x))
    n_train = count_train_dispatches(
        lambda p: lstm.loss_fn(p, x, labels, cfg, forward=fwd), params)
    assert (n_fwd, n_train) == (1, 2), (n_fwd, n_train)

    want = lstm.forward_sequential(params, x, cfg)
    got = fwd(params, x, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    gw = _grads("sequential", cfg, params, x, labels)
    _, gg = jax.value_and_grad(
        lambda p: lstm.loss_fn(p, x, labels, cfg, forward=fwd))(params)
    for a, w in zip(jax.tree.leaves(gg), jax.tree.leaves(gw)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(w, np.float32),
                                   rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# Q8 (ISSUE 5 acceptance): the int8-weight plan's ERROR-BAND equivalence
# contract — tight vs the dequantize oracle, banded vs the f32 plans,
# exact-math straight-through gradients, O(1) dispatches, and a
# strictly-no-finer quantization-aware tiling at the mobile-class budget.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "b{}t{}h{}d{}l{}"
                         .format(*s))
def test_q8_plan_matches_oracle_and_band(shape):
    """The q8 plan (a) agrees with the dequantize-then-run oracle within fp
    rounding — the real kernel contract — and (b) stays inside the
    documented int8 band of the sequential f32 oracle."""
    from repro.kernels import lstm_seq as seq_lib
    from repro.kernels import ref
    from repro.partitioning import split

    cfg, params, x = _setup(shape, "float32")
    got = lstm.forward_fused_seq_q8(params, x, cfg)
    want_f32 = lstm.forward_sequential(params, x, cfg)
    assert got.shape == want_f32.shape and got.dtype == want_f32.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_f32),
                               **Q8_BAND)
    # dequantize-oracle reference for the same logits
    values, _ = split(params)
    w_stack, b_stack, p_width = seq_lib.stack_params(values["layers"],
                                                     cfg.hidden)
    xp = seq_lib.pad_input(x, p_width)
    wq, scales = ref.quantize_q8(w_stack)
    _, h = ref.lstm_seq_q8(wq, scales, b_stack, xp)
    want_q8 = h[-1] @ values["head"]["w"] + values["head"]["b"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_q8),
                               **Q8_ORACLE_TOL)


def test_q8_grads_match_ste_reference():
    """Straight-through training contract: grads of the q8 plan equal the
    grads of the sequential oracle run over ref.quantize_dequantize_ste
    weights — same quantized forward, identity passthrough to the masters.
    Checked at the plan level (stacking + head included)."""
    from repro.kernels import ref

    cfg, params, x = _setup(SHAPES[0], "float32")
    labels = jnp.array([0, 3, 5])

    def ste_forward(p, x, cfg):
        # quantize each layer's stacked rows exactly as the plan does:
        # through the SAME stacked (L, P+H, 4H) layout
        from repro.kernels import lstm_seq as seq_lib
        from repro.partitioning import split as _split
        values, _ = _split(p)
        w_stack, b_stack, p_width = seq_lib.stack_params(values["layers"],
                                                         cfg.hidden)
        w_ste = ref.quantize_dequantize_ste(w_stack)
        xp = seq_lib.pad_input(x, p_width)
        _, h = ref.lstm_seq(w_ste, b_stack.astype(jnp.float32), xp)
        return h[-1] @ values["head"]["w"] + values["head"]["b"]

    got = _grads("fused_seq_q8", cfg, params, x, labels)
    want = jax.value_and_grad(
        lambda p: lstm.loss_fn(p, x, labels, cfg, forward=ste_forward))(
            params)[1]
    for a, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == w.dtype and a.shape == w.shape
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                   rtol=1e-4, atol=1e-5)


def test_q8_value_and_grad_dispatches_O1_in_T():
    """Quantization happens in jnp outside the kernels: the q8 training
    step is still exactly 2 Pallas dispatches at every T, and the forward
    exactly 1."""
    from repro.analysis import count_kernel_dispatches, count_train_dispatches

    counts = []
    for t in (3, 12, 48):
        cfg, params, x = _setup((2, t, 16, 9, 2), "float32")
        labels = jnp.array([0, 1])
        n = count_kernel_dispatches(jax.make_jaxpr(
            lambda p, x: lstm.forward_fused_seq_q8(p, x, cfg))(params, x))
        counts.append((n, count_train_dispatches(
            lambda p: lstm.loss_fn(
                p, x, labels, cfg,
                forward=lstm.FORWARD_PLANS["fused_seq_q8"]),
            params)))
    assert counts == [(1, 2), (1, 2), (1, 2)], counts


def test_q8_budget_no_finer_than_f32_at_mobile_budget():
    """ISSUE 5 acceptance: at the 320K mobile-class budget the
    quantization-aware table returns a strictly-no-finer (block_b,
    time_chunk) than f32 at every T/mode — and strictly COARSER somewhere
    (the widened whole-T window), including a (T, mode) where f32 must
    stream but q8 stays whole-T resident."""
    from repro.kernels import lstm_seq as seq_lib

    cfg = LSTMConfig()
    p_width = max(cfg.input_dim, cfg.hidden)
    strictly_coarser = wholeT_won = False
    for T in (32, 128, 256, 512, 1024, 2048):
        for mode in ("fwd", "bwd"):
            f32 = seq_lib.choose_batch_block(
                2, T, cfg.n_layers, p_width, cfg.hidden,
                vmem_budget=_STREAM_BUDGET, mode=mode)
            q8 = seq_lib.choose_batch_block(
                2, T, cfg.n_layers, p_width, cfg.hidden,
                vmem_budget=_STREAM_BUDGET, mode=mode, quantized=True)
            assert q8 is not None, (T, mode)
            if f32 is None:
                strictly_coarser = True
                continue
            assert q8.block_b >= f32.block_b, (T, mode, f32, q8)
            if q8.time_chunk is None:
                if f32.time_chunk is not None:
                    strictly_coarser = wholeT_won = True
            else:
                assert f32.time_chunk is not None, (T, mode, f32, q8)
                assert q8.time_chunk >= f32.time_chunk, (T, mode, f32, q8)
                if q8.time_chunk > f32.time_chunk:
                    strictly_coarser = True
    assert strictly_coarser     # the 4x weight term must actually matter
    assert wholeT_won           # the widened whole-T-resident window
