"""Load-aware scheduler (paper Fig 7) and preallocated state pools (§3.2)."""
import jax.numpy as jnp
import pytest

from repro.core.scheduler import (Plan, ProcLoadSensor, Scheduler,
                                  SyntheticLoadSensor)
from repro.core.state import StatePool
import jax


def _sched(accel_base=0.03, cpu_base=0.1):
    s = Scheduler(SyntheticLoadSensor(0.0))
    s.register(Plan("accel", lambda: None, base_latency_s=accel_base,
                    shared=True, sensitivity=1.0))
    s.register(Plan("cpu", lambda: None, base_latency_s=cpu_base,
                    shared=False))
    return s


def test_low_load_prefers_accelerator():
    s = _sched()
    assert s.choose(load=0.1).plan == "accel"
    assert s.choose(load=0.4).plan == "accel"


def test_high_load_crosses_over_to_cpu():
    """Paper Fig 7: under high accelerator load the CPU path wins."""
    s = _sched()
    assert s.choose(load=0.9).plan == "cpu"


def test_crossover_point_matches_contention_model():
    # accel wins iff base/(1-load) < cpu_base  =>  load < 1 - accel/cpu
    s = _sched(accel_base=0.03, cpu_base=0.1)
    crossover = 1 - 0.03 / 0.1
    assert s.choose(load=crossover - 0.05).plan == "accel"
    assert s.choose(load=crossover + 0.05).plan == "cpu"


def test_observation_updates_base_latency():
    s = _sched()
    p = s.plans["accel"]
    for _ in range(50):
        p.observe(0.2, load=0.0)      # accel got slow
    assert s.choose(load=0.0).plan == "cpu"


def test_proc_sensor_in_range():
    v = ProcLoadSensor().load()
    assert 0.0 <= v <= 1.0


# ---------------------------------------------------------------------------
# Viability filtering (ROADMAP: schedule from the VMEM model, not just EMA)
# ---------------------------------------------------------------------------
def test_viability_filters_choose():
    s = _sched()                      # accel would win at low load...
    s.viable = lambda name: name != "accel"
    assert s.choose(load=0.0).plan == "cpu"   # ...but it is not viable


def test_viability_never_calibrates_nonviable():
    calls = []
    s = Scheduler(SyntheticLoadSensor(0.0),
                  viable=lambda name: name == "cpu")
    s.register(Plan("accel", lambda: calls.append("accel"), shared=True))
    s.register(Plan("cpu", lambda: calls.append("cpu"), shared=False))
    s.calibrate(repeats=1)
    # one untimed warmup + one timed repeat, the non-viable plan never runs
    assert calls == ["cpu", "cpu"]
    assert s.plans["accel"].base_latency_s == float("inf")


def test_calibrate_warmup_excludes_compile_cost():
    """Regression: calibrate used to time the FIRST call, so jit compile
    cost landed in base_latency_s and poisoned every choose() afterwards.
    A fn that is slow exactly once (compile) must calibrate to its
    steady-state latency."""
    import time as _time

    calls = []

    def fn():
        calls.append(1)
        if len(calls) == 1:
            _time.sleep(0.05)        # "compilation" on first invocation

    s = Scheduler(SyntheticLoadSensor(0.0))
    s.register(Plan("p", fn))
    s.calibrate(repeats=1)
    assert len(calls) == 2           # warmup + one timed repeat
    # the timed repeat must not see the 50ms first-call cost
    assert s.plans["p"].base_latency_s < 0.025


def test_calibrate_seeds_from_profile_without_running():
    """A persisted device profile short-circuits measurement: profiled
    plans take their base latency from the profile and their fn is never
    invoked; unprofiled plans still get the measured path."""
    ran = []
    s = Scheduler(SyntheticLoadSensor(0.0))
    s.register(Plan("profiled", lambda: ran.append("profiled")))
    s.register(Plan("measured", lambda: ran.append("measured")))
    s.calibrate(repeats=1, profile={"profiled": 0.007})
    assert s.plans["profiled"].base_latency_s == 0.007
    assert "profiled" not in ran
    assert ran == ["measured", "measured"]      # warmup + timed
    assert s.plans["measured"].base_latency_s < float("inf")


def test_viability_rejecting_everything_raises():
    s = _sched()
    with pytest.raises(ValueError, match="no viable plan"):
        s.choose(load=0.0, viable=lambda name: False)


def test_plan_viability_from_vmem_model():
    """kernels/lstm_seq.choose_batch_block wires into Scheduler(viable=...):
    past the VMEM budget the sequence-resident plan is filtered out."""
    from repro.configs import MOBIRNN_LSTM
    from repro.core import lstm

    cfg = MOBIRNN_LSTM
    fits = lstm.plan_viability(cfg, 8, cfg.seq_len)
    assert fits("fused_seq") and fits("fused_cell") and fits("sequential")
    tiny = lstm.plan_viability(cfg, 8, cfg.seq_len, vmem_budget=1024)
    assert not tiny("fused_seq")
    assert tiny("fused_cell") and tiny("sequential")  # fallbacks stay

    s = Scheduler(SyntheticLoadSensor(0.0), viable=tiny)
    s.register(Plan("fused_seq", lambda: None, base_latency_s=0.001,
                    shared=True))
    s.register(Plan("fused_cell", lambda: None, base_latency_s=0.01,
                    shared=True))
    assert s.choose(load=0.0).plan == "fused_cell"


def test_plan_viability_train_mode_is_stricter():
    """Under jax.grad the fused-seq working set grows ~3x (trajectory
    residuals + gradient accumulators), so there is a budget window where
    the plan is viable for inference but NOT for training — a train-time
    scheduler must pass train=True or it will pick a plan whose backward
    silently drops to the oracle replay.  With time streaming the window
    is the gap between the two modes' (bm=1, tc=1) FLOORS — the f32 dw/db
    accumulators and gradient outputs that no amount of chunking can
    shrink — narrower than the old whole-T-resident gap, but still there."""
    from repro.configs import MOBIRNN_LSTM
    from repro.core import lstm
    from repro.kernels import lstm_seq as seq_lib

    cfg = MOBIRNN_LSTM
    p_width = max(cfg.input_dim, cfg.hidden)
    floor = dict(block_b=1, mode="bwd", time_chunk=1)
    bwd_floor = seq_lib.working_set_bytes(
        cfg.seq_len, cfg.n_layers, p_width, cfg.hidden, **floor)
    budget = bwd_floor - 1
    infer = lstm.plan_viability(cfg, 8, cfg.seq_len, vmem_budget=budget)
    train = lstm.plan_viability(cfg, 8, cfg.seq_len, vmem_budget=budget,
                                train=True)
    assert infer("fused_seq")
    assert not train("fused_seq")
    assert train("fused_cell") and train("sequential")  # fallbacks stay
    # with a real budget both modes admit the plan
    assert lstm.plan_viability(cfg, 8, cfg.seq_len, train=True)("fused_seq")


def test_plan_viability_long_T_streams_instead_of_filtering():
    """The (block_b, time_chunk) decision table makes the Fig 7 viability
    surface T-independent: long sequences stream the time axis through
    double-buffered chunks instead of disqualifying fused_seq — only a
    weight stack (plus gradient accumulators under train=True) that blows
    the budget at (bm=1, tc=1) still filters it out."""
    from repro.configs import MOBIRNN_LSTM
    from repro.core import lstm
    from repro.core.factorization import MOBILE_VMEM_BUDGET

    cfg = MOBIRNN_LSTM
    budget = MOBILE_VMEM_BUDGET   # whole-T bwd never fits it
    for T in (128, 512, 2048, 8192):
        for train in (False, True):
            ok = lstm.plan_viability(cfg, 2, T, vmem_budget=budget,
                                     train=train)
            assert ok("fused_seq"), (T, train)
    # the weight-stack floor is the only remaining filter
    floor = lstm.plan_viability(cfg, 2, 128, vmem_budget=16 << 10)
    assert not floor("fused_seq")
    assert floor("fused_cell") and floor("sequential")


def test_plan_viability_quantized_widens_both_windows():
    """ISSUE 5: the int8 plan's viability surface strictly contains the f32
    plan's.  The inference-viable-vs-train-viable window shifts DOWN with
    1-byte weights: budgets exist where (a) f32 is not even
    inference-viable but q8 is, and (b) f32 training falls back while q8
    training stays fused — because both (bm=1, tc=1) floors drop by the
    quartered weight stack (fwd) / stack + f32-outs delta (bwd)."""
    from repro.configs import MOBIRNN_LSTM
    from repro.core import lstm
    from repro.kernels import lstm_seq as seq_lib

    cfg = MOBIRNN_LSTM
    p_width = max(cfg.input_dim, cfg.hidden)

    def floor(mode, quantized):
        return seq_lib.working_set_bytes(
            cfg.seq_len, cfg.n_layers, p_width, cfg.hidden, 1, mode=mode,
            time_chunk=1, quantized=quantized)

    # the q8 floors sit strictly below the f32 floors in both modes
    assert floor("fwd", True) < floor("fwd", False)
    assert floor("bwd", True) < floor("bwd", False)

    # (a) inference window: below the f32 fwd floor, above the q8 one
    budget = floor("fwd", False) - 1
    infer = lstm.plan_viability(cfg, 8, cfg.seq_len, vmem_budget=budget)
    assert not infer("fused_seq")
    assert infer("fused_seq_q8")
    assert infer("fused_cell") and infer("sequential")

    # (b) training window: below the f32 bwd floor, above the q8 one —
    # the old inference-viable-but-not-train-viable gap now ALSO has a
    # quantized escape hatch before the fused_cell fallback
    budget = floor("bwd", False) - 1
    assert budget > floor("bwd", True)
    train = lstm.plan_viability(cfg, 8, cfg.seq_len, vmem_budget=budget,
                                train=True)
    assert not train("fused_seq")
    assert train("fused_seq_q8")

    # (c) below the q8 bwd floor both fused-seq plans are out; the q8 fwd
    # can still be inference-viable there (its window is wider than its
    # train window, exactly like f32)
    budget = floor("bwd", True) - 1
    train_tiny = lstm.plan_viability(cfg, 8, cfg.seq_len,
                                     vmem_budget=budget, train=True)
    assert not train_tiny("fused_seq_q8")
    assert not train_tiny("fused_seq")
    assert train_tiny("fused_cell") and train_tiny("sequential")
    if budget >= floor("fwd", True):
        assert lstm.plan_viability(cfg, 8, cfg.seq_len,
                                   vmem_budget=budget)("fused_seq_q8")

    # at a real budget every plan is viable in both modes
    full = lstm.plan_viability(cfg, 8, cfg.seq_len, train=True)
    assert full("fused_seq") and full("fused_seq_q8")


# ---------------------------------------------------------------------------
# Two-family registry viability (ISSUE 6): ONE scheduler serving lstm AND
# rwkv6 plans through core/plans.scheduler_viability — a budget-non-viable
# rwkv plan is never calibrated and never chosen, while the other family's
# plans and the CPU fallbacks are untouched.
# ---------------------------------------------------------------------------
def _two_family_viable(rwkv_budget=None):
    from repro.configs import MOBIRNN_LSTM
    from repro.core import lstm, plans

    cfg = MOBIRNN_LSTM
    return plans.scheduler_viability({
        "accel_seq": ("fused_seq",
                      lstm.plan_viability(cfg, 8, cfg.seq_len)),
        "accel_wkv": ("chunked_scan",
                      plans.rwkv_viability(128, 64, 64,
                                           vmem_budget=rwkv_budget)),
    })


def test_two_family_nonviable_rwkv_never_calibrated_or_chosen():
    """rwkv's choose_chunk finds nothing at a 2 KiB budget (the per-head
    state blocks alone blow it), so the bound scheduler name is filtered
    everywhere; the lstm family's fast path and the unbound CPU plans are
    unaffected."""
    calls = []
    viable = _two_family_viable(rwkv_budget=2048)
    s = Scheduler(SyntheticLoadSensor(0.0), viable=viable)
    s.register(Plan("accel_wkv", lambda: calls.append("accel_wkv"),
                    base_latency_s=0.001, shared=True))   # would always win
    s.register(Plan("accel_seq", lambda: calls.append("accel_seq"),
                    base_latency_s=0.01, shared=True))
    s.register(Plan("cpu", lambda: calls.append("cpu"), base_latency_s=0.1,
                    shared=False))
    s.calibrate(repeats=1)
    assert "accel_wkv" not in calls
    # calibrate never ran it: the registered base is untouched — and even
    # with the winning latency on the books, choose filters it out
    assert s.plans["accel_wkv"].base_latency_s == 0.001
    for load in (0.0, 0.5, 0.95):
        assert s.choose(load=load).plan != "accel_wkv"
    # the lstm fast path and the CPU fallback were both calibrated and
    # remain choosable (which wins is calibration noise between no-op fns)
    assert "accel_seq" in calls and "cpu" in calls
    assert viable("accel_seq") and viable("cpu")


def test_two_family_real_budget_admits_both_fast_paths():
    viable = _two_family_viable(rwkv_budget=None)          # default budget
    assert viable("accel_seq") and viable("accel_wkv") and viable("cpu")
    s = Scheduler(SyntheticLoadSensor(0.0), viable=viable)
    s.register(Plan("accel_wkv", lambda: None, base_latency_s=0.001,
                    shared=True))
    s.register(Plan("cpu", lambda: None, base_latency_s=0.1, shared=False))
    assert s.choose(load=0.0).plan == "accel_wkv"


def test_rwkv_viability_train_mode_is_stricter():
    """The reverse-sweep backward holds ~3x the forward working set, so a
    budget window exists where the Pallas wkv plan is inference-viable but
    not train-viable — mirroring the lstm family's train=True contract."""
    from repro.core import plans
    from repro.kernels import wkv6 as wkv6_lib

    S, dk, dv = 128, 64, 64
    fwd_need = wkv6_lib.working_set_bytes(S, dk, dv, 1, mode="fwd")
    bwd_need = wkv6_lib.working_set_bytes(S, dk, dv, 1, mode="bwd")
    assert bwd_need > fwd_need
    budget = bwd_need - 1
    infer = plans.rwkv_viability(S, dk, dv, vmem_budget=budget)
    train = plans.rwkv_viability(S, dk, dv, vmem_budget=budget, train=True)
    assert infer("chunked_scan")
    assert not train("chunked_scan")
    assert train("stepwise") and train("chunked_xla")      # fallbacks stay


def test_rwkv_choose_chunk_halves_under_pressure():
    """The (C,) decision mirrors SeqBlocks coarseness order: full target
    chunk at a real budget, halved chunks as the budget shrinks (the
    (C, C, dk) intra-chunk tensor is the dominant term), None only when
    even C=1 does not fit."""
    from repro.kernels import wkv6 as wkv6_lib

    S, dk, dv = 128, 64, 64
    full = wkv6_lib.choose_chunk(S, dk, dv, target=32)
    assert full == wkv6_lib.WkvBlocks(32)
    seen = {full.chunk}
    budget = wkv6_lib.working_set_bytes(S, dk, dv, 32) - 1
    while True:
        blocks = wkv6_lib.choose_chunk(S, dk, dv, target=32,
                                       vmem_budget=budget)
        if blocks is None:
            break
        assert blocks.chunk < 32 and 32 % blocks.chunk == 0
        assert wkv6_lib.working_set_bytes(
            S, dk, dv, blocks.chunk) <= budget
        seen.add(blocks.chunk)
        budget = wkv6_lib.working_set_bytes(S, dk, dv, blocks.chunk) - 1
    assert len(seen) >= 3                   # the search actually halves
    assert wkv6_lib.choose_chunk(S, dk, dv, vmem_budget=64) is None


def test_tile_plan_protocol_unifies_family_blocks():
    """ISSUE 10 satellite: SeqBlocks / WkvBlocks / MambaBlocks all satisfy
    the core.tiling.TilePlan protocol, so viability factories (and any
    future consumer) can read batch_tile/time_chunk without knowing the
    family-specific field names."""
    from repro.core import tiling
    from repro.kernels import lstm_seq, mamba_scan, wkv6 as wkv6_lib

    seq = lstm_seq.SeqBlocks(block_b=8)
    wkv = wkv6_lib.WkvBlocks(16)
    mamba = mamba_scan.MambaBlocks(block_b=4, chunk=32)
    for plan in (seq, wkv, mamba):
        assert isinstance(plan, tiling.TilePlan)
    assert seq.batch_tile == 8 and seq.time_chunk is None
    assert wkv.batch_tile == wkv.bh_tile and wkv.time_chunk == 16
    assert mamba.batch_tile == 4 and mamba.time_chunk == 32
    # something without the accessors is NOT a TilePlan
    assert not isinstance(object(), tiling.TilePlan)


def test_wkv6_choose_chunk_deprecated_alias_over_choose_blocks():
    from repro.kernels import wkv6 as wkv6_lib

    S, dk, dv = 128, 64, 64
    with pytest.warns(DeprecationWarning, match="choose_blocks"):
        legacy = wkv6_lib.choose_chunk(S, dk, dv, target=16)
    modern = wkv6_lib.choose_blocks(1, S, dk, dv, target=16)
    assert legacy == modern


def test_slot_engine_per_tick_choice_respects_two_family_viability():
    """Per-tick choice inside SlotEngine: with a faster-calibrated rwkv
    decode plan registered but bound non-viable, every tick's Decision
    picks the base plan and serving output is unaffected; with the real
    budget the same registration wins the ticks."""
    import dataclasses as dc

    import numpy as np

    from repro import steps as steps_lib
    from repro.configs import get_arch
    from repro.core import plans
    from repro.models import registry as model_registry
    from repro.partitioning import split as p_split
    from repro.serving import Request, SlotEngine

    cfg = dc.replace(get_arch("qwen2-0.5b").reduced(), n_layers=2,
                     d_model=64, n_heads=2, n_kv_heads=1, head_dim=16,
                     d_ff=128, vocab=128)
    model = model_registry.build(cfg)
    params, _ = p_split(model.init(jax.random.PRNGKey(0)))

    def run(rwkv_budget):
        engine = SlotEngine(
            model, params, n_slots=2, max_seq=32,
            extra_plans={"decode/wkv_fused":
                         lambda p, c, b: steps_lib.decode_step(cfg, p, c, b)})
        engine.scheduler.viable = plans.scheduler_viability({
            "decode/wkv_fused":
            ("chunked_scan",
             plans.rwkv_viability(128, 64, 64, vmem_budget=rwkv_budget))})
        # make the rwkv-bound plan the would-be winner of every tick
        engine.scheduler.plans["decode/wkv_fused"].base_latency_s = 1e-6
        engine.scheduler.plans["decode/base"].base_latency_s = 1e-3
        rng = np.random.default_rng(0)
        reqs = [Request(i, rng.integers(0, cfg.vocab, (4,)).astype(np.int32),
                        max_new_tokens=3) for i in range(3)]
        results = engine.serve(reqs)
        assert [r.uid for r in results] == [0, 1, 2]
        ticks = [d.plan for d in engine.scheduler.decisions]
        assert ticks, "no decode ticks recorded"
        return results, ticks

    res_blocked, ticks_blocked = run(rwkv_budget=2048)
    assert set(ticks_blocked) == {"decode/base"}   # never the non-viable one
    res_open, ticks_open = run(rwkv_budget=None)
    # with the budget open the bound plan wins the tick (later ticks may
    # legitimately flip as plan.observe folds REAL latencies over the
    # seeded bases — per-tick choice staying live is the point)
    assert ticks_open[0] == "decode/wkv_fused"
    for a, b in zip(res_blocked, res_open):      # same decode fn: same tokens
        assert np.array_equal(a.tokens, b.tokens)


# ---------------------------------------------------------------------------
def _spec():
    return {"c": jax.ShapeDtypeStruct((2, 4), jnp.float32),
            "h": jax.ShapeDtypeStruct((2, 4), jnp.float32)}


def test_pool_checkout_return_cycle():
    pool = StatePool(_spec(), capacity=3)
    a = pool.checkout()
    b = pool.checkout()
    assert pool.stats.outstanding == 2
    pool.give_back(a)
    pool.give_back(b)
    assert pool.stats.outstanding == 0
    assert pool.stats.high_water == 2


def test_pool_exhaustion_raises():
    pool = StatePool(_spec(), capacity=1)
    pool.checkout()
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.checkout()


def test_pool_returns_zeroed_buffers():
    pool = StatePool(_spec(), capacity=1)
    buf = pool.checkout()
    buf = {k: v + 7.0 for k, v in buf.items()}
    pool.give_back(buf)
    again = pool.checkout()
    assert float(jnp.sum(jnp.abs(again["c"]))) == 0.0


def test_pool_allocation_accounting():
    pool = StatePool(_spec(), capacity=4)
    assert pool.stats.allocation_bytes == 4 * 2 * (2 * 4 * 4)


def test_give_back_resets_without_allocating():
    """Regression: give_back used to run ``b * 0`` per return — a fresh
    buffer per cycle despite the 'reset without allocating' docstring.  The
    reset now goes through a donated jit: the returned buffer is zeroed in
    place, the caller's handle is invalidated, and the pool never builds a
    buffer after __init__."""
    pool = StatePool(_spec(), capacity=1)
    for cycle in range(5):
        buf = pool.checkout()
        buf = {k: v + 7.0 for k, v in buf.items()}
        leaves = jax.tree.leaves(buf)
        pool.give_back(buf)
        # donation invalidated the returned handle — in-place reset
        assert all(leaf.is_deleted() for leaf in leaves), cycle
    assert pool.stats.buffers_built == 1        # no growth in live buffers
    assert pool.stats.resets == 5


def test_lane_zero_zeroes_single_lane():
    from repro.core.state import donate, lane_zero

    tree = {"c": jnp.ones((3, 2, 4)), "h": jnp.ones((3, 2, 4))}
    reset = donate(lambda t, i: lane_zero(t, i, axis=1), (0,))
    out = reset(tree, jnp.asarray(1, jnp.int32))
    assert float(jnp.sum(out["c"][:, 1])) == 0.0
    assert float(jnp.sum(out["c"][:, 0])) == 3 * 4     # untouched lane
