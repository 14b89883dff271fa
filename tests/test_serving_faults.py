"""The chaos path: seeded fault injection through the SlotEngine.

The headline invariant (ISSUE 9 / ROADMAP §Robustness): lanes never
interact, so under ANY seeded FaultPlan the healthy lanes' greedy tokens
are bit-identical to a fault-free run, every request terminates with a
finish_reason from the closed set, and the zero-allocation invariant
(``StatePool.stats.buffers_built`` stays at capacity) holds through
quarantine, retry and re-admission.

The property test proper needs hypothesis (a dev dependency — CI installs
it); a deterministic two-seed parametrisation of the same property runs
everywhere so the chaos path is never silently unexercised.
"""
import dataclasses
import gc

import jax
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import registry
from repro.partitioning import split
from repro.serving import (FINISH_REASONS, EngineConfig, FaultInjector,
                           FaultPlan, FinishReason, LanePoison,
                           PrefillFault, QueueFlood, Request, Result,
                           SlotEngine, SlowTick)
from repro import steps as steps_lib

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # hypothesis is a dev-only dependency
    HAVE_HYPOTHESIS = False


@pytest.fixture(autouse=True)
def _release_compiled_state():
    # Engines are built per-test, so their jit closures (and the XLA
    # executables behind them) are garbage after each test.  Dropping them
    # eagerly keeps the long-lived suite process from accumulating native
    # compiler state across the many engine constructions in this module.
    yield
    gc.collect()
    jax.clear_caches()


def _tiny_cfg():
    return dataclasses.replace(
        get_arch("qwen2-0.5b").reduced(), n_layers=2, d_model=64,
        n_heads=2, n_kv_heads=1, head_dim=16, d_ff=128, vocab=128)


@pytest.fixture(scope="module")
def tiny():
    cfg = _tiny_cfg()
    model = registry.build(cfg)
    params, _ = split(model.init(jax.random.PRNGKey(0)))
    return cfg, model, params


LENS, NEWS = [5, 9, 3, 7], [6, 4, 8, 5]


def _requests(cfg, lens=LENS, news=NEWS, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(0, cfg.vocab, (int(l),)).astype(np.int32),
                    max_new_tokens=int(m))
            for i, (l, m) in enumerate(zip(lens, news))]


@pytest.fixture(scope="module")
def baseline(tiny):
    """Fault-free reference tokens for the standard request set — what
    every healthy (finish_reason='length') lane must match bit-for-bit."""
    cfg, model, params = tiny
    engine = SlotEngine(model, params, n_slots=2, max_seq=64,
                        queue_capacity=8)
    results = engine.serve(_requests(cfg))
    assert all(r.finish_reason == FinishReason.LENGTH for r in results)
    return {r.uid: r.tokens for r in results}


class FakeClock:
    """Deterministic monotonic clock: advances 1.0 per call."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        self.t += 1.0
        return self.t


# ---------------------------------------------------------------------------
# FaultPlan / closed finish_reason set (no model)
# ---------------------------------------------------------------------------
def test_fault_plan_seeded_deterministic_and_json_roundtrip():
    kw = dict(n_slots=2, ticks=8, uids=(0, 1, 2), n_poison=2, n_prefill=1,
              n_slow_burst=1, n_flood=1)
    a, b = FaultPlan.seeded(7, **kw), FaultPlan.seeded(7, **kw)
    assert a == b                                  # structural determinism
    assert a != FaultPlan.seeded(8, **kw)
    assert FaultPlan.from_json(a.to_json()) == a
    kinds = {type(f) for f in a.faults}
    assert kinds == {LanePoison, PrefillFault, SlowTick, QueueFlood}


def test_result_rejects_reasons_outside_closed_set():
    empty = np.zeros((0,), np.int32)
    for reason in sorted(FINISH_REASONS):
        Result(0, empty, 0.0, 0.0, [], finish_reason=reason)
    with pytest.raises(ValueError, match="closed"):
        Result(0, empty, 0.0, 0.0, [], finish_reason="oom")


# ---------------------------------------------------------------------------
# Guard semantics at the steps level
# ---------------------------------------------------------------------------
def test_guarded_step_all_false_poison_is_bit_identical(tiny):
    cfg, model, params = tiny
    cache, _ = split(model.init_cache(2, 16))
    cache = dict(cache, pos=np.array([3, 0], np.int32))
    batch = {"tokens": np.array([7, 0], np.int32),
             "active": np.array([True, False])}
    ref_logits, ref_cache = steps_lib.masked_decode_step(
        cfg, params, jax.tree.map(np.copy, cache), dict(batch))
    logits, lane_ok, _ = steps_lib.guarded_decode_step(
        cfg, params, cache, dict(batch, poison=np.array([False, False])))
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref_logits))
    # inactive lanes never report faults, whatever their logits hold
    assert np.asarray(lane_ok).tolist() == [True, True]
    poisoned, lane_ok, _ = steps_lib.guarded_decode_step(
        cfg, params, ref_cache, dict(batch, poison=np.array([True, False])))
    assert np.asarray(lane_ok).tolist() == [False, True]
    assert np.isnan(np.asarray(poisoned)[0]).all()


# ---------------------------------------------------------------------------
# Engine: DOA fast-fail, quarantine, retries, prefill faults
# ---------------------------------------------------------------------------
def test_submit_dead_on_arrival_publishes_deadline_result(tiny):
    cfg, model, params = tiny
    engine = SlotEngine(model, params, n_slots=2, max_seq=64,
                        queue_capacity=4, clock=FakeClock())
    req = Request(9, np.array([1, 2], np.int32), max_new_tokens=2,
                  deadline_s=0.5)
    assert engine.submit(req) is False
    assert len(engine.queue) == 0
    res = engine.take_finished()[9]
    assert res.finish_reason == FinishReason.DEADLINE
    assert res.tokens.shape[-1] == 0
    assert engine.metrics.counter("serving/deadline_miss").value == 1


def test_quarantine_without_budget_errors_healthy_lane_identical(
        tiny, baseline):
    cfg, model, params = tiny
    faults = FaultPlan(seed=0, faults=(LanePoison(tick=1, lane=0),))
    engine = SlotEngine(model, params, n_slots=2, max_seq=64,
                        queue_capacity=8, faults=faults)
    reqs = _requests(cfg, lens=LENS[:2], news=[6, 4])
    results = engine.serve(reqs)
    # uid0 (lane 0) quarantined at tick 1: admit token + tick-0 token kept,
    # the poisoned tick-1 token never recorded
    assert results[0].finish_reason == FinishReason.ERROR
    assert results[0].tokens.shape[-1] == 2
    np.testing.assert_array_equal(results[0].tokens, baseline[0][:2])
    # the neighbour lane never noticed
    assert results[1].finish_reason == FinishReason.LENGTH
    np.testing.assert_array_equal(results[1].tokens, baseline[1])
    assert engine.metrics.counter("serving/quarantined").value == 1
    assert engine.metrics.counter("serving/retries").value == 0
    assert engine.pool.stats.buffers_built == 1       # zero-alloc held


def test_quarantine_retry_regenerates_identical_tokens(tiny, baseline):
    cfg, model, params = tiny
    faults = FaultPlan(seed=0, faults=(LanePoison(tick=1, lane=0),))
    engine = SlotEngine(model, params, n_slots=2, max_seq=64,
                        queue_capacity=8, faults=faults, retry_budget=2)
    results = engine.serve(_requests(cfg, lens=LENS[:2], news=[6, 4]))
    # the retried request restarts from prefill, so greedy decode
    # regenerates exactly the fault-free tokens
    for r in results:
        assert r.finish_reason == FinishReason.LENGTH
        np.testing.assert_array_equal(r.tokens, baseline[r.uid])
    assert engine.metrics.counter("serving/quarantined").value == 1
    assert engine.metrics.counter("serving/retries").value == 1
    assert engine.pool.stats.buffers_built == 1


def test_retries_exhausted_under_persistent_poison(tiny):
    cfg, model, params = tiny
    faults = FaultPlan(seed=0, faults=tuple(
        LanePoison(tick=t, lane=0) for t in range(64)))
    engine = SlotEngine(model, params, n_slots=1, max_seq=64,
                        queue_capacity=4, faults=faults, retry_budget=1)
    [res] = engine.serve(_requests(cfg, lens=[5], news=[4]))
    assert res.finish_reason == FinishReason.RETRIES_EXHAUSTED
    assert engine.metrics.counter("serving/retries").value == 1
    assert engine.metrics.counter("serving/quarantined").value == 2
    assert engine.pool.stats.buffers_built == 1


def test_prefill_fault_without_budget_is_error(tiny, baseline):
    cfg, model, params = tiny
    faults = FaultPlan(seed=0, faults=(PrefillFault(uid=0),))
    engine = SlotEngine(model, params, n_slots=2, max_seq=64,
                        queue_capacity=8, faults=faults)
    results = engine.serve(_requests(cfg, lens=LENS[:2], news=[6, 4]))
    assert results[0].finish_reason == FinishReason.ERROR
    assert results[0].tokens.shape[-1] == 0
    assert results[1].finish_reason == FinishReason.LENGTH
    np.testing.assert_array_equal(results[1].tokens, baseline[1])
    # injected prefill faults raise BEFORE the dispatch: the donated B=1
    # scratch survives and is never rebuilt
    assert engine._scratch_pool.stats.buffers_built == 1


def test_prefill_fault_with_budget_retries_to_length(tiny, baseline):
    cfg, model, params = tiny
    faults = FaultPlan(seed=0, faults=(PrefillFault(uid=0),))
    engine = SlotEngine(model, params, n_slots=2, max_seq=64,
                        queue_capacity=8, faults=faults, retry_budget=1)
    results = engine.serve(_requests(cfg, lens=LENS[:2], news=[6, 4]))
    for r in results:
        assert r.finish_reason == FinishReason.LENGTH
        np.testing.assert_array_equal(r.tokens, baseline[r.uid])
    assert engine.metrics.counter("serving/retries").value == 1
    assert engine._scratch_pool.stats.buffers_built == 1
    # the retry keeps the request's first stamp: its TTFT and queue time
    # include the failed attempt
    assert 0.0 < results[0].queue_s < results[0].ttft_s


# ---------------------------------------------------------------------------
# Chunked prefill x faults: per-ATTEMPT consumption, chunk-k targeting
# ---------------------------------------------------------------------------
def test_take_prefill_fault_is_per_attempt_and_chunk_targeted():
    """No model needed: the injector's chunk-matching semantics alone.
    A ``chunk=k`` fault skips attempts for earlier chunks, fires exactly
    once at chunk k, and is consumed — the retry's chunk-k attempt passes."""
    plan = FaultPlan(seed=0, faults=(PrefillFault(uid=1, chunk=2),))
    inj = FaultInjector(plan, 2, vocab=16, max_seq=32)
    assert not inj.take_prefill_fault(1, chunk=0)
    assert not inj.take_prefill_fault(1, chunk=1)
    assert inj.take_prefill_fault(1, chunk=2)
    assert not inj.take_prefill_fault(1, chunk=2)     # consumed per attempt
    # chunk=None (the whole-prompt path's meaning) matches ANY attempt
    inj2 = FaultInjector(FaultPlan(seed=0, faults=(PrefillFault(uid=3),)),
                         2, vocab=16, max_seq=32)
    assert inj2.take_prefill_fault(3, chunk=5)
    assert not inj2.take_prefill_fault(3, chunk=5)
    # the chunk field round-trips; pre-chunk plans (no field) still load
    p = FaultPlan(seed=1, faults=(PrefillFault(uid=2, chunk=1),))
    assert FaultPlan.from_json(p.to_json()) == p
    legacy = {"seed": 0, "faults": [{"kind": "PrefillFault", "uid": 4}]}
    assert FaultPlan.from_json(legacy).faults[0].chunk is None


def test_chunk_k_fault_discards_partial_state_retry_token_identical(tiny):
    """ISSUE 10 satellite: a fault at chunk k of a chunked admission
    discards the k chunks of partial scratch state; the retry restarts
    from chunk 0 and the final tokens are bit-identical to an unfaulted
    chunked run (which is itself identical to whole-prompt prefill)."""
    cfg, model, params = tiny
    def reqs():
        return _requests(cfg, lens=[13, 5], news=[4, 3])
    clean = SlotEngine(model, params, config=EngineConfig(
        n_slots=2, max_seq=64, queue_capacity=8,
        prefill_chunk_len=4, prefill_lanes=2))
    want = {r.uid: r.tokens for r in clean.serve(reqs())}

    # prompt_len=13, chunk_len=4 -> schedule [4,4,4,1]; fault the third
    # attempt (chunk=2), i.e. after 8 tokens of partial prefill state
    faults = FaultPlan(seed=0, faults=(PrefillFault(uid=0, chunk=2),))
    engine = SlotEngine(model, params, config=EngineConfig(
        n_slots=2, max_seq=64, queue_capacity=8,
        prefill_chunk_len=4, prefill_lanes=2,
        faults=faults, retry_budget=1))
    results = engine.serve(reqs())
    for r in results:
        assert r.finish_reason == FinishReason.LENGTH
        np.testing.assert_array_equal(r.tokens, want[r.uid])
    assert engine.metrics.counter("serving/retries").value == 1
    # injected faults raise BEFORE dispatch: the lane scratch survives,
    # is zero-reset on give_back, and the pool never rebuilds
    sp = engine._scratch_pool.stats
    assert sp.buffers_built == sp.capacity == 2
    assert sp.outstanding == 0


def test_chunk_k_fault_without_budget_is_error(tiny):
    cfg, model, params = tiny
    faults = FaultPlan(seed=0, faults=(PrefillFault(uid=0, chunk=1),))
    engine = SlotEngine(model, params, config=EngineConfig(
        n_slots=1, max_seq=64, queue_capacity=4,
        prefill_chunk_len=4, prefill_lanes=1, faults=faults))
    [res] = engine.serve(_requests(cfg, lens=[9], news=[3]))
    assert res.finish_reason == FinishReason.ERROR
    assert res.tokens.shape[-1] == 0
    sp = engine._scratch_pool.stats
    assert sp.buffers_built == sp.capacity == 1
    assert sp.outstanding == 0


# ---------------------------------------------------------------------------
# Degradation ladder: watchdog downshift, shed, recovery
# ---------------------------------------------------------------------------
def test_ladder_degrades_sheds_and_recovers(tiny):
    cfg, model, params = tiny
    faults = FaultPlan(seed=0, faults=tuple(
        SlowTick(tick=t, extra_s=1e6) for t in range(3)))
    engine = SlotEngine(
        model, params, n_slots=2, max_seq=64, queue_capacity=4,
        extra_plans={"decode/fallback":
                     lambda p, c, b: steps_lib.decode_step(cfg, p, c, b)},
        faults=faults, tick_slo_s=50.0, slo_breach_ticks=3,
        slo_recover_ticks=3, ladder=["decode/base"])
    reqs = _requests(cfg, lens=[5, 9], news=[12, 12])
    # queued behind both lanes with a deadline far under the post-breach
    # tick EMA (~1e6 s): provably unmeetable once degraded -> shed
    doomed = Request(7, np.array([1, 2, 3], np.int32), max_new_tokens=4,
                     deadline_s=engine.clock() + 1000.0)
    results = engine.serve(reqs + [doomed])
    assert [r.finish_reason for r in results[:2]] == [
        FinishReason.LENGTH, FinishReason.LENGTH]
    assert results[2].finish_reason == FinishReason.SHED
    assert engine.metrics.counter("serving/shed").value == 1
    # the downshift is visible in the per-tick decisions: decode/base until
    # the third breach, decode/fallback while degraded
    plans = [d.plan for d in engine.scheduler.decisions]
    assert plans[:3] == ["decode/base"] * 3
    assert "decode/fallback" in plans[3:]
    # three healthy ticks after the burst step the ladder back up
    assert engine.scheduler.level == 0
    assert engine.pool.stats.buffers_built == 1


# ---------------------------------------------------------------------------
# The chaos property (hypothesis in CI, fixed seeds everywhere)
# ---------------------------------------------------------------------------
def _chaos_property(tiny, baseline, seed):
    """Any seeded FaultPlan: no exception escapes stream(), every request
    terminates with a reason from the closed set, healthy lanes match the
    fault-free run token-for-token, and the pool never reallocates."""
    cfg, model, params = tiny
    reqs = _requests(cfg)
    faults = FaultPlan.seeded(seed, n_slots=2, ticks=10,
                              uids=tuple(r.uid for r in reqs),
                              n_poison=2, n_prefill=1, n_slow_burst=1,
                              slow_extra_s=0.01, n_flood=1, flood_n=2)
    engine = SlotEngine(model, params, n_slots=2, max_seq=64,
                        queue_capacity=4, faults=faults, retry_budget=1)
    for ev in engine.stream(reqs):
        assert ev.finish_reason is None or ev.finish_reason in FINISH_REASONS
    done = engine.take_finished()
    for req in reqs:
        assert req.uid in done, f"request {req.uid} never terminated"
        res = done[req.uid]
        assert res.finish_reason in FINISH_REASONS
        if res.finish_reason == FinishReason.LENGTH:
            np.testing.assert_array_equal(res.tokens, baseline[req.uid])
    assert engine.pool.stats.buffers_built == 1
    assert engine._scratch_pool.stats.buffers_built == 1
    # every TTFT stamp closed: at a first token or a tokenless end
    assert not engine._submitted


@pytest.mark.parametrize("seed", [0, 3])
def test_chaos_fixed_seeds(tiny, baseline, seed):
    _chaos_property(tiny, baseline, seed)


if HAVE_HYPOTHESIS:
    @settings(max_examples=4, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2**16))
    def test_chaos_property_hypothesis(tiny, baseline, seed):
        _chaos_property(tiny, baseline, seed)
else:                                       # pragma: no cover
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_chaos_property_hypothesis():
        pass
