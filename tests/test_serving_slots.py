"""Slot-resident continuous batching (serving/slots.py + SlotEngine).

The fast tests drive a micro dense model (2 layers, d=64) — they are the
quick-loop serving smoke.  The per-family slot-vs-wave equivalence sweeps
build full reduced() archs and carry the ``slow`` marker.
"""
import dataclasses
import gc

import jax
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import registry
from repro.partitioning import split
from repro.serving import (Engine, EngineConfig, QueueFull, Request,
                           RequestQueue, SlotEngine, chunk_schedule)


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


def _requests(cfg, lens, news, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(0, cfg.vocab, (int(l),)).astype(np.int32),
                    max_new_tokens=int(m))
            for i, (l, m) in enumerate(zip(lens, news))]


class FakeClock:
    """Deterministic monotonic clock: advances 1.0 per call."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        self.t += 1.0
        return self.t


# ---------------------------------------------------------------------------
# Queue (no model)
# ---------------------------------------------------------------------------
def test_queue_fifo_and_backpressure():
    q = RequestQueue(capacity=2)
    a = Request(0, np.array([1], np.int32))
    b = Request(1, np.array([2], np.int32))
    q.submit(a)
    q.submit(b)
    assert q.full and len(q) == 2
    with pytest.raises(QueueFull, match="full"):
        q.submit(Request(2, np.array([3], np.int32)))
    assert q.pop() is a          # FIFO
    assert q.pop() is b
    assert q.pop() is None


def test_queue_expiry_with_duplicate_uids_and_equal_prompts():
    """Regression: expiry partitions by identity — dataclass ``==`` over
    ndarray prompts would raise 'truth value of an array is ambiguous'."""
    clock = FakeClock()
    q = RequestQueue(capacity=4, clock=clock)
    # no deadline -> no clock call; the second submit sees clock=1.0, so a
    # deadline of 1.5 is still live at submit but dead at the expire sweep
    assert q.submit(Request(5, np.array([1, 2, 3], np.int32)))
    assert q.submit(Request(5, np.array([1, 2, 3], np.int32), deadline_s=1.5))
    expired = q.expire()                                  # clock -> 2.0
    assert len(expired) == 1 and expired[0].deadline_s == 1.5
    assert len(q) == 1 and q.pop().deadline_s is None


def test_queue_deadline_expiry():
    clock = FakeClock()
    q = RequestQueue(capacity=4, clock=clock)
    # already-passed deadline is dead on arrival: rejected at submit (no
    # dead work queued until the next expiry sweep), False returned
    assert not q.submit(Request(0, np.array([1], np.int32), deadline_s=0.5))
    assert len(q) == 0
    assert q.submit(Request(1, np.array([2], np.int32), deadline_s=2.5))
    assert q.submit(Request(2, np.array([3], np.int32)))            # none
    expired = q.expire()                                  # clock -> 3.0
    assert [r.uid for r in expired] == [1]
    assert len(q) == 1 and q.pop().uid == 2


# ---------------------------------------------------------------------------
# Slot engine (quick-loop serving smoke: tiny config, 8 requests)
# ---------------------------------------------------------------------------
def test_slot_engine_smoke_mixed_max_new(tiny):
    cfg, model, params = tiny
    engine = SlotEngine(model, params, n_slots=3, max_seq=64,
                        queue_capacity=4)
    reqs = _requests(cfg, [5, 9, 3, 7, 5, 9, 3, 7], [2, 8, 4, 6, 8, 1, 6, 4])
    events = []
    results = engine.serve(reqs, on_token=events.append)
    assert [r.uid for r in results] == list(range(8))
    for r, req in zip(results, reqs):
        assert r.finish_reason == "length"
        assert r.tokens.shape == (req.max_new_tokens,)
    # streamed events reassemble into exactly the returned tokens
    for req, res in zip(reqs, results):
        toks = [ev.token for ev in events if ev.uid == req.uid]
        assert np.array_equal(np.stack(toks, -1), res.tokens)
        dones = [ev.done for ev in events if ev.uid == req.uid]
        assert sum(dones) == 1 and dones[-1]
    # uid 0 (2 tokens) must retire before uid 1 (8 tokens) completes
    order = [ev.uid for ev in events if ev.done]
    assert order.index(0) < order.index(1)
    # no serving-path allocation: both pools keep their build-time buffers
    assert engine.pool.stats.buffers_built == engine.pool.stats.capacity == 1
    assert engine._scratch_pool.stats.buffers_built == 1


def test_slot_engine_no_alloc_after_warmup(tiny):
    import gc

    cfg, model, params = tiny
    engine = SlotEngine(model, params, n_slots=2, max_seq=64)
    engine.serve(_requests(cfg, [4, 6, 4], [3, 2, 4]))           # warmup
    gc.collect()
    live0 = len(jax.live_arrays())
    engine.serve(_requests(cfg, [4, 6, 4], [2, 4, 3], seed=1))
    gc.collect()
    # the live device-buffer population does not grow across a warm serve
    # — every serving-path update runs through a donated jit in place
    assert len(jax.live_arrays()) <= live0
    assert (engine.pool.stats.buffers_built,
            engine._scratch_pool.stats.buffers_built) == (1, 1)
    # ONE resident + ONE scratch checkout for the engine's whole life: the
    # scratch is zeroed in place inside the donated prefill jit, never
    # returned/rebuilt
    assert engine.pool.stats.checkouts == 1
    assert engine._scratch_pool.stats.checkouts == 1
    # the always-on serving metrics are host-side ints/deques — populating
    # them across two serves must not have touched the device pools above
    assert engine.metrics.counter("serving/ticks").value > 0
    assert engine.metrics.histogram("serving/ttft_s").count == 6


def test_slot_engine_traced_run_token_identical(tiny):
    """Tracing on vs off must not change a single token or allocate on
    the serving path, and the trace must carry per-tick spans with the
    chosen plan, TTFT admit events, and nested sched/choose decisions."""
    from repro.obs import ListSink, Tracer, set_tracer

    cfg, model, params = tiny
    reqs = lambda: _requests(cfg, [4, 6, 3], [3, 2, 4])
    base = SlotEngine(model, params, n_slots=2, max_seq=64)
    want = [r.tokens for r in base.serve(reqs())]

    sink = ListSink()
    old = set_tracer(Tracer(sink))
    try:
        traced = SlotEngine(model, params, n_slots=2, max_seq=64)
        got = [r.tokens for r in traced.serve(reqs())]
    finally:
        set_tracer(old)

    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    assert traced.pool.stats.buffers_built == 1       # zero-alloc holds

    ticks = [r for r in sink.records if r["name"] == "serve/tick"]
    admits = [r for r in sink.records if r["name"] == "serve/admit"]
    chooses = [r for r in sink.records if r["name"] == "sched/choose"]
    assert ticks and len(admits) == 3
    tick_ids = {r["span"] for r in ticks}
    for t in ticks:
        assert t["type"] == "span" and t["attrs"]["plan"]
        assert t["attrs"]["tick_s"] > 0
    for a in admits:
        assert a["attrs"]["ttft_s"] > 0
    # every per-tick plan decision nests under its tick span, inside the
    # tick's prepare phase
    prepare = {r["span"]: r["parent"] for r in sink.records
               if r["name"] == "serve/tick/prepare"}
    assert chooses and all(prepare.get(c["parent"]) in tick_ids
                           for c in chooses)
    # the run closes with a metrics summary event
    summaries = [r for r in sink.records if r["name"] == "serve/metrics"]
    assert summaries
    snap = summaries[-1]["attrs"]
    assert snap["counters"]["serving/deadline_miss"] == 0
    assert snap["counters"]["serving/retired"] == 3
    assert snap["histograms"]["serving/ttft_s"]["count"] == 3


def test_slot_engine_ttft_on_results(tiny):
    """Satellite: per-request TTFT (submit -> first token on host) rides on
    Result next to decode_s with its queue part, and feeds the
    serving/ttft_s histogram."""
    cfg, model, params = tiny
    engine = SlotEngine(model, params, n_slots=2, max_seq=64)
    results = engine.serve(_requests(cfg, [4, 7, 3], [3, 2, 4]))
    for r in results:
        assert r.finish_reason == "length"
        assert r.ttft_s > 0.0
        assert 0.0 <= r.queue_s < r.ttft_s
        # the first token is produced AT admission, before any decode tick
        assert r.ttft_s - r.queue_s <= r.prefill_s + r.decode_s + 1.0
    # the third request waited in the queue for a free slot
    assert results[2].queue_s > results[0].queue_s
    h = engine.metrics.histogram("serving/ttft_s")
    assert h.count == len(results)
    assert engine.metrics.histogram("serving/tbt_s").count > 0
    assert engine.metrics.counter("serving/retired").value == len(results)
    # zero-alloc invariant holds with metrics populated
    assert engine.pool.stats.buffers_built == 1


def test_slot_engine_backpressure(tiny):
    cfg, model, params = tiny
    engine = SlotEngine(model, params, n_slots=1, max_seq=64,
                        queue_capacity=2)
    engine.submit(Request(0, np.array([1, 2], np.int32), max_new_tokens=2))
    engine.submit(Request(1, np.array([3], np.int32), max_new_tokens=2))
    with pytest.raises(QueueFull):
        engine.submit(Request(2, np.array([4], np.int32)))
    # drain what was accepted
    for _ in engine.stream():
        pass
    assert sorted(engine.finished) == [0, 1]


def test_deadline_expiry_queued_and_resident(tiny):
    cfg, model, params = tiny
    clock = FakeClock()
    engine = SlotEngine(model, params, n_slots=1, max_seq=64, clock=clock)
    reqs = [
        # admitted first; deadline hits mid-generation (clock ticks ~1/loop)
        Request(0, np.array([1, 2, 3], np.int32), max_new_tokens=32,
                deadline_s=6.0),
        # waits behind uid 0 in the single slot; already past its deadline
        # by the time the loop re-checks the queue
        Request(1, np.array([4, 5], np.int32), max_new_tokens=2,
                deadline_s=0.5),
        # no deadline: must still complete fully
        Request(2, np.array([6], np.int32), max_new_tokens=3),
    ]
    results = engine.serve(reqs)
    assert results[0].finish_reason == "deadline"
    assert 0 < results[0].tokens.shape[-1] < 32     # partial output surfaced
    assert results[1].finish_reason == "deadline"
    assert results[1].tokens.shape[-1] == 0         # dropped from the queue
    assert results[2].finish_reason == "length"
    assert results[2].tokens.shape[-1] == 3
    assert not engine._submitted     # expiries close their TTFT stamps


def test_zero_budget_request_gets_zero_tokens(tiny):
    """max_new_tokens=0 completes without prefilling or occupying a lane —
    matching the wave engine's per-request truncation."""
    cfg, model, params = tiny
    engine = SlotEngine(model, params, n_slots=2, max_seq=64)
    reqs = [Request(0, np.array([1, 2], np.int32), max_new_tokens=0),
            Request(1, np.array([3, 4], np.int32), max_new_tokens=2)]
    results = engine.serve(reqs)
    assert results[0].tokens.shape == (0,)
    assert results[0].finish_reason == "length"
    assert results[1].tokens.shape == (2,)
    assert not engine._submitted


def test_deadline_checked_on_mid_admission_refill(tiny):
    """Regression: a request that only reaches the queue during the
    admission loop's refill (queue was full at loop top) must still be
    deadline-checked, not silently served."""
    cfg, model, params = tiny
    clock = FakeClock()
    engine = SlotEngine(model, params, n_slots=2, max_seq=64,
                        queue_capacity=1, clock=clock)
    reqs = [Request(0, np.array([1, 2], np.int32), max_new_tokens=1),
            Request(1, np.array([3], np.int32), max_new_tokens=1,
                    deadline_s=0.5)]        # already past at first tick
    results = engine.serve(reqs)
    assert results[0].finish_reason == "length"
    assert results[1].finish_reason == "deadline"
    assert results[1].tokens.shape[-1] == 0


def test_request_exceeding_lane_budget_rejected_upfront(tiny):
    """prompt_len + max_new_tokens - 1 > max_seq would scatter decode KV
    out of range (silently dropped) — rejected at submit time instead."""
    cfg, model, params = tiny
    engine = SlotEngine(model, params, n_slots=2, max_seq=16)
    with pytest.raises(ValueError, match="exceeds max_seq"):
        engine.submit(Request(0, np.arange(12, dtype=np.int32),
                              max_new_tokens=8))
    with pytest.raises(ValueError, match="exceeds max_seq"):
        engine.serve([Request(0, np.arange(12, dtype=np.int32),
                              max_new_tokens=8)])
    # exactly at the budget is fine: positions 11 + 0..5 < 16
    res = engine.serve([Request(0, np.arange(12, dtype=np.int32),
                                max_new_tokens=5)])
    assert res[0].tokens.shape == (5,)


def test_wave_engine_pads_with_inactive_dummies(tiny):
    """Ragged wave tails pad with zero-length dummy requests, not
    duplicates of real work; every request gets ITS OWN token budget."""
    cfg, model, params = tiny
    engine = Engine(model, params, batch_size=4, max_seq=64,
                    pool_capacity=1)
    reqs = _requests(cfg, [6, 6, 6, 6, 6], [4, 2, 4, 4, 3])
    results = engine.serve(reqs)
    assert [r.uid for r in results] == [0, 1, 2, 3, 4]
    assert [r.tokens.shape[-1] for r in results] == [4, 2, 4, 4, 3]


# ---------------------------------------------------------------------------
# EngineConfig (consolidated construction surface + deprecated aliases)
# ---------------------------------------------------------------------------
def test_engine_config_aliases_warn_and_match(tiny):
    cfg, model, params = tiny
    with pytest.warns(DeprecationWarning, match="deprecated"):
        legacy = SlotEngine(model, params, n_slots=3, max_seq=64,
                            queue_capacity=4, retry_budget=1)
    modern = SlotEngine(model, params, config=EngineConfig(
        n_slots=3, max_seq=64, queue_capacity=4, retry_budget=1))
    assert legacy.config == modern.config
    assert (legacy.n_slots, legacy.max_seq, legacy.retry_budget) == (3, 64, 1)
    # behaviour, not just bookkeeping: same tokens either way
    want = [r.tokens for r in modern.serve(_requests(cfg, [4, 6], [3, 2]))]
    got = [r.tokens for r in legacy.serve(_requests(cfg, [4, 6], [3, 2]))]
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    # wave engine: batch_size is the alias of n_slots
    with pytest.warns(DeprecationWarning, match="deprecated"):
        wave = Engine(model, params, batch_size=2, max_seq=32,
                      pool_capacity=1)
    assert wave.config.n_slots == wave.config.batch_size == 2


def test_engine_config_rejects_mixed_and_unknown(tiny):
    cfg, model, params = tiny
    with pytest.raises(ValueError, match="not both"):
        SlotEngine(model, params, config=EngineConfig(), n_slots=2)
    with pytest.raises(TypeError, match="unexpected keyword"):
        SlotEngine(model, params, bogus_knob=2)
    with pytest.raises(TypeError, match="unexpected keyword"):
        Engine(model, params, n_slots=2)      # a slot-only spelling


# ---------------------------------------------------------------------------
# Chunked prefill (EngineConfig.prefill_chunk_len — admission interleaving)
# ---------------------------------------------------------------------------
def test_chunk_schedule_fixed_shapes():
    # full chunks then the remainder's binary decomposition, descending
    assert chunk_schedule(13, 8) == [8, 4, 1]
    assert chunk_schedule(24, 8) == [8, 8, 8]
    assert chunk_schedule(7, 8) == [4, 2, 1]
    assert chunk_schedule(1, 8) == [1]
    assert chunk_schedule(0, 8) == []
    with pytest.raises(ValueError):
        chunk_schedule(4, 0)
    # the compiled-shape bound: whatever the prompt mix, segment lengths
    # come from {chunk_len} U {powers of two below it}
    allowed = {8, 4, 2, 1}
    for s in range(1, 70):
        segs = chunk_schedule(s, 8)
        assert sum(segs) == s and set(segs) <= allowed


def test_chunked_prefill_token_identity_and_one_shape(tiny):
    """Chunking changes scheduling, not math: greedy tokens match
    whole-prompt admission bit-for-bit, the chunk jit compiles exactly one
    executable per DISTINCT segment length, and both pools keep their
    build-time buffers through checkout/give_back lane churn."""
    cfg, model, params = tiny
    lens, news = [5, 29, 3, 13, 7, 21], [4, 6, 3, 5, 2, 4]
    whole = SlotEngine(model, params, config=EngineConfig(
        n_slots=3, max_seq=64))
    want = whole.serve(_requests(cfg, lens, news, seed=3))

    engine = SlotEngine(model, params, config=EngineConfig(
        n_slots=3, max_seq=64, prefill_chunk_len=8, prefill_lanes=2))
    got = engine.serve(_requests(cfg, lens, news, seed=3))
    for w, g in zip(want, got):
        assert g.finish_reason == "length"
        np.testing.assert_array_equal(w.tokens, g.tokens)
    segs = set()
    for l in lens:
        segs.update(chunk_schedule(l, 8))
    assert engine._prefill_chunk._cache_size() == len(segs)
    assert engine.pool.stats.buffers_built == 1
    sp = engine._scratch_pool.stats
    assert sp.buffers_built == sp.capacity == 2       # == prefill_lanes
    assert sp.outstanding == 0                        # every lane released
    assert engine.metrics.histogram("serving/prefill_chunk_s").count == \
        sum(len(chunk_schedule(l, 8)) for l in lens)


def test_decode_continues_during_chunked_prefill(tiny):
    """The headline scheduling property: a resident short request keeps
    producing decode tokens while a long-prompt adversary prefills in
    chunks — admission stalls the tick loop by at most one chunk, not the
    adversary's whole prefill."""
    cfg, model, params = tiny
    engine = SlotEngine(model, params, config=EngineConfig(
        n_slots=2, max_seq=64, queue_capacity=4, prefill_chunk_len=4,
        prefill_lanes=2))
    short = Request(0, np.arange(1, 5, dtype=np.int32), max_new_tokens=12)
    adversary = Request(1, np.arange(1, 25, dtype=np.int32),  # 6 chunks
                        max_new_tokens=2)
    events = []
    results = engine.serve([short, adversary], on_token=events.append)
    uids = [ev.uid for ev in events if ev.token is not None]
    first_adv = uids.index(1)
    # the short request decoded through the adversary's whole chunked
    # prefill: several of its tokens land BEFORE the adversary's first
    assert uids[:first_adv].count(0) >= 5
    assert all(r.finish_reason == "length" for r in results)


def test_partial_prefill_abort_keeps_pool_at_capacity(tiny):
    """A deadline that lands mid-chunked-prefill aborts the lane: the
    partial state is discarded through the pool's donated reset
    (buffers_built untouched) and later requests are served normally."""
    cfg, model, params = tiny
    clock = FakeClock()
    engine = SlotEngine(model, params, clock=clock, config=EngineConfig(
        n_slots=1, max_seq=64, queue_capacity=4, prefill_chunk_len=4,
        prefill_lanes=1))
    doomed = Request(0, np.arange(1, 41, dtype=np.int32),   # 10 chunks
                     max_new_tokens=4, deadline_s=4.0)      # dies mid-prefill
    healthy = Request(1, np.arange(1, 7, dtype=np.int32), max_new_tokens=3)
    results = engine.serve([doomed, healthy])
    assert results[0].finish_reason == "deadline"
    assert results[0].tokens.shape[-1] == 0
    assert results[1].finish_reason == "length"
    assert results[1].tokens.shape == (3,)
    sp = engine._scratch_pool.stats
    assert sp.buffers_built == sp.capacity == 1
    assert sp.outstanding == 0
    assert engine.pool.stats.buffers_built == 1
    assert engine.metrics.counter("serving/deadline_miss").value == 1


def test_chunked_rejects_invalid_config(tiny):
    cfg, model, params = tiny
    with pytest.raises(ValueError, match="prefill_chunk_len"):
        SlotEngine(model, params, config=EngineConfig(
            n_slots=2, max_seq=64, prefill_chunk_len=65))
    with pytest.raises(ValueError, match="prefill_lanes"):
        SlotEngine(model, params, config=EngineConfig(
            n_slots=2, max_seq=64, prefill_chunk_len=4, prefill_lanes=0))


# ---------------------------------------------------------------------------
# Engine spans: the tick and the chunk split into their phases, and one
# serve/request interval per request
# ---------------------------------------------------------------------------
def _kids(records, parent):
    return sorted((r for r in records
                   if r["type"] == "span" and r["parent"] == parent["span"]),
                  key=lambda r: r["ts"])


def _end(rec):
    return rec["ts"] + rec["dur_s"]


def _assert_phases(records, parent, names):
    """``parent``'s child spans are ``names`` in order, inside it, and do
    not overlap."""
    kids = _kids(records, parent)
    assert [k["name"] for k in kids] == names
    assert parent["ts"] <= kids[0]["ts"] and _end(kids[-1]) <= _end(parent)
    for a, b in zip(kids, kids[1:]):
        assert _end(a) <= b["ts"]


@pytest.mark.parametrize("chunk", [None, 4], ids=["whole", "chunked"])
def test_engine_spans_split_tick_and_chunk(tiny, chunk):
    """Traced serving, whole-prompt and chunked admission: every tick has
    prepare/dispatch/sync phases and every chunk dispatch/sync; each
    request has one serve/request whose queue and lane parts add up to
    its TTFT; no engine span is open while the client runs; the chunk
    events keep their schema; tokens and pools are as untraced."""
    from repro.obs import ListSink, Tracer, get_tracer, set_tracer

    cfg, model, params = tiny
    lens, news = [5, 11, 3, 9], [4, 3, 5, 2]

    def serve(on_token=None):
        engine = SlotEngine(model, params, config=EngineConfig(
            n_slots=2, max_seq=64, queue_capacity=8,
            prefill_chunk_len=chunk))
        return engine, engine.serve(_requests(cfg, lens, news, seed=5),
                                    on_token=on_token)

    _, want = serve()
    sink = ListSink()
    old = set_tracer(Tracer(sink))
    open_at_yield = []
    try:
        engine, got = serve(lambda ev: open_at_yield.append(
            list(get_tracer()._stack)))
    finally:
        set_tracer(old)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w.tokens, g.tokens)
    assert engine.pool.stats.buffers_built == 1       # zero-alloc holds
    sp = engine._scratch_pool.stats
    assert sp.buffers_built == sp.capacity
    assert open_at_yield and not any(open_at_yield)
    assert not engine._submitted                      # every stamp closed

    recs = sink.records
    named = lambda n: [r for r in recs if r["name"] == n]
    ticks = named("serve/tick")
    assert ticks
    for t in ticks:
        _assert_phases(recs, t, ["serve/tick/prepare", "serve/tick/dispatch",
                                 "serve/tick/sync"])
        assert 1 <= t["attrs"]["active"] <= t["attrs"]["occupied"]

    chunks, events = named("serve/chunk"), named("serve/prefill_chunk")
    starts = named("serve/prefill_start")
    if chunk is None:
        assert not chunks and not events and not starts
    else:
        n_chunks = sum(len(chunk_schedule(n, chunk)) for n in lens)
        assert len(chunks) == len(events) == n_chunks
        assert len(starts) == len(lens)
        for c, e in zip(chunks, events):
            _assert_phases(recs, c, ["serve/chunk/dispatch",
                                     "serve/chunk/sync"])
            assert set(c["attrs"]) == {"uid", "chunk", "seg_len"}
            assert e["type"] == "event" and e["parent"] is None
            assert set(e["attrs"]) == {"uid", "chunk", "seg_len", "filled",
                                       "chunk_s"}
            assert [c["attrs"][k] for k in ("uid", "chunk", "seg_len")] == \
                [e["attrs"][k] for k in ("uid", "chunk", "seg_len")]
            # the event is stamped once the chunk's token is ready
            assert _end(c) <= e["ts"]
        for e in starts:
            assert set(e["attrs"]) == {"uid", "prompt_len", "n_chunks"}

    reqs = named("serve/request")
    assert sorted(r["attrs"]["uid"] for r in reqs) == list(range(len(lens)))
    results = {r.uid: r for r in got}
    for r in reqs:
        a, res = r["attrs"], results[r["attrs"]["uid"]]
        assert r["type"] == "span" and r["parent"] is None
        assert a["queue_s"] >= 0 and a["lane_s"] > 0
        assert a["queue_s"] + a["lane_s"] == pytest.approx(r["dur_s"],
                                                           abs=1e-9)
        assert r["dur_s"] == pytest.approx(res.ttft_s, abs=1e-9)
        assert a["queue_s"] == pytest.approx(res.queue_s, abs=1e-9)
        assert a["prompt_len"] == lens[a["uid"]]
        assert a["chunks"] == (1 if chunk is None else
                               len(chunk_schedule(lens[a["uid"]], chunk)))
    admits = {r["attrs"]["uid"]: r["attrs"]["ttft_s"]
              for r in named("serve/admit")}
    assert admits == pytest.approx({u: r.ttft_s for u, r in results.items()})


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["qwen2-0.5b",            # dense
                                  "jamba-1.5-large-398b",  # ssm (mamba)
                                  "rwkv6-3b"])             # rwkv
def test_chunked_prefill_token_identity_per_family(arch):
    """Chunked admission is token-identical to whole-prompt admission for
    every serving family — attention replays the exact positions through
    the chunk mask, rwkv/mamba prefill FROM their cache state natively —
    and both are the greedy tokens of the cacheless full-sequence
    forward, so a state layout the prefill and the decode tick share
    (rwkv6's packed wkv pool) cannot agree with itself by mistake."""
    cfg = get_arch(arch).reduced()
    model = registry.build(cfg)
    params, _ = split(model.init(jax.random.PRNGKey(0)))
    reqs = _requests(cfg, [4, 10, 6, 8], [3, 8, 2, 5], seed=1)
    whole = SlotEngine(model, params, config=EngineConfig(
        n_slots=2, max_seq=32)).serve(reqs)
    chunked = SlotEngine(model, params, config=EngineConfig(
        n_slots=2, max_seq=32, prefill_chunk_len=4,
        prefill_lanes=2)).serve(reqs)
    for w, g, want in zip(whole, chunked, _forward_greedy(model, params,
                                                          reqs, 32)):
        assert np.array_equal(w.tokens, g.tokens), (w.uid, w.tokens,
                                                    g.tokens)
        assert np.array_equal(g.tokens, want), (g.uid, g.tokens, want)


def _forward_greedy(model, params, reqs, width):
    """Greedy continuations from ``model.forward`` over the whole
    sequence so far, right-padded to one ``width`` (causal: the padding
    never reaches the position read)."""
    fwd = jax.jit(lambda p, t: model.forward(p, {"tokens": t},
                                             inference=True)[0])
    out = []
    for r in reqs:
        toks = list(r.prompt)
        for _ in range(r.max_new_tokens):
            row = np.zeros((1, width), np.int32)
            row[0, :len(toks)] = toks
            toks.append(int(np.argmax(fwd(params, row)[0, len(toks) - 1])))
        out.append(np.asarray(toks[len(r.prompt):], np.int32))
    return out


# ---------------------------------------------------------------------------
# Slot-vs-wave greedy equivalence per model family
# ---------------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.parametrize("arch", ["qwen2-0.5b",            # dense
                                  "jamba-1.5-large-398b",  # ssm (mamba)
                                  "rwkv6-3b"])             # rwkv
def test_slot_vs_wave_equivalence(arch):
    """The slot engine's greedy outputs are token-identical to the
    unpadded per-request reference (the wave engine at batch_size=1) on a
    ragged request set — per-lane prefill, per-lane positions and the
    active-mask select are all exact."""
    cfg = get_arch(arch).reduced()
    model = registry.build(cfg)
    params, _ = split(model.init(jax.random.PRNGKey(0)))
    reqs = _requests(cfg, [4, 10, 6, 8], [3, 8, 2, 5], seed=1)
    ref = Engine(model, params, batch_size=1, max_seq=32,
                 pool_capacity=1).serve(reqs)
    out = SlotEngine(model, params, n_slots=2, max_seq=32).serve(reqs)
    for r, o in zip(ref, out):
        assert np.array_equal(r.tokens, o.tokens), (r.uid, r.tokens,
                                                    o.tokens)
