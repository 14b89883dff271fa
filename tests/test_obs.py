"""Observability layer (src/repro/obs): trace core, metrics, measured
profiler, and the instrumented scheduler / plan-dispatch paths.

The traced-SlotEngine integration checks (token identity, per-tick spans,
zero-alloc with tracing on) live in tests/test_serving_slots.py next to
the serving fixtures; this module owns the unit surface.
"""
import contextlib
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import (JsonlSink, ListSink, Metrics, Tracer, read_jsonl,
                       set_tracer)
from repro.obs import profile as profile_lib
from repro.obs import trace as trace_lib


@pytest.fixture
def list_sink():
    """Install a ListSink tracer globally; always restore the old one."""
    sink = ListSink()
    old = set_tracer(Tracer(sink))
    yield sink
    set_tracer(old)


# ---------------------------------------------------------------------------
# trace core
# ---------------------------------------------------------------------------
def test_default_tracer_disabled_and_noop():
    tr = Tracer()                     # no sink -> NullSink
    assert tr.enabled is False
    tr.event("x", a=1)                # must not raise, must not record
    span = tr.span("y")
    assert span is trace_lib.NULL_SPAN    # shared no-op, no allocation
    with span:
        span.set(z=2)                 # no-op


def test_span_nesting_parent_ids_and_seq_order():
    sink = ListSink()
    tr = Tracer(sink)
    with tr.span("outer", a=1) as outer:
        tr.event("evt", k="v")
        with tr.span("inner") as inner:
            inner.set(result=7)
        outer.set(done=True)
    recs = sink.records
    assert [r["name"] for r in recs] == ["evt", "inner", "outer"]
    evt, inner_r, outer_r = recs
    # events parent to the innermost OPEN span; spans carry their own id
    assert evt["type"] == "event" and evt["parent"] == outer_r["span"]
    assert inner_r["parent"] == outer_r["span"]
    assert outer_r["parent"] is None
    # spans emit at exit: child seq < parent seq, seq strictly increasing
    seqs = [r["seq"] for r in recs]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    assert inner_r["seq"] < outer_r["seq"]
    # set() lands mid-flight attrs on the final record
    assert inner_r["attrs"] == {"result": 7}
    assert outer_r["attrs"] == {"a": 1, "done": True}
    assert outer_r["dur_s"] >= 0.0 and outer_r["dur_s"] >= inner_r["dur_s"]


def test_jsonl_round_trip_and_sanitisation(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    tr = Tracer(JsonlSink(path))
    with tr.span("s", pred=float("inf")):
        tr.event("e", nan=float("nan"), npval=np.int64(3), arr=np.arange(2))
    tr.close()
    assert tr.enabled is False        # close() disarms the tracer
    recs = read_jsonl(path)
    assert [r["name"] for r in recs] == ["e", "s"]
    # strict JSON: non-finite floats become null, numpy scalars unwrap,
    # arbitrary objects fall back to repr
    assert recs[0]["attrs"]["nan"] is None
    assert recs[0]["attrs"]["npval"] == 3
    assert isinstance(recs[0]["attrs"]["arr"], list)
    assert recs[1]["attrs"]["pred"] is None


def test_configure_installs_and_rejects_both(tmp_path):
    old = trace_lib.get_tracer()
    try:
        with pytest.raises(ValueError, match="not both"):
            trace_lib.configure(path="x", sink=ListSink())
        tr = trace_lib.configure(path=str(tmp_path / "t.jsonl"))
        assert trace_lib.get_tracer() is tr and tr.enabled
        tr.close()
    finally:
        set_tracer(old)


def test_spans_land_on_the_profilers_host_plane(tmp_path):
    """Under a running profile every span is a host-plane event of its
    bare name, on the profile's clock: inside the window annotation and
    nested as the tracer nested it.  Events stay in the sink."""
    import glob

    from jax.profiler import ProfileData, TraceAnnotation

    sink = ListSink()
    tr = Tracer(sink)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("bench/trace_window"):
            with tr.span("serve/tick", tick=3):
                with tr.span("serve/tick/dispatch", plan="p"):
                    jnp.ones(4).block_until_ready()
                tr.event("serve/fault", kind="slow")
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                           "*.xplane.pb"))
    seen = {e.name: (e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}
    lo, hi = seen["bench/trace_window"]
    t0, t1 = seen["serve/tick"]
    d0, d1 = seen["serve/tick/dispatch"]
    assert lo <= t0 <= d0 <= d1 <= t1 <= hi
    assert "serve/fault" not in seen
    # the sink's records keep their schema
    assert [r["name"] for r in sink.records] == [
        "serve/tick/dispatch", "serve/fault", "serve/tick"]
    assert sink.records[-1]["attrs"] == {"tick": 3}


def test_interval_records_explicit_times_off_the_stack():
    sink = ListSink()
    tr = Tracer(sink)
    with tr.span("outer") as outer:
        tr.interval("serve/request", 1.5, 4.0, uid=7, queue_s=1.0)
        assert tr._stack == [outer.span_id]       # the stack is untouched
        tr.event("after")
    interval, after, outer_r = sink.records
    assert interval == {"type": "span", "name": "serve/request",
                        "span": interval["span"], "parent": None,
                        "ts": 1.5, "dur_s": 2.5, "seq": 0,
                        "attrs": {"uid": 7, "queue_s": 1.0}}
    assert interval["span"] != outer_r["span"]
    assert after["parent"] == outer_r["span"]
    off = Tracer()
    off.interval("serve/request", 0.0, 1.0, uid=1)    # disabled: no-op
    assert off._seq == 0


def test_disabled_tracer_builds_no_annotation(monkeypatch):
    """Tracing off, a span is the shared no-op: no record, no profiler
    annotation.  Tracing on, each span enters one."""
    built = []
    monkeypatch.setattr(trace_lib, "_annotation",
                        lambda name: built.append(name) or
                        contextlib.nullcontext())
    off = Tracer()
    with off.span("serve/tick"):
        pass
    assert built == []
    with Tracer(ListSink()).span("serve/tick"):
        pass
    assert built == ["serve/tick"]


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------
def test_metrics_counter_gauge_histogram():
    m = Metrics()
    m.counter("c").inc()
    m.counter("c").inc(4)             # get-or-create returns the same object
    m.gauge("g").set(0.5)
    h = m.histogram("h")
    for v in range(100):
        h.observe(float(v))
    assert m.counter("c").value == 5
    assert h.count == 100
    assert h.percentile(50) == 49.0   # nearest-rank
    assert h.percentile(99) == 98.0
    snap = m.snapshot()
    assert snap["counters"]["c"] == 5
    assert snap["gauges"]["g"] == 0.5
    assert snap["histograms"]["h"]["count"] == 100
    assert math.isnan(Metrics().histogram("empty").percentile(50))


def test_histogram_is_bounded():
    h = Metrics().histogram("h")
    for v in range(5000):
        h.observe(float(v))
    assert h.count == 4096            # bounded deque: old samples roll off
    assert h.percentile(100) == 4999.0


# ---------------------------------------------------------------------------
# measured profiler (tiny shapes: this is the quick-loop version of the
# CI --obs-smoke sweep)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def swept():
    from repro.core.factorization import MOBILE_VMEM_BUDGET

    return profile_lib.profile_families(
        ("lstm", "rwkv6"), vmem_budget=MOBILE_VMEM_BUDGET, repeats=1,
        warmup=1, max_points=2,
        hook_kwargs={"lstm": {"batch": 2, "seq_len": 16},
                     "rwkv6": {"seq_len": 32, "n_bh": 2, "target": 8}})


def test_profiler_sweeps_both_families(swept):
    assert swept.families() == ["lstm", "rwkv6"]
    assert swept.device_kind == profile_lib.device_kind()
    assert swept.key.endswith(f"/vmem{swept.vmem_budget}")
    for fam in ("lstm", "rwkv6"):
        pts = [p for p in swept.points if p.family == fam]
        assert len(pts) >= 2          # >= 2 tiling points per family
        for p in pts:
            assert p.measured_s > 0 and math.isfinite(p.measured_s)
            assert p.point            # tiling coordinates recorded


def test_profile_save_load_round_trip(swept, tmp_path):
    path = swept.save(str(tmp_path / "profile.json"))
    loaded = profile_lib.DeviceProfile.load(path)
    assert loaded.to_json() == swept.to_json()
    assert loaded.key == swept.key


def test_model_vs_measured_report(swept):
    rows = profile_lib.model_vs_measured(swept, threshold=3.0)
    assert len(rows) == len(swept.points)
    for r in rows:
        assert r["finite"]            # every profiled point has a model
        assert r["ratio"] > 0
    # interpret-mode Pallas on CPU vs a TPU roofline: uniformly diverged —
    # the ratio is a relative diagnostic here (ROADMAP §Observability)
    assert all(r["diverged"] for r in rows)
    with pytest.raises(ValueError, match="> 1"):
        profile_lib.model_vs_measured(swept, threshold=1.0)


def test_calibrate_consumes_profile(swept):
    from repro.core.scheduler import Plan, Scheduler, SyntheticLoadSensor

    def boom():
        raise AssertionError("profiled plan must not run during calibrate")

    s = Scheduler(SyntheticLoadSensor(0.0))
    s.register(Plan("fused_seq", boom))
    s.register(Plan("chunked_scan", boom))
    s.calibrate(profile=swept.best_latencies())
    for name in ("fused_seq", "chunked_scan"):
        assert math.isfinite(s.plans[name].base_latency_s)
        assert s.plans[name].base_latency_s > 0
    # rename maps family plan names onto the scheduler's registry
    renamed = swept.best_latencies(rename={"fused_seq": "accel"})
    assert "accel" in renamed and "fused_seq" not in renamed


def test_unknown_family_hook_raises():
    from repro.core import plans as plans_lib

    fam = plans_lib.get_family("lstm")
    assert fam.profile_hook is not None
    with pytest.raises(ValueError, match="no profile_hook"):
        bare = dataclasses.replace(fam, profile_hook=None)
        orig = plans_lib.get_family
        try:
            plans_lib.get_family = lambda name: bare
            profile_lib.profile_families(("lstm",), max_points=1)
        finally:
            plans_lib.get_family = orig


# ---------------------------------------------------------------------------
# instrumented scheduler + plan dispatch
# ---------------------------------------------------------------------------
def test_scheduler_choose_and_run_emit(list_sink):
    from repro.core.scheduler import Plan, Scheduler, SyntheticLoadSensor

    s = Scheduler(SyntheticLoadSensor(0.25))
    s.register(Plan("a", lambda: 1, base_latency_s=0.01, shared=True))
    s.register(Plan("b", lambda: 2, base_latency_s=0.5))
    out, d = s.run()
    assert out == 1 and d.plan == "a"
    names = [r["name"] for r in list_sink.records]
    assert names == ["sched/choose", "sched/run"]
    choose, run = list_sink.records
    assert choose["attrs"]["plan"] == "a"
    assert choose["attrs"]["load"] == 0.25
    assert math.isfinite(choose["attrs"]["predicted_s"])
    assert run["type"] == "span"
    assert run["attrs"]["plan"] == "a" and run["attrs"]["latency_s"] > 0


def test_scheduler_calibrate_emits_source(list_sink):
    from repro.core.scheduler import Plan, Scheduler, SyntheticLoadSensor

    s = Scheduler(SyntheticLoadSensor(0.0))
    s.register(Plan("seeded", lambda: None))
    s.register(Plan("timed", lambda: None))
    s.calibrate(repeats=1, profile={"seeded": 0.003})
    evts = {r["attrs"]["plan"]: r["attrs"]["source"]
            for r in list_sink.records if r["name"] == "sched/calibrate"}
    assert evts == {"seeded": "profile", "timed": "measured"}


def test_lstm_dispatch_event_records_tiling(list_sink):
    from repro.configs.mobirnn_lstm import LSTMConfig
    from repro.core import lstm

    cfg = dataclasses.replace(LSTMConfig(), seq_len=8)
    params = lstm.init_params(jax.random.PRNGKey(0), cfg)
    x = jnp.ones((2, cfg.seq_len, cfg.input_dim), jnp.float32)
    lstm.forward_fused_seq(params, x, cfg)
    evts = [r for r in list_sink.records if r["name"] == "plan/dispatch"]
    assert len(evts) == 1
    a = evts[0]["attrs"]
    assert a["family"] == "lstm" and a["plan"] == "fused_seq"
    assert a["block_b"] >= 1 and (a["batch"], a["seq_len"]) == (2, 8)
    assert "fallback" not in a


def test_lstm_dispatch_event_flags_fallback(list_sink):
    from repro.configs.mobirnn_lstm import LSTMConfig
    from repro.core import lstm

    cfg = dataclasses.replace(LSTMConfig(), seq_len=4)
    params = lstm.init_params(jax.random.PRNGKey(0), cfg)
    x = jnp.ones((1, cfg.seq_len, cfg.input_dim), jnp.float32)
    # a budget the weight stack itself cannot fit: the silent per-cell
    # fallback must become a visible dispatch event
    lstm.forward_fused_seq(params, x, cfg, vmem_budget=64)
    evts = [r for r in list_sink.records if r["name"] == "plan/dispatch"]
    assert len(evts) == 1
    assert evts[0]["attrs"]["fallback"] == "fused_cell"


def test_rwkv_dispatch_event(list_sink):
    from repro.kernels import wkv6 as wkv6_lib

    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 5)
    n_bh, T, dk, dv = 2, 8, 4, 4
    r = jax.random.normal(ks[0], (n_bh, T, dk))
    k = jax.random.normal(ks[1], (n_bh, T, dk))
    v = jax.random.normal(ks[2], (n_bh, T, dv))
    logw = -jnp.exp(jax.random.normal(ks[3], (n_bh, T, dk)))
    u = jax.random.normal(ks[4], (n_bh, dk))
    state = jnp.zeros((n_bh, dk, dv))
    wkv6_lib.wkv6(r, k, v, logw, u, state, chunk=4)
    evts = [rec for rec in list_sink.records
            if rec["name"] == "plan/dispatch"]
    assert len(evts) == 1
    a = evts[0]["attrs"]
    assert a["family"] == "rwkv6" and a["plan"] == "chunked_scan"
    assert a["chunk"] == 4 and a["seq_len"] == T and a["n_bh"] == n_bh


def test_disabled_tracer_changes_nothing():
    """Tracing off vs on must be bit-identical through the fused plan."""
    from repro.configs.mobirnn_lstm import LSTMConfig
    from repro.core import lstm

    cfg = dataclasses.replace(LSTMConfig(), seq_len=8)
    params = lstm.init_params(jax.random.PRNGKey(0), cfg)
    x = jnp.ones((2, cfg.seq_len, cfg.input_dim), jnp.float32)
    base = lstm.forward_fused_seq(params, x, cfg)      # NullSink default
    old = set_tracer(Tracer(ListSink()))
    try:
        traced = lstm.forward_fused_seq(params, x, cfg)
    finally:
        set_tracer(old)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(traced))
