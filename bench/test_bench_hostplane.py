"""The engine's spans on the profile's host plane, and the readers that
turn them into per-layer metrics: a CPU profile recorded around the
program's tracer spans, read back by ``harness.hostplane``, with device
idle gaps placed by hand inside the spans it finds."""
from __future__ import annotations

import os
import sys
import time
import types

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

from harness import hostplane, manifest, xtrace  # noqa: E402

NEW = ("queue_p95_ms", "lane_p95_ms", "tick_idle_ms", "chunk_idle_ms")


def _phases(tracer, parent: str, kids: tuple[str, ...], **attrs) -> None:
    with tracer.span(parent, **attrs):
        for kid in kids:
            with tracer.span(f"{parent}/{kid}"):
                time.sleep(0.003)


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """Two ticks and one chunk inside ``bench/trace_window``, one tick
    before it; returns the log directory and the tracer's records."""
    import jax

    from repro.obs import ListSink, Tracer

    log_dir = str(tmp_path_factory.mktemp("profile"))
    sink = ListSink()
    tr = Tracer(sink)
    jax.profiler.start_trace(log_dir)
    try:
        _phases(tr, "serve/tick", ("prepare", "dispatch", "sync"), tick=0)
        with jax.profiler.TraceAnnotation(xtrace.WINDOW):
            for tick in (1, 2):
                _phases(tr, "serve/tick", ("prepare", "dispatch", "sync"),
                        tick=tick)
            _phases(tr, "serve/chunk", ("dispatch", "sync"), uid=7)
    finally:
        jax.profiler.stop_trace()
    return log_dir, sink.records


def _ctx(log_dir, **kw):
    return types.SimpleNamespace(
        capture=types.SimpleNamespace(log_dir=log_dir), **kw)


def _window(log_dir) -> tuple[float, float]:
    """The ``bench/trace_window`` annotation on the host plane (ns)."""
    from jax.profiler import ProfileData

    [(lo, hi)] = [(e.start_ns, e.start_ns + e.duration_ns)
                  for plane in ProfileData.from_file(
                      xtrace.newest_xplane(log_dir)).planes
                  if plane.name.startswith("/host:")
                  for line in plane.lines for e in line.events
                  if e.name == xtrace.WINDOW]
    return lo, hi


def test_spans_read_back_from_the_host_plane(profiled):
    log_dir, records = profiled
    sp = hostplane.spans(_ctx(log_dir))
    names = [n for n, _, _ in sp]
    # every tracer span, by its bare name (attributes stay out of it)
    assert sorted(names) == sorted(r["name"] for r in records)
    assert names.count("serve/tick") == 3
    lo, hi = _window(log_dir)
    inside = [s for s in sp if lo <= s[1] and s[2] <= hi]
    assert len(inside) == len(sp) - 4          # the first tick is outside
    # on the profiler's clock each span keeps its length and nesting
    for (name, a, b), r in zip(sorted(sp, key=lambda s: s[1]),
                               sorted(records, key=lambda r: r["ts"])):
        assert name == r["name"]
        assert (b - a) * 1e-9 == pytest.approx(r["dur_s"], abs=5e-4)


def test_no_profile_or_no_spans_reads_nothing(tmp_path):
    assert hostplane.spans(_ctx(None)) == []
    assert hostplane.spans(_ctx(str(tmp_path))) == []
    ctx = _ctx(str(tmp_path), records=[], requests={},
               trace=xtrace.Trace(window=(0.0, 1.0), devices={}))
    for name in NEW:
        assert manifest.metric_reader(name).read(ctx) is None


def _device_trace(lo, hi, gaps):
    """A device busy from ``lo`` to ``hi`` but for ``gaps`` (ns)."""
    ops, t = [], lo
    for a, b in sorted(gaps):
        ops.append(("fusion", t, a - t))
        t = b
    ops.append(("fusion", t, hi - t))
    return xtrace.Trace(window=(lo, hi),
                        devices={"/device:TPU:0": {xtrace.OPS_LINE: ops}})


def test_idle_readers_attribute_gaps_to_their_spans(profiled):
    log_dir, _ = profiled
    sp = hostplane.spans(_ctx(log_dir))
    lo, hi = _window(log_dir)

    def middle(name, k, width):
        _, a, b = [s for s in sp if s[0] == name and s[1] >= lo][k]
        mid = 0.5 * (a + b)
        return (mid - width / 2, mid + width / 2)

    ms = 1e6
    gaps = [middle("serve/tick/sync", 0, 1.0 * ms),
            middle("serve/tick/prepare", 1, 0.5 * ms),
            middle("serve/chunk/dispatch", 0, 2.0 * ms)]
    ctx = _ctx(log_dir, trace=_device_trace(lo, hi, gaps))
    # 1.5 ms over the two ticks in the window; 2 ms over the one chunk
    tick = manifest.metric_reader("tick_idle_ms").read(ctx)
    chunk = manifest.metric_reader("chunk_idle_ms").read(ctx)
    assert tick == pytest.approx(0.75, rel=1e-6)
    assert chunk == pytest.approx(2.0, rel=1e-6)
    # the same gaps as the breakdown labels them
    idle = dict(xtrace.breakdown(ctx.trace, sp)["idle_gaps"])
    assert idle["serve/tick/sync"] + idle["serve/tick/prepare"] == \
        pytest.approx(2 * tick * 1e-3)
    assert idle["serve/chunk/dispatch"] == pytest.approx(chunk * 1e-3)


def test_request_percentiles_read_the_window_requests():
    """``queue_s``/``lane_s`` of ``serve/request``, requests due in the
    window only, nearest-rank 95th percentile, in ms."""
    rec = lambda phase: types.SimpleNamespace(phase=phase)
    requests = {u: rec("window") for u in range(20)}
    requests[99] = rec("ramp")
    records = [{"type": "span", "name": "serve/request", "ts": 0.0,
                "dur_s": 0.003 * u, "attrs": {"uid": u, "queue_s": 0.001 * u,
                                              "lane_s": 0.002 * u}}
               for u in list(range(20)) + [99]]
    records.append({"type": "event", "name": "serve/admit", "ts": 0.0,
                    "attrs": {"uid": 3, "queue_s": 9.0}})
    ctx = types.SimpleNamespace(records=records, requests=requests)
    # rank ceil(0.95 * 20) = 19 of 0..19 -> uid 18
    assert manifest.metric_reader("queue_p95_ms").read(ctx) == \
        pytest.approx(18.0)
    assert manifest.metric_reader("lane_p95_ms").read(ctx) == \
        pytest.approx(36.0)
