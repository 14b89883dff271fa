"""From a JAX profiler trace to device metrics.

A run with ``--trace 1`` records a few steady seconds with
``jax.profiler``, marked on the host by a ``bench/trace_window``
annotation.  ``load`` turns the ``.xplane.pb`` into a plain ``Trace``
(the device planes' op and module lines and the window, nothing else);
everything after that works on the plain form, which ``save`` writes
and ``Trace.from_json`` reads back, so a trimmed trace can be kept as a
test sample.

* busy time: the union of the intervals in which an operation ran on a
  device (its ``XLA Ops`` line), inside the window, averaged over chips;
* device time per program: the ``XLA Modules`` line, by module name;
* device time per operation: the ``XLA Ops`` line, by op name;
* idle gaps: the holes in the busy union, each labelled with the host
  span that was open at its midpoint.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re
from typing import Iterable

WINDOW = "bench/trace_window"
#: one plane per chip ("/device:TPU:0"), not its non-core or host planes
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Trace:
    """Events as (name, start_ns, dur_ns), per device and line."""
    window: tuple[float, float]                    # ns, profiler clock
    devices: dict[str, dict[str, list[tuple[str, float, float]]]]

    def to_json(self) -> dict:
        return {"window": list(self.window),
                "devices": {d: {ln: [list(e) for e in evs]
                                for ln, evs in lines.items()}
                            for d, lines in self.devices.items()}}

    @classmethod
    def from_json(cls, obj: dict) -> "Trace":
        return cls(window=tuple(obj["window"]),
                   devices={d: {ln: [tuple(e) for e in evs]
                                for ln, evs in lines.items()}
                            for d, lines in obj["devices"].items()})

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def op_name(raw: str) -> str:
    """An ``XLA Ops`` event's name: the TPU trace gives the whole HLO
    instruction (``%_lstm_seq_call.1 = (f32[...]) custom-call(...)``);
    keep the instruction's name (``_lstm_seq_call.1``)."""
    head, sep, _ = raw.partition(" = ")
    return head.lstrip("%") if sep else raw


def newest_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb``: the device planes' op and module lines, and
    the window annotation from the host plane."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    window = None
    devices: dict[str, dict[str, list]] = {}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    short = op_name if line.name == OPS_LINE else str
                    lines[line.name] = [(short(e.name), e.start_ns,
                                         e.duration_ns)
                                        for e in line.events]
            if lines:
                devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} annotation on the host")
    if not devices:
        raise ValueError(f"{path}: no device plane with {OPS_LINE!r}")
    return Trace(window=window, devices=devices)


def _clip(events: Iterable[tuple[str, float, float]], lo: float, hi: float):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def union(intervals: Iterable[tuple[float, float]]) -> list[list[float]]:
    """Merge overlapping intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _line(trace: Trace, device: str, line: str) -> list:
    lines = trace.devices[device]
    return lines.get(line) or lines.get(OPS_LINE) or []


def busy_s(trace: Trace) -> float:
    """Seconds in which any operation ran, averaged over the devices."""
    lo, hi = trace.window
    total = 0.0
    for dev in trace.devices:
        merged = union((a, b) for _, a, b in
                       _clip(_line(trace, dev, OPS_LINE), lo, hi))
        total += sum(b - a for a, b in merged)
    return total * 1e-9 / len(trace.devices)


def idle_gaps(trace: Trace) -> list[tuple[float, float]]:
    """Holes in the first device's busy union inside the window (ns)."""
    lo, hi = trace.window
    dev = sorted(trace.devices)[0]
    merged = union((a, b) for _, a, b in
                   _clip(_line(trace, dev, OPS_LINE), lo, hi))
    gaps, t = [], lo
    for a, b in merged:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def time_by_name(trace: Trace, line: str) -> dict[str, tuple[int, float]]:
    """name -> (events starting in the window, seconds inside it), summed
    over devices."""
    lo, hi = trace.window
    out: dict[str, list] = {}
    for dev in trace.devices:
        for name, s, d in trace.devices[dev].get(line, []):
            a, b = max(s, lo), min(s + d, hi)
            if b <= a:
                continue
            acc = out.setdefault(name, [0, 0.0])
            acc[0] += int(lo <= s < hi)
            acc[1] += (b - a) * 1e-9
    return {k: (v[0], v[1]) for k, v in out.items()}


def matching(table: dict[str, tuple[int, float]], needles: Iterable[str]
             ) -> tuple[int, float]:
    """Summed (count, seconds) of the entries whose name holds any needle."""
    needles = tuple(needles)
    n, s = 0, 0.0
    for name, (c, sec) in table.items():
        if any(x in name for x in needles):
            n += c
            s += sec
    return n, s


def label_gaps(gaps: list[tuple[float, float]],
               spans: list[tuple[str, float, float]]) -> list[tuple[str, float]]:
    """Each gap with the innermost host span open at its midpoint
    (``spans`` in ns on the trace's clock), or ``host/outside-spans``."""
    spans = sorted(spans, key=lambda sp: sp[1])
    starts = [sp[1] for sp in spans]
    longest = max((e - s for _, s, e in spans), default=0.0)
    out = []
    for a, b in gaps:
        mid = 0.5 * (a + b)
        best = None
        i = bisect.bisect_right(starts, mid) - 1
        while i >= 0 and starts[i] >= mid - longest:
            name, s, e = spans[i]
            if mid <= e and (best is None or e - s < best[2] - best[1]):
                best = (name, s, e)
            i -= 1
        out.append((best[0] if best else "host/outside-spans",
                    (b - a) * 1e-9))
    return out


def breakdown(trace: Trace, spans: list[tuple[str, float, float]],
              top: int = 10) -> dict:
    """The device operations that took most time, and idle time by what
    the host was doing, each at most ``top`` entries, largest first."""
    ops = time_by_name(trace, OPS_LINE)
    by_op = sorted(((k, v[1]) for k, v in ops.items()),
                   key=lambda kv: -kv[1])[:top]
    idle: dict[str, float] = {}
    for name, sec in label_gaps(idle_gaps(trace), spans):
        idle[name] = idle.get(name, 0.0) + sec
    by_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in by_op],
            "idle_gaps": [[k, v] for k, v in by_idle]}


def trimmed(trace: Trace, span_ns: float) -> Trace:
    """The first ``span_ns`` of the window, for a recorded test sample."""
    lo = trace.window[0]
    hi = min(trace.window[1], lo + span_ns)
    devices = {d: {ln: [e for e in evs if e[1] < hi and e[1] + e[2] > lo]
                   for ln, evs in lines.items()}
               for d, lines in trace.devices.items()}
    return Trace(window=(lo, hi), devices=devices)


def save(trace: Trace, path: str) -> None:
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(trace.to_json(), fh)


class Capture:
    """``with Capture(log_dir) as cap:`` traces the block; ``cap.t0`` and
    ``cap.t1`` (host ``perf_counter``) afterwards, and ``cap.load()``
    reads the trace (outside any timed part: it takes seconds)."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.trace: Trace | None = None
        self.t0 = self.t1 = 0.0

    def __enter__(self) -> "Capture":
        import shutil
        import time

        import jax

        shutil.rmtree(self.log_dir, ignore_errors=True)
        os.makedirs(self.log_dir, exist_ok=True)
        jax.profiler.start_trace(self.log_dir)
        self._ann = jax.profiler.TraceAnnotation(WINDOW)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        import time

        import jax

        self.t1 = time.perf_counter()
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        return False

    def load(self) -> Trace:
        self.trace = load(newest_xplane(self.log_dir))
        return self.trace

    def to_ns(self, t: float) -> float:
        """A host ``perf_counter`` time on the trace's clock."""
        return self.trace.window[0] + (t - self.t0) * 1e9
