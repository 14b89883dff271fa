"""Structured tracing: spans + events over a pluggable sink.

Design constraints (ISSUE 7 tentpole):

* **Zero overhead when disabled.**  The process-global tracer defaults to
  a ``NullSink``; ``Tracer.enabled`` is a plain attribute read, so a hot
  call site guards with ``if tr.enabled:`` and pays one branch — no attr
  dicts are built, no records allocated.  ``tr.event(...)`` /
  ``tr.span(...)`` are also safe to call unguarded (they early-return /
  return a shared no-op span), but hot loops should guard so the kwargs
  dict is never constructed.
* **Single-threaded span nesting.**  The serving loop and scheduler run
  on one thread; nesting is a plain list stack.  Each record carries a
  monotonically increasing ``seq`` plus ``span``/``parent`` ids so
  ordering and nesting reconstruct offline.
* **JSONL export.**  One JSON object per line; ``read_jsonl`` is the
  inverse.  Span records are emitted at span *exit* (so a child's record
  precedes its parent's) carrying ``ts`` (entry time) and ``dur_s``.
* **The profiler's clock.**  While the tracer is enabled, every span
  also enters a ``jax.profiler.TraceAnnotation`` of its bare name, so
  under a running profile the program's spans sit on the host plane
  beside the device's operations.  ``Tracer.interval`` records a span
  that is already over (a request's life, which crosses generator
  yields); it goes to the sink only.

Record schema (see ROADMAP §Observability for the full event-name list —
serving admission emits ``serve/admit`` per admitted request and, under
chunked prefill, one ``serve/prefill_start`` plus one
``serve/prefill_chunk`` per fixed-shape chunk dispatch):

    {"type": "span"|"event", "name": str, "seq": int, "ts": float,
     "span": int|None, "parent": int|None, "dur_s": float (spans only),
     "attrs": {...}}

No imports from the rest of ``repro`` — core/kernels/serving import
*this* module, never the reverse.
"""
from __future__ import annotations

import json
import time
from typing import Any, Callable, TextIO


def _jsonable(obj: Any) -> Any:
    """Fallback encoder: numpy scalars -> python, array-likes -> lists,
    anything else -> repr."""
    try:
        return obj.item()          # numpy scalar / 0-d array
    except ValueError:             # size > 1 array: keep the values
        try:
            return obj.tolist()
        except Exception:
            return repr(obj)
    except AttributeError:
        return repr(obj)


class NullSink:
    """The default: tracing off.  ``enabled`` is False and ``emit`` is
    unreachable from guarded call sites."""
    enabled = False

    def emit(self, record: dict) -> None:  # pragma: no cover - guarded off
        pass

    def flush(self) -> None:
        pass

    def close(self) -> None:
        pass


class ListSink:
    """In-memory sink for tests."""
    enabled = True

    def __init__(self) -> None:
        self.records: list[dict] = []

    def emit(self, record: dict) -> None:
        self.records.append(record)

    def flush(self) -> None:
        pass

    def close(self) -> None:
        pass


class JsonlSink:
    """One JSON object per line, append-as-you-go (a crash keeps the
    prefix).  Non-finite floats are JSON-sanitised to ``None`` so the
    file stays parseable by strict readers."""
    enabled = True

    def __init__(self, path: str):
        self.path = str(path)
        self._fh: TextIO = open(self.path, "w", encoding="utf-8")

    def emit(self, record: dict) -> None:
        self._fh.write(json.dumps(_sanitize(record), default=_jsonable))
        self._fh.write("\n")

    def flush(self) -> None:
        self._fh.flush()

    def close(self) -> None:
        if not self._fh.closed:
            self._fh.flush()
            self._fh.close()


def _sanitize(obj: Any) -> Any:
    """Replace non-finite floats with None, recursively (strict JSON has
    no Infinity/NaN literals; plan predictions can legitimately be inf)."""
    if isinstance(obj, float):
        return obj if obj == obj and obj not in (float("inf"), float("-inf")) else None
    if isinstance(obj, dict):
        return {k: _sanitize(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_sanitize(v) for v in obj]
    return obj


def read_jsonl(path: str) -> list[dict]:
    with open(path, "r", encoding="utf-8") as fh:
        return [json.loads(line) for line in fh if line.strip()]


class _NullSpan:
    """Shared no-op context manager returned when tracing is off."""
    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> None:
        pass


NULL_SPAN = _NullSpan()


def _annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` of ``name``; jax is imported
    here, so this module stays importable without it."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


class Span:
    """Emitted as ONE record at exit; ``set`` adds attrs mid-flight.  Its
    bare name is annotated on the profiler's host plane meanwhile."""
    __slots__ = ("_tracer", "name", "attrs", "span_id", "parent_id", "_t0",
                 "_ann")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self.span_id = tracer._new_id()
        self.parent_id: int | None = None
        self._t0 = 0.0

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __enter__(self) -> "Span":
        tr = self._tracer
        self.parent_id = tr._stack[-1] if tr._stack else None
        tr._stack.append(self.span_id)
        self._ann = _annotation(self.name)
        self._ann.__enter__()
        self._t0 = tr.clock()
        return self

    def __exit__(self, *exc) -> bool:
        tr = self._tracer
        dur = tr.clock() - self._t0
        self._ann.__exit__(None, None, None)
        if tr._stack and tr._stack[-1] == self.span_id:
            tr._stack.pop()
        tr._emit({
            "type": "span", "name": self.name, "span": self.span_id,
            "parent": self.parent_id, "ts": self._t0, "dur_s": dur,
            "attrs": self.attrs,
        })
        return False


class Tracer:
    """Span/event frontend over a sink.  ``Tracer()`` is disabled (NullSink)."""

    def __init__(self, sink=None, clock: Callable[[], float] = time.perf_counter):
        self.sink = sink if sink is not None else NullSink()
        self.enabled: bool = self.sink.enabled
        self.clock = clock
        self._seq = 0
        self._next = 0
        self._stack: list[int] = []

    def _new_id(self) -> int:
        self._next += 1
        return self._next

    def _emit(self, record: dict) -> None:
        record["seq"] = self._seq
        self._seq += 1
        self.sink.emit(record)

    def event(self, name: str, **attrs) -> None:
        """Point-in-time record, parented to the innermost open span."""
        if not self.enabled:
            return
        self._emit({
            "type": "event", "name": name, "span": None,
            "parent": self._stack[-1] if self._stack else None,
            "ts": self.clock(), "attrs": attrs,
        })

    def span(self, name: str, **attrs):
        """Context manager; a shared no-op when disabled."""
        if not self.enabled:
            return NULL_SPAN
        return Span(self, name, attrs)

    def interval(self, name: str, t0: float, t1: float, **attrs) -> None:
        """Record a finished span from ``t0`` to ``t1`` on ``clock``.  It
        has no parent and leaves the span stack alone: it is for work
        that outlives the spans around it, such as a request's life."""
        if not self.enabled:
            return
        self._emit({
            "type": "span", "name": name, "span": self._new_id(),
            "parent": None, "ts": t0, "dur_s": t1 - t0, "attrs": attrs,
        })

    def flush(self) -> None:
        self.sink.flush()

    def close(self) -> None:
        self.sink.close()
        self.enabled = False
        self.sink = NullSink()


#: process-global tracer; NullSink by default so instrumented hot paths
#: pay one ``enabled`` branch until someone calls configure()/set_tracer()
_GLOBAL = Tracer()


def get_tracer() -> Tracer:
    return _GLOBAL


def set_tracer(tracer: Tracer) -> Tracer:
    """Install ``tracer`` globally; returns the previous one (so callers
    can restore it — tests and --trace both do)."""
    global _GLOBAL
    old = _GLOBAL
    _GLOBAL = tracer
    return old


def configure(path: str | None = None, sink=None) -> Tracer:
    """Install a global tracer: JSONL to ``path``, an explicit ``sink``,
    or (neither) the disabled default."""
    if path is not None and sink is not None:
        raise ValueError("pass path or sink, not both")
    if path is not None:
        sink = JsonlSink(path)
    tracer = Tracer(sink)
    set_tracer(tracer)
    return tracer
