"""The engine's own spans, as the program records them.

With tracing on, every span of the program's tracer is also a
``jax.profiler.TraceAnnotation`` of its bare name, so a traced run's
profile holds the engine's ``serve/*`` spans on its host plane, on the
clock of the device's operations.  ``spans`` reads them from there.  A
request's life (``serve/request``: submit to first token, with its
``queue_s`` and ``lane_s``) is recorded after the fact and reaches the
sink only; ``request_p95`` reads it from the run's records.

A program without these spans gives an empty list and ``None``: the
readers then leave their metric out.
"""
from __future__ import annotations

import functools
import os

from harness import stats, xtrace

PREFIX = "serve/"


@functools.lru_cache(maxsize=4)      # both idle readers read one profile
def _read(path: str, mtime: float) -> tuple[tuple[str, float, float], ...]:
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns))
    return tuple(sorted(out, key=lambda sp: sp[1]))


def spans(ctx) -> list[tuple[str, float, float]]:
    """(name, start_ns, end_ns) of the engine's spans on the host plane
    of the run's profile, by start."""
    log_dir = getattr(ctx.capture, "log_dir", None)
    if not log_dir:
        return []
    try:
        path = xtrace.newest_xplane(log_dir)
    except FileNotFoundError:
        return []
    return list(_read(path, os.path.getmtime(path)))


def idle_per_span(ctx, family: str) -> float | None:
    """Milliseconds of device idle, per ``family`` span that starts in the
    trace window, over the idle gaps whose midpoint falls in a ``family``
    span or one of its ``family/...`` children (innermost span wins, as
    in the breakdown)."""
    sp = spans(ctx)
    lo, hi = ctx.trace.window
    n = sum(1 for name, s, _ in sp if name == family and lo <= s < hi)
    if not n:
        return None
    idle = sum(sec for name, sec in
               xtrace.label_gaps(xtrace.idle_gaps(ctx.trace), sp)
               if name == family or name.startswith(family + "/"))
    return idle * 1e3 / n


def request_p95(ctx, key: str) -> float | None:
    """95th percentile (nearest rank), in ms, of ``key`` (``queue_s`` or
    ``lane_s``) over the ``serve/request`` records of the requests due in
    the measured window."""
    values = []
    for r in ctx.records:
        if r.get("type") != "span" or r.get("name") != "serve/request":
            continue
        req = ctx.requests.get(r["attrs"]["uid"])
        if req is not None and req.phase == "window":
            values.append(r["attrs"][key] * 1e3)
    return stats.percentile(values, 95) if values else None
