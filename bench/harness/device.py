"""The chip: refuse to run without one, place the compile cache, count
compiles, and describe the device in the result line."""
from __future__ import annotations

import os

from harness.manifest import ROOT

#: JAX's persistent compilation cache of this checkout: a fixed path, so
#: a later run of the same checkout finds every program again
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")

#: the process's compile counter, once ``configure_jax`` has run
COUNTER = None

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class CompileCounter:
    """Counts backend compiles and persistent-cache loads as they happen
    (``jax.monitoring``), so a run can say how many fell in its window."""

    def __init__(self) -> None:
        self.compiles = 0
        self.loads = 0
        self.compile_s = 0.0

    def __call__(self, event: str, duration_s: float, **_kw) -> None:
        if event == _BACKEND_COMPILE:
            self.compiles += 1
            self.compile_s += duration_s
        elif event == _CACHE_LOAD:
            self.loads += 1

    def snapshot(self) -> tuple[int, int]:
        return self.compiles, self.loads


def configure_jax(cache_dir: str | None = None) -> CompileCounter:
    """Persistent cache in the checkout (or where JAX_COMPILATION_CACHE_DIR
    says), every compile cached however short, and a compile counter."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", cache_dir or CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    return counter


def require_chips(n: int):
    """The first ``n`` TPU devices; raises NoAccelerator otherwise (never
    a fallback to the CPU)."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as err:
        raise NoAccelerator(f"JAX found no devices: {err}") from err
    if not devices or devices[0].platform != "tpu":
        raise NoAccelerator(
            f"JAX's first device is {devices[0].platform if devices else None}"
            f", not a TPU: this benchmark measures the chip only")
    if len(devices) < n:
        raise NoAccelerator(f"the cell asks for {n} chips, JAX sees "
                            f"{len(devices)}")
    return devices[:n]


def describe(devices) -> dict:
    """The result line's ``device``: as JAX reports it, with the peak
    bytes in use on the fullest chip."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": peak}
