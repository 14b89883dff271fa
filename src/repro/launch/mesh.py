"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax use).

Production topology (TPU v5e): one pod = 256 chips as (data=16, model=16);
multi-pod = 2 pods as (pod=2, data=16, model=16).  The 'pod' axis carries
only data parallelism (gradient all-reduce across DCN/ICI), 'model' carries
tensor/expert/sequence parallelism, 'data' carries batch + FSDP weight
sharding.
"""
from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import AxisType


def _auto(n_axes: int) -> tuple[AxisType, ...]:
    """Auto axis types: the partitioner places what ``constrain`` leaves
    open (jax.make_mesh defaults to Explicit axes, which refuse the
    with_sharding_constraint calls the model code makes)."""
    return (AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False,
                         data: int = 16, model: int = 16):
    """Default production topology is (16, 16) / (2, 16, 16); `data`/`model`
    allow aspect-ratio ablations over the same 256 chips per pod
    (EXPERIMENTS.md §Perf iteration D)."""
    assert data * model == 256, (data, model)
    shape = (2, data, model) if multi_pod else (data, model)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} — the "
            "dry-run must set XLA_FLAGS=--xla_force_host_platform_device_count"
            " before any jax import (see launch/dryrun.py)")
    return jax.make_mesh(shape, axes, devices=devices[:n],
                         axis_types=_auto(len(shape)))


def make_host_mesh(model_axis: int = 1):
    """Tiny mesh on the real local device(s) for tests/examples."""
    devices = jax.devices()
    n = len(devices)
    data = max(n // model_axis, 1)
    return jax.make_mesh((data, model_axis), ("data", "model"),
                         devices=devices[: data * model_axis],
                         axis_types=_auto(2))
