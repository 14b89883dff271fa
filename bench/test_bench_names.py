"""The names the device-trace readers look for, pinned to the program.

The readers find the decode tick, the admission prefill and the fused
LSTM kernel in a profile by name: the jit names of the engine's
programs (the trace's ``XLA Modules`` line) and the custom call of the
Pallas kernel (its ``XLA Ops`` line).  A refactor that renames one of
them would silently drop a metric; these tests fail instead.  The
engine's programs are lowered at a tiny size on the CPU; the kernel's
name is read from the cell's own forward (``tests/test_tpu_compile.py``
checks that the compiled kernel takes it)."""
from __future__ import annotations

import os
import re
import sys

import jax
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

from harness import manifest, xtrace  # noqa: E402


def _needles(*readers: str, attr: str) -> set[tuple[str, ...]]:
    return {getattr(manifest.metric_reader(r), attr) for r in readers}


def _found(name: str, needles: tuple[str, ...]) -> bool:
    return xtrace.matching({name: (1, 1.0)}, needles) == (1, 1.0)


@pytest.fixture(scope="module")
def engine():
    from repro.configs import get_arch
    from repro.models import registry
    from repro.partitioning import split
    from repro.serving import EngineConfig, SlotEngine

    model = registry.build(get_arch("rwkv6-3b").reduced())
    params, _ = split(model.init(jax.random.PRNGKey(0)))
    return SlotEngine(model, params, config=EngineConfig(
        n_slots=2, max_seq=32, prefill_chunk_len=8, prefill_lanes=1))


def _module(lowered) -> str:
    return re.search(r"module @(\S+)", lowered.as_text()).group(1)


def test_decode_tick_program_name(engine):
    plan = engine.scheduler.plans["decode/base"].fn
    batch = dict(engine.manager.tick_batch(), poison=engine._no_poison)
    name = _module(plan.lower(engine.params, engine.manager.cache, batch))
    assert name == "jit_plan"
    for needles in _needles("decode_tick_ms", "decode_mfu", attr="PROGRAMS"):
        assert _found(f"{name}(12)", needles)


def test_chunk_prefill_program_name(engine):
    batch = engine._prefill_batch(np.zeros((1, 8), np.int32))
    name = _module(engine._prefill_chunk.lower(
        engine.params, engine._scratch_abs, batch, engine._first_true))
    assert name == "jit_prefill_chunk_sample"
    for needles in _needles("prefill_ms_per_ktok", "prefill_mfu",
                            attr="PROGRAMS"):
        assert _found(f"{name}(3)", needles)


def test_lstm_kernel_call_name_in_the_cell_forward():
    """The ``har-b1`` cell's forward calls the fused kernel through the
    jitted ``_lstm_seq_call``, whose name the kernel's custom call takes
    on the chip."""
    cell = manifest.resolve_cell(manifest.load_manifest(), "har-b1")
    _, _, fwd, pool = manifest.driver(cell.config).build(cell.config, 5)
    jaxpr = jax.make_jaxpr(fwd)(pool[:1])
    names = set()

    def walk(jx):
        for eqn in jx.eqns:
            if "name" in eqn.params:
                names.add(eqn.params["name"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert "_lstm_seq_call" in names
    for needles in _needles("lstm_seq_us", "lstm_seq_roofline",
                            attr="KERNEL"):
        assert _found("_lstm_seq_call", needles)
