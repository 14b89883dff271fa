"""The harness, run on the CPU at a tiny size past its look for a chip,
finds a sound program correct, and finds ``correct`` false when the timed
path is broken underneath (a token or an answer altered where it is
produced, a step that hands back its state unchanged) or when the control
is put in the program's place (``bench/control.py``: weights rounded to
fp8, or the reference in fp8, for the served model; the bfloat16 path for
the LSTM).  Each fault is applied from outside, by wrapping what the
driver builds."""
from __future__ import annotations

import contextlib
import os
import sys

import jax
import jax.numpy as jnp
import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import control as control_lib  # noqa: E402
import run as run_lib  # noqa: E402
from harness import manifest  # noqa: E402

MAN = manifest.load_manifest()
SEED = 2**33 + 5
#: requests due in the tiny serving window: 4 per second for 1.5 s
WINDOW_REQUESTS = 6


def _e2e(*names):
    return [m for m in MAN["end_to_end"] if m["name"] in names]


def _config(name: str) -> dict:
    cfg = manifest.load_json(os.path.join(BENCH, "configs", name + ".json"))
    cfg["_dir"] = os.path.join(BENCH, "configs")
    return cfg


def serve_cell() -> manifest.Cell:
    cfg = _config("rwkv6-3b-serve")
    # a vocabulary and outputs wide enough that near-ties, where a lower
    # precision flips a token, occur at all
    cfg["model"].update(n_layers=2, d_model=64, head_dim=32, d_ff=128,
                        vocab=8192, lora_rank=16)
    cfg["engine"].update(n_slots=4, prefill_chunk_len=8, max_seq=256,
                         queue_capacity=64)
    # tiny-size limit, from CPU readings over seeds 5-7: sound 0.6e-4 to
    # 1.4e-4, int8 weights 1.2e-4 to 4.6e-4, fp8 weights 2.0e-3 to 2.8e-3
    cfg["check"].update(block=16, served_gap_mean=6e-4)
    mix = {"kind": "open_loop", "rate_per_s": 4.0, "block": 2,
           "ramp_s": 0.5, "drain_s": 20, "trace_s": 0.5,
           "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                      "min": 4, "max": 30},
           "output": {"dist": "uniform", "min": 20, "max": 40}}
    return manifest.Cell("tiny-serve", 1, cfg, mix,
                         _e2e("ttft_p95_ms", "tbt_p95_ms", "setup_s"), [])


def har_cell() -> manifest.Cell:
    cfg = _config("mobirnn-har")
    cfg["model"]["seq_len"] = 16
    cfg["pool"] = 16
    mix = {"kind": "closed_loop_windows", "batch": 1, "warmup_windows": 2,
           "trace_offset_s": 0.2, "trace_s": 0.2}
    return manifest.Cell("tiny-har", 1, cfg, mix,
                         _e2e("window_ms", "setup_s"), [])


@pytest.fixture(scope="module")
def cpu():
    return jax.devices("cpu")[:1]


def _wrap_build(monkeypatch, cell, after=None):
    """Make the cell's driver build as usual, then hand what it built to
    ``after``."""
    if after is None:
        return
    drv = manifest.driver(cell.config)
    orig = drv.build
    monkeypatch.setattr(drv, "build", lambda *a: after(*orig(*a)))


def _control(cell, name):
    """The control ``name`` in the program's place, if it is one."""
    drv = manifest.driver(cell.config)
    if name in control_lib.CONTROLS[cell.config["driver"]]:
        return control_lib.in_place(drv, name)
    return contextlib.nullcontext()


def _decode_plans(engine, make):
    for plan in engine.scheduler.plans.values():
        plan.fn = make(plan.fn)


def token_altered(ref, w, engine):
    vocab = engine.cfg.vocab

    def make(orig):
        def fn(p, c, b):
            s, ok, c = orig(p, c, b)
            return (s + 1) % vocab, ok, c
        return fn

    _decode_plans(engine, make)
    return ref, w, engine


def state_unchanged(ref, w, engine):
    def make(orig):
        def fn(p, c, b):
            s, ok, _ = orig(p, jax.tree.map(jnp.copy, c), b)
            return s, ok, c
        return fn

    _decode_plans(engine, make)
    return ref, w, engine


SERVE_BROKEN = {"token": token_altered, "state": state_unchanged}


@pytest.mark.parametrize("fault", [None, *SERVE_BROKEN, "fp8", "ref-fp8"])
def test_serving_cell(fault, cpu, monkeypatch):
    cell = serve_cell()
    _wrap_build(monkeypatch, cell, SERVE_BROKEN.get(fault))
    with _control(cell, fault):
        res = run_lib.run_cell(cell, SEED, 1.5, False, cpu)
    checks = res["checks"]
    assert res["correct"] is (fault is None), checks
    assert res["attempted"] == WINDOW_REQUESTS and res["failed"] == 0
    assert set(res["metrics"]) == {"ttft_p95_ms", "tbt_p95_ms", "setup_s"}
    assert list(res)[-2:] == ["checks", "_notes"]


def answer_altered(ref, w, fwd, pool):
    return ref, w, (lambda x: fwd(x).at[:, 0].add(1.0)), pool


def state_initial(ref, w, fwd, pool):
    # a recurrence that never leaves its zero state: the head sees h = 0
    return ref, w, (lambda x: jnp.zeros_like(fwd(x))), pool


@pytest.mark.parametrize("fault", [None, "answer", "state", "bfloat16"])
def test_har_cell(fault, cpu, monkeypatch):
    cell = har_cell()
    _wrap_build(monkeypatch, cell, {"answer": answer_altered,
                                    "state": state_initial}.get(fault))
    with _control(cell, fault):
        res = run_lib.run_cell(cell, SEED, 0.5, False, cpu)
    assert res["correct"] is (fault is None), res["checks"]
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {"window_ms", "setup_s"}
