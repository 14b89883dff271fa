"""The traffic generator repeats for one seed, changes with the seed, and
gives every seed the same work in another order."""
from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

from harness import traffic  # noqa: E402

BIG = 2**31 + 12345


#: mixes that are not cells yet (PERF.md, open questions), inline
INLINE = {
    "longprompt": {
        "kind": "open_loop", "rate_per_s": 2.0, "block": 8, "ramp_s": 6,
        "drain_s": 30,
        "prompt": {"dist": "lognormal", "median": 1024, "sigma": 0.6,
                   "min": 256, "max": 4096},
        "output": {"dist": "uniform", "min": 16, "max": 64}},
}


def _mix(name):
    if name in INLINE:
        return INLINE[name]
    with open(os.path.join(BENCH, "traffic", name + ".json")) as fh:
        return json.load(fh)


OPEN = ["chat", "longprompt"]


@pytest.mark.parametrize("name", OPEN)
def test_open_loop_repeats_for_a_seed(name):
    a = traffic.open_loop(_mix(name), BIG, 10.0)
    b = traffic.open_loop(_mix(name), BIG, 10.0)
    assert a == b


@pytest.mark.parametrize("name", OPEN)
def test_open_loop_differs_across_seeds(name):
    a = traffic.open_loop(_mix(name), 1, 10.0)
    b = traffic.open_loop(_mix(name), 2, 10.0)
    assert [x.due_s for x in a] != [x.due_s for x in b]
    assert [x.prompt_len for x in a] != [x.prompt_len for x in b]


@pytest.mark.parametrize("name", OPEN)
def test_seed_rotates_one_fixed_sequence(name):
    """Every seed sees the same (gap, prompt, output) sequence, started at
    another point of it."""
    mix = _mix(name)

    def window(seed):
        w = [x for x in traffic.open_loop(mix, seed, 30.0)
             if x.phase == "window"]
        gaps = np.diff([x.due_s for x in w]).round(9).tolist()
        return [(x.prompt_len, x.output_len) for x in w], gaps

    (a, ga), (b, gb) = window(3), window(BIG)
    k = next(i for i in range(len(a)) if a[i:] + a[:i] == b)
    assert a[k:] + a[:k] == b
    # the gaps are one cycle too; each window leaves out a different one
    assert len(set(ga) & set(gb)) >= len(ga) - 1


@pytest.mark.parametrize("name", OPEN)
def test_window_holds_the_same_work_for_every_seed(name):
    mix = _mix(name)

    def window(seed):
        return [x for x in traffic.open_loop(mix, seed, 30.0)
                if x.phase == "window"]

    a, b = window(3), window(BIG)
    assert len(a) == len(b) == round(mix["rate_per_s"] * 30.0)
    assert sorted(x.prompt_len for x in a) == sorted(x.prompt_len for x in b)
    assert sorted(x.output_len for x in a) == sorted(x.output_len for x in b)
    lo = mix["ramp_s"]
    for w in (a, b):
        assert all(lo <= x.due_s < lo + 30.0 for x in w)
        assert all(mix["prompt"]["min"] <= x.prompt_len <= mix["prompt"]["max"]
                   for x in w)


def test_gaps_fill_their_span_exactly():
    rng = traffic.rng_for(BIG, 1)
    g = traffic.gaps(7.0, 30.0, rng)
    assert len(g) == 210
    assert g.sum() == pytest.approx(30.0)
    assert (g > 0).all()


@pytest.mark.parametrize("block", [1, 8])
def test_arrange_keeps_the_values_and_strata(block):
    """Every block of the arrangement holds one value of each 1/block of
    the values (sorted value v sits in block v mod nb, stratum v // nb)."""
    vals = np.arange(130)
    got = traffic.arrange(vals, traffic.rng_for(BIG, 2), block)
    assert sorted(got) == list(vals)
    nb = 130 // block
    runs = [[got[0]]]
    for v in got[1:]:
        if v % nb == runs[-1][0] % nb:
            runs[-1].append(v)
        else:
            runs.append([v])
    assert len(runs) == nb
    for r in runs:
        assert sorted(v // nb for v in r) == list(range(len(r)))


def test_stratified_lognormal_median():
    rng = traffic.rng_for(5, 0)
    dist = {"dist": "lognormal", "median": 256, "sigma": 1.0, "min": 16,
            "max": 2048}
    vals = traffic.stratified(dist, 1001, rng)
    assert int(np.median(vals)) == 256
    assert vals.min() >= 16 and vals.max() <= 2048


def test_token_ids_repeat_and_differ():
    a = traffic.token_ids(BIG, 3, 50, 65536)
    assert (a == traffic.token_ids(BIG, 3, 50, 65536)).all()
    assert not (a == traffic.token_ids(BIG, 4, 50, 65536)).all()
    assert a.dtype == np.int32 and a.max() < 65536


def test_har_windows_repeat_for_a_seed():
    sys.path.insert(0, os.path.join(BENCH, "configs"))
    import lstm_ref

    a = lstm_ref.windows(traffic.rng_for(BIG, 40), 8)
    b = lstm_ref.windows(traffic.rng_for(BIG, 40), 8)
    c = lstm_ref.windows(traffic.rng_for(1, 40), 8)
    assert a.shape == (8, 128, 9) and a.dtype == np.float32
    assert (a == b).all() and not (a == c).all()
