"""Tails and rates come from every sample of the window, and the FLOP
arithmetic agrees with the program's parameter count."""
from __future__ import annotations

import math
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

from harness import flops, stats  # noqa: E402


def test_percentile_uses_every_sample():
    # far more samples than a bounded 4,096-entry history would keep: the
    # slow ones come first, so a history of the last 4,096 would miss them
    vals = [100.0] * 1200 + [1.0] * 18800
    assert stats.percentile(vals, 95) == 100.0
    assert stats.percentile(vals, 50) == 1.0
    assert stats.percentile(vals[-4096:], 95) == 1.0


def test_percentile_nearest_rank():
    vals = list(range(1, 101))
    assert stats.percentile(vals, 95) == 95
    assert stats.percentile(vals, 100) == 100
    assert stats.percentile([5.0], 95) == 5.0


def test_missing_samples_count_as_missing_the_limit():
    vals = [1.0] * 90 + [math.inf] * 10
    assert stats.percentile(vals, 95) == math.inf


def test_rate_over_the_whole_window():
    assert stats.rate(3000, 30.0) == 100.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


RWKV = {"n_layers": 32, "d_model": 2560, "head_dim": 64, "d_ff": 8960,
        "vocab": 65536, "lora_rank": 64, "mix_lora_rank": 32}


def test_rwkv6_param_count_is_the_published_3b():
    assert flops.rwkv6_param_count(RWKV) == 3_099_857_920


def test_rwkv6_param_count_matches_the_program():
    import jax
    sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
    from repro.configs import get_arch
    from repro.models import registry

    values, _ = registry.build(get_arch("rwkv6-3b")).abstract_params()
    n = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(values))
    assert n == flops.rwkv6_param_count(RWKV)


def test_rwkv6_token_flops():
    matmul = flops.rwkv6_matmul_params(RWKV)
    # every parameter but the embedding, the head, and the vectors
    vectors = 32 * (6 * 2560 + 2560 + 2560 + 2 * 2560 + 2 * 2560 + 4 * 2560)
    assert matmul == (3_099_857_920 - 2 * 65536 * 2560 - 2 * 2560 - vectors)
    head = 2 * 2560 * 65536
    assert (flops.rwkv6_token_flops(RWKV, head=True)
            - flops.rwkv6_token_flops(RWKV, head=False)) == head


LSTM = {"n_layers": 2, "hidden": 32, "input_dim": 9, "seq_len": 128,
        "n_classes": 6}


def test_lstm_flops_and_bytes():
    per_step = 2 * (9 + 32) * 128 + 2 * (32 + 32) * 128
    assert flops.lstm_seq_flops(LSTM, 1) == 128 * per_step
    assert flops.lstm_window_flops(LSTM) == 128 * per_step + 2 * 32 * 6
    weights = (41 * 128 + 128) + (64 * 128 + 128)
    assert flops.lstm_seq_bytes(LSTM, 1) == 4 * (weights + 128 * 9 + 2 * 2 * 32)
