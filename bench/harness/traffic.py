"""The one traffic generator: reads a mix's parameters from its data file
(``bench/traffic/<name>.json``) and turns them, with ``--seed``, into
requests.

Every seed gets the same work in another order.  Lengths and gaps are
stratified draws: the ``n`` quantiles ``(i + 0.5) / n`` of the mix's
distribution, put in one fixed arrangement (drawn once, from a stream
that is not the seed's) in which every ``block`` consecutive arrivals
hold one draw from each ``1 / block`` of each distribution (the mix's
``block``).  The seed rotates that arrangement: the run starts at
another point of the same sequence, with the same gap, prompt and output
length at each arrival.  So the window of a run holds the same requests,
and the same gaps summing to the same span, whatever the seed; the seed
picks where the cycle starts and the token ids.  (A fresh shuffle per
seed moved the 95th percentile of a chat window's TTFT by 27% from seed
to seed on one TPU v5e, far more than two runs of one seed differ: where
the long prompts bunch up decides that tail.)

Distributions (``{"dist": ...}``):

* ``lognormal``: ``median``, ``sigma``, clipped to ``[min, max]``;
* ``uniform``:   integers on ``[min, max]``;
* ``fixed``:     ``value``.
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def _quantile(dist: dict, u: float) -> int:
    kind = dist["dist"]
    if kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(u))
        return int(min(max(round(x), dist["min"]), dist["max"]))
    if kind == "uniform":
        lo, hi = dist["min"], dist["max"]
        return int(min(lo + math.floor(u * (hi - lo + 1)), hi))
    if kind == "fixed":
        return int(dist["value"])
    raise ValueError(f"unknown length distribution {kind!r}")


def arrange(vals: np.ndarray, rng: np.random.Generator, block: int = 1
            ) -> np.ndarray:
    """Ascending ``vals`` in an order drawn from ``rng`` in which every
    ``block`` consecutive entries hold one from each ``1 / block`` of them
    (entry ``i`` goes to block ``i mod (n // block)``; the entries within
    a block, and the blocks, are shuffled).  ``block`` 1 is a plain
    shuffle."""
    n = len(vals)
    nb = max(n // block, 1)
    order = [rng.permutation(np.arange(b, n, nb)) for b in range(nb)]
    return vals[np.concatenate([order[b] for b in rng.permutation(nb)])]


def stratified(dist: dict, n: int, rng: np.random.Generator,
               block: int = 1) -> np.ndarray:
    """``n`` lengths at the distribution's stratified quantiles, arranged
    by ``rng`` (see ``arrange``)."""
    vals = np.array([_quantile(dist, (i + 0.5) / n) for i in range(n)],
                    np.int64)
    return arrange(vals, rng, block)


def gaps(rate_per_s: float, span_s: float, rng: np.random.Generator,
         block: int = 1) -> np.ndarray:
    """Poisson inter-arrival gaps for ``span_s`` seconds at ``rate_per_s``:
    ``round(rate * span)`` exponential quantiles, arranged by ``rng`` (see
    ``arrange``), scaled so they sum to exactly ``span_s``."""
    n = max(int(round(rate_per_s * span_s)), 1)
    u = (np.arange(n) + 0.5) / n
    g = arrange(-np.log1p(-u) / rate_per_s, rng, block)
    return g * (span_s / g.sum())


@dataclasses.dataclass(frozen=True)
class Arrival:
    uid: int
    due_s: float              # seconds after the schedule's start
    prompt_len: int
    output_len: int
    phase: str                # "ramp" | "window" | "tail"


#: the seed of the fixed arrangement that every seed rotates
ARRANGEMENT = 0


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per use, from one (possibly > 32-bit) seed."""
    return np.random.default_rng([int(seed) & (2**63 - 1), stream])


def open_loop(mix: dict, seed: int, window_s: float) -> list[Arrival]:
    """Open-loop schedule: a ramp of ``mix['ramp_s']`` seconds, the
    measured window, then a tail of ``mix['drain_s']`` seconds of the same
    arrivals (so the window's last requests get their first token under
    the same load).
    Each phase is its own stratified block, in the fixed arrangement,
    rotated by an offset drawn from ``seed``."""
    rate = float(mix["rate_per_s"])
    phases = (("ramp", float(mix["ramp_s"])), ("window", float(window_s)),
              ("tail", float(mix["drain_s"])))
    out: list[Arrival] = []
    block = int(mix["block"])
    t0 = 0.0
    for k, (phase, span) in enumerate(phases):
        if span <= 0:
            continue
        fixed = rng_for(ARRANGEMENT, 10 + k)
        g = gaps(rate, span, fixed, block)
        p = stratified(mix["prompt"], len(g), fixed, block)
        o = stratified(mix["output"], len(g), fixed, block)
        r = int(rng_for(seed, 10 + k).integers(len(g)))
        g, p, o = np.roll(g, -r), np.roll(p, -r), np.roll(o, -r)
        due = t0 + np.concatenate([[0.0], np.cumsum(g)[:-1]])
        for d, pl, ol in zip(due, p, o):
            out.append(Arrival(len(out), float(d), int(pl), int(ol), phase))
        t0 += span
    return out


def key_for(seed: int):
    """A JAX key from one (possibly > 32-bit) seed: its low 31 bits seed
    the key, the next 31 are folded in."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def token_ids(seed: int, uid: int, length: int, vocab: int) -> np.ndarray:
    """The prompt of request ``uid``: ids drawn from the seed."""
    rng = np.random.default_rng([int(seed) & (2**63 - 1), 1000, uid])
    return rng.integers(0, vocab, length, dtype=np.int64).astype(np.int32)
