"""Single-token flash-decode attention Pallas TPU kernel.

The serving hot spot: one new query token attends over a long KV cache.
MobiRNN's factorization rule applied to decode: the cache is streamed
through VMEM in coarse blocks of `block_s` positions (few large work units),
with the online-softmax running statistics (m, l, acc) held in VMEM scratch
across the sequential cache-block grid dimension — no (B,H,S) score tensor
ever exists in HBM.

GQA is handled in the index map: query head h reads kv head h // group.

Grid: (B, Hq, S/block_s), cache-block dim innermost/sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import compiler_params, resolve_interpret

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, len_ref, o_ref, m_scr, l_scr, acc_scr,
            *, scale: float, block_s: int):
    s = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(s == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)          # (dk,)
    k = k_ref[0, :, 0].astype(jnp.float32)       # (block_s, dk)
    v = v_ref[0, :, 0].astype(jnp.float32)       # (block_s, dv)
    length = len_ref[0, 0]

    pos = s * block_s + jax.lax.broadcasted_iota(jnp.int32, (block_s,), 0)
    valid = pos < length
    # zero invalid rows: padded partial blocks are NaN-poisoned in interpret
    # mode and 0 * NaN would otherwise leak into the accumulator
    v = jnp.where(valid[:, None], v, 0.0)
    scores = (k @ q) * scale                     # (block_s,)
    scores = jnp.where(valid, scores, NEG_INF)

    m_prev = m_scr[0, 0]
    m_new = jnp.maximum(m_prev, jnp.max(scores))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)                  # (block_s,)
    l_new = l_scr[0, 0] * alpha + jnp.sum(p)
    acc_new = acc_scr[0] * alpha + p @ v         # (dv,)
    m_scr[0, 0] = m_new
    l_scr[0, 0] = l_new
    acc_scr[0] = acc_new

    @pl.when(s == ns - 1)
    def _final():
        o_ref[0, 0] = (acc_new / l_new).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_s", "interpret", "scale"))
def decode_attn(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                lengths: jax.Array, *, scale: float | None = None,
                block_s: int = 128,
                interpret: bool | None = None) -> jax.Array:
    """q: (B, Hq, dk); caches: (B, S, Hkv, dk); lengths: (B,) int32.

    Returns (B, Hq, dk) attention outputs for the single new token.
    """
    B, Hq, dk = q.shape
    _, S, Hkv, dv = v_cache.shape
    assert Hq % Hkv == 0
    group = Hq // Hkv
    scale = dk ** -0.5 if scale is None else scale
    bs = min(block_s, S)
    ns = pl.cdiv(S, bs)
    len2 = lengths.reshape(B, 1).astype(jnp.int32)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_s=bs),
        grid=(B, Hq, ns),
        in_specs=[
            pl.BlockSpec((1, 1, dk), lambda b, h, s: (b, h, 0)),
            pl.BlockSpec((1, bs, 1, dk), lambda b, h, s: (b, s, h // group, 0)),
            pl.BlockSpec((1, bs, 1, dv), lambda b, h, s: (b, s, h // group, 0)),
            pl.BlockSpec((1, 1), lambda b, h, s: (b, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, dv), lambda b, h, s: (b, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, dv), jnp.float32),
        ],
        compiler_params=compiler_params(),
        interpret=resolve_interpret(interpret),
    )(q, k_cache, v_cache, len2)
    return out
