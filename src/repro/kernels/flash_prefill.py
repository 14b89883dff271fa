"""Blocked causal (flash) prefill attention Pallas TPU kernel.

The prefill compute hot-spot.  MobiRNN's coarse-factorization rule sets the
block shapes (few, large, MXU-aligned VMEM tiles); the causal structure
prunes work at BLOCK granularity: a kv block entirely in the future of a
query block contributes nothing and its math is skipped with ``pl.when``
(the grid still visits it, but no FLOPs are issued — the TPU analogue of
not launching the work unit at all).  Sliding windows prune past blocks the
same way.  Online-softmax statistics live in VMEM scratch across the
sequential kv-block grid dimension.

Grid: (B, Hq, nq, nk), kv-block dim innermost.  GQA via index_map
(query head h reads kv head h // group).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import compiler_params, resolve_interpret

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, q_block: int, k_block: int, window: int,
            seq_len: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * q_block
    q_end = q_start + q_block - 1
    k_start = kj * k_block
    k_end = k_start + k_block - 1

    # causal block skip: kv block entirely in the future -> no work unit
    live = k_start <= q_end
    if window:
        # window skip: kv block entirely before the window of every query
        live = jnp.logical_and(live, k_end >= q_start - window + 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (qb, dh)
        k = k_ref[0, 0].astype(jnp.float32)          # (kb, dh)
        v = v_ref[0, 0].astype(jnp.float32)          # (kb, dh)
        qp = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                (q_block, k_block), 0)
        kp = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                (q_block, k_block), 1)
        mask = (qp >= kp) & (kp < seq_len)
        if window:
            mask &= (qp - kp) < window
        # padded partial-block tails are NaN-poisoned in interpret mode;
        # zero v there so 0*NaN can't leak into the accumulator
        kvalid = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                    (k_block,), 0) < seq_len
        v = jnp.where(kvalid[:, None], v, 0.0)
        s = jax.lax.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        l_scr[:, 0] = l_scr[:, 0] * alpha + jnp.sum(p, axis=-1)
        acc_scr[...] = (acc_scr[...] * alpha[:, None]
                        + jax.lax.dot(p, v,
                                      preferred_element_type=jnp.float32))
        m_scr[:, 0] = m_new

    @pl.when(kj == nk - 1)
    def _final():
        l = jnp.maximum(l_scr[:, 0], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "q_block", "k_block", "window", "scale", "interpret"))
def flash_prefill(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  window: int = 0, scale: float | None = None,
                  q_block: int = 128, k_block: int = 128,
                  interpret: bool | None = None) -> jax.Array:
    """q: (B, S, Hq, dh); k, v: (B, S, Hkv, dh).  Returns (B, S, Hq, dh).

    Causal; window > 0 additionally restricts attention to the last
    `window` positions."""
    B, S, Hq, dh = q.shape
    Hkv = k.shape[2]
    assert Hq % Hkv == 0
    group = Hq // Hkv
    scale = dh ** -0.5 if scale is None else scale
    qb = min(q_block, S)
    kb = min(k_block, S)
    nq, nk = pl.cdiv(S, qb), pl.cdiv(S, kb)
    # layout: (B, H, S, dh) blocks
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, q_block=qb, k_block=kb,
                          window=window, seq_len=S),
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, qb, dh), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, kb, dh),
                         lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, kb, dh),
                         lambda b, h, i, j: (b, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, qb, dh),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qb, 1), jnp.float32),
            pltpu.VMEM((qb, 1), jnp.float32),
            pltpu.VMEM((qb, dh), jnp.float32),
        ],
        compiler_params=compiler_params(),
        interpret=resolve_interpret(interpret),
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
