"""Public jit'd entry points for the Pallas kernels.

Block sizes are chosen by core/factorization.choose_block (the MobiRNN
coarse-factorization rule) unless explicitly overridden.  ``interpret=None``
(the default everywhere) lets ``repro.kernels.resolve_interpret`` pick the
mode from the backend: the Pallas interpreter on the CPU (the test suite),
compiled Mosaic kernels on a TPU, where ``interpret=True`` is an error.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax

from repro.core import factorization
from repro.kernels import decode_attn as _decode_attn
from repro.kernels import lstm_cell as _lstm_cell
from repro.kernels import wkv6 as _wkv6


def lstm_cell_step(w: jax.Array, b: jax.Array, batch: int, *,
                   interpret: bool | None = None, block_b: int | None = None,
                   block_h: int | None = None
                   ) -> Callable[[jax.Array, jax.Array, jax.Array],
                                 tuple[jax.Array, jax.Array]]:
    """The fused cell as ``step(x, c, h) -> (c', h')`` with its blocks
    chosen and the weights laid out for the kernel once — call it in a
    time loop without re-laying the weights every step."""
    K, H = w.shape[0], w.shape[1] // 4
    if block_b is None or block_h is None:
        bm, bn, _ = factorization.choose_block(batch, 4 * H, K)
        block_b = block_b or bm
        block_h = block_h or max(bn // 4, 1)
    w_t, b_t = _lstm_cell.gate_major(w, b, block_h)
    return functools.partial(_lstm_cell.lstm_cell_tiled, w_t, b_t,
                             block_b=block_b, block_h=block_h,
                             interpret=interpret)


def lstm_cell(w: jax.Array, b: jax.Array, x: jax.Array, c: jax.Array,
              h: jax.Array, *, interpret: bool | None = None,
              block_b: int | None = None, block_h: int | None = None
              ) -> tuple[jax.Array, jax.Array]:
    H = c.shape[-1]
    assert w.shape == (x.shape[-1] + H, 4 * H), (w.shape, x.shape, H)
    return lstm_cell_step(w, b, x.shape[0], interpret=interpret,
                          block_b=block_b, block_h=block_h)(x, c, h)


def lstm_seq(w: jax.Array, b: jax.Array, x: jax.Array, *,
             interpret: bool | None = None, block_b: int | None = None,
             time_chunk: int | None = None,
             bwd_block_b: int | None = None,
             bwd_time_chunk: int | None = None
             ) -> tuple[jax.Array, jax.Array]:
    """Whole-sequence stacked LSTM — ONE kernel dispatch for all T steps
    (and, under ``jax.grad``, ONE reverse-sweep dispatch for the backward).

    w: (L, P+H, 4H) stacked weights (lstm_seq.stack_params); b: (L, 4H);
    x: (B, T, P) padded input.  Returns final (c, h), each (L, B, H).
    ``block_b``/``time_chunk`` tile the forward (None = auto via
    ``choose_batch_block``: whole-T VMEM residency when it fits, otherwise
    double-buffered time streaming); ``bwd_block_b``/``bwd_time_chunk``
    tile the training path (``bwd_block_b=0`` forces the oracle-VJP
    fallback).  Raises ValueError when the weight stack exceeds the VMEM
    budget even at (bm=1, tc=1) — callers route to the per-cell
    ``lstm_cell`` fallback (see core/lstm.forward_fused_seq, which
    automates both the stacking and the fallback).
    """
    from repro.kernels import lstm_seq as _lstm_seq
    return _lstm_seq.lstm_seq(w, b, x, block_b=block_b,
                              time_chunk=time_chunk,
                              bwd_block_b=bwd_block_b,
                              bwd_time_chunk=bwd_time_chunk,
                              interpret=interpret)


def lstm_seq_q8(w: jax.Array, b: jax.Array, x: jax.Array, *,
                interpret: bool | None = None, block_b: int | None = None,
                time_chunk: int | None = None,
                bwd_block_b: int | None = None,
                bwd_time_chunk: int | None = None
                ) -> tuple[jax.Array, jax.Array]:
    """Int8-weight whole-sequence stacked LSTM — same single-dispatch
    contract as ``lstm_seq`` but ``w`` is quantized to per-output-channel
    symmetric int8 inside (kernels/ref.quantize_q8) and the kernels hold
    the stack in VMEM as int8 + f32 scales, quartering the dominant weight
    term.  Oracle: kernels/ref.lstm_seq_q8; training runs the q8 reverse
    sweep with straight-through master-weight gradients (still exactly 2
    dispatches per ``value_and_grad``).
    """
    from repro.kernels import lstm_seq as _lstm_seq
    return _lstm_seq.lstm_seq_q8(w, b, x, block_b=block_b,
                                 time_chunk=time_chunk,
                                 bwd_block_b=bwd_block_b,
                                 bwd_time_chunk=bwd_time_chunk,
                                 interpret=interpret)


def wkv6(r, k, v, logw, u, state, *, chunk: int = 32,
         bwd: int = _wkv6.FUSED_BWD, interpret: bool | None = None):
    return _wkv6.wkv6(r, k, v, logw, u, state, chunk=chunk, bwd=bwd,
                      interpret=interpret)


def decode_attn(q, k_cache, v_cache, lengths, *, scale=None,
                block_s: int = 128, interpret: bool | None = None):
    return _decode_attn.decode_attn(q, k_cache, v_cache, lengths,
                                    scale=scale, block_s=block_s,
                                    interpret=interpret)


def flash_prefill(q, k, v, *, window: int = 0, scale=None,
                  q_block: int = 128, k_block: int = 128,
                  interpret: bool | None = None):
    from repro.kernels import flash_prefill as _fp
    return _fp.flash_prefill(q, k, v, window=window, scale=scale,
                             q_block=q_block, k_block=k_block,
                             interpret=interpret)
