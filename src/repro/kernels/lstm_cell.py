"""Fused LSTM-cell Pallas TPU kernel — the paper's core optimization.

MobiRNN §3.2/§3.3: combine the four gate matmuls into ONE coarse work unit
([x,h] @ W_fused) and fuse the point-wise gate non-linearities behind it so
no intermediate gate tensor round-trips through backing memory.  On TPU this
becomes a single `pallas_call`: the gate matmul runs on the MXU from VMEM
tiles, and the sigmoid/tanh/c/h updates happen in VREGs before the (c', h')
blocks are written back — one HBM round-trip per cell instead of ~10.

Block decomposition follows core/factorization.choose_block: grid over
(batch tiles x hidden tiles), the reduction dim (D+H) is kept whole per block
(it is the paper's "pack many vector products into one work unit" rule; for
the model sizes this framework serves, (D+H) x 4*bh tiles fit VMEM).

Weight layout: ``gate_major`` lays W out as (D+H, n_tiles * 4 * bh),
gate-major within each hidden tile, so one (D+H, 4*bh) block pulls the
matching column slice of ALL FOUR gates and every block is lane-dense — a
(K, 4, bh) block is refused by Mosaic at bh < 128 (the in-kernel reshape to
split the gates is an unsupported shape cast), a lane slice of the 4*bh
gate row is not.  Callers lay the weights out once per forward
(ops.lstm_cell_step), not once per step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import compiler_params, resolve_interpret


def _kernel(xh_ref, w_ref, b_ref, c_ref, c_out_ref, h_out_ref):
    xh = xh_ref[...]                       # (bm, K)
    w = w_ref[...]                         # (K, 4*bh), gate-major
    b = b_ref[...]                         # (1, 4*bh)
    bh = c_ref.shape[-1]
    # one coarse MXU work unit: all four gates of this hidden tile at once
    gates = jax.lax.dot_general(
        xh, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + b.astype(jnp.float32)
    i = jax.nn.sigmoid(gates[:, 0 * bh:1 * bh])
    f = jax.nn.sigmoid(gates[:, 1 * bh:2 * bh])
    g = jnp.tanh(gates[:, 2 * bh:3 * bh])
    o = jax.nn.sigmoid(gates[:, 3 * bh:4 * bh])
    c = c_ref[...].astype(jnp.float32)
    c_new = f * c + i * g
    h_new = o * jnp.tanh(c_new)
    c_out_ref[...] = c_new.astype(c_out_ref.dtype)
    h_out_ref[...] = h_new.astype(h_out_ref.dtype)


def gate_major(w: jax.Array, b: jax.Array, block_h: int
               ) -> tuple[jax.Array, jax.Array]:
    """The kernel's weight layout: (K, 4H) / (4H,) in gate order (i,f,g,o)
    -> (K, nh*4*bh) / (1, nh*4*bh), hidden tile j holding [i_j|f_j|g_j|o_j]
    with bh = min(block_h, H) and each gate padded to nh*bh columns.  A
    per-step caller lays the weights out once, outside its time loop."""
    H = w.shape[-1] // 4
    bh = min(block_h, H)
    return _to_gate_major(w, H, bh), _to_gate_major(b[None], H, bh)


def _to_gate_major(w: jax.Array, H: int, bh: int) -> jax.Array:
    nh = -(-H // bh)
    if nh == 1:
        return w
    rows = w.shape[0]
    w4 = w.reshape(rows, 4, H)
    if nh * bh != H:
        w4 = jnp.pad(w4, ((0, 0), (0, 0), (0, nh * bh - H)))
    return (w4.reshape(rows, 4, nh, bh).transpose(0, 2, 1, 3)
            .reshape(rows, nh * 4 * bh))


def _from_gate_major(w_t: jax.Array, H: int, bh: int) -> jax.Array:
    """Inverse of ``_to_gate_major``: (rows, nh*4*bh) -> (rows, 4H)."""
    nh = -(-H // bh)
    if nh == 1:
        return w_t
    rows = w_t.shape[0]
    return (w_t.reshape(rows, nh, 4, bh).transpose(0, 2, 1, 3)
            .reshape(rows, 4, nh * bh)[:, :, :H].reshape(rows, 4 * H))


@functools.partial(
    jax.jit,
    static_argnames=("block_b", "block_h", "interpret"),
)
def _lstm_cell_call(w_t: jax.Array, b_t: jax.Array, x: jax.Array,
                    c: jax.Array, h: jax.Array, block_b: int, block_h: int,
                    interpret: bool | None) -> tuple[jax.Array, jax.Array]:
    B, D = x.shape
    H = c.shape[-1]
    K = D + H
    xh = jnp.concatenate([x, h], axis=-1)
    bm = min(block_b, B)
    bh = min(block_h, H)
    grid = (pl.cdiv(B, bm), pl.cdiv(H, bh))
    out_struct = jax.ShapeDtypeStruct((B, H), c.dtype)
    c_new, h_new = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, K), lambda ib, jh: (ib, 0)),
            pl.BlockSpec((K, 4 * bh), lambda ib, jh: (0, jh)),
            pl.BlockSpec((1, 4 * bh), lambda ib, jh: (0, jh)),
            pl.BlockSpec((bm, bh), lambda ib, jh: (ib, jh)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bh), lambda ib, jh: (ib, jh)),
            pl.BlockSpec((bm, bh), lambda ib, jh: (ib, jh)),
        ],
        out_shape=[out_struct, out_struct],
        compiler_params=compiler_params(),
        interpret=resolve_interpret(interpret),
    )(xh, w_t, b_t, c)
    return c_new, h_new


# ---------------------------------------------------------------------------
# Differentiable entry point: pallas_call has no VJP rule, so the backward
# differentiates the per-cell jnp oracle (kernels/ref.lstm_cell — identical
# math, read through the inverse layout), making the per-cell plan a real
# TRAINING choice.  Per cell that is one oracle-VJP; composed over the scan
# it is the O(T*L) baseline the sequence-resident reverse sweep
# (kernels/lstm_seq_bwd.py) coarsens away.
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _lstm_cell(w_t, b_t, x, c, h, block_b, block_h, interpret):
    return _lstm_cell_call(w_t, b_t, x, c, h, block_b, block_h, interpret)


def _lstm_cell_fwd(w_t, b_t, x, c, h, block_b, block_h, interpret):
    out = _lstm_cell_call(w_t, b_t, x, c, h, block_b, block_h, interpret)
    return out, (w_t, b_t, x, c, h)


def _lstm_cell_bwd(block_b, block_h, interpret, residuals, cotangents):
    from repro.kernels import ref

    H = residuals[3].shape[-1]
    bh = min(block_h, H)

    def oracle(w_t, b_t, x, c, h):
        return ref.lstm_cell(_from_gate_major(w_t, H, bh),
                             _from_gate_major(b_t, H, bh)[0], x, c, h)

    _, vjp = jax.vjp(oracle, *residuals)
    return vjp(cotangents)


_lstm_cell.defvjp(_lstm_cell_fwd, _lstm_cell_bwd)


def lstm_cell_tiled(w_t: jax.Array, b_t: jax.Array, x: jax.Array,
                    c: jax.Array, h: jax.Array, *, block_b: int,
                    block_h: int, interpret: bool | None = None
                    ) -> tuple[jax.Array, jax.Array]:
    """Fused cell step over weights already in the kernel's layout
    (``gate_major(w, b, block_h)``, same ``block_h``)."""
    return _lstm_cell(w_t, b_t, x, c, h, block_b, block_h, interpret)
