"""One rwkv6 decode step of a layer of the packed wkv state pool, in place.

The decode tick's state traffic is the stacked f32 wkv pool,
``(n_groups, B, H/p, dk, p*dv)`` with ``p`` heads side by side on each
128-lane row (models/rwkv.py, "Packed wkv state").  Written as jnp ops
(``rwkv.wkv_step``), XLA on a TPU makes two passes over a layer's slice:
one fusion reads it for the output's reduction, a second reads it again
for the new state and writes it back into the pool.  This kernel reads
each block of the layer's slice once and writes it once, in place:

* grid over blocks of ``rows_per_step`` packed rows, every lane at once;
  the pool is aliased input to output, and its block index map picks the
  layer from a scalar-prefetched index, so no other layer's state is
  touched or copied;
* the per-head vectors ``r``, ``k`` and ``w = exp(logw)`` arrive as
  ``(H, dk, B)``: dk on sublanes and the lanes of the batch on lanes,
  which is how the projections leave the MXU, so XLA hands them over
  without a relayout; each lane's column is then a lane broadcast;
* lanes with ``active`` False keep their state (the tick's free slots).

The state update is ``rwkv.wkv_step``'s arithmetic element for element
(a compiler may still contract its multiply-add, as XLA's CPU backend
does under the interpreter).  The output folds the bonus term as
``sum_i r_i S_i + (sum_i r_i u_i k_i) v``, which agrees with
``wkv_step``'s ``sum_i r_i (S_i + u_i k_i v)`` to f32 rounding.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import tiling
from repro.core.factorization import PIPELINE_BUFFERS
from repro.kernels import resolve_interpret

#: most bytes of the pool one grid step holds: 2 of rwkv6-3b's packed
#: rows over 64 lanes are 4.2 MB, so the pool's in and out blocks,
#: double-buffered, take 17 MB
MAX_BLOCK_BYTES = 5 << 20
#: scoped VMEM the kernel asks for beyond its blocks
VMEM_HEADROOM = 4 << 20


def rows_per_step(rows: int, row_bytes: int) -> int:
    """Most packed rows, dividing ``rows``, whose pool block fits
    MAX_BLOCK_BYTES (at least one)."""
    return max([j for j in range(1, rows + 1)
                if rows % j == 0 and j * row_bytes <= MAX_BLOCK_BYTES]
               or [1])


def vmem_limit(batch: int, jt: int, p: int, dk: int, width: int,
               vec_bytes: int) -> int:
    """The kernel's scoped-VMEM limit: its pipelined blocks, padded to
    (8, 128) tiles, plus headroom.  XLA reserves the whole limit around
    the call, so the package-wide limit would evict every other VMEM
    resident (weights it keeps there) at each layer."""
    def tile(sub, lane, itemsize=4):
        return tiling.pad_tiles(sub, 8) * tiling.lanes(lane) * itemsize

    pool = batch * jt * tile(dk, width)
    vecs = 3 * p * jt * tile(dk, batch, vec_bytes)   # r, k (io dtype), w
    rowwise = 3 * jt * tile(batch, width)            # v, bonus and out
    return PIPELINE_BUFFERS * (2 * pool + vecs + rowwise) + VMEM_HEADROOM


def _kernel(layer_ref, active_ref, r_ref, k_ref, w_ref, v_ref, bonus_ref,
            s_ref, out_ref, s_out_ref, *, p: int):
    del layer_ref                                  # used by the index maps
    B, jt, dk, width = s_ref.shape
    dv = width // p
    f32 = jnp.float32
    head = lax.broadcasted_iota(jnp.int32, (dk, width), 1) // dv

    def one_row(j, carry):
        # (p, dk, B): the row's p heads, one column per lane
        r, k, w = (ref[pl.ds(p * j, p)].astype(f32)
                   for ref in (r_ref, k_ref, w_ref))
        v, bonus = v_ref[j], bonus_ref[j]          # (B, p*dv)

        def over_row(x, b):
            # lane l of the row takes head l // dv's column of lane b
            out = jnp.broadcast_to(x[p - 1][:, b:b + 1], (dk, width))
            for q in range(p - 1):
                out = jnp.where(head == q, jnp.broadcast_to(
                    x[q][:, b:b + 1], (dk, width)), out)
            return out

        for b in range(B):
            r4, k4, w4 = (over_row(x, b) for x in (r, k, w))
            s = s_ref[b, j]                        # (dk, p*dv)
            vb = v[b:b + 1]                        # (1, p*dv)
            out_ref[j, pl.ds(b, 1), :] = (
                jnp.sum(r4 * s, axis=0, keepdims=True)
                + bonus[b:b + 1] * vb)
            s_out_ref[b, j] = jnp.where(active_ref[b] == 0, s,
                                        w4 * s + k4 * vb)
        return carry

    lax.fori_loop(0, jt, one_row, 0)


def wkv_decode(pool: jax.Array, layer: jax.Array, active: jax.Array,
               r: jax.Array, k: jax.Array, logw: jax.Array, v: jax.Array,
               u: jax.Array, *, interpret: bool | None = None
               ) -> tuple[jax.Array, jax.Array]:
    """Advance layer ``layer`` of the packed pool by one token.

    pool: f32 (G, B, H/p, dk, p*dv), updated in place (donate it);
    layer: int scalar; active: (B,) bool; r, k, logw: (B, H, dk);
    v: (B, H, dv); u: (H, dk).  Returns (out (B, H, dv) f32, pool)."""
    f32 = jnp.float32
    _, B, rows, dk, width = pool.shape
    H, dv = v.shape[1], v.shape[2]
    p = width // dv
    jt = rows_per_step(rows, B * dk * width * 4)

    def columns(x):
        return jnp.transpose(x, (1, 2, 0))                   # (H, dk, B)

    def by_row(x):
        return jnp.swapaxes(x.reshape(B, rows, width), 0, 1)  # (rows, B, .)

    bonus = jnp.sum(r.astype(f32) * u.astype(f32) * k.astype(f32), axis=-1)
    bonus = jnp.broadcast_to(bonus[..., None], (B, H, dv))
    vec_spec = pl.BlockSpec((p * jt, dk, B), lambda j, *_: (j, 0, 0))
    row_spec = pl.BlockSpec((jt, B, width), lambda j, *_: (j, 0, 0))
    pool_spec = pl.BlockSpec((None, B, jt, dk, width),
                             lambda j, g, _: (g[0], 0, j, 0, 0))
    out, pool = pl.pallas_call(
        lambda *refs: _kernel(*refs, p=p),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows // jt,),
            in_specs=[vec_spec, vec_spec, vec_spec, row_spec, row_spec,
                      pool_spec],
            out_specs=[row_spec, pool_spec]),
        out_shape=[jax.ShapeDtypeStruct((rows, B, width), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit(
            B, jt, p, dk, width, r.dtype.itemsize)),
        interpret=resolve_interpret(interpret),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), active.astype(jnp.int32),
      columns(r), columns(k), columns(jnp.exp(logw.astype(f32))),
      by_row(v.astype(f32)), by_row(bonus), pool)
    return jnp.swapaxes(out, 0, 1).reshape(B, H, dv), pool
