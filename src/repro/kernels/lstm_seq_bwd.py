"""Sequence-resident fused LSTM BACKWARD: the whole BPTT sweep in ONE
``pallas_call``.

This is the other half of kernels/lstm_seq.py — MobiRNN's coarsening lesson
applied to training.  The naive custom-VJP fallback replays the entire
forward through the jnp oracle and lets autodiff unroll T x L cell
backwards, so training with the "fast" plan used to be dispatch-bound again
exactly where the forward had stopped being.  Here the reverse-time loop
runs INSIDE the kernel:

* grid over batch tiles (batch rows stay independent in the backward);
* ``fori_loop`` over reversed time; per step, layers unwind top-down;
* gates are RECOMPUTED from the stored (T, L, bm, H) f32 trajectory
  residuals (the lstm_seq._seq_traj_kernel contract) — same matmuls as the
  forward, so the recomputed activations are bit-identical and the
  gradients exact-math;
* ``dw``/``db`` accumulate in f32 VMEM scratch that persists across grid
  steps (batch tiles), written to the outputs once on the last tile;
* the ``(dc, dh)`` time-carries live in VMEM scratch and never round-trip
  HBM between steps — the preallocation bound, mirrored in reverse.

Cotangent contract: inputs are the final-state cotangents ``(dc, dh)``
each (L, B, H); outputs are ``(dw, db, dx)`` in the parameter/input dtypes.
VMEM sizing: lstm_seq.working_set_bytes(mode="bwd"); when
choose_batch_block(mode="bwd") returns None the custom_vjp in lstm_seq.py
falls back to the oracle instead of dispatching this kernel.

Time streaming (``time_chunk=tc``): the whole-T-resident layout holds two
(T, L, bm, H) f32 trajectories in VMEM, which dominates the backward
working set at long T.  The chunked layout keeps x and both trajectories
in HBM and streams them through double-buffered VMEM windows in REVERSE
chunk order — chunk k-1 prefetches while chunk k unwinds — with a
(tc+1)-row trajectory window so the pre-step state of a chunk's first
timestep (the last row of the previous chunk) is always present; dx
streams out through two staging buffers.  The f32 dw/db accumulators and
the (dc, dh) carries stay VMEM-resident across chunks AND batch tiles, so
residency is O(tc) in T.  Chunking changes data movement only — the
unwind math is identical step-for-step, so gradients are bit-identical to
the unchunked sweep (tests/test_lstm_seq.py asserts it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import compiler_params, resolve_interpret

F32 = jnp.float32


def _unwind_step(x_t, c_t, h_t, c_prev_all, h_prev_all, w_ref, b_ref,
                 dw_scr, db_scr, dc_scr, dh_scr,
                 *, n_layers: int, p_width: int, s_ref=None):
    """Unwind ALL layers of one timestep, updating the (dc, dh) carries and
    the dw/db accumulators in place; returns this step's dx row (bm, P).

    Inputs are the (already masked) forward values at step t: x_t (bm, P),
    post-step states c_t/h_t (L, bm, H) and pre-step states
    c_prev_all/h_prev_all (L, bm, H, zeros at t == 0).  Shared by the
    whole-T-resident and time-chunked kernel bodies so the two layouts
    unwind bit-identically.

    ``s_ref`` (optional): (L, 4H) f32 per-channel scales — the int8 path.
    The gate recompute folds the scale into the pre-activations EXACTLY as
    the q8 forward did (bit-identical recompute); the outgoing input/carry
    grads dot ``dgates * s`` against the int8 block (dgates @ (wq*s)^T ==
    (dgates*s) @ wq^T); the dw/db accumulation is unchanged — it is the
    STRAIGHT-THROUGH gradient wrt the DEQUANTIZED weights, accumulated and
    emitted in f32 for the master stack.
    """
    hidden = dc_scr.shape[-1]
    dinp = jnp.zeros_like(x_t)                           # from layer above
    for layer in range(n_layers - 1, -1, -1):            # static unroll
        w = w_ref[layer].astype(F32)                     # (P+H, 4H)
        scale = None if s_ref is None else s_ref[layer].astype(F32)
        c_prev = c_prev_all[layer]
        h_prev = h_prev_all[layer]
        if layer == 0:
            inp = x_t
        else:
            below = h_t[layer - 1]
            inp = below if p_width == hidden else \
                jnp.pad(below, ((0, 0), (0, p_width - hidden)))
        # recompute this cell's gates — same two matmuls as the forward
        gates = (
            jax.lax.dot_general(inp, w[:p_width],
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=F32)
            + jax.lax.dot_general(h_prev, w[p_width:],
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=F32))
        if scale is not None:
            gates = gates * scale                        # fold channel scale
        gates = gates + b_ref[layer].astype(F32)
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        si, sf, so = (jax.nn.sigmoid(i), jax.nn.sigmoid(f),
                      jax.nn.sigmoid(o))
        tg = jnp.tanh(g)
        tc_ = jnp.tanh(c_t[layer])
        # incoming grads: time-carry + the layer above's input grad
        dh = dh_scr[layer] + dinp[:, :hidden]
        dc = dc_scr[layer] + dh * so * (1.0 - tc_ * tc_)
        dgates = jnp.concatenate([
            dc * tg * si * (1.0 - si),                   # d pre-i
            dc * c_prev * sf * (1.0 - sf),               # d pre-f
            dc * si * (1.0 - tg * tg),                   # d pre-g
            dh * tc_ * so * (1.0 - so),                  # d pre-o
        ], axis=-1)                                      # (bm, 4H)
        # parameter grads: [inp | h_prev]^T @ dgates, f32 accumulation
        dw_rows = jnp.concatenate([
            jax.lax.dot_general(inp, dgates, (((0,), (0,)), ((), ())),
                                preferred_element_type=F32),
            jax.lax.dot_general(h_prev, dgates,
                                (((0,), (0,)), ((), ())),
                                preferred_element_type=F32),
        ], axis=0)                                       # (P+H, 4H)
        dw_scr[layer] = dw_scr[layer] + dw_rows
        db_scr[layer] = db_scr[layer] + jnp.sum(dgates, axis=0)
        # outgoing grads: recurrence carry + the layer below / input —
        # through the DEQUANTIZED weights on the q8 path
        dg_w = dgates if scale is None else dgates * scale
        dh_scr[layer] = jax.lax.dot_general(
            dg_w, w[p_width:], (((1,), (1,)), ((), ())),
            preferred_element_type=F32)                  # -> h_{t-1}[layer]
        dc_scr[layer] = dc * sf                          # -> c_{t-1}[layer]
        dinp = jax.lax.dot_general(
            dg_w, w[:p_width], (((1,), (1,)), ((), ())),
            preferred_element_type=F32)                  # (bm, P)
    return dinp


def _seq_bwd_kernel(x_ref, w_ref, b_ref, ct_ref, ht_ref, dcf_ref, dhf_ref,
                    dw_ref, db_ref, dx_ref,
                    dw_scr, db_scr, dc_scr, dh_scr,
                    *, n_layers: int, seq_len: int, p_width: int,
                    n_tiles: int, batch: int, s_ref=None):
    """One batch tile unwinds the whole (T x L) recurrence from VMEM.

    x_ref: (T, bm, P); w_ref: (L, P+H, 4H); b_ref: (L, 4H);
    ct_ref/ht_ref: (T, L, bm, H) f32 post-step state trajectories;
    dcf_ref/dhf_ref: (L, bm, H) final-state cotangents.
    dw_scr/db_scr are f32 accumulators shared across ALL grid steps (scratch
    persists between batch tiles); dc_scr/dh_scr carry the per-tile
    reverse-time gradient state.

    Unlike the forward — where a non-dividing final tile's out-of-range
    rows just compute garbage that the output re-tiling drops — here those
    rows would flow into the SHARED dw/db accumulators, so every load is
    masked to the valid batch rows of this tile.
    """
    bm = dc_scr.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
    valid = (pl.program_id(0) * bm + rows) < batch       # (bm, 1)

    def mask2(a):                                        # (bm, X)
        return jnp.where(valid, a, 0.0)

    def mask3(a):                                        # (L, bm, X)
        return jnp.where(valid[None], a, 0.0)

    @pl.when(pl.program_id(0) == 0)
    def _zero_accumulators():
        dw_scr[...] = jnp.zeros_like(dw_scr)
        db_scr[...] = jnp.zeros_like(db_scr)

    dc_scr[...] = mask3(dcf_ref[...].astype(F32))
    dh_scr[...] = mask3(dhf_ref[...].astype(F32))

    def step(rev_t, carry):
        t = seq_len - 1 - rev_t
        x_t = mask2(x_ref[pl.ds(t, 1)][0].astype(F32))   # (bm, P)
        c_t = mask3(ct_ref[pl.ds(t, 1)][0])              # (L, bm, H)
        h_t = mask3(ht_ref[pl.ds(t, 1)][0])
        # pre-step state: the previous trajectory row, zeros at t == 0
        # (clamped read + where keeps the access in bounds under tracing)
        tm1 = jnp.maximum(t - 1, 0)
        alive = (t > 0).astype(F32)
        c_prev_all = mask3(ct_ref[pl.ds(tm1, 1)][0]) * alive
        h_prev_all = mask3(ht_ref[pl.ds(tm1, 1)][0]) * alive

        dinp = _unwind_step(x_t, c_t, h_t, c_prev_all, h_prev_all,
                            w_ref, b_ref, dw_scr, db_scr, dc_scr, dh_scr,
                            n_layers=n_layers, p_width=p_width, s_ref=s_ref)
        dx_ref[pl.ds(t, 1)] = dinp[None].astype(dx_ref.dtype)
        return carry

    jax.lax.fori_loop(0, seq_len, step, 0)

    @pl.when(pl.program_id(0) == n_tiles - 1)
    def _emit_param_grads():
        dw_ref[...] = dw_scr[...].astype(dw_ref.dtype)
        db_ref[...] = db_scr[...].astype(db_ref.dtype)


def _seq_bwd_q8_kernel(x_ref, w_ref, s_ref, b_ref, ct_ref, ht_ref, dcf_ref,
                       dhf_ref, dw_ref, db_ref, dx_ref,
                       dw_scr, db_scr, dc_scr, dh_scr,
                       *, n_layers: int, seq_len: int, p_width: int,
                       n_tiles: int, batch: int):
    """Int8-weight reverse sweep: the same unwind with the (L, 4H) f32
    scales as an extra input and int8 weights VMEM-resident; dw/db emit in
    f32 (straight-through master-weight gradients)."""
    _seq_bwd_kernel(x_ref, w_ref, b_ref, ct_ref, ht_ref, dcf_ref, dhf_ref,
                    dw_ref, db_ref, dx_ref, dw_scr, db_scr, dc_scr, dh_scr,
                    n_layers=n_layers, seq_len=seq_len, p_width=p_width,
                    n_tiles=n_tiles, batch=batch, s_ref=s_ref)


def _seq_bwd_chunked_kernel(x_hbm, w_ref, b_ref, ct_hbm, ht_hbm,
                            dcf_ref, dhf_ref,
                            dw_ref, db_ref, dx_hbm,
                            xbuf, ctb, htb, dxb,
                            dw_scr, db_scr, dc_scr, dh_scr,
                            xsem, csem, hsem, osem,
                            *, n_layers: int, seq_len: int, p_width: int,
                            tc: int, tw: int, nc: int, n_tiles: int,
                            batch: int, s_ref=None):
    """Time-chunked reverse sweep: the same BPTT unwind, but x and the two
    trajectories stream through double-buffered VMEM windows in REVERSE
    chunk order (chunk k-1 prefetches while chunk k computes) and dx streams
    out through two staging buffers.

    x_hbm: (T, Bp, Pl); ct_hbm/ht_hbm: (T, L, Bp, Hl) f32; dx_hbm:
    (nc*tc, Bp, Pl) time-padded (wrapper slices [:T, :B, :P]); every
    streamed array is lane-padded to ``tiling.LANES`` and only its first
    P/H lanes are read or written.  The trajectory
    window is ``tw = tc+1`` rows (tc when nc == 1) starting one row BEFORE
    the chunk so the pre-step state of the chunk's first timestep — the
    carry crossing the chunk boundary — comes from the same residuals the
    unchunked kernel reads, bit-identically.  Copy starts are clamped so
    the static-size windows stay in bounds at the ends; the masked dw/db
    accumulation is unchanged (batch padding rows never reach the shared
    accumulators).
    """
    bm = dc_scr.shape[1]
    ib = pl.program_id(0)
    rows = jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
    valid = (ib * bm + rows) < batch                     # (bm, 1)

    def mask2(a):                                        # (bm, X)
        return jnp.where(valid, a, 0.0)

    def mask3(a):                                        # (L, bm, X)
        return jnp.where(valid[None], a, 0.0)

    def x_src(k):
        return jnp.minimum(k * tc, seq_len - tc)

    def t_src(k):
        return jnp.minimum(jnp.maximum(k * tc - 1, 0), seq_len - tw)

    def dma_x(slot, k):
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(x_src(k), tc), pl.ds(ib * bm, bm)],
            xbuf.at[slot], xsem.at[slot])

    def dma_traj(hbm, buf, sem, slot, k):
        return pltpu.make_async_copy(
            hbm.at[pl.ds(t_src(k), tw), :, pl.ds(ib * bm, bm)],
            buf.at[slot], sem.at[slot])

    def dma_dx(slot, k):
        return pltpu.make_async_copy(
            dxb.at[slot],
            dx_hbm.at[pl.ds(k * tc, tc), pl.ds(ib * bm, bm)],
            osem.at[slot])

    def start_in(slot, k):
        dma_x(slot, k).start()
        dma_traj(ct_hbm, ctb, csem, slot, k).start()
        dma_traj(ht_hbm, htb, hsem, slot, k).start()

    def wait_in(slot, k):
        dma_x(slot, k).wait()
        dma_traj(ct_hbm, ctb, csem, slot, k).wait()
        dma_traj(ht_hbm, htb, hsem, slot, k).wait()

    @pl.when(ib == 0)
    def _zero_accumulators():
        dw_scr[...] = jnp.zeros_like(dw_scr)
        db_scr[...] = jnp.zeros_like(db_scr)

    dc_scr[...] = mask3(dcf_ref[...].astype(F32))
    dh_scr[...] = mask3(dhf_ref[...].astype(F32))

    start_in(jax.lax.rem(nc - 1, 2), nc - 1)             # warm-up (last)

    def chunk(rev_k, carry):
        k = nc - 1 - rev_k
        slot = jax.lax.rem(k, 2)

        @pl.when(k >= 1)                                 # reverse prefetch
        def _prefetch():
            start_in(jax.lax.rem(k - 1, 2), k - 1)

        wait_in(slot, k)
        # the dx staging slot's previous flight (chunk k+2) must land
        # before this chunk overwrites it
        @pl.when(k + 2 < nc)
        def _reclaim():
            dma_dx(slot, k + 2).wait()

        xs, ts = x_src(k), t_src(k)

        def step(i, c2):
            t = k * tc + (tc - 1 - i)                    # reverse in chunk

            @pl.when(t < seq_len)                        # tail-chunk guard
            def _unwind():
                hidden = dc_scr.shape[-1]
                x_t = mask2(xbuf[slot, t - xs][:, :p_width].astype(F32))
                c_t = mask3(ctb[slot, t - ts][..., :hidden])   # (L, bm, H)
                h_t = mask3(htb[slot, t - ts][..., :hidden])
                lm1 = jnp.maximum(t - 1 - ts, 0)
                alive = (t > 0).astype(F32)
                c_prev_all = mask3(ctb[slot, lm1][..., :hidden]) * alive
                h_prev_all = mask3(htb[slot, lm1][..., :hidden]) * alive
                dinp = _unwind_step(x_t, c_t, h_t, c_prev_all, h_prev_all,
                                    w_ref, b_ref, dw_scr, db_scr,
                                    dc_scr, dh_scr,
                                    n_layers=n_layers, p_width=p_width,
                                    s_ref=s_ref)
                dxb[slot, t - k * tc, :, :p_width] = dinp.astype(dxb.dtype)
            return c2

        jax.lax.fori_loop(0, tc, step, 0)
        dma_dx(slot, k).start()
        return carry

    jax.lax.fori_loop(0, nc, chunk, 0)
    # drain the (at most two) outstanding dx flights: chunks 0 and 1
    dma_dx(0, 0).wait()

    @pl.when(nc >= 2)
    def _drain_prev():
        dma_dx(1, 1).wait()

    @pl.when(ib == n_tiles - 1)
    def _emit_param_grads():
        dw_ref[...] = dw_scr[...].astype(dw_ref.dtype)
        db_ref[...] = db_scr[...].astype(db_ref.dtype)


def _seq_bwd_chunked_q8_kernel(x_hbm, w_ref, s_ref, b_ref, ct_hbm, ht_hbm,
                               dcf_ref, dhf_ref,
                               dw_ref, db_ref, dx_hbm,
                               xbuf, ctb, htb, dxb,
                               dw_scr, db_scr, dc_scr, dh_scr,
                               xsem, csem, hsem, osem,
                               *, n_layers: int, seq_len: int, p_width: int,
                               tc: int, tw: int, nc: int, n_tiles: int,
                               batch: int):
    """Int8-weight streamed reverse sweep (scales with the resident stack)."""
    _seq_bwd_chunked_kernel(x_hbm, w_ref, b_ref, ct_hbm, ht_hbm,
                            dcf_ref, dhf_ref, dw_ref, db_ref, dx_hbm,
                            xbuf, ctb, htb, dxb,
                            dw_scr, db_scr, dc_scr, dh_scr,
                            xsem, csem, hsem, osem,
                            n_layers=n_layers, seq_len=seq_len,
                            p_width=p_width, tc=tc, tw=tw, nc=nc,
                            n_tiles=n_tiles, batch=batch, s_ref=s_ref)


@functools.partial(jax.jit,
                   static_argnames=("block_b", "time_chunk", "interpret"))
def _lstm_seq_bwd_call(w, b, x, ct, ht, dc, dh, block_b: int,
                       time_chunk: int | None, interpret: bool | None,
                       scales=None):
    L, H = w.shape[0], w.shape[-1] // 4
    P = w.shape[1] - H
    B, T, _ = x.shape
    bm = min(block_b, B)
    n_tiles = pl.cdiv(B, bm)
    xt = jnp.swapaxes(x, 0, 1)                           # (T, B, P)
    if time_chunk is not None:
        return _lstm_seq_bwd_chunked_call(w, b, xt, ct, ht, dc, dh, bm,
                                          min(time_chunk, T), interpret,
                                          scales=scales)
    if scales is None:
        kernel = functools.partial(_seq_bwd_kernel, n_layers=L, seq_len=T,
                                   p_width=P, n_tiles=n_tiles, batch=B)
        s_in, s_spec = (), ()
        dw_dt, db_dt = w.dtype, b.dtype
    else:
        kernel = functools.partial(_seq_bwd_q8_kernel, n_layers=L,
                                   seq_len=T, p_width=P, n_tiles=n_tiles,
                                   batch=B)
        s_in = (scales,)
        s_spec = (pl.BlockSpec((L, 4 * H), lambda ib: (0, 0)),)
        dw_dt, db_dt = F32, F32       # straight-through master-weight grads
    dw, db, dxt = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((T, bm, P), lambda ib: (0, ib, 0)),
            pl.BlockSpec((L, P + H, 4 * H), lambda ib: (0, 0, 0)),
            *s_spec,
            pl.BlockSpec((L, 4 * H), lambda ib: (0, 0)),
            pl.BlockSpec((T, L, bm, H), lambda ib: (0, 0, ib, 0)),
            pl.BlockSpec((T, L, bm, H), lambda ib: (0, 0, ib, 0)),
            pl.BlockSpec((L, bm, H), lambda ib: (0, ib, 0)),
            pl.BlockSpec((L, bm, H), lambda ib: (0, ib, 0)),
        ],
        out_specs=[
            # constant index maps: the dw/db blocks are revisited by every
            # grid step; the actual cross-tile accumulation happens in the
            # persistent f32 scratch, written out on the last tile
            pl.BlockSpec((L, P + H, 4 * H), lambda ib: (0, 0, 0)),
            pl.BlockSpec((L, 4 * H), lambda ib: (0, 0)),
            pl.BlockSpec((T, bm, P), lambda ib: (0, ib, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(w.shape, dw_dt),
            jax.ShapeDtypeStruct(b.shape, db_dt),
            jax.ShapeDtypeStruct(xt.shape, x.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM(w.shape, F32),                    # dw accumulator
            pltpu.VMEM(b.shape, F32),                    # db accumulator
            pltpu.VMEM((L, bm, H), F32),                 # dc time-carry
            pltpu.VMEM((L, bm, H), F32),                 # dh time-carry
        ],
        compiler_params=compiler_params(),
        interpret=resolve_interpret(interpret),
    )(xt, w, *s_in, b, ct, ht, dc, dh)
    return dw, db, jnp.swapaxes(dxt, 0, 1)               # dx: (B, T, P)


def _lstm_seq_bwd_chunked_call(w, b, xt, ct, ht, dc, dh, bm: int, tc: int,
                               interpret: bool | None, scales=None):
    """Streamed reverse sweep: x + trajectories in HBM, O(tc) VMEM."""
    from repro.core import tiling
    from repro.kernels.lstm_seq import _pad_axis

    L, H = w.shape[0], w.shape[-1] // 4
    P = w.shape[1] - H
    T, B, _ = xt.shape
    n_tiles = pl.cdiv(B, bm)
    Bp = n_tiles * bm
    nc = pl.cdiv(T, tc)
    Tp = nc * tc              # time-padded dx: chunk windows stay disjoint
    tw = tc + 1 if nc > 1 else tc
    Pl, Hl = tiling.lanes(P), tiling.lanes(H)
    xt = _pad_axis(_pad_axis(xt, 1, Bp), 2, Pl)
    ct = _pad_axis(_pad_axis(ct, 2, Bp), 3, Hl)
    ht = _pad_axis(_pad_axis(ht, 2, Bp), 3, Hl)
    dc = _pad_axis(dc, 1, Bp)
    dh = _pad_axis(dh, 1, Bp)
    if scales is None:
        kernel = functools.partial(_seq_bwd_chunked_kernel, n_layers=L,
                                   seq_len=T, p_width=P, tc=tc, tw=tw,
                                   nc=nc, n_tiles=n_tiles, batch=B)
        s_in, s_spec = (), ()
        dw_dt, db_dt = w.dtype, b.dtype
    else:
        kernel = functools.partial(_seq_bwd_chunked_q8_kernel, n_layers=L,
                                   seq_len=T, p_width=P, tc=tc, tw=tw,
                                   nc=nc, n_tiles=n_tiles, batch=B)
        s_in = (scales,)
        s_spec = (pl.BlockSpec((L, 4 * H), lambda ib: (0, 0)),)
        dw_dt, db_dt = F32, F32       # straight-through master-weight grads
    dw, db, dxt = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),        # x streams manually
            pl.BlockSpec((L, P + H, 4 * H), lambda ib: (0, 0, 0)),
            *s_spec,
            pl.BlockSpec((L, 4 * H), lambda ib: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),        # c_traj streams
            pl.BlockSpec(memory_space=pl.ANY),        # h_traj streams
            pl.BlockSpec((L, bm, H), lambda ib: (0, ib, 0)),
            pl.BlockSpec((L, bm, H), lambda ib: (0, ib, 0)),
        ],
        out_specs=[
            # constant index maps: dw/db accumulate in persistent scratch,
            # written on the last batch tile (same contract as unchunked)
            pl.BlockSpec((L, P + H, 4 * H), lambda ib: (0, 0, 0)),
            pl.BlockSpec((L, 4 * H), lambda ib: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),        # dx streams out
        ],
        out_shape=[
            jax.ShapeDtypeStruct(w.shape, dw_dt),
            jax.ShapeDtypeStruct(b.shape, db_dt),
            jax.ShapeDtypeStruct((Tp, Bp, Pl), xt.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, tc, bm, Pl), xt.dtype),       # x double buffer
            pltpu.VMEM((2, tw, L, bm, Hl), F32),         # c_traj window
            pltpu.VMEM((2, tw, L, bm, Hl), F32),         # h_traj window
            pltpu.VMEM((2, tc, bm, Pl), xt.dtype),       # dx staging
            pltpu.VMEM(w.shape, F32),                    # dw accumulator
            pltpu.VMEM(b.shape, F32),                    # db accumulator
            pltpu.VMEM((L, bm, H), F32),                 # dc time-carry
            pltpu.VMEM((L, bm, H), F32),                 # dh time-carry
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=compiler_params(),
        interpret=resolve_interpret(interpret),
    )(xt, w, *s_in, b, ct, ht, dc, dh)
    return dw, db, jnp.swapaxes(dxt[:T, :B, :P], 0, 1)   # dx: (B, T, P)


def lstm_seq_bwd(w, b, x, ct, ht, dc, dh, *, block_b: int,
                 time_chunk: int | None = None,
                 interpret: bool | None = None,
                 scales=None):
    """Whole-sequence BPTT in ONE dispatch: (dw, db, dx).

    w: (L, P+H, 4H); b: (L, 4H); x: (B, T, P) padded input;
    ct/ht: (T, L, B, H) f32 trajectories (lstm_seq trajectory contract);
    dc/dh: (L, B, H) cotangents of the final state.  ``block_b`` /
    ``time_chunk`` come from ``lstm_seq.choose_batch_block(mode="bwd")`` —
    callers must not dispatch this kernel when that returns None.
    ``time_chunk=None`` keeps x and both trajectories VMEM-resident;
    ``time_chunk=tc`` streams them in double-buffered reverse-order chunks
    (O(tc) residency, same gradients bit-for-bit).

    ``scales`` (optional): (L, 4H) f32 per-channel scales for the int8 path
    — ``w`` is then the int8 stack the q8 forward ran with, the gate
    recompute folds the scales exactly as the forward did, and (dw, db)
    come back in f32 (straight-through gradients for the master weights).
    """
    L, H = w.shape[0], w.shape[-1] // 4
    P = w.shape[1] - H
    B, T, xw = x.shape
    assert xw == P and ct.shape == (T, L, B, H) == ht.shape, \
        (w.shape, x.shape, ct.shape, ht.shape)
    assert dc.shape == (L, B, H) == dh.shape, (dc.shape, dh.shape)
    return _lstm_seq_bwd_call(w, b, x, ct, ht, dc, dh, block_b, time_chunk,
                              interpret, scales=scales)
