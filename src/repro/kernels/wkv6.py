"""RWKV6 chunked-scan Pallas TPU kernel — forward AND fused backward.

This is MobiRNN's coarse work-unit factorization applied to the RWKV6
recurrence: instead of T tiny sequential state updates (the "CUDA-style"
per-step plan, kernels/ref.wkv6_stepwise), the sequence is processed in
chunks of C steps.  Within a chunk everything is a dense MXU-friendly batch
of matmuls on VMEM tiles (one coarse work unit); only the (dk x dv) state
crosses chunk boundaries — it lives in a VMEM scratch accumulator across the
sequential chunk grid dimension, so it never round-trips to HBM during the
scan (the paper's preallocated-state-reuse rule).

Numerical safety: all within-chunk decay exponents are differences
L_a - L_b with a >= b of a running log-decay cumsum, hence <= 0 — no
exp overflow regardless of decay strength (logw <= 0).

Tiling (the lstm_seq contract, via core/tiling): the work unit is a
``(bh_tile, chunk)`` tile of the ``(BH, T)`` surface.  Batch-head rows are
independent, so they tile freely — ``bh_tile`` rows share one grid step,
their f32 states carried together in VMEM scratch (per-row math is
statically unrolled, so results are bit-identical at ANY bh_tile).  The
time axis STREAMS: the r/k/v/logw chunk windows live in HBM
(``pl.ANY``) and the kernel moves them through two-slot double-buffered
VMEM windows with async copies, prefetching chunk t+1 while chunk t
computes (pallas_guide §Double Buffering — the same pipeline as
kernels/lstm_seq's input streaming).  The backward streams the SAME windows
plus the dout cotangent and the stored trajectory states in REVERSE chunk
order.  Streaming changes data movement only — the chunk math is untouched,
so streamed kernels are bit-identical to the window-per-BlockSpec layout at
``chunk=1``, ``chunk=T``, and non-dividing ``T``/``BH``
(tests/test_wkv6.py asserts it).

Grid: (ceil(BH/bh_tile), ceil(T/C)); the chunk dimension is innermost
(sequential on TPU), so the scratch state carries correctly.  Non-dividing
T is zero-padded at the END: padded steps have r = k = v = 0 and logw = 0,
which is the IDENTITY on the state (exp(0) = 1 decay, zero k^T v outer
product) and contributes zero output rows that the wrapper slices off — so
padding never changes results, only the grid extent.  Non-dividing BH is
zero-padded the same way: batch-head rows are independent and all-zero
inputs with zero incoming state produce zero outputs and zero state, so the
padded tail rows of the shared f32 state scratch can never leak into real
rows; the wrapper slices them off.

Autodiff: ``pallas_call`` has no VJP rule, so ``wkv6`` wraps the kernel in a
``jax.custom_vjp`` mirroring kernels/lstm_seq.py.  Under differentiation the
forward runs a trajectory-emitting variant (same math, same single dispatch)
that additionally writes the CHUNK-INCOMING states ``s_traj
(BH, nt, dk, dv)`` — the residual the backward recomputes from — and the
backward runs the whole reverse-time sweep in ONE kernel dispatch: the grid
walks chunks in reverse, the streamed windows arrive through the same
two-slot prefetch pipeline (window t+1 of the SWEEP — chunk nt-2-t — in
flight while chunk nt-1-t computes), the state cotangent ``ds`` lives in
VMEM scratch across the sweep, ``du`` accumulates in scratch, and each
chunk's (dr, dk, dv, dlogw) falls out of ``jax.vjp`` of the pure chunk
math re-linearised from the stored incoming state.  ``value_and_grad`` is
exactly 2 Pallas dispatches at any T — O(1) in T, O(BH/bh_tile * T/C) grid
steps (``analysis.count_pallas_grid_steps``).  ``bwd=ORACLE_BWD`` restores
the oracle-VJP fallback (differentiate kernels/ref.wkv6), used when
``choose_chunk(mode="bwd")`` finds no viable chunk.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import factorization, tiling
from repro.kernels import compiler_params, resolve_interpret

F32 = jnp.float32

#: ``bwd=`` sentinel: differentiate the pure-jnp oracle instead of running
#: the fused reverse sweep (the principled fallback past the bwd budget).
ORACLE_BWD = 0
#: ``bwd=`` default: ONE reverse-order Pallas dispatch for the whole sweep.
FUSED_BWD = 1


# ---------------------------------------------------------------------------
# VMEM budget — the (bh_tile, chunk) analogue of lstm_seq's
# (block_b, time_chunk), built on the same core/tiling substrate.
# ---------------------------------------------------------------------------
class WkvBlocks(NamedTuple):
    """The chunked-scan kernel's tiling decision: chunk length x BH tile.

    ``chunk`` is the work-unit-coarseness knob of the WKV6 plan — larger C
    means denser MXU matmuls and fewer grid steps (O(T/C)), at the price of
    the (C, C, dk) f32 intra-chunk decay tensor, the dominant VMEM term.
    ``bh_tile`` is the batch axis of the same surface — how many
    independent batch-head rows share one grid step (coarser = fewer grid
    steps, more streamed-window and state bytes per step).

    Presents the family-generic ``core/tiling.TilePlan`` interface:
    ``batch_tile`` is this family's ``bh_tile`` (fused B*H rows),
    ``time_chunk`` its ``chunk`` (this grid always streams time, so it is
    never None)."""
    chunk: int
    bh_tile: int = 1

    @property
    def batch_tile(self) -> int:
        return self.bh_tile

    @property
    def time_chunk(self) -> int:
        return self.chunk


def working_set_bytes(seq_len: int, dk: int, dv: int, chunk: int,
                      dtype_bytes: int = 4, mode: str = "fwd", *,
                      bh_tile: int = 1) -> int:
    """VMEM working set of one (bh_tile, chunk) grid step, per phase.

    ``mode="fwd"`` sizes the inference forward: the two-slot double-buffered
    r/k/v/logw streamed windows + the output tile, u, the s0/s_out blocks,
    the f32 state scratch (all x ``bh_tile`` rows), and the (C, C, dk) f32
    intra-chunk decay tensor plus its (C, C) score matrix — priced once,
    not per row, because the per-row chunk math unrolls sequentially within
    the grid step; it is the term that grows quadratically in C and makes
    the chunk length a real budget decision.

    ``mode="bwd"`` sizes the reverse-sweep dispatch, which strictly
    dominates the trajectory-emitting forward that feeds it: on top of the
    forward set it holds the two-slot streamed chunk-incoming state and
    dout cotangent windows, the mirrored (dr, dk, dv, dlogw) output tiles,
    the ds state-cotangent scratch + ds0/ds_fin blocks, the du accumulator,
    and a second copy of the intra-chunk tensors (the linearised chunk
    recompute keeps forward values live while the cotangent flows back) —
    roughly 3x the forward working set at typical head shapes.
    """
    ws = tiling.WorkingSet(mode)
    C = max(1, min(chunk, seq_len))
    bt = max(1, bh_tile)
    row_in = (3 * C * dk + C * dv) * dtype_bytes       # r, k, logw | v
    out_tile = bt * C * dv * dtype_bytes
    intra = C * C * dk * 4 + C * C * 4                 # exp(diff) + scores
    ws.add("in_windows", tiling.STREAM_SLOTS * bt * row_in)
    ws.add("out_tile", out_tile)
    ws.add("u", bt * dk * 4)
    ws.add("state_io", 2 * bt * dk * dv * 4)           # s0 in + s_out out
    ws.add("state_scratch", bt * dk * dv * 4)          # carried states
    ws.add("intra", intra)
    ws.add("straj_windows", tiling.STREAM_SLOTS * bt * dk * dv * 4,
           bwd_only=True)
    ws.add("dout_windows", tiling.STREAM_SLOTS * out_tile, bwd_only=True)
    ws.add("grad_tiles", bt * row_in, bwd_only=True)   # dr/dk/dv/dlogw
    ws.add("ds", 3 * bt * dk * dv * 4, bwd_only=True)  # scratch + ds0/dsf
    ws.add("du", bt * dk * 4, bwd_only=True)
    ws.add("intra_linearised", intra, bwd_only=True)
    return ws.total()


def choose_blocks(n_bh: int, seq_len: int, dk: int, dv: int, *,
                  target: int = 32, dtype_bytes: int = 4,
                  vmem_budget: int | None = None,
                  mode: str = "fwd") -> WkvBlocks | None:
    """Pick the (chunk, bh_tile), or None when not viable — the
    SeqBlocks-style decision function, via the shared
    ``core/tiling.joint_search`` in MobiRNN coarseness order: the BH tile
    seeds at ``n_bh`` (coarsest — one grid row), the chunk halves from
    ``target`` (clamped to T) first, and only when even C=1 does not fit
    does the BH tile halve — the same keep-the-batch-tile-coarse priority
    as lstm_seq.choose_batch_block.  This kernel always streams the time
    axis (there is no whole-T-resident layout), so the search runs with
    ``whole_t_first=False``: the coarsest chunk IS the coarsest residency.

    Returns None only when even (bh_tile=1, C=1) does not fit — i.e. the
    per-head state blocks themselves blow VMEM; T alone never disqualifies
    the plan (the grid streams chunks, residency is O(C) in sequence
    length).  Callers then route to the stepwise/XLA plan (fwd) or the
    oracle VJP (bwd)."""
    budget = factorization.DEFAULT_VMEM_BUDGET if vmem_budget is None \
        else vmem_budget

    def fits(bt: int, tc: int | None) -> bool:
        return working_set_bytes(seq_len, dk, dv, tc, dtype_bytes,
                                 mode=mode, bh_tile=bt) <= budget

    found = tiling.joint_search(
        n_bh, seq_len, fits, seed_batch_tile=n_bh, whole_t_first=False,
        chunk_start=max(1, min(target, seq_len)))
    if found is None:
        return None
    bt, c = found
    return WkvBlocks(c, bt)


def choose_chunk(seq_len: int, dk: int, dv: int, *, target: int = 32,
                 dtype_bytes: int = 4, vmem_budget: int | None = None,
                 mode: str = "fwd") -> WkvBlocks | None:
    """DEPRECATED thin alias for ``choose_blocks(1, ...)`` — the chunk-only
    decision at ``bh_tile=1`` (one BH row per grid step, grid steps exactly
    BH * ceil(T/C)).  ``choose_blocks`` is the joint surface every family
    exposes; call it directly."""
    import warnings
    warnings.warn("wkv6.choose_chunk is deprecated; call "
                  "choose_blocks(1, seq_len, dk, dv, ...)",
                  DeprecationWarning, stacklevel=2)
    return choose_blocks(1, seq_len, dk, dv, target=target,
                         dtype_bytes=dtype_bytes, vmem_budget=vmem_budget,
                         mode=mode)


# ---------------------------------------------------------------------------
# Shared chunk math — the single source of truth for fwd, traj, and bwd.
# ---------------------------------------------------------------------------
def _chunk_math(r, k, v, logw, u, s):
    """One chunk of the recurrence in f32.  r,k,logw: (C, dk); v: (C, dv);
    u: (dk,); s: (dk, dv).  Returns (out (C, dv), s_new (dk, dv)).

    Shared by the plain and trajectory-emitting kernel bodies (so the two
    forward dispatches are bit-identical) and DIFFERENTIATED via ``jax.vjp``
    inside the reverse-sweep kernel body — the chunk backward needs no
    hand-derived math, only the stored incoming state."""
    C = r.shape[0]
    L = jnp.cumsum(logw, axis=0)
    L_prev = L - logw
    # carry term r_i diag(exp(L_prev_i)) S  — one (C,dk)x(dk,dv) MXU matmul
    out = jax.lax.dot(r * jnp.exp(L_prev), s, preferred_element_type=F32)
    # intra-chunk: A[i,j,c] = exp(L_prev[i,c] - L[j,c]), j < i (exponent <= 0)
    diff = L_prev[:, None, :] - L[None, :, :]
    mask = (jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
            > jax.lax.broadcasted_iota(jnp.int32, (C, C), 1))
    # mask the EXPONENT, not the scores: the j >= i entries are positive and
    # overflow exp to inf under strong decay — the forward would mask the
    # infs away, but the einsum VJP then multiplies inf by the zeroed
    # cotangent and turns every gradient into NaN
    diff = jnp.where(mask[:, :, None], diff, -jnp.inf)
    scores = jnp.einsum("ic,jc,ijc->ij", r, k, jnp.exp(diff),
                        preferred_element_type=F32)
    out = out + jax.lax.dot(scores, v, preferred_element_type=F32)
    # bonus diagonal term
    bonus = jnp.sum(r * u[None, :] * k, axis=-1, keepdims=True)
    out = out + bonus * v
    # state update
    L_last = L[-1]
    decay_j = jnp.exp(L_last[None, :] - L)
    s_new = (jnp.exp(L_last)[:, None] * s
             + jax.lax.dot((k * decay_j).T, v, preferred_element_type=F32))
    return out, s_new


# ---------------------------------------------------------------------------
# Kernel bodies — time windows stream through two-slot VMEM double buffers.
# ---------------------------------------------------------------------------
def _window_dma(hbm, buf, sems, j, slot, idx, *, ib, bt, chunk):
    """Async copy of chunk window ``idx`` of stream ``j`` into buffer slot
    ``slot``: a (bt, chunk, d) tile of the (BHp, Tp, d) HBM array (the
    wrapper zero-pads both axes, so the window is always in bounds).
    ``ib`` is the BH-tile id, captured ONCE at kernel top — calling
    ``pl.program_id`` inside a ``pl.when`` branch does not lower."""
    return pltpu.make_async_copy(
        hbm.at[pl.ds(ib * bt, bt), pl.ds(idx * chunk, chunk), :],
        buf.at[slot], sems.at[j, slot])


def _fwd_body(r_hbm, k_hbm, v_hbm, lw_hbm, u_ref, s0_ref, out_ref, s_out_ref,
              straj_ref, rbuf, kbuf, vbuf, lwbuf, state, sems):
    """Forward/trajectory body: chunk t's r/k/v/logw windows arrive through
    the two-slot pipeline (slot t%2 computes while slot (t+1)%2 prefetches),
    the bh_tile f32 states carry in VMEM scratch across the inner grid
    dimension, and the per-row chunk math is STATICALLY unrolled so results
    are bit-identical at any bh_tile."""
    ib = pl.program_id(0)
    t = pl.program_id(1)
    nt = pl.num_programs(1)
    bt, chunk = rbuf.shape[1], rbuf.shape[2]
    streams = ((r_hbm, rbuf), (k_hbm, kbuf), (v_hbm, vbuf), (lw_hbm, lwbuf))

    def dma(j, slot, idx):
        hbm, buf = streams[j]
        return _window_dma(hbm, buf, sems, j, slot, idx, ib=ib, bt=bt,
                           chunk=chunk)

    @pl.when(t == 0)
    def _init():
        for j in range(len(streams)):                    # warm-up windows
            dma(j, 0, 0).start()
        state[...] = s0_ref[...].astype(F32)

    slot = jax.lax.rem(t, 2)

    @pl.when(t + 1 < nt)
    def _prefetch():
        nxt = jax.lax.rem(t + 1, 2)
        for j in range(len(streams)):
            dma(j, nxt, t + 1).start()

    for j in range(len(streams)):
        dma(j, slot, t).wait()

    r = rbuf[slot].astype(F32)
    k = kbuf[slot].astype(F32)
    v = vbuf[slot].astype(F32)
    logw = lwbuf[slot].astype(F32)
    for i in range(bt):                                  # static unroll
        s_in = state[i]
        if straj_ref is not None:
            straj_ref[i, 0] = s_in                # incoming state of chunk t
        out, s_new = _chunk_math(r[i], k[i], v[i], logw[i],
                                 u_ref[i].astype(F32), s_in)
        state[i] = s_new
        out_ref[i] = out.astype(out_ref.dtype)

    @pl.when(t == nt - 1)
    def _final():
        s_out_ref[...] = state[...].astype(s_out_ref.dtype)


def _kernel(r_hbm, k_hbm, v_hbm, lw_hbm, u_ref, s0_ref, out_ref, s_out_ref,
            rbuf, kbuf, vbuf, lwbuf, state, sems):
    _fwd_body(r_hbm, k_hbm, v_hbm, lw_hbm, u_ref, s0_ref, out_ref, s_out_ref,
              None, rbuf, kbuf, vbuf, lwbuf, state, sems)


def _traj_kernel(r_hbm, k_hbm, v_hbm, lw_hbm, u_ref, s0_ref, out_ref,
                 s_out_ref, straj_ref, rbuf, kbuf, vbuf, lwbuf, state, sems):
    """Trajectory-emitting forward: same math and dispatch count as
    ``_kernel``, plus the CHUNK-INCOMING states written to ``s_traj`` —
    the residual the reverse sweep re-linearises each chunk from."""
    _fwd_body(r_hbm, k_hbm, v_hbm, lw_hbm, u_ref, s0_ref, out_ref, s_out_ref,
              straj_ref, rbuf, kbuf, vbuf, lwbuf, state, sems)


def _bwd_kernel(r_hbm, k_hbm, v_hbm, lw_hbm, u_ref, straj_hbm, do_hbm,
                dsf_ref, dr_ref, dk_ref, dv_ref, dlw_ref, du_ref, ds0_ref,
                rbuf, kbuf, vbuf, lwbuf, dobuf, sbuf, ds_scr, du_scr, sems):
    """Reverse-time BPTT sweep over chunks — ONE dispatch for the whole
    backward.  Grid step t processes chunk nt-1-t; the r/k/v/logw/dout
    windows AND the stored chunk-incoming states stream through the same
    two-slot pipeline as the forward, in REVERSE chunk order (sweep window
    t+1 — chunk nt-2-t — prefetches while chunk nt-1-t computes).  The
    state cotangents ``ds`` carry across grid steps in VMEM scratch (seeded
    from the final-state cotangent at reverse step 0), ``du`` accumulates
    per row in scratch, and both are written once at the last reverse step,
    where ``ds0`` (the cotangent of the incoming state) is also emitted."""
    ib = pl.program_id(0)
    t = pl.program_id(1)
    nt = pl.num_programs(1)
    bt, chunk = rbuf.shape[1], rbuf.shape[2]
    kc = nt - 1 - t                           # reverse-order chunk index
    win_streams = ((r_hbm, rbuf), (k_hbm, kbuf), (v_hbm, vbuf),
                   (lw_hbm, lwbuf), (do_hbm, dobuf))
    n_streams = len(win_streams) + 1          # + the s_traj state stream

    def dma(j, slot, idx):
        if j < len(win_streams):
            hbm, buf = win_streams[j]
            return _window_dma(hbm, buf, sems, j, slot, idx, ib=ib, bt=bt,
                               chunk=chunk)
        return pltpu.make_async_copy(         # (bt, 1, dk, dv) state window
            straj_hbm.at[pl.ds(ib * bt, bt), pl.ds(idx, 1), :, :],
            sbuf.at[slot], sems.at[j, slot])

    @pl.when(t == 0)
    def _init():
        for j in range(n_streams):            # warm-up: last chunk's windows
            dma(j, 0, kc).start()
        ds_scr[...] = dsf_ref[...].astype(F32)
        du_scr[...] = jnp.zeros_like(du_scr)

    slot = jax.lax.rem(t, 2)

    @pl.when(t + 1 < nt)
    def _prefetch():
        nxt = jax.lax.rem(t + 1, 2)
        for j in range(n_streams):
            dma(j, nxt, kc - 1).start()

    for j in range(n_streams):
        dma(j, slot, kc).wait()

    r = rbuf[slot].astype(F32)
    k = kbuf[slot].astype(F32)
    v = vbuf[slot].astype(F32)
    logw = lwbuf[slot].astype(F32)
    dout = dobuf[slot].astype(F32)
    for i in range(bt):                                  # static unroll
        _, chunk_vjp = jax.vjp(_chunk_math, r[i], k[i], v[i], logw[i],
                               u_ref[i].astype(F32), sbuf[slot, i, 0])
        dr, dkk, dvv, dlw, du, ds_in = chunk_vjp((dout[i], ds_scr[i]))
        ds_scr[i] = ds_in
        du_scr[i] = du_scr[i] + du
        dr_ref[i] = dr.astype(dr_ref.dtype)
        dk_ref[i] = dkk.astype(dk_ref.dtype)
        dv_ref[i] = dvv.astype(dv_ref.dtype)
        dlw_ref[i] = dlw.astype(dlw_ref.dtype)

    @pl.when(t == nt - 1)                     # reverse-last = chunk 0
    def _final():
        du_ref[...] = du_scr[...].astype(du_ref.dtype)
        ds0_ref[...] = ds_scr[...].astype(ds0_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call wrappers (T % chunk == 0, BH % bh_tile == 0 — the entry pads)
# ---------------------------------------------------------------------------
_ANY = functools.partial(pl.BlockSpec, memory_space=pl.ANY)


def _fwd_call(r, k, v, logw, u, state, chunk, bh_tile, interpret,
              traj: bool):
    BH, T, dk = r.shape
    dv = v.shape[-1]
    assert T % chunk == 0 and BH % bh_tile == 0, (T, chunk, BH, bh_tile)
    nt = T // chunk
    bt = bh_tile
    in_specs = [_ANY(), _ANY(), _ANY(), _ANY()] + [
        pl.BlockSpec((bt, dk), lambda b, t: (b, 0)),
        pl.BlockSpec((bt, dk, dv), lambda b, t: (b, 0, 0)),
    ]
    out_specs = [
        pl.BlockSpec((bt, chunk, dv), lambda b, t: (b, t, 0)),
        pl.BlockSpec((bt, dk, dv), lambda b, t: (b, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((BH, T, dv), v.dtype),
        jax.ShapeDtypeStruct((BH, dk, dv), jnp.float32),
    ]
    kernel = _kernel
    if traj:
        kernel = _traj_kernel
        out_specs.append(pl.BlockSpec((bt, 1, dk, dv),
                                      lambda b, t: (b, t, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((BH, nt, dk, dv), jnp.float32))
    return pl.pallas_call(
        kernel,
        grid=(BH // bt, nt),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((2, bt, chunk, dk), r.dtype),
                        pltpu.VMEM((2, bt, chunk, dk), k.dtype),
                        pltpu.VMEM((2, bt, chunk, dv), v.dtype),
                        pltpu.VMEM((2, bt, chunk, dk), logw.dtype),
                        pltpu.VMEM((bt, dk, dv), jnp.float32),
                        pltpu.SemaphoreType.DMA((4, 2))],
        compiler_params=compiler_params(),
        interpret=resolve_interpret(interpret),
    )(r, k, v, logw, u, state)


def _bwd_call(r, k, v, logw, u, s_traj, dout, ds_fin, s0_dtype, chunk,
              bh_tile, interpret):
    BH, T, dk = r.shape
    dv = v.shape[-1]
    nt = T // chunk
    bt = bh_tile
    rev = nt - 1                              # reversed chunk index map

    in_specs = [_ANY(), _ANY(), _ANY(), _ANY()] + [
        pl.BlockSpec((bt, dk), lambda b, t: (b, 0)),
        _ANY(),                               # s_traj streams in reverse
        _ANY(),                               # dout streams in reverse
        pl.BlockSpec((bt, dk, dv), lambda b, t: (b, 0, 0)),
    ]
    out_specs = [
        pl.BlockSpec((bt, chunk, dk), lambda b, t: (b, rev - t, 0)),
        pl.BlockSpec((bt, chunk, dk), lambda b, t: (b, rev - t, 0)),
        pl.BlockSpec((bt, chunk, dv), lambda b, t: (b, rev - t, 0)),
        pl.BlockSpec((bt, chunk, dk), lambda b, t: (b, rev - t, 0)),
        pl.BlockSpec((bt, dk), lambda b, t: (b, 0)),
        pl.BlockSpec((bt, dk, dv), lambda b, t: (b, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct(r.shape, r.dtype),
        jax.ShapeDtypeStruct(k.shape, k.dtype),
        jax.ShapeDtypeStruct(v.shape, v.dtype),
        jax.ShapeDtypeStruct(logw.shape, logw.dtype),
        jax.ShapeDtypeStruct(u.shape, u.dtype),
        jax.ShapeDtypeStruct((BH, dk, dv), s0_dtype),
    ]
    return pl.pallas_call(
        _bwd_kernel,
        grid=(BH // bt, nt),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((2, bt, chunk, dk), r.dtype),
                        pltpu.VMEM((2, bt, chunk, dk), k.dtype),
                        pltpu.VMEM((2, bt, chunk, dv), v.dtype),
                        pltpu.VMEM((2, bt, chunk, dk), logw.dtype),
                        pltpu.VMEM((2, bt, chunk, dv), dout.dtype),
                        pltpu.VMEM((2, bt, 1, dk, dv), jnp.float32),
                        pltpu.VMEM((bt, dk, dv), jnp.float32),
                        pltpu.VMEM((bt, dk), jnp.float32),
                        pltpu.SemaphoreType.DMA((6, 2))],
        compiler_params=compiler_params(),
        interpret=resolve_interpret(interpret),
    )(r, k, v, logw, u, s_traj, dout, ds_fin)


# ---------------------------------------------------------------------------
# custom VJP — 1 dispatch fwd, 2 dispatches per value_and_grad
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _wkv6(r, k, v, logw, u, s0, chunk, bh_tile, bwd, interpret):
    out, s_out = _fwd_call(r, k, v, logw, u, s0, chunk, bh_tile, interpret,
                           traj=False)
    return out, s_out


def _wkv6_fwd(r, k, v, logw, u, s0, chunk, bh_tile, bwd, interpret):
    if bwd == ORACLE_BWD:
        out, s_out = _fwd_call(r, k, v, logw, u, s0, chunk, bh_tile,
                               interpret, traj=False)
        return (out, s_out), (r, k, v, logw, u, s0, None)
    out, s_out, s_traj = _fwd_call(r, k, v, logw, u, s0, chunk, bh_tile,
                                   interpret, traj=True)
    return (out, s_out), (r, k, v, logw, u, s0, s_traj)


def _oracle(r, k, v, logw, u, s0, chunk):
    """Batched pure-jnp reference with the kernel's exact output dtypes —
    the oracle-VJP fallback differentiates this."""
    from repro.kernels import ref

    out, s_out = jax.vmap(
        lambda rr, kk, vv, ww, uu, ss: ref.wkv6(rr, kk, vv, ww, uu, ss,
                                                chunk))(r, k, v, logw, u, s0)
    return out.astype(v.dtype), s_out.astype(jnp.float32)


def _wkv6_bwd(chunk, bh_tile, bwd, interpret, residuals, cots):
    r, k, v, logw, u, s0, s_traj = residuals
    dout, ds_fin = cots
    if bwd == ORACLE_BWD:
        _, oracle_vjp = jax.vjp(
            lambda *a: _oracle(*a, chunk), r, k, v, logw, u, s0)
        return oracle_vjp((dout, ds_fin))
    return _bwd_call(r, k, v, logw, u, s_traj, dout, ds_fin, s0.dtype,
                     chunk, bh_tile, interpret)


_wkv6.defvjp(_wkv6_fwd, _wkv6_bwd)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "bh_tile", "bwd", "interpret"))
def wkv6(r: jax.Array, k: jax.Array, v: jax.Array, logw: jax.Array,
         u: jax.Array, state: jax.Array, *, chunk: int = 32,
         bh_tile: int = 1, bwd: int = FUSED_BWD,
         interpret: bool | None = None
         ) -> tuple[jax.Array, jax.Array]:
    """Chunked RWKV6 scan over full sequences — ONE Pallas dispatch.

    r, k, logw: (BH, T, dk); v: (BH, T, dv); u: (BH, dk);
    state: (BH, dk, dv).  Any T and BH — non-dividing axes are zero-padded
    to the next chunk/bh_tile multiple (identity on the state: logw = 0,
    zero kv; padded BH rows are fully zero and independent) and the padded
    output rows sliced off.  ``chunk`` is clamped to T and ``bh_tile`` to
    BH.  Returns (out (BH, T, dv), final state (BH, dk, dv) f32).

    Differentiable: under ``jax.grad`` the forward becomes the
    trajectory-emitting kernel and the backward ONE reverse-sweep dispatch
    (``bwd=FUSED_BWD``, the default) — or the oracle VJP replay
    (``bwd=ORACLE_BWD``) when the caller's ``choose_chunk(mode="bwd")``
    found no viable chunk.
    """
    BH, T, dk = r.shape
    chunk = max(1, min(chunk, T))
    bh_tile = max(1, min(bh_tile, BH))
    from repro.obs import trace as trace_lib
    tracer = trace_lib.get_tracer()
    if tracer.enabled:
        tracer.event("plan/dispatch", family="rwkv6", plan="chunked_scan",
                     chunk=chunk, bh_tile=bh_tile, bwd=bwd, n_bh=BH,
                     seq_len=T)
    pad = (-T) % chunk
    padb = (-BH) % bh_tile
    if pad or padb:
        def zpad(a):
            return jnp.pad(a, ((0, padb), (0, pad), (0, 0)))

        r, k, v, logw = zpad(r), zpad(k), zpad(v), zpad(logw)
        if padb:
            u = jnp.pad(u, ((0, padb), (0, 0)))
            state = jnp.pad(state, ((0, padb), (0, 0), (0, 0)))
    out, s_out = _wkv6(r, k, v, logw, u, state, chunk, bh_tile, bwd,
                       interpret)
    if pad or padb:
        out = out[:BH, :T]
        s_out = s_out[:BH]
    return out, s_out
