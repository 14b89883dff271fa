"""Mamba (S6) fused selective-scan Pallas kernel — forward AND backward.

The third recurrence family on the MobiRNN substrate.  Unlike RWKV6, the
selective scan admits NO matmul-form chunking: the decay exp(dt ⊙ A) is
per-(channel, state) and data-dependent, so the (C, C) intra-chunk kernel
trick would blow up per channel x state (models/mamba.py, DESIGN.md).  The
coarse work unit here is therefore a STEPWISE chunk: one grid step advances
``chunk`` timesteps of a ``block_b`` batch tile with the faithful per-step
recurrence (a ``lax.scan`` inside the kernel body), and the f32
(block_b, d_inner, d_state) state lives in VMEM scratch carried across the
sequential time-chunk grid dimension — the paper's preallocated-state-reuse
rule, same as lstm_seq's (c, h) carries and wkv6's (dk, dv) state.  What
the fusion buys is MobiRNN's §3.1 dispatch economics: ONE ``pallas_call``
for any T instead of the XLA scan's per-step op stream, with chunking
changing I/O granularity ONLY — the per-step math is identical at every
(block_b, chunk), so results match the ``lax.scan`` oracle at plain f32
tolerances.

Tiling rides the shared ``core/tiling`` substrate: ``working_set_bytes``
is a WorkingSet term table (with the fwd/bwd mode split — the backward
holds the linearised scan residuals, the dominant bwd-only term) and
``choose_blocks`` runs the family-generic coarseness-ordered
``(block_b, chunk)`` joint search, whole-T residency first (``chunk=T`` —
one grid step per tile) before halving chunks, then batch tiles.

Autodiff mirrors kernels/wkv6.py: a ``jax.custom_vjp`` whose forward (under
differentiation) runs a trajectory-emitting variant writing the
CHUNK-INCOMING states ``h_traj (B, nt, di, ds)`` — the residual — and whose
backward is ONE reverse-order dispatch: the grid walks chunks backward via
reversed index maps, ``jax.vjp`` of the pure chunk scan re-linearises each
chunk from its stored incoming state, the state cotangent ``dh`` carries in
VMEM scratch, and ``da`` accumulates in scratch across ALL grid steps
(batch tiles included — the lstm_seq_bwd dw idiom) and is emitted once at
the last step.  ``value_and_grad`` is exactly 2 Pallas dispatches at any T.
``bwd=ORACLE_BWD`` differentiates the ``lax.scan`` reference instead —
the fallback when ``choose_blocks(mode="bwd")`` finds nothing.

Non-dividing shapes zero-pad at the END of either axis: padded steps have
dt = x = b = c = 0, which is the IDENTITY on the state (decay exp(0) = 1,
zero injection) and yields zero output rows the wrapper slices off; padded
batch rows are fully zero and independent, so the shared f32 state scratch
never leaks across rows.
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

#: ``bwd=`` sentinel: differentiate the lax.scan reference instead of
#: running the fused reverse sweep (the fallback past the bwd budget).
ORACLE_BWD = 0
#: ``bwd=`` default: ONE reverse-order Pallas dispatch for the whole sweep.
FUSED_BWD = 1


# ---------------------------------------------------------------------------
# VMEM budget — the (block_b, chunk) decision on the shared substrate.
# ---------------------------------------------------------------------------
class MambaBlocks(NamedTuple):
    """The fused scan's tiling decision: batch tile x time chunk.

    ``chunk`` here changes I/O granularity only (the recurrence is
    per-step either way) — larger chunks mean fewer grid steps and larger
    streamed tiles; ``chunk == seq_len`` is the whole-T-resident layout,
    one grid step per batch tile.

    Presents the family-generic ``core/tiling.TilePlan`` interface:
    ``batch_tile`` is this family's ``block_b``, ``time_chunk`` its
    ``chunk`` (whole-T residency is spelled ``chunk == seq_len`` here,
    never None)."""
    block_b: int
    chunk: int

    @property
    def batch_tile(self) -> int:
        return self.block_b

    @property
    def time_chunk(self) -> int:
        return self.chunk


def working_set_bytes(seq_len: int, d_inner: int, d_state: int,
                      block_b: int, chunk: int, dtype_bytes: int = 4,
                      mode: str = "fwd") -> int:
    """VMEM working set of one (block_b, chunk) grid step, per phase.

    ``mode="fwd"``: the pipelined x/dt/b/c input tiles and y output tile
    (x STREAM_SLOTS — pallas double-buffers revisited blocks), A, the
    h0/h_out blocks, and the f32 state scratch.

    ``mode="bwd"`` sizes the reverse-sweep dispatch, which strictly
    dominates the trajectory-emitting forward: on top of the forward set it
    holds the stored chunk-incoming state tile, the dy cotangent tile, the
    mirrored (dx, ddt, db, dc) output tiles, the dh scratch + dh0/dh_fin
    blocks, the da accumulator + output, and the linearised scan residuals
    (~3 state-sized tensors PER STEP of the chunk — the dominant bwd term,
    which is what pushes the chunk DOWN in training where the forward
    would happily take chunk = T)."""
    ws = tiling.WorkingSet(mode)
    C = max(1, min(chunk, seq_len))
    bm = max(1, block_b)
    in_tiles = (bm * C * d_inner * dtype_bytes        # x
                + bm * C * d_inner * 4                # dt (f32)
                + 2 * bm * C * d_state * 4)           # b, c (f32)
    out_tile = bm * C * d_inner * dtype_bytes
    state = bm * d_inner * d_state * 4
    ws.add("in_tiles", tiling.STREAM_SLOTS * in_tiles)
    ws.add("out_tile", tiling.STREAM_SLOTS * out_tile)
    ws.add("a", d_inner * d_state * 4)
    ws.add("state_io", 2 * state)                     # h0 in + h_out out
    ws.add("state_scratch", state)
    ws.add("htraj_tile", tiling.STREAM_SLOTS * state, bwd_only=True)
    ws.add("dy_tile", tiling.STREAM_SLOTS * out_tile, bwd_only=True)
    ws.add("grad_tiles", in_tiles, bwd_only=True)     # dx/ddt/db/dc
    ws.add("dh", 3 * state, bwd_only=True)            # scratch + dh0/dhf
    ws.add("da", 2 * d_inner * d_state * 4, bwd_only=True)
    ws.add("linearised_scan", 3 * C * state, bwd_only=True)
    return ws.total()


def choose_blocks(batch: int, seq_len: int, d_inner: int, d_state: int, *,
                  dtype_bytes: int = 4, vmem_budget: int | None = None,
                  mode: str = "fwd") -> MambaBlocks | None:
    """Pick the (block_b, chunk), or None when not viable — the shared
    ``core/tiling.joint_search`` in MobiRNN coarseness order: whole-T
    residency (``chunk = T``, one grid step per batch tile) at the full
    batch first, streamed chunks from T//2 down to 1 second, smaller batch
    tiles last.  Returns None only when even (1, 1) does not fit — the
    state blocks themselves blow VMEM; callers then route to the XLA scan
    (fwd) or the oracle VJP (bwd)."""
    budget = factorization.DEFAULT_VMEM_BUDGET if vmem_budget is None \
        else vmem_budget

    def fits(bm: int, tc: int | None) -> bool:
        c = seq_len if tc is None else tc
        return working_set_bytes(seq_len, d_inner, d_state, bm, c,
                                 dtype_bytes, mode=mode) <= budget

    found = tiling.joint_search(batch, seq_len, fits)
    if found is None:
        return None
    bm, tc = found
    return MambaBlocks(bm, seq_len if tc is None else tc)


# ---------------------------------------------------------------------------
# Shared chunk math — the single source of truth for fwd, traj, and bwd.
# ---------------------------------------------------------------------------
def _chunk_math(x, dt, b, c, a, h):
    """``chunk`` steps of the selective scan in f32, batched over the tile.
    x, dt: (bm, C, di); b, c: (bm, C, ds); a: (di, ds); h: (bm, di, ds).
    Returns (y (bm, C, di), h_new (bm, di, ds)).  The step body is the
    models/mamba._scan recurrence VERBATIM — chunking changes where the
    loop lives (inside one grid step), not the math."""

    def step(h, xs):
        x_t, dt_t, b_t, c_t = xs                     # (bm,di),(bm,di),(bm,ds)x2
        decay = jnp.exp(dt_t[..., None] * a)         # (bm,di,ds)
        dbx = (dt_t * x_t)[..., None] * b_t[:, None, :]
        h = decay * h + dbx
        y = jnp.einsum("bds,bs->bd", h, c_t)
        return h, y

    xs = (jnp.swapaxes(x, 0, 1), jnp.swapaxes(dt, 0, 1),
          jnp.swapaxes(b, 0, 1), jnp.swapaxes(c, 0, 1))
    h, ys = jax.lax.scan(step, h, xs)
    return jnp.swapaxes(ys, 0, 1), h


def mamba_scan_ref(x, dt, b, c, a, h0):
    """Pure ``lax.scan`` reference over the whole sequence — the oracle
    plan (and the dtype contract: y in x.dtype, final state f32)."""
    ys, h = _chunk_math(x.astype(F32), dt.astype(F32), b.astype(F32),
                        c.astype(F32), a.astype(F32), h0.astype(F32))
    return ys.astype(x.dtype), h


# ---------------------------------------------------------------------------
# Kernel bodies
# ---------------------------------------------------------------------------
def _load(refs):
    return tuple(ref[...].astype(F32) for ref in refs)


def _fwd_body(x_ref, dt_ref, b_ref, c_ref, a_ref, h0_ref, y_ref, h_out_ref,
              htraj_ref, state):
    t = pl.program_id(1)
    nt = pl.num_programs(1)
    x, dt, b, c, a = _load((x_ref, dt_ref, b_ref, c_ref, a_ref))

    @pl.when(t == 0)
    def _init():
        state[...] = h0_ref[...].astype(F32)

    h_in = state[...]
    if htraj_ref is not None:
        htraj_ref[:, 0] = h_in                # incoming state of chunk t
    ys, h_new = _chunk_math(x, dt, b, c, a, h_in)
    state[...] = h_new
    y_ref[...] = ys.astype(y_ref.dtype)

    @pl.when(t == nt - 1)
    def _final():
        h_out_ref[...] = h_new.astype(h_out_ref.dtype)


def _kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, h0_ref, y_ref, h_out_ref,
            state):
    _fwd_body(x_ref, dt_ref, b_ref, c_ref, a_ref, h0_ref, y_ref, h_out_ref,
              None, state)


def _traj_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, h0_ref, y_ref,
                 h_out_ref, htraj_ref, state):
    """Trajectory-emitting forward: same math and dispatch count as
    ``_kernel``, plus the CHUNK-INCOMING states written to ``h_traj`` —
    the residual the reverse sweep re-linearises each chunk from."""
    _fwd_body(x_ref, dt_ref, b_ref, c_ref, a_ref, h0_ref, y_ref, h_out_ref,
              htraj_ref, state)


def _bwd_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, htraj_ref, dy_ref,
                dhf_ref, dx_ref, ddt_ref, db_ref, dc_ref, da_ref, dh0_ref,
                dh_scr, da_scr):
    """Reverse-time sweep over chunks — ONE dispatch for the whole
    backward.  Grid step t processes chunk nt-1-t (reversed index maps);
    ``dh`` carries in scratch per batch tile (seeded from the final-state
    cotangent at reverse step 0), ``da`` accumulates in scratch across ALL
    grid steps — batch tiles included — and is emitted once at the very
    last step (the lstm_seq_bwd dw-accumulator idiom); ``dh0`` is emitted
    per tile at the last reverse step."""
    ib = pl.program_id(0)
    t = pl.program_id(1)
    nb = pl.num_programs(0)
    nt = pl.num_programs(1)
    x, dt, b, c, a = _load((x_ref, dt_ref, b_ref, c_ref, a_ref))
    dy = dy_ref[...].astype(F32)
    h_in = htraj_ref[:, 0]                    # chunk-incoming state (f32)

    @pl.when(jnp.logical_and(ib == 0, t == 0))
    def _zero_da():
        da_scr[...] = jnp.zeros_like(da_scr)

    @pl.when(t == 0)
    def _seed_dh():
        dh_scr[...] = dhf_ref[...].astype(F32)

    _, chunk_vjp = jax.vjp(_chunk_math, x, dt, b, c, a, h_in)
    dx, ddt, db, dc, da, dh = chunk_vjp((dy, dh_scr[...]))
    dh_scr[...] = dh
    da_scr[...] = da_scr[...] + da
    dx_ref[...] = dx.astype(dx_ref.dtype)
    ddt_ref[...] = ddt.astype(ddt_ref.dtype)
    db_ref[...] = db.astype(db_ref.dtype)
    dc_ref[...] = dc.astype(dc_ref.dtype)

    @pl.when(t == nt - 1)                     # reverse-last = chunk 0
    def _emit_dh0():
        dh0_ref[...] = dh_scr[...].astype(dh0_ref.dtype)

    @pl.when(jnp.logical_and(ib == nb - 1, t == nt - 1))
    def _emit_da():
        da_ref[...] = da_scr[...].astype(da_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call wrappers (T % chunk == 0, B % block_b == 0 — the entry pads)
# ---------------------------------------------------------------------------
def _fwd_call(x, dt, b, c, a, h0, chunk, block_b, interpret, traj: bool):
    B, T, di = x.shape
    ds = b.shape[-1]
    assert T % chunk == 0 and B % block_b == 0, (T, chunk, B, block_b)
    nt = T // chunk
    bm = block_b
    in_specs = [
        pl.BlockSpec((bm, chunk, di), lambda i, t: (i, t, 0)),
        pl.BlockSpec((bm, chunk, di), lambda i, t: (i, t, 0)),
        pl.BlockSpec((bm, chunk, ds), lambda i, t: (i, t, 0)),
        pl.BlockSpec((bm, chunk, ds), lambda i, t: (i, t, 0)),
        pl.BlockSpec((di, ds), lambda i, t: (0, 0)),
        pl.BlockSpec((bm, di, ds), lambda i, t: (i, 0, 0)),
    ]
    out_specs = [
        pl.BlockSpec((bm, chunk, di), lambda i, t: (i, t, 0)),
        pl.BlockSpec((bm, di, ds), lambda i, t: (i, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((B, T, di), x.dtype),
        jax.ShapeDtypeStruct((B, di, ds), jnp.float32),
    ]
    kernel = _kernel
    if traj:
        kernel = _traj_kernel
        out_specs.append(pl.BlockSpec((bm, 1, di, ds),
                                      lambda i, t: (i, t, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((B, nt, di, ds), jnp.float32))
    return pl.pallas_call(
        kernel,
        grid=(B // bm, nt),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bm, di, ds), jnp.float32)],
        compiler_params=compiler_params(),
        interpret=resolve_interpret(interpret),
    )(x, dt, b, c, a, h0)


def _bwd_call(x, dt, b, c, a, h_traj, dy, dh_fin, h0_dtype, chunk, block_b,
              interpret):
    B, T, di = x.shape
    ds = b.shape[-1]
    nt = T // chunk
    bm = block_b
    rev = nt - 1                              # reversed chunk index map

    in_specs = [
        pl.BlockSpec((bm, chunk, di), lambda i, t: (i, rev - t, 0)),
        pl.BlockSpec((bm, chunk, di), lambda i, t: (i, rev - t, 0)),
        pl.BlockSpec((bm, chunk, ds), lambda i, t: (i, rev - t, 0)),
        pl.BlockSpec((bm, chunk, ds), lambda i, t: (i, rev - t, 0)),
        pl.BlockSpec((di, ds), lambda i, t: (0, 0)),
        pl.BlockSpec((bm, 1, di, ds), lambda i, t: (i, rev - t, 0, 0)),
        pl.BlockSpec((bm, chunk, di), lambda i, t: (i, rev - t, 0)),
        pl.BlockSpec((bm, di, ds), lambda i, t: (i, 0, 0)),
    ]
    out_specs = [
        pl.BlockSpec((bm, chunk, di), lambda i, t: (i, rev - t, 0)),
        pl.BlockSpec((bm, chunk, di), lambda i, t: (i, rev - t, 0)),
        pl.BlockSpec((bm, chunk, ds), lambda i, t: (i, rev - t, 0)),
        pl.BlockSpec((bm, chunk, ds), lambda i, t: (i, rev - t, 0)),
        pl.BlockSpec((di, ds), lambda i, t: (0, 0)),
        pl.BlockSpec((bm, di, ds), lambda i, t: (i, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct(x.shape, x.dtype),
        jax.ShapeDtypeStruct(dt.shape, dt.dtype),
        jax.ShapeDtypeStruct(b.shape, b.dtype),
        jax.ShapeDtypeStruct(c.shape, c.dtype),
        jax.ShapeDtypeStruct(a.shape, a.dtype),
        jax.ShapeDtypeStruct((B, di, ds), h0_dtype),
    ]
    return pl.pallas_call(
        _bwd_kernel,
        grid=(B // bm, nt),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bm, di, ds), jnp.float32),
                        pltpu.VMEM((di, ds), jnp.float32)],
        compiler_params=compiler_params(),
        interpret=resolve_interpret(interpret),
    )(x, dt, b, c, a, h_traj, dy, dh_fin)


# ---------------------------------------------------------------------------
# custom VJP — 1 dispatch fwd, 2 dispatches per value_and_grad
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _mamba(x, dt, b, c, a, h0, chunk, block_b, bwd, interpret):
    y, h_out = _fwd_call(x, dt, b, c, a, h0, chunk, block_b, interpret,
                         traj=False)
    return y, h_out


def _mamba_fwd(x, dt, b, c, a, h0, chunk, block_b, bwd, interpret):
    if bwd == ORACLE_BWD:
        y, h_out = _fwd_call(x, dt, b, c, a, h0, chunk, block_b, interpret,
                             traj=False)
        return (y, h_out), (x, dt, b, c, a, h0, None)
    y, h_out, h_traj = _fwd_call(x, dt, b, c, a, h0, chunk, block_b,
                                 interpret, traj=True)
    return (y, h_out), (x, dt, b, c, a, h0, h_traj)


def _mamba_bwd(chunk, block_b, bwd, interpret, residuals, cots):
    x, dt, b, c, a, h0, h_traj = residuals
    dy, dh_fin = cots
    if bwd == ORACLE_BWD:
        _, oracle_vjp = jax.vjp(mamba_scan_ref, x, dt, b, c, a, h0)
        return oracle_vjp((dy, dh_fin))
    return _bwd_call(x, dt, b, c, a, h_traj, dy, dh_fin, h0.dtype, chunk,
                     block_b, interpret)


_mamba.defvjp(_mamba_fwd, _mamba_bwd)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "block_b", "bwd", "interpret"))
def mamba_scan(x: jax.Array, dt: jax.Array, b: jax.Array, c: jax.Array,
               a: jax.Array, h0: jax.Array, *, chunk: int = 16,
               block_b: int | None = None, bwd: int = FUSED_BWD,
               interpret: bool | None = None
               ) -> tuple[jax.Array, jax.Array]:
    """Fused selective scan over full sequences — ONE Pallas dispatch.

    x, dt: (B, T, di); b, c: (B, T, ds); a: (di, ds) (= -exp(a_log), f32);
    h0: (B, di, ds) f32.  Any T and B — non-dividing axes are zero-padded
    to the next chunk/block_b multiple (identity on the state: dt = 0 means
    decay 1 and zero injection) and the padded rows sliced off.  ``chunk``
    is clamped to T; ``block_b`` defaults to the whole batch (coarsest
    tile) and is clamped to B.  Returns (y (B, T, di) in x.dtype, final
    state (B, di, ds) f32).

    Differentiable: under ``jax.grad`` the forward becomes the
    trajectory-emitting kernel and the backward ONE reverse-sweep dispatch
    (``bwd=FUSED_BWD``, the default) — or the oracle VJP replay
    (``bwd=ORACLE_BWD``) when the caller's ``choose_blocks(mode="bwd")``
    found nothing viable.
    """
    B, T, di = x.shape
    chunk = max(1, min(chunk, T))
    block_b = B if block_b is None else max(1, min(block_b, B))
    from repro.obs import trace as trace_lib
    tracer = trace_lib.get_tracer()
    if tracer.enabled:
        tracer.event("plan/dispatch", family="mamba", plan="fused_scan",
                     chunk=chunk, block_b=block_b, bwd=bwd, batch=B,
                     seq_len=T)
    pad = (-T) % chunk
    padb = (-B) % block_b
    if pad or padb:
        def zpad(arr):
            return jnp.pad(arr, ((0, padb), (0, pad), (0, 0)))

        x, dt, b, c = zpad(x), zpad(dt), zpad(b), zpad(c)
        if padb:
            h0 = jnp.pad(h0, ((0, padb), (0, 0), (0, 0)))
    y, h_out = _mamba(x, dt, b, c, a, h0, chunk, block_b, bwd, interpret)
    if pad or padb:
        y = y[:B, :T]
        h_out = h_out[:B]
    return y, h_out
