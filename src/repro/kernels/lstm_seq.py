"""Sequence-resident fused stacked-LSTM Pallas kernel.

MobiRNN's §3.2-3.3 lesson is that RNN latency on a constrained accelerator
is won by coarsening work units and keeping state resident.  The per-cell
kernel (kernels/lstm_cell.py) coarsens WITHIN a timestep but still launches
one ``pallas_call`` per cell per step — T x L dispatches, with the gate
weights re-read from HBM every time.  This kernel moves the ENTIRE time loop
inside one ``pallas_call``:

* grid over batch tiles — batch rows are independent, so they tile freely;
* ``jax.lax.fori_loop`` over T inside the kernel body;
* stacked per-layer weights ``(L, P+H, 4H)`` loaded into VMEM once and
  reused across all T timesteps (P = max(input_dim, H), rows zero-padded so
  every layer shares one shape — same trick as wavefront.stack_homogeneous);
* ``(c, h)`` carried in VMEM scratch, so recurrent state never round-trips
  HBM between steps — the paper's preallocation bound realised at kernel
  level.

Dispatch count is O(1) in sequence length instead of O(T*L)
(``analysis.count_kernel_dispatches`` asserts this in tests and benchmarks).

Why the grid does NOT tile the hidden dimension: h_t feeds the gates of
step t+1 across ALL hidden columns, so a hidden tile would need the other
tiles' h before its own time loop could advance — the recurrence makes
hidden tiles non-independent.  When the ``(L, P+H, 4H)`` weight stack (plus
state and the input block) exceeds the VMEM budget, ``choose_batch_block``
returns None and callers fall back to the per-cell kernel, which DOES tile
hidden because it re-synchronises through HBM every step.  See
core/lstm.py for the four-plan decision table.

Time streaming: the recurrence is sequential in T, but the INPUT is not —
so past a modest T the kernel does not need the whole ``(T, bm, P)`` block
resident.  With ``time_chunk=tc`` the input stays in HBM
(``pl.ANY``) and the kernel streams it through two ``(tc, bm, P)`` VMEM
buffers with async copies, prefetching chunk k+1 while chunk k computes
(the classic double-buffer pipeline; pallas_guide §Double Buffering —
exactly the remedy Lee et al. and Rezk et al. prescribe for RNN state on
constrained accelerators).  The trajectory-emitting forward additionally
streams its ``(tc, L, bm, H)`` residual chunks OUT through two staging
buffers, so VMEM residency is O(tc) — not O(T) — in every training-path
dispatch while weights and the ``(c, h)`` carries stay resident across
chunks.  Every streamed array is lane-padded to a multiple of 128 first
(``_pad_axis`` to ``tiling.lanes``): Mosaic refuses a DMA window whose
last axis is narrower than the array's 128-lane tiling, which at the paper's H=32 is
every window; the kernels read and write only the first P/H lanes.
Chunking changes data movement only: the per-step math is the
shared ``_step_layers`` body, so chunked and unchunked kernels are
bit-identical (tests/test_lstm_seq.py asserts it).

Autodiff: ``pallas_call`` has no VJP rule, so ``lstm_seq`` wraps the kernel
in a ``jax.custom_vjp``.  Under differentiation the forward runs a
trajectory-emitting variant of the kernel (same math, same single dispatch)
that additionally writes the per-step ``(c, h)`` trajectory — two
``(T, L, B, H)`` f32 residuals — and the backward runs the whole
reverse-time BPTT sweep in ONE kernel dispatch (kernels/lstm_seq_bwd.py):
gates are recomputed from the stored trajectory, ``dw``/``db`` accumulate in
f32 VMEM scratch across batch tiles, and the ``(dc, dh)`` carries never
leave VMEM.  When ``choose_batch_block(mode="bwd")`` finds no batch tile
whose backward working set (~3x the forward one: trajectories + dw scratch
+ dx block ride along) fits the budget, the backward falls back to
differentiating the pure-jnp oracle (kernels/ref.lstm_seq) — numerically
identical forward math, so gradients stay exact either way
(tests/test_lstm_seq.py checks both paths against end-to-end reference
grads).
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


# ---------------------------------------------------------------------------
# Parameter stacking — one (L, P+H, 4H) weight block the kernel loads once.
# ---------------------------------------------------------------------------
def stack_params(layers: list[dict], hidden: int
                 ) -> tuple[jax.Array, jax.Array, int]:
    """Stack per-layer cell params to (L, P+H, 4H) / (L, 4H).

    ``layers`` are PLAIN (un-annotated) per-layer dicts with "w" of shape
    (in_dim_i + H, 4H).  Rows are rearranged to [input rows | h rows] with
    the input rows zero-padded to P = max(max_i in_dim_i, H), so one VMEM
    block serves every layer; callers zero-pad the raw input to width P
    (pad_input).  Padding rows multiply padded zeros — exactly equivalent.
    Returns (w_stack, b_stack, P).
    """
    in_dims = [layer["w"].shape[0] - hidden for layer in layers]
    p_width = max(max(in_dims), hidden)
    ws, bs = [], []
    for layer, in_dim in zip(layers, in_dims):
        w = layer["w"]
        if in_dim < p_width:
            pad = jnp.zeros((p_width - in_dim, 4 * hidden), w.dtype)
            w = jnp.concatenate([w[:in_dim], pad, w[in_dim:]], axis=0)
        ws.append(w)
        bs.append(layer["b"])
    return jnp.stack(ws), jnp.stack(bs), p_width


def pad_input(x: jax.Array, p_width: int) -> jax.Array:
    """Zero-pad x: (B, T, D) to (B, T, P) to match the stacked weight rows."""
    d = x.shape[-1]
    if d == p_width:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, p_width - d)))


# ---------------------------------------------------------------------------
# VMEM budget — the MobiRNN packing rule applied to the whole sequence.
# ---------------------------------------------------------------------------
class SeqBlocks(NamedTuple):
    """The fused kernel's tiling decision: batch tile x time residency.

    ``time_chunk=None`` means the whole (T, bm, P) input block (and, in bwd,
    the whole trajectories) stay VMEM-resident for the grid step — the
    fastest layout when it fits.  An integer ``time_chunk=tc`` means the
    kernel streams the time axis through double-buffered (tc, bm, P) VMEM
    buffers instead, making residency O(tc) in sequence length.

    Presents the family-generic ``core/tiling.TilePlan`` interface:
    ``batch_tile`` is this family's ``block_b``; ``time_chunk`` is already
    the shared spelling."""
    block_b: int
    time_chunk: int | None = None

    @property
    def batch_tile(self) -> int:
        return self.block_b


def working_set_bytes(seq_len: int, n_layers: int, p_width: int, hidden: int,
                      block_b: int, dtype_bytes: int = 4,
                      w_dtype_bytes: int | None = None,
                      mode: str = "fwd",
                      time_chunk: int | None = None,
                      quantized: bool = False) -> int:
    """Kernel working set for one grid step, per phase.

    ``mode="fwd"`` sizes the inference forward: stacked weights + the batch
    tile's input residency + f32 (c,h) scratch + output blocks.

    ``mode="bwd"`` sizes the TRAINING working set — the reverse-sweep kernel
    (kernels/lstm_seq_bwd.py), which strictly dominates the
    trajectory-emitting forward that feeds it, so one number gates both
    dispatches.  On top of the forward set it holds the (T, L, bm, H) f32
    trajectory residuals (or their double-buffered chunk windows), the f32
    dw/db accumulator scratch (a second weight-stack-sized block), the
    dw/db output blocks, the dx residency (mirroring the input) and the
    (dc, dh) carry scratch — roughly 3x the forward working set at the
    paper's shapes.

    ``time_chunk=None`` sizes the whole-T-resident layout: the input block
    is (T, bm, P) and the bwd trajectories are fully resident — O(T) VMEM.
    ``time_chunk=tc`` sizes the STREAMED layout: two (tc, bm, P) input
    buffers (prefetch + compute), and in bwd two (tc+1)-row windows per
    trajectory plus a mirrored two-slot dx staging — O(tc) VMEM; weights,
    carries, and dw/db accumulators stay resident across chunks either way.

    ``dtype_bytes`` sizes activations/outputs; ``w_dtype_bytes`` sizes the
    weight stack (defaults to ``dtype_bytes`` — pass it explicitly under
    mixed precision, e.g. bf16 activations over f32 parameters).

    Every term is counted at its minor axis padded to whole lanes
    (``tiling.lanes``), as VMEM holds it: at the paper's H=32 the (c, h)
    blocks, trajectories and x/dx buffers take four times their logical
    width.

    ``quantized=True`` sizes the int8-weight plan (``fused_seq_q8``): the
    weight stack is 1 byte/weight (unless ``w_dtype_bytes`` overrides), the
    f32 per-channel scales ride along with the f32 biases, PLUS one
    f32 (P+H, 4H) slab for the active layer's on-the-fly dequantized block
    (``_step_layers``/``_unwind_step`` cast ``w_ref[layer]`` to f32 before
    the matmuls — a live weight-layer-sized temporary the int8 residency
    saving must pay for), and in ``bwd`` the dw/db OUTPUTS are f32
    (straight-through gradients land on the f32 master weights, never on
    the int8 stack) — the f32 dw/db accumulator scratch is unchanged
    either way."""
    ws = tiling.WorkingSet(mode)
    wb = tiling.weight_dtype_bytes(dtype_bytes, w_dtype_bytes, quantized)
    pw, hw, gw = (tiling.lanes(p_width), tiling.lanes(hidden),
                  tiling.lanes(4 * hidden))
    w_count = n_layers * (p_width + hidden) * gw
    b_count = n_layers * gw
    weights = w_count * wb
    if quantized:
        biases = b_count * 4 * 2        # f32 bias + f32 per-channel scales
        weights += (p_width + hidden) * gw * 4       # dequant temporary
    else:
        biases = b_count * wb
    ws.add("weights", weights).add("biases", biases)
    x_rows = tiling.streamed_rows(seq_len, time_chunk)
    x_block = block_b * x_rows * pw * dtype_bytes
    ws.add("x_block", x_block)
    ws.add("state", 2 * n_layers * block_b * hw * 4)         # f32 scratch
    ws.add("outs", 2 * n_layers * block_b * hw * dtype_bytes)
    if time_chunk is None:
        traj_rows = seq_len                                  # resident
    else:                                         # 2 slots x (tc+1)-row win
        traj_rows = tiling.STREAM_SLOTS * tiling.bwd_window_rows(
            seq_len, time_chunk)
    ws.add("traj", 2 * traj_rows * n_layers * block_b * hw * 4,
           bwd_only=True)
    ws.add("dw_scratch", (w_count + b_count) * 4, bwd_only=True)  # f32 accum
    if quantized:
        dw_out = (w_count + b_count) * 4         # f32 master-weight grads
    else:
        dw_out = weights + biases                              # param dtype
    ws.add("dw_out", dw_out, bwd_only=True)
    ws.add("dx_block", x_block, bwd_only=True)   # dx mirrors x residency
    # (dc, dh) carries reuse `state`; the final-state cotangent blocks:
    ws.add("cots", 2 * n_layers * block_b * hw * dtype_bytes,
           bwd_only=True)
    return ws.total()


def choose_batch_block(batch: int, seq_len: int, n_layers: int,
                       p_width: int, hidden: int, dtype_bytes: int = 4,
                       vmem_budget: int | None = None,
                       w_dtype_bytes: int | None = None,
                       mode: str = "fwd",
                       allow_chunk: bool = True,
                       quantized: bool = False) -> SeqBlocks | None:
    """Pick the (batch tile, time residency), or None when not viable.

    Seeds the batch tile from factorization.choose_block on the per-step
    gate matmul (B, P+H) x (P+H, 4H) — the coarsest MXU-aligned block — then
    searches the joint ``(block_b, time_chunk)`` surface via the shared
    ``core/tiling.joint_search`` in MobiRNN coarseness order:

    1. whole-T residency at the current batch tile (``time_chunk=None`` —
       no streaming machinery at all) when it fits;
    2. otherwise STREAM the time axis: a halving sweep from ``tc = T//2``
       down to 1 takes the first (coarsest) chunk whose double-buffered
       working set fits — this keeps the batch tile coarse (full MXU rows,
       one grid step) and hides the input DMA behind compute instead of
       multiplying grid steps;
    3. only when even ``tc=1`` does not fit, halve the batch tile and
       retry — shrinking bm shrinks the weight-independent terms too.

    ``mode="bwd"`` sizes the TRAINING working set instead (trajectory
    residuals + gradient accumulators, see ``working_set_bytes``) — under
    ``jax.grad`` this is the number that matters, and it is ~3x the forward
    one, so a tiling that is fine for inference can be non-viable for
    training.  Returns None only when even ``(bm=1, tc=1)`` cannot fit —
    i.e. the weight stack plus its gradient accumulators themselves blow
    VMEM (large H/L); long T alone is no longer a reason to fall back.
    Callers then route to the per-cell kernel (fwd) or the oracle VJP
    (bwd).  ``allow_chunk=False`` restores the pre-streaming decision
    surface (whole-T residency or bust) — used by benchmarks to show the
    cliff the pipeline removes.  ``quantized=True`` sizes the int8-weight
    plan (1 byte/weight + f32 scales, f32 dw/db outs in bwd — see
    ``working_set_bytes``): with the dominant weight term quartered, the
    same coarseness search admits whole-T residency deeper into T and
    coarser tiles at budgets where f32 weights force streaming or fail.
    """
    budget = factorization.DEFAULT_VMEM_BUDGET if vmem_budget is None \
        else vmem_budget

    def fits(bm: int, tc: int | None) -> bool:
        return working_set_bytes(seq_len, n_layers, p_width, hidden, bm,
                                 dtype_bytes, w_dtype_bytes, mode=mode,
                                 time_chunk=tc, quantized=quantized) <= budget

    seed, _, _ = factorization.choose_block(
        batch, 4 * hidden, p_width + hidden, bytes_per_elem=dtype_bytes,
        vmem_budget=budget)
    found = tiling.joint_search(batch, seq_len, fits, seed_batch_tile=seed,
                                allow_chunk=allow_chunk)
    return None if found is None else SeqBlocks(*found)


# ---------------------------------------------------------------------------
# Kernel
# ---------------------------------------------------------------------------
def _step_layers(inp, w_ref, b_ref, c_scr, h_scr, *, n_layers: int,
                 p_width: int, s_ref=None) -> None:
    """Advance all L layers one timestep, updating (c, h) scratch in place.

    ``inp``: (bm, P) f32 — this step's (padded) input.  Shared by the plain,
    trajectory-emitting, and backward-recompute kernel bodies so the three
    dispatches stay bit-identical in their forward math.

    ``s_ref`` (optional): (L, 4H) f32 per-output-channel scales — the int8
    path (``fused_seq_q8``).  The weights then live in VMEM as int8 and are
    dequantized ON THE FLY: cast to f32 for the gate matmuls and the
    per-channel scale folded into the pre-activations afterwards
    ((x @ wq) * s == x @ (wq * s) — exact in reals, an fp-rounding error
    band vs the dequantize oracle).  The dequantized block is a per-layer
    f32 temporary — one (P+H, 4H) slab at a time, which
    ``working_set_bytes(quantized=True)`` counts on top of the resident
    1-byte stack.
    """
    for layer in range(n_layers):                        # static unroll
        w = w_ref[layer]                                 # (P+H, 4H)
        if s_ref is not None:
            w = w.astype(F32)                            # int8 -> f32
        # one coarse MXU work unit per layer: all four gates at once,
        # split as x-part + h-part to skip an in-loop concatenate
        gates = (
            jax.lax.dot_general(inp, w[:p_width],
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=F32)
            + jax.lax.dot_general(h_scr[layer], w[p_width:],
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=F32))
        if s_ref is not None:
            gates = gates * s_ref[layer].astype(F32)     # fold channel scale
        gates = gates + b_ref[layer].astype(F32)
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        c_new = (jax.nn.sigmoid(f) * c_scr[layer]
                 + jax.nn.sigmoid(i) * jnp.tanh(g))
        h_new = jax.nn.sigmoid(o) * jnp.tanh(c_new)
        c_scr[layer] = c_new
        h_scr[layer] = h_new
        hidden = h_new.shape[-1]
        inp = h_new if p_width == hidden else \
            jnp.pad(h_new, ((0, 0), (0, p_width - hidden)))


def _seq_kernel(x_ref, w_ref, b_ref, c_out_ref, h_out_ref, c_scr, h_scr,
                *, n_layers: int, seq_len: int, p_width: int, s_ref=None):
    """One batch tile runs the whole (T x L) recurrence from VMEM.

    x_ref: (T, bm, P) time-major input tile; w_ref: (L, P+H, 4H);
    b_ref: (L, 4H); c_scr/h_scr: (L, bm, H) f32 VMEM scratch that IS the
    paper's preallocated state — written every step, never leaving VMEM.
    ``s_ref``: (L, 4H) f32 per-channel scales when w_ref is int8 (q8 plan).
    """
    c_scr[...] = jnp.zeros_like(c_scr)
    h_scr[...] = jnp.zeros_like(h_scr)

    def step(t, carry):
        inp = x_ref[pl.ds(t, 1)][0].astype(F32)          # (bm, P)
        _step_layers(inp, w_ref, b_ref, c_scr, h_scr, n_layers=n_layers,
                     p_width=p_width, s_ref=s_ref)
        return carry

    jax.lax.fori_loop(0, seq_len, step, 0)
    c_out_ref[...] = c_scr[...].astype(c_out_ref.dtype)
    h_out_ref[...] = h_scr[...].astype(h_out_ref.dtype)


def _seq_q8_kernel(x_ref, w_ref, s_ref, b_ref, c_out_ref, h_out_ref, c_scr,
                   h_scr, *, n_layers: int, seq_len: int, p_width: int):
    """Int8-weight forward: the same body with the (L, 4H) f32 scales as an
    extra input ref and the weight stack VMEM-resident as int8 (4x smaller
    than the f32 plan's dominant term)."""
    _seq_kernel(x_ref, w_ref, b_ref, c_out_ref, h_out_ref, c_scr, h_scr,
                n_layers=n_layers, seq_len=seq_len, p_width=p_width,
                s_ref=s_ref)


def _seq_traj_kernel(x_ref, w_ref, b_ref, c_out_ref, h_out_ref, ct_ref,
                     ht_ref, c_scr, h_scr, *, n_layers: int, seq_len: int,
                     p_width: int, s_ref=None):
    """Forward with residuals: same recurrence, but every step also writes
    the post-step (c, h) into the (T, L, bm, H) f32 trajectory outputs —
    the residual contract the reverse-sweep kernel (lstm_seq_bwd) consumes.
    Still ONE dispatch; the trajectory rows stream out of the same loop.
    """
    c_scr[...] = jnp.zeros_like(c_scr)
    h_scr[...] = jnp.zeros_like(h_scr)

    def step(t, carry):
        inp = x_ref[pl.ds(t, 1)][0].astype(F32)          # (bm, P)
        _step_layers(inp, w_ref, b_ref, c_scr, h_scr, n_layers=n_layers,
                     p_width=p_width, s_ref=s_ref)
        ct_ref[pl.ds(t, 1)] = c_scr[...][None]
        ht_ref[pl.ds(t, 1)] = h_scr[...][None]
        return carry

    jax.lax.fori_loop(0, seq_len, step, 0)
    c_out_ref[...] = c_scr[...].astype(c_out_ref.dtype)
    h_out_ref[...] = h_scr[...].astype(h_out_ref.dtype)


def _seq_traj_q8_kernel(x_ref, w_ref, s_ref, b_ref, c_out_ref, h_out_ref,
                        ct_ref, ht_ref, c_scr, h_scr, *, n_layers: int,
                        seq_len: int, p_width: int):
    """Int8-weight trajectory-emitting forward (q8 training-path fwd)."""
    _seq_traj_kernel(x_ref, w_ref, b_ref, c_out_ref, h_out_ref, ct_ref,
                     ht_ref, c_scr, h_scr, n_layers=n_layers,
                     seq_len=seq_len, p_width=p_width, s_ref=s_ref)


# ---------------------------------------------------------------------------
# Time-chunked, double-buffered kernel bodies: x stays in HBM (pl.ANY)
# and streams through two (tc, bm, P) VMEM buffers; trajectory residuals
# stream OUT through two staging buffers.  Helpers shared by fwd + traj.
# ---------------------------------------------------------------------------
def _x_chunk_dma(x_hbm, xbuf, xsem, slot, k, *, tc: int, seq_len: int,
                 bm: int, ib):
    """Async copy of input chunk k into buffer ``slot``.

    The copy window is static-size ``tc`` rows with a CLAMPED start
    (min(k*tc, T-tc)) so the tail chunk of a non-dividing T stays in
    bounds; steps index the buffer at ``t - start``, and rows below the
    chunk (duplicates of already-consumed steps) are simply never read.
    ``ib`` is the batch-tile id, captured ONCE at kernel top — calling
    ``pl.program_id`` inside a ``pl.when`` branch does not lower.
    """
    src = jnp.minimum(k * tc, seq_len - tc)
    return pltpu.make_async_copy(
        x_hbm.at[pl.ds(src, tc), pl.ds(ib * bm, bm)],
        xbuf.at[slot], xsem.at[slot])


def _seq_chunked_kernel(x_hbm, w_ref, b_ref, c_out_ref, h_out_ref,
                        xbuf, c_scr, h_scr, xsem,
                        *, n_layers: int, seq_len: int, p_width: int,
                        tc: int, nc: int, s_ref=None):
    """Forward with O(tc) input residency: same recurrence as ``_seq_kernel``
    but the (T, bm, P) block never materialises — chunk k+1 prefetches while
    chunk k computes.  x_hbm: (T, Bp, Pl) in HBM (batch padded to the tile
    grid, lanes to ``tiling.LANES``); xbuf: (2, tc, bm, Pl) VMEM; weights
    and (c, h) stay resident.
    """
    bm = c_scr.shape[1]
    ib = pl.program_id(0)
    c_scr[...] = jnp.zeros_like(c_scr)
    h_scr[...] = jnp.zeros_like(h_scr)

    def dma(slot, k):
        return _x_chunk_dma(x_hbm, xbuf, xsem, slot, k, tc=tc,
                            seq_len=seq_len, bm=bm, ib=ib)

    dma(0, 0).start()                                    # warm-up

    def chunk(k, carry):
        slot = jax.lax.rem(k, 2)

        @pl.when(k + 1 < nc)
        def _prefetch():
            dma(jax.lax.rem(k + 1, 2), k + 1).start()

        dma(slot, k).wait()
        src = jnp.minimum(k * tc, seq_len - tc)

        def step(i, c2):
            t = k * tc + i

            @pl.when(t < seq_len)                        # tail-chunk guard
            def _advance():
                inp = xbuf[slot, t - src][:, :p_width].astype(F32)
                _step_layers(inp, w_ref, b_ref, c_scr, h_scr,
                             n_layers=n_layers, p_width=p_width,
                             s_ref=s_ref)
            return c2

        jax.lax.fori_loop(0, tc, step, 0)
        return carry

    jax.lax.fori_loop(0, nc, chunk, 0)
    c_out_ref[...] = c_scr[...].astype(c_out_ref.dtype)
    h_out_ref[...] = h_scr[...].astype(h_out_ref.dtype)


def _seq_chunked_q8_kernel(x_hbm, w_ref, s_ref, b_ref, c_out_ref, h_out_ref,
                           xbuf, c_scr, h_scr, xsem,
                           *, n_layers: int, seq_len: int, p_width: int,
                           tc: int, nc: int):
    """Int8-weight streamed forward (scales ride with the resident stack)."""
    _seq_chunked_kernel(x_hbm, w_ref, b_ref, c_out_ref, h_out_ref,
                        xbuf, c_scr, h_scr, xsem, n_layers=n_layers,
                        seq_len=seq_len, p_width=p_width, tc=tc, nc=nc,
                        s_ref=s_ref)


def _seq_traj_chunked_kernel(x_hbm, w_ref, b_ref, c_out_ref, h_out_ref,
                             ct_hbm, ht_hbm,
                             xbuf, ctb, htb, c_scr, h_scr,
                             xsem, csem, hsem,
                             *, n_layers: int, seq_len: int, p_width: int,
                             tc: int, nc: int, s_ref=None):
    """Trajectory-emitting forward with O(tc) residency on BOTH sides: input
    chunks stream in, (tc, L, bm, H) trajectory chunks stream out through
    two staging buffers each.  ct_hbm/ht_hbm are (nc*tc, L, Bp, Hl) in HBM —
    time-padded so every chunk's output window is disjoint, lane-padded to
    ``tiling.LANES`` (the wrapper slices [:T, :, :B, :H]); a staging slot
    is reused only after its previous flight completes (the k-2 wait
    below).
    """
    bm = c_scr.shape[1]
    ib = pl.program_id(0)
    c_scr[...] = jnp.zeros_like(c_scr)
    h_scr[...] = jnp.zeros_like(h_scr)

    def dma_in(slot, k):
        return _x_chunk_dma(x_hbm, xbuf, xsem, slot, k, tc=tc,
                            seq_len=seq_len, bm=bm, ib=ib)

    def dma_out(buf, hbm, sem, slot, k):
        return pltpu.make_async_copy(
            buf.at[slot],
            hbm.at[pl.ds(k * tc, tc), :, pl.ds(ib * bm, bm)],
            sem.at[slot])

    dma_in(0, 0).start()                                 # warm-up

    def chunk(k, carry):
        slot = jax.lax.rem(k, 2)

        @pl.when(k + 1 < nc)
        def _prefetch():
            dma_in(jax.lax.rem(k + 1, 2), k + 1).start()

        dma_in(slot, k).wait()
        # the staging slot's previous flight (chunk k-2) must land before
        # this chunk overwrites it
        @pl.when(k >= 2)
        def _reclaim():
            dma_out(ctb, ct_hbm, csem, slot, k - 2).wait()
            dma_out(htb, ht_hbm, hsem, slot, k - 2).wait()

        src = jnp.minimum(k * tc, seq_len - tc)

        def step(i, c2):
            t = k * tc + i

            @pl.when(t < seq_len)                        # tail-chunk guard
            def _advance():
                inp = xbuf[slot, t - src][:, :p_width].astype(F32)
                _step_layers(inp, w_ref, b_ref, c_scr, h_scr,
                             n_layers=n_layers, p_width=p_width,
                             s_ref=s_ref)
                hidden = c_scr.shape[-1]
                ctb[slot, i, :, :, :hidden] = c_scr[...]
                htb[slot, i, :, :, :hidden] = h_scr[...]
            return c2

        jax.lax.fori_loop(0, tc, step, 0)
        dma_out(ctb, ct_hbm, csem, slot, k).start()
        dma_out(htb, ht_hbm, hsem, slot, k).start()
        return carry

    jax.lax.fori_loop(0, nc, chunk, 0)
    # drain the (at most two) outstanding trajectory flights
    dma_out(ctb, ct_hbm, csem, jax.lax.rem(nc - 1, 2), nc - 1).wait()
    dma_out(htb, ht_hbm, hsem, jax.lax.rem(nc - 1, 2), nc - 1).wait()

    @pl.when(nc >= 2)
    def _drain_prev():
        dma_out(ctb, ct_hbm, csem, jax.lax.rem(nc - 2, 2), nc - 2).wait()
        dma_out(htb, ht_hbm, hsem, jax.lax.rem(nc - 2, 2), nc - 2).wait()

    c_out_ref[...] = c_scr[...].astype(c_out_ref.dtype)
    h_out_ref[...] = h_scr[...].astype(h_out_ref.dtype)


def _seq_traj_chunked_q8_kernel(x_hbm, w_ref, s_ref, b_ref, c_out_ref,
                                h_out_ref, ct_hbm, ht_hbm,
                                xbuf, ctb, htb, c_scr, h_scr,
                                xsem, csem, hsem,
                                *, n_layers: int, seq_len: int, p_width: int,
                                tc: int, nc: int):
    """Int8-weight streamed trajectory-emitting forward."""
    _seq_traj_chunked_kernel(x_hbm, w_ref, b_ref, c_out_ref, h_out_ref,
                             ct_hbm, ht_hbm, xbuf, ctb, htb, c_scr, h_scr,
                             xsem, csem, hsem, n_layers=n_layers,
                             seq_len=seq_len, p_width=p_width, tc=tc, nc=nc,
                             s_ref=s_ref)


def _pad_axis(a: jax.Array, axis: int, padded: int) -> jax.Array:
    """Zero-pad ``axis`` of ``a`` to length ``padded``.  Manual-DMA kernels
    address batch tiles themselves, so the tile grid must divide exactly
    (garbage rows are masked/sliced, never computed into shared state), and
    their DMA windows must span whole 128-lane tiles (lanes pad to
    ``tiling.lanes``)."""
    if a.shape[axis] == padded:
        return a
    pads = [(0, 0)] * a.ndim
    pads[axis] = (0, padded - a.shape[axis])
    return jnp.pad(a, pads)


@functools.partial(jax.jit,
                   static_argnames=("block_b", "time_chunk", "interpret"))
def _lstm_seq_call(w: jax.Array, b: jax.Array, x: jax.Array,
                   block_b: int, time_chunk: int | None,
                   interpret: bool | None,
                   scales: jax.Array | None = None
                   ) -> tuple[jax.Array, jax.Array]:
    L, H = w.shape[0], w.shape[-1] // 4
    P = w.shape[1] - H
    B, T, _ = x.shape
    bm = min(block_b, B)
    xt = jnp.swapaxes(x, 0, 1)                           # (T, B, P)
    if time_chunk is not None:
        return _lstm_seq_chunked_call(w, b, xt, bm, min(time_chunk, T),
                                      interpret, scales=scales)
    out = jax.ShapeDtypeStruct((L, B, H), x.dtype)
    if scales is None:
        kernel = functools.partial(_seq_kernel, n_layers=L, seq_len=T,
                                   p_width=P)
        s_in, s_spec = (), ()
    else:
        kernel = functools.partial(_seq_q8_kernel, n_layers=L, seq_len=T,
                                   p_width=P)
        s_in = (scales,)
        s_spec = (pl.BlockSpec((L, 4 * H), lambda ib: (0, 0)),)
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(B, bm),),
        in_specs=[
            pl.BlockSpec((T, bm, P), lambda ib: (0, ib, 0)),
            pl.BlockSpec((L, P + H, 4 * H), lambda ib: (0, 0, 0)),
            *s_spec,
            pl.BlockSpec((L, 4 * H), lambda ib: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((L, bm, H), lambda ib: (0, ib, 0)),
            pl.BlockSpec((L, bm, H), lambda ib: (0, ib, 0)),
        ],
        out_shape=[out, out],
        scratch_shapes=[
            pltpu.VMEM((L, bm, H), F32),
            pltpu.VMEM((L, bm, H), F32),
        ],
        compiler_params=compiler_params(),
        interpret=resolve_interpret(interpret),
    )(xt, w, *s_in, b)


def _lstm_seq_chunked_call(w, b, xt, bm: int, tc: int,
                           interpret: bool | None,
                           scales=None) -> tuple[jax.Array, jax.Array]:
    """Streamed forward: x lives in HBM, VMEM holds O(tc) of it."""
    L, H = w.shape[0], w.shape[-1] // 4
    P = w.shape[1] - H
    T, B, _ = xt.shape
    n_tiles = pl.cdiv(B, bm)
    Bp = n_tiles * bm
    nc = pl.cdiv(T, tc)
    Pl = tiling.lanes(P)
    xt = _pad_axis(_pad_axis(xt, 1, Bp), 2, Pl)
    out = jax.ShapeDtypeStruct((L, Bp, H), xt.dtype)
    if scales is None:
        kernel = functools.partial(_seq_chunked_kernel, n_layers=L,
                                   seq_len=T, p_width=P, tc=tc, nc=nc)
        s_in, s_spec = (), ()
    else:
        kernel = functools.partial(_seq_chunked_q8_kernel, n_layers=L,
                                   seq_len=T, p_width=P, tc=tc, nc=nc)
        s_in = (scales,)
        s_spec = (pl.BlockSpec((L, 4 * H), lambda ib: (0, 0)),)
    c, h = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),        # x streams manually
            pl.BlockSpec((L, P + H, 4 * H), lambda ib: (0, 0, 0)),
            *s_spec,
            pl.BlockSpec((L, 4 * H), lambda ib: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((L, bm, H), lambda ib: (0, ib, 0)),
            pl.BlockSpec((L, bm, H), lambda ib: (0, ib, 0)),
        ],
        out_shape=[out, out],
        scratch_shapes=[
            pltpu.VMEM((2, tc, bm, Pl), xt.dtype),       # double buffer
            pltpu.VMEM((L, bm, H), F32),
            pltpu.VMEM((L, bm, H), F32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=compiler_params(),
        interpret=resolve_interpret(interpret),
    )(xt, w, *s_in, b)
    return c[:, :B], h[:, :B]


@functools.partial(jax.jit,
                   static_argnames=("block_b", "time_chunk", "interpret"))
def _lstm_seq_traj_call(w: jax.Array, b: jax.Array, x: jax.Array,
                        block_b: int, interpret: bool | None,
                        time_chunk: int | None = None,
                        scales: jax.Array | None = None
                        ) -> tuple[jax.Array, jax.Array, jax.Array,
                                   jax.Array]:
    """Trajectory-emitting forward: (c, h, c_traj, h_traj), still ONE
    dispatch.  Trajectories are (T, L, B, H) f32 — the residual contract,
    identical (bit-for-bit) whether the kernel holds T resident
    (``time_chunk=None``) or streams it in chunks."""
    L, H = w.shape[0], w.shape[-1] // 4
    P = w.shape[1] - H
    B, T, _ = x.shape
    bm = min(block_b, B)
    xt = jnp.swapaxes(x, 0, 1)                           # (T, B, P)
    if time_chunk is not None:
        return _lstm_seq_traj_chunked_call(w, b, xt, bm, min(time_chunk, T),
                                           interpret, scales=scales)
    out = jax.ShapeDtypeStruct((L, B, H), x.dtype)
    traj = jax.ShapeDtypeStruct((T, L, B, H), F32)
    if scales is None:
        kernel = functools.partial(_seq_traj_kernel, n_layers=L, seq_len=T,
                                   p_width=P)
        s_in, s_spec = (), ()
    else:
        kernel = functools.partial(_seq_traj_q8_kernel, n_layers=L,
                                   seq_len=T, p_width=P)
        s_in = (scales,)
        s_spec = (pl.BlockSpec((L, 4 * H), lambda ib: (0, 0)),)
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(B, bm),),
        in_specs=[
            pl.BlockSpec((T, bm, P), lambda ib: (0, ib, 0)),
            pl.BlockSpec((L, P + H, 4 * H), lambda ib: (0, 0, 0)),
            *s_spec,
            pl.BlockSpec((L, 4 * H), lambda ib: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((L, bm, H), lambda ib: (0, ib, 0)),
            pl.BlockSpec((L, bm, H), lambda ib: (0, ib, 0)),
            pl.BlockSpec((T, L, bm, H), lambda ib: (0, 0, ib, 0)),
            pl.BlockSpec((T, L, bm, H), lambda ib: (0, 0, ib, 0)),
        ],
        out_shape=[out, out, traj, traj],
        scratch_shapes=[
            pltpu.VMEM((L, bm, H), F32),
            pltpu.VMEM((L, bm, H), F32),
        ],
        compiler_params=compiler_params(),
        interpret=resolve_interpret(interpret),
    )(xt, w, *s_in, b)


def _lstm_seq_traj_chunked_call(w, b, xt, bm: int, tc: int,
                                interpret: bool | None,
                                scales=None
                                ) -> tuple[jax.Array, jax.Array, jax.Array,
                                           jax.Array]:
    """Streamed trajectory forward: O(tc) VMEM for input AND residuals."""
    L, H = w.shape[0], w.shape[-1] // 4
    P = w.shape[1] - H
    T, B, _ = xt.shape
    n_tiles = pl.cdiv(B, bm)
    Bp = n_tiles * bm
    nc = pl.cdiv(T, tc)
    Tp = nc * tc              # time-padded so chunk windows are disjoint
    Pl, Hl = tiling.lanes(P), tiling.lanes(H)
    xt = _pad_axis(_pad_axis(xt, 1, Bp), 2, Pl)
    out = jax.ShapeDtypeStruct((L, Bp, H), xt.dtype)
    traj = jax.ShapeDtypeStruct((Tp, L, Bp, Hl), F32)
    if scales is None:
        kernel = functools.partial(_seq_traj_chunked_kernel, n_layers=L,
                                   seq_len=T, p_width=P, tc=tc, nc=nc)
        s_in, s_spec = (), ()
    else:
        kernel = functools.partial(_seq_traj_chunked_q8_kernel, n_layers=L,
                                   seq_len=T, p_width=P, tc=tc, nc=nc)
        s_in = (scales,)
        s_spec = (pl.BlockSpec((L, 4 * H), lambda ib: (0, 0)),)
    c, h, ct, ht = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),        # x streams manually
            pl.BlockSpec((L, P + H, 4 * H), lambda ib: (0, 0, 0)),
            *s_spec,
            pl.BlockSpec((L, 4 * H), lambda ib: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((L, bm, H), lambda ib: (0, ib, 0)),
            pl.BlockSpec((L, bm, H), lambda ib: (0, ib, 0)),
            pl.BlockSpec(memory_space=pl.ANY),        # traj streams out
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_shape=[out, out, traj, traj],
        scratch_shapes=[
            pltpu.VMEM((2, tc, bm, Pl), xt.dtype),       # x double buffer
            pltpu.VMEM((2, tc, L, bm, Hl), F32),         # c_traj staging
            pltpu.VMEM((2, tc, L, bm, Hl), F32),         # h_traj staging
            pltpu.VMEM((L, bm, H), F32),
            pltpu.VMEM((L, bm, H), F32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=compiler_params(),
        interpret=resolve_interpret(interpret),
    )(xt, w, *s_in, b)
    return c[:, :B], h[:, :B], ct[:T, :, :B, :H], ht[:T, :, :B, :H]


# ---------------------------------------------------------------------------
# Differentiable entry point
# ---------------------------------------------------------------------------
#: bwd spec sentinel: "no viable backward tiling — use the oracle VJP".
ORACLE_BWD = 0


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _lstm_seq(w, b, x, fwd_spec, bwd_spec, interpret):
    return _lstm_seq_call(w, b, x, fwd_spec[0], fwd_spec[1], interpret)


def _lstm_seq_fwd(w, b, x, fwd_spec, bwd_spec, interpret):
    if bwd_spec == ORACLE_BWD:
        # backward working set does not fit VMEM: plain forward, oracle VJP
        return (_lstm_seq_call(w, b, x, fwd_spec[0], fwd_spec[1], interpret),
                (w, b, x))
    c, h, ct, ht = _lstm_seq_traj_call(w, b, x, bwd_spec[0], interpret,
                                       time_chunk=bwd_spec[1])
    return (c, h), (w, b, x, ct, ht)


def _lstm_seq_bwd(fwd_spec, bwd_spec, interpret, residuals, cotangents):
    if bwd_spec == ORACLE_BWD:
        from repro.kernels import ref
        w, b, x = residuals
        _, vjp = jax.vjp(ref.lstm_seq, w, b, x)
        return vjp(cotangents)
    from repro.kernels import lstm_seq_bwd as bwd_lib
    w, b, x, ct, ht = residuals
    dc, dh = cotangents
    return bwd_lib.lstm_seq_bwd(w, b, x, ct, ht, dc, dh,
                                block_b=bwd_spec[0], time_chunk=bwd_spec[1],
                                interpret=interpret)


_lstm_seq.defvjp(_lstm_seq_fwd, _lstm_seq_bwd)


# ---------------------------------------------------------------------------
# Int8-weight differentiable entry point (the `fused_seq_q8` plan).
#
# The primal takes the f32 MASTER weight stack; quantization (per-output-
# channel symmetric int8, kernels/ref.quantize_q8) happens inside the traced
# function with plain jnp ops — no extra kernel dispatch — so `value_and_grad`
# stays at exactly 2 pallas_calls (trajectory-emitting q8 forward + q8
# reverse sweep).  Gradients are STRAIGHT-THROUGH: the backward differentiates
# the forward the kernel actually ran (dequantized int8 weights) and hands dw
# to the master stack unchanged (d wdq / d w = identity), with the f32 dw/db
# accumulators of the sweep untouched by the weight dtype.
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _lstm_seq_q8(w, b, x, fwd_spec, bwd_spec, interpret):
    from repro.kernels import ref
    wq, s = ref.quantize_q8(w)
    return _lstm_seq_call(wq, b, x, fwd_spec[0], fwd_spec[1], interpret,
                          scales=s)


def _lstm_seq_q8_fwd(w, b, x, fwd_spec, bwd_spec, interpret):
    from repro.kernels import ref
    wq, s = ref.quantize_q8(w)
    if bwd_spec == ORACLE_BWD:
        # backward working set does not fit VMEM: plain q8 forward, oracle
        # VJP over the dequantized weights (straight-through to the master)
        out = _lstm_seq_call(wq, b, x, fwd_spec[0], fwd_spec[1], interpret,
                             scales=s)
        return out, (wq, s, b, x)
    c, h, ct, ht = _lstm_seq_traj_call(wq, b, x, bwd_spec[0], interpret,
                                       time_chunk=bwd_spec[1], scales=s)
    return (c, h), (wq, s, b, x, ct, ht)


def _lstm_seq_q8_bwd(fwd_spec, bwd_spec, interpret, residuals, cotangents):
    from repro.kernels import ref
    if bwd_spec == ORACLE_BWD:
        wq, s, b, x = residuals
        _, vjp = jax.vjp(ref.lstm_seq, ref.dequantize_q8(wq, s), b, x)
        return vjp(cotangents)          # dw wrt dequantized weights (STE)
    from repro.kernels import lstm_seq_bwd as bwd_lib
    wq, s, b, x, ct, ht = residuals
    dc, dh = cotangents
    return bwd_lib.lstm_seq_bwd(wq, b, x, ct, ht, dc, dh,
                                block_b=bwd_spec[0], time_chunk=bwd_spec[1],
                                interpret=interpret, scales=s)


_lstm_seq_q8.defvjp(_lstm_seq_q8_fwd, _lstm_seq_q8_bwd)


def _resolve_specs(B: int, T: int, L: int, P: int, H: int, *,
                   dtype_bytes: int, w_dtype_bytes: int | None,
                   quantized: bool, block_b: int | None,
                   time_chunk: int | None, bwd_block_b: int | None,
                   bwd_time_chunk: int | None):
    """Shared ``(fwd_spec, bwd_spec)`` resolution for the f32 and q8 entry
    points: explicit tiles pin the layout, otherwise ``choose_batch_block``
    searches the (quantization-aware) joint surface.  Raises when even a
    (bm=1, tc=1) forward tiling cannot fit — callers route to the per-cell
    fallback (core/lstm automates this)."""
    if block_b is None:
        blocks = choose_batch_block(
            B, T, L, P, H, dtype_bytes=dtype_bytes,
            w_dtype_bytes=w_dtype_bytes, quantized=quantized)
        if blocks is None:
            raise ValueError(
                f"sequence-resident working set (L={L}, P+H={P + H}, "
                f"4H={4 * H}, quantized={quantized}) exceeds the VMEM "
                "budget even at batch tile 1 with tc=1 time streaming; use "
                "the per-cell fallback (core/lstm routes this "
                "automatically)")
        block_b = blocks.block_b
        if time_chunk is None:         # explicit time_chunk survives auto-bm
            time_chunk = blocks.time_chunk
    fwd_spec = (block_b, time_chunk)
    if bwd_block_b is None:
        bwd_blocks = choose_batch_block(
            B, T, L, P, H, dtype_bytes=dtype_bytes,
            w_dtype_bytes=w_dtype_bytes, mode="bwd", quantized=quantized)
        if bwd_blocks is None:
            bwd_spec = ORACLE_BWD
        elif bwd_time_chunk is not None:
            bwd_spec = (bwd_blocks.block_b, bwd_time_chunk)
        else:
            bwd_spec = tuple(bwd_blocks)
    elif bwd_block_b == ORACLE_BWD:
        bwd_spec = ORACLE_BWD
    else:
        bwd_spec = (bwd_block_b, bwd_time_chunk)
    return fwd_spec, bwd_spec


def lstm_seq_q8(w: jax.Array, b: jax.Array, x: jax.Array, *,
                block_b: int | None = None, time_chunk: int | None = None,
                bwd_block_b: int | None = None,
                bwd_time_chunk: int | None = None,
                interpret: bool | None = None
                ) -> tuple[jax.Array, jax.Array]:
    """Whole-sequence stacked LSTM with int8-quantized weights, ONE dispatch.

    Same contract as ``lstm_seq`` (w is the f32 MASTER (L, P+H, 4H) stack;
    quantize/dequantize happen inside — per-output-channel symmetric int8,
    see kernels/ref.quantize_q8), but the kernels hold the weight stack in
    VMEM as int8 + (L, 4H) f32 scales — the dominant VMEM term quartered —
    so ``choose_batch_block(quantized=True)`` admits whole-T residency and
    coarse batch tiles at budgets where the f32 plan must stream or shrink.
    Oracle: kernels/ref.lstm_seq_q8 (dequantize-then-run), matched within an
    fp-rounding error band (the scale folds into the pre-activations); vs
    the UNQUANTIZED plans the contract is the documented int8 error band
    (tests/test_plan_equivalence.py).  Under ``jax.grad``: straight-through
    gradients via the q8 reverse sweep, still 2 dispatches per
    ``value_and_grad`` at any T.
    """
    L, H = w.shape[0], w.shape[-1] // 4
    P = w.shape[1] - H
    B, T, xw = x.shape
    assert w.shape[1] == P + H and xw == P, (w.shape, x.shape)
    fwd_spec, bwd_spec = _resolve_specs(
        B, T, L, P, H, dtype_bytes=jnp.dtype(x.dtype).itemsize,
        w_dtype_bytes=None, quantized=True, block_b=block_b,
        time_chunk=time_chunk, bwd_block_b=bwd_block_b,
        bwd_time_chunk=bwd_time_chunk)
    return _lstm_seq_q8(w, b, x, fwd_spec, bwd_spec, interpret)


def lstm_seq(w: jax.Array, b: jax.Array, x: jax.Array, *,
             block_b: int | None = None, time_chunk: int | None = None,
             bwd_block_b: int | None = None,
             bwd_time_chunk: int | None = None,
             interpret: bool | None = None
             ) -> tuple[jax.Array, jax.Array]:
    """Whole-sequence stacked LSTM in ONE kernel dispatch.

    w: (L, P+H, 4H) stacked gate weights (stack_params); b: (L, 4H);
    x: (B, T, P) input zero-padded to width P (pad_input).
    Returns final (c, h), each (L, B, H).  Oracle: kernels/ref.lstm_seq.

    When ``block_b`` is None the ``(block_b, time_chunk)`` tiling comes
    from ``choose_batch_block`` — whole-T residency when it fits, streamed
    time chunks otherwise; an explicit ``time_chunk`` still pins the time
    layout (only the batch tile is chosen).  An explicit ``block_b`` pins
    the batch tile and ``time_chunk`` then selects the layout directly
    (None = whole-T resident; tc = double-buffered streaming), still ONE
    dispatch either way.

    ``bwd_block_b``/``bwd_time_chunk`` tile the TRAINING path (the
    trajectory-emitting forward + the reverse-sweep kernel, each ONE
    dispatch); defaults come from ``choose_batch_block(mode="bwd")``.  Pass
    ``bwd_block_b=ORACLE_BWD`` (0) to force the oracle-VJP fallback — which
    is also what happens automatically when even a ``(bm=1, tc=1)`` backward
    tiling cannot fit the VMEM budget.  Inference through ``lstm_seq`` never
    pays for residuals: the trajectory variant only runs under
    differentiation (custom_vjp fwd rule).
    """
    L, H = w.shape[0], w.shape[-1] // 4
    P = w.shape[1] - H
    B, T, xw = x.shape
    assert w.shape[1] == P + H and xw == P, (w.shape, x.shape)
    fwd_spec, bwd_spec = _resolve_specs(
        B, T, L, P, H, dtype_bytes=jnp.dtype(x.dtype).itemsize,
        w_dtype_bytes=jnp.dtype(w.dtype).itemsize, quantized=False,
        block_b=block_b, time_chunk=time_chunk, bwd_block_b=bwd_block_b,
        bwd_time_chunk=bwd_time_chunk)
    return _lstm_seq(w, b, x, fwd_spec, bwd_spec, interpret)
