"""The paper's model: stacked LSTM for activity recognition (MobiRNN §4.1).

FIVE execution plans over the same parameters (the first four numerically
equivalent, asserted by tests/test_plan_equivalence.py; the fifth equivalent
within a documented int8 error band), and when the scheduler
(core/scheduler.py) should prefer each:

* ``forward_sequential`` — reference plan: scan over time, layers unrolled
  inside the step (the single-threaded baseline of Fig 3/4).  Prefer on the
  CPU path / under high accelerator load (paper Fig 7).
* ``forward_wavefront`` — the paper's Fig 1 diagonal parallelism: cells on an
  anti-diagonal (layer i, time t, i+t = const) execute together as ONE vmapped
  cell call over layers (see core/wavefront.py).  Prefer when L is large
  enough for the diagonal batching to pay for its masking overhead.
* ``forward_fused_kernel`` — sequential plan but each cell is the Pallas
  fused-gate kernel (kernels/lstm_cell.py) instead of jnp ops.  T x L kernel
  dispatches; prefer in COMPUTE-BOUND regimes where H is too large for the
  whole weight stack to sit in VMEM (the per-cell kernel tiles hidden).
* ``forward_fused_seq`` — sequence-resident Pallas kernel
  (kernels/lstm_seq.py): the whole T-step, L-layer recurrence in ONE
  dispatch, weights loaded to VMEM once, (c, h) never leaving VMEM.  Prefer
  in DISPATCH-BOUND regimes (small/medium models, long sequences) — the
  MobiRNN fast path.  Falls back to ``forward_fused_kernel`` when the
  stacked weights exceed the VMEM budget (core/factorization).
* ``forward_fused_seq_q8`` — the sequence-resident plan with per-output-
  channel symmetric INT8 weights (f32 scales + biases), dequantized on the
  fly inside the fused kernels.  Quarters the dominant VMEM term and the
  streamed weight traffic, so it stays whole-T-resident (and viable at all)
  deeper into the ``(T, budget)`` surface than ``fused_seq`` — the
  RTMobile/Grachev compression lever applied to the MobiRNN fast path.
  Matches the dequantize oracle (kernels/ref.lstm_seq_q8) within fp
  rounding and the f32 plans within the int8 error band.

All five are real TRAINING choices too: under ``jax.grad`` the fused plans
carry custom VJPs — ``fused_seq`` runs ONE reverse-sweep BPTT kernel
(kernels/lstm_seq_bwd.py; 2 dispatches per value_and_grad, O(1) in T) with
an oracle-VJP fallback gated by ``choose_batch_block(mode="bwd")``;
``fused_cell`` differentiates the per-cell oracle.  Train-time schedulers
must size the backward working set via ``plan_viability(train=True)``.

The classifier head follows Guan & Ploetz-style HAR models: last hidden state
-> dense -> 6-way softmax.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.configs.mobirnn_lstm import LSTMConfig
from repro.core import cell as cell_lib
from repro.partitioning import Annot, split


def init_params(key: jax.Array, cfg: LSTMConfig) -> dict:
    """Annotated parameter tree for the stacked LSTM + HAR head."""
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, cfg.n_layers + 1)
    layers = []
    for i in range(cfg.n_layers):
        in_dim = cfg.input_dim if i == 0 else cfg.hidden
        layers.append(cell_lib.init_cell(keys[i], in_dim, cfg.hidden, dtype))
    head_w = jax.random.truncated_normal(
        keys[-1], -2.0, 2.0, (cfg.hidden, cfg.n_classes), jnp.float32
    ) * cfg.hidden ** -0.5
    return {
        "layers": layers,
        "head": {
            "w": Annot(head_w.astype(dtype), ("embed", None)),
            "b": Annot(jnp.zeros((cfg.n_classes,), dtype), (None,)),
        },
    }


def init_state(cfg: LSTMConfig, batch: int, dtype=jnp.float32
               ) -> tuple[jax.Array, jax.Array]:
    """Preallocated (c, h) buffers, one pair per layer (paper §3.2: state
    tensors are preallocated once and reused across the whole sequence)."""
    shape = (cfg.n_layers, batch, cfg.hidden)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def _plain_params(params: dict) -> dict:
    values, _ = split(params)
    return values


def forward_sequential(
    params: dict, x: jax.Array, cfg: LSTMConfig,
    cell_fn: Callable = cell_lib.lstm_cell_fused,
) -> jax.Array:
    """Reference plan.  x: (batch, seq, input_dim) -> logits (batch, classes).

    Scan over time; within a step, layers run in dependency order.  The (c,h)
    buffers are the scan carry — XLA keeps them in place (donated buffers),
    realising the paper's preallocation/reuse optimization.
    """
    p = _plain_params(params)
    cells = [functools.partial(cell_fn, layer) for layer in p["layers"]]
    return _scan_cells(cells, p["head"], x, cfg)


def _scan_cells(cells: list[Callable], head: dict, x: jax.Array,
                cfg: LSTMConfig) -> jax.Array:
    """Run ``cells[i](inp, c, h) -> (c', h')`` layer by layer inside one
    scan over time, then the classifier head on the last layer's h."""
    c0, h0 = init_state(cfg, x.shape[0], x.dtype)

    def step(carry, x_t):
        c, h = carry
        inp = x_t
        cs, hs = [], []
        for i, cell in enumerate(cells):
            c_i, h_i = cell(inp, c[i], h[i])
            cs.append(c_i)
            hs.append(h_i)
            inp = h_i
        return (jnp.stack(cs), jnp.stack(hs)), None

    (c, h), _ = jax.lax.scan(step, (c0, h0), jnp.swapaxes(x, 0, 1))
    return h[-1] @ head["w"] + head["b"]


def forward_fused_kernel(params: dict, x: jax.Array, cfg: LSTMConfig,
                         interpret: bool | None = None) -> jax.Array:
    """Sequential plan with the Pallas fused-cell kernel as the cell body;
    each layer's weights are laid out for the kernel once per forward."""
    from repro.kernels import ops as kernel_ops

    p = _plain_params(params)
    cells = [kernel_ops.lstm_cell_step(layer["w"], layer["b"], x.shape[0],
                                       interpret=interpret)
             for layer in p["layers"]]
    return _scan_cells(cells, p["head"], x, cfg)


def forward_fused_seq(params: dict, x: jax.Array, cfg: LSTMConfig,
                      interpret: bool | None = None,
                      vmem_budget: int | None = None) -> jax.Array:
    """Sequence-resident plan: ONE Pallas dispatch for the whole (T x L)
    recurrence (kernels/lstm_seq.py) — dispatch count O(1) in T instead of
    the per-cell plan's O(T*L).  Under ``jax.grad`` the custom VJP runs the
    trajectory-emitting forward plus ONE reverse-sweep BPTT dispatch
    (kernels/lstm_seq_bwd.py); when the backward working set (~3x the
    forward one) does not fit VMEM, the backward alone falls back to the
    oracle VJP while the forward stays fused.

    The tiling comes from ``choose_batch_block`` as a ``(block_b,
    time_chunk)`` pair: whole-T VMEM residency when it fits, otherwise the
    kernels STREAM the time axis through double-buffered chunks — long T is
    no longer a reason to leave the fused plan.  Only when even a
    ``(bm=1, tc=1)`` tiling cannot fit (the weight stack itself blows the
    budget) does this route to ``forward_fused_kernel``, whose per-cell
    kernel tiles the hidden dimension through HBM instead.
    """
    return _forward_fused_seq_impl(params, x, cfg, interpret=interpret,
                                   vmem_budget=vmem_budget, quantized=False)


def _forward_fused_seq_impl(params: dict, x: jax.Array, cfg: LSTMConfig, *,
                            interpret: bool | None,
                            vmem_budget: int | None,
                            quantized: bool) -> jax.Array:
    """Shared body of the f32 and int8 sequence-resident plans: stack the
    layer params, consult the (quantization-aware) ``(block_b, time_chunk)``
    table for the fwd and bwd dispatches, fall back to the per-cell kernel
    when even (bm=1, tc=1) cannot fit, run the fused kernel, apply the
    head.  ``quantized`` flips the budget surface, casts the stack to f32
    masters (so int8 rounding is the ONLY deviation and straight-through
    grads land in f32 before the astype VJP returns them to param dtype),
    and dispatches the q8 kernel."""
    from repro.kernels import lstm_seq as seq_lib
    from repro.kernels import ops as kernel_ops

    p = _plain_params(params)
    w_stack, b_stack, p_width = seq_lib.stack_params(p["layers"], cfg.hidden)
    if quantized:
        w_stack = w_stack.astype(jnp.float32)
        b_stack = b_stack.astype(jnp.float32)
        w_bytes = None                  # 1 byte/weight via quantized=True
    else:
        w_bytes = jnp.dtype(w_stack.dtype).itemsize
    B, T, _ = x.shape
    dtype_bytes = jnp.dtype(x.dtype).itemsize
    blocks = seq_lib.choose_batch_block(
        B, T, cfg.n_layers, p_width, cfg.hidden,
        dtype_bytes=dtype_bytes, vmem_budget=vmem_budget,
        w_dtype_bytes=w_bytes, quantized=quantized)
    from repro.obs import trace as trace_lib
    tracer = trace_lib.get_tracer()
    plan_name = "fused_seq_q8" if quantized else "fused_seq"
    if blocks is None:    # weight stack > VMEM even at (bm=1, tc=1)
        if tracer.enabled:   # the silent fallback, made visible
            tracer.event("plan/dispatch", family="lstm", plan=plan_name,
                         fallback="fused_cell", batch=B, seq_len=T)
        return forward_fused_kernel(params, x, cfg, interpret=interpret)
    bwd_blocks = seq_lib.choose_batch_block(
        B, T, cfg.n_layers, p_width, cfg.hidden,
        dtype_bytes=dtype_bytes, vmem_budget=vmem_budget,
        w_dtype_bytes=w_bytes, mode="bwd", quantized=quantized)
    xp = seq_lib.pad_input(x, p_width)
    if bwd_blocks is None:
        bwd_kw = dict(bwd_block_b=seq_lib.ORACLE_BWD)
    else:
        bwd_kw = dict(bwd_block_b=bwd_blocks.block_b,
                      bwd_time_chunk=bwd_blocks.time_chunk)
    if tracer.enabled:
        tracer.event("plan/dispatch", family="lstm", plan=plan_name,
                     block_b=blocks.block_b, time_chunk=blocks.time_chunk,
                     bwd_block_b=bwd_kw.get("bwd_block_b"),
                     bwd_time_chunk=bwd_kw.get("bwd_time_chunk"),
                     batch=B, seq_len=T)
    op = kernel_ops.lstm_seq_q8 if quantized else kernel_ops.lstm_seq
    _, h = op(w_stack, b_stack, xp, block_b=blocks.block_b,
              time_chunk=blocks.time_chunk, interpret=interpret, **bwd_kw)
    return h[-1] @ p["head"]["w"] + p["head"]["b"]


def forward_fused_seq_q8(params: dict, x: jax.Array, cfg: LSTMConfig,
                         interpret: bool | None = None,
                         vmem_budget: int | None = None) -> jax.Array:
    """Int8-weight sequence-resident plan: the ``fused_seq`` fast path with
    the stacked weights quantized to per-output-channel symmetric int8
    (kernels/ref.quantize_q8) and dequantized on the fly inside the fused
    kernels.  The dominant VMEM term — the (L, P+H, 4H) weight stack —
    shrinks 4x, so ``choose_batch_block(quantized=True)`` keeps whole-T
    residency deeper into T and coarser batch tiles at budgets where the
    f32 plan must stream or fall back, and the streamed-HBM roofline sees
    ~4x less weight traffic (analysis.lstm_seq_stream_costs).

    NOT numerically equivalent to the other plans: it matches the
    dequantize oracle within fp rounding, and the f32 plans within the
    documented int8 error band (tests/test_plan_equivalence.py).  Under
    ``jax.grad`` the straight-through q8 reverse sweep keeps
    ``value_and_grad`` at exactly 2 dispatches; masters stay f32, so the
    plan is a drop-in quantization-aware-training choice.
    """
    return _forward_fused_seq_impl(params, x, cfg, interpret=interpret,
                                   vmem_budget=vmem_budget, quantized=True)


def forward_wavefront(params: dict, x: jax.Array, cfg: LSTMConfig
                      ) -> jax.Array:
    """Paper Fig 1 diagonal plan — see core/wavefront.py."""
    from repro.core import wavefront
    return wavefront.forward_wavefront(params, x, cfg)


#: All five execution plans, keyed by scheduler Plan name — the registration
#: table used by benchmarks/run.py, examples/quickstart.py, and the
#: equivalence tests.  Every entry maps (params, x, cfg) -> logits.  The
#: first four are numerically equivalent; ``fused_seq_q8`` is the
#: int8-weight variant of ``fused_seq`` and matches the others only within
#: the documented int8 error band (see its docstring).
FORWARD_PLANS: dict[str, Callable] = {
    "sequential": forward_sequential,
    "wavefront": forward_wavefront,
    "fused_cell": forward_fused_kernel,
    "fused_seq": forward_fused_seq,
    "fused_seq_q8": forward_fused_seq_q8,
}


def plan_viability(cfg: LSTMConfig, batch: int, seq_len: int, *,
                   seq_plan_names: tuple[str, ...] = ("fused_seq",),
                   q8_plan_names: tuple[str, ...] = ("fused_seq_q8",),
                   dtype_bytes: int = 4, w_dtype_bytes: int | None = None,
                   vmem_budget: int | None = None,
                   train: bool = False) -> Callable[[str], bool]:
    """Viability predicate for ``Scheduler(viable=...)``.

    The sequence-resident plan is only a real plan while
    ``kernels/lstm_seq.choose_batch_block`` finds a ``(block_b,
    time_chunk)`` tiling whose working set fits VMEM — whole-T residency or
    double-buffered time streaming; past the budget ``forward_fused_seq``
    silently reroutes to the per-cell kernel, so calibrating or choosing it
    would just duplicate ``fused_cell`` under a misleading name.  With time
    streaming, long T alone never disqualifies the plan — only a weight
    stack (plus its gradient accumulators, under ``train=True``) that blows
    the budget at ``(bm=1, tc=1)`` does.  ``seq_plan_names`` lists the
    scheduler names registered for the sequence-resident plan (benchmarks
    register it as ``accel_seq``).  All other plan names are always viable.

    ``train=True`` sizes the BACKWARD working set instead
    (``choose_batch_block(mode="bwd")``: trajectory residuals + gradient
    accumulators, ~3x the forward) — the number that matters when the
    scheduled step runs under ``jax.grad``.  Without it the scheduler can
    pick ``fused_seq`` for a training step whose backward residuals blow
    the VMEM budget and silently drops to the oracle VJP, i.e. the slow
    path under the fast plan's name.

    ``q8_plan_names`` lists the scheduler names of the INT8-weight
    sequence-resident plan (``fused_seq_q8``); its viability surface is the
    quantization-aware table (``choose_batch_block(quantized=True)``: 1-byte
    weight stack + f32 scales; f32 dw/db outs under ``train=True``) — a
    strictly-no-smaller window than the f32 plan's, so there are budgets
    where the scheduler may only offer the quantized fast path.
    """
    from repro.kernels import lstm_seq as seq_lib

    p_width = max(cfg.input_dim, cfg.hidden)
    mode = "bwd" if train else "fwd"
    block = seq_lib.choose_batch_block(
        batch, seq_len, cfg.n_layers, p_width, cfg.hidden,
        dtype_bytes=dtype_bytes, vmem_budget=vmem_budget,
        w_dtype_bytes=w_dtype_bytes, mode=mode)
    q8_block = seq_lib.choose_batch_block(
        batch, seq_len, cfg.n_layers, p_width, cfg.hidden,
        dtype_bytes=dtype_bytes, vmem_budget=vmem_budget, mode=mode,
        quantized=True)

    def viable(plan_name: str) -> bool:
        if plan_name in q8_plan_names:
            return q8_block is not None
        return block is not None or plan_name not in seq_plan_names

    return viable


def loss_fn(params: dict, x: jax.Array, labels: jax.Array, cfg: LSTMConfig,
            forward: Callable = forward_sequential) -> jax.Array:
    logits = forward(params, x, cfg)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def accuracy(params: dict, x: jax.Array, labels: jax.Array, cfg: LSTMConfig,
             forward: Callable = forward_sequential) -> jax.Array:
    logits = forward(params, x, cfg)
    return jnp.mean((jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32))
