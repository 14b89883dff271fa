"""Step functions shared by training, serving, smoke tests and the dry-run.

All functions take PLAIN pytrees (post ``partitioning.split``); sharding is
applied by the callers via in_shardings/out_shardings.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import transformer


def _xent(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Mean cross-entropy; logits (..., V) fp32, targets (...) int."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


def loss_fn(params: Any, cfg: ModelConfig, batch: dict, *,
            remat: bool = True) -> tuple[jax.Array, dict]:
    logits, aux = transformer.forward(params, cfg, batch, remat=remat)
    toks = batch["tokens"]
    if cfg.n_codebooks:
        # logits (B,K,S,V): every codebook predicts its own next token
        loss = _xent(logits[:, :, :-1], toks[:, :, 1:])
    elif cfg.n_vis_tokens:
        # layout [vis | text]: position n_vis-1+i predicts text token i
        nv = cfg.n_vis_tokens
        loss = _xent(logits[:, nv - 1:-1], toks)
    else:
        loss = _xent(logits[:, :-1], toks[:, 1:])
    metrics = {"xent": loss}
    if cfg.moe is not None:
        n_moe = sum(cfg.layer_is_moe(i) for i in range(cfg.n_layers))
        lb = aux["moe_load_balance"] / max(n_moe, 1)
        zl = aux["moe_z_loss"] / max(n_moe, 1)
        loss = loss + cfg.moe.router_aux_weight * (lb + 0.1 * zl)
        metrics.update(moe_load_balance=lb, moe_z_loss=zl,
                       moe_drop_frac=aux["moe_drop_frac"] / max(n_moe, 1))
    metrics["loss"] = loss
    return loss, metrics


def train_step(optimizer, cfg: ModelConfig, params: Any, opt_state: dict,
               batch: dict) -> tuple[Any, dict, dict]:
    (loss, metrics), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(params, cfg, batch)
    params, opt_state, opt_metrics = optimizer.update(grads, opt_state,
                                                      params)
    metrics.update(opt_metrics)
    return params, opt_state, metrics


def eval_step(cfg: ModelConfig, params: Any, batch: dict) -> dict:
    loss, metrics = loss_fn(params, cfg, batch, remat=False)
    return metrics


def prefill_step(cfg: ModelConfig, params: Any, cache: Any, batch: dict
                 ) -> tuple[jax.Array, Any]:
    return transformer.prefill(params, cfg, cache, batch)


def chunked_prefill_step(cfg: ModelConfig, params: Any, cache: Any,
                         batch: dict) -> tuple[jax.Array, Any]:
    """One fixed-shape admission-prefill chunk (transformer.prefill_chunk):
    ``batch['tokens']`` is a (B, L) prompt slice whose absolute start is
    the TRACED ``cache['pos']``, so ONE compiled executable per chunk
    length L serves every chunk of every prompt — the one-shape-per-
    ``(chunk_len,)`` contract chunked admission is built on."""
    return transformer.prefill_chunk(params, cfg, cache, batch)


def decode_step(cfg: ModelConfig, params: Any, cache: Any, batch: dict
                ) -> tuple[jax.Array, Any]:
    return transformer.decode_step(params, cfg, cache, batch)


def masked_decode_step(cfg: ModelConfig, params: Any, cache: Any,
                       batch: dict, step_fn: Any = None
                       ) -> tuple[jax.Array, Any]:
    """One fused decode tick across B slots honouring a per-slot active mask.

    ``batch['active']`` is a (B,) bool mask; ``cache['pos']`` must be the
    per-lane (B,) vector form.  Inactive lanes (free slots, finished
    requests) still ride through the fixed-shape computation — that is the
    point: ONE dispatch per tick regardless of occupancy — but their cache
    slices and position counters keep their input values, so a dead lane is
    semantically a no-op and its logits are garbage the caller must ignore.

    The cache-slot select happens inside the step's layer scan
    (transformer.decode_step, per layer), where the state is already read
    and written; only the (B,) ``pos`` is selected here.  So ``step_fn``
    (default ``decode_step``; the serving engine wraps alternate decode
    plans the same way) receives ``batch['active']`` and must honour it.
    """
    step = step_fn or decode_step
    active = batch["active"]
    logits, new_cache = step(cfg, params, cache, batch)
    pos = jnp.where(active, new_cache["pos"], cache["pos"])
    return logits, {"pos": pos, "slots": new_cache["slots"]}


def guarded_decode_step(cfg: ModelConfig, params: Any, cache: Any,
                        batch: dict, step_fn: Any = None
                        ) -> tuple[jax.Array, jax.Array, Any]:
    """``masked_decode_step`` plus the per-lane finite guard — the serving
    fault path's device half, folded into the SAME jit as the tick (one
    extra reduction, no extra dispatch, no shape change).

    ``batch['poison']`` is an optional (B,) bool fault-injection hook
    (serving/faults.FaultPlan): poisoned lanes' logits are overwritten with
    NaN INSIDE the jit, exercising exactly the guard a genuinely non-finite
    lane would trip.  Returns ``(logits, lane_ok, new_cache)`` where
    ``lane_ok`` is (B,) bool — False iff an ACTIVE lane produced non-finite
    logits this tick (inactive lanes carry garbage logits by design and
    never report faults).  With an all-False poison mask the logits are
    bit-identical to the unguarded tick: ``where`` with a false mask and
    the ``isfinite`` reduction change no values.
    """
    active = batch["active"]
    poison = batch.get("poison")
    logits, new_cache = masked_decode_step(
        cfg, params, cache,
        {k: v for k, v in batch.items() if k != "poison"}, step_fn=step_fn)
    if poison is not None:
        m = poison.reshape((-1,) + (1,) * (logits.ndim - 1))
        logits = jnp.where(m, jnp.asarray(jnp.nan, logits.dtype), logits)
    finite = jnp.all(jnp.isfinite(logits),
                     axis=tuple(range(1, logits.ndim)))
    return logits, finite | ~active, new_cache


def greedy_sample(logits: jax.Array) -> jax.Array:
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)
