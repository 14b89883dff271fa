"""Generic decoder assembly for all assigned architectures.

A model is a periodic stack of blocks; each block = (mix, mlp) where
  mix ∈ {attention, rwkv6 time-mix, mamba}   and
  mlp ∈ {dense MLP, MoE, rwkv6 channel-mix}
chosen per slot index by the config (cfg.layer_kind / cfg.layer_is_moe).
Layers are executed with ``lax.scan`` over groups of one period (stacked
parameters) to bound HLO size at 48-72 layer depth.

Three entry points share the block code:
  * forward      — full-sequence, no cache (training / dry-run prefill)
  * prefill      — full-sequence, writes the decode cache (serving)
  * decode_step  — one token against the preallocated cache

Modality fronts (per assignment these are the only stubs in the system):
  * vlm    — precomputed patch embeddings -> learned 2-layer projector,
             prepended to the text sequence
  * audio  — K parallel EnCodec codebook ids, embedded and summed; K output
             heads predict the next token of every codebook
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention, common, mamba, mlp, moe, rwkv
from repro.partitioning import Annot, constrain, split


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def _init_slot(key, cfg: ModelConfig, slot: int, dtype) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    kind = cfg.layer_kind(slot)
    p: dict = {"ln1": common.init_norm(cfg.d_model, cfg.norm, jnp.float32)}
    if kind == "attn":
        p["mix"] = attention.init_attention(k1, cfg, dtype)
    elif cfg.ssm.kind == "rwkv6":
        p["mix"] = rwkv.init_tmix(k1, cfg, dtype)
    else:
        p["mix"] = mamba.init_mamba(k1, cfg, dtype)
    p["ln2"] = common.init_norm(cfg.d_model, cfg.norm, jnp.float32)
    if cfg.ssm is not None and cfg.ssm.kind == "rwkv6":
        p["mlp"] = rwkv.init_cmix(k2, cfg, dtype)
    elif cfg.layer_is_moe(slot):
        p["mlp"] = moe.init_moe(k3, cfg, dtype)
    else:
        p["mlp"] = mlp.init_mlp(k4, cfg, dtype)
    return p


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    """Annotated parameter tree (run under jax.eval_shape for dry-runs)."""
    dtype = jnp.dtype(cfg.dtype)
    period = cfg.period
    n_groups = cfg.n_layers // period
    k_embed, k_blocks, k_head, k_extra = jax.random.split(key, 4)

    p: dict = {}
    if cfg.n_codebooks:
        e = jax.random.truncated_normal(
            k_embed, -2.0, 2.0, (cfg.n_codebooks, cfg.vocab, cfg.d_model),
            jnp.float32) * cfg.d_model ** -0.5
        p["audio_embed"] = Annot(e.astype(dtype), (None, "vocab", "embed"))
    else:
        p["embed"] = common.init_embedding(k_embed, cfg.vocab, cfg.d_model,
                                           dtype)
    if cfg.n_vis_tokens:
        kv1, kv2 = jax.random.split(k_extra)
        p["vis_proj"] = {
            "in": common.init_linear(kv1, cfg.vis_dim, cfg.d_model,
                                     ("embed_nofsdp", "embed"), dtype,
                                     bias=True),
            "out": common.init_linear(kv2, cfg.d_model, cfg.d_model,
                                      ("embed", "embed_nofsdp"), dtype,
                                      bias=True),
        }

    # blocks: tuple over period slots, leaves stacked over groups.  One
    # vmap over the groups' keys draws each slot's stack directly: no
    # per-group copies to stack afterwards, and a jitted init compiles one
    # slot's program instead of n_groups unrolled ones.
    slots = []
    block_keys = jax.random.split(k_blocks, n_groups * period
                                  ).reshape(n_groups, period, 2)
    for s in range(period):
        init = functools.partial(_init_slot, cfg=cfg, slot=s, dtype=dtype)
        _, axes = split(jax.eval_shape(init, block_keys[0, s]))
        values = jax.vmap(lambda k: split(init(k))[0])(block_keys[:, s])
        slots.append(jax.tree.map(
            lambda v, ax: Annot(v, ("layers",) + tuple(ax)), values, axes))
    p["blocks"] = tuple(slots)

    p["final_norm"] = common.init_norm(cfg.d_model, cfg.norm, jnp.float32)
    if cfg.n_codebooks:
        h = jax.random.truncated_normal(
            k_head, -2.0, 2.0, (cfg.n_codebooks, cfg.d_model, cfg.vocab),
            jnp.float32) * cfg.d_model ** -0.5
        p["audio_heads"] = Annot(h.astype(dtype), (None, "embed", "vocab"))
    elif not cfg.tie_embeddings:
        p["lm_head"] = common.init_linear(
            k_head, cfg.d_model, cfg.vocab, ("embed", "vocab"), dtype)
    return p


def abstract_params(cfg: ModelConfig, key=None) -> tuple[Any, Any]:
    """(ShapeDtypeStruct tree, axes tree) without materialising anything."""
    key = key if key is not None else jax.random.PRNGKey(0)
    annot = jax.eval_shape(functools.partial(init_params, cfg), key)
    # eval_shape maps through Annot dataclass?  Annot is not a pytree — the
    # shapes come back as Annot(value=ShapeDtypeStruct).  Split as usual.
    return split(annot)


# ---------------------------------------------------------------------------
# Cache
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_seq: int) -> dict:
    """Annotated zero decode cache (the preallocated state pool contents)."""
    dtype = jnp.dtype(cfg.dtype)
    period = cfg.period
    n_groups = cfg.n_layers // period
    slots = []
    for s in range(period):
        kind = cfg.layer_kind(s)
        if kind == "attn":
            slot = attention.init_cache_slot(cfg, n_groups, batch, max_seq,
                                             dtype)
        elif cfg.ssm.kind == "rwkv6":
            H, dh = rwkv.n_heads(cfg), cfg.ssm.head_dim
            d = cfg.d_model
            slot = {
                "shift_t": Annot(jnp.zeros((n_groups, batch, d), dtype),
                                 ("layers", "batch", "embed_nofsdp")),
                "wkv": Annot(jnp.zeros((n_groups, batch, H, dh, dh),
                                       jnp.float32),
                             ("layers", "batch", "heads", None, None)),
                "shift_c": Annot(jnp.zeros((n_groups, batch, d), dtype),
                                 ("layers", "batch", "embed_nofsdp")),
            }
        else:
            di, ds, dc = (mamba.d_inner(cfg), cfg.ssm.d_state,
                          cfg.ssm.d_conv)
            slot = {
                "conv": Annot(jnp.zeros((n_groups, batch, dc - 1, di), dtype),
                              ("layers", "batch", None, "mlp")),
                "h": Annot(jnp.zeros((n_groups, batch, di, ds), jnp.float32),
                           ("layers", "batch", "mlp", None)),
            }
        slots.append(slot)
    return {"pos": Annot(jnp.zeros((), jnp.int32), ()),
            "slots": tuple(slots)}


def abstract_cache(cfg: ModelConfig, batch: int, max_seq: int):
    annot = jax.eval_shape(
        functools.partial(init_cache, cfg, batch, max_seq))
    return split(annot)


# ---------------------------------------------------------------------------
# Block application
# ---------------------------------------------------------------------------
def _apply_mlp_slot(slot_p, cfg: ModelConfig, slot: int, x, cache, aux,
                    mode: str):
    """Second half-block (mlp / moe / cmix) with residual."""
    h = common.apply_norm(slot_p["ln2"], x, cfg.norm)
    if cfg.ssm is not None and cfg.ssm.kind == "rwkv6":
        out, shift = rwkv.apply_cmix(slot_p["mlp"], h, cache["shift_c"])
        cache = dict(cache, shift_c=shift)
        return x + out, cache, aux
    if cfg.layer_is_moe(slot):
        out, moe_aux = moe.apply_moe(slot_p["mlp"], h, cfg,
                                     no_drop=(mode != "full"))
        for k, v in moe_aux.items():
            aux = dict(aux)
            aux[k] = aux.get(k, 0.0) + v
    else:
        out = mlp.apply_mlp(slot_p["mlp"], h, cfg)
    return x + out, cache, aux


def _dummy_cache_slot(cfg: ModelConfig, slot: int, batch: int) -> dict:
    """Zero-state stand-in when running without a cache (training mode)."""
    kind = cfg.layer_kind(slot)
    dtype = jnp.dtype(cfg.dtype)
    if kind == "attn":
        return {}
    if cfg.ssm.kind == "rwkv6":
        H, dh = rwkv.n_heads(cfg), cfg.ssm.head_dim
        return {"shift_t": jnp.zeros((batch, cfg.d_model), dtype),
                "wkv": jnp.zeros((batch, H, dh, dh), jnp.float32),
                "shift_c": jnp.zeros((batch, cfg.d_model), dtype)}
    di, ds, dc = mamba.d_inner(cfg), cfg.ssm.d_state, cfg.ssm.d_conv
    return {"conv": jnp.zeros((batch, dc - 1, di), dtype),
            "h": jnp.zeros((batch, di, ds), jnp.float32)}


def _apply_block(slot_p, cfg: ModelConfig, slot: int, x, cache_slot,
                 positions, pos, aux, mode: str):
    """One block (mix + mlp).  cache_slot has NO group dim here (inside
    scan).  mode: 'full' | 'prefill' | 'prefill_chunk' | 'decode'."""
    kind = cfg.layer_kind(slot)
    x = constrain(x, ("batch", _sax(cfg), None))
    h = common.apply_norm(slot_p["ln1"], x, cfg.norm)
    new_cache = dict(cache_slot)
    if kind == "attn":
        if mode == "decode":
            out, kv = attention.decode_attention(slot_p["mix"], h,
                                                 cache_slot, pos, cfg)
            new_cache.update(kv)
        elif mode == "prefill_chunk":
            out, kv = attention.chunk_prefill_attention(
                slot_p["mix"], h, cache_slot, cfg, positions)
            new_cache.update(kv)
        else:
            out = attention.apply_attention(slot_p["mix"], h, cfg, positions)
            if mode == "prefill":
                new_cache.update(attention.prefill_cache(
                    slot_p["mix"], h, cache_slot, cfg, positions))
    elif cfg.ssm.kind == "rwkv6":
        fn = rwkv.step_tmix if mode == "decode" else rwkv.apply_tmix
        out, shift, state = fn(slot_p["mix"], cfg, h,
                               cache_slot["shift_t"], cache_slot["wkv"])
        new_cache.update(shift_t=shift, wkv=state)
    else:
        fn = mamba.step_mamba if mode == "decode" else mamba.apply_mamba
        out, conv, hst = fn(slot_p["mix"], cfg, h, cache_slot["conv"],
                            cache_slot["h"])
        new_cache.update(conv=conv, h=hst)
    x = x + out
    return _apply_mlp_slot(slot_p, cfg, slot, x, new_cache, aux, mode)


# ---------------------------------------------------------------------------
# Embedding / head fronts
# ---------------------------------------------------------------------------
def _sax(cfg: ModelConfig) -> str:
    """Logical name of the activation sequence axis (sequence parallelism
    shards it over 'model' for cfg.seq_shard archs)."""
    return "seq_model" if cfg.seq_shard else "seq"


def embed_inputs(params, cfg: ModelConfig, batch: dict) -> jax.Array:
    if cfg.n_codebooks:
        toks = batch["tokens"]                          # (B, K, S)
        x = jnp.zeros(toks.shape[:1] + toks.shape[2:]
                      + (cfg.d_model,), jnp.dtype(cfg.dtype))
        for k in range(cfg.n_codebooks):                # sum codebook embeds
            x = x + jnp.take(params["audio_embed"][k], toks[:, k], axis=0)
        return x                                        # (B, S, d)
    x = jnp.take(params["embed"], batch["tokens"], axis=0)
    x = constrain(x, ("batch", _sax(cfg), None))
    if cfg.n_vis_tokens and "vis_embeds" in batch:
        vp = params["vis_proj"]
        v = common.apply_linear(vp["in"], batch["vis_embeds"].astype(x.dtype))
        v = common.apply_linear(vp["out"], jax.nn.gelu(v))
        x = jnp.concatenate([v, x], axis=1)
    return x


def lm_logits(params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    sax = _sax(cfg)
    x = constrain(x, ("batch", sax, None))
    if cfg.n_codebooks:
        logits = jnp.einsum("bsd,kdv->bksv", x, params["audio_heads"])
        logits = constrain(logits, ("batch", None, sax, "vocab"))
    elif cfg.tie_embeddings:
        logits = x @ params["embed"].T
        logits = constrain(logits, ("batch", sax, "vocab"))
    else:
        logits = common.apply_linear(params["lm_head"], x)
        logits = constrain(logits, ("batch", sax, "vocab"))
    return common.softcap(logits.astype(jnp.float32), cfg.logit_softcap)


# ---------------------------------------------------------------------------
# Entry points (take PLAIN param / cache trees, post-split)
# ---------------------------------------------------------------------------
def forward(params, cfg: ModelConfig, batch: dict, *, remat: bool = False,
            inference: bool = False) -> tuple[jax.Array, dict]:
    """Full-sequence forward, no cache.  Returns (logits, aux).

    inference=True switches MoE layers to drop-free dispatch so the result
    is bit-consistent with the prefill/decode paths."""
    mode = "infer" if inference else "full"
    x = embed_inputs(params, cfg, batch)
    B, S = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    period = cfg.period
    dummies = tuple(_dummy_cache_slot(cfg, s, B) for s in range(period))
    aux0 = {}
    if cfg.moe is not None:
        z = jnp.zeros((), jnp.float32)
        aux0 = {"moe_load_balance": z, "moe_z_loss": z, "moe_drop_frac": z}

    def group_fn(carry, group_params):
        x, aux = carry
        for s in range(period):
            x, _, aux = _apply_block(group_params[s], cfg, s, x, dummies[s],
                                     positions, None, aux, mode)
        return (x, aux), None

    fn = jax.checkpoint(group_fn) if remat else group_fn
    (x, aux), _ = jax.lax.scan(fn, (x, aux0), params["blocks"])
    x = common.apply_norm(params["final_norm"], x, cfg.norm)
    return lm_logits(params, cfg, x), aux


def prefill(params, cfg: ModelConfig, cache, batch: dict
            ) -> tuple[jax.Array, dict]:
    """Full-sequence forward that also fills the decode cache.

    Returns (logits of the LAST position, updated cache)."""
    x = embed_inputs(params, cfg, batch)
    B, S = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    period = cfg.period
    aux = {}

    def group_fn(carry, xs):
        x = carry
        group_params, cache_slots = xs
        new_slots = []
        a = {}
        for s in range(period):
            x, new_c, a = _apply_block(group_params[s], cfg, s, x,
                                       cache_slots[s], positions, None, a,
                                       "prefill")
            new_slots.append(new_c)
        return x, tuple(new_slots)

    x, new_slots = jax.lax.scan(group_fn, x,
                                (params["blocks"], cache["slots"]))
    x = common.apply_norm(params["final_norm"], x, cfg.norm)
    logits = lm_logits(params, cfg, x[:, -1:])
    new_cache = {"pos": jnp.asarray(S, jnp.int32), "slots": new_slots}
    del aux
    return logits, new_cache


def prefill_chunk(params, cfg: ModelConfig, cache, batch: dict
                  ) -> tuple[jax.Array, dict]:
    """One fixed-shape prefill chunk: a (B, L) prompt slice continuing at
    absolute position ``cache['pos']`` (a TRACED scalar, unlike
    ``prefill``'s static S — one compiled executable serves every chunk of
    length L wherever it lands in the prompt).

    Attention scatters the chunk's k/v into the cache and attends the full
    cache under a content-position mask
    (attention.chunk_prefill_attention); rwkv/mamba consume the cache as
    their incoming recurrent state — for them a chunk is mathematically
    just a shorter ``prefill`` that starts from carried state.  Returns
    (logits of the chunk's LAST position, updated cache with
    ``pos += L``) — only the final chunk's logits sample a real token.

    Not valid for vis-token prompts (cfg.n_vis_tokens): the learned
    vis prefix is prepended whole at embed time and cannot be sliced
    into token chunks; callers route those through ``prefill``.
    """
    x = embed_inputs(params, cfg, batch)
    B, L = x.shape[0], x.shape[1]
    base = cache["pos"]
    positions = base + jnp.broadcast_to(jnp.arange(L), (B, L))
    period = cfg.period

    def group_fn(carry, xs):
        x = carry
        group_params, cache_slots = xs
        new_slots = []
        a = {}
        for s in range(period):
            x, new_c, a = _apply_block(group_params[s], cfg, s, x,
                                       cache_slots[s], positions, None, a,
                                       "prefill_chunk")
            new_slots.append(new_c)
        return x, tuple(new_slots)

    x, new_slots = jax.lax.scan(group_fn, x,
                                (params["blocks"], cache["slots"]))
    x = common.apply_norm(params["final_norm"], x, cfg.norm)
    logits = lm_logits(params, cfg, x[:, -1:])
    new_cache = {"pos": base + jnp.asarray(L, jnp.int32),
                 "slots": new_slots}
    return logits, new_cache


def decode_step(params, cfg: ModelConfig, cache, batch: dict
                ) -> tuple[jax.Array, dict]:
    """One decode step.  batch['tokens']: (B,) or (B,K) audio.
    Returns (logits (B,[K,]vocab), updated cache).

    ``cache['pos']`` may be a scalar (lockstep waves) or a (B,) vector
    (slot-resident continuous batching, serving/slots.py) — attention
    handles both; rwkv/mamba state is positionless either way."""
    toks = batch["tokens"]
    if cfg.n_codebooks:
        x = jnp.zeros((toks.shape[0], 1, cfg.d_model), jnp.dtype(cfg.dtype))
        for k in range(cfg.n_codebooks):
            x = x + jnp.take(params["audio_embed"][k], toks[:, k:k + 1],
                             axis=0)
    else:
        x = jnp.take(params["embed"], toks[:, None], axis=0)
    pos = cache["pos"]
    period = cfg.period

    def group_fn(x, xs):
        group_params, cache_slots = xs
        new_slots = []
        aux = {}
        for s in range(period):
            x, new_c, aux = _apply_block(group_params[s], cfg, s, x,
                                         cache_slots[s], None, pos, aux,
                                         "decode")
            new_slots.append(new_c)
        return x, tuple(new_slots)

    x, new_slots = jax.lax.scan(group_fn, x,
                                (params["blocks"], cache["slots"]))
    x = common.apply_norm(params["final_norm"], x, cfg.norm)
    logits = lm_logits(params, cfg, x)[:, 0] if not cfg.n_codebooks else \
        lm_logits(params, cfg, x)[:, :, 0]
    new_cache = {"pos": pos + 1, "slots": new_slots}
    return logits, new_cache
