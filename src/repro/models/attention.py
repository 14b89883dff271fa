"""GQA attention: chunked (flash-style) prefill/train and cached decode.

Prefill/train uses a two-level ``lax.scan`` over query and key/value blocks
with online-softmax accumulation — the O(S) working-set formulation required
for 32k prefill.  This is the MobiRNN coarse-factorization rule at the
sequence level: blocks are the work units; their size is the coarseness knob.

Decode attends one new token against a preallocated cache.  Two cache
layouts are supported:
  * full    — (B, S_max, Hkv, dh), position `pos` written in place
  * ring    — sliding-window (B, W, Hkv, dh), slot ``pos % W`` overwritten;
              slot j holds absolute position pos - ((pos - j) mod W)
Ring caches are what make `long_500k` decode possible for dense archs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import common
from repro.partitioning import Annot

NEG_INF = -1e30


def init_attention(key, cfg: ModelConfig, dtype) -> dict:
    d, hq, hkv, dh = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                      cfg.resolved_head_dim)
    ks = jax.random.split(key, 4)
    s = d ** -0.5

    def w(k, shape, axes):
        return Annot((jax.random.truncated_normal(k, -2.0, 2.0, shape,
                                                  jnp.float32) * s
                      ).astype(dtype), axes)

    p = {
        "wq": w(ks[0], (d, hq, dh), ("embed", "heads", None)),
        "wk": w(ks[1], (d, hkv, dh), ("embed", "kv_heads", None)),
        "wv": w(ks[2], (d, hkv, dh), ("embed", "kv_heads", None)),
        "wo": w(ks[3], (hq, dh, d), ("heads", None, "embed")),
    }
    if cfg.qkv_bias:
        p["bq"] = Annot(jnp.zeros((hq, dh), dtype), ("heads", None))
        p["bk"] = Annot(jnp.zeros((hkv, dh), dtype), ("kv_heads", None))
        p["bv"] = Annot(jnp.zeros((hkv, dh), dtype), ("kv_heads", None))
    return p


def _qkv(p: dict, x: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    q = jnp.einsum("...d,dhk->...hk", x, p["wq"])
    k = jnp.einsum("...d,dhk->...hk", x, p["wk"])
    v = jnp.einsum("...d,dhk->...hk", x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return q, k, v


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    window: int = 0, q_block: int = 512,
                    kv_block: int = 1024) -> jax.Array:
    """Causal blockwise attention with grouped GQA (kv is NEVER expanded to
    Hq heads).  q: (B, S, Hq, dh); k,v: (B, S, Hkv, dh), Hq % Hkv == 0.

    window > 0 restricts attention to the last `window` positions.
    """
    B, S, Hq, dh = q.shape
    Hkv = k.shape[2]
    g = Hq // Hkv
    qb = min(q_block, S)
    kb = min(kv_block, S)
    assert S % qb == 0 and S % kb == 0, (S, qb, kb)
    nq, nk = S // qb, S // kb
    scale = dh ** -0.5
    qr = (q.reshape(B, nq, qb, Hkv, g, dh).astype(jnp.float32) * scale)
    kr = k.reshape(B, nk, kb, Hkv, dh)
    vr = v.reshape(B, nk, kb, Hkv, dh)

    q_pos = jnp.arange(S).reshape(nq, qb)
    k_pos = jnp.arange(S).reshape(nk, kb)

    def per_q_block(_, qi):
        q_i = qr[:, qi]                       # (B, qb, Hkv, g, dh)
        qp = q_pos[qi]                        # (qb,)
        m0 = jnp.full((B, Hkv, g, qb), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, g, qb), jnp.float32)
        a0 = jnp.zeros((B, Hkv, g, qb, dh), jnp.float32)

        def per_kv_block(carry, kj):
            m, l, acc = carry
            k_j = kr[:, kj].astype(jnp.float32)
            v_j = vr[:, kj].astype(jnp.float32)
            kp = k_pos[kj]
            s = jnp.einsum("bqhgd,bkhd->bhgqk", q_i, k_j)
            mask = qp[:, None] >= kp[None, :]
            if window:
                mask &= (qp[:, None] - kp[None, :]) < window
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            p = jnp.where(mask[None, None, None], p, 0.0)
            l = l * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum("bhgqk,bkhd->bhgqd",
                                                      p, v_j)
            return (m_new, l, acc), None

        (m, l, acc), _ = jax.lax.scan(per_kv_block, (m0, l0, a0),
                                      jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]  # (B,Hkv,g,qb,dh)
        return None, out.transpose(0, 3, 1, 2, 4)     # (B,qb,Hkv,g,dh)

    _, outs = jax.lax.scan(per_q_block, None, jnp.arange(nq))
    # outs: (nq, B, qb, Hkv, g, dh) -> (B, S, Hq, dh)
    return outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, Hq, dh
                                                    ).astype(q.dtype)


def apply_attention(p: dict, x: jax.Array, cfg: ModelConfig,
                    positions: jax.Array) -> jax.Array:
    """Full-sequence (train/prefill) attention.  x: (B, S, d)."""
    q, k, v = _qkv(p, x)
    q = common.apply_rope(q, positions, cfg.rope_theta)
    k = common.apply_rope(k, positions, cfg.rope_theta)
    out = flash_attention(q, k, v, window=cfg.sliding_window)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def prefill_cache(p: dict, x: jax.Array, cache: dict, cfg: ModelConfig,
                  positions: jax.Array) -> dict:
    """Write the (roped) k/v of a full prefill segment into the cache.

    x: (B, S, d); cache arrays (B, S_c, Hkv, dh).  For ring caches only the
    last W positions are written, at their ``pos % W`` slots."""
    _, k, v = _qkv(p, x)
    k = common.apply_rope(k, positions, cfg.rope_theta)
    S = x.shape[1]
    s_c = cache["k"].shape[1]
    writes = {"k": k, "v": v}
    if cfg.kv_quant:
        kq, ks = _quantize(k)
        vq, vs = _quantize(v)
        writes = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    new = {}
    for name, val in writes.items():
        tgt = cache[name]
        if S <= s_c and not cfg.sliding_window:
            new[name] = jax.lax.dynamic_update_slice_in_dim(
                tgt, val.astype(tgt.dtype), 0, axis=1)
        else:
            keep = min(S, s_c)
            slots = jnp.arange(S - keep, S) % s_c
            new[name] = tgt.at[:, slots].set(
                val[:, -keep:].astype(tgt.dtype))
    return new


def chunk_prefill_attention(p: dict, x: jax.Array, cache: dict,
                            cfg: ModelConfig, positions: jax.Array
                            ) -> tuple[jax.Array, dict]:
    """One fixed-shape prefill CHUNK against the decode cache.

    The chunked-admission middle ground between ``apply_attention`` (whole
    sequence, no cache read) and ``decode_attention`` (one token): x is a
    (B, L, d) slice of the prompt whose absolute positions are
    ``positions`` (B, L) — consecutive, continuing wherever the previous
    chunk stopped.  The chunk's roped k/v are scattered into the cache at
    their position slots (ring slots ``pos % S_c`` for sliding-window
    layouts, mirroring ``decode_attention``), and the chunk's queries
    attend the FULL cache under a content-position validity mask, so
    chunk k sees every key chunks 0..k-1 wrote plus its own causal prefix.

    Token identity with whole-prompt prefill holds as long as the ring
    never evicts a position a later query still needs — i.e. for
    sliding-window layouts only while the whole prompt fits the ring
    (prompt_len <= S_c); the serving engine routes longer windowed
    prompts through a one-segment whole-prompt prefill instead.

    Returns (attention output (B, L, d), cache writes dict).
    """
    from repro.partitioning import constrain

    B, L, _ = x.shape
    q, k, v = _qkv(p, x)
    q = common.apply_rope(q, positions, cfg.rope_theta)
    k = common.apply_rope(k, positions, cfg.rope_theta)
    s_c = cache["k"].shape[1]
    w = cfg.sliding_window or 0
    slots = (positions % s_c) if w else positions     # (B, L) write slots
    b_idx = jnp.arange(B)[:, None]

    def dus(name, val):
        tgt = cache[name]
        return tgt.at[b_idx, slots].set(val.astype(tgt.dtype))

    if cfg.kv_quant:
        kq, ks = _quantize(k)
        vq, vs = _quantize(v)
        new_kv = {"k": dus("k", kq), "v": dus("v", vq),
                  "k_scale": dus("k_scale", ks),
                  "v_scale": dus("v_scale", vs)}
    else:
        new_kv = {"k": dus("k", k), "v": dus("v", v)}
    k_cache, v_cache = new_kv["k"], new_kv["v"]

    hkv = cfg.n_kv_heads
    group = cfg.n_heads // hkv
    dh = cfg.resolved_head_dim
    scale = dh ** -0.5
    q5 = q.reshape(B, L, hkv, group, dh)
    q5 = q5.astype(x.dtype if cfg.kv_quant else k_cache.dtype)
    scores = jnp.einsum("blkgd,bskd->bkgls", q5,
                        k_cache.astype(q5.dtype),
                        preferred_element_type=jnp.float32) * scale
    if cfg.kv_quant:
        # per-(token, head) dequant scales fold into the scores, exactly
        # as in decode_attention
        scores = scores * jnp.swapaxes(new_kv["k_scale"], 1, 2)[:, :, None,
                                                                None]
    scores = constrain(scores, ("batch", None, None, None, "cache_seq"))

    # content-position mask: slot j holds the key of absolute position
    # content_pos[j]; a query at qp may attend it iff 0 <= content_pos <=
    # qp (and within the sliding window).  The same formula covers the
    # full layout (content_pos == j for written slots, negative
    # otherwise) and the ring (latest write wins), including the
    # intra-chunk causal half: slots this chunk wrote for positions > qp
    # resolve to content_pos > qp and are masked.
    idx = jnp.arange(s_c)                             # (S_c,)
    p_last = positions[:, -1][:, None]                # (B, 1) chunk end
    written = jnp.mod(p_last - idx[None], s_c) < L    # (B, S_c)
    prev_last = p_last - L                            # end of chunks 0..k-1
    content_pos = jnp.where(
        written, p_last - jnp.mod(p_last - idx[None], s_c),
        prev_last - jnp.mod(prev_last - idx[None], s_c))
    qp = positions[:, :, None]                        # (B, L, 1)
    cp = content_pos[:, None, :]                      # (B, 1, S_c)
    valid = (cp >= 0) & (cp <= qp)                    # (B, L, S_c)
    if w:
        valid &= (qp - cp) < w
    scores = jnp.where(valid[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)           # (B,Hkv,g,L,S_c) f32
    if cfg.kv_quant:
        probs = probs * jnp.swapaxes(new_kv["v_scale"], 1, 2)[:, :, None,
                                                              None]
        out = jnp.einsum("bkgls,bskd->blkgd", probs.astype(x.dtype),
                         v_cache.astype(x.dtype),
                         preferred_element_type=jnp.float32)
    else:
        out = jnp.einsum("bkgls,bskd->blkgd", probs.astype(v_cache.dtype),
                         v_cache, preferred_element_type=jnp.float32)
    out = out.reshape(B, L, cfg.n_heads, dh).astype(x.dtype)
    y = jnp.einsum("blhk,hkd->bld", out, p["wo"])
    return y, new_kv


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------
def init_cache_slot(cfg: ModelConfig, n_groups: int, batch: int,
                    max_seq: int, dtype) -> dict:
    """Annotated zero KV cache for one attention slot, stacked over groups.

    kv_quant stores int8 values + per-(token, kv-head) float scales —
    halving (vs bf16) the cache bytes streamed per decode step."""
    w = cfg.sliding_window or 0
    s_c = min(max_seq, w) if w else max_seq
    shape = (n_groups, batch, s_c, cfg.n_kv_heads, cfg.resolved_head_dim)
    axes = ("layers", "batch", "cache_seq", "kv_heads", None)
    if cfg.kv_quant:
        sshape = shape[:-1]
        saxes = axes[:-1]
        return {"k": Annot(jnp.zeros(shape, jnp.int8), axes),
                "v": Annot(jnp.zeros(shape, jnp.int8), axes),
                "k_scale": Annot(jnp.zeros(sshape, jnp.float32), saxes),
                "v_scale": Annot(jnp.zeros(sshape, jnp.float32), saxes)}
    return {"k": Annot(jnp.zeros(shape, dtype), axes),
            "v": Annot(jnp.zeros(shape, dtype), axes)}


def _quantize(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-(..., head) symmetric int8 quantization over the last dim."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.round(x.astype(jnp.float32) / scale[..., None])
    return q.astype(jnp.int8), scale


def _dequant(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def decode_attention(p: dict, x: jax.Array, cache: dict, pos: jax.Array,
                     cfg: ModelConfig) -> tuple[jax.Array, dict]:
    """One-token cached attention.  x: (B, 1, d); cache slot arrays
    (B, S_c, Hkv, dh); pos: absolute position of this token — a scalar
    (all lanes in lockstep) or a (B,) vector (each lane at its own
    position, the slot-resident continuous-batching engine).
    The scalar case is exactly the vector case with every lane equal, so
    one code path serves both.

    GQA is computed in GROUPED form (q reshaped to (B, Hkv, group, dh)) so
    the kv cache is never expanded to Hq heads — materialising the repeat
    forced XLA to all-gather the whole seq-sharded cache every layer
    (537MB x 2 x 48 layers/token for yi-9b, §Perf iteration B1).  The
    contractions keep the cache dim shard-local; only the (B,Hkv,g,dh)
    output needs a cross-shard sum."""
    from repro.partitioning import constrain

    B = x.shape[0]
    pos_b = jnp.broadcast_to(jnp.asarray(pos), (B,))          # (B,)
    q, k, v = _qkv(p, x)                          # (B,1,h,dh)
    q = common.apply_rope(q, pos_b[:, None], cfg.rope_theta)
    k = common.apply_rope(k, pos_b[:, None], cfg.rope_theta)
    s_c = cache["k"].shape[1]
    w = cfg.sliding_window or 0
    slot_b = (pos_b % s_c) if w else pos_b        # (B,) per-lane write slot

    def dus(name, val):
        tgt = cache[name]
        return tgt.at[jnp.arange(B), slot_b].set(
            val[:, 0].astype(tgt.dtype))

    if cfg.kv_quant:
        kq, ks = _quantize(k)
        vq, vs = _quantize(v)
        new_kv = {"k": dus("k", kq), "v": dus("v", vq),
                  "k_scale": dus("k_scale", ks),
                  "v_scale": dus("v_scale", vs)}
    else:
        new_kv = {"k": dus("k", k), "v": dus("v", v)}
    k_cache, v_cache = new_kv["k"], new_kv["v"]

    hkv = cfg.n_kv_heads
    group = cfg.n_heads // hkv
    dh = cfg.resolved_head_dim
    scale = dh ** -0.5
    q4 = q[:, 0].reshape(B, hkv, group, dh)
    q4 = q4.astype(x.dtype if cfg.kv_quant else k_cache.dtype)
    scores = jnp.einsum("bkgd,bskd->bkgs", q4,
                        k_cache.astype(q4.dtype),
                        preferred_element_type=jnp.float32) * scale
    if cfg.kv_quant:
        # fold the per-(token, head) dequant scales into the scores
        scores = scores * jnp.swapaxes(new_kv["k_scale"], 1, 2)[:, :, None]
    scores = constrain(scores, ("batch", None, None, "cache_seq"))
    idx = jnp.arange(s_c)
    if w:
        # slot j holds absolute position pos - ((pos - j) mod S_c)
        slot_pos = pos_b[:, None] - jnp.mod(pos_b[:, None] - idx[None], s_c)
        valid = slot_pos >= 0                     # (B, S_c)
    else:
        valid = idx[None] <= pos_b[:, None]       # (B, S_c)
    scores = jnp.where(valid[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)        # (B,Hkv,g,S) f32
    if cfg.kv_quant:
        # fold v's dequant scales into the probabilities
        probs = probs * jnp.swapaxes(new_kv["v_scale"], 1, 2)[:, :, None]
        out = jnp.einsum("bkgs,bskd->bkgd", probs.astype(x.dtype),
                         v_cache.astype(x.dtype),
                         preferred_element_type=jnp.float32)
    else:
        out = jnp.einsum("bkgs,bskd->bkgd", probs.astype(v_cache.dtype),
                         v_cache, preferred_element_type=jnp.float32)
    out = out.reshape(B, cfg.n_heads, dh).astype(x.dtype)
    y = jnp.einsum("bhk,hkd->bd", out, p["wo"])[:, None]
    return y, new_kv
