"""RWKV6 (Finch) blocks: time-mix (wkv recurrence with data-dependent decay)
and channel-mix, with both execution plans:

* chunked scan (default) — MobiRNN-style coarse work units over the sequence
  (matmul form within a chunk, state carried across chunks); mirrors the
  Pallas kernel kernels/wkv6.py and is validated against the per-step oracle.
* per-step scan — the fine-grained reference plan (``wkv_step``; decode
  runs the same step as the kernel kernels/wkv_decode.py).

Token-shift state and the (dk x dv) wkv state per head are the recurrent
state buffers of the preallocated decode cache (models/transformer.init_cache).

Packed wkv state.  The cache stores each layer's f32 wkv state with ``p``
heads side by side on the minor axis, ``(B, H/p, dk, p*dv)`` instead of
``(B, H, dk, dv)``: lane ``l`` of packed row ``j`` holds head
``p*j + l // dv``, column ``l % dv`` (pack_state / unpack_state).  A TPU
tiles an f32 array's minor axis in 128 lanes, so a 64-wide head row would
be half padding; ``p = 128 // dv`` fills the tile.  ``p`` comes from the
shapes alone (heads_per_row): ``128 // dv`` when ``dv`` divides 128 and
``p`` divides H, else 1, the plain layout.  Decode works on the packed
rows directly: kernels/wkv_decode.py steps a layer of the stacked pool in
place, and ``wkv_step`` is the same step in jnp.  The full-sequence scans
take ``(B, H, dk, dv)``; the caller converts at their entry and exit
(transformer._apply_block).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.tiling import LANES
from repro.kernels.wkv_decode import wkv_decode
from repro.models import common
from repro.obs import trace as trace_lib
from repro.partitioning import Annot, shard_map

N_MIX = 5  # w, k, v, r, g interpolation vectors


def _w(key, shape, axes, scale, dtype):
    return Annot((jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                              jnp.float32) * scale
                  ).astype(dtype), axes)


def n_heads(cfg: ModelConfig) -> int:
    return cfg.d_model // cfg.ssm.head_dim


def init_tmix(key, cfg: ModelConfig, dtype) -> dict:
    d = cfg.d_model
    r = cfg.ssm.lora_rank
    H, dh = n_heads(cfg), cfg.ssm.head_dim
    ks = jax.random.split(key, 12)
    f32 = jnp.float32
    p = {
        # token-shift interpolation: base mu vectors + data-dependent LoRA
        "maa_x": Annot(jnp.zeros((d,), f32), ("embed_nofsdp",)),
        "maa": Annot(jnp.zeros((N_MIX, d), f32), (None, "embed_nofsdp")),
        "tm_w1": _w(ks[0], (d, N_MIX * 32), ("embed", None), d ** -0.5, f32),
        "tm_w2": _w(ks[1], (N_MIX, 32, d), (None, None, "embed"), 32 ** -0.5, f32),
        # data-dependent decay: w0 + LoRA(xw)
        "w0": Annot(jnp.linspace(-6.0, -0.3, d, dtype=f32), ("embed_nofsdp",)),
        "td_w1": _w(ks[2], (d, r), ("embed", None), d ** -0.5, f32),
        "td_w2": _w(ks[3], (r, d), (None, "embed"), r ** -0.5, f32),
        # projections
        "wr": _w(ks[4], (d, d), ("embed", "mlp"), d ** -0.5, dtype),
        "wk": _w(ks[5], (d, d), ("embed", "mlp"), d ** -0.5, dtype),
        "wv": _w(ks[6], (d, d), ("embed", "mlp"), d ** -0.5, dtype),
        "wg": _w(ks[7], (d, d), ("embed", "mlp"), d ** -0.5, dtype),
        "wo": _w(ks[8], (d, d), ("mlp", "embed"), d ** -0.5, dtype),
        # per-head bonus u
        "u": Annot(jnp.zeros((H, dh), f32), ("heads", None)),
        "gn": common.init_groupnorm(H, d, f32),
    }
    return p


def _ddlerp(p: dict, x: jax.Array, sx: jax.Array) -> tuple[jax.Array, ...]:
    """Data-dependent token-shift interpolation (rwkv6 'ddlerp')."""
    B, S, d = x.shape
    xxx = x + sx * p["maa_x"]
    lora = jnp.tanh(xxx @ p["tm_w1"]).reshape(B, S, N_MIX, 32)
    # f32 weights, f32 product: at the TPU's default precision XLA rounds
    # the whole (n_groups, 5, 32, d) stack to bf16 once, outside the
    # decode layer scan, and then moves that 26 MB copy in and out of
    # VMEM every layer (PERF.md §6)
    mixes = jnp.einsum("bsnr,nrd->nbsd", lora, p["tm_w2"],
                       precision="highest")                  # (5,B,S,d)
    outs = []
    for i in range(N_MIX):
        outs.append(x + sx * (p["maa"][i] + mixes[i]))
    return tuple(outs)  # xw, xk, xv, xr, xg


def _project(p: dict, cfg: ModelConfig, x: jax.Array, x_prev: jax.Array):
    """Common head: token shift + ddlerp + projections.

    x: (B,S,d); x_prev: (B,d) last token of the previous segment.
    Returns r,k,v,g (B,S,H,*), logw (B,S,H,dk), new shift state (B,d).
    """
    B, S, d = x.shape
    H, dh = n_heads(cfg), cfg.ssm.head_dim
    shifted = jnp.concatenate([x_prev[:, None], x[:, :-1]], axis=1)
    sx = shifted - x
    xw, xk, xv, xr, xg = _ddlerp(p, x.astype(jnp.float32),
                                 sx.astype(jnp.float32))
    dt = x.dtype
    r = (xr.astype(dt) @ p["wr"]).reshape(B, S, H, dh)
    k = (xk.astype(dt) @ p["wk"]).reshape(B, S, H, dh)
    v = (xv.astype(dt) @ p["wv"]).reshape(B, S, H, dh)
    g = jax.nn.silu(xg.astype(dt) @ p["wg"])
    w = p["w0"] + jnp.tanh(xw @ p["td_w1"]) @ p["td_w2"]      # (B,S,d) f32
    logw = -jnp.exp(w.reshape(B, S, H, dh))                   # <= 0
    return r, k, v, g, logw, x[:, -1]


def wkv_chunked(r, k, v, logw, u, state, chunk: int):
    """Batched chunked wkv scan.  r,k,logw: (B,S,H,dk); v: (B,S,H,dv);
    u: (H,dk); state: (B,H,dk,dv).  Returns (out, state')."""
    B, S, H, dk = r.shape
    dv = v.shape[-1]
    assert S % chunk == 0, (S, chunk)
    n = S // chunk
    f32 = jnp.float32

    def to_chunks(a):
        return a.reshape(B, n, chunk, H, -1).transpose(1, 0, 3, 2, 4)

    rc, kc, vc, wc = map(to_chunks, (r, k, v, logw))  # (n,B,H,C,*)
    mask = (jnp.arange(chunk)[:, None] > jnp.arange(chunk)[None, :])

    def step(s, xs):
        rr, kk, vv, ww = (a.astype(f32) for a in xs)   # (B,H,C,*)
        L = jnp.cumsum(ww, axis=2)
        L_prev = L - ww
        out = jnp.einsum("bhck,bhkv->bhcv", rr * jnp.exp(L_prev), s)
        # mask the exponent, not the scores: j >= i entries are positive
        # and would overflow exp under strong decay, NaN-ing the VJP
        diff = L_prev[:, :, :, None, :] - L[:, :, None, :, :]
        diff = jnp.exp(jnp.where(mask[..., None], diff, -jnp.inf))
        scores = jnp.einsum("bhik,bhjk,bhijk->bhij", rr, kk, diff)
        out = out + jnp.einsum("bhij,bhjv->bhiv", scores, vv)
        bonus = jnp.einsum("bhck,hk,bhck->bhc", rr, u.astype(f32), kk)
        out = out + bonus[..., None] * vv
        L_last = L[:, :, -1]
        decay_j = jnp.exp(L_last[:, :, None, :] - L)
        s_new = (jnp.exp(L_last)[..., None] * s
                 + jnp.einsum("bhck,bhcv->bhkv", kk * decay_j, vv))
        return s_new, out

    state, outs = jax.lax.scan(step, state.astype(f32), (rc, kc, vc, wc))
    out = outs.transpose(1, 0, 3, 2, 4).reshape(B, S, H, dv)
    return out, state


def heads_per_row(cfg: ModelConfig) -> int:
    """Heads the cache packs side by side on one 128-lane state row."""
    H, dv = n_heads(cfg), cfg.ssm.head_dim
    if dv < LANES and LANES % dv == 0 and H % (LANES // dv) == 0:
        return LANES // dv
    return 1


def pack_state(s: jax.Array, p: int) -> jax.Array:
    """(..., H, dk, dv) -> (..., H/p, dk, p*dv), heads ``p*j .. p*j+p-1``
    side by side on packed row ``j``."""
    if p == 1:
        return s
    *lead, H, dk, dv = s.shape
    s = jnp.swapaxes(s.reshape(*lead, H // p, p, dk, dv), -3, -2)
    return s.reshape(*lead, H // p, dk, p * dv)


def unpack_state(s: jax.Array, p: int) -> jax.Array:
    """Inverse of pack_state: (..., H/p, dk, p*dv) -> (..., H, dk, dv)."""
    if p == 1:
        return s
    *lead, Hp, dk, pdv = s.shape
    s = jnp.swapaxes(s.reshape(*lead, Hp, dk, p, pdv // p), -3, -2)
    return s.reshape(*lead, Hp * p, dk, pdv // p)


class PoolLayer(NamedTuple):
    """Layer ``index`` of the stacked packed wkv pool (n_groups, B, ...),
    which decode steps in place; lanes with ``active`` False keep their
    state."""
    pool: jax.Array
    index: jax.Array
    active: jax.Array


def record_layout(cfg: ModelConfig, pool_leaf) -> None:
    """``plan/state_layout`` trace event naming the wkv state layout a
    decode plan runs on.  Fires at trace time: once per compilation."""
    tracer = trace_lib.get_tracer()
    if tracer.enabled:
        tracer.event("plan/state_layout", family="rwkv6",
                     heads_per_row=heads_per_row(cfg),
                     shape=list(pool_leaf.shape),
                     bytes=pool_leaf.size * pool_leaf.dtype.itemsize)


def _over_rows(a: jax.Array, p: int, dv: int) -> jax.Array:
    """Per-head vectors (..., H, dk) laid over the packed rows:
    (..., H/p, dk, p*dv), lane ``l`` of row ``j`` holding head
    ``p*j + l // dv``'s vector, by a select on a lane iota."""
    *lead, H, dk = a.shape
    a = a.reshape(*lead, H // p, p, dk, 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (p * dv,), 0) // dv
    out = a[..., p - 1, :, :]
    for q in range(p - 1):
        out = jnp.where(head == q, a[..., q, :, :], out)
    return out


def wkv_step(r, k, v, logw, u, state):
    """Single decode step.  r,k,logw: (B,H,dk); v: (B,H,dv); u: (H,dk);
    state: (B,H/p,dk,p*dv), packed as pack_state lays it out, with ``p``
    read from its minor axis (an unpacked (B,H,dk,dv) state is p = 1).
    Returns out (B,H,dv) and the new state in the same layout."""
    f32 = jnp.float32
    B, H, dv = v.shape
    p = state.shape[-1] // dv
    r4, k4, w4 = (_over_rows(a, p, dv) for a in (
        r.astype(f32), k.astype(f32), jnp.exp(logw.astype(f32))))
    u4 = _over_rows(u.astype(f32), p, dv)
    kv = k4 * v.astype(f32).reshape(B, H // p, 1, p * dv)
    out = jnp.sum(r4 * (state + u4 * kv), axis=-2)     # (B,H/p,p*dv)
    return out.reshape(B, H, dv), w4 * state + kv


def apply_tmix(p: dict, cfg: ModelConfig, x: jax.Array, x_prev: jax.Array,
               state: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Full-sequence time-mix.  Returns (out, shift', state').

    Under an active sharding-rules context with cfg.seq_shard and a >1
    'model' axis, the sequence-parallel pipeline (_apply_tmix_seqpar) runs:
    the residual stream stays sequence-sharded and the wkv recurrence is
    distributed with an affine-prefix exchange — the MobiRNN wavefront
    across chips."""
    from repro import partitioning as pt

    B, S, d = x.shape
    if cfg.seq_shard and pt._ACTIVE_RULES:
        rules = pt._ACTIVE_RULES[-1]
        m = rules.mesh.shape.get("model", 1)
        if m > 1 and S % m == 0 and (S // m) >= 4:
            return _apply_tmix_seqpar(p, cfg, x, x_prev, state, rules)
    return _apply_tmix_local(p, cfg, x, x_prev, state)


#: default core/plans.RWKV_PLANS plan for the full-sequence scan — the
#: registry is the single decision table for which wkv execution runs
#: ("chunked_xla" wraps wkv_chunked below; "chunked_scan" is the fused
#: Pallas kernel; "stepwise" the per-step oracle).  Override per call via
#: ``_apply_tmix_local(..., plan=...)`` or globally for experiments.
WKV_PLAN = "chunked_xla"


def _apply_tmix_local(p, cfg, x, x_prev, state, plan: str | None = None):
    from repro.core import plans as plans_lib

    B, S, d = x.shape
    H = n_heads(cfg)
    r, k, v, g, logw, shift = _project(p, cfg, x, x_prev)
    wkv_fn = plans_lib.RWKV_PLANS[plan or WKV_PLAN]
    out, state = wkv_fn(r, k, v, logw, p["u"], state, chunk=cfg.ssm.chunk)
    out = common.apply_groupnorm(p["gn"], out.reshape(B, S, d), H)
    out = (out.astype(x.dtype) * g) @ p["wo"]
    return out, shift, state


def _apply_tmix_seqpar(p: dict, cfg: ModelConfig, x: jax.Array,
                       x_prev: jax.Array, state: jax.Array, rules
                       ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Sequence-parallel time-mix.

    Activations arrive sequence-sharded over the 'model' axis.  Everything
    per-token (ddlerp, projections, groupnorm, gating) is shard-local; the
    only cross-chip parts are

      1. token shift: the last token of shard i is the shift input of
         shard i+1 — one (B, d) collective-permute;
      2. the wkv state carry: the per-shard scan summary is affine in the
         incoming state, ``S_out = D ⊙ S_in + A`` with D = exp(Σ logw) and
         A = scan-from-zero final state, so the global recurrence is an
         exclusive prefix over shards of affine maps — computed with
         ceil(log2(m)) Hillis-Steele collective-permute rounds of
         (B, H, dk, dv)-sized pairs;
      3. one correction matmul folding the incoming state into the local
         outputs: out_t += (r_t ⊙ exp(L_prev,t)) @ S_in.

    vs. the XLA-derived tensor-parallel layout this removes ~14 full
    (B, S, d) all-gathers/all-reduces per layer (§Perf iteration C1).
    """
    from jax.sharding import PartitionSpec as P

    mesh = rules.mesh
    m_size = mesh.shape["model"]
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    B, S, d = x.shape
    H, dh = n_heads(cfg), cfg.ssm.head_dim

    x_spec = rules.spec_for(("batch", "seq_model", None), x.shape)
    bvec_spec = rules.spec_for(("batch", None), (B, d))
    st_spec = rules.spec_for(("batch", None, None, None), state.shape)
    p_spec = jax.tree.map(lambda _: P(), p)

    def local_fn(x_loc, x_prev_g, s0_g, p_loc):
        idx = jax.lax.axis_index("model")
        B_loc, S_loc = x_loc.shape[0], x_loc.shape[1]
        # --- 1. token shift across the shard boundary ------------------
        last = x_loc[:, -1]
        recv = jax.lax.ppermute(last, "model",
                                [(i, (i + 1) % m_size)
                                 for i in range(m_size)])
        xp = jnp.where(idx == 0, x_prev_g.astype(x_loc.dtype), recv)
        r, k, v, g, logw, _ = _project(p_loc, cfg, x_loc, xp)

        # --- 2. local scan from zero + affine summary ------------------
        chunk = cfg.ssm.chunk
        while S_loc % chunk:
            chunk -= 1
        zero = jnp.zeros((B_loc, H, dh, dh), jnp.float32)
        out0, a_loc = wkv_chunked(r, k, v, logw, p_loc["u"], zero, chunk)
        d_loc = jnp.exp(jnp.sum(logw.astype(jnp.float32), axis=1))  # B,H,dk

        # inclusive Hillis-Steele prefix of (D, A) over the model axis
        d_agg, a_agg = d_loc, a_loc
        shift_amt = 1
        while shift_amt < m_size:
            perm = [(i, i + shift_amt) for i in range(m_size - shift_amt)]
            d_r = jax.lax.ppermute(d_agg, "model", perm)
            a_r = jax.lax.ppermute(a_agg, "model", perm)
            has = idx >= shift_amt
            # compose: earlier segment (recv) then mine:
            #   D = D_mine * D_recv ; A = D_mine ⊙ A_recv + A_mine
            d_new = jnp.where(has, d_agg * d_r, d_agg)
            a_new = jnp.where(has, d_agg[..., None] * a_r + a_agg, a_agg)
            d_agg, a_agg = d_new, a_new
            shift_amt *= 2
        # exclusive prefix = inclusive of shard i-1 (shard 0: global s0)
        perm1 = [(i, i + 1) for i in range(m_size - 1)]
        a_excl = jax.lax.ppermute(a_agg, "model", perm1)
        d_excl = jax.lax.ppermute(d_agg, "model", perm1)
        s0 = s0_g.astype(jnp.float32)
        s_in = jnp.where(idx == 0, s0,
                         a_excl + d_excl[..., None] * s0)

        # --- 3. fold the carry into local outputs ----------------------
        lw32 = logw.astype(jnp.float32)
        l_prev = jnp.cumsum(lw32, axis=1) - lw32          # (B,S,H,dk)
        carry = jnp.einsum("bshk,bhkv->bshv",
                           r.astype(jnp.float32) * jnp.exp(l_prev), s_in)
        out = out0 + carry

        # final state (replicated): inclusive aggregate of the last shard
        s_fin = a_agg + d_agg[..., None] * s0
        s_fin = jnp.where(idx == m_size - 1, s_fin, jnp.zeros_like(s_fin))
        s_fin = jax.lax.psum(s_fin, "model")
        # shift state = globally-last token (replicated)
        shift = jnp.where(idx == m_size - 1, x_loc[:, -1],
                          jnp.zeros_like(x_loc[:, -1]))
        shift = jax.lax.psum(shift, "model")

        out = common.apply_groupnorm(p_loc["gn"],
                                     out.reshape(B_loc, S_loc, d), H)
        out = (out.astype(x_loc.dtype) * g) @ p_loc["wo"]
        return out, shift.astype(x_loc.dtype), s_fin

    fn = shard_map(
        local_fn, mesh=mesh,
        in_specs=(x_spec, bvec_spec, st_spec, p_spec),
        out_specs=(x_spec, bvec_spec, st_spec))
    return fn(x, x_prev, state, p)


def step_tmix(p: dict, cfg: ModelConfig, x: jax.Array, x_prev: jax.Array,
              layer: PoolLayer) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-token time-mix.  x: (B,1,d).  Steps ``layer`` of the packed
    pool in place (kernels/wkv_decode.py) and returns (out, shift', pool')."""
    B, _, d = x.shape
    H = n_heads(cfg)
    record_layout(cfg, layer.pool)
    r, k, v, g, logw, shift = _project(p, cfg, x, x_prev)
    out, pool = wkv_decode(layer.pool, layer.index, layer.active, r[:, 0],
                           k[:, 0], logw[:, 0], v[:, 0], p["u"])
    out = common.apply_groupnorm(p["gn"], out.reshape(B, 1, d), H)
    out = (out.astype(x.dtype) * g) @ p["wo"]
    return out, shift, pool


# ---------------------------------------------------------------------------
# Channel-mix
# ---------------------------------------------------------------------------
def init_cmix(key, cfg: ModelConfig, dtype) -> dict:
    d, ff = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "mu_k": Annot(jnp.zeros((d,), jnp.float32), ("embed_nofsdp",)),
        "mu_r": Annot(jnp.zeros((d,), jnp.float32), ("embed_nofsdp",)),
        "wk": _w(ks[0], (d, ff), ("embed", "mlp"), d ** -0.5, dtype),
        "wv": _w(ks[1], (ff, d), ("mlp", "embed"), ff ** -0.5, dtype),
        "wr": _w(ks[2], (d, d), ("embed", "mlp"), d ** -0.5, dtype),
    }


def apply_cmix(p: dict, x: jax.Array, x_prev: jax.Array
               ) -> tuple[jax.Array, jax.Array]:
    """Channel-mix with token shift.  x: (B,S,d); x_prev: (B,d)."""
    shifted = jnp.concatenate([x_prev[:, None], x[:, :-1]], axis=1)
    sx = (shifted - x).astype(x.dtype)
    xk = x + sx * p["mu_k"].astype(x.dtype)
    xr = x + sx * p["mu_r"].astype(x.dtype)
    k = jnp.square(jax.nn.relu(xk @ p["wk"]))
    out = jax.nn.sigmoid(xr @ p["wr"]) * (k @ p["wv"])
    return out, x[:, -1]
