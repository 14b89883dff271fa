"""Plain reference of RWKV6 (Finch) [arXiv:2404.05892], and the weights
the benchmark serves, drawn from the seed.

Written from the architecture's equations in straightforward
``jax.numpy``, float32 at "highest" matmul precision, with the wkv
recurrence as a per-token scan (no chunking, no kernels, no batching
tricks).  It imports nothing of the program under test.

Per layer, on the residual stream ``x``::

    h   = LN1(x);  sx = shift(h) - h                      # token shift
    x_* = h + sx * (maa_* + LoRA_mix(h + sx * maa_x)_*)   # * in w,k,v,r,g
    r, k, v = x_r Wr, x_k Wk, x_v Wv;  g = silu(x_g Wg)
    decay = exp(-exp(w0 + tanh(x_w A) B))                 # per channel
    o_t = r_t (S + u (k_t v_t^T));  S <- decay_t S + k_t v_t^T   # per head
    x  += (GroupNorm_heads(o) * g) Wo
    h2  = LN2(x);  sx2 = shift(h2) - h2
    x  += sigmoid((h2 + sx2 mu_r) Wr') * (relu((h2 + sx2 mu_k) Wk')^2 Wv')

then ``logits = LN(x) W_head``.  Norms use eps 1e-5 (the group norm too,
as the system under test does; the published model divides by a head
size factor there instead).

``logit_gaps`` runs the model over whole sequences in blocks of rows,
carrying the recurrent state from block to block, and returns how far
below the reference's best logit each given token lies.  With
``quantized=True`` it is the control instead: the same model with every
weight matrix rounded to int8 per output channel and activations in
bfloat16, and the gap read is that of the token the control puts first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

N_MIX = 5
EPS = 1e-5


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------
def _tn(key, shape, scale):
    return jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                       jnp.float32) * scale


def make_weights(m: dict, init: dict, key: jax.Array) -> dict:
    """Every weight from ``key``, stacked over layers, in the dtype it is
    served in (``m['dtype']`` for the large matrices, float32 for the
    vectors, norms and LoRA factors).  Call under ``jax.jit``."""
    d, ff, V, L = m["d_model"], m["d_ff"], m["vocab"], m["n_layers"]
    r, mr = m["lora_rank"], m["mix_lora_rank"]
    H, dh = d // m["head_dim"], m["head_dim"]
    dt = jnp.dtype(m["dtype"])
    f32 = jnp.float32
    lora = init["lora_scale"]
    out = init["out_scale"]
    ks = iter(jax.random.split(key, 32))

    def uni(shape, lo=0.0, hi=1.0):
        return jax.random.uniform(next(ks), shape, f32, lo, hi)

    def norm():
        return {"scale": jnp.ones((L, d), f32), "bias": jnp.zeros((L, d), f32)}

    w0 = jnp.broadcast_to(jnp.linspace(-6.0, -0.3, d, dtype=f32), (L, d))
    mix = {
        "maa_x": uni((L, d)),
        "maa": uni((L, N_MIX, d)),
        "tm_w1": _tn(next(ks), (L, d, N_MIX * mr), lora * d ** -0.5),
        "tm_w2": _tn(next(ks), (L, N_MIX, mr, d), lora * mr ** -0.5),
        "w0": w0 + uni((L, d), -0.5, 0.5),
        "td_w1": _tn(next(ks), (L, d, r), lora * d ** -0.5),
        "td_w2": _tn(next(ks), (L, r, d), lora * r ** -0.5),
        "wr": _tn(next(ks), (L, d, d), d ** -0.5).astype(dt),
        "wk": _tn(next(ks), (L, d, d), d ** -0.5).astype(dt),
        "wv": _tn(next(ks), (L, d, d), d ** -0.5).astype(dt),
        "wg": _tn(next(ks), (L, d, d), d ** -0.5).astype(dt),
        "wo": _tn(next(ks), (L, d, d), out * d ** -0.5).astype(dt),
        "u": uni((L, H, dh), -0.5, 0.5),
        "gn": norm(),
    }
    mlp = {
        "mu_k": uni((L, d)),
        "mu_r": uni((L, d)),
        "wk": _tn(next(ks), (L, d, ff), d ** -0.5).astype(dt),
        "wv": _tn(next(ks), (L, ff, d), out * ff ** -0.5).astype(dt),
        "wr": _tn(next(ks), (L, d, d), d ** -0.5).astype(dt),
    }
    return {
        "embed": (jax.random.normal(next(ks), (V, d), f32)
                  * init["embed_std"]).astype(dt),
        "blocks": {"ln1": norm(), "mix": mix, "ln2": norm(), "mlp": mlp},
        "final_norm": {"scale": jnp.ones((d,), f32),
                       "bias": jnp.zeros((d,), f32)},
        "lm_head": _tn(next(ks), (d, V), d ** -0.5).astype(dt),
    }


def to_program(w: dict) -> dict:
    """The same arrays in the parameter layout of the system under test
    (one period slot of stacked layers; the head as a linear layer)."""
    return {"embed": w["embed"], "blocks": (w["blocks"],),
            "final_norm": w["final_norm"], "lm_head": {"w": w["lm_head"]}}


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------
def _ln(x, p):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + EPS) * p["scale"] + p["bias"]


def _q8(w, kind):
    """A weight matrix rounded per output channel (last axis) to int8, or
    to float8 e4m3 scaled to its range, dequantized to bf16."""
    w = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    if kind == "fp8":
        scale = jnp.where(amax == 0, 1.0, amax / 448.0)
        q = (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return (q * scale).astype(jnp.bfloat16)
    scale = jnp.where(amax == 0, 1.0, amax / 127.0)
    q = jnp.clip(jnp.round(w / scale), -127, 127)
    return (q * scale).astype(jnp.bfloat16)


#: the large matrices of a block (time mix and channel mix)
MATRICES = ("wr", "wk", "wv", "wg", "wo")


def rounded(w: dict, kind: str) -> dict:
    """The served weights with every large matrix (each block's
    projections and the head) rounded per output channel to the ``kind``
    grid, "int8" or "fp8", and held in bf16: weight-only lower precision,
    as the control puts it in the program's place."""
    q = jax.jit(functools.partial(_q8, kind=kind))

    def mats(t):
        return {k: (q(v) if k in MATRICES else v) for k, v in t.items()}

    b = w["blocks"]
    return dict(w, blocks=dict(b, mix=mats(b["mix"]), mlp=mats(b["mlp"])),
                lm_head=q(w["lm_head"]))


def _mm(x, w, quantized):
    if quantized:        # bf16 in, bf16 out, as the served model computes
        y = jnp.matmul(x.astype(jnp.bfloat16), _q8(w, quantized))
        return y.astype(jnp.float32)
    return x @ w.astype(jnp.float32)


def _round(x, quantized):
    """The control keeps activations in bfloat16 between operations."""
    return x.astype(jnp.bfloat16).astype(jnp.float32) if quantized else x


def _layer(m, quantized, x, xs):
    p, st = xs
    B, T, d = x.shape
    H, dh = d // m["head_dim"], m["head_dim"]
    mix, mlp = p["mix"], p["mlp"]
    mm = functools.partial(_mm, quantized=quantized)

    h = _round(_ln(x, p["ln1"]), quantized)
    sx = jnp.concatenate([st["shift_t"][:, None], h[:, :-1]], 1) - h
    lora = jnp.tanh((h + sx * mix["maa_x"]) @ mix["tm_w1"])
    lora = lora.reshape(B, T, N_MIX, -1)
    mixes = jnp.einsum("btnr,nrd->nbtd", lora, mix["tm_w2"])
    xw, xk, xv, xr, xg = (h + sx * (mix["maa"][i] + mixes[i])
                          for i in range(N_MIX))
    r = mm(xr, mix["wr"]).reshape(B, T, H, dh)
    k = mm(xk, mix["wk"]).reshape(B, T, H, dh)
    v = mm(xv, mix["wv"]).reshape(B, T, H, dh)
    g = jax.nn.silu(mm(xg, mix["wg"]))
    w = mix["w0"] + jnp.tanh(xw @ mix["td_w1"]) @ mix["td_w2"]
    decay = jnp.exp(-jnp.exp(w)).reshape(B, T, H, dh)
    u = mix["u"]

    def step(s, inp):
        r_t, k_t, v_t, d_t = inp                       # (B, H, dh)
        kv = k_t[..., :, None] * v_t[..., None, :]
        o = jnp.einsum("bhk,bhkv->bhv", r_t, s + u[..., None] * kv,
                       precision="highest")
        return d_t[..., None] * s + kv, o

    tm = lambda a: jnp.swapaxes(a, 0, 1)
    s, o = jax.lax.scan(step, st["wkv"], (tm(r), tm(k), tm(v), tm(decay)))
    o = tm(o)                                          # (B, T, H, dh)
    mu = jnp.mean(o, -1, keepdims=True)
    var = jnp.mean(jnp.square(o - mu), -1, keepdims=True)
    o = ((o - mu) * jax.lax.rsqrt(var + EPS)).reshape(B, T, d)
    o = o * p["mix"]["gn"]["scale"] + p["mix"]["gn"]["bias"]
    x = _round(x + mm(_round(o, quantized) * g, mix["wo"]), quantized)

    h2 = _round(_ln(x, p["ln2"]), quantized)
    sx2 = jnp.concatenate([st["shift_c"][:, None], h2[:, :-1]], 1) - h2
    kk = jnp.square(jax.nn.relu(mm(h2 + sx2 * mlp["mu_k"], mlp["wk"])))
    rr = jax.nn.sigmoid(mm(h2 + sx2 * mlp["mu_r"], mlp["wr"]))
    x = _round(x + rr * mm(_round(kk, quantized), mlp["wv"]), quantized)
    return x, {"shift_t": h[:, -1], "wkv": s, "shift_c": h2[:, -1]}


def zero_state(m: dict, batch: int) -> dict:
    d, L = m["d_model"], m["n_layers"]
    H, dh = d // m["head_dim"], m["head_dim"]
    f32 = jnp.float32
    return {"shift_t": jnp.zeros((L, batch, d), f32),
            "wkv": jnp.zeros((L, batch, H, dh, dh), f32),
            "shift_c": jnp.zeros((L, batch, d), f32)}


def logits_block(m: dict, quantized: bool, w: dict, state: dict,
                 tokens: jax.Array):
    """Logits (B, T, V) of one block of rows, continuing from ``state``."""
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    keep = {"wr", "wk", "wv", "wg", "wo"} if quantized else set()

    def body(x, xs):
        # one layer's weights at a time, so float32 copies never pile up
        p, st = xs
        p = dict(f32({k: v for k, v in p.items() if k not in ("mix", "mlp")}),
                 mix={k: (v if k in keep else f32(v))
                      for k, v in p["mix"].items()},
                 mlp={k: (v if k in keep else f32(v))
                      for k, v in p["mlp"].items()})
        return _layer(m, quantized, x, (p, st))

    x, state = jax.lax.scan(body, x, (w["blocks"], state))
    x = _round(_ln(x, f32(w["final_norm"])), quantized)
    return _mm(x, w["lm_head"], quantized), state


@functools.partial(jax.jit, static_argnums=(0, 1))
def _gap_block(m_items, control, w, st_ref, st_ctl, tokens, targets):
    m = dict(m_items)
    with jax.default_matmul_precision("highest"):
        ref, st_ref = logits_block(m, None, w, st_ref, tokens)
    best = jnp.max(ref, axis=-1)
    if control:
        with jax.default_matmul_precision("default"):
            ctl, st_ctl = logits_block(m, control, w, st_ctl, tokens)
        targets = jnp.where(targets >= 0,
                            jnp.argmax(ctl, axis=-1).astype(jnp.int32), -1)
    chosen = jnp.take_along_axis(ref, jnp.maximum(targets, 0)[..., None],
                                 axis=-1)[..., 0]
    gap = jnp.where(targets >= 0, best - chosen, 0.0)
    return jnp.stack([gap, best]), st_ref, st_ctl


def logit_gaps(m: dict, w: dict, seqs: list[np.ndarray],
               targets: list[np.ndarray], block: int,
               control: str | None = None
               ) -> tuple[list[np.ndarray], list[np.ndarray]]:
    """For every sequence, at each position whose ``targets`` entry is
    >= 0: the gap, the reference's best logit minus its logit for that
    target (or, with ``control`` "fp8" or "int8", for the token that
    control puts first there), and the best logit itself.  Sequences run
    together, right-padded to a whole number of blocks; the recurrent
    state carries over.  Returns (gaps, best logits), one array each per
    sequence."""
    B = len(seqs)
    n = max(len(s) for s in seqs)
    n = -(-n // block) * block
    toks = np.zeros((B, n), np.int32)
    tgts = np.full((B, n), -1, np.int32)
    for i, (s, t) in enumerate(zip(seqs, targets)):
        toks[i, :len(s)] = s
        tgts[i, :len(t)] = t
    key = tuple(sorted((k, v) for k, v in m.items()
                       if isinstance(v, (int, str))))
    st_ref = zero_state(m, B)
    st_ctl = zero_state(m, B)
    out = []
    for a in range(0, n, block):
        g, st_ref, st_ctl = _gap_block(key, control, w, st_ref, st_ctl,
                                       toks[:, a:a + block],
                                       tgts[:, a:a + block])
        out.append(np.asarray(g))
    gaps, best = np.concatenate(out, axis=2)
    keep = [(i, t >= 0) for i, t in enumerate(targets)]
    return ([gaps[i, :len(k)][k] for i, k in keep],
            [best[i, :len(k)][k] for i, k in keep])
