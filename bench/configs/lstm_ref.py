"""Plain reference of the paper's stacked LSTM classifier (MobiRNN,
arXiv:1706.00878 §4.1), the weights the benchmark serves, and the
sensor windows it classifies.

Per time step and layer (gate order i, f, g, o)::

    [i f g o] = [x_t, h] W + b
    c = sigmoid(f) c + sigmoid(i) tanh(g);   h = sigmoid(o) tanh(c)

the next layer's input is this layer's ``h``; the logits are the last
layer's final ``h`` times the head.  Float32 at "highest" matmul
precision, a scan over time; it imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def make_weights(m: dict, key: jax.Array) -> dict:
    """Per-layer fused gate weights (in + H, 4H) and biases (forget gate
    +1), and the classifier head, in the configuration's dtype."""
    H, L, C = m["hidden"], m["n_layers"], m["n_classes"]
    dt = jnp.dtype(m["dtype"])
    ks = jax.random.split(key, L + 1)
    layers = []
    for i in range(L):
        fan = (m["input_dim"] if i == 0 else H) + H
        w = jax.random.truncated_normal(ks[i], -2.0, 2.0, (fan, 4 * H),
                                        jnp.float32) * fan ** -0.5
        b = jnp.zeros((4 * H,), jnp.float32).at[H:2 * H].set(1.0)
        layers.append({"w": w.astype(dt), "b": b.astype(dt)})
    head = jax.random.truncated_normal(ks[-1], -2.0, 2.0, (H, C),
                                       jnp.float32) * H ** -0.5
    return {"layers": layers,
            "head": {"w": head.astype(dt), "b": jnp.zeros((C,), dt)}}


@jax.jit
def logits(w: dict, x: jax.Array) -> jax.Array:
    """x: (B, T, input_dim) -> logits (B, n_classes), float32."""
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        layers = [{k: v.astype(f32) for k, v in p.items()}
                  for p in w["layers"]]
        B = x.shape[0]
        H = layers[0]["w"].shape[1] // 4
        zero = jnp.zeros((len(layers), B, H), f32)

        def step(carry, x_t):
            c, h = carry
            inp = x_t
            cs, hs = [], []
            for i, p in enumerate(layers):
                gates = jnp.concatenate([inp, h[i]], -1) @ p["w"] + p["b"]
                gi, gf, gg, go = jnp.split(gates, 4, axis=-1)
                ci = jax.nn.sigmoid(gf) * c[i] + (jax.nn.sigmoid(gi)
                                                  * jnp.tanh(gg))
                hi = jax.nn.sigmoid(go) * jnp.tanh(ci)
                cs.append(ci)
                hs.append(hi)
                inp = hi
            return (jnp.stack(cs), jnp.stack(hs)), None

        (c, h), _ = jax.lax.scan(step, (zero, zero),
                                 jnp.swapaxes(x.astype(f32), 0, 1))
        return h[-1] @ w["head"]["w"].astype(f32) + w["head"]["b"].astype(f32)


# ---------------------------------------------------------------------------
# Sensor windows: UCI-HAR-shaped synthetic activity signals (128 readings
# of 9 channels at 50 Hz: body acceleration, gyroscope, total
# acceleration), a class-conditional fundamental, amplitude, gravity
# orientation and noise floor per activity.
# ---------------------------------------------------------------------------
#: per class: (fundamental Hz, amplitude, noise, gravity xyz)
PROFILE = (
    (2.0, 1.00, 0.25, (0.0, 0.0, 1.0)),     # walking
    (1.6, 1.20, 0.30, (0.2, 0.0, 0.95)),    # upstairs
    (2.3, 1.35, 0.35, (-0.2, 0.0, 0.95)),   # downstairs
    (0.0, 0.08, 0.10, (0.5, 0.5, 0.70)),    # sitting
    (0.0, 0.05, 0.08, (0.0, 0.0, 1.0)),     # standing
    (0.0, 0.04, 0.06, (0.0, 1.0, 0.05)),    # laying
)


def windows(rng: np.random.Generator, n: int, seq_len: int = 128,
            channels: int = 9) -> np.ndarray:
    """``n`` windows (n, seq_len, channels) float32 drawn from ``rng``."""
    prof = np.array([p[:3] for p in PROFILE])
    grav = np.array([p[3] for p in PROFILE])
    y = rng.integers(0, len(PROFILE), n)
    f0, amp, noise = prof[y, 0], prof[y, 1], prof[y, 2]
    f = f0 * rng.uniform(0.85, 1.15, n)
    phase = rng.uniform(0, 2 * np.pi, n)
    t = np.arange(seq_len) / 50.0
    arg = 2 * np.pi * f[:, None] * t[None] + phase[:, None]
    x = np.zeros((n, seq_len, channels))
    moving = (f > 0)[:, None]
    for c in range(3):
        x[:, :, c] = moving * amp[:, None] * (
            np.sin(arg + c * 2.1) + 0.3 * np.sin(2 * arg - phase[:, None]))
        x[:, :, 3 + c] = moving * 0.6 * amp[:, None] * np.cos(arg + c)
        x[:, :, 6 + c] = x[:, :, c] + (grav[y, c]
                                       * rng.uniform(0.95, 1.05, n))[:, None]
    x += rng.normal(0.0, 1.0, x.shape) * noise[:, None, None]
    return x.astype(np.float32)
