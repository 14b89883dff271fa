"""Operations and bytes the algorithms need, from the configuration's
sizes at their logical widths (no lane padding, no recomputation).

A multiply-add counts as 2 FLOPs.  Only matrix products and the
recurrences count; norms, gates and other element-wise work do not.
"""
from __future__ import annotations

N_MIX = 5          # rwkv6 token-shift interpolations: w, k, v, r, g


def rwkv6_param_count(m: dict) -> int:
    """Every parameter of the RWKV6 model in the configuration's file."""
    d, ff, v, L = m["d_model"], m["d_ff"], m["vocab"], m["n_layers"]
    r, mr = m["lora_rank"], m["mix_lora_rank"]
    tmix = (d + N_MIX * d                         # maa_x, maa
            + d * N_MIX * mr + N_MIX * mr * d     # ddlerp LoRA
            + d + d * r + r * d                   # w0, decay LoRA
            + 5 * d * d                           # r, k, v, g, o
            + d                                   # u (H x head_dim)
            + 2 * d)                              # group norm
    cmix = 2 * d + d * ff + ff * d + d * d
    norms = 4 * d                                 # ln1, ln2 (scale, bias)
    return L * (tmix + cmix + norms) + 2 * v * d + 2 * d


def rwkv6_matmul_params(m: dict) -> int:
    """Weights one token multiplies through in the blocks (no embedding,
    which is only indexed, and no head)."""
    d, ff, L = m["d_model"], m["d_ff"], m["n_layers"]
    r, mr = m["lora_rank"], m["mix_lora_rank"]
    per_layer = (d * N_MIX * mr + N_MIX * mr * d + d * r + r * d
                 + 5 * d * d + d * ff + ff * d + d * d)
    return L * per_layer


def rwkv6_wkv_flops(m: dict) -> int:
    """The wkv recurrence per token, all layers: the state update
    (decay and outer product) and the read-out, each 2 per element of
    the (head_dim x head_dim) state of every head."""
    H = m["d_model"] // m["head_dim"]
    return m["n_layers"] * H * 4 * m["head_dim"] * m["head_dim"]


def rwkv6_token_flops(m: dict, head: bool) -> int:
    """FLOPs of one token through the model; ``head`` adds the logits,
    counted only where they are used (a decoded token, or the last
    position of a prompt)."""
    f = 2 * rwkv6_matmul_params(m) + rwkv6_wkv_flops(m)
    if head:
        f += 2 * m["d_model"] * m["vocab"]
    return f


def lstm_window_flops(m: dict) -> int:
    """One window through the stacked LSTM and its classifier head."""
    H, D, L, T = m["hidden"], m["input_dim"], m["n_layers"], m["seq_len"]
    per_step = sum(2 * ((D if i == 0 else H) + H) * 4 * H for i in range(L))
    return T * per_step + 2 * H * m["n_classes"]


def lstm_seq_flops(m: dict, batch: int) -> int:
    """The fused sequence kernel's share: the recurrence, without the head."""
    return batch * (lstm_window_flops(m) - 2 * m["hidden"] * m["n_classes"])


def lstm_seq_bytes(m: dict, batch: int, dtype_bytes: int = 4) -> int:
    """HBM bytes the fused sequence kernel must move at logical widths:
    every layer's gate weights and biases once, the input windows, and
    the final (c, h) of every layer."""
    H, D, L, T = m["hidden"], m["input_dim"], m["n_layers"], m["seq_len"]
    weights = sum(((D if i == 0 else H) + H) * 4 * H + 4 * H
                  for i in range(L))
    return dtype_bytes * (weights + batch * T * D + 2 * L * batch * H)
