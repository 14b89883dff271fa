"""Whole serving step: FLOPs of every prompt token prefilled and every
token decoded in the trace (the head only where its logits are used)
over the traced window times the chip's bf16 peak, in %.  It bounds
``prefill_mfu`` and ``decode_mfu`` together, and stays when a change
takes a program off the path.  Moves ``tbt_p95_ms``."""
from harness import serving


def read(ctx):
    tokens, finals = serving.prefill_work(ctx)
    decoded = serving.decoded_tokens(ctx)
    if not tokens and not decoded:
        return None
    f, m = ctx.flops, ctx.model
    work = (tokens * f.rwkv6_token_flops(m, head=False)
            + finals * 2 * m["d_model"] * m["vocab"]
            + decoded * f.rwkv6_token_flops(m, head=True))
    return 100.0 * work / (ctx.trace.window_s * ctx.peak["bf16_flops_per_s"])
