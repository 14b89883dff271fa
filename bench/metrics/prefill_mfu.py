"""Prefill layer: FLOPs of the prompt tokens prefilled in the trace (the
head only at each prompt's last position) over the admission-prefill
programs' device time times the chip's bf16 peak, in %.  Moves
``ttft_p95_ms``."""
from harness import serving, xtrace

PROGRAMS = ("prefill_chunk_sample",)


def read(ctx):
    n, sec = xtrace.matching(ctx.programs, PROGRAMS)
    tokens, finals = serving.prefill_work(ctx)
    if not n or not tokens or sec <= 0:
        return None
    f = ctx.flops
    work = (tokens * f.rwkv6_token_flops(ctx.model, head=False)
            + finals * 2 * ctx.model["d_model"] * ctx.model["vocab"])
    return 100.0 * work / (sec * ctx.peak["bf16_flops_per_s"])
