"""Decode layer: FLOPs of the tokens decoded for active lanes in the
trace over the decode programs' device time times the chip's bf16 peak,
in %.  Moves ``tbt_p95_ms``."""
from harness import serving, xtrace

PROGRAMS = ("jit_plan",)


def read(ctx):
    n, sec = xtrace.matching(ctx.programs, PROGRAMS)
    tokens = serving.decoded_tokens(ctx)
    if not n or not tokens or sec <= 0:
        return None
    work = tokens * ctx.flops.rwkv6_token_flops(ctx.model, head=True)
    return 100.0 * work / (sec * ctx.peak["bf16_flops_per_s"])
