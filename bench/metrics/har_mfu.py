"""Whole HAR step: FLOPs of the windows classified in the trace over the
traced window times the chip's bf16 peak, in %.  Moves ``window_ms``."""


def read(ctx):
    if not ctx.windows:
        return None
    work = ctx.windows * ctx.batch * ctx.flops.lstm_window_flops(ctx.model)
    return 100.0 * work / (ctx.trace.window_s * ctx.peak["bf16_flops_per_s"])
