"""Kernel layer: the least time the fused sequence kernel could take for
one call, max(FLOPs / bf16 peak, HBM bytes / HBM bandwidth) at logical
widths, over its measured device time per call, in %.  At the paper's
2 x 32 LSTM the bytes bound applies.  Moves ``window_ms``."""
from harness import xtrace

KERNEL = ("_lstm_seq_call",)


def read(ctx):
    n, sec = xtrace.matching(ctx.ops, KERNEL)
    if not n or sec <= 0:
        return None
    f, m, b = ctx.flops, ctx.model, ctx.batch
    least = max(f.lstm_seq_flops(m, b) / ctx.peak["bf16_flops_per_s"],
                f.lstm_seq_bytes(m, b) / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / (sec / n)
