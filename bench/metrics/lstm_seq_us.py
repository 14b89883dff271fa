"""Kernel layer: device time per call of the fused sequence kernel
(kernels/lstm_seq.py, the ``fused_seq`` plan's one dispatch).  Moves
``window_ms``."""
from harness import xtrace

#: the kernel's op name in the trace: the custom call is named after
#: the function that makes the ``pallas_call`` (``_lstm_seq_call.<n>``)
KERNEL = ("_lstm_seq_call",)


def read(ctx):
    n, sec = xtrace.matching(ctx.ops, KERNEL)
    return sec * 1e6 / n if n else None
