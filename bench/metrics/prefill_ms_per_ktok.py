"""Prefill layer: device time of the admission-prefill programs per
1,000 prompt tokens prefilled in the trace.  Moves ``ttft_p95_ms``."""
from harness import serving, xtrace

#: jit name of the chunked admission prefill as the trace shows it
PROGRAMS = ("prefill_chunk_sample",)


def read(ctx):
    n, sec = xtrace.matching(ctx.programs, PROGRAMS)
    tokens, _ = serving.prefill_work(ctx)
    if not n or not tokens:
        return None
    return sec * 1e3 / (tokens / 1000.0)
