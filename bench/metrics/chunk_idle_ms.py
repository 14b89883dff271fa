"""Prefill layer: device idle per admission-prefill chunk, in ms: the
idle gaps whose midpoint falls in an engine ``serve/chunk`` span or one
of its phases (``dispatch``, ``sync``) on the profile's host plane, over
the ``serve/chunk`` spans that start in the trace.  Moves
``ttft_p95_ms``."""
from harness import hostplane


def read(ctx):
    return hostplane.idle_per_span(ctx, "serve/chunk")
