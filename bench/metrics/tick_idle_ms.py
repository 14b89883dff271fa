"""Decode layer: device idle per decode tick, in ms: the idle gaps whose
midpoint falls in an engine ``serve/tick`` span or one of its phases
(``prepare``, ``dispatch``, ``sync``) on the profile's host plane, over
the ``serve/tick`` spans that start in the trace.  Moves ``tbt_p95_ms``."""
from harness import hostplane


def read(ctx):
    return hostplane.idle_per_span(ctx, "serve/tick")
