"""Engine layer: 95th percentile (nearest rank), over the requests due in
the measured window, of each request's wait from the engine taking it
to its prefill starting (``queue_s`` of the engine's ``serve/request``).
Moves ``ttft_p95_ms``."""
from harness import hostplane


def read(ctx):
    return hostplane.request_p95(ctx, "queue_s")
