"""Prefill layer: 95th percentile (nearest rank), over the requests due
in the measured window, of each request's time in a prefill lane, from
its prefill starting to its first token on the host (``lane_s`` of the
engine's ``serve/request``): its chunks, round-robin with the other
lanes and interleaved with decode ticks.  Moves ``ttft_p95_ms``."""
from harness import hostplane


def read(ctx):
    return hostplane.request_p95(ctx, "lane_s")
