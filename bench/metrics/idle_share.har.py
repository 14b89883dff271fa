"""Device: share of the traced window in which no operation ran on the
chip, in %.  Moves ``window_ms``."""
from harness import xtrace


def read(ctx):
    return 100.0 * (1.0 - xtrace.busy_s(ctx.trace) / ctx.trace.window_s)
