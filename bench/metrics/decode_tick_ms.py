"""Decode layer: device time per execution of the decode-tick program
(the engine's masked, guarded decode plan, jitted as ``plan``).  Moves
``tbt_p95_ms``."""
from harness import xtrace

#: jit name of the decode plan closure as the trace shows it
PROGRAMS = ("jit_plan",)


def read(ctx):
    n, sec = xtrace.matching(ctx.programs, PROGRAMS)
    return sec * 1e3 / n if n else None
