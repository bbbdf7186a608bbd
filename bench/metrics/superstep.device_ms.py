"""Device time per engine superstep: the superstep program's device time
in the traced window over the supersteps the window ran."""
from bench.trace import module_time

PROGRAM = r"jit_step"


def read(ctx):
    seconds, runs = module_time(ctx["trace"], PROGRAM)
    steps = sum(r["work"] for r in ctx["results"])
    if not runs or not steps:
        return None
    return seconds / steps * 1e3
