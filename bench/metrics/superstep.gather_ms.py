"""Device time per engine superstep in the gather (messages and the
scatter-add into the per-partition accumulators): the self time of the ops
under the scope ``engine.gather`` in the ``jit_step`` program over the
supersteps the window ran (``bench.scopes``)."""
from bench.scopes import superstep_ms


def read(ctx):
    return superstep_ms(ctx, "engine.gather")
