"""Device time per engine superstep in the combine (the replica mask, the
sum over the partition slabs and the psum over chips): the self time of
the ops under the scope ``engine.combine`` in the ``jit_step`` program
over the supersteps the window ran (``bench.scopes``)."""
from bench.scopes import superstep_ms


def read(ctx):
    return superstep_ms(ctx, "engine.combine")
