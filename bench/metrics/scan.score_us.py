"""Device time per ADWISE ring-scan step in phases 2-4 of the step (lazy
selection, R + CS rescoring, the score matrix and the threshold): the self
time of the ops under the scope ``adwise.score`` in the ``_run_scan_ring``
program over the scan steps it ran (``bench.scopes``)."""
from bench.scopes import scan_us_per_step


def read(ctx):
    return scan_us_per_step(ctx, "adwise.score")
