"""Device time per ADWISE ring-scan step in phases 6-7 of the step (the
vertex-cache and replica updates, lambda and the window controller): the
self time of the ops under the scope ``adwise.apply`` in the
``_run_scan_ring`` program over the scan steps it ran (``bench.scopes``)."""
from bench.scopes import scan_us_per_step


def read(ctx):
    return scan_us_per_step(ctx, "adwise.apply")
