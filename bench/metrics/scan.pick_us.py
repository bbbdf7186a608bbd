"""Device time per ADWISE ring-scan step in phase 5 of the step (the pick
of the top vertex-disjoint window edges): the self time of the ops under
the scope ``adwise.pick`` in the ``_run_scan_ring`` program over the scan
steps it ran (``bench.scopes``)."""
from bench.scopes import scan_us_per_step


def read(ctx):
    return scan_us_per_step(ctx, "adwise.pick")
