"""Device time per ADWISE ring-scan step in phase 1 of the step (the
window refill, streamed degrees and revocation): the self time of the ops
under the scope ``adwise.window`` in the ``_run_scan_ring`` program over
the scan steps it ran (``bench.scopes``)."""
from bench.scopes import scan_us_per_step


def read(ctx):
    return scan_us_per_step(ctx, "adwise.window")
